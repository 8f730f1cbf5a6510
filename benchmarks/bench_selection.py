"""Paper experiment analogues (Figures 2, 3, 4) + distributed A/B benches.

Three table families, matching the paper's experimental setup (§5):
  * accuracy-vs-rounds   (Figs 2a/2d, 3a/3d, 4a/4d)
  * accuracy-vs-k        (Figs 2b/2e, 3b/3e, 4b/4e)
  * time-vs-k            (Figs 2c/2f, 3c/3f, 4c/4f)

Algorithms: DASH, SDS_MA (parallel-oracle greedy), TOP-K, RANDOM, LASSO.
Datasets: D1 (synthetic regression), D2 (clinical surrogate), D3
(synthetic classification), D4 (gene surrogate), D1-design (A-opt).
Sizes default to a CPU-friendly scale; pass ``--full`` for the paper's
n (the algorithms are identical — only wall time changes).

``--suite distributed`` runs the generic ``dash_distributed`` runner
against single-device ``dash`` for all three objectives on whatever mesh
the host devices allow (force a pod-in-miniature with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), recording
values and wall times per runtime.  ``--suite baselines`` sweeps the
whole ``core.algorithms.select`` registry — every §5 competitor,
single-device AND sharded — into the same artifact (see
``run_baselines``).  ``--json`` writes every emitted row
as ``BENCH_selection.json`` — the CI artifact that accumulates the
selection-benchmark trajectory alongside ``BENCH_kernels.json``.

Sequential-SDS_MA timing is *derived* (n−i single-gain oracle calls per
round) rather than simulated call-by-call, matching the paper's
parallel-vs-sequential accounting.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, wall_time
from repro.core import (
    AOptimalityObjective,
    ClassificationObjective,
    DashConfig,
    RegressionObjective,
    dash,
    dash_auto,
    greedy,
    lasso_path_select,
    normalize_columns,
    random_select,
    top_k_select,
)
from repro.data.synthetic import (
    make_d1_design,
    make_d1_regression,
    make_d2_clinical,
    make_d3_classification,
    make_d4_gene,
)
from repro.utils.compile_cache import enable_compile_cache

KEY = jax.random.PRNGKey(0)


def _dash_call(obj, k, alpha):
    """Practical DASH: OPT-guess lattice (paper App. G), best value wins."""
    return dash_auto(obj, k, KEY, eps=0.25, alpha=alpha, n_samples=8,
                     n_guesses=6)


def _bench_objective(name, obj, k_grid, *, lasso_xy=None, task="linear",
                     alpha=0.6):
    rows = []
    for k in k_grid:
        # warmup=1: exclude jit compilation from the reported wall time
        g_t, g = wall_time(lambda: jax.block_until_ready(greedy(obj, k)),
                           warmup=1, iters=1)
        d_t, d = wall_time(
            lambda: jax.block_until_ready(_dash_call(obj, k, alpha)),
            warmup=1, iters=1)
        t = top_k_select(obj, k)
        r = random_select(obj, k, KEY)
        row = {
            "dataset": name, "k": k,
            "dash_value": float(d.value), "dash_time_s": d_t,
            "dash_rounds": int(d.rounds),
            "greedy_value": float(g.value), "greedy_time_s": g_t,
            "greedy_rounds": k,
            "topk_value": float(t.value),
            "random_value": float(r.value),
        }
        if lasso_xy is not None:
            X, y = lasso_xy
            t0 = time.perf_counter()
            best, _ = lasso_path_select(X, y, k, task=task, iters=150)
            row["lasso_nnz"] = int(best.nnz)
            row["lasso_time_s"] = time.perf_counter() - t0
            sup = jnp.nonzero(best.support, size=k, fill_value=0)[0]
            st = obj.add_set(obj.init(), sup.astype(jnp.int32),
                             jnp.ones(k, bool))
            row["lasso_value"] = float(obj.value(st))
        rows.append(row)
        emit(f"selection/{name}/k={k}/dash", d_t * 1e6,
             f"value={row['dash_value']:.4f};rounds={row['dash_rounds']}")
        emit(f"selection/{name}/k={k}/greedy", g_t * 1e6,
             f"value={row['greedy_value']:.4f};rounds={k}")
        emit(f"selection/{name}/k={k}/topk_random", 0.0,
             f"topk={row['topk_value']:.4f};random={row['random_value']:.4f}")
        # parallel-runtime proxy: adaptive rounds (depth).  Wall-clock on
        # this 1-core CPU host cannot express parallel speedup — DASH's
        # win is depth, which the paper converts to wall time on ≥8 cores.
        emit(f"selection/{name}/k={k}/depth_speedup", 0.0,
             f"greedy_rounds_over_dash={k / max(int(d.rounds), 1):.2f}x")
    return rows


def filter_engine_ab(name, X, y, k, kmax):
    """DASH wall-clock with the sample-batched filter engine on vs off.

    Same key, same config — the only difference is whether the filter
    step evaluates its Monte-Carlo samples through the fused
    ``filter_gains`` engine or the per-sample add_set + gains path.
    """
    cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=8)
    out = {}
    for tag, flag in (("per_sample", False), ("engine", True)):
        obj = RegressionObjective(jnp.asarray(X), jnp.asarray(y), kmax=kmax,
                                  use_filter_engine=flag)
        t, res = wall_time(
            lambda: jax.block_until_ready(dash(obj, cfg, KEY, opt=0.9)),
            warmup=1, iters=1)
        out[tag] = (t, float(res.value))
        emit(f"selection/{name}/k={k}/dash_filter_{tag}", t * 1e6,
             f"value={float(res.value):.4f}")
    t_ps, t_en = out["per_sample"][0], out["engine"][0]
    emit(f"selection/{name}/k={k}/dash_filter_speedup", 0.0,
         f"engine_over_per_sample={t_ps / max(t_en, 1e-12):.2f}x")
    return out


def accuracy_vs_rounds(name, obj, k):
    """Fig 2a-style trace: objective value per adaptive round."""
    g = greedy(obj, k)
    cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=6)
    res = dash(obj, cfg, KEY, opt=float(g.value) * 1.05)
    emit(f"rounds/{name}/greedy_final", 0.0,
         f"value={float(g.value):.4f};rounds={k}")
    emit(f"rounds/{name}/dash_final", 0.0,
         f"value={float(res.value):.4f};rounds={int(res.rounds)}")
    return np.asarray(res.trace.values), np.asarray(g.values)


def distributed_vs_single(name, make_obj, X, k, *, alpha=0.6, eps=0.25,
                          n_samples=4):
    """Generic-runner A/B: dash_distributed(obj) vs single-device dash.

    ``make_obj(Xp)`` builds the objective on the (d, n) candidate matrix
    AFTER it is zero-padded to the mesh's model-axis size, so the suite
    runs on any host device count.  Same objective instance, same
    config; the distributed run shards the candidate axis over ``model``
    and the Monte-Carlo replicas over ``data``.  On a 1-core CPU host
    the wall-clock ratio mostly measures collective overhead — the depth
    (adaptive rounds) is identical by construction since both bind the
    SAME shared selection loop.
    """
    from repro.core.distributed import dash_distributed, pad_ground_set
    from repro.launch.mesh import make_host_mesh

    # data-major factorization: (4, 2) on the 8-device CI host, so the
    # data-axis pmean/psum cost is part of the recorded timings.
    mesh = make_host_mesh()
    Xp, _ = pad_ground_set(jnp.asarray(X, jnp.float32),
                           mesh.shape["model"])
    obj = make_obj(Xp)
    cfg = DashConfig(k=k, eps=eps, alpha=alpha, n_samples=n_samples)
    g = greedy(obj, k)
    opt = float(g.value) * 1.05

    t_s, r_s = wall_time(
        lambda: jax.block_until_ready(dash(obj, cfg, KEY, opt)),
        warmup=1, iters=1)
    t_d, r_d = wall_time(
        lambda: jax.block_until_ready(dash_distributed(obj, cfg, KEY, opt,
                                                       mesh)),
        warmup=1, iters=1)
    shape = "x".join(str(s) for s in mesh.devices.shape)
    emit(f"distributed/{name}/k={k}/single", t_s * 1e6,
         f"value={float(r_s.value):.4f};rounds={int(r_s.rounds)}")
    emit(f"distributed/{name}/k={k}/sharded", t_d * 1e6,
         f"value={float(r_d.value):.4f};rounds={int(r_d.rounds)};"
         f"mesh={shape}")
    emit(f"distributed/{name}/k={k}/parity", 0.0,
         f"sharded_over_single_value={float(r_d.value) / max(float(r_s.value), 1e-9):.3f};"
         f"greedy={float(g.value):.4f}")
    return r_s, r_d


def lattice_ab(name, obj, k, *, eps, alpha, n_samples, n_guesses=8):
    """Loop-mode vs batched single-jit (OPT, α) lattice wall-clock.

    Same key, same guesses, same selection loop — the modes are
    bitwise-identical per guess (tests assert it); only the execution
    strategy differs: ``loop`` dispatches one jitted run per guess,
    ``batched`` advances every guess in lockstep under one compilation
    with a device-side argmax.  On CPU the batched win is dispatch
    amortization, so the default sizes are small (the DASH regime where
    per-op overhead dominates); at large per-guess problem sizes the
    lockstep vmap pays for the heaviest guess's filter iterations in
    every lane and loop mode can win on CPU — on TPU the batched mode
    additionally folds all guesses into ONE filter-engine launch
    streaming X once.  Compilation is excluded (warmup=1; dash_auto
    caches its jitted runners).
    """
    out = {}
    for mode in ("batched", "loop"):
        t, res = wall_time(
            lambda m=mode: jax.block_until_ready(
                dash_auto(obj, k, KEY, eps=eps, alpha=alpha,
                          n_samples=n_samples, n_guesses=n_guesses,
                          guess_mode=m).value
            ),
            warmup=1, iters=5)
        out[mode] = (t, float(res))
        emit(f"lattice/{name}/G={n_guesses}/{mode}", t * 1e6,
             f"value={float(res):.4f}")
    speed = out["loop"][0] / max(out["batched"][0], 1e-12)
    assert abs(out["loop"][1] - out["batched"][1]) < 1e-6
    emit(f"lattice/{name}/G={n_guesses}/speedup", 0.0,
         f"batched_over_loop={speed:.2f}x")
    return speed


def lattice_pod_ab(name, make_obj, X, k, *, eps, alpha, n_samples,
                   n_guesses=8):
    """Pod-sharded lattice A/B + strict parity vs the per-guess sweep.

    Parity leg (n_guesses = pod size, one guess per pod slice): the
    single shard_map launch must return the IDENTICAL best solution as
    running ``dash_distributed`` once per guess on an equally-shaped
    (data, model) submesh — bitwise, not approximately.  Timing leg
    (``n_guesses`` joint guesses): one pod-lattice launch vs the
    sequential per-guess sweep.  Skips (with a recorded row) when the
    host exposes fewer than 8 devices.
    """
    from repro.core.dash import lattice_grid, opt_guess_lattice
    from repro.core.distributed import (
        dash_auto_distributed,
        dash_distributed,
        pad_ground_set,
    )
    from repro.launch.mesh import make_lattice_mesh, make_mesh

    if len(jax.devices()) < 8:
        emit(f"lattice_pod/{name}/skipped", 0.0,
             f"needs 8 devices, have {len(jax.devices())}")
        return None
    mesh3 = make_lattice_mesh(2)                      # (2, 2, 2) pod mesh
    pod = mesh3.shape["pod"]
    Xp, _ = pad_ground_set(jnp.asarray(X, jnp.float32),
                           mesh3.shape["model"])
    obj = make_obj(Xp)
    # The per-guess reference must run on a submesh shaped exactly like
    # ONE pod slice — derive it from mesh3 (hosts with >8 devices get a
    # bigger data axis) so the bitwise-parity claim stays valid.
    nd, nm = mesh3.shape["data"], mesh3.shape["model"]
    sub = make_mesh((nd, nm), ("data", "model"),
                    devices=jax.devices()[: nd * nm])
    cfg = DashConfig(k=k, eps=eps, alpha=alpha, n_samples=n_samples)

    # --- strict parity: one guess per pod slice, bitwise comparison ----
    res = dash_auto_distributed(obj, k, KEY, mesh3, eps=eps, alpha=alpha,
                                n_samples=n_samples, n_guesses=pod)
    opts, _ = lattice_grid(opt_guess_lattice(obj, eps, pod, k), [alpha])
    keys = jax.random.split(KEY, pod)
    sweep = [dash_distributed(obj, cfg, keys[i], opts[i], sub)
             for i in range(pod)]
    vals = [float(r.value) for r in sweep]
    best = int(np.argmax(vals))
    identical = (
        float(res.value) == vals[best]
        and bool(np.array_equal(np.asarray(res.sel_mask),
                                np.asarray(sweep[best].sel_mask)))
        and [float(v) for v in np.asarray(res.lattice_values)] == vals
    )
    emit(f"lattice_pod/{name}/parity", 0.0,
         f"identical_best={identical};best_value={float(res.value):.4f};"
         f"n_guesses={pod}")

    # --- timing: the full lattice in one launch vs the sequential sweep
    t_pod, _ = wall_time(
        lambda: jax.block_until_ready(
            dash_auto_distributed(obj, k, KEY, mesh3, eps=eps, alpha=alpha,
                                  n_samples=n_samples,
                                  n_guesses=n_guesses).value),
        warmup=1, iters=1)
    opts_n, _ = lattice_grid(opt_guess_lattice(obj, eps, n_guesses, k),
                             [alpha])
    keys_n = jax.random.split(KEY, n_guesses)

    def sweep_all():
        vs = [dash_distributed(obj, cfg, keys_n[i], opts_n[i], sub).value
              for i in range(n_guesses)]
        return jax.block_until_ready(jnp.stack(vs))

    t_sweep, _ = wall_time(sweep_all, warmup=1, iters=1)
    emit(f"lattice_pod/{name}/G={n_guesses}/pod_lattice", t_pod * 1e6,
         f"mesh=2x2x2")
    emit(f"lattice_pod/{name}/G={n_guesses}/per_guess_sweep",
         t_sweep * 1e6, "mesh=2x2(submesh)")
    emit(f"lattice_pod/{name}/G={n_guesses}/speedup", 0.0,
         f"pod_over_sweep={t_sweep / max(t_pod, 1e-12):.2f}x")
    return identical


def run_lattice(full: bool = False):
    """--suite lattice: loop vs batched vs pod-sharded (OPT, α) lattice
    A/B for all three objectives.

    Default sizes sit in the dispatch-bound regime where the batched
    single-jit lattice wins ≥2× on CPU (the acceptance target);
    ``--full`` doubles them, honestly recording the CPU crossover where
    the lockstep vmap starts paying for the heaviest guess in every lane
    (TPU numbers are the roadmap item — there the folded engine launch
    changes the large-size story).
    """
    scale = 2 if full else 1
    rng = np.random.default_rng(0)

    d, n, k = 32 * scale, 24 * scale, 4 * scale
    X0 = rng.normal(size=(d, n)) + 0.4 * rng.normal(size=(d, 1))
    X = normalize_columns(jnp.asarray(X0, jnp.float32))
    w = np.zeros(n)
    w[:k] = rng.uniform(-2, 2, k)
    y = jnp.asarray(X0 @ w + 0.1 * rng.normal(size=d), jnp.float32)
    obj = RegressionObjective(X, y, kmax=k)
    lattice_ab("regression", obj, k, eps=0.25, alpha=0.6, n_samples=4)
    lattice_pod_ab("regression",
                   lambda Xp: RegressionObjective(Xp, y, kmax=k), X, k,
                   eps=0.25, alpha=0.6, n_samples=4)

    da, na, ka = 24 * scale, 48 * scale, 6 * scale
    Xa = rng.normal(size=(da, na))
    Xa = jnp.asarray(Xa / np.linalg.norm(Xa, axis=0, keepdims=True),
                     jnp.float32)
    obja = AOptimalityObjective(Xa, kmax=ka)
    lattice_ab("aopt", obja, ka, eps=0.25, alpha=0.5, n_samples=4)
    lattice_pod_ab("aopt", lambda Xp: AOptimalityObjective(Xp, kmax=ka),
                   Xa, ka, eps=0.25, alpha=0.5, n_samples=4)

    dc, nc, kc = 32 * scale, 20 * scale, 3 * scale
    Xc0 = rng.normal(size=(dc, nc))
    Xc = normalize_columns(jnp.asarray(Xc0, jnp.float32)) * np.sqrt(dc)
    wc = np.zeros(nc)
    wc[:kc] = rng.uniform(-2, 2, kc)
    yc = jnp.asarray((1 / (1 + np.exp(-Xc0 @ wc)) > 0.5).astype(np.float32))
    objc = ClassificationObjective(Xc, yc, kmax=kc, newton_steps=2,
                                   newton_gain_steps=1)
    lattice_ab("logistic", objc, kc, eps=0.3, alpha=0.4, n_samples=3)
    lattice_pod_ab(
        "logistic",
        lambda Xp: ClassificationObjective(Xp, yc, kmax=kc, newton_steps=2,
                                           newton_gain_steps=1),
        Xc, kc, eps=0.3, alpha=0.4, n_samples=3)


def run_distributed(full: bool = False):
    """Distributed-vs-single benches for ALL THREE paper objectives."""
    scale = 1 if full else 2
    rng = np.random.default_rng(0)

    d, n, k = 192 // scale, 128 // scale, 16 // scale
    X0 = rng.normal(size=(d, n)) + 0.4 * rng.normal(size=(d, 1))
    X = normalize_columns(jnp.asarray(X0, jnp.float32))
    w = np.zeros(n)
    w[:k] = rng.uniform(-2, 2, k)
    y = jnp.asarray(X0 @ w + 0.1 * rng.normal(size=d), jnp.float32)
    distributed_vs_single(
        "regression", lambda Xp: RegressionObjective(Xp, y, kmax=k), X, k)

    da, na, ka = 48 // scale, 128 // scale, 16 // scale
    Xa = rng.normal(size=(da, na))
    Xa = jnp.asarray(Xa / np.linalg.norm(Xa, axis=0, keepdims=True),
                     jnp.float32)
    distributed_vs_single(
        "aopt", lambda Xp: AOptimalityObjective(Xp, kmax=ka), Xa, ka,
        alpha=0.5)

    dc, nc, kc = 160 // scale, 64 // scale, 8 // scale
    Xc0 = rng.normal(size=(dc, nc))
    Xc = normalize_columns(jnp.asarray(Xc0, jnp.float32)) * np.sqrt(dc)
    wc = np.zeros(nc)
    wc[:kc] = rng.uniform(-2, 2, kc)
    yc = jnp.asarray((1 / (1 + np.exp(-Xc0 @ wc)) > 0.5).astype(np.float32))
    distributed_vs_single(
        "logistic",
        lambda Xp: ClassificationObjective(Xp, yc, kmax=kc, newton_steps=4,
                                           newton_gain_steps=2),
        Xc, kc, alpha=0.4, eps=0.3, n_samples=3)


def run_resilience(full: bool = False):
    """Resilient-runtime costs: the price of round snapshots and the
    restore → reshard → continue path (docs/resilience.md).

    Rows (prefix ``resilience/``):
      * ``fused`` / ``stepped``   — one-launch vs host-stepped run,
      * ``ckpt_blocking`` / ``ckpt_async`` — per-round snapshots; the
        derived field records ``overhead_per_round`` (seconds) and
        ``overhead_frac`` (fraction of a stepped round) — the number the
        compare-vs-main summary watches,
      * ``resume`` — kill at mid-run, restore + replay to completion.
    """
    import shutil
    import tempfile

    from repro.core import ResilienceConfig
    from repro.core.distributed import dash_distributed, pad_ground_set
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.fault_tolerance import FailureInjector

    scale = 1 if full else 2
    rng = np.random.default_rng(0)
    d, n, k = 192 // scale, 128 // scale, 16 // scale
    X0 = rng.normal(size=(d, n)) + 0.4 * rng.normal(size=(d, 1))
    X = normalize_columns(jnp.asarray(X0, jnp.float32))
    w = np.zeros(n)
    w[:k] = rng.uniform(-2, 2, k)
    y = jnp.asarray(X0 @ w + 0.1 * rng.normal(size=d), jnp.float32)

    mesh = make_host_mesh()
    Xp, _ = pad_ground_set(X, mesh.shape["model"])
    obj = RegressionObjective(Xp, y, kmax=k)
    cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=4)
    opt = float(greedy(obj, k).value) * 1.05
    r = cfg.resolve(obj.n).r

    t_fused, rf = wall_time(
        lambda: jax.block_until_ready(
            dash_distributed(obj, cfg, KEY, opt, mesh)),
        warmup=1, iters=1)
    t_step, rs = wall_time(
        lambda: jax.block_until_ready(
            dash_distributed(obj, cfg, KEY, opt, mesh,
                             resilience=ResilienceConfig())),
        warmup=1, iters=1)
    emit(f"resilience/regression/k={k}/fused", t_fused * 1e6,
         f"value={float(rf.value):.4f};rounds={r}")
    emit(f"resilience/regression/k={k}/stepped", t_step * 1e6,
         f"value={float(rs.value):.4f};"
         f"stepped_over_fused={t_step / max(t_fused, 1e-9):.2f}")

    def timed_ckpt(async_save):
        tmp = tempfile.mkdtemp(prefix="bench_resilience_")
        try:
            t, _ = wall_time(
                lambda: jax.block_until_ready(dash_distributed(
                    obj, cfg, KEY, opt, mesh,
                    resilience=ResilienceConfig(
                        ckpt_dir=tmp, every=1, keep_last=2,
                        async_save=async_save))),
                warmup=1, iters=1)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        return t

    for label, async_save in (("ckpt_blocking", False),
                              ("ckpt_async", True)):
        t_ck = timed_ckpt(async_save)
        over = max(t_ck - t_step, 0.0) / r
        frac = over / max(t_step / r, 1e-9)
        emit(f"resilience/regression/k={k}/{label}", t_ck * 1e6,
             f"overhead_per_round={over * 1e6:.1f}us;"
             f"overhead_frac={frac:.3f}")

    # kill at round r//2, then time restore + replay-to-completion
    tmp = tempfile.mkdtemp(prefix="bench_resilience_")
    try:
        res = ResilienceConfig(ckpt_dir=tmp, every=1, async_save=False)
        try:
            dash_distributed(obj, cfg, KEY, opt, mesh, resilience=res,
                             failure_injector=FailureInjector(
                                 fail_at=(max(r // 2, 1),)))
        except RuntimeError:
            pass
        t_rs, rr = wall_time(
            lambda: jax.block_until_ready(dash_distributed(
                obj, cfg, KEY, opt, mesh, resilience=res, resume=True)),
            warmup=0, iters=1)
        emit(f"resilience/regression/k={k}/resume", t_rs * 1e6,
             f"value={float(rr.value):.4f};from_round={max(r // 2, 1)};"
             f"bitwise={bool(np.all(np.asarray(rr.sel_mask) == np.asarray(rs.sel_mask)))}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_serve(full: bool = False):
    """Selection-service behavior under offered load (docs/serving.md).

    Three offered-load levels against one tenant dataset — under the
    bucket size, saturating the admission queues, and past the global
    pending cap — each measured with chaos off and on (a per-launch
    injected failure at round 1, exercising the hedged-resume path).

    Rows (prefix ``serve/``): ``us_per_call`` is the whole drain's wall
    time; the derived field carries the latency/goodput envelope the
    compare-vs-main summary watches — ``p50``/``p99`` reply latency,
    ``goodput`` (OK replies per second), and the explicit-shedding
    counters (every offered request gets a terminal reply; under
    overload the surplus shows up in ``rejected``, never in latency).
    """
    from repro.runtime.fault_tolerance import FailureInjector
    from repro.runtime.hedging import HedgePolicy
    from repro.serve import AdmissionPolicy, SelectionServer, SelectRequest

    scale = 2 if full else 1
    rng = np.random.default_rng(0)
    d, n, k = 96 * scale, 64 * scale, 8
    X0 = rng.normal(size=(d, n)) + 0.4 * rng.normal(size=(d, 1))
    X = normalize_columns(jnp.asarray(X0, jnp.float32))
    w = np.zeros(n)
    w[:k] = rng.uniform(-2, 2, k)
    y = jnp.asarray(X0 @ w + 0.1 * rng.normal(size=d), jnp.float32)

    admission = AdmissionPolicy(max_batch=4, max_queue=8, max_pending=16)
    loads = (2, 8, 24)          # under-bucket / saturating / shedding
    for chaos_on in (False, True):
        srv = SelectionServer(
            admission=admission,
            chaos=FailureInjector(fail_at=(1,)) if chaos_on else None,
            hedge=HedgePolicy(max_attempts=3, backoff_s=0.0,
                              sleep_fn=lambda s: None))
        srv.register("bench", "regression", X, y, kmax=k)
        for w in (1, 2, 4):    # pre-compile every padded lane shape
            srv.serve([SelectRequest("bench", k, 0) for _ in range(w)])
        for load in loads:
            before = dict(srv.stats)
            t0 = time.perf_counter()
            replies = srv.serve(
                [SelectRequest("bench", k, s) for s in range(load)])
            wall = time.perf_counter() - t0
            lats = sorted(r.latency_s for r in replies if r.ok)
            n_ok = len(lats)
            n_rej = sum(r.status == "rejected" for r in replies)
            p50 = lats[n_ok // 2] if lats else float("nan")
            p99 = lats[min(int(0.99 * n_ok), n_ok - 1)] if lats \
                else float("nan")
            retries = srv.stats["hedge_retries"] - before["hedge_retries"]
            assert n_ok + n_rej + sum(
                r.status == "failed" for r in replies) == load
            emit(f"serve/load={load}/chaos={'on' if chaos_on else 'off'}",
                 wall * 1e6,
                 f"p50={p50 * 1e3:.1f}ms;p99={p99 * 1e3:.1f}ms;"
                 f"goodput={n_ok / max(wall, 1e-9):.1f}rps;"
                 f"ok={n_ok};rejected={n_rej};hedge_retries={retries}")


def _baseline_datasets(scale: int):
    """The three paper objectives at baseline-suite sizes, as
    ``(name, make_obj(X) factory, X, k_grid, select-opts)`` tuples —
    factories take the (possibly padded) candidate matrix so the same
    problems drive both the single-device and the sharded legs."""
    rng = np.random.default_rng(0)

    d, n, k = 96 * scale, 64 * scale, 8 * scale
    X0 = rng.normal(size=(d, n)) + 0.4 * rng.normal(size=(d, 1))
    X = normalize_columns(jnp.asarray(X0, jnp.float32))
    w = np.zeros(n)
    w[: k] = rng.uniform(-2, 2, k)
    y = jnp.asarray(X0 @ w + 0.1 * rng.normal(size=d), jnp.float32)
    reg = ("regression", lambda Xp: RegressionObjective(Xp, y, kmax=k), X,
           [k // 2, k], {"alpha": 0.6, "eps": 0.25})

    da, na, ka = 24 * scale, 48 * scale, 6 * scale
    Xa0 = rng.normal(size=(da, na))
    Xa = jnp.asarray(Xa0 / np.linalg.norm(Xa0, axis=0, keepdims=True),
                     jnp.float32)
    aopt = ("aopt", lambda Xp: AOptimalityObjective(Xp, kmax=ka), Xa,
            [ka // 2, ka], {"alpha": 0.5, "eps": 0.25})

    dc, nc, kc = 96 * scale, 32 * scale, 4 * scale
    Xc0 = rng.normal(size=(dc, nc))
    Xc = normalize_columns(jnp.asarray(Xc0, jnp.float32)) * np.sqrt(dc)
    wc = np.zeros(nc)
    wc[: kc] = rng.uniform(-2, 2, kc)
    yc = jnp.asarray((1 / (1 + np.exp(-Xc0 @ wc)) > 0.5).astype(np.float32))
    logi = ("logistic",
            lambda Xp: ClassificationObjective(Xp, yc, kmax=kc,
                                               newton_steps=3,
                                               newton_gain_steps=2),
            Xc, [kc], {"alpha": 0.4, "eps": 0.3})
    return [reg, aopt, logi]


#: Baseline-suite roster: every registry algorithm with per-algorithm
#: select() opts (dash runs a small guess lattice; lazy_greedy is the
#: host-driven variant, single-device only by design; fast runs its
#: in-graph binary search over the default guess lattice).
_BASELINE_ALGOS = (
    ("dash", {"n_samples": 4, "n_guesses": 4}),
    ("greedy", {}),
    ("lazy_greedy", {}),
    ("fast", {}),
    ("stochastic_greedy", {}),
    ("topk", {}),
    ("random", {}),
)


def run_baselines(full: bool = False):
    """--suite baselines: the §5 comparison shape for the WHOLE registry.

    Three table families into ``BENCH_selection.json``:
      * value-vs-k        — every algorithm × every objective (the Fig
                            2b/3b/4b analogue, now including stochastic
                            and lazy greedy),
      * single-vs-sharded — every algorithm with a distributed twin run
                            through ``select(..., mesh=mesh)`` on the
                            host mesh, with a value-parity field (the
                            acceptance gate: sharded must agree with its
                            single-device twin),
      * time-vs-n         — greedy / stochastic-greedy / topk / fast
                            wall-clock as the ground set grows (all
                            jitted with data as arguments), plus the
                            host-driven lazy_greedy reference and the
                            fast-over-lazy speedup row with a
                            slack-normalized value gate, plus the
                            derived adaptivity accounting from
                            ``algorithm_cost``.

    Row schema: every row carries the cost-model round count
    (``rounds=``) and, for algorithms whose result traces it (dash,
    fast), the MEASURED adaptivity of that run (``rounds_measured=``)
    next to the wall-clock value.
    """
    from repro.core import algorithm_cost, get_algorithm, select
    from repro.core.distributed import pad_ground_set
    from repro.launch.mesh import make_host_mesh

    scale = 2 if full else 1
    key = jax.random.PRNGKey(0)
    mesh = make_host_mesh() if len(jax.devices()) > 1 else None

    for name, make_obj, X, k_grid, opts in _baseline_datasets(scale):
        obj = make_obj(X)
        dash_opts = {kk: v for kk, v in opts.items()}
        for k in k_grid:
            # ---- value-vs-k: every algorithm, single device ----------
            single_vals = {}
            for algo, aopts in _BASELINE_ALGOS:
                use = dict(dash_opts, **aopts) if algo == "dash" else dict(aopts)
                t, res = wall_time(
                    lambda a=algo, u=use: jax.block_until_ready(
                        select(a, obj, k, key=key, **u)),
                    warmup=1, iters=1)
                single_vals[algo] = float(res.value)
                cost = algorithm_cost(algo, obj.n, k)
                meas = (f";rounds_measured={int(res.raw.rounds)}"
                        if hasattr(res.raw, "rounds") else "")
                emit(f"baselines/{name}/k={k}/{algo}", t * 1e6,
                     f"value={float(res.value):.4f};"
                     f"rounds={cost['adaptive_rounds']};"
                     f"queries={cost['oracle_calls']}" + meas)

            # ---- single-vs-sharded: the distributed twins ------------
            if mesh is not None:
                Xp, _ = pad_ground_set(X, mesh.shape["model"])
                obj_p = make_obj(Xp)
                for algo, aopts in _BASELINE_ALGOS:
                    if get_algorithm(algo).distributed is None:
                        continue
                    use = dict(aopts)
                    if algo == "dash":
                        # single-guess sharded dash: pin OPT from greedy
                        use = dict(dash_opts, opt=single_vals["greedy"] * 1.05,
                                   n_samples=4)
                    t, res = wall_time(
                        lambda a=algo, u=use: jax.block_until_ready(
                            select(a, obj_p, k, key=key, mesh=mesh, **u)),
                        warmup=1, iters=1)
                    ref = single_vals[algo]
                    meas = (f";rounds_measured={int(res.raw.rounds)}"
                            if hasattr(res.raw, "rounds") else "")
                    emit(f"baselines/{name}/k={k}/{algo}_sharded", t * 1e6,
                         f"value={float(res.value):.4f};"
                         f"single_value={ref:.4f};"
                         f"parity={float(res.value) / max(ref, 1e-9):.4f};"
                         f"mesh={'x'.join(str(s) for s in mesh.devices.shape)}"
                         + meas)

    # ---- time-vs-n: wall-clock growth of the per-round sweeps --------
    # Jitted whole-selection runners (warmup excludes compile) on the
    # LOGISTIC objective — the oracle-bound regime where stochastic
    # greedy's k·s query count converts into wall-clock (measured
    # ~1.6–2.2× over greedy on CPU; on the cheap regression oracle the
    # per-round noise/top-k overhead outweighs the saved GEMM and exact
    # greedy wins — query counts are recorded either way, so the
    # artifact carries the honest crossover).
    from repro.core import fast as fast_fn
    from repro.core import greedy as greedy_fn
    from repro.core import lazy_greedy as lazy_fn
    from repro.core import stochastic_greedy as stochastic_fn
    from repro.core import top_k_select as topk_fn

    rng = np.random.default_rng(1)
    k = 8 * scale
    for n in (128 * scale, 256 * scale, 512 * scale):
        d = 128 * scale
        X0 = rng.normal(size=(d, n))
        X = normalize_columns(jnp.asarray(X0, jnp.float32)) * np.sqrt(d)
        w = np.zeros(n)
        w[: k] = rng.uniform(-2, 2, k)
        yb = jnp.asarray((1 / (1 + np.exp(-X0 @ w)) > 0.5).astype(np.float32))
        # Data enters as jit ARGUMENTS (not closures) so XLA cannot
        # constant-fold the oracle sweeps being timed.
        def make(Xa, ya):
            return ClassificationObjective(Xa, ya, kmax=k, newton_steps=3,
                                           newton_gain_steps=2)

        runners = {
            "greedy": (
                jax.jit(lambda Xa, ya: greedy_fn(make(Xa, ya), k)),
                (X, yb)),
            "stochastic_greedy": (
                jax.jit(lambda Xa, ya, kk:
                        stochastic_fn(make(Xa, ya), k, kk)),
                (X, yb, key)),
            "topk": (
                jax.jit(lambda Xa, ya: topk_fn(make(Xa, ya), k)),
                (X, yb)),
            "fast": (
                jax.jit(lambda Xa, ya, kk: fast_fn(make(Xa, ya), k, kk)),
                (X, yb, key)),
        }
        times, vals = {}, {}
        for algo, (fn, fargs) in runners.items():
            t, res = wall_time(
                lambda f=fn, a=fargs: jax.block_until_ready(f(*a)),
                warmup=1, iters=3)
            times[algo] = t
            vals[algo] = float(res.value)
            cost = algorithm_cost(algo, n, k)
            meas = (f";rounds_measured={int(res.rounds)}"
                    if hasattr(res, "rounds") else "")
            emit(f"baselines/time_vs_n/n={n}/{algo}", t * 1e6,
                 f"value={vals[algo]:.4f};queries={cost['oracle_calls']}"
                 + meas)
        # lazy_greedy drives its priority queue from the host, so it is
        # timed as-is (compile amortized by the warmup run) — it is the
        # wall-clock reference FAST has to beat at equal value.
        obj_t = make(X, yb)
        t, res = wall_time(
            lambda: jax.block_until_ready(lazy_fn(obj_t, k)),
            warmup=1, iters=3)
        times["lazy_greedy"] = t
        vals["lazy_greedy"] = float(res.value)
        cost = algorithm_cost("lazy_greedy", n, k)
        emit(f"baselines/time_vs_n/n={n}/lazy_greedy", t * 1e6,
             f"value={vals['lazy_greedy']:.4f};"
             f"queries={cost['oracle_calls']}")
        emit(f"baselines/time_vs_n/n={n}/speedup", 0.0,
             f"greedy_over_stochastic="
             f"{times['greedy'] / max(times['stochastic_greedy'], 1e-12):.2f}x")
        # The acceptance row: fast must beat lazy_greedy's wall-clock at
        # equal slack-normalized value (value_ok = fast within 5% of the
        # lazy-greedy objective or better).
        emit(f"baselines/time_vs_n/n={n}/fast_over_lazy", 0.0,
             f"speedup="
             f"{times['lazy_greedy'] / max(times['fast'], 1e-12):.2f}x;"
             f"value_fast={vals['fast']:.4f};"
             f"value_lazy={vals['lazy_greedy']:.4f};"
             f"value_ok={int(vals['fast'] >= 0.95 * vals['lazy_greedy'])}")


#: --suite train roster: selection policies A/B'd at equal step count.
_TRAIN_ALGOS = (
    ("dash", {"n_samples": 4}),
    ("stochastic_greedy", {}),
    ("random", {}),
    ("none", None),
)


def run_train(full: bool = False):
    """--suite train: tokens-to-loss for selection-in-the-loop.

    Trains the reduced smollm config from the SAME init and token
    stream under each selection policy (dash / stochastic_greedy /
    random coreset picks, plus the no-selection stream baseline) and
    reports the tail loss at equal step count — i.e. equal *trained*
    tokens, the honest axis for data selection: a selection win means
    better loss from the same token budget.  Selection-step overhead is
    recorded per row (``selection_s`` / ``selection_frac``) so the
    quality-vs-overhead tradeoff lands in the same artifact, and the
    summary row carries the dash-vs-random gap the acceptance criterion
    asks for.
    """
    from repro.configs import TrainConfig, get_reduced_config
    from repro.data.pipeline import TokenPipeline
    from repro.data.selection import BatchSelector
    from repro.data.synthetic import make_lm_tokens
    from repro.models import build_model
    from repro.train.loop import train_loop

    steps = 60 if full else 30
    batch, seq = 8, 32
    cfg = get_reduced_config("smollm-135m")
    model = build_model(cfg)
    tokens = make_lm_tokens(0, 400_000, cfg.vocab_size)
    tcfg = TrainConfig(total_steps=steps, learning_rate=3e-3,
                       warmup_steps=max(steps // 10, 1))
    finals = {}
    for algo, opts in _TRAIN_ALGOS:
        selector = None if opts is None else BatchSelector(
            k=batch, algo=algo, feature_mode="grad", embed_dim_cap=32,
            **opts)
        with TokenPipeline(tokens, batch, seq) as pipeline:
            t0 = time.perf_counter()
            res = train_loop(model, tcfg, pipeline, selector=selector,
                             selection_every=2, selection_pool_factor=4,
                             log_every=10 ** 9)
            t = time.perf_counter() - t0
        tail = max(steps // 5, 1)
        finals[algo] = float(np.mean(res.losses[-tail:]))
        emit(f"train/{algo}/tokens_to_loss", t * 1e6,
             f"final_loss={finals[algo]:.4f};tokens={steps * batch * seq};"
             f"selection_s={res.selection_time_s:.2f};"
             f"selection_frac={res.selection_time_s / max(t, 1e-9):.2f}")
    emit("train/dash_vs_random", 0.0,
         f"random_minus_dash={finals['random'] - finals['dash']:+.4f};"
         f"dash={finals['dash']:.4f};random={finals['random']:.4f};"
         f"none={finals['none']:.4f}")
    return finals


def run(full: bool = False):
    scale = 1 if full else 4

    # D1 regression (paper: n=500 features, k≤100)
    X, y, _ = make_d1_regression(
        n_samples=1000 // scale * scale, n_features=500 // scale,
        support=100 // scale)
    obj = RegressionObjective(jnp.asarray(X), jnp.asarray(y),
                              kmax=100 // scale)
    _bench_objective("D1_regression", obj,
                     [25 // scale, 50 // scale, 100 // scale],
                     lasso_xy=(X, y))
    accuracy_vs_rounds("D1_regression", obj, 100 // scale)
    filter_engine_ab("D1_regression", X, y, 50 // scale, 100 // scale)

    # D2 clinical surrogate
    X2, y2 = make_d2_clinical(n_samples=1200 // scale, n_features=385 // scale)
    obj2 = RegressionObjective(jnp.asarray(X2), jnp.asarray(y2),
                               kmax=100 // scale)
    _bench_objective("D2_clinical", obj2, [50 // scale, 100 // scale],
                     lasso_xy=(X2, y2))

    # D3 classification
    X3, y3, _ = make_d3_classification(
        n_samples=600 // scale, n_features=200 // scale,
        support=50 // scale)
    obj3 = ClassificationObjective(jnp.asarray(X3), jnp.asarray(y3),
                                   kmax=60 // scale)
    _bench_objective("D3_classification", obj3, [20 // scale, 40 // scale],
                     lasso_xy=(X3, y3), task="logistic")

    # D4 gene surrogate (paper: k up to 200)
    X4, y4, _ = make_d4_gene(n_samples=800 // scale,
                             n_features=2500 // scale)
    obj4 = ClassificationObjective(jnp.asarray(X4), jnp.asarray(y4),
                                   kmax=200 // scale)
    _bench_objective("D4_gene", obj4, [100 // scale, 200 // scale])

    # Bayesian A-optimal experimental design (Fig 4) — smaller γ ⇒
    # smaller α guess (Cor. 9)
    Xd = make_d1_design(n_samples=1024 // scale, n_features=256 // scale)
    objd = AOptimalityObjective(jnp.asarray(Xd), kmax=100 // scale,
                                beta2=1.0, sigma2=1.0)
    _bench_objective("D1_design_aopt", objd, [50 // scale, 100 // scale],
                     alpha=0.4)
    accuracy_vs_rounds("D1_design_aopt", objd, 100 // scale)


def main() -> None:
    enable_compile_cache()
    import argparse
    import json

    from benchmarks.common import rows

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--json", nargs="?", const="BENCH_selection.json", default=None,
        metavar="PATH",
        help="also write the emitted rows as a JSON trajectory artifact "
             "(default path: BENCH_selection.json)",
    )
    ap.add_argument("--full", action="store_true",
                    help="paper-scale problem sizes")
    ap.add_argument(
        "--suite", default="all",
        help="comma-separated subset of {paper, distributed, lattice, "
             "baselines, train, resilience, serve} or 'all'.  'paper' = Fig 2/3/4 "
             "analogues; 'distributed' = dash_distributed vs dash for "
             "all three objectives; 'lattice' = loop vs batched vs "
             "pod-sharded (OPT, α) guess lattice; 'baselines' = the "
             "full select() registry (§5 competitors), value-vs-k / "
             "single-vs-sharded / time-vs-n; 'train' = tokens-to-loss "
             "for coreset selection-in-the-loop, dash vs stochastic "
             "greedy vs random vs no selection (the distributed CI job "
             "greedy vs random vs no selection; 'resilience' = round-"
             "snapshot overhead + kill/restore/replay costs; 'serve' = "
             "selection-service p50/p99 latency + goodput at three "
             "offered-load levels, chaos off and on (the "
             "distributed CI job runs "
             "'distributed,lattice,baselines,train,resilience,serve' "
             "with 8 forced host devices)",
    )
    args = ap.parse_args()
    known = {"paper", "distributed", "lattice", "baselines", "train",
             "resilience", "serve"}
    suites = (known if args.suite == "all"
              else {s.strip() for s in args.suite.split(",")})
    unknown = suites - known
    if unknown:
        ap.error(f"unknown suite(s): {sorted(unknown)}")
    if "paper" in suites:
        run(full=args.full)
    if "distributed" in suites:
        run_distributed(full=args.full)
    if "lattice" in suites:
        run_lattice(full=args.full)
    if "baselines" in suites:
        run_baselines(full=args.full)
    if "train" in suites:
        run_train(full=args.full)
    if "resilience" in suites:
        run_resilience(full=args.full)
    if "serve" in suites:
        run_serve(full=args.full)
    if args.json:
        payload = {"suite": f"bench_selection/{args.suite}",
                   "backend": jax.default_backend(),
                   "n_devices": len(jax.devices()), "rows": rows()}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
