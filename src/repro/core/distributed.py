"""Distributed DASH — the paper's parallelism mapped onto a device mesh.

This is the shard_map realization of paper Algorithm 1 (Thm 10): the
O(log n)-adaptivity guarantee only buys wall-clock time if every round's
oracle sweep really runs as one parallel pass, which is what the layout
below provides — for ALL THREE of the paper's objectives (regression,
A-optimal design, logistic feature selection; Cor. 7/8/9), not just one.

The round/filter control flow is NOT re-implemented here: this module
binds ``core.selection_loop.run_selection_rounds`` — the exact loop the
single-device ``core.dash`` runs — to distributed Monte-Carlo estimators
built from an objective's column-based ``DistributedObjective`` contract
(``objectives/base.py``).  ``dash_distributed(obj, ...)`` therefore works
for any objective implementing that contract; adding a fourth objective
requires no change in this file (see docs/distributed.md).

Layout:
  * ground-set columns of X sharded over the ``model`` axis — each shard
    evaluates the batched gain oracle for its own candidate block
    (the paper's "one oracle query per core", scaled to a pod),
  * Monte-Carlo expectation replicas over the ``data`` axis — each data
    row draws its own R ~ U(X) and the estimate is a ``pmean`` (under a
    straggler deadline the reduction switches to the trimmed
    responders-only ``runtime/straggler.py::robust_estimate``),
  * independent (OPT, α) guesses map onto the ``pod`` axis:
    ``dash_auto_distributed`` runs the WHOLE App.-G guess lattice in one
    ``shard_map`` launch — each pod slice drives its guesses through the
    same single-guess body ``dash_distributed`` uses, and the winner is
    committed with one ``all_gather``/argmax/``psum`` over ``pod``.

Resilience (docs/resilience.md): the same entry points also run in a
round-STEPPED mode (``resilience=`` / ``resume=`` / ``failure_injector=``)
— one compiled launch per adaptive round, with the between-round
``SelectionCarry`` snapshotted atomically at round boundaries
(``ckpt/checkpoint.py``), restorable onto a mesh with a different
model-axis width (``runtime/elastic.py``), and per-round straggler
deadlines simulated with responder-robust estimators
(``runtime/straggler.py``).  ``dash_distributed_restartable`` composes
the whole story under ``runtime/fault_tolerance.py::run_with_restart``.
Because the candidate draw uses replicated Gumbel noise over the GLOBAL
ground set, the selection is invariant to the model-axis partition —
resumed runs (even elastically reshaped ones) are bitwise the
uninterrupted run.

Collectives per adaptive round (n = ground set, P = model shards,
b = block size ⌈k/r⌉, d = feature dim):
  sampling     all_gather  (P·b scores)             — O(P·b)
  column fetch psum        (d × b one-hot GEMM)     — O(d·b)
  estimates    pmean       (scalar / (n/P,) gains)  — O(n/P)
Everything else is shard-local dense linear algebra (the objective's
``dist_*`` oracles are collective-free by contract).  This is why DASH
parallelizes: per round the communication volume is O(d·b + n/P), while
greedy must synchronize after every single pick (k rounds of latency).

Filter loop (the inner while of Alg. 1): the statistic Ê_R[f_{S∪R}(a)]
is estimated exactly as in ``core.dash._estimate_elem_gains`` — gains at
every Monte-Carlo perturbed state S ∪ R_i, leave-one-out-averaged over
the samples with a ∉ R_i, pmean'd over the data axis.  With
``use_filter_engine=True`` (the default wherever the objective opts in)
the per-shard evaluation goes through the objective's
``dist_filter_gains_batch``: shared state stays replicated, each sample
contributes only its small delta (MGS delta columns / Woodbury factors /
refit logits), and one fused ``repro.kernels.filter_gains`` launch
sweeps the local candidate shard for ALL samples — sharding the engine's
candidate axis over ``model`` is exactly shard_map-compatible because
the call is shard-local dense math with no collectives inside.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.estimators import gumbel_noise, top_k_rows
from repro.core.objectives.base import with_precision
from repro.core.selection_loop import (
    DashConfig,
    DashTrace,
    ResilienceConfig,
    RoundCheckpointer,
    SelectionCarry,
    SelectionHooks,
    cached_runner,
    drive_checkpointed_rounds,
    initial_carry,
    make_round_body,
    round_arrivals,
    run_selection_rounds,
)


class DistDashResult(NamedTuple):
    sel_mask: jnp.ndarray      # (n,) bool — global (gathered)
    sel_count: jnp.ndarray
    value: jnp.ndarray
    rounds: jnp.ndarray        # adaptive rounds consumed (filter iters + r)
    values_trace: jnp.ndarray  # (r,)
    trace: DashTrace | None = None


class LatticeDistResult(NamedTuple):
    """Best-of-lattice result of :func:`dash_auto_distributed`: the
    winning guess's solution plus the whole lattice's values.  The
    winning guess's per-round values are ``trace.values`` (no separate
    ``values_trace`` alias — ``trace`` is always present here, unlike
    :class:`DistDashResult`)."""
    sel_mask: jnp.ndarray        # (n,) bool — the WINNING guess's solution
    sel_count: jnp.ndarray
    value: jnp.ndarray
    rounds: jnp.ndarray
    trace: DashTrace             # winning guess's full trace
    lattice_values: jnp.ndarray  # (n_guesses,) f(S) per joint (OPT, α) guess
    best_guess: jnp.ndarray      # () int32 — argmax index into the lattice


# ---------------------------------------------------------------------------
# distributed primitives (run inside shard_map; `axis` is the mesh axis name)
# ---------------------------------------------------------------------------

def _dist_sample(key, alive_local, m, n_local, n_global, axis):
    """Globally-uniform without-replacement sample of ≤ m alive elements.

    Every shard evaluates the SAME replicated (n,) Gumbel draw
    (``estimators.gumbel_noise`` from the replicated key — the PR-5
    layout the baselines use) and slices its contiguous block, publishes
    its local top-m via all_gather, and all shards deterministically
    reduce to the same global top-m.  Because the noise is a function of
    (key, n) alone — NOT of the shard count — the sampled set is
    invariant to the mesh's model-axis width, which is what lets a
    checkpoint taken on 8 devices resume on 4 with a bitwise-identical
    selection (docs/resilience.md).  Returns the local view:
    (idx_local, owned&valid, valid_global).
    """
    rank = jax.lax.axis_index(axis)
    g = _local_noise_slice(gumbel_noise(key, n_global), rank, n_local)
    scores = jnp.where(alive_local, g, -jnp.inf)
    loc_vals, loc_idx = top_k_rows(scores, m)             # rank 2 under vmaps

    all_vals = jax.lax.all_gather(loc_vals, axis)          # (P, m)
    all_idx = jax.lax.all_gather(loc_idx, axis)            # (P, m)
    flat_vals = all_vals.reshape(-1)
    top_vals, top_flat = jax.lax.top_k(flat_vals, m)       # global top-m
    top_shard = top_flat // m
    top_local = jnp.take(all_idx.reshape(-1), top_flat)
    valid_global = jnp.isfinite(top_vals)
    owned = (top_shard == rank) & valid_global
    return top_local.astype(jnp.int32), owned, valid_global


def _dist_gather_columns(X_local, idx_local, owned, axis):
    """psum-gather of the sampled global set's columns: (d, m)."""
    cols = jnp.take(X_local, idx_local, axis=1)
    cols = cols * owned.astype(X_local.dtype)[None, :]
    return jax.lax.psum(cols, axis)


# ---------------------------------------------------------------------------
# the generic sharded runner
# ---------------------------------------------------------------------------

def _make_hooks(obj, cfg: DashConfig, X_local, n_global: int,
                model_axis: str, data_axis: str | None,
                use_filter_engine: bool, *,
                arrived=None, policy=None) -> SelectionHooks:
    """Bind the shared selection loop to a shard of a
    ``DistributedObjective`` — called INSIDE ``shard_map`` with the
    traced ``X_local`` shard.

    ``arrived`` (optional, (n_samples,) bool) is the round's
    Monte-Carlo-replica responder mask: with it the two estimators
    become straggler-aware — non-responder replicas contribute nothing
    (their leave-one-out weights are zeroed; the set-gain reduction
    switches to ``runtime/straggler.py::robust_estimate`` under
    ``policy``), while a fully-arrived round short-circuits to the plain
    mean, bitwise identical to the deadline-free path.  The COMMIT draw
    (``pick_and_add``) never consults ``arrived``: committing is a
    collective the round barrier waits out, which is what keeps the
    selected set deterministic per key regardless of stragglers.
    """
    block = cfg.block
    n_local = X_local.shape[1]

    def draw(kk, alive, allowed):
        """One global sample: local indices/ownership + gathered cols.

        Collectives (all_gather / psum over the model axis) stay in
        this stage; every oracle call on the result is shard-local.
        """
        idx_l, owned, validg = _dist_sample(
            kk, alive, block, n_local, n_global, model_axis
        )
        slot_ok = validg & (jnp.arange(block) < allowed)
        C = _dist_gather_columns(X_local, idx_l, owned & slot_ok,
                                 model_axis)
        return idx_l, owned, slot_ok, C

    def fold_data(key):
        # Each data-axis replica evaluates its own samples; the
        # estimators pmean/psum the results back together.  (Folding
        # with the data index means the data-axis SIZE is part of the
        # sampling determinism — elastic restores must preserve it.)
        didx = jax.lax.axis_index(data_axis) if data_axis else 0
        return jax.random.fold_in(key, didx)

    def gains_local(ds, sel_local):
        return jnp.where(sel_local, 0.0, obj.dist_gains(ds, X_local))

    def estimate_set_gain(state, alive, allowed, key):
        ds, _ = state

        def one(kk):
            _, _, slot_ok, C = draw(kk, alive, allowed)
            return obj.dist_set_gain(ds, C, slot_ok)

        vals = jax.vmap(one)(
            jax.random.split(fold_data(key), cfg.n_samples)
        )
        if arrived is None:
            est = jnp.mean(vals)
        else:
            from repro.runtime.straggler import robust_estimate

            # All replicas made the deadline → the exact plain mean
            # (bitwise the deadline-free estimate); otherwise the
            # robust deadline reduction over the responders.
            est = jnp.where(jnp.all(arrived), jnp.mean(vals),
                            robust_estimate(vals, arrived, policy))
        if data_axis:
            est = jax.lax.pmean(est, data_axis)
        return est

    def estimate_elem_gains(state, alive, allowed, key):
        ds, sel_local = state
        keys = jax.random.split(fold_data(key), cfg.n_samples)

        def one_draw(kk):
            idx_l, owned, slot_ok, C = draw(kk, alive, allowed)
            w = jnp.ones((n_local,)).at[idx_l].add(
                jnp.where(owned & slot_ok, -1.0, 0.0)
            )
            return C, slot_ok, w

        Cs, slot_oks, ws = jax.vmap(one_draw)(keys)
        if use_filter_engine:
            # Shared state + per-sample deltas: one fused engine
            # sweep of the local candidate shard for all samples.
            gs = obj.dist_filter_gains_batch(ds, Cs, slot_oks, X_local)
        else:
            def perturbed_gains(C, v):
                with jax.named_scope("repro.add_set"):
                    perturbed = obj.dist_add_set(ds, C, v, X_local)
                return obj.dist_gains(perturbed, X_local)

            gs = jax.vmap(perturbed_gains)(Cs, slot_oks)
        gs = jnp.where(sel_local[None, :], 0.0, gs)

        if arrived is not None:
            # A replica that missed the deadline contributes no weight:
            # its gains can never be attributed to any candidate.  With
            # every replica arrived this multiplies by 1.0 — bitwise
            # the deadline-free weights.
            ws = ws * arrived.astype(ws.dtype)[:, None]
        gsum, wsum = jnp.sum(gs * ws, axis=0), jnp.sum(ws, axis=0)
        if data_axis:
            gsum = jax.lax.psum(gsum, data_axis)
            wsum = jax.lax.psum(wsum, data_axis)
        est = gsum / jnp.maximum(wsum, 1.0)
        return jnp.where(wsum > 0, est, gains_local(ds, sel_local))

    def pick_and_add(state, alive, allowed, key):
        ds, sel_local = state
        idx_l, owned, slot_ok, C = draw(key, alive, allowed)
        with jax.named_scope("repro.add_set"):
            ds = obj.dist_add_set(ds, C, slot_ok, X_local)
        # Scatter ONLY the owned slots: idx_l entries for slots owned
        # by other shards are foreign local indices that can collide
        # with an owned slot's index, and a duplicate-index .set()
        # could then drop the True write.  Routing non-owned slots to
        # an out-of-bounds index (mode="drop") makes the scatter
        # collision-free.
        idx_safe = jnp.where(owned & slot_ok, idx_l, n_local)
        sel_local = sel_local.at[idx_safe].set(True, mode="drop")
        added = jax.lax.psum(
            jnp.sum((owned & slot_ok).astype(jnp.int32)), model_axis
        )
        return (ds, sel_local), added

    return SelectionHooks(
        value=lambda state: obj.dist_value(state[0]),
        sel_mask=lambda state: state[1],
        estimate_set_gain=estimate_set_gain,
        estimate_elem_gains=estimate_elem_gains,
        pick_and_add=pick_and_add,
        count_alive=lambda alive: jax.lax.psum(
            jnp.sum(alive.astype(jnp.int32)), model_axis
        ),
    )


def _init_state_alive(obj, X_local):
    """Round-0 ``(state, alive)`` for one shard of the ground set."""
    state0 = (
        obj.dist_init(X_local),
        jnp.zeros((X_local.shape[1],), bool),     # shard-local sel mask
    )
    # Zero columns (pad_ground_set padding, or genuinely empty
    # candidates) start dead: they can contribute nothing, and the
    # commit step samples uniformly from `alive`, so leaving them in
    # would let padding burn capacity and pollute sel_mask whenever a
    # round commits without filtering.
    alive0 = jnp.sum(X_local * X_local, axis=0) > 0
    return state0, alive0


def _make_guess_runner(obj, cfg: DashConfig, n_local: int, n_global: int,
                       model_axis: str, data_axis: str | None,
                       use_filter_engine: bool):
    """Build the shard-local single-guess DASH body.

    Returns ``run_one(X_local, key, opt, alpha=None) -> (sel_local,
    count, value, rounds, trace)`` — the function both sharded runtimes
    trace inside ``shard_map``: :func:`dash_distributed` runs it for one
    (OPT, α) guess, :func:`dash_auto_distributed` vmaps it over the pod
    slice's share of the guess lattice.  All collectives inside touch
    only ``model_axis`` / ``data_axis``, so the caller is free to lay a
    ``pod`` axis on top.
    """
    def run_one(X_local, key_rep, opt_rep, alpha_rep=None):
        hooks = _make_hooks(obj, cfg, X_local, n_global, model_axis,
                            data_axis, use_filter_engine)
        state0, alive0 = _init_state_alive(obj, X_local)
        (ds, sel_local), _, count, _, trace = run_selection_rounds(
            hooks, cfg, opt_rep, key_rep, state0, alive0, alpha=alpha_rep
        )
        rounds = jnp.sum(trace.filter_iters) + jnp.asarray(cfg.r, jnp.int32)
        return sel_local, count, obj.dist_value(ds), rounds, trace

    return run_one


def _shard_mapped(run, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the Monte-Carlo
    estimators vmap over sample keys with collectives (psum/all_gather)
    inside the vmapped body; the VMA invariant checker does not support
    that composition."""
    return jax.shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _top_gain(obj, mesh, model_axis: str):
    """max_a f(a) at S = ∅, swept shard by shard: the opening probe of
    the OPT guess lattice.  A sweep of the global objective would pass
    the sharded X to a kernel that cannot be partitioned, and the
    compiler would gather all of X onto every device."""
    def top(X_local):
        g = obj.dist_gains(obj.dist_init(X_local), X_local)
        return jax.lax.pmax(jnp.max(g), model_axis)

    run = cached_runner(
        obj, ("top_gain", mesh, model_axis),
        lambda: jax.jit(_shard_mapped(top, mesh, (P(None, model_axis),),
                                      P())),
    )
    return run(obj.X)


def _resolve_engine_flag(obj, use_filter_engine: bool | None) -> bool:
    if use_filter_engine is None:
        use_filter_engine = bool(getattr(obj, "use_filter_engine", False))
    return use_filter_engine and hasattr(obj, "dist_filter_gains_batch")


def _dist_runner(obj, cfg: DashConfig, mesh, n_local: int, model_axis: str,
                 data_axis: str | None, engine: bool):
    """Jitted single-guess sharded executor, cached per objective
    (weakly — see :func:`core.selection_loop.cached_runner`) on the
    (resolved config, mesh, layout) residual.  Rebuilding the
    jit(shard_map) closure per call would retrace and recompile on EVERY
    invocation — guess sweeps and benchmarks call this repeatedly."""
    def build():
        run_one = _make_guess_runner(
            obj, cfg, n_local, n_local * mesh.shape[model_axis],
            model_axis, data_axis, engine,
        )
        in_specs = (P(None, model_axis), P(), P())
        out_specs = (
            P(model_axis), P(), P(), P(),
            DashTrace(values=P(), alive=P(), filter_iters=P(),
                      est_set_gain=P()),
        )
        return jax.jit(_shard_mapped(run_one, mesh, in_specs, out_specs))

    return cached_runner(
        obj, ("dist", cfg, mesh, n_local, model_axis, data_axis, engine),
        build,
    )


def dash_distributed(
    obj, cfg: DashConfig, key, opt, mesh,
    *, model_axis: str = "model", data_axis: str | None = "data",
    use_filter_engine: bool | None = None,
    precision: str | None = None,
    resilience: ResilienceConfig | None = None,
    resume: str | bool | None = None,
    failure_injector=None,
):
    """Run DASH for any ``DistributedObjective`` on a device mesh.

    ``obj.X`` (d, n) is sharded over ``model_axis`` (n must be divisible
    by the axis size — pad first, see ``pad_ground_set``); Monte-Carlo
    estimate replicas ride ``data_axis`` (pass ``None`` for a pure
    model-parallel mesh).  The selection loop, thresholds and trace are
    the shared ``core.selection_loop`` implementation, so solutions are
    statistically exchangeable with single-device ``dash(obj, ...)``.

    ``use_filter_engine=None`` defers to ``obj.use_filter_engine``;
    ``False`` forces the per-sample ``dist_add_set`` + ``dist_gains``
    path, which re-evaluates the full local shard once per sample.

    Resilience (docs/resilience.md): passing any of ``resilience`` /
    ``resume`` / ``failure_injector`` switches to the host-stepped
    runtime — one compiled launch per round instead of one per run —
    which snapshots the carry at round boundaries, simulates straggler
    deadlines, and can ``resume`` (a checkpoint directory, or ``True``
    for ``resilience.ckpt_dir``) onto THIS mesh even when the snapshot
    was taken on a mesh with a different model-axis width: the carry is
    re-sharded via ``runtime/elastic.py::reshard_tree`` and the
    replicated-Gumbel sampling is partition-invariant, so the resumed
    selection is bitwise the uninterrupted one.  (The data-axis size
    must be preserved — it is folded into the sample keys — and is
    validated against the snapshot manifest.)

    This runs ONE (OPT, α) guess; :func:`dash_auto_distributed` sweeps
    the whole guess lattice over the ``pod`` mesh axis in one launch.

    ``precision="bf16"`` streams the per-shard kernel operands in bf16
    with f32 accumulation (see ``objectives.base.with_precision``).
    """
    if precision is not None:
        obj = with_precision(obj, precision)
    X = obj.X
    d, n = X.shape
    cfg = cfg.resolve(n)
    Pm = mesh.shape[model_axis]
    assert n % Pm == 0, f"pad ground set: n={n} % model={Pm}"
    engine = _resolve_engine_flag(obj, use_filter_engine)
    if resilience is not None or resume or failure_injector is not None:
        return _dash_distributed_stepped(
            obj, cfg, key, opt, mesh, model_axis, data_axis, engine,
            resilience, resume, failure_injector,
        )
    run_sharded = _dist_runner(
        obj, cfg, mesh, n // Pm, model_axis, data_axis, engine,
    )
    sel, nsel, value, rounds, trace = run_sharded(
        X, key, jnp.asarray(opt, jnp.float32)
    )
    return DistDashResult(
        sel_mask=sel, sel_count=nsel, value=value, rounds=rounds,
        values_trace=trace.values, trace=trace,
    )


# ---------------------------------------------------------------------------
# resilient (round-stepped) runtime: snapshot / elastic resume / stragglers
# ---------------------------------------------------------------------------

def _dist_state_specs(obj, n_local: int, model_axis: str):
    """PartitionSpecs for an objective's dist-state pytree, inferred
    without extending the ``DistributedObjective`` contract: evaluate
    ``dist_init``'s shape structure for a LOCAL shard and for the GLOBAL
    ground set — dimensions that scale with the shard width are
    column-sharded (``model_axis``), identical ones are replicated."""
    d, n = obj.X.shape
    dt = obj.X.dtype
    local = jax.eval_shape(
        obj.dist_init, jax.ShapeDtypeStruct((d, n_local), dt))
    glob = jax.eval_shape(obj.dist_init, jax.ShapeDtypeStruct((d, n), dt))

    def one(loc, glo):
        return P(*[model_axis if int(ls) != int(gs) else None
                   for ls, gs in zip(loc.shape, glo.shape)])

    return jax.tree_util.tree_map(one, local, glob)


def _carry_specs(obj, n_local: int, model_axis: str) -> SelectionCarry:
    """PartitionSpecs for the full :class:`SelectionCarry`.  Used as the
    stepped runners' in/out specs — which makes the host-side carry a
    GLOBAL view (shard-local leaves reassembled along ``model_axis``),
    i.e. the snapshot format is mesh-shape-agnostic by construction."""
    return SelectionCarry(
        state=(_dist_state_specs(obj, n_local, model_axis), P(model_axis)),
        alive=P(model_axis), count=P(), key=P(),
        trace=DashTrace(values=P(), alive=P(), filter_iters=P(),
                        est_set_gain=P()),
    )


def _round_step_runner(obj, cfg: DashConfig, mesh, n_local: int,
                       model_axis: str, data_axis: str | None, engine: bool,
                       policy):
    """Jitted ONE-ROUND sharded executor (weak-cached).  ``rho``, OPT, α
    and the responder mask are runtime inputs, so a single compilation
    serves every round of every (resumed) run.  ``policy`` non-None
    builds the straggler-aware estimators."""
    def build():
        n_glob = n_local * mesh.shape[model_axis]
        cspecs = _carry_specs(obj, n_local, model_axis)

        def step(X_local, rho, opt, alpha, arrived, carry):
            hooks = _make_hooks(
                obj, cfg, X_local, n_glob, model_axis, data_axis, engine,
                arrived=arrived if policy is not None else None,
                policy=policy,
            )
            return make_round_body(hooks, cfg)(rho, carry, opt, alpha)

        in_specs = (P(None, model_axis), P(), P(), P(), P(), cspecs)
        return jax.jit(_shard_mapped(step, mesh, in_specs, cspecs))

    return cached_runner(
        obj,
        ("dist_step", cfg, mesh, n_local, model_axis, data_axis, engine,
         policy),
        build,
    )


def _init_carry_runner(obj, cfg: DashConfig, mesh, n_local: int,
                       model_axis: str):
    def build():
        cspecs = _carry_specs(obj, n_local, model_axis)

        def init(X_local, key):
            state0, alive0 = _init_state_alive(obj, X_local)
            return initial_carry(cfg, key, state0, alive0)

        return jax.jit(
            _shard_mapped(init, mesh, (P(None, model_axis), P()), cspecs))

    return cached_runner(
        obj, ("dist_init_carry", cfg, mesh, n_local, model_axis), build)


def _finalize_runner(obj, cfg: DashConfig, mesh, n_local: int,
                     model_axis: str):
    def build():
        cspecs = _carry_specs(obj, n_local, model_axis)

        def fin(carry):
            (ds, sel_local), _, count, _, trace = carry
            rounds = (jnp.sum(trace.filter_iters)
                      + jnp.asarray(cfg.r, jnp.int32))
            return sel_local, count, obj.dist_value(ds), rounds, trace

        out_specs = (P(model_axis), P(), P(), P(), cspecs.trace)
        return jax.jit(_shard_mapped(fin, mesh, (cspecs,), out_specs))

    return cached_runner(
        obj, ("dist_finalize", cfg, mesh, n_local, model_axis), build)


def _snapshot_meta(algo: str, cfg: DashConfig, n: int,
                   data_size: int) -> dict:
    """Manifest `extra` for round snapshots: everything a resume target
    must agree on.  The model-axis width is deliberately ABSENT — that
    is the degree of freedom elastic restore exercises."""
    return {"algo": algo, "n": int(n), "k": int(cfg.k), "r": int(cfg.r),
            "n_samples": int(cfg.n_samples), "data_axis_size": int(data_size)}


def _restore_carry(resume_dir: str, like, specs, mesh, expect_meta: dict):
    """Latest complete snapshot → carry RE-SHARDED onto ``mesh``.

    Returns ``(carry, start_round)`` or None when the directory has no
    complete snapshot (cold start).  The manifest's compatibility meta
    is validated against ``expect_meta`` first — resuming onto a
    different data-axis size (or a different problem entirely) fails
    loudly instead of silently diverging.
    """
    from repro.ckpt.checkpoint import (
        latest_complete_step,
        read_manifest,
        restore_checkpoint,
    )
    from repro.runtime.elastic import reshard_tree

    snap = latest_complete_step(resume_dir)
    if snap is None:
        return None
    meta = read_manifest(resume_dir, snap).get("extra", {})
    for name, want in expect_meta.items():
        got = meta.get(name)
        if got is not None and got != want:
            raise ValueError(
                f"snapshot {resume_dir} step {snap}: {name}={got!r} is "
                f"incompatible with the resume target ({name}={want!r})")
    carry_host, _ = restore_checkpoint(resume_dir, like, step=snap)
    return reshard_tree(carry_host, specs, mesh), int(meta["round"])


def _carry_like(init_runner, X, key):
    """Global ShapeDtypeStructs of the carry — the restore `like` tree."""
    return jax.eval_shape(
        init_runner,
        jax.ShapeDtypeStruct(X.shape, X.dtype),
        jax.ShapeDtypeStruct(key.shape, key.dtype),
    )


def _dash_distributed_stepped(obj, cfg: DashConfig, key, opt, mesh,
                              model_axis: str, data_axis: str | None,
                              engine: bool,
                              resilience: ResilienceConfig | None,
                              resume, failure_injector):
    """Host-stepped :func:`dash_distributed` body (resolved cfg)."""
    d, n = obj.X.shape
    n_local = n // mesh.shape[model_axis]
    res = resilience if resilience is not None else ResilienceConfig()
    policy = res.resolved_policy() if res.straggler else None
    step = _round_step_runner(obj, cfg, mesh, n_local, model_axis,
                              data_axis, engine, policy)
    init = _init_carry_runner(obj, cfg, mesh, n_local, model_axis)
    fin = _finalize_runner(obj, cfg, mesh, n_local, model_axis)
    data_size = mesh.shape[data_axis] if data_axis else 1
    meta = _snapshot_meta("dash_distributed", cfg, n, data_size)

    carry, start_round = None, 0
    if resume:
        resume_dir = res.ckpt_dir if resume is True else resume
        restored = _restore_carry(
            resume_dir, _carry_like(init, obj.X, key),
            _carry_specs(obj, n_local, model_axis), mesh, meta)
        if restored is not None:
            carry, start_round = restored
    if carry is None:
        carry = init(obj.X, key)

    opt_v = jnp.asarray(opt, jnp.float32)
    alpha_v = jnp.asarray(cfg.alpha, jnp.float32)
    carry = drive_checkpointed_rounds(
        lambda rho, c, arrived: step(obj.X, rho, opt_v, alpha_v, arrived, c),
        carry, cfg, resilience=resilience, start_round=start_round,
        failure_injector=failure_injector, snapshot_extra=meta,
    )
    sel, nsel, value, rounds, trace = fin(carry)
    return DistDashResult(
        sel_mask=sel, sel_count=nsel, value=value, rounds=rounds,
        values_trace=trace.values, trace=trace,
    )


def dash_distributed_restartable(
    obj, cfg: DashConfig, key, opt,
    *, resilience: ResilienceConfig, mesh_provider,
    model_axis: str = "model", data_axis: str | None = "data",
    use_filter_engine: bool | None = None, precision: str | None = None,
    failure_injector=None,
    max_failures: int = 3, backoff_s: float = 0.0, sleep_fn=None,
) -> DistDashResult:
    """The full resilience composition: ``run_with_restart`` driving
    restore → (elastic) reshard → continue.

    ``mesh_provider()`` is consulted at every (re)start and may return a
    DIFFERENT mesh than the previous attempt ran on — a device loss
    shrinks the fleet, ``runtime/elastic.py::elastic_mesh`` builds the
    survivor mesh, and the restored carry is re-sharded onto it.  Every
    attempt replays from the newest complete round snapshot in
    ``resilience.ckpt_dir``; ``failure_injector`` (checked before each
    round) turns this into the kill-and-resume chaos test.  Snapshot
    writes ride ``run_with_restart``'s at-most-once ``on_step`` hook, so
    replayed rounds never double-save.
    """
    from repro.ckpt.checkpoint import latest_complete_step
    from repro.runtime.fault_tolerance import run_with_restart

    if not resilience.ckpt_dir:
        raise ValueError(
            "dash_distributed_restartable needs resilience.ckpt_dir")
    if precision is not None:
        obj = with_precision(obj, precision)
    d, n = obj.X.shape
    cfg = cfg.resolve(n)
    engine = _resolve_engine_flag(obj, use_filter_engine)
    policy = resilience.resolved_policy() if resilience.straggler else None
    ctx: dict = {}

    def activate():
        mesh = mesh_provider()
        Pm = mesh.shape[model_axis]
        assert n % Pm == 0, f"pad ground set: n={n} % model={Pm}"
        n_local = n // Pm
        ctx.update(
            mesh=mesh, n_local=n_local,
            data_size=mesh.shape[data_axis] if data_axis else 1,
            specs=_carry_specs(obj, n_local, model_axis),
            step=_round_step_runner(obj, cfg, mesh, n_local, model_axis,
                                    data_axis, engine, policy),
            init=_init_carry_runner(obj, cfg, mesh, n_local, model_axis),
            fin=_finalize_runner(obj, cfg, mesh, n_local, model_axis),
        )

    def meta():
        return _snapshot_meta("dash_distributed", cfg, n, ctx["data_size"])

    def make_state():
        activate()
        return ctx["init"](obj.X, key), 0

    def restore():
        if latest_complete_step(resilience.ckpt_dir) is None:
            return None        # nothing saved yet → cold restart
        activate()             # fresh (possibly shrunken) mesh
        return _restore_carry(
            resilience.ckpt_dir, _carry_like(ctx["init"], obj.X, key),
            ctx["specs"], ctx["mesh"], meta())

    ckpt = RoundCheckpointer(resilience)
    opt_v = jnp.asarray(opt, jnp.float32)
    alpha_v = jnp.asarray(cfg.alpha, jnp.float32)

    def step_fn(carry, rho):
        if failure_injector is not None:
            failure_injector.check(rho)
        arrived = round_arrivals(resilience, cfg, rho)
        return ctx["step"](obj.X, rho, opt_v, alpha_v, arrived, carry)

    def on_step(carry, rho):
        if (rho + 1) % resilience.every == 0:
            ckpt.save(rho + 1, carry, extra=meta())

    kw = {} if sleep_fn is None else {"sleep_fn": sleep_fn}
    carry = run_with_restart(
        total_steps=cfg.r, make_state=make_state, restore=restore,
        step_fn=step_fn, on_step=on_step, max_failures=max_failures,
        backoff_s=backoff_s, **kw,
    )
    ckpt.wait()
    sel, nsel, value, rounds, trace = ctx["fin"](carry)
    return DistDashResult(
        sel_mask=sel, sel_count=nsel, value=value, rounds=rounds,
        values_trace=trace.values, trace=trace,
    )


def _commit_lattice_winner(res, g_local: int, pod_axis: str):
    """Winner commit shared by the fused and the round-stepped lattice
    runtimes.  ``res`` is the per-guess stacked result tuple
    ``(sel_local, count, value, rounds, trace)`` with a leading
    ``g_local`` axis (shard-local view, inside ``shard_map``).

    Local best of this pod slice's guesses, then the global commit:
    all_gather (pod,) values → replicated argmax → psum broadcast.  NaN
    lanes are masked out of both argmaxes (nan_to_neginf) so a
    degenerate guess can never win the lattice."""
    from repro.core.dash import nan_to_neginf

    def commit_winner(tree, win):
        # Broadcast the winning pod's pytree to every pod (exactly one
        # pod has ``win=True``, so the psum IS the winner's value).
        def pick(x):
            masked = jnp.where(win, x, jnp.zeros_like(x))
            if x.dtype == jnp.bool_:
                return jax.lax.psum(masked.astype(jnp.int32), pod_axis) > 0
            return jax.lax.psum(masked, pod_axis)
        return jax.tree_util.tree_map(pick, tree)

    value_s = res[2]
    bi = jnp.argmax(nan_to_neginf(value_s))
    local_best = jax.tree_util.tree_map(
        lambda x: jnp.take(x, bi, axis=0), res
    )
    vals_pod = jax.lax.all_gather(local_best[2], pod_axis)         # (Pp,)
    gbi = jnp.argmax(nan_to_neginf(vals_pod))
    win = jax.lax.axis_index(pod_axis) == gbi
    sel_b, count_b, value_b, rounds_b, trace_b = commit_winner(
        local_best, win
    )
    best_guess = gbi.astype(jnp.int32) * g_local + bi.astype(jnp.int32)
    best_guess = commit_winner(best_guess, win)
    return (sel_b, count_b, value_b, rounds_b, trace_b, value_s,
            best_guess)


def _lattice_dist_runner(obj, cfg: DashConfig, mesh, n_local: int,
                         g_local: int, pod_axis: str, model_axis: str,
                         data_axis: str | None, engine: bool):
    """Jitted pod-lattice executor (cached like :func:`_dist_runner`).

    The traced program: every pod slice runs its ``g_local`` guesses
    through the SAME single-guess body ``dash_distributed`` uses
    (vmapped when g_local > 1; called directly when g_local == 1 so the
    numerics are bitwise those of the per-guess runs), picks its local
    best, and the winner is committed with an ``all_gather`` of per-pod
    best values + replicated argmax + ``psum`` broadcast."""
    run_one = _make_guess_runner(
        obj, cfg, n_local, n_local * mesh.shape[model_axis], model_axis,
        data_axis, engine,
    )

    def run(X_local, keys_l, opts_l, alphas_l):
        if g_local == 1:
            # Bitwise-identical to a dash_distributed run of this guess:
            # no vmap wrapper to perturb the numerics.
            res = run_one(X_local, keys_l[0], opts_l[0], alphas_l[0])
            res = jax.tree_util.tree_map(lambda x: x[None], res)
        else:
            res = jax.vmap(
                lambda kk, g, a: run_one(X_local, kk, g, a)
            )(keys_l, opts_l, alphas_l)
        return _commit_lattice_winner(res, g_local, pod_axis)

    trace_spec = DashTrace(values=P(), alive=P(), filter_iters=P(),
                           est_set_gain=P())
    in_specs = (P(None, model_axis), P(pod_axis), P(pod_axis), P(pod_axis))
    out_specs = (P(model_axis), P(), P(), P(), trace_spec, P(pod_axis), P())
    return cached_runner(
        obj,
        ("lattice_dist", cfg, mesh, n_local, g_local, pod_axis, model_axis,
         data_axis, engine),
        lambda: jax.jit(_shard_mapped(run, mesh, in_specs, out_specs)),
    )


def _lattice_carry_specs(obj, n_local: int, pod_axis: str,
                         model_axis: str) -> SelectionCarry:
    """Per-guess carry specs: the single-guess specs with the lattice's
    leading guess axis sharded over ``pod``."""
    base = _carry_specs(obj, n_local, model_axis)
    return jax.tree_util.tree_map(lambda s: P(pod_axis, *s), base)


def _lattice_step_runner(obj, cfg: DashConfig, mesh, n_local: int,
                         g_local: int, pod_axis: str, model_axis: str,
                         data_axis: str | None, engine: bool, policy):
    """One lattice ROUND: every pod slice advances its ``g_local``
    per-guess carries through the shared round body (vmapped)."""
    def build():
        n_glob = n_local * mesh.shape[model_axis]
        cspecs = _lattice_carry_specs(obj, n_local, pod_axis, model_axis)

        def step(X_local, rho, opts_l, alphas_l, arrived, carry):
            hooks = _make_hooks(
                obj, cfg, X_local, n_glob, model_axis, data_axis, engine,
                arrived=arrived if policy is not None else None,
                policy=policy,
            )
            body = make_round_body(hooks, cfg)
            return jax.vmap(
                lambda c, g, a: body(rho, c, g, a)
            )(carry, opts_l, alphas_l)

        in_specs = (P(None, model_axis), P(), P(pod_axis), P(pod_axis),
                    P(), cspecs)
        return jax.jit(_shard_mapped(step, mesh, in_specs, cspecs))

    return cached_runner(
        obj,
        ("lattice_step", cfg, mesh, n_local, g_local, pod_axis, model_axis,
         data_axis, engine, policy),
        build,
    )


def _lattice_init_runner(obj, cfg: DashConfig, mesh, n_local: int,
                         g_local: int, pod_axis: str, model_axis: str):
    def build():
        cspecs = _lattice_carry_specs(obj, n_local, pod_axis, model_axis)

        def init(X_local, keys_l):
            def one(kk):
                state0, alive0 = _init_state_alive(obj, X_local)
                return initial_carry(cfg, kk, state0, alive0)
            return jax.vmap(one)(keys_l)

        return jax.jit(_shard_mapped(
            init, mesh, (P(None, model_axis), P(pod_axis)), cspecs))

    return cached_runner(
        obj,
        ("lattice_init_carry", cfg, mesh, n_local, g_local, pod_axis,
         model_axis),
        build,
    )


def _lattice_finalize_runner(obj, cfg: DashConfig, mesh, n_local: int,
                             g_local: int, pod_axis: str, model_axis: str):
    def build():
        cspecs = _lattice_carry_specs(obj, n_local, pod_axis, model_axis)

        def fin(carry):
            def one(c):
                (ds, sel_local), _, count, _, trace = c
                rounds = (jnp.sum(trace.filter_iters)
                          + jnp.asarray(cfg.r, jnp.int32))
                return sel_local, count, obj.dist_value(ds), rounds, trace
            res = jax.vmap(one)(carry)
            return _commit_lattice_winner(res, g_local, pod_axis)

        trace_spec = DashTrace(values=P(), alive=P(), filter_iters=P(),
                               est_set_gain=P())
        out_specs = (P(model_axis), P(), P(), P(), trace_spec,
                     P(pod_axis), P())
        return jax.jit(_shard_mapped(fin, mesh, (cspecs,), out_specs))

    return cached_runner(
        obj,
        ("lattice_finalize", cfg, mesh, n_local, g_local, pod_axis,
         model_axis),
        build,
    )


def _dash_auto_distributed_stepped(obj, cfg: DashConfig, keys, opts,
                                   alphas_arr, mesh, g_local: int,
                                   pod_axis: str, model_axis: str,
                                   data_axis: str | None, engine: bool,
                                   resilience: ResilienceConfig | None,
                                   resume, failure_injector):
    """Host-stepped lattice body: snapshot/resume the whole pod sweep."""
    d, n = obj.X.shape
    n_local = n // mesh.shape[model_axis]
    res = resilience if resilience is not None else ResilienceConfig()
    policy = res.resolved_policy() if res.straggler else None
    step = _lattice_step_runner(obj, cfg, mesh, n_local, g_local, pod_axis,
                                model_axis, data_axis, engine, policy)
    init = _lattice_init_runner(obj, cfg, mesh, n_local, g_local, pod_axis,
                                model_axis)
    fin = _lattice_finalize_runner(obj, cfg, mesh, n_local, g_local,
                                   pod_axis, model_axis)
    data_size = mesh.shape[data_axis] if data_axis else 1
    meta = _snapshot_meta("dash_auto_distributed", cfg, n, data_size)
    # The guess→pod layout is part of the key stream: both the lattice
    # width and the pod-axis size must be preserved across a resume.
    meta["n_runs"] = int(opts.shape[0])
    meta["pod_axis_size"] = int(mesh.shape[pod_axis])

    carry, start_round = None, 0
    if resume:
        resume_dir = res.ckpt_dir if resume is True else resume
        restored = _restore_carry(
            resume_dir, _carry_like(init, obj.X, keys),
            _lattice_carry_specs(obj, n_local, pod_axis, model_axis),
            mesh, meta)
        if restored is not None:
            carry, start_round = restored
    if carry is None:
        carry = init(obj.X, keys)

    carry = drive_checkpointed_rounds(
        lambda rho, c, arrived: step(obj.X, rho, opts, alphas_arr,
                                     arrived, c),
        carry, cfg, resilience=resilience, start_round=start_round,
        failure_injector=failure_injector, snapshot_extra=meta,
    )
    sel, nsel, value, rounds, trace, lattice_values, best_guess = fin(carry)
    return LatticeDistResult(
        sel_mask=sel, sel_count=nsel, value=value, rounds=rounds,
        trace=trace, lattice_values=lattice_values, best_guess=best_guess,
    )


def dash_auto_distributed(
    obj, k: int, key, mesh,
    *, eps: float = 0.2, alpha: float = 0.5, r: int = 0,
    n_samples: int = 8, n_guesses: int = 8, trim_frac: float = 0.0,
    alphas=None, pod_axis: str = "pod", model_axis: str = "model",
    data_axis: str | None = "data", use_filter_engine: bool | None = None,
    precision: str | None = None,
    resilience: ResilienceConfig | None = None,
    resume: str | bool | None = None, failure_injector=None,
) -> LatticeDistResult:
    """Distributed DASH over the WHOLE (OPT, α) guess lattice — one
    compiled ``shard_map`` launch instead of ``n_guesses`` sequential
    :func:`dash_distributed` runs.

    The joint guess lattice (``opt_guess_lattice`` × optional
    ``alphas``, OPT-major — the exact grid the single-device batched
    ``dash_auto`` runs) is laid over the leading ``pod`` mesh axis: each
    pod slice receives ``n_guesses_total / pod`` guesses and runs the
    generic ``DistributedObjective`` selection loop over its own
    ``data``/``model`` shards (vmapped when a slice owns more than one
    guess — all of a slice's guesses advance in lockstep, exactly like
    the single-device batched lattice).  The only cross-pod
    communication is the final commit: an ``all_gather`` of the per-pod
    best values (O(pod) scalars), a replicated argmax, and a ``psum``
    that broadcasts the winning guess's solution — no per-guess host
    sync anywhere.

    Requires ``pod_axis`` in the mesh and the total number of joint
    guesses divisible by its size.  Returns :class:`LatticeDistResult`;
    ``lattice_values`` holds every guess's final f(S) in lattice order.

    ``resilience`` / ``resume`` / ``failure_injector`` switch to the
    round-stepped runtime (see :func:`dash_distributed`), which
    snapshots ALL per-guess carries each round; a resume must preserve
    the lattice width, pod-axis size and data-axis size (validated
    against the snapshot manifest) but may change the model-axis width.
    """
    from repro.core.dash import lattice_grid, opt_guess_lattice

    if precision is not None:
        obj = with_precision(obj, precision)
    X = obj.X
    d, n = X.shape
    cfg = DashConfig(k=k, r=r, eps=eps, alpha=alpha, n_samples=n_samples,
                     trim_frac=trim_frac).resolve(n)
    Pp = mesh.shape[pod_axis]
    Pm = mesh.shape[model_axis]
    assert n % Pm == 0, f"pad ground set: n={n} % model={Pm}"
    guesses = opt_guess_lattice(obj, eps, n_guesses, k,
                                top_gain=_top_gain(obj, mesh, model_axis))
    opts, alphas_arr = lattice_grid(
        guesses, [alpha] if alphas is None else alphas
    )
    n_runs = int(opts.shape[0])
    assert n_runs % Pp == 0, (
        f"joint guesses {n_runs} must be divisible by pod axis {Pp}"
    )
    g_local = n_runs // Pp
    keys = jax.random.split(key, n_runs)
    engine = _resolve_engine_flag(obj, use_filter_engine)
    if resilience is not None or resume or failure_injector is not None:
        return _dash_auto_distributed_stepped(
            obj, cfg, keys, opts, alphas_arr, mesh, g_local, pod_axis,
            model_axis, data_axis, engine, resilience, resume,
            failure_injector,
        )
    run_sharded = _lattice_dist_runner(
        obj, cfg, mesh, n // Pm, g_local, pod_axis, model_axis, data_axis,
        engine,
    )
    sel, nsel, value, rounds, trace, lattice_values, best_guess = run_sharded(
        X, keys, opts, alphas_arr
    )
    return LatticeDistResult(
        sel_mask=sel, sel_count=nsel, value=value, rounds=rounds,
        trace=trace, lattice_values=lattice_values, best_guess=best_guess,
    )


def dash_distributed_regression(
    X, y, cfg: DashConfig, key, opt, mesh,
    *, model_axis: str = "model", data_axis: str | None = "data",
    use_filter_engine: bool = True,
):
    """Back-compat wrapper: regression DASH on the generic runner.

    Prefer constructing a ``RegressionObjective`` (with the ``kmax`` you
    want) and calling ``dash_distributed`` directly — this wrapper pins
    ``kmax = cfg.k`` to match the historical behaviour.
    """
    from repro.core.objectives.regression import RegressionObjective

    obj = RegressionObjective(X, y, kmax=cfg.k,
                              use_filter_engine=use_filter_engine)
    return dash_distributed(
        obj, cfg, key, opt, mesh, model_axis=model_axis,
        data_axis=data_axis, use_filter_engine=use_filter_engine,
    )


# ---------------------------------------------------------------------------
# distributed §5 baselines — every competitor on the SAME sharded contract
# ---------------------------------------------------------------------------

class DistSelectResult(NamedTuple):
    """Result of the distributed baseline selectors.  ``values`` is the
    per-pick f(S) trace for the greedy family and empty (shape (0,)) for
    the one-shot TOP-k/RANDOM selectors."""
    sel_mask: jnp.ndarray      # (n,) bool — global (gathered)
    sel_count: jnp.ndarray     # () int32
    value: jnp.ndarray         # () f32
    values: jnp.ndarray        # (k,) trace, or (0,)


def _local_noise_slice(noise, rank, n_local: int):
    """This shard's block of a replicated (n,) noise vector.

    Every shard evaluates the SAME ``round_gumbel`` draw (replicated
    key ⇒ replicated noise) and slices its contiguous column block, so
    globally the sample is bitwise the one the single-device runtime
    draws — the property the parity suite pins down.
    """
    return jax.lax.dynamic_slice(noise, (rank * n_local,), (n_local,))


def _global_topk_commit(scores_l, k_top: int, n_local: int, rank, axis):
    """Global top-``k_top`` of shard-local scores → local view.

    all_gather of each shard's local top-t (t = min(k_top, n_local)),
    replicated re-top-k over the P·t finalists.  ``lax.top_k`` is stable
    and the gather is shard-major, so ties resolve in global index order
    exactly like a single-device top-k over the concatenated vector.
    Returns (idx_local, owned, valid_global) like ``_dist_sample``.
    """
    t = min(k_top, n_local)
    loc_vals, loc_idx = jax.lax.top_k(scores_l, t)
    all_vals = jax.lax.all_gather(loc_vals, axis)           # (P, t)
    all_idx = jax.lax.all_gather(loc_idx, axis)             # (P, t)
    top_vals, top_flat = jax.lax.top_k(all_vals.reshape(-1), k_top)
    top_shard = top_flat // t
    top_local = jnp.take(all_idx.reshape(-1), top_flat)
    valid_global = jnp.isfinite(top_vals)
    owned = (top_shard == rank) & valid_global
    return top_local.astype(jnp.int32), owned, valid_global


def _greedy_runner(obj, k: int, mesh, n_local: int, n: int,
                   model_axis: str, subsample: int | None):
    """Jitted sharded greedy/stochastic-greedy executor (weak-cached per
    objective like the DASH runners).  One adaptive round per pick; the
    collectives per round are one all_gather of per-shard argmax scores
    (+ one for the sample threshold when subsampling) and one psum that
    fetches the winning column."""
    def build():
        from repro.core.greedy import round_gumbel

        def run(X_local, key_rep):
            rank = jax.lax.axis_index(model_axis)
            alive0 = jnp.sum(X_local * X_local, axis=0) > 0

            def body(i, carry):
                ds, sel_local, count, values = carry
                g = jnp.where(
                    sel_local | ~alive0, -jnp.inf,
                    obj.dist_gains(ds, X_local),
                )
                if subsample is not None:
                    # Replicated per-round noise, local slice, global
                    # top-s threshold: the sample is bitwise the one
                    # single-device stochastic_greedy draws.
                    noise_l = _local_noise_slice(
                        round_gumbel(key_rep, i, n), rank, n_local
                    )
                    noise_l = jnp.where(sel_local, -jnp.inf, noise_l)
                    t = min(subsample, n_local)
                    lv = jax.lax.top_k(noise_l, t)[0]
                    av = jax.lax.all_gather(lv, model_axis).reshape(-1)
                    thr = jax.lax.top_k(av, subsample)[0][-1]
                    g = jnp.where(noise_l >= thr, g, -jnp.inf)

                # Global argmax commit: per-shard max → all_gather →
                # replicated argmax (ties resolve to the lowest shard,
                # i.e. the lowest global index — single-device argmax
                # semantics) → one-hot psum fetches the winning column.
                lmax = jnp.max(g)
                larg = jnp.argmax(g)
                allmax = jax.lax.all_gather(lmax, model_axis)   # (P,)
                wshard = jnp.argmax(allmax)
                accept = jnp.isfinite(allmax[wshard]) & (count < k)
                win = (rank == wshard) & accept
                col = jnp.where(win, X_local[:, larg], 0.0)
                C = jax.lax.psum(col, model_axis)[:, None]
                ds = obj.dist_add_set(
                    ds, C, jnp.full((1,), True) & accept, X_local
                )
                sel_local = sel_local.at[
                    jnp.where(win, larg, n_local)
                ].set(True, mode="drop")
                values = values.at[i].set(obj.dist_value(ds))
                return ds, sel_local, count + accept.astype(jnp.int32), values

            ds, sel_local, count, values = jax.lax.fori_loop(
                0, k, body,
                (obj.dist_init(X_local), jnp.zeros((n_local,), bool),
                 jnp.zeros((), jnp.int32), jnp.zeros((k,), jnp.float32)),
            )
            return sel_local, count, obj.dist_value(ds), values

        in_specs = (P(None, model_axis), P())
        out_specs = (P(model_axis), P(), P(), P())
        return jax.jit(_shard_mapped(run, mesh, in_specs, out_specs))

    return cached_runner(
        obj, ("greedy_dist", k, mesh, n_local, model_axis, subsample), build
    )


def _check_sharding(obj, mesh, model_axis: str):
    n = obj.X.shape[1]
    Pm = mesh.shape[model_axis]
    assert n % Pm == 0, f"pad ground set: n={n} % model={Pm}"
    return n, n // Pm


def greedy_distributed(obj, k: int, mesh, *, key=None,
                       model_axis: str = "model") -> DistSelectResult:
    """Parallel SDS_MA on a device mesh — the paper's §5 greedy
    competitor with its per-round gain sweep sharded over ``model_axis``
    through the same ``DistributedObjective`` oracles DASH uses.

    Each of the k rounds runs one shard-local fused gain sweep
    (``dist_gains`` → the ``repro.kernels`` ops wrappers), one
    all_gather/argmax to pick the global best candidate, and one psum to
    fetch its column — greedy's k-round sequential latency is the
    baseline DASH's O(log n) adaptivity beats.  ``key`` is unused
    (greedy is deterministic) and accepted for registry uniformity.
    """
    n, n_local = _check_sharding(obj, mesh, model_axis)
    run = _greedy_runner(obj, int(k), mesh, n_local, n, model_axis, None)
    sel, count, value, values = run(obj.X, jax.random.PRNGKey(0))
    return DistSelectResult(sel, count, value, values)


def stochastic_greedy_distributed(
    obj, k: int, key, mesh, *, subsample: int | None = None,
    eps: float = 0.1, model_axis: str = "model",
) -> DistSelectResult:
    """Distributed stochastic greedy (subsampled argmax SDS_MA).

    Identical noise layout to the single-device ``stochastic_greedy``
    (replicated per-round Gumbel draw, global top-s threshold), so for
    the same ``key`` the two runtimes select bitwise-identical sets —
    the sharding only distributes the gain sweep and the argmax.

    Unlike the single-device twin (which evaluates ``gains_subset`` for
    the s sampled candidates only), each shard here sweeps its full
    local block and masks to the sample: the column-based
    ``DistributedObjective`` contract has no subset oracle, and the
    block sweep IS the shard-parallel design — per-shard work is
    n/P ≥ s/P either way at the mesh sizes this runtime targets.
    """
    from repro.core.greedy import subsample_size

    n, n_local = _check_sharding(obj, mesh, model_axis)
    s = (subsample_size(n, int(k), eps) if subsample is None
         else max(1, min(int(subsample), n)))
    run = _greedy_runner(obj, int(k), mesh, n_local, n, model_axis, s)
    sel, count, value, values = run(obj.X, key)
    return DistSelectResult(sel, count, value, values)


def _oneshot_runner(obj, kk: int, mesh, n_local: int, n: int,
                    model_axis: str, kind: str):
    """Jitted sharded TOP-k / RANDOM executor (weak-cached).  One gain
    sweep (TOP-k only), one all_gather for the global top-k, one psum
    for the column fetch — a single adaptive round."""
    def build():
        from repro.core.estimators import gumbel_noise

        def run(X_local, key_rep):
            rank = jax.lax.axis_index(model_axis)
            alive0 = jnp.sum(X_local * X_local, axis=0) > 0
            ds0 = obj.dist_init(X_local)
            if kind == "topk":
                scores = obj.dist_gains(ds0, X_local)
            else:
                # Same (n,) draw ``sample_set_from_mask`` makes from this
                # key on one device — replicated, then locally sliced.
                scores = _local_noise_slice(
                    gumbel_noise(key_rep, n), rank, n_local
                )
            scores = jnp.where(alive0, scores, -jnp.inf)
            idx_l, owned, validg = _global_topk_commit(
                scores, kk, n_local, rank, model_axis
            )
            C = _dist_gather_columns(X_local, idx_l, owned, model_axis)
            ds = obj.dist_add_set(ds0, C, validg, X_local)
            sel_local = jnp.zeros((n_local,), bool).at[
                jnp.where(owned, idx_l, n_local)
            ].set(True, mode="drop")
            count = jax.lax.psum(
                jnp.sum(owned.astype(jnp.int32)), model_axis
            )
            return sel_local, count, obj.dist_value(ds)

        in_specs = (P(None, model_axis), P())
        out_specs = (P(model_axis), P(), P())
        return jax.jit(_shard_mapped(run, mesh, in_specs, out_specs))

    return cached_runner(
        obj, ("oneshot_dist", kind, kk, mesh, n_local, model_axis), build
    )


def top_k_distributed(obj, k: int, mesh, *, key=None,
                      model_axis: str = "model") -> DistSelectResult:
    """TOP-k on a device mesh: one sharded singleton-gain sweep, one
    all_gather for the global top-k, one psum column fetch.  ``k > n``
    is clamped like the single-device twin; zero (padding) columns are
    excluded before the top-k so they can never burn a slot."""
    n, n_local = _check_sharding(obj, mesh, model_axis)
    kk = min(int(k), n)
    run = _oneshot_runner(obj, kk, mesh, n_local, n, model_axis, "topk")
    sel, count, value = run(obj.X, jax.random.PRNGKey(0))
    return DistSelectResult(sel, count, value, jnp.zeros((0,), jnp.float32))


def random_distributed(obj, k: int, key, mesh, *,
                       model_axis: str = "model") -> DistSelectResult:
    """RANDOM on a device mesh.  The sample is the global top-k of a
    replicated Gumbel draw — bitwise the set single-device
    ``random_select`` commits for the same key (modulo padding columns,
    which are excluded here).  ``sel_count`` reports the committed size;
    it can be < k when fewer than k candidates are alive."""
    n, n_local = _check_sharding(obj, mesh, model_axis)
    kk = min(int(k), n)
    run = _oneshot_runner(obj, kk, mesh, n_local, n, model_axis, "random")
    sel, count, value = run(obj.X, key)
    return DistSelectResult(sel, count, value, jnp.zeros((0,), jnp.float32))


class FastDistResult(NamedTuple):
    """Result of :func:`fast_distributed`.  ``values`` is the per-round
    f(S) trace of the winning OPT probe (0-padded to the static round
    cap); ``opt`` is the OPT guess the in-graph binary search settled
    on."""
    sel_mask: jnp.ndarray      # (n,) bool — global (gathered)
    sel_count: jnp.ndarray     # () int32
    value: jnp.ndarray         # () f32
    rounds: jnp.ndarray        # () int32 — adaptive rounds consumed
    values: jnp.ndarray        # (r_max,) per-round trace
    opt: jnp.ndarray           # () f32 — binary-searched OPT guess


def _fast_dist_runner(obj, k: int, mesh, n_local: int, n: int,
                      model_axis: str, eps: float, r_max: int,
                      n_guesses: int, engine: bool):
    """Jitted sharded FAST executor (weak-cached per objective).

    Mirrors ``core.fast._make_fast_core`` shard-by-shard: the sequence
    draw is the global top-L of a REPLICATED Gumbel vector (the PR-5
    noise layout), so for the same key the drawn sequence — and hence
    the committed set — is bitwise the single-device one.  Collectives
    per round: one all_gather (global sequence draw), one psum (column
    fetch of the ≤ L sequence candidates), and one psum for the prefix
    decision (each shard contributes the insertion-point gains of the
    sequence elements it owns); the L + 1 prefix sweeps between them are
    ONE shard-local fused ``dist_filter_gains_batch`` launch — prefixes
    ride the engine's sample axis, exactly like the single runtime.
    """
    def build():
        from repro.core.fast import (FastResult, binary_search_opt,
                                     prefix_masks, q_cmp)

        L = min(k, n)
        ar = jnp.arange(L)

        def run(X_local, key_rep, guesses_rep):
            rank = jax.lax.axis_index(model_axis)

            def run_core(kk, opt):
                opt = jnp.asarray(opt, jnp.float32)
                ds0 = obj.dist_init(X_local)
                g0 = obj.dist_gains(ds0, X_local)
                # Argmax seed — greedy's bitwise global-argmax commit
                # (per-shard max → all_gather → replicated argmax, ties
                # to the lowest shard = lowest global index), then the
                # ladder opens one rung below the global top singleton
                # gain; the guess only sets the ε·opt/k floor.  See
                # _make_fast_core for why the seed + (1−ε)·max start
                # (rather than a ladder opening AT the max) is what
                # keeps parity off the tied-singleton knife-edge.
                qg0 = q_cmp(g0)
                allmax = jax.lax.all_gather(jnp.max(qg0), model_axis)
                wshard = jnp.argmax(allmax)
                win = rank == wshard
                larg = jnp.argmax(qg0)
                col = jnp.where(win, X_local[:, larg], 0.0)
                C0 = jax.lax.psum(col, model_axis)[:, None]
                ds0 = obj.dist_add_set(
                    ds0, C0, jnp.ones((1,), bool), X_local)
                sel0 = jnp.zeros((n_local,), bool).at[
                    jnp.where(win, larg, n_local)
                ].set(True, mode="drop")
                t0 = (1.0 - eps) * jax.lax.pmax(jnp.max(g0), model_axis)
                t_min = eps * opt / k
                alive0 = (q_cmp(obj.dist_gains(ds0, X_local))
                          >= q_cmp(t0)) & ~sel0

                def cond(c):
                    _, _, _, t, count, _, rho, _ = c
                    return (rho < r_max) & (count < k) & (t >= t_min)

                def body(c):
                    ds, sel, alive, t, count, kk, rho, values = c
                    kk, k_seq = jax.random.split(kk)
                    # Replicated (n,) Gumbel draw, local slice, global
                    # top-L: bitwise the single-device
                    # ``sample_set_from_mask`` sequence.
                    noise_l = _local_noise_slice(
                        gumbel_noise(k_seq, n), rank, n_local)
                    scores_l = jnp.where(alive, noise_l, -jnp.inf)
                    idx_l, owned, validg = _global_topk_commit(
                        scores_l, L, n_local, rank, model_axis)
                    allowed = jnp.clip(k - count, 0, L)
                    slot_ok = validg & (ar < allowed)
                    C = _dist_gather_columns(
                        X_local, idx_l, owned & slot_ok, model_axis)
                    masks = prefix_masks(L) & slot_ok[None, :]
                    if engine:
                        Cs = jnp.broadcast_to(C, (L + 1,) + C.shape)
                        G = obj.dist_filter_gains_batch(ds, Cs, masks,
                                                        X_local)
                    else:
                        G = jax.vmap(
                            lambda m: obj.dist_gains(
                                obj.dist_add_set(ds, C, m, X_local),
                                X_local)
                        )(masks)
                    G = jnp.where(sel[None, :], 0.0, G)
                    # Prefix decision — ONE psum: each shard owns the
                    # insertion-point gains of its sequence elements.
                    marg = jax.lax.psum(
                        jnp.where(owned, G[ar, idx_l], 0.0), model_axis)
                    # Leading run of clears — every committed element
                    # individually certified ≥ t at insertion.
                    clear = slot_ok & (q_cmp(marg) >= q_cmp(t))
                    c_len = jnp.sum(jnp.cumprod(
                        clear.astype(jnp.int32))).astype(jnp.int32)
                    commit = ar < c_len
                    ds = obj.dist_add_set(ds, C, commit, X_local)
                    sel = sel.at[
                        jnp.where(owned & commit, idx_l, n_local)
                    ].set(True, mode="drop")
                    count = count + c_len
                    t = jnp.where(c_len > 0, t, (1.0 - eps) * t)
                    g_c = jnp.take(G, c_len, axis=0)
                    alive = (q_cmp(g_c) >= q_cmp(t)) & ~sel
                    values = values.at[rho].set(obj.dist_value(ds))
                    return ds, sel, alive, t, count, kk, rho + 1, values

                ds, sel, _, _, count, _, rho, values = jax.lax.while_loop(
                    cond, body,
                    (ds0, sel0, alive0, t0,
                     jnp.ones((), jnp.int32), kk,
                     jnp.zeros((), jnp.int32),
                     jnp.zeros((r_max,), jnp.float32)),
                )
                return FastResult(
                    sel_mask=sel, sel_count=count,
                    value=obj.dist_value(ds), rounds=rho, values=values,
                    opt=opt,
                )

            best = binary_search_opt(run_core, key_rep, guesses_rep, eps)
            return (best.sel_mask, best.sel_count, best.value,
                    best.rounds, best.values, best.opt)

        in_specs = (P(None, model_axis), P(), P())
        out_specs = (P(model_axis), P(), P(), P(), P(), P())
        return jax.jit(_shard_mapped(run, mesh, in_specs, out_specs))

    return cached_runner(
        obj, ("fast_dist", k, mesh, n_local, model_axis, eps, r_max,
              n_guesses, engine),
        build,
    )


def fast_distributed(
    obj, k: int, key, mesh, *, eps: float = 0.06, opt=None,
    n_guesses: int = 8, max_rounds: int = 0,
    model_axis: str = "model", use_filter_engine: bool | None = None,
    precision: str | None = None,
) -> FastDistResult:
    """Breuer et al.'s FAST on a device mesh — the distributed twin of
    ``core.fast.fast`` on the same ``DistributedObjective`` contract the
    other baselines use (see docs/fast.md for the collectives table).

    The replicated-Gumbel sequence draw makes the selection bitwise the
    single-device one for the same ``key`` and a pinned ``opt=`` guess
    (the parity lane's configuration); with ``opt=None`` the in-graph
    binary search over the ``n_guesses``-point lattice runs identically
    on both runtimes, replicated across shards.  ``precision="bf16"``
    streams the shard-local kernel operands in bf16 with f32
    accumulation, exactly like the single runtime.
    """
    if precision is not None:
        obj = with_precision(obj, precision)
    n, n_local = _check_sharding(obj, mesh, model_axis)
    k = int(k)
    if k <= 0:
        raise ValueError(f"k must be a positive integer, got {k!r}")
    eps = float(eps)
    if key is None:
        key = jax.random.PRNGKey(0)
    engine = _resolve_engine_flag(obj, use_filter_engine)
    from repro.core.fast import fast_round_cap

    r_max = int(max_rounds) or fast_round_cap(k, eps)
    if opt is not None:
        guesses = jnp.asarray(opt, jnp.float32).reshape(1)
    else:
        from repro.core.dash import opt_guess_lattice

        guesses = opt_guess_lattice(
            obj, eps, n_guesses, k, top_gain=_top_gain(obj, mesh, model_axis))
    run = _fast_dist_runner(obj, k, mesh, n_local, n, model_axis, eps,
                            r_max, int(guesses.shape[0]), engine)
    sel, count, value, rounds, values, opt_used = run(obj.X, key, guesses)
    return FastDistResult(sel, count, value, rounds, values, opt_used)


def pad_ground_set(X, multiple: int):
    """Pad candidate columns with zeros to a multiple (zero columns can
    never be selected: the runner starts them outside the alive set, so
    they are never sampled, and every objective's ``dist_add_set``
    accept rule rejects zero columns as a second line of defence)."""
    d, n = X.shape
    n_pad = (-n) % multiple
    if n_pad == 0:
        return X, n
    return jnp.concatenate([X, jnp.zeros((d, n_pad), X.dtype)], axis=1), n
