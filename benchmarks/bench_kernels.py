"""Kernel micro-benchmarks: Pallas (interpret) validation + the jnp
reference wall-clock (the CPU numbers sanity-check the harness; TPU
numbers come from running the same entry points on device)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, wall_time
from repro.kernels.aopt_gains.ref import aopt_gains_ref
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.logistic_gains.ref import logistic_gains_ref
from repro.kernels.marginal_gains.ref import regression_gains_ref
from repro.utils.compile_cache import enable_compile_cache

RNG = np.random.default_rng(0)


def _bench_filter_pair(tag: str, obj_ps, obj_en, fill: int, m: int,
                       block: int, derived: str):
    """Time ``_estimate_elem_gains`` — the DASH filter statistic — through
    the engine (``obj_en``) and the per-sample vmap path (``obj_ps``) on
    identical state and keys, and emit per_sample/engine/speedup rows.
    """
    from repro.core.dash import DashConfig, _estimate_elem_gains

    n = obj_ps.n
    # part-filled solution: the engine's win is reusing the shared state
    idx = jnp.arange(fill, dtype=jnp.int32)
    state = obj_ps.add_set(obj_ps.init(), idx, jnp.ones(fill, bool))
    alive = jnp.ones((n,), bool) & ~state.sel_mask
    cfg = DashConfig(k=obj_ps.kmax, n_samples=m).resolve(n)
    key = jax.random.PRNGKey(0)
    allowed = jnp.asarray(block)

    def run_with(obj):
        # state passed as an argument so XLA cannot constant-fold the
        # shared-state projections into the compiled executable
        f = jax.jit(lambda st, k: _estimate_elem_gains(
            obj, st, alive, block, allowed, k, cfg))
        return wall_time(lambda: jax.block_until_ready(f(state, key)),
                         warmup=1, iters=3)

    t_ps, est_ps = run_with(obj_ps)
    t_en, est_en = run_with(obj_en)
    err = float(jnp.max(jnp.abs(est_en - est_ps))
                / jnp.maximum(jnp.max(jnp.abs(est_ps)), 1e-12))
    emit(f"kernel/{tag}_per_sample", t_ps * 1e6, derived)
    emit(f"kernel/{tag}_engine", t_en * 1e6, f"{derived};block={block}")
    emit(f"kernel/{tag}_speedup", 0.0,
         f"engine_over_per_sample={t_ps / t_en:.2f}x;max_rel_err={err:.2e}")
    return t_ps, t_en, err


def bench_filter_engine(m: int = 8, d: int = 1024, n: int = 4096,
                        kcap: int = 64, block: int = 8):
    """Regression filter statistic.  The per-sample path pays an
    (m · kcap · d · n) projection GEMM plus a full-width MGS per sample;
    the engine computes the shared-base projection once and only the
    (m · block · d · n) delta projections per sample."""
    from repro.core.objectives import RegressionObjective, normalize_columns

    X = normalize_columns(jnp.asarray(RNG.normal(size=(d, n)), jnp.float32))
    y = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    return _bench_filter_pair(
        "filter_gains",
        RegressionObjective(X, y, kmax=kcap, use_filter_engine=False),
        RegressionObjective(X, y, kmax=kcap, use_filter_engine=True),
        kcap // 2, m, block, f"m={m};d={d};n={n};kcap={kcap}")


def bench_aopt_filter_engine(m: int = 8, d: int = 256, n: int = 2048,
                             block: int = 8):
    """A-optimality filter statistic.  The per-sample path re-factorizes
    M_i and pays two (d, d, n) triangular solves per sample; the engine
    reads the state-cached shared solve plus (m · block · d · n) delta
    GEMMs."""
    from repro.core.objectives import AOptimalityObjective

    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    X = X / jnp.linalg.norm(X, axis=0, keepdims=True)
    kw = dict(kmax=n, beta2=1.0, sigma2=1.0)
    return _bench_filter_pair(
        "aopt_filter",
        AOptimalityObjective(X, use_filter_engine=False, **kw),
        AOptimalityObjective(X, use_filter_engine=True, **kw),
        32, m, block, f"m={m};d={d};n={n}")


def bench_logistic_filter_engine(m: int = 8, d: int = 512, n: int = 2048,
                                 kcap: int = 32, block: int = 4):
    """Logistic filter statistic.  Unlike the regression/A-opt epilogues
    there is no shared GEMM, so on CPU (jnp reference both ways) this is
    a parity check at ~1× — the engine's win is the fused Pallas launch
    streaming X from HBM once for all samples, which only shows on TPU.
    """
    from repro.core.objectives import ClassificationObjective, \
        normalize_columns

    X0 = RNG.normal(size=(d, n))
    X = normalize_columns(jnp.asarray(X0, jnp.float32)) * np.sqrt(d)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5).astype(np.float32))
    return _bench_filter_pair(
        "logistic_filter",
        ClassificationObjective(X, y, kmax=kcap, use_filter_engine=False),
        ClassificationObjective(X, y, kmax=kcap, use_filter_engine=True),
        kcap // 2, m, block, f"m={m};d={d};n={n};kcap={kcap}")


def bench_guess_axis_engine(G: int = 8, m: int = 8, d: int = 512,
                            n: int = 2048, kcap: int = 32, b: int = 8):
    """Folded guess axis: one G·m lattice launch vs G separate m-sample
    launches through the SAME entry points, per epilogue.

    On CPU both sides run the jnp reference, so the row tracks the
    batching/dispatch win of folding (one einsum set over G·m states vs
    G dispatches); on TPU the same entry points compare one fused launch
    streaming X from HBM once against G launches streaming it G times.
    """
    from repro.kernels.filter_gains.ops import (
        aopt_filter_gains,
        filter_gains,
        logistic_filter_gains,
    )

    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)

    # regression: per-guess shared bases + deltas/residuals
    Qs = []
    for _ in range(G):
        Qg, _ = np.linalg.qr(RNG.normal(size=(d, kcap)))
        Qs.append(Qg)
    Q = jnp.asarray(np.stack(Qs), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(G, m, d, b)) * 0.2, jnp.float32)
    R = jnp.asarray(RNG.normal(size=(G, m, d)), jnp.float32)
    fold = jax.jit(lambda Q, D, R: filter_gains(X, Q, D, R, csq))
    per = jax.jit(lambda Q, D, R: filter_gains(X, Q, D, R, csq))

    def sweep(Q, D, R):
        return jnp.stack([per(Q[g], D[g], R[g]) for g in range(G)])

    t_f, _ = wall_time(lambda: jax.block_until_ready(fold(Q, D, R)))
    t_p, _ = wall_time(lambda: jax.block_until_ready(sweep(Q, D, R)))
    derived = f"G={G};m={m};d={d};n={n};kcap={kcap}"
    emit("kernel/guess_axis_filter_folded", t_f * 1e6, derived)
    emit("kernel/guess_axis_filter_per_guess", t_p * 1e6, derived)
    emit("kernel/guess_axis_filter_speedup", 0.0,
         f"folded_over_per_guess={t_p / t_f:.2f}x")

    # A-optimality: per-guess shared solves + Woodbury factors
    W = jnp.asarray(RNG.normal(size=(G, d, n)), jnp.float32)
    E = jnp.asarray(RNG.normal(size=(G, m, d, b)) * 0.3, jnp.float32)
    F = jnp.einsum("gmdb,gmdc->gmbc", E, E)
    fold_a = jax.jit(lambda W, E, F: aopt_filter_gains(X, W, E, F, 1.0))
    per_a = jax.jit(lambda W, E, F: aopt_filter_gains(X, W, E, F, 1.0))

    def sweep_a(W, E, F):
        return jnp.stack([per_a(W[g], E[g], F[g]) for g in range(G)])

    t_f, _ = wall_time(lambda: jax.block_until_ready(fold_a(W, E, F)))
    t_p, _ = wall_time(lambda: jax.block_until_ready(sweep_a(W, E, F)))
    emit("kernel/guess_axis_aopt_folded", t_f * 1e6,
         f"G={G};m={m};d={d};n={n}")
    emit("kernel/guess_axis_aopt_per_guess", t_p * 1e6,
         f"G={G};m={m};d={d};n={n}")
    emit("kernel/guess_axis_aopt_speedup", 0.0,
         f"folded_over_per_guess={t_p / t_f:.2f}x")

    # logistic: per-guess refit logits (folded to G·m samples)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5).astype(np.float32))
    etas = jnp.asarray(RNG.normal(size=(G, m, d)) * 0.4, jnp.float32)
    fold_l = jax.jit(lambda e: logistic_filter_gains(X, y, e, steps=3))
    per_l = jax.jit(lambda e: logistic_filter_gains(X, y, e, steps=3))

    def sweep_l(etas):
        return jnp.stack([per_l(etas[g]) for g in range(G)])

    t_f, _ = wall_time(lambda: jax.block_until_ready(fold_l(etas)))
    t_p, _ = wall_time(lambda: jax.block_until_ready(sweep_l(etas)))
    emit("kernel/guess_axis_logistic_folded", t_f * 1e6,
         f"G={G};m={m};d={d};n={n}")
    emit("kernel/guess_axis_logistic_per_guess", t_p * 1e6,
         f"G={G};m={m};d={d};n={n}")
    emit("kernel/guess_axis_logistic_speedup", 0.0,
         f"folded_over_per_guess={t_p / t_f:.2f}x")


def bench_kernel_precisions(autotune: bool = False):
    """The ``kernels/*`` precision lane: every ops wrapper timed at f32
    and bf16 streaming, annotated against the roofline models.

    Three rows per kernel: ``kernels/<name>/f32`` and ``/bf16`` carry
    the measured µs plus the model-derived GB/s, arithmetic intensity
    and roofline fraction (``bench_roofline.kernel_model``);
    ``/bf16_over_f32`` carries the speedup ratio and the bf16-vs-f32
    max relative output error.  ``autotune=True`` first drives the
    persistent block autotuner (``repro.kernels.tuning``) through the
    same wrappers, so the timed rows run at the measured-winner block;
    otherwise the wrappers' cached-or-heuristic choice is timed as-is.
    On CPU the wrappers route to the jnp reference (quantized
    identically), so the rows track the precision policy's numerics;
    the bandwidth columns are meaningful on TPU runs (the artifact
    records the backend).
    """
    from benchmarks import bench_roofline as roofline
    from repro.kernels import tuning
    from repro.kernels.aopt_gains.ops import aopt_gains
    from repro.kernels.filter_gains.ops import (
        aopt_filter_gains,
        filter_gains,
        logistic_filter_gains,
    )
    from repro.kernels.logistic_gains.ops import logistic_gains
    from repro.kernels.marginal_gains.ops import regression_gains

    d, n = 512, 4096
    k, b, m, g, steps = 64, 8, 8, 1, 3
    # Structurally valid operands (orthonormal bases, a genuine shared
    # solve): the epilogues divide by residual norms, so random garbage
    # would make the bf16-vs-f32 error column track conditioning noise
    # instead of the precision policy.
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)
    Q, _ = jnp.linalg.qr(jnp.asarray(RNG.normal(size=(d, k)), jnp.float32))
    resid = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    resid = resid - Q @ (Q.T @ resid)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5).astype(np.float32))
    eta = jnp.zeros((d,), jnp.float32)
    D0 = jnp.asarray(RNG.normal(size=(m, d, b)), jnp.float32)
    D0 = D0 - Q @ jnp.einsum("dk,mdb->mkb", Q, D0)     # ⊥ shared basis
    D = jnp.linalg.qr(D0)[0]
    R = jnp.asarray(RNG.normal(size=(m, d)), jnp.float32)
    R = R - (R @ Q) @ Q.T
    sel = RNG.choice(n, size=32, replace=False)
    Xn = np.asarray(X)
    M = np.eye(d) + Xn[:, sel] @ Xn[:, sel].T          # A-opt information
    W = jnp.asarray(np.linalg.solve(M, Xn), jnp.float32)
    Es = []
    for i in range(m):                                 # genuine Woodbury
        C = Xn[:, RNG.choice(n, size=b, replace=False)]
        P = np.linalg.solve(M, C)
        Lk = np.linalg.cholesky(np.eye(b) + C.T @ P)
        Es.append(np.linalg.solve(Lk, P.T).T)          # E = P L⁻ᵀ
    E = jnp.asarray(np.stack(Es), jnp.float32)
    F = jnp.einsum("mdb,mdc->mbc", E, E)
    etas = jnp.asarray(RNG.normal(size=(m, d)) * 0.4, jnp.float32)

    # Operands ride as jit ARGUMENTS: closing over them would let XLA
    # constant-fold the whole kernel at compile time and the timed call
    # would fetch a precomputed constant.
    groups = [
        ("regression_gains", {"d": d, "k": k, "n": n}, (X, Q, resid, csq),
         lambda p, bn: jax.jit(lambda *a: regression_gains(
             *a, precision=p, block_n=bn))),
        ("aopt_gains", {"d": d, "n": n}, (X, W),
         lambda p, bn: jax.jit(lambda *a: aopt_gains(
             *a, 1.0, precision=p, block_n=bn))),
        ("logistic_gains", {"d": d, "n": n, "steps": steps}, (X, y, eta),
         lambda p, bn: jax.jit(lambda *a: logistic_gains(
             *a, steps=steps, precision=p, block_n=bn))),
        ("filter_gains", {"d": d, "k": k, "b": b, "m": m, "g": g, "n": n},
         (X, Q, D, R, csq),
         lambda p, bn: jax.jit(lambda *a: filter_gains(
             *a, precision=p, block_n=bn))),
        ("aopt_filter_gains", {"d": d, "b": b, "m": m, "g": g, "n": n},
         (X, W, E, F),
         lambda p, bn: jax.jit(lambda *a: aopt_filter_gains(
             *a, 1.0, precision=p, block_n=bn))),
        ("logistic_filter_gains",
         {"d": d, "m": m, "g": g, "n": n, "steps": steps}, (X, y, etas),
         lambda p, bn: jax.jit(lambda *a: logistic_filter_gains(
             *a, steps=steps, precision=p, block_n=bn))),
    ]

    for name, dims, arrs, make in groups:
        timed = {}
        for prec in ("f32", "bf16"):
            model = roofline.kernel_model(name, dims, prec)
            if autotune:
                bn = tuning.autotune(
                    name, prec, model["tuning_dims"],
                    lambda cand: make(prec, cand)(*arrs), model["vmem"],
                )
            else:
                bn = tuning.tuned_block_n(
                    name, prec, model["tuning_dims"], model["vmem"],
                )
            f = make(prec, bn)
            t, out = wall_time(lambda: jax.block_until_ready(f(*arrs)))
            model = roofline.kernel_model(name, dims, prec, block_n=bn)
            pt = roofline.roofline_point(model["flops"], model["bytes"], t)
            dim_str = ";".join(f"{kk}={vv}" for kk, vv in dims.items())
            emit(
                f"kernels/{name}/{prec}", t * 1e6,
                f"{dim_str};block={bn};ai={pt['ai']:.2f};"
                f"gbps={pt['gbps']:.2f};tflops={pt['tflops']:.4f};"
                f"roofline_frac={pt['roofline_frac']:.4f}",
            )
            timed[prec] = (t, out)
        t32, o32 = timed["f32"]
        t16, o16 = timed["bf16"]
        err = float(jnp.max(jnp.abs(o16 - o32))
                    / jnp.maximum(jnp.max(jnp.abs(o32)), 1e-12))
        emit(f"kernels/{name}/bf16_over_f32", 0.0,
             f"ratio={t32 / t16:.2f}x;max_rel_err={err:.2e}")


def run(autotune: bool = False):
    # marginal gains — the DASH per-round oracle
    d, n, k = 512, 2048, 64
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    Q, _ = jnp.linalg.qr(jnp.asarray(RNG.normal(size=(d, k)), jnp.float32))
    r = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)
    f = jax.jit(lambda: regression_gains_ref(X, Q, r, csq))
    t, _ = wall_time(f)
    flops = 2 * d * n * (k + 1)
    emit("kernel/marginal_gains_ref", t * 1e6,
         f"d={d};n={n};k={k};gflops={flops / t / 1e9:.1f}")

    # A-opt gains
    W = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    f = jax.jit(lambda: aopt_gains_ref(X, W, 1.0))
    t, _ = wall_time(f)
    emit("kernel/aopt_gains_ref", t * 1e6, f"d={d};n={n}")

    # logistic gains (3-step Newton)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5).astype(np.float32))
    eta = jnp.zeros((d,), jnp.float32)
    f = jax.jit(lambda: logistic_gains_ref(X, y, eta, steps=3))
    t, _ = wall_time(f)
    emit("kernel/logistic_gains_ref", t * 1e6, f"d={d};n={n};steps=3")

    # sample-batched filter engine — the DASH inner-loop hot-spot,
    # one epilogue per objective
    bench_filter_engine()
    bench_aopt_filter_engine()
    bench_logistic_filter_engine()

    # folded guess axis — the whole (OPT, α) lattice in one launch
    bench_guess_axis_engine()

    # mixed-precision lane: f32 vs bf16 streaming against the roofline
    bench_kernel_precisions(autotune=autotune)

    # flash attention
    b, s, h, hkv, dh = 1, 1024, 8, 2, 64
    q = jnp.asarray(RNG.normal(size=(b, s, h, dh)), jnp.bfloat16)
    kk = jnp.asarray(RNG.normal(size=(b, s, hkv, dh)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, dh)), jnp.bfloat16)
    f = jax.jit(lambda: flash_attention_ref(q, kk, v, causal=True))
    t, _ = wall_time(f)
    aflops = 4 * b * s * s * h * dh / 2   # causal halves the work
    emit("kernel/flash_attention_ref", t * 1e6,
         f"s={s};h={h};gflops={aflops / t / 1e9:.1f}")


def main() -> None:
    enable_compile_cache()
    import argparse
    import json

    from benchmarks.common import rows

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--json", nargs="?", const="BENCH_kernels.json", default=None,
        metavar="PATH",
        help="also write the emitted rows as a JSON trajectory artifact "
             "(default path: BENCH_kernels.json)",
    )
    ap.add_argument(
        "--autotune", action="store_true",
        help="measure block-size candidates through the wrappers and "
             "persist the winners (repro.kernels.tuning cache) before "
             "timing the kernels/* rows",
    )
    args = ap.parse_args()
    run(autotune=args.autotune)
    if args.json:
        payload = {"suite": "bench_kernels",
                   "backend": jax.default_backend(), "rows": rows()}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
