#!/usr/bin/env python3
"""Bring-up smoke of ``select()`` on a TPU at deployment size.

    python3 chip_smoke.py [--seed N]            # one chip
    python3 chip_smoke.py --chips 4 [--seed N]  # the sharded path, 4 chips

One chip drives the main path once through the entry points a user
calls, on planted-support problems generated on the device from
``--seed`` (the D1 / D1-design / D3 protocols of ``data/synthetic.py``):

* regression feature selection (Cor. 7), d=1024 × n=2²⁰, k=200 —
  ``select("greedy")`` as the quality reference, ``select("dash")`` and
  ``select("fast")``, then a ``SelectionServer`` answering dash and fast
  requests on the same dataset;
* A-optimal design (Cor. 9), d=512 × n=2¹⁹, k=100, ``select("dash")``;
* logistic feature selection (Cor. 8), d=1024 × n=2¹⁹, k=100,
  ``select("dash")``.

Each phase prints one ``PHASE {...}`` JSON line: sizes, compile and run
seconds, the device's ``peak_bytes_in_use`` so far, and its checks —
each Pallas kernel against its jnp reference, every compiled program
holding a Pallas kernel (``tpu_custom_call``), every selected set's
f(S) recomputed by a plain f32 ``jax.numpy`` implementation written
here, |S| = k (FAST: ≤ k), and DASH/FAST against the greedy floor.
``--chips 4`` runs only the sharded path: regression at n=2²² sharded
four ways over the model axis of a (pod, data, model) = (1, 1, 4) mesh
(DASH at k=200, FAST at k=50), and a bitwise parity check of sharded
against single-device FAST at n=2²⁰.

The last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero and prints no result.  It runs in one process:
the process that touches JAX holds the chip.  Compiled programs persist
in ``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache/``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Deployments.  A-optimal design runs at n=2¹⁹ with a 4-guess lattice:
# every guess carries its own W = M⁻¹X, and the v5e compiler reports the
# 8-guess lattice needing 32.6 GiB at n=2²⁰ and 16.1 GiB of 15.75 GiB at
# n=2¹⁹ (X may not go below 1 GiB), so the lattice is what gives.
# The planted supports hold 2k features, so a correct selection has k
# candidates of real signal to commit and |S| = k is a fair check.
REGRESSION = dict(d=1024, n=1 << 20, k=200, support=400)
AOPT = dict(d=512, n=1 << 19, k=100, n_guesses=4)
LOGISTIC = dict(d=1024, n=1 << 19, k=100, support=200)
SHARDED = dict(d=1024, n=1 << 22, k=200, fast_k=50, support=400,
               parity_n=1 << 20, parity_eps=0.2)
# FAST scores all k + 1 insertion prefixes of a sequence every round, so
# a round at k = 200 projects X onto ~200 columns 201 times (~1.5 s per
# round on v5e).  ε = 0.5 and a two-point lattice make one probe, at the
# lowest OPT guess (the deepest floor ε·OPT/k); the defaults (ε = 0.06,
# 8 guesses) run three probes of up to 333 rounds each.  FAST stops at
# its floor by design, so its |S| may fall short of k.  The sharded run
# and its parity check take FAST at k = 50, where a round costs 1/16.
FAST_OPTS = dict(eps=0.5, n_guesses=2)
SERVE_FAST_K = 20           # the service's fast requests run its defaults
SAMPLES = 8                 # DASH's Monte-Carlo samples (its default)
PARITY_N = 1 << 16          # candidate columns in the kernel-vs-ref checks
VALUE_RTOL = 1e-2           # reported f(S) against the plain f32 recompute
QUALITY_FLOOR = 0.4         # DASH/FAST ≥ this × greedy (tests/test_dash.py)
TIME_BUDGET_S = 1000.0      # later phases are skipped (and fail) past this
ALGO_TAGS = {"greedy": 10, "dash": 11, "fast": 12}   # key = fold_in(seed, tag)


def load_repro():
    """Import the program from this checkout, and only from it."""
    sys.path.insert(0, SRC)
    import repro

    if not os.path.abspath(repro.__file__).startswith(SRC + os.sep):
        raise ImportError(f"repro came from {repro.__file__}, not {SRC}")
    return repro


def on_tpu(jax) -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# data, generated on the device from the seed
# ---------------------------------------------------------------------------

def make_generators(jax, jnp):
    """Plain generator functions; callers jit them with the sizes static
    (and, on a mesh, with the output sharding)."""

    def correlated(key, d, n, rho, common_axis):
        """One-factor correlated normals (``synthetic._correlated_normal``)
        with the shared factor per row (axis 1) or per column (axis 0)."""
        kc, ke = jax.random.split(key)
        shape = (d, 1) if common_axis == 1 else (1, n)
        return (math.sqrt(rho) * jax.random.normal(kc, shape)
                + math.sqrt(1.0 - rho) * jax.random.normal(ke, (d, n)))

    def unit_columns(X, center):
        if center:
            X = X - jnp.mean(X, axis=0, keepdims=True)
        return X / jnp.linalg.norm(X, axis=0, keepdims=True)

    def d1_regression(key, d, n, support):
        """D1: correlated features (ρ = 0.4), β ~ U(−2, 2) on a planted
        support, noise 0.1; centered unit-norm columns."""
        kx, ks, kb, kn = jax.random.split(key, 4)
        X = correlated(kx, d, n, 0.4, 1)
        sup = jax.random.choice(ks, n, (support,), replace=False)
        beta = jax.random.uniform(kb, (support,), minval=-2.0, maxval=2.0)
        y = X[:, sup] @ beta + 0.1 * jax.random.normal(kn, (d,))
        return unit_columns(X, True), y

    def d1_design(key, d, n):
        """D1-design: stimuli with correlated coordinates (ρ = 0.8),
        each candidate column ℓ2-normalized."""
        return unit_columns(correlated(key, d, n, 0.8, 0), False)

    def d3_classification(key, d, n, support):
        """D3 with Bernoulli labels (the paper thresholds at p = ½, which
        makes a planted support separable and the MLE infinite); columns
        scaled to norm √d as in ``make_d3_classification``."""
        kx, ks, kb, ky = jax.random.split(key, 4)
        X = correlated(kx, d, n, 0.4, 1)
        sup = jax.random.choice(ks, n, (support,), replace=False)
        beta = jax.random.uniform(kb, (support,), minval=-2.0, maxval=2.0)
        p = jax.nn.sigmoid(X[:, sup] @ beta)
        y = jax.random.bernoulli(ky, p).astype(jnp.float32)
        return unit_columns(X, True) * math.sqrt(d), y

    return d1_regression, d1_design, d3_classification


# ---------------------------------------------------------------------------
# plain f32 references of f(S), independent of the objectives under test
# ---------------------------------------------------------------------------

def make_references(jax, jnp):
    hi = "highest"

    @jax.jit
    def regression_value(X, y, idx):
        """‖proj_{span X_S} y‖² / ‖y‖²."""
        with jax.default_matmul_precision(hi):
            q, _ = jnp.linalg.qr(jnp.take(X, idx, axis=1))
            p = q.T @ y
            return jnp.sum(p * p) / jnp.sum(y * y)

    @jax.jit
    def aopt_value(X, idx):
        """Tr(I) − Tr((I + X_S X_Sᵀ)⁻¹)  (β² = σ² = 1)."""
        with jax.default_matmul_precision(hi):
            Xs = jnp.take(X, idx, axis=1)
            d = X.shape[0]
            L = jnp.linalg.cholesky(jnp.eye(d) + Xs @ Xs.T)
            Z = jax.scipy.linalg.solve_triangular(L, jnp.eye(d), lower=True)
            return d - jnp.sum(Z * Z)

    @jax.jit
    def logistic_value(X, y, idx):
        """max_w ℓ(y, X_S w) − ℓ(y, 0): 50 damped Newton steps with the
        objective's ridge (1e-4)."""
        def loglik(eta):
            return jnp.sum(y * eta - jax.nn.softplus(eta))

        with jax.default_matmul_precision(hi):
            Xs = jnp.take(X, idx, axis=1)
            k = Xs.shape[1]

            def step(_, w):
                p = jax.nn.sigmoid(Xs @ w)
                g = Xs.T @ (y - p)
                H = Xs.T @ (Xs * (p * (1.0 - p) + 1e-6)[:, None])
                delta = jnp.linalg.solve(H + 1e-4 * jnp.eye(k), g)
                deta = Xs @ delta
                return w + jnp.minimum(
                    1.0, 4.0 / jnp.maximum(jnp.max(jnp.abs(deta)), 1e-9)
                ) * delta

            w = jax.lax.fori_loop(0, 50, step, jnp.zeros((k,)))
            return loglik(Xs @ w) - loglik(jnp.zeros_like(y))

    return regression_value, aopt_value, logistic_value


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, jax, jnp, np, seed: int):
        self.jax, self.jnp, self.np = jax, jnp, np
        self.key = jax.random.PRNGKey(seed)
        self.t0 = time.perf_counter()
        self.ok = True

    def fold(self, tag: int):
        return self.jax.random.fold_in(self.key, tag)

    def memory(self) -> dict:
        """Peak and live device bytes, the largest over the devices."""
        stats = [d.memory_stats() or {} for d in self.jax.devices()]
        return {f: max(int(s.get(f, 0)) for s in stats)
                for f in ("peak_bytes_in_use", "bytes_in_use")}

    def progress(self, what: str):
        """A step on stderr, so that a run that hangs shows where."""
        print(f"[{time.perf_counter() - self.t0:7.1f}s] {what}",
              file=sys.stderr, flush=True)

    def phase(self, name: str, fn):
        """Run one phase; print its line; any exception fails it."""
        rec = {"phase": name}
        if time.perf_counter() - self.t0 > TIME_BUDGET_S:
            rec.update(ok=False, error="skipped: time budget spent")
        else:
            self.progress(f"{name}: start")
            t = time.perf_counter()
            try:
                rec.update(fn())
                rec["ok"] = all(rec.get("checks", {"ran": True}).values())
            except Exception as e:      # a phase that raises has failed
                traceback.print_exc()
                rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:500])
            rec["phase_s"] = time.perf_counter() - t
            rec.update(self.memory())
        self.ok &= bool(rec["ok"])
        print("PHASE " + json.dumps(rec, default=float), flush=True)
        return rec

    # -- helpers -----------------------------------------------------------
    def compile(self, fn, *args):
        """AOT-compile ``jit(fn)`` for ``args``: (compiled, seconds, info)."""
        t = time.perf_counter()
        compiled = self.jax.jit(fn).lower(*args).compile()
        seconds = time.perf_counter() - t
        self.progress(f"compiled in {seconds:.1f}s")
        mem = compiled.memory_analysis()
        info = {"kernel_in_program": "tpu_custom_call" in compiled.as_text()}
        if mem is not None:
            info["program_bytes"] = int(mem.argument_size_in_bytes
                                        + mem.output_size_in_bytes
                                        + mem.temp_size_in_bytes)
        return compiled, seconds, info

    def run(self, compiled, *args):
        t = time.perf_counter()
        out = self.jax.block_until_ready(compiled(*args))
        self.progress(f"ran in {time.perf_counter() - t:.1f}s")
        return out, time.perf_counter() - t

    def selected(self, sel_mask):
        return self.np.flatnonzero(self.np.asarray(sel_mask))

    def check_selection(self, out, k, ref_value, checks, tag, exact_k=True):
        """|S| = k (FAST: |S| ≤ k) with no duplicates and ``sel_count``
        equal to |S|, and f(S) against the plain ref."""
        sel_mask, sel_count, value = out
        idx = self.selected(sel_mask)
        ref = float(ref_value(idx))
        value = float(value)
        size_ok = len(idx) == k if exact_k else 0 < len(idx) <= k
        checks[f"{tag}_count_is_{'k' if exact_k else 'at_most_k'}"] = (
            size_ok and int(sel_count) == len(idx)
            and len(set(idx.tolist())) == len(idx))
        checks[f"{tag}_value_matches_ref"] = (
            abs(value - ref) <= VALUE_RTOL * max(abs(ref), 1e-6))
        return {"value": value, "ref_value": ref, "sel_count": int(sel_count)}

    def select_phase(self, algo, obj, k, ref_value, mesh=None, exact_k=None,
                     **opts):
        """Compile and run ``select(algo, obj, k, key)`` at full size.
        ``exact_k`` (default: all but FAST) asks for |S| = k, else
        |S| ≤ k."""
        from repro.core import select

        def program(o, kk):
            r = select(algo, o, k, kk, mesh=mesh, **opts)
            rounds = getattr(r.raw, "rounds", None)
            return (r.sel_mask, r.sel_count, r.value,
                    self.jnp.int32(-1) if rounds is None else rounds)

        key = self.fold(ALGO_TAGS[algo])
        compiled, compile_s, info = self.compile(program, obj, key)
        out, run_s = self.run(compiled, obj, key)
        checks = {"kernel_in_program": info.pop("kernel_in_program")}
        if exact_k is None:
            exact_k = algo != "fast"
        res = self.check_selection(out[:3], k, ref_value, checks, algo,
                                   exact_k)
        rec = {"algo": algo, "opts": opts, "compile_s": compile_s,
               "run_s": run_s, **info, **res, "checks": checks}
        if int(out[3]) >= 0:
            rec["rounds"] = int(out[3])
        return rec

    def kernel_phase(self, cases):
        """Each kernel wrapper (compiled Pallas on the TPU) against its
        jnp reference computed at full f32 (``highest``).

        On the TPU an f32 matmul at default precision, in a Pallas kernel
        as in XLA, takes one bf16 pass through the MXU, so the f32
        kernel is held to the bf16 budget of ``STREAM_PARITY_TOL``:
        max |kernel − ref| over max |ref|.  The error against the ref at
        default precision is reported beside it."""
        from repro.kernels.common import STREAM_PARITY_TOL

        tol = STREAM_PARITY_TOL["bf16"]["vs_f32"]
        checks, detail, compile_s, run_s = {}, {}, 0.0, 0.0
        np = self.np
        for name, kernel, ref, args in cases:
            compiled, c_s, info = self.compile(kernel, *args)
            got, r_s = self.run(compiled, *args)
            compile_s, run_s = compile_s + c_s, run_s + r_s
            with self.jax.default_matmul_precision("highest"):
                want = np.asarray(self.jax.jit(ref)(*args))
            same_prec = np.asarray(self.jax.jit(ref)(*args))
            got = np.asarray(got)
            scale = max(float(np.max(np.abs(want))), 1e-30)
            err = float(np.max(np.abs(got - want))) / scale
            detail[name] = {
                "max_abs_err_over_max_ref": err,
                "max_abs_err_vs_default_precision_ref_over_max_ref":
                    float(np.max(np.abs(got - same_prec))) / scale,
                "run_s": r_s}
            checks[f"{name}_matches_f32_ref"] = bool(err <= tol)
            checks[f"{name}_is_pallas"] = info["kernel_in_program"]
        return {"tol": tol, "kernels": detail, "compile_s": compile_s,
                "run_s": run_s, "checks": checks}


def one_chip(s: Smoke):
    jax, jnp = s.jax, s.jnp
    from repro.core import (
        AOptimalityObjective,
        ClassificationObjective,
        RegressionObjective,
    )
    from repro.core.selection_loop import DashConfig
    from repro.kernels import operands
    from repro.kernels.aopt_gains.ops import aopt_gains
    from repro.kernels.aopt_gains.ref import aopt_gains_ref
    from repro.kernels.filter_gains.ops import (
        aopt_filter_gains,
        filter_gains,
        logistic_filter_gains,
    )
    from repro.kernels.filter_gains.ref import (
        aopt_filter_gains_ref,
        filter_gains_ref,
        logistic_filter_gains_ref,
    )
    from repro.kernels.logistic_gains.ops import logistic_gains
    from repro.kernels.logistic_gains.ref import logistic_gains_ref
    from repro.kernels.marginal_gains.ops import regression_gains
    from repro.kernels.marginal_gains.ref import regression_gains_ref

    d1_regression, d1_design, d3_classification = (
        jax.jit(f, static_argnums=(1, 2, 3)[:nargs])
        for f, nargs in zip(make_generators(jax, jnp), (3, 2, 3)))
    reg_ref, aopt_ref, log_ref = make_references(jax, jnp)

    def block(n, k):
        return DashConfig(k=k).resolve(n).block

    # -- regression ---------------------------------------------------------
    c = REGRESSION
    d, n, k = c["d"], c["n"], c["k"]
    b = block(n, k)

    def reg_kernels():
        X, Q, r, csq, D, R = operands.regression_operands(
            s.fold(1), d, PARITY_N, k, SAMPLES, b)
        out = s.kernel_phase([
            ("regression_gains", regression_gains, regression_gains_ref,
             (X, Q, r, csq)),
            ("filter_gains", filter_gains, filter_gains_ref,
             (X, Q, D, R, csq)),
        ])
        return {"sizes": dict(d=d, n=PARITY_N, k=k, m=SAMPLES, b=b), **out}

    s.phase("kernels/regression", reg_kernels)
    X, y = d1_regression(s.fold(2), d, n, c["support"])
    obj = RegressionObjective(X, y, kmax=k, use_kernel=True)
    ref = lambda idx: reg_ref(X, y, idx)
    sizes = {"d": d, "n": n, "k": k, "X_bytes": 4 * d * n}
    values = {}
    for algo, opts in (("greedy", {}), ("dash", {}), ("fast", FAST_OPTS)):
        rec = s.phase(f"regression/{algo}", lambda: {
            "sizes": sizes, **s.select_phase(algo, obj, k, ref, **opts)})
        values[algo] = rec.get("value")

    def floor():
        g = values["greedy"]
        checks = {f"{a}_at_least_{QUALITY_FLOOR}_greedy":
                  values[a] is not None and g is not None
                  and values[a] >= QUALITY_FLOOR * g for a in ("dash", "fast")}
        return {"values": values, "checks": checks}

    s.phase("regression/quality_floor", floor)
    s.phase("regression/service", lambda: service(s, X, y, k, ref))
    del obj, X, y, ref
    gc.collect()

    # -- A-optimal design ---------------------------------------------------
    c = AOPT
    d, n, k = c["d"], c["n"], c["k"]
    b = block(n, k)

    def aopt_kernels():
        X, W, E, F = operands.aopt_operands(s.fold(3), d, PARITY_N, SAMPLES, b)
        out = s.kernel_phase([
            ("aopt_gains", lambda X, W: aopt_gains(X, W, 1.0),
             lambda X, W: aopt_gains_ref(X, W, 1.0), (X, W)),
            ("aopt_filter_gains",
             lambda X, W, E, F: aopt_filter_gains(X, W, E, F, 1.0),
             lambda X, W, E, F: aopt_filter_gains_ref(X, W, E, F, 1.0),
             (X, W, E, F)),
        ])
        return {"sizes": dict(d=d, n=PARITY_N, m=SAMPLES, b=b), **out}

    s.phase("kernels/aopt", aopt_kernels)
    X = d1_design(s.fold(4), d, n)
    obj = AOptimalityObjective(X, kmax=k, use_kernel=True)
    s.phase("aopt/dash", lambda: {
        "sizes": {"d": d, "n": n, "k": k, "X_bytes": 4 * d * n},
        **s.select_phase("dash", obj, k, lambda idx: aopt_ref(X, idx),
                         n_guesses=c["n_guesses"])})
    del obj, X
    gc.collect()

    # -- logistic -----------------------------------------------------------
    c = LOGISTIC
    d, n, k = c["d"], c["n"], c["k"]

    def log_kernels():
        X, yl, eta, etas = operands.logistic_operands(
            s.fold(5), d, PARITY_N, SAMPLES)
        out = s.kernel_phase([
            ("logistic_gains", logistic_gains, logistic_gains_ref,
             (X, yl, eta)),
            ("logistic_filter_gains", logistic_filter_gains,
             logistic_filter_gains_ref, (X, yl, etas)),
        ])
        return {"sizes": dict(d=d, n=PARITY_N, m=SAMPLES), **out}

    s.phase("kernels/logistic", log_kernels)
    X, y = d3_classification(s.fold(6), d, n, c["support"])
    obj = ClassificationObjective(X, y, kmax=k, use_kernel=True)
    s.phase("logistic/dash", lambda: {
        "sizes": {"d": d, "n": n, "k": k, "X_bytes": 4 * d * n},
        **s.select_phase("dash", obj, k, lambda idx: log_ref(X, y, idx))})
    del obj, X, y


def service(s: Smoke, X, y, k, ref):
    """A ``SelectionServer`` with the regression dataset registered
    answers two dash requests at k and two fast requests at
    ``SERVE_FAST_K``, twice: the first pass compiles, the second is
    warm."""
    from repro.serve import OK, SelectionServer, SelectRequest
    from repro.serve.degradation import DegradationLadder

    server = SelectionServer(ladder=DegradationLadder(
        ("dash", "fast", "stochastic_greedy", "topk")))
    t = time.perf_counter()
    server.register("d1", "regression", X, y, kmax=k, use_kernel=True)
    register_s = time.perf_counter() - t
    reqs = [SelectRequest("d1", k, 0), SelectRequest("d1", k, 1),
            SelectRequest("d1", SERVE_FAST_K, 2, algo="fast"),
            SelectRequest("d1", SERVE_FAST_K, 3, algo="fast")]
    t = time.perf_counter()
    server.serve(reqs)
    first_s = time.perf_counter() - t
    t = time.perf_counter()
    replies = server.serve(reqs)
    warm_s = time.perf_counter() - t
    checks, detail = {}, []
    for i, (req, rep) in enumerate(zip(reqs, replies)):
        tag = f"req{i}_{req.algo}_k{req.k}"
        checks[f"{tag}_ok"] = rep.status == OK
        if rep.status != OK:
            detail.append({"status": rep.status, "detail": rep.detail})
            continue
        detail.append({"tier": rep.tier, **s.check_selection(
            (rep.sel_mask, rep.sel_count, rep.value), req.k, ref, checks,
            tag, exact_k=req.algo != "fast")})
        checks[f"{tag}_not_degraded"] = rep.tier == req.algo
    return {"sizes": {"d": X.shape[0], "n": X.shape[1],
                      "k": sorted({r.k for r in reqs})},
            "register_s": register_s, "compile_s": first_s - warm_s,
            "run_s": warm_s, "replies": detail, "checks": checks}


def four_chips(s: Smoke):
    """The sharded path: regression at n=2²² over a (1, 1, 4) mesh, and
    sharded FAST against the single-device run at n=2²⁰."""
    jax, jnp = s.jax, s.jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import RegressionObjective, select
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found "
                           f"{len(jax.devices())}")
    mesh = make_mesh((1, 1, 4), ("pod", "data", "model"),
                     devices=jax.devices()[:4])
    cols = NamedSharding(mesh, P(None, "model"))
    d1_regression = make_generators(jax, jnp)[0]
    reg_ref = make_references(jax, jnp)[0]
    c = SHARDED
    d, k = c["d"], c["k"]

    def sharded_data(tag, n):
        gen = jax.jit(d1_regression, static_argnums=(1, 2, 3),
                      out_shardings=(cols, NamedSharding(mesh, P())))
        return gen(s.fold(tag), d, n, c["support"])

    n = c["n"]
    X, y = sharded_data(7, n)
    jax.block_until_ready((X, y))
    s.progress(f"sharded data ready: d={d}, n={n}")
    obj = RegressionObjective(X, y, kmax=k, use_kernel=True)
    sizes = {"d": d, "n": n, "k": k, "X_bytes": 4 * d * n,
             "X_bytes_per_chip": 4 * d * n // 4}
    ref = lambda idx: reg_ref(X, y, idx)
    for algo, kk, opts in (("dash", k, {}), ("fast", c["fast_k"], FAST_OPTS)):
        s.phase(f"sharded/{algo}", lambda: {
            "sizes": {**sizes, "k": kk}, "mesh": dict(mesh.shape),
            **s.select_phase(algo, obj, kk, ref, mesh=mesh, exact_k=False,
                             **opts)})
    del obj, X, y, ref
    gc.collect()

    def parity():
        """One pinned OPT guess (the top-k probe scaled like
        ``BatchSelector``'s) and the same key on both runtimes."""
        n, pk = c["parity_n"], c["fast_k"]
        X, y = sharded_data(8, n)
        one = jax.devices()[0]
        sharded = RegressionObjective(X, y, kmax=pk, use_kernel=True)
        single = RegressionObjective(jax.device_put(X, one),
                                     jax.device_put(y, one), kmax=pk,
                                     use_kernel=True)
        opt = 1.25 * float(select("topk", single, pk).value)
        key = s.fold(9)
        rec, masks = {"sizes": {"d": d, "n": n, "k": pk}, "opt": opt}, []
        for name, o, m in (("single", single, None),
                           ("sharded", sharded, mesh)):
            def program(o, kk, _m=m):
                r = select("fast", o, pk, kk, mesh=_m, opt=opt,
                           eps=c["parity_eps"])
                return r.sel_mask, r.sel_count, r.value

            compiled, compile_s, _ = s.compile(program, o, key)
            out, run_s = s.run(compiled, o, key)
            masks.append(s.np.asarray(out[0]))
            rec[name] = {"compile_s": compile_s, "run_s": run_s,
                         "sel_count": int(out[1]), "value": float(out[2])}
        rec["checks"] = {
            "sel_mask_bitwise_equal": bool(s.np.array_equal(*masks)),
            "sel_count_equal": rec["single"]["sel_count"]
            == rec["sharded"]["sel_count"] == int(masks[0].sum())}
        return rec

    s.phase("sharded/fast_parity", parity)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    load_repro()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if not on_tpu(jax):
        print(f"chip_smoke: no TPU (JAX found {jax.devices()[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    s = Smoke(jax, jnp, np, args.seed)
    (four_chips if args.chips == 4 else one_chip)(s)
    print(f"total_s {time.perf_counter() - s.t0:.1f}", flush=True)
    if not s.ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
