"""Public jit'd wrappers for the sample-batched filter-gain engine.

One wrapper per objective epilogue — ``filter_gains`` (regression),
``aopt_filter_gains`` (A-optimality), ``logistic_filter_gains``
(classification) — all sharing the same contract: padding / block-size /
backend routing via ``repro.kernels.common`` (non-TPU backends run the
also-sample-batched jnp reference; Pallas interpret mode only when
requested explicitly), block sizes from the ``repro.kernels.tuning``
cache when a measured winner exists for the shape bucket, grid geometry
via ``repro.kernels.filter_gains.core``.  Padded delta columns, residual
rows and logits are zero, so they contribute nothing to the projections.

Precision policy
----------------
``precision="bf16"`` stores the *streamed* operands — X, and the
A-optimality per-guess solve W — in bf16, halving the HBM traffic the
engine exists to amortize; the epilogues upcast right after load so all
accumulation stays f32.  The reference branches quantize the same
operands through the same round-trip (``common.quantize``), so kernel
and reference compute the same function per precision and the parity
suites can assert tight per-dtype tolerances
(``common.STREAM_PARITY_TOL``).

Guess lattice
-------------
Every wrapper accepts the per-guess state operands with an optional
leading ``n_guesses`` axis (Q: (G, d, k), W: (G, d, n), etas:
(G, m, d), …) and then runs the WHOLE (OPT, α) lattice as one launch:
the guess axis is folded into the sample grid axis (see ``core.py``) so
X streams from HBM once for all G·m perturbed states instead of once
per guess.  Returns (G, m, n) in that mode.

The wrappers additionally register ``jax.custom_vmap`` batching rules:
``jax.vmap`` over the per-guess operands (which is exactly what the
batched ``dash_auto`` lattice does — one vmapped selection loop per
guess) resolves to the SAME folded single launch rather than G logical
copies of the kernel.  Unexpected batching patterns (a batched ground
set X) fall back to the vmapped reference — correct, just without the
stream amortization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    pad1d,
    pad2d,
    quantize,
    resolve_path,
    resolve_precision,
    round_up,
    stream_dtype,
    stream_resident_bytes,
    sublane_for,
)
from repro.kernels.filter_gains.kernel import filter_gains_pallas
from repro.kernels.filter_gains.kernel_aopt import aopt_filter_gains_pallas
from repro.kernels.filter_gains.kernel_logistic import (
    logistic_filter_gains_pallas,
)
from repro.kernels.filter_gains.ref import (
    SPAN_TOL,
    aopt_filter_gains_lattice_ref,
    aopt_filter_gains_ref,
    filter_gains_lattice_ref,
    filter_gains_ref,
    logistic_filter_gains_ref,
)
from repro.kernels.tuning import bucket_n, tuned_block_n


def _bcast(x, batched: bool, axis_size: int):
    """Give ``x`` the leading batch axis the custom-vmap rules expect."""
    return x if batched else jnp.broadcast_to(x[None], (axis_size,) + x.shape)


# ---------------------------------------------------------------------------
# regression epilogue
# ---------------------------------------------------------------------------

def _filter_gains_lattice(X, Q, D, R, col_sq, interpret, precision=None,
                          block_n=None):
    """Folded-guess-axis launch: Q (G, d, k), D (G, m, d, b), R (G, m, d).
    Returns (G, m, n)."""
    use_ref, interpret = resolve_path(interpret)
    prec = resolve_precision(precision)
    sdt = stream_dtype(prec)
    sb = stream_resident_bytes(prec)
    d, n = X.shape
    g, _, k = Q.shape
    m, b = D.shape[1], D.shape[3]
    dp = round_up(d, sublane_for(sdt))
    kp = round_up(max(k, 1), sublane_for(sdt))
    bp = round_up(max(b, 1), sublane_for(sdt))
    # Per-step VMEM is unchanged by the guess fold (one Q_g/D_gi/r_gi
    # resident at a time): X block at stream precision (+ f32 upcast),
    # then f32 Q_g, D_gi, r_gi, col_sq, base scratch + out block.
    vmem = lambda bn: sb * dp * bn + 4 * (dp * (kp + bp + 1) + 3 * bn)
    bn = block_n or tuned_block_n(
        "filter_gains", prec,
        {"dp": dp, "kp": kp, "bp": bp, "m": m, "g": g, "nb": bucket_n(n)},
        vmem,
    )
    np_ = round_up(n, bn)
    if use_ref:
        return filter_gains_lattice_ref(quantize(X, prec), Q, D, R, col_sq)

    Xp = pad2d(X, dp, np_, dtype=sdt)
    Qp = jnp.zeros((g, dp, kp), jnp.float32).at[:, :d, :k].set(Q)
    Dp = jnp.zeros((g * m, dp, bp), jnp.float32).at[:, :d, :b].set(
        D.reshape(g * m, d, b)
    )
    Rp = jnp.zeros((g * m, dp), jnp.float32).at[:, :d].set(
        R.reshape(g * m, d)
    )
    # Padded candidates: col_sq = 1 so the span guard clamps them to 0.
    cp = pad1d(col_sq, np_, fill=1.0)
    out = filter_gains_pallas(
        Xp, Qp, Dp, Rp, cp, block_n=bn, span_tol=SPAN_TOL,
        interpret=interpret,
    )
    return out.reshape(g, m, -1)[:, :, :n]


def _filter_gains_single(X, Q, D, R, col_sq, interpret, precision=None,
                         block_n=None):
    """Guess-free sweep: the lattice launch at G = 1 (the kernel path),
    the plain reference off-TPU."""
    use_ref, _ = resolve_path(interpret)
    if use_ref:
        return filter_gains_ref(quantize(X, precision), Q, D, R, col_sq)
    return _filter_gains_lattice(X, Q[None], D[None], R[None], col_sq,
                                 interpret, precision, block_n)[0]


@functools.lru_cache(maxsize=None)
def _filter_gains_batched(interpret, precision, block_n):
    """custom-vmap wrapper: vmapping the per-guess operands folds into
    ONE lattice launch instead of G logical kernel copies."""

    @jax.custom_batching.custom_vmap
    def fg(X, Q, D, R, col_sq):
        return _filter_gains_single(X, Q, D, R, col_sq, interpret,
                                    precision, block_n)

    @fg.def_vmap
    def _fg_vmap(axis_size, in_batched, X, Q, D, R, col_sq):
        xb, qb, db, rb, cb = in_batched
        if xb or cb:
            # Per-lane ground sets: no shared stream to amortize.
            out = jax.vmap(
                lambda Xg, Qg, Dg, Rg, cg: filter_gains_ref(
                    quantize(Xg, precision), Qg, Dg, Rg, cg
                )
            )(
                _bcast(X, xb, axis_size), _bcast(Q, qb, axis_size),
                _bcast(D, db, axis_size), _bcast(R, rb, axis_size),
                _bcast(col_sq, cb, axis_size),
            )
            return out, True
        out = _filter_gains_lattice(
            X, _bcast(Q, qb, axis_size), _bcast(D, db, axis_size),
            _bcast(R, rb, axis_size), col_sq, interpret, precision, block_n,
        )
        return out, True

    return fg


def filter_gains(X, Q, D, R, col_sq, *, interpret: bool | None = None,
                 precision: str | None = None, block_n: int | None = None):
    """Sample-batched regression filter gains for DASH.

    X: (d, n) candidates; Q: (d, k) shared basis; D: (m, d, b) per-sample
    orthonormal deltas (⊥ Q); R: (m, d) per-sample residuals; col_sq:
    (n,).  Returns (m, n) unnormalized gains, one row per sample.

    Guess lattice: pass Q (G, d, k), D (G, m, d, b), R (G, m, d) to sweep
    all G guesses' perturbed states in one folded launch — returns
    (G, m, n).  ``jax.vmap`` over (Q, D, R) resolves to the same launch.

    ``precision="bf16"`` streams X in bf16 with f32 accumulation (the
    reference path quantizes X identically); ``block_n`` forces the
    candidate block size (the autotuner's measurement hook).
    """
    if Q.ndim == 3:
        return _filter_gains_lattice(X, Q, D, R, col_sq, interpret,
                                     precision, block_n)
    return _filter_gains_batched(
        interpret, resolve_precision(precision), block_n
    )(X, Q, D, R, col_sq)


# ---------------------------------------------------------------------------
# A-optimality epilogue
# ---------------------------------------------------------------------------

def _aopt_filter_gains_lattice(X, W, E, F, isig2, interpret, precision=None,
                               block_n=None):
    """Folded-guess-axis launch: W (G, d, n), E (G, m, d, b),
    F (G, m, b, b).  Returns (G, m, n)."""
    use_ref, interpret = resolve_path(interpret)
    prec = resolve_precision(precision)
    sdt = stream_dtype(prec)
    sb = stream_resident_bytes(prec)
    d, n = X.shape
    g = W.shape[0]
    m, b = E.shape[1], E.shape[3]
    dp = round_up(d, sublane_for(sdt))
    bp = round_up(max(b, 1), sublane_for(sdt))
    # Per-step VMEM unchanged by the fold: X + W_g blocks at stream
    # precision (+ their f32 upcasts), f32 E_gi, F_gi, wsq, xw, out, and
    # the t/u/ft (bp, bn) temporaries.
    vmem = lambda bn: 2 * sb * dp * bn + 4 * (dp * bp + bp * bp + 3 * bn
                                              + 3 * bp * bn)
    bn = block_n or tuned_block_n(
        "aopt_filter_gains", prec,
        {"dp": dp, "bp": bp, "m": m, "g": g, "nb": bucket_n(n)},
        vmem,
    )
    np_ = round_up(n, bn)
    # wsq/xw are functions of the STREAMED values: compute them from the
    # quantized operands on both routes so kernel (which reads the bf16
    # store) and reference agree exactly per precision.
    Xq = quantize(X, prec)
    Wq = quantize(W, prec)
    if use_ref:
        return aopt_filter_gains_lattice_ref(Xq, Wq, E, F, isig2)

    Xp = pad2d(X, dp, np_, dtype=sdt)
    Wp = jnp.zeros((g, dp, np_), sdt).at[:, :d, :n].set(W.astype(sdt))
    Ep = jnp.zeros((g * m, dp, bp), jnp.float32).at[:, :d, :b].set(
        E.reshape(g * m, d, b)
    )
    Fp = jnp.zeros((g * m, bp, bp), jnp.float32).at[:, :b, :b].set(
        F.reshape(g * m, b, b)
    )
    # Padded candidates have x = w = 0 → num = 0, den = 1 → gain 0.
    wsq = jnp.zeros((g, np_), jnp.float32).at[:, :n].set(
        jnp.sum(Wq * Wq, axis=1)
    )
    xw = jnp.zeros((g, np_), jnp.float32).at[:, :n].set(
        jnp.sum(Xq[None] * Wq, axis=1)
    )
    out = aopt_filter_gains_pallas(
        Xp, Wp, Ep, Fp, wsq, xw, isig2=float(isig2), block_n=bn,
        interpret=interpret,
    )
    return out.reshape(g, m, -1)[:, :, :n]


def _aopt_filter_gains_single(X, W, E, F, isig2, interpret, precision=None,
                              block_n=None):
    use_ref, _ = resolve_path(interpret)
    if use_ref:
        return aopt_filter_gains_ref(
            quantize(X, precision), quantize(W, precision), E, F, isig2
        )
    return _aopt_filter_gains_lattice(X, W[None], E[None], F[None], isig2,
                                      interpret, precision, block_n)[0]


# Bounded: the key includes the data-dependent float isig2 (one entry —
# and one retained custom_vmap wrapper + its executables — per distinct
# sigma2), unlike the interpret/steps-keyed caches below whose key spaces
# are tiny enums.
@functools.lru_cache(maxsize=64)
def _aopt_filter_gains_batched(isig2, interpret, precision, block_n):
    @jax.custom_batching.custom_vmap
    def fg(X, W, E, F):
        return _aopt_filter_gains_single(X, W, E, F, isig2, interpret,
                                         precision, block_n)

    @fg.def_vmap
    def _fg_vmap(axis_size, in_batched, X, W, E, F):
        xb, wb, eb, fb = in_batched
        if xb:
            out = jax.vmap(
                lambda Xg, Wg, Eg, Fg: aopt_filter_gains_ref(
                    quantize(Xg, precision), quantize(Wg, precision),
                    Eg, Fg, isig2
                )
            )(
                _bcast(X, xb, axis_size), _bcast(W, wb, axis_size),
                _bcast(E, eb, axis_size), _bcast(F, fb, axis_size),
            )
            return out, True
        out = _aopt_filter_gains_lattice(
            X, _bcast(W, wb, axis_size), _bcast(E, eb, axis_size),
            _bcast(F, fb, axis_size), isig2, interpret, precision, block_n,
        )
        return out, True

    return fg


def aopt_filter_gains(X, W, E, F, isig2, *, interpret: bool | None = None,
                      precision: str | None = None,
                      block_n: int | None = None):
    """Sample-batched A-optimality (Woodbury) filter gains for DASH.

    X: (d, n) stimuli; W = M⁻¹X (d, n) shared solve; E: (m, d, b)
    per-sample Woodbury factors; F: (m, b, b) Grams E_iᵀE_i; isig2 =
    1/σ².  Returns (m, n) gains, one row per perturbed state S ∪ R_i.

    Guess lattice: pass W (G, d, n), E (G, m, d, b), F (G, m, b, b) for
    one folded launch over all guesses — returns (G, m, n).  ``jax.vmap``
    over (W, E, F) resolves to the same launch when ``isig2`` is a host
    scalar (the objective's, always).

    ``precision="bf16"`` streams X AND W in bf16 with f32 accumulation;
    ``block_n`` forces the candidate block size (autotuner hook).
    """
    if E.ndim == 4:
        return _aopt_filter_gains_lattice(X, W, E, F, isig2, interpret,
                                          precision, block_n)
    if isinstance(isig2, (int, float)):
        return _aopt_filter_gains_batched(
            float(isig2), interpret, resolve_precision(precision), block_n
        )(X, W, E, F)
    return _aopt_filter_gains_single(X, W, E, F, isig2, interpret,
                                     precision, block_n)


# ---------------------------------------------------------------------------
# logistic epilogue
# ---------------------------------------------------------------------------

def _logistic_filter_gains_folded(X, y, etas, steps, interpret,
                                  precision=None, block_n=None):
    """Folded sweep: etas (M, d) for M = G·m perturbed states."""
    use_ref, interpret = resolve_path(interpret)
    prec = resolve_precision(precision)
    sdt = stream_dtype(prec)
    sb = stream_resident_bytes(prec)
    d, n = X.shape
    m = etas.shape[0]
    dp = round_up(d, sublane_for(sdt))
    # Bytes resident per grid step: X block at stream precision (+ f32
    # upcast), the f32 (d, bn) Newton logits temporary, y and η_i
    # columns, ~4 (1, bn) rows.
    vmem = lambda bn: sb * dp * bn + 4 * (dp * bn + 2 * dp + 4 * bn)
    bn = block_n or tuned_block_n(
        "logistic_filter_gains", prec,
        {"dp": dp, "m": m, "steps": steps, "nb": bucket_n(n)},
        vmem,
    )
    np_ = round_up(n, bn)
    if use_ref:
        return logistic_filter_gains_ref(quantize(X, prec), y, etas,
                                         steps=steps)

    # Padded rows have x = y = η = 0: zero g/h contributions, and their
    # −log 2 softplus terms cancel exactly in ll_new − ll_old.
    Xp = pad2d(X, dp, np_, dtype=sdt)
    yp = pad1d(y, dp)
    ep = jnp.zeros((m, dp), jnp.float32).at[:, :d].set(etas)
    out = logistic_filter_gains_pallas(
        Xp, yp, ep, steps=steps, block_n=bn, interpret=interpret,
    )
    return out[:, :n]


@functools.lru_cache(maxsize=None)
def _logistic_filter_gains_batched(steps, interpret, precision, block_n):
    @jax.custom_batching.custom_vmap
    def fg(X, y, etas):
        return _logistic_filter_gains_folded(X, y, etas, steps, interpret,
                                             precision, block_n)

    @fg.def_vmap
    def _fg_vmap(axis_size, in_batched, X, y, etas):
        xb, yb, eb = in_batched
        if xb or yb:
            out = jax.vmap(
                lambda Xg, yg, eg: logistic_filter_gains_ref(
                    quantize(Xg, precision), yg, eg, steps=steps
                )
            )(
                _bcast(X, xb, axis_size), _bcast(y, yb, axis_size),
                _bcast(etas, eb, axis_size),
            )
            return out, True
        eg = _bcast(etas, eb, axis_size)
        g, m, d = eg.shape
        out = _logistic_filter_gains_folded(
            X, y, eg.reshape(g * m, d), steps, interpret, precision, block_n
        )
        return out.reshape(g, m, -1), True

    return fg


def logistic_filter_gains(X, y, etas, *, steps: int = 3,
                          interpret: bool | None = None,
                          precision: str | None = None,
                          block_n: int | None = None):
    """Sample-batched logistic filter gains for DASH.

    X: (d, n) features; y: (d,) labels; etas: (m, d) per-sample refit
    logits.  Returns (m, n) gains — row i is the ``steps``-step-Newton
    log-likelihood improvement of each candidate at state S ∪ R_i.

    Guess lattice: pass etas (G, m, d) for one folded launch over all
    guesses — returns (G, m, n).  ``jax.vmap`` over etas resolves to the
    same launch (the logistic state is fully described by its logits, so
    the lattice is simply G·m folded samples).

    ``precision="bf16"`` streams X in bf16 (Newton math stays f32);
    ``block_n`` forces the candidate block size (autotuner hook).
    """
    if etas.ndim == 3:
        return _unfold_logistic(X, y, etas, steps, interpret, precision,
                                block_n)
    return _logistic_filter_gains_batched(
        steps, interpret, resolve_precision(precision), block_n
    )(X, y, etas)


def _unfold_logistic(X, y, etas, steps, interpret, precision=None,
                     block_n=None):
    g, m, d = etas.shape
    out = _logistic_filter_gains_folded(X, y, etas.reshape(g * m, d),
                                        steps, interpret, precision, block_n)
    return out.reshape(g, m, -1)
