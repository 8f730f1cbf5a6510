"""Plain float32 references for sparse-regression feature selection.

f(S) = ‖proj_{span X_S} y‖² / ‖y‖², and the singleton gain of candidate
a at a state whose selected columns span the orthonormal basis B:

    gain(a) = (x_aᵀ r)² / (‖x_a‖² − ‖Bᵀx_a‖²),   r = y − BBᵀy,

zero where the denominator is within 1e-6·max(‖x_a‖², 1) of zero
(a candidate already in the span adds nothing).  Written from the
mathematics alone; nothing here comes from the program under test.
``lower`` selects a control (see ``harness.lowp``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.lowp import matmul_precision, rounder

SPAN_TOL = 1e-6


def value(data, idx, size, params, lower=None):
    """f(S) for S = idx[:size] (the rest of ``idx`` is padding)."""
    rnd = rounder(lower)
    X, y = data["X"], rnd(data["y"])
    valid = jnp.arange(idx.shape[0]) < size
    Xs = rnd(jnp.where(valid[None, :], jnp.take(X, idx, axis=1), 0.0))
    with jax.default_matmul_precision(matmul_precision(lower)):
        # Householder QR: the first `size` columns of q span the valid
        # (leading) columns, whatever the zero padding behind them.
        q, _ = jnp.linalg.qr(Xs)
        p = jnp.where(valid, q.T @ y, 0.0)
        return jnp.sum(p * p) / jnp.sum(y * y)


def basis(X, cols):
    """Orthonormal basis (d, c) of span X[:, cols], at highest precision."""
    with jax.default_matmul_precision("highest"):
        q, _ = jnp.linalg.qr(jnp.take(X, cols, axis=1))
    return q


def gains(X, B, r, col_sq, lower=None):
    """(n,) singleton gains at basis B (d, c) and residual r (d,)."""
    rnd = rounder(lower)
    X, B, r = rnd(X), rnd(B), rnd(r)
    with jax.default_matmul_precision(matmul_precision(lower)):
        c = r @ X
        P = B.T @ X
    denom = col_sq - jnp.sum(P * P, axis=0)
    floor = SPAN_TOL * jnp.maximum(col_sq, 1.0)
    return jnp.where(denom > floor, c * c / jnp.maximum(denom, 1e-30), 0.0)


def state_gains(X, y, cols, col_sq, lower=None):
    """(n,) singleton gains, over ‖y‖², at the state S = ``cols``: the
    basis of X_S and the residual of y off it at ``highest`` precision,
    then ``gains``."""
    B = basis(X, cols)
    with jax.default_matmul_precision("highest"):
        r = y - B @ (B.T @ y)
        ysq = jnp.sum(y * y)
    return gains(X, B, r, col_sq, lower) / ysq


def sweep_gains(X, y, base, lower=None):
    """(G, n) gains at the states S_g = base[g] (G, c)."""
    col_sq = jnp.sum(X * X, axis=0)
    return jax.lax.map(lambda cols: state_gains(X, y, cols, col_sq, lower),
                       base)


def filter_gains(X, y, base, samp, lower=None):
    """(G, m, n) gains at the perturbed states S_g ∪ R_gi: base (G, c),
    samp (G, m, b).  Every state's basis is formed from its columns
    directly, one state at a time, so the projections fit beside X."""
    col_sq = jnp.sum(X * X, axis=0)

    def guess(args):
        bs, ss = args
        return jax.lax.map(lambda r: state_gains(
            X, y, jnp.concatenate([bs, r]), col_sq, lower), ss)

    return jax.lax.map(guess, (base, samp))
