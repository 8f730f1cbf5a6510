"""Plain float32 references for Bayesian A-optimal design (β², σ² from
the configuration).

f(S) = Tr(Λ⁻¹) − Tr(M_S⁻¹),  M_S = Λ + σ⁻² X_S X_Sᵀ,  Λ = β² I, and the
singleton gain of candidate a at M:

    gain(a) = σ⁻² ‖M⁻¹x_a‖² / (1 + σ⁻² x_aᵀM⁻¹x_a).

Each precision matrix is factored and solved directly (Cholesky, two
triangular solves): no Woodbury split, nothing from the program under
test.  ``lower`` selects a control (see ``harness.lowp``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from harness.lowp import matmul_precision, rounder


def _consts(params):
    return float(params.get("beta2", 1.0)), 1.0 / float(params.get("sigma2", 1.0))


def precision_matrix(X, cols_mask_pairs, params, lower=None):
    """M = β² I + σ⁻² Σ C Cᵀ over the (columns, mask) pairs given."""
    beta2, isig2 = _consts(params)
    rnd = rounder(lower)
    d = X.shape[0]
    M = beta2 * jnp.eye(d, dtype=jnp.float32)
    with jax.default_matmul_precision(matmul_precision(lower)):
        for cols, mask in cols_mask_pairs:
            C = rnd(jnp.where(mask[None, :], jnp.take(X, cols, axis=1), 0.0))
            M = M + isig2 * (C @ C.T)
    return M


def value(data, idx, size, params, lower=None):
    """f(S) for S = idx[:size] (the rest of ``idx`` is padding)."""
    beta2, _ = _consts(params)
    X = data["X"]
    d = X.shape[0]
    valid = jnp.arange(idx.shape[0]) < size
    M = precision_matrix(X, [(idx, valid)], params, lower)
    with jax.default_matmul_precision(matmul_precision(lower)):
        L = jnp.linalg.cholesky(M)
        Z = solve_triangular(L, jnp.eye(d, dtype=jnp.float32), lower=True)
    return d / beta2 - jnp.sum(Z * Z)


def solve(M, B, lower=None):
    """M⁻¹B by Cholesky and two triangular solves."""
    with jax.default_matmul_precision(matmul_precision(lower)):
        L = jnp.linalg.cholesky(M)
        Z = solve_triangular(L, B, lower=True)
        return solve_triangular(L.T, Z, lower=False)


def gains_at(X, M, params, lower=None):
    """(n,) Sherman–Morrison gains of every candidate at precision M."""
    _, isig2 = _consts(params)
    Xr = rounder(lower)(X)
    W = solve(M, Xr, lower)
    num = isig2 * jnp.sum(W * W, axis=0)
    den = 1.0 + isig2 * jnp.sum(Xr * W, axis=0)
    return num / jnp.maximum(den, 1e-30)


def sweep_gains(X, base, params, lower=None):
    """(G, n) gains at the states S_g = base[g] (G, c)."""
    c = base.shape[1]
    return jax.lax.map(lambda cols: gains_at(
        X, precision_matrix(X, [(cols, jnp.ones((c,), bool))], params,
                            lower), params, lower), base)

