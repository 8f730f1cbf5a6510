"""Seeded, well-conditioned operands for the six gain/filter kernels.

Kernel-vs-reference parity is only meaningful on operands shaped like
the ones the objectives produce: unit-norm candidate columns, an
orthonormal shared basis with per-sample deltas orthogonal to it, a
genuine shared solve W = M⁻¹X with Woodbury factors of a real
perturbation, logits of modest scale.  Raw normal draws instead push the
rational epilogues into magnitudes where a comparison measures
conditioning, not the kernel.  Everything is generated with
``jax.random`` from one key, so the same call builds small operands for
the parity tests and full-width ones on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def unit_columns(key, d: int, n: int):
    """(d, n) f32 normal draws with unit-norm columns."""
    X = jax.random.normal(key, (d, n), jnp.float32)
    return X / jnp.linalg.norm(X, axis=0, keepdims=True)


def _orthonormal(G):
    q, _ = jnp.linalg.qr(G)
    return q


def regression_operands(key, d: int, n: int, k: int, m: int, b: int):
    """``regression_gains`` / ``filter_gains`` operands.

    Returns ``(X, Q, resid, col_sq, D, R)``: Q (d, k) orthonormal, D
    (m, d, b) per-sample orthonormal deltas ⊥ Q, resid (d,) and R (m, d)
    residuals orthogonal to the basis they pair with.
    """
    kx, kq, kd, kr = jax.random.split(key, 4)
    X = unit_columns(kx, d, n)
    Q = _orthonormal(jax.random.normal(kq, (d, k), jnp.float32))

    def delta(kk):
        G = jax.random.normal(kk, (d, b), jnp.float32)
        return _orthonormal(G - Q @ (Q.T @ G))

    D = jax.vmap(delta)(jax.random.split(kd, m))
    r = jax.random.normal(kr, (m + 1, d), jnp.float32)
    r = r - (r @ Q) @ Q.T
    R = r[1:] - jnp.einsum("mdb,mb->md", D, jnp.einsum("mdb,md->mb", D, r[1:]))
    return X, Q, r[0], jnp.sum(X * X, axis=0), D, R


def aopt_operands(key, d: int, n: int, m: int, b: int, n_sel: int = 16):
    """``aopt_gains`` / ``aopt_filter_gains`` operands.

    Returns ``(X, W, E, F)``: M = I + X_S X_Sᵀ for ``n_sel`` columns,
    W = M⁻¹X, and per sample the Woodbury factor E_i = (M⁻¹C_i) L_i⁻ᵀ of
    adding b further columns C_i (L_i L_iᵀ = I + C_iᵀM⁻¹C_i), F_i = E_iᵀE_i.
    """
    kx, ks, kc = jax.random.split(key, 3)
    X = unit_columns(kx, d, n)
    Xs = X[:, jax.random.choice(ks, n, (n_sel,), replace=False)]
    M = jnp.eye(d, dtype=jnp.float32) + Xs @ Xs.T
    Lm = jnp.linalg.cholesky(M)
    W = jax.scipy.linalg.cho_solve((Lm, True), X)

    def factor(kk):
        C = X[:, jax.random.choice(kk, n, (b,), replace=False)]
        P = jax.scipy.linalg.cho_solve((Lm, True), C)
        Lk = jnp.linalg.cholesky(jnp.eye(b, dtype=jnp.float32) + C.T @ P)
        return jax.scipy.linalg.solve_triangular(Lk, P.T, lower=True).T

    E = jax.vmap(factor)(jax.random.split(kc, m))
    return X, W, E, jnp.einsum("mdb,mdc->mbc", E, E)


def logistic_operands(key, d: int, n: int, m: int):
    """``logistic_gains`` / ``logistic_filter_gains`` operands.

    Returns ``(X, y, eta, etas)``: unit-norm columns, Bernoulli(½) labels,
    current logits eta (d,) and per-sample refit logits etas (m, d).
    """
    kx, ky, ke = jax.random.split(key, 3)
    X = unit_columns(kx, d, n)
    y = jax.random.bernoulli(ky, 0.5, (d,)).astype(jnp.float32)
    e = 0.4 * jax.random.normal(ke, (m + 1, d), jnp.float32)
    return X, y, e[0], e[1:]
