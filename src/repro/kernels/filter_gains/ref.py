"""Pure-jnp oracles for the sample-batched filter-gain engine.

The filter step of DASH estimates Ê_R[f_{S∪R}(a)] for every candidate a
over ``n_samples`` Monte-Carlo sets R_1..R_m.  Every objective splits
the perturbed state S ∪ R_i into state shared by all samples plus a
small per-sample delta, so the expensive candidate sweep is paid once:

* regression (``filter_gains_ref``): shared orthonormal basis Q of
  span(X_S) plus per-sample delta columns D_i ⊥ Q and residual r_i,

      gain_i(a) = (x_aᵀ r_i)² / (‖x_a‖² − ‖Qᵀ x_a‖² − ‖D_iᵀ x_a‖²)

  because D_i ⊥ span(Q) implies ‖[Q D_i]ᵀ x‖² = ‖Qᵀx‖² + ‖D_iᵀx‖².  The
  shared-base term is computed ONCE for all samples: the per-sample path
  pays an (n_samples · kcap · d · n) GEMM, this formulation pays
  (kcap + n_samples · block) · d · n.

* A-optimality (``aopt_filter_gains_ref``): shared solve W = M⁻¹X plus
  per-sample Woodbury factors E_i with M_i⁻¹ = M⁻¹ − E_i E_iᵀ; the
  per-sample path writes a fresh (d, n) W_i = W − E_i(E_iᵀX) per sample.

* logistic (``logistic_filter_gains_ref``): per-sample refit logits η_i;
  each row is exactly ``logistic_gains_ref`` at η_i — no shared GEMM,
  but the fused kernel streams X from HBM once for all samples.

In-span candidates (denominator ≤ tol·‖x_a‖²) are clamped to 0, matching
``marginal_gains.ref``.  The regression gains are unnormalized — the
objective divides by ‖y‖².
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.logistic_gains.ref import logistic_gains_ref

SPAN_TOL = 1e-6


def filter_gains_ref(X, Q, D, R, col_sq, *, span_tol: float = SPAN_TOL):
    """X: (d, n); Q: (d, k) shared zero-padded orthonormal basis;
    D: (m, d, b) per-sample delta bases (zero-padded, ⊥ Q);
    R: (m, d) per-sample residuals; col_sq: (n,) column squared norms.
    Returns (m, n) f32 gains."""
    c = R @ X                                          # (m, n)
    B = Q.T @ X                                        # (k, n)
    base = jnp.sum(B * B, axis=0)                      # (n,) — shared
    BD = jnp.einsum("mdb,dn->mbn", D, X)               # (m, b, n)
    sd = jnp.sum(BD * BD, axis=1)                      # (m, n)
    denom = (col_sq - base)[None, :] - sd
    floor = span_tol * jnp.maximum(col_sq, 1.0)
    gains = (c * c) / jnp.maximum(denom, 1e-30)
    return jnp.where(denom > floor[None, :], gains, 0.0)


def aopt_filter_gains_ref(X, W, E, F, isig2):
    """X: (d, n); W = M⁻¹X (d, n) shared solve; E: (m, d, b) per-sample
    Woodbury factors (M_i⁻¹ = M⁻¹ − E_i E_iᵀ, zero-padded columns);
    F: (m, b, b) Grams E_iᵀE_i; isig2 = 1/σ².  Returns (m, n) f32 gains

        σ⁻² ‖M_i⁻¹x_a‖² / (1 + σ⁻² x_aᵀM_i⁻¹x_a).
    """
    wsq = jnp.sum(W * W, axis=0)                       # (n,) — shared
    xw = jnp.sum(X * W, axis=0)                        # (n,) — shared
    T = jnp.einsum("mdb,dn->mbn", E, X)                # E_iᵀ X
    U = jnp.einsum("mdb,dn->mbn", E, W)                # E_iᵀ W
    FT = jnp.einsum("mbc,mcn->mbn", F, T)
    num = wsq[None, :] - 2.0 * jnp.sum(U * T, axis=1) + jnp.sum(T * FT, axis=1)
    den = 1.0 + isig2 * (xw[None, :] - jnp.sum(T * T, axis=1))
    # num is a squared norm: clamp the f32 cancellation residue at 0.
    return isig2 * jnp.maximum(num, 0.0) / jnp.maximum(den, 1e-30)


def logistic_filter_gains_ref(X, y, etas, *, steps: int = 3,
                              eps: float = 1e-9):
    """X: (d, n); y: (d,); etas: (m, d) per-sample refit logits.  Row i is
    ``logistic_gains_ref`` evaluated at η_i — (m, n) f32 gains."""
    return jax.vmap(
        lambda eta: logistic_gains_ref(X, y, eta, steps=steps, eps=eps)
    )(etas)


# ---------------------------------------------------------------------------
# guess-lattice variants: one leading (OPT, α)-guess axis over the
# per-guess state operands, the ground set X shared by every guess.
# These are the non-TPU execution paths of the folded-guess-axis engine
# (ops.py routes here off-TPU) as well as its test oracles.
# ---------------------------------------------------------------------------

def filter_gains_lattice_ref(X, Q, D, R, col_sq, *,
                             span_tol: float = SPAN_TOL):
    """Per-guess bases Q: (G, d, k), deltas D: (G, m, d, b), residuals
    R: (G, m, d); shared X: (d, n), col_sq: (n,).  Returns (G, m, n)."""
    return jax.vmap(
        lambda Qg, Dg, Rg: filter_gains_ref(X, Qg, Dg, Rg, col_sq,
                                            span_tol=span_tol)
    )(Q, D, R)


def aopt_filter_gains_lattice_ref(X, W, E, F, isig2):
    """Per-guess shared solves W: (G, d, n), factors E: (G, m, d, b),
    Grams F: (G, m, b, b); shared X: (d, n).  Returns (G, m, n)."""
    return jax.vmap(
        lambda Wg, Eg, Fg: aopt_filter_gains_ref(X, Wg, Eg, Fg, isig2)
    )(W, E, F)


def logistic_filter_gains_lattice_ref(X, y, etas, *, steps: int = 3,
                                      eps: float = 1e-9):
    """Per-guess logits etas: (G, m, d); shared X: (d, n), y: (d,).
    Returns (G, m, n)."""
    g, m, d = etas.shape
    out = logistic_filter_gains_ref(X, y, etas.reshape(g * m, d),
                                    steps=steps, eps=eps)
    return out.reshape(g, m, -1)
