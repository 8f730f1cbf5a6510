"""Pure-jnp oracle for the batched A-optimality (Sherman–Morrison) gains.

Given W = M⁻¹X (the objective's cached shared solve):

    gain(a) = σ⁻² ‖w_a‖² / (1 + σ⁻² x_aᵀ w_a)
"""

from __future__ import annotations

import jax.numpy as jnp


def aopt_gains_ref(X, W, isig2):
    """X, W: (d, n); isig2 = 1/σ².  Returns (n,) gains."""
    num = isig2 * jnp.sum(W * W, axis=0)
    den = 1.0 + isig2 * jnp.sum(X * W, axis=0)
    return num / jnp.maximum(den, 1e-30)
