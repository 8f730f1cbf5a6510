"""Pallas TPU kernel: fused A-optimality Sherman–Morrison gains.

Per candidate column a (X and W = M⁻¹X streamed in column blocks):

    gain_a = σ⁻² ‖w_a‖² / (1 + σ⁻² x_aᵀ w_a)

The fusion saves two (n,)-sized HBM round-trips for the intermediate
column reductions — the kernel is bandwidth-bound, so the win is
proportional to the number of fused intermediates.

Tiling: grid over candidate blocks; VMEM per step = 2·d·block_n·4 bytes
(e.g. d=4096, block_n=256 → 8 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _aopt_kernel(x_ref, w_ref, o_ref, *, isig2: float):
    # Streamed X/W may arrive in bf16 storage; reductions run in f32.
    x = x_ref[...].astype(jnp.float32)  # (d, bn)
    w = w_ref[...].astype(jnp.float32)  # (d, bn)
    num = isig2 * jnp.sum(w * w, axis=0, keepdims=True)      # (1, bn)
    den = 1.0 + isig2 * jnp.sum(x * w, axis=0, keepdims=True)
    o_ref[...] = num / jnp.maximum(den, 1e-30)


@functools.partial(jax.jit, static_argnames=("isig2", "block_n", "interpret"))
def aopt_gains_pallas(X, W, *, isig2: float, block_n: int = 256,
                      interpret: bool = True):
    """X, W: (d, n) with n % block_n == 0.  Returns (n,) f32 gains."""
    d, n = X.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    out = pl.pallas_call(
        functools.partial(_aopt_kernel, isig2=isig2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="aopt_gains_pallas",
    )(X, W)
    return out[0]
