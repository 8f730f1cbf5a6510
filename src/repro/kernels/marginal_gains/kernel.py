"""Pallas TPU kernel: fused batched regression marginal gains.

One pass over the candidate axis computes, per column a of X:

    c_a     = x_aᵀ r                      (GEMV against the residual)
    s_a     = ‖Qᵀ x_a‖²                   (GEMM against the basis + reduce)
    gain_a  = c_a² / (‖x_a‖² − s_a)       (guarded by the span tolerance)

Fusing the GEMM with the reduction + ratio avoids materializing the
(k × n) projection matrix B = QᵀX in HBM: the kernel streams X once.

Tiling
------
grid = (n // block_n,).  Per grid step the kernel holds in VMEM:
    X block   (d, block_n)
    Q         (d, kcap)
    resid     (d, 1)
    col_sq    (1, block_n)
    out       (1, block_n)
``d`` and ``kcap`` are padded to multiples of 8 and ``block_n`` to 128 by
ops.py so the MXU sees aligned shapes.  VMEM footprint (f32):
4·d·(block_n + kcap + 1) bytes — e.g. d=4096, block_n=256, kcap=512:
~12.6 MB < 16 MB v5e VMEM.  ops.py shrinks block_n when needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SPAN_TOL = 1e-6


def _gains_kernel(x_ref, q_ref, r_ref, csq_ref, o_ref, *, span_tol: float):
    # Streamed X may arrive in bf16 storage; all epilogue math is f32.
    x = x_ref[...].astype(jnp.float32)  # (d, bn)
    q = q_ref[...]                      # (d, k)
    r = r_ref[...]                      # (d, 1)
    csq = csq_ref[...]                  # (1, bn)

    # c = rᵀX  — (1, bn); accumulate in f32 on the MXU.
    c = jax.lax.dot_general(
        r, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # B = QᵀX — (k, bn), then column sum of squares, fused in-register.
    b = jax.lax.dot_general(
        q, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = jnp.sum(b * b, axis=0, keepdims=True)       # (1, bn)
    denom = csq - s
    floor = span_tol * jnp.maximum(csq, 1.0)
    gains = (c * c) / jnp.maximum(denom, 1e-30)
    o_ref[...] = jnp.where(denom > floor, gains, 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_n", "span_tol", "interpret")
)
def regression_gains_pallas(
    X, Q, resid, col_sq, *, block_n: int = 256, span_tol: float = SPAN_TOL,
    interpret: bool = True,
):
    """X: (d, n), Q: (d, k), resid: (d,), col_sq: (n,) — all pre-padded so
    that n % block_n == 0.  Returns (n,) f32 gains."""
    d, n = X.shape
    k = Q.shape[1]
    assert n % block_n == 0, (n, block_n)

    grid = (n // block_n,)
    out = pl.pallas_call(
        functools.partial(_gains_kernel, span_tol=span_tol),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
            pl.BlockSpec((d, k), lambda i: (0, 0)),
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="regression_gains_pallas",
    )(X, Q, resid[:, None], col_sq[None, :])
    return out[0]
