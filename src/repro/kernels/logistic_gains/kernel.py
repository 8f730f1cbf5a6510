"""Pallas TPU kernel: fused 1-D-Newton logistic marginal gains.

Each grid step holds one candidate block X[:, j:j+bn] in VMEM and runs the
full ``steps``-iteration scalar-Newton recurrence *in registers/VMEM*,
then emits the per-candidate log-likelihood gain.  Fusion matters here:
the jnp reference materializes a (d, n) logits tensor per Newton step
(``steps``+1 HBM round-trips of d·n·4 bytes); the kernel streams X once.
This is the oracle hot-spot of the paper's logistic-regression experiment
(Fig. 3: a single oracle sweep took >1 min on their gene dataset).

VMEM per step: d·bn·4 (X block) + ~3·bn·4 + 2·d·4 bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def newton_gain_sweep(x, y, eta, *, steps: int, eps: float):
    """``steps`` scalar-Newton iterations per candidate column of ``x``
    (d, bn) at logits ``eta`` (d, 1), labels ``y`` (d, 1); returns the
    (1, bn) log-likelihood improvements.  Shared by this kernel and the
    sample-batched filter epilogue
    (``repro.kernels.filter_gains.kernel_logistic``).
    """
    bn = x.shape[1]
    w = jnp.zeros((1, bn), jnp.float32)

    # Static unroll: ``steps`` is small, and the TPU lowering accepts no
    # ``lax.scan`` inside a kernel.
    for _ in range(steps):
        z = eta + x * w                 # (d, bn)
        p = jax.nn.sigmoid(z)
        g = jnp.sum(x * (y - p), axis=0, keepdims=True)
        h = jnp.sum((x * x) * (p * (1.0 - p)), axis=0, keepdims=True)
        w = w + g / (h + eps)
    z = eta + x * w
    ll_new = jnp.sum(y * z - jax.nn.softplus(z), axis=0, keepdims=True)
    ll_old = jnp.sum(y * eta - jax.nn.softplus(eta))
    return jnp.maximum(ll_new - ll_old, 0.0)


def _logistic_kernel(x_ref, y_ref, eta_ref, o_ref, *, steps: int, eps: float):
    # Streamed X may arrive in bf16 storage; the recurrence runs in f32.
    o_ref[...] = newton_gain_sweep(
        x_ref[...].astype(jnp.float32), y_ref[...], eta_ref[...],
        steps=steps, eps=eps,
    )


@functools.partial(
    jax.jit, static_argnames=("steps", "block_n", "eps", "interpret")
)
def logistic_gains_pallas(X, y, eta, *, steps: int = 3, block_n: int = 256,
                          eps: float = 1e-9, interpret: bool = True):
    """X: (d, n) with n % block_n == 0; y, eta: (d,).  Returns (n,) f32."""
    d, n = X.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    out = pl.pallas_call(
        functools.partial(_logistic_kernel, steps=steps, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="logistic_gains_pallas",
    )(X, y[:, None], eta[:, None])
    return out[0]
