"""Pallas TPU flash attention.

Grid: (B, H, n_q_blocks, n_kv_blocks) — the last dim is the streaming
axis: the output BlockSpec ignores it, so the kernel revisits the same
output block while marching over KV blocks, keeping the online-softmax
running state (m, l, acc) in VMEM scratch.  This is the canonical
TPU-native flash layout: the (block_q × block_kv) score tile lives
entirely in VMEM/registers, the MXU sees two aligned GEMMs per tile, and
HBM traffic is one pass over Q, K, V, O.

VMEM per step (f32): block_q·d + 2·block_kv·d + block_q·block_kv
+ block_q·(d+2) scratch — e.g. d=128, block_q=block_kv=512: ~1.7 MB.

Masking (causal / sliding window) is computed from block indices; blocks
that are fully masked still execute (interpret-mode friendliness) but
contribute exp(−inf)=0 — the ops.py wrapper documents the skip
optimization applied on real TPUs via block-sparse grid pruning.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  block_q: int, block_kv: int, n_kv: int, skv: int,
                  q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                     # (block_q, d)
    k = k_ref[0, 0]                     # (block_kv, d)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                           # (block_q, block_kv)
    if softcap and softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    kpos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    rel = qpos - kpos
    valid = kpos < skv
    if causal:
        valid &= rel >= 0
    if window and window > 0:
        valid &= rel < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                 # (block_q, 1)
    m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))[:, None]
    p = jnp.exp(s - m_new)              # (block_q, block_kv)
    corr = jnp.exp(m_prev - m_new)      # (block_q, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_kv", "q_offset",
    "skv_actual", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, block_q: int = 128,
                           block_kv: int = 128, q_offset: int = 0,
                           skv_actual: int = 0, interpret: bool = True):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D) with Hkv | H.
    GQA is zero-copy: the K/V BlockSpec index maps query head h to KV
    head h // (H/Hkv), so grouped heads share the same VMEM block.
    Sq % block_q == 0, Skv % block_kv == 0.  Returns (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = h // hkv
    assert sq % block_q == 0 and skv % block_kv == 0
    n_q = sq // block_q
    n_kv = skv // block_kv
    grid = (b, h, n_q, n_kv)
    scale = 1.0 / math.sqrt(d)
    skv_true = skv_actual or skv    # mask KV padding, not the padded len

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, n_kv=n_kv,
        skv=skv_true, q_offset=q_offset,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ik: (b, h // n_rep, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ik: (b, h // n_rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
        ],
        interpret=interpret,
        name="flash_attention_pallas",
    )(q, k, v)
