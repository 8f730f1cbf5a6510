"""Regression epilogue of the sample-batched filter engine.

One launch evaluates the DASH filter statistic for ALL perturbed states
S_g ∪ R_{g,i} of the whole (OPT, α) guess lattice — the per-sample path
launches ``n_guesses · n_samples`` independent ``gains`` passes,
re-streaming the full (d, n) matrix X from HBM each time.  Per candidate
a, guess g and sample i:

    c_gia   = x_aᵀ r_{g,i}                (GEMV against sample residual)
    s_ga    = ‖Q_gᵀ x_a‖²                 (shared-base projection)
    t_gia   = ‖D_{g,i}ᵀ x_a‖²             (per-sample delta projection)
    gain    = c² / (‖x_a‖² − s_ga − t_gia)    (span-tolerance guarded)

Tiling (``core.launch_filter_engine``): grid = (n // block_n, G·m) with
the folded (guess, sample) axis minor, so one X block stays resident in
VMEM and is reused against every guess's (Q_g, D_{g,i}, r_{g,i}).  The
shared-base projection ‖Q_gᵀx‖² is computed at sample 0 of each guess
(``s % m == 0``) and cached in a VMEM scratch accumulator for the
guess's remaining samples (grid dimensions are sequential/"arbitrary"
by default, which this relies on).

Per grid step the kernel holds in VMEM (f32):
    X block   (d, block_n)     stream
    Q_g       (1, d, kcap)     gconst — fetched once per guess
    D_gi      (1, d, bcap)     sample
    r_gi      (1, d)           sample
    col_sq    (1, block_n)     cand
    base      (1, block_n)     scratch
    out       (1, block_n)
4·(d·(block_n + kcap + bcap + 1) + 3·block_n) bytes; e.g. d=1024,
block_n=512, kcap=64, bcap=8: ~2.4 MB ≪ 16 MB v5e VMEM — unchanged by
the guess fold, which only lengthens the grid.  ops.py shrinks block_n
when needed and pads d/kcap/bcap to sublane multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.filter_gains.core import Operand, launch_filter_engine
from repro.kernels.filter_gains.ref import SPAN_TOL


def _regression_epilogue(x_ref, q_ref, d_ref, r_ref, csq_ref, o_ref,
                         base_ref, *, n_samples: int, span_tol: float):
    s = pl.program_id(1)
    # Streamed X may arrive in bf16 storage; all epilogue math is f32.
    x = x_ref[...].astype(jnp.float32)      # (d, bn)

    # Shared-base projection: once per (candidate block, guess) — at the
    # guess's sample 0 — then reused from scratch while the same X block
    # stays resident across the guess's remaining samples.
    @pl.when(s % n_samples == 0)
    def _():
        b = jax.lax.dot_general(
            q_ref[0], x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                   # (k, bn)
        base_ref[...] = jnp.sum(b * b, axis=0, keepdims=True)

    # c = r_giᵀ X — (1, bn) on the MXU.
    c = jax.lax.dot_general(
        r_ref[...], x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # Per-sample delta projection D_giᵀ X — (bcap, bn), reduced in-register.
    bd = jax.lax.dot_general(
        d_ref[0], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    csq = csq_ref[...]                      # (1, bn)
    denom = csq - base_ref[...] - jnp.sum(bd * bd, axis=0, keepdims=True)
    floor = span_tol * jnp.maximum(csq, 1.0)
    gains = (c * c) / jnp.maximum(denom, 1e-30)
    o_ref[...] = jnp.where(denom > floor, gains, 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_n", "span_tol", "interpret")
)
def filter_gains_pallas(
    X, Q, D, R, col_sq, *, block_n: int = 256, span_tol: float = SPAN_TOL,
    interpret: bool = True,
):
    """X: (d, n), Q: (G, d, k) per-guess bases, D: (G·m, d, b) folded
    guess-major deltas, R: (G·m, d) folded residuals, col_sq: (n,) — all
    pre-padded so that n % block_n == 0.  Returns (G·m, n) f32 gains.
    A guess-free sweep is simply G = 1."""
    n = X.shape[1]
    g = Q.shape[0]
    m = D.shape[0] // g
    return launch_filter_engine(
        functools.partial(_regression_epilogue, n_samples=m,
                          span_tol=span_tol),
        [
            Operand(X, "stream"),
            Operand(Q, "gconst"),
            Operand(D, "sample"),
            Operand(R, "sample"),
            Operand(col_sq, "cand"),
        ],
        name="filter_gains_pallas",
        n=n,
        n_samples=m,
        n_guesses=g,
        block_n=block_n,
        scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
        interpret=interpret,
    )
