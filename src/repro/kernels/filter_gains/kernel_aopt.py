"""A-optimality epilogue of the sample-batched filter engine.

The perturbed precision M_i = M + σ⁻² C_i C_iᵀ of state S ∪ R_i admits
the Woodbury split (``AOptimalityObjective.expand_factors``):

    M_i⁻¹ = M⁻¹ − E_i E_iᵀ,      E_i = σ⁻¹ M⁻¹C_i L_i⁻ᵀ  (d, b)

so with the *shared* solve W = M⁻¹X done once per filter evaluation, the
Sherman–Morrison gain of candidate a under sample i needs only two small
per-sample projections t = E_iᵀx_a, u = E_iᵀw_a and the (b, b) Gram
F_i = E_iᵀE_i:

    ‖M_i⁻¹x_a‖² = ‖w_a‖² − 2 uᵀt + tᵀF_i t
    x_aᵀM_i⁻¹x_a = x_aᵀw_a − ‖t‖²
    gain_ia = σ⁻² ‖M_i⁻¹x_a‖² / (1 + σ⁻² x_aᵀM_i⁻¹x_a)

The per-sample path instead re-factorizes M_i and writes a (d, n)
W_i = W − E_i(E_iᵀX) per sample; the engine reads the one shared W plus
(m · b · d · n) delta GEMMs — same shape of win as the regression
epilogue's shared-base projection.

Guess lattice: each OPT guess g has its own state, hence its own shared
solve W_g = M_g⁻¹X (a ``gstream`` operand — one (d, n) slab per guess,
re-fetched only at guess boundaries thanks to the sample-minor grid
order) and its own ‖w_a‖² / x_aᵀw_a rows (``gcand``).  X itself stays a
single ``stream`` — fetched from HBM once for the whole lattice instead
of once per guess.

Per grid step the kernel holds in VMEM (f32): X and W_g blocks
(stream/gstream), E_gi (d, bcap) + F_gi (bcap, bcap) (sample), wsq/xw
rows (gcand), t/u/ft temporaries (3·bcap·block_n) — ops.py budgets
block_n accordingly; the guess fold leaves the per-step working set
unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.filter_gains.core import Operand, launch_filter_engine


def _aopt_epilogue(x_ref, w_ref, e_ref, f_ref, wsq_ref, xw_ref, o_ref,
                   *, isig2: float):
    # Streamed X/W may arrive in bf16 storage; all epilogue math is f32.
    x = x_ref[...].astype(jnp.float32)      # (d, bn)
    w = w_ref[0].astype(jnp.float32)        # (d, bn) — this guess's W slab
    e = e_ref[0]                            # (d, b)
    t = jax.lax.dot_general(                # E_giᵀ X — (b, bn)
        e, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    u = jax.lax.dot_general(                # E_giᵀ W_g — (b, bn)
        e, w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ft = jax.lax.dot_general(               # F_gi t — (b, bn)
        f_ref[0], t, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    num = wsq_ref[...] - 2.0 * jnp.sum(u * t, axis=0, keepdims=True) \
        + jnp.sum(t * ft, axis=0, keepdims=True)
    den = 1.0 + isig2 * (xw_ref[...] - jnp.sum(t * t, axis=0, keepdims=True))
    # num is a squared norm: clamp the f32 cancellation residue at 0.
    o_ref[...] = isig2 * jnp.maximum(num, 0.0) / jnp.maximum(den, 1e-30)


@functools.partial(
    jax.jit, static_argnames=("isig2", "block_n", "interpret")
)
def aopt_filter_gains_pallas(
    X, W, E, F, wsq, xw, *, isig2: float, block_n: int = 256,
    interpret: bool = True,
):
    """X: (d, n); W: (G, d, n) per-guess shared solves; E: (G·m, d, b);
    F: (G·m, b, b) folded guess-major; wsq, xw: (G, n) — all pre-padded
    so that n % block_n == 0.  Returns (G·m, n) f32 gains.  A guess-free
    sweep is simply G = 1."""
    n = X.shape[1]
    g = W.shape[0]
    m = E.shape[0] // g
    return launch_filter_engine(
        functools.partial(_aopt_epilogue, isig2=isig2),
        [
            Operand(X, "stream"),
            Operand(W, "gstream"),
            Operand(E, "sample"),
            Operand(F, "sample"),
            Operand(wsq, "gcand"),
            Operand(xw, "gcand"),
        ],
        name="aopt_filter_gains_pallas",
        n=n,
        n_samples=m,
        n_guesses=g,
        block_n=block_n,
        interpret=interpret,
    )
