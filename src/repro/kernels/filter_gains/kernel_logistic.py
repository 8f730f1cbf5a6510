"""Logistic epilogue of the sample-batched filter engine.

The perturbed state S ∪ R_i of the classification objective is fully
described by its refit logits η_i = X_{S∪R_i} w^{(S∪R_i)} — the small
per-sample IRLS refit happens outside the kernel
(``ClassificationObjective.expand_logits``); the engine fuses the
*candidate sweep*: for every sample i and candidate a, ``steps``
scalar-Newton iterations on max_w ℓ(y, η_i + x_a·w), emitting the
log-likelihood improvement.

Unlike the regression/A-opt epilogues there is no shared GEMM to
amortize — the Newton recurrence is (d, block_n) element-wise VPU work —
but the HBM story is identical: the per-sample path streams the full
(d, n) matrix X from HBM once per sample per Newton step, while here
one X block is fetched once per launch and reused across all samples
and all steps (sample axis minor, X resident in VMEM).

Guess lattice: the logistic perturbed state is FULLY described by its
refit logits, so the (OPT, α) lattice needs no per-guess operand kinds —
ops.py simply folds the (G, m, d) logits stack to (G·m, d) guess-major
"samples" and this kernel sweeps the whole lattice in one launch (X
fetched once for all G·m states instead of once per guess).

Per grid step the kernel holds in VMEM (f32): the X block (d·block_n),
y and η_i columns (2·d), the (d, block_n) logits temporary of the
Newton recurrence, and ~4 (1, block_n) rows — ops.py budgets block_n
for roughly twice the X block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.filter_gains.core import Operand, launch_filter_engine
from repro.kernels.logistic_gains.kernel import newton_gain_sweep


def _logistic_epilogue(x_ref, y_ref, eta_ref, o_ref, *, steps: int,
                       eps: float):
    # eta_ref[0]: this sample's (d, 1) logits; the sweep itself is the
    # single-state marginal-gain kernel's.  Streamed X may arrive in
    # bf16 storage; the Newton recurrence runs in f32.
    o_ref[...] = newton_gain_sweep(
        x_ref[...].astype(jnp.float32), y_ref[...], eta_ref[0],
        steps=steps, eps=eps,
    )


@functools.partial(
    jax.jit, static_argnames=("steps", "block_n", "eps", "interpret")
)
def logistic_filter_gains_pallas(
    X, y, etas, *, steps: int = 3, block_n: int = 256, eps: float = 1e-9,
    interpret: bool = True,
):
    """X: (d, n) with n % block_n == 0; y: (d,); etas: (m, d) per-sample
    logits.  Returns (m, n) f32 gains."""
    n = X.shape[1]
    m = etas.shape[0]
    return launch_filter_engine(
        functools.partial(_logistic_epilogue, steps=steps, eps=eps),
        [
            Operand(X, "stream"),
            Operand(y[:, None], "const"),
            Operand(etas[:, :, None], "sample"),
        ],
        name="logistic_filter_gains_pallas",
        n=n,
        n_samples=m,
        block_n=block_n,
        interpret=interpret,
    )
