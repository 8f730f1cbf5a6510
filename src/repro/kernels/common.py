"""Shared infrastructure for the Pallas kernel wrappers (the ops.py layer).

This module IS the kernel-authoring contract (long form: docs/kernels.md).
Every kernel package splits into ``kernel.py`` (the ``pl.pallas_call``
with explicit BlockSpecs, assuming pre-padded shapes), ``ops.py`` (the
public wrapper) and ``ref.py`` (the pure-jnp oracle), and every ops.py
does the same four things before dispatching:

  1. **Backend routing** (``resolve_path``).  The ops-level ``interpret``
     argument is tri-state:
       * ``None`` (the default) — compiled Pallas kernel on TPU, the jnp
         reference everywhere else.  Pallas interpret mode is orders of
         magnitude slower than the reference on CPU, so it is never an
         implicit fallback — only an explicit choice.
       * ``True``  — Pallas interpret mode (kernel validation anywhere).
       * ``False`` — compiled Pallas unconditionally.
     Callers (objectives, distributed loops) always pass ``None`` and let
     the wrapper route; tests pass ``True`` to validate kernel logic on
     CPU.
  2. **Precision policy** (``resolve_precision`` / ``stream_dtype`` /
     ``quantize``).  The ops-level ``precision`` argument selects the
     storage dtype of the *streamed* operands (the big HBM-bound
     matrices: X, and A-optimality's per-guess solve W) — ``"f32"`` or
     ``"bf16"``.  Accumulation is ALWAYS f32: kernels upcast streamed
     blocks right after load, so bf16 halves HBM traffic without
     touching the epilogue math.  The reference path applies the SAME
     quantization (``quantize`` round-trips through bf16) so kernel and
     reference compute the same function per precision and parity stays
     tight per dtype (see ``STREAM_PARITY_TOL``).
  3. **Padding** to TPU-aligned shapes (``round_up`` / ``pad1d`` /
     ``pad2d``): ``sublane_for(dtype)`` multiples on the feature/basis
     axes — (8, 128) tiles for f32, (16, 128) for bf16 — and a
     ``block_n`` multiple on the candidate axis.  The wrapper must
     choose fills so padded entries cannot contribute — zero columns for
     streamed operands, and for guard vectors a fill that trips the
     guard (e.g. ``filter_gains`` pads ``col_sq`` with 1.0 so the span
     tolerance clamps padded candidates to 0).  Padding adds less than
     one block of columns, and an operand that is already aligned is
     passed through uncopied, so it never decides the path.
  4. **Block-size selection** (``repro.kernels.tuning.tuned_block_n``
     over ``pick_block_n``).  The wrapper states its per-grid-step
     working set as bytes(block_n) — inputs + outputs + scratch + large
     temporaries, with streamed operands counted at
     ``stream_resident_bytes`` per element — and first consults the
     persistent autotuning cache for a measured winner at this
     (kernel, precision, shape bucket); on a miss it falls back to the
     largest candidate from ``BLOCK_N_CANDIDATES`` that fits
     ``VMEM_BUDGET`` (12 MB, leaving v5e headroom for double buffering).

These heuristics used to be copy-pasted across ``marginal_gains``,
``aopt_gains`` and ``logistic_gains``; they live here so a tiling or
routing fix lands in every kernel at once.  New kernels must build on
this module instead of re-deriving tiling; sample-batched filter kernels
additionally build their grid via ``repro.kernels.filter_gains.core``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

# Leave headroom of the 16 MB v5e per-core VMEM for double buffering.
VMEM_BUDGET = 12 * 1024 * 1024
# Tiling constraints: the lane axis is always 128; the sublane multiple
# depends on element width — (8, 128) f32 tiles, (16, 128) bf16,
# (32, 128) int8/fp8.
SUBLANE = 8        # f32 sublane; kept for dtype-oblivious callers
LANE = 128
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}
BLOCK_N_CANDIDATES = (512, 256, 128)

# Streamed-operand precision policies: storage dtype of the HBM-bound
# operands; accumulation is always f32.
PRECISIONS = ("f32", "bf16")
_STREAM_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}

# Asserted parity tolerances per streamed-operand precision (see
# docs/kernels.md "Autotuning & mixed precision" for the measured-vs-
# asserted rationale).  ``kernel_vs_ref`` bounds the interpret-mode
# kernel against the same-precision reference (both compute the same
# function on identically quantized operands, so it is precision-
# independent and tight).  ``vs_f32`` bounds the bf16 result against the
# f32 result as max-abs-error normalized by the max f32 gain — bf16
# storage carries ~2^-8 relative mantissa error which squares through
# the gain ratios; worst measured deviation across the parity and
# bench shapes is ~2e-3, asserted well above so growing accumulation
# depth never turns the quantization budget into a flaky test.
STREAM_PARITY_TOL = {
    "f32": {"kernel_vs_ref": 2e-4, "vs_f32": 0.0},
    "bf16": {"kernel_vs_ref": 2e-4, "vs_f32": 5e-2},
}


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is ≥ ``x``."""
    return ((x + m - 1) // m) * m


def sublane_for(dtype) -> int:
    """Minimum second-to-last-axis tile multiple for ``dtype``:
    8 for 4-byte, 16 for 2-byte, 32 for 1-byte elements."""
    return _SUBLANE_BY_ITEMSIZE[jnp.dtype(dtype).itemsize]


def resolve_precision(precision: str | None) -> str:
    """Normalize the ops-level ``precision`` argument: ``None`` means
    f32 streaming (the historical behavior)."""
    p = "f32" if precision is None else str(precision)
    if p not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    return p


def stream_dtype(precision: str | None):
    """Storage dtype for streamed operands under ``precision``."""
    return _STREAM_DTYPES[resolve_precision(precision)]


def quantize(x, precision: str | None):
    """Round-trip ``x`` through the streamed storage dtype, back to f32.

    This is the reference-path emulation of bf16 streaming: the kernel
    stores the operand in bf16 and upcasts after load, so the values it
    computes with are exactly ``f32(bf16(x))`` — applying the same
    round-trip to the reference's inputs makes kernel and reference
    compute the same function per precision.  f32 is the identity.
    """
    dt = stream_dtype(precision)
    if dt == jnp.float32:
        return jnp.asarray(x, jnp.float32)
    return jnp.asarray(x).astype(dt).astype(jnp.float32)


def stream_resident_bytes(precision: str | None) -> int:
    """Per-element VMEM bytes for a streamed operand block: the stored
    block plus, for sub-f32 storage, the f32 upcast copy the epilogue
    materializes right after load.  (f32 → 4, bf16 → 2 + 4 = 6: bf16
    halves the HBM traffic but the VMEM budget must count both copies.)
    """
    item = jnp.dtype(stream_dtype(precision)).itemsize
    return item if item >= 4 else item + 4


def pick_block_n(
    vmem_bytes: Callable[[int], int],
    *,
    budget: int = VMEM_BUDGET,
    candidates: tuple[int, ...] = BLOCK_N_CANDIDATES,
) -> int:
    """Largest candidate block size whose VMEM working set fits.

    ``vmem_bytes`` maps a candidate ``block_n`` to the number of bytes the
    kernel holds resident per grid step (inputs + outputs + scratch).
    Falls back to the smallest candidate when nothing fits; the TPU
    compiler then refuses a kernel whose blocks exceed VMEM.
    """
    for bn in candidates:
        if vmem_bytes(bn) <= budget:
            return bn
    return candidates[-1]


def resolve_path(interpret: bool | None) -> tuple[bool, bool]:
    """Map the ops-level ``interpret`` argument to (use_ref, interpret).

    * ``None``  — compiled Pallas on TPU, jnp reference everywhere else.
      Interpret mode is orders of magnitude slower than the reference on
      CPU, so it is never an implicit fallback — only an explicit choice.
    * ``True``  — Pallas interpret mode (kernel validation on any host).
    * ``False`` — compiled Pallas unconditionally.
    """
    if interpret is None:
        return jax.default_backend() != "tpu", False
    return False, bool(interpret)


def pad2d(x, rows: int, cols: int, dtype=jnp.float32):
    """Pad a 2-D array up to (rows, cols) with zeros, in ``dtype``.

    The cast rides the pad: streaming wrappers pad X directly into its
    bf16 storage buffer, so quantization costs no extra pass.  An
    operand that already has the target shape is only cast."""
    r, c = x.shape
    if (r, c) == (rows, cols):
        return x.astype(dtype)
    return jnp.zeros((rows, cols), dtype).at[:r, :c].set(x.astype(dtype))


def pad1d(x, size: int, fill: float = 0.0, dtype=jnp.float32):
    """Pad a 1-D array up to ``size`` with ``fill``, in ``dtype``."""
    return jnp.full((size,), fill, dtype).at[: x.shape[0]].set(
        x.astype(dtype)
    )
