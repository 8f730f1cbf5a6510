"""Rounding of a reference's operands, for the controls of ``correct``.

``None`` is the plain reference: float32 operands, matrix products at
``highest`` precision.  A control rounds every operand to a lower
format and takes the products at the TPU's default precision:

* ``bf16``: round to bfloat16's 8 exponent and 7 mantissa bits;
* ``fp8``: scale the tensor so its largest magnitude is the largest
  finite number of 4 exponent and 3 mantissa bits (240), round to
  those bits (float8 e4m3), scale back (per-tensor scaling, as an fp8
  streaming path would store it).

The rounding is ``lax.reduce_precision``, an operation of its own that
the compiler keeps; a cast to the narrow type and back is a pair of
conversions that a compiler allowed excess precision may drop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LOWER = (None, "bf16", "fp8")
_E4M3_MAX = 240.0           # 1.875 * 2**7


def rounder(lower):
    if lower is None:
        return lambda x: x
    if lower == "bf16":
        return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                  mantissa_bits=7)
    if lower == "fp8":
        def fp8(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
            return jax.lax.reduce_precision(
                x / s, exponent_bits=4, mantissa_bits=3) * s
        return fp8
    raise ValueError(f"unknown lower precision {lower!r}; have {LOWER}")


def matmul_precision(lower) -> str:
    return "highest" if lower is None else "default"
