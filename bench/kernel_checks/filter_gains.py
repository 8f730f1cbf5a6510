"""The regression filter kernel: the plain reference's side.

For each guess g and sample i the state S_g ∪ R_gi (``harness.check``
draws the sets at the cell's launch shape) gets its own orthonormal
basis by QR of its columns and the residual of y off it, at ``highest``
precision, and every candidate is scored from the definition, over
‖y‖² as the objective reports it.
"""

import jax


def reference(cell, data, sets, ref, lower=None):
    fn = jax.jit(ref.filter_gains, static_argnames="lower")
    return fn(data["X"], data["y"], sets["base"], sets["samp"], lower=lower)
