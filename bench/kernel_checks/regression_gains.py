"""The regression marginal-gains kernel: the plain reference's side.

Each state S_g (``harness.check`` draws it at the cell's launch shape)
gets its own orthonormal basis by QR of its columns and the residual of
y off it, at ``highest`` precision, and every candidate is scored from
the definition, over ‖y‖² as the objective reports it.
"""

import jax


def reference(cell, data, sets, ref, lower=None):
    fn = jax.jit(ref.sweep_gains, static_argnames="lower")
    return fn(data["X"], data["y"], sets["base"], lower=lower)
