"""The A-optimality gains kernel: the plain reference's side.

For each state S_g (``harness.check`` draws it at the cell's launch
shape) the precision M_g = β²I + σ⁻²X_S X_Sᵀ is formed and factored
directly, and every candidate's Sherman–Morrison gain is computed from
its solve.
"""

import jax


def reference(cell, data, sets, ref, lower=None):
    params = cell.config["objective"]["options"]
    fn = jax.jit(lambda X, b: ref.sweep_gains(X, b, params, lower))
    return fn(data["X"], sets["base"])
