"""Lowerings of a jaxpr to MLIR inside the window, per select() call.

Counted from JAX's own monitoring event
(``/jax/core/compile/jaxpr_to_mlir_module_duration``).  A call that
finds its programs in JAX's in-memory caches lowers nothing; one that
rebuilds a traced function lowers again and reloads or recompiles.
"""


def read(run):
    if not run.calls:
        return None
    return run.lowerings / len(run.calls)
