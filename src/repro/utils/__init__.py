from repro.utils.tree import (
    tree_bytes,
    tree_count,
    tree_norm,
    tree_zeros_like,
)

__all__ = [
    "tree_bytes",
    "tree_count",
    "tree_norm",
    "tree_zeros_like",
]
