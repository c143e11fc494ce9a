from repro.utils.tree import (
    tree_add,
    tree_scale,
    tree_axpy,
    tree_dot,
    tree_norm,
    tree_sub,
    tree_zeros_like,
    tree_size,
    tree_cast,
)
from repro.utils.timing import span
from repro.utils.logging import get_logger

__all__ = [
    "tree_add",
    "tree_scale",
    "tree_axpy",
    "tree_dot",
    "tree_norm",
    "tree_sub",
    "tree_zeros_like",
    "tree_size",
    "tree_cast",
    "span",
    "get_logger",
]
