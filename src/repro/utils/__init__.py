"""Small shared utilities: pytrees, timing, the compilation cache."""
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.tree import (
    tree_bytes,
    tree_count,
    tree_flatten_with_paths,
    tree_zeros_like,
)
from repro.utils.timer import Timer, now_monotonic

__all__ = [
    "Timer",
    "enable_compile_cache",
    "now_monotonic",
    "tree_bytes",
    "tree_count",
    "tree_flatten_with_paths",
    "tree_zeros_like",
]
