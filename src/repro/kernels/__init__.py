"""Pallas TPU kernels for the perf-critical compute hot-spots:

* ``kmeans``    — MASA streaming K-Means assignment (paper Table 1)
* ``tomo``      — forward/back projectors for GridRec & ML-EM (paper §3.2.2)
* ``attention`` — blocked flash attention (prefill) and paged decode attention

Each has ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py`` (jit'd
wrapper with ref/kernel dispatch) and ``ref.py`` (pure-jnp oracle). On the
TPU the kernels compile natively (``tests/test_chip_compile.py`` compiles
them for a v5e at real sizes); the CPU test suite runs them in Pallas
interpret mode against the oracles (``tests/test_kernels.py``).
"""
from __future__ import annotations

import jax


def kernel_interpret(interpret: bool | None = None) -> bool:
    """The kernel mode. A caller may state it; otherwise this is the one
    place it is derived: native on a TPU backend, Pallas interpret mode on
    any other (the CPU test suite). The ``ops.py`` entries pass their
    ``interpret`` argument (default ``None``) through here."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
