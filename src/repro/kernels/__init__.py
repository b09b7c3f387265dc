"""Pallas kernels for the graph hot loop.

:func:`pallas_compiled` is the one place that decides how a Pallas kernel
runs: compiled by Mosaic on a TPU, in the Pallas interpreter on any other
platform (the CPU test tier).  Kernel wrappers ask it when they trace; no
caller chooses.
"""

from __future__ import annotations


def pallas_compiled() -> bool:
    """True when Pallas kernels compile for the device (a TPU backend)."""
    import jax

    return jax.default_backend() == "tpu"
