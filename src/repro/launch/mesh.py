"""Production / host mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because ``dryrun.py`` must set
XLA_FLAGS before any jax initialisation.

Both constructors derive their device requirement from the requested shape
and raise the same :class:`RuntimeError` (``mesh_device_error``) when the
process has too few devices — callers (tests, the engine's ``mesh=`` boot
path) match on one message format instead of two drifting ones.
"""

from __future__ import annotations

import numpy as np


def mesh_device_error(shape, have: int, platform: str) -> RuntimeError:
    """The uniform too-few-devices error: count derived from ``shape``,
    naming the platform and device count JAX found."""
    need = int(np.prod(shape))
    return RuntimeError(
        f"mesh shape {tuple(shape)} needs {need} devices, have {have} "
        f"on platform {platform!r}"
    )


def _take_devices(shape):
    """The first ``prod(shape)`` devices, or raise the uniform error.

    Taking a prefix of ``jax.devices()`` when MORE devices exist is
    deliberate (a (2, 2) test mesh on an 8-device host); having FEWER is
    an error here rather than a confusing failure inside ``make_mesh``.
    """
    import jax

    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise mesh_device_error(shape, len(devices), devices[0].platform)
    return devices[:need]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips)."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, devices=_take_devices(shape))


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over host devices (tests, examples, the engine's
    ``mesh=int`` boot path).  Raises the uniform error instead of silently
    truncating to however many devices exist."""
    import jax

    return jax.make_mesh(shape, axes, devices=_take_devices(shape))
