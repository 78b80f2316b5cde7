"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device counts are locked at first backend init,
and only launch/dryrun.py is allowed to force the 512-device emulation.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "compat_make_mesh"]


def compat_make_mesh(shape, axes, devices=None):
    """jax.make_mesh with explicit Auto axes (keeps jax from warning)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run "
            "under launch/dryrun.py (it forces host-device emulation) or on "
            "real hardware")
    return compat_make_mesh(shape, axes, devices=devices)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices exist (tests / single-host training)."""
    n = len(jax.devices())
    data = data if data is not None else max(1, n // model)
    devices = jax.devices()[: data * model]
    return compat_make_mesh((data, model), ("data", "model"), devices=devices)
