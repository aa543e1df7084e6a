"""Device mesh helpers."""

from __future__ import annotations

from typing import Mapping, Optional, Sequence


def make_mesh(axes: Optional[Mapping[str, int]] = None, devices=None):
    """Build a ``jax.sharding.Mesh``.

    ``axes`` maps axis name → size (e.g. ``{"data": 4, "block": 2}``); by
    default a 1-D ``{"data": n_devices}`` mesh over all local devices. Sizes
    must multiply to the device count used.
    """
    import jax
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"data": len(devices)}
    names: Sequence[str] = tuple(axes.keys())
    sizes = tuple(axes.values())
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(f"mesh axes {dict(axes)} need {total} devices, have {len(devices)}")
    mesh_devices = np.asarray(devices).reshape(sizes)
    return jax.sharding.Mesh(mesh_devices, names)
