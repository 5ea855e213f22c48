"""Production mesh construction.

Single pod: (data=16, model=16) — one v5e pod, 256 chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the "pod" axis extends
data parallelism so only gradient/FSDP reductions cross pods.

`make_production_mesh` is a FUNCTION (not module-level state) so importing
this module never touches jax device state; the dry-run entrypoint sets
XLA_FLAGS before any jax import to get 512 host placeholder devices.
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (n,), ("data",)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
