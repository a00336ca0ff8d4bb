"""Production meshes. A FUNCTION (not a module-level constant) so importing
this module never touches jax device state — device count is locked on
first jax init, and only dryrun.py sets the 512-device XLA flag.

Axes are Auto: the models place tensors with ``with_sharding_constraint``
(``distributed/sharding.py``), which refers only to Auto axes, and
``jax.make_mesh`` otherwise makes them Explicit."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod of TPU v5e; 2x16x16 (pod, data, model)
    for the two-pod deployment. Requires 256 / 512 visible devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1D 'data' mesh (CPU tests)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))
