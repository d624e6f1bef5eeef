"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax
device state; the dry-run sets XLA_FLAGS for 512 host devices BEFORE
calling this.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules in this repo
    are ``PartitionSpec`` constraints for the compiler to propagate, which
    ``Explicit`` axes (``jax.make_mesh``'s default in JAX 0.9) refuse."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the same axis names (smoke tests / examples)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_local_data_mesh():
    """All local devices on the DATA axis (model=1).

    The mesh the ``--mesh`` launchers hand to ``ff.on_mesh``: the FF
    reductions partition over the data-parallel axis, so on a multi-device
    host the compensated cross-device combines actually engage
    (``make_local_mesh`` puts every device on 'model', leaving a size-1
    data axis — correct for TP layout experiments, inert for the mesh
    reduction tier)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
