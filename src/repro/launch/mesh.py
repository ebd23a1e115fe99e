"""Production mesh builders (functions, not module constants — importing
this module never touches jax device state).

Axes are ``Auto``: the model code places activations with
``with_sharding_constraint`` (``parallel.sharding.shard_activation``),
which only accepts axes the partitioner is free to propagate."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2x16x16 = 512 across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke runs of the launch path."""
    return _auto_mesh((1, 1), ("data", "model"))
