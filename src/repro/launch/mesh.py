"""Production meshes (a function, never a module-level constant — importing
this module must not touch jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips.

    Axes are ``Auto``: the programs place shardings with ``NamedSharding``
    and let the partitioner propagate them (``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which e.g. the embedding gather of a
    vocab-sharded table has no inferable output sharding)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
