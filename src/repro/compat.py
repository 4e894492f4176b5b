"""The repo's two entry points into JAX's sharding API.

Every explicit-collective module routes ``shard_map`` through one wrapper
(replication check off), and every mesh is built by :func:`make_mesh`
with Auto axes: ``jax.make_mesh`` defaults to Explicit axes, under which
``with_sharding_constraint`` and GSPMD-partitioned gathers refuse the
repo's sharding rules.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def shard_map(fn, mesh, in_specs, out_specs):
    """jax.shard_map with the replication/VMA check disabled."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """A mesh of ``shape`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )
