"""Thin wrappers over the JAX mesh and ``shard_map`` API this repo uses.

``make_mesh`` pins every axis to ``AxisType.Auto``: ``jax.make_mesh``
defaults to ``Explicit`` axes, under which the host-side gathers of the
sharded backend (membership, crash/repair, the L1 paths) raise
``ShardingTypeError``.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(fn, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
