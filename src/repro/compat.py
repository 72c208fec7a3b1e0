"""Thin wrappers over the jax API calls this repo makes in several places.

Everything that shards a body or builds a mesh goes through here, so the
spelling of those calls is decided once.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_rep=True):
    """``jax.shard_map`` with the replication check as ``check_rep``.

    Callers that shard a ``pallas_call`` body must disable it (no
    replication rule); jax spells the flag ``check_vma``.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
