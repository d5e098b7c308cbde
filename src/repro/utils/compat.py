"""Mesh and ``shard_map`` helpers shared by the distributed code.

Written for the installed jax (0.9): ``jax.shard_map``, ``jax.lax.axis_size``
and meshes with explicit ``AxisType.Auto`` axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with replication (vma) checking off.

    ``pallas_call`` has no replication rule, so bodies that launch Pallas
    kernels — e.g. ``mcscan``'s fused blocked pipeline or the Pallas-method
    distributed operators in ``repro.core.dist_ops`` — must disable the
    check.  With checking off, jax no longer *verifies* that values under
    replicated ``out_specs`` are identical across shards.  That trade is safe
    here: every unchecked body in this repo only ever returns (a) per-shard
    outputs under sharded specs or (b) values produced by ``psum`` /
    ``all_gather``, which are replicated by construction; the multi-device
    parity suites (``tests/test_distributed.py``, ``tests/test_dist_ops.py``)
    verify the gathered results against the single-device siblings.
    """
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
