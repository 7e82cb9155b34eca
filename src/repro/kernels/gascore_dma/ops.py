"""shard_map wrapper for the RDMA ring all-reduce."""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.kernels.gascore_dma.gascore_dma import ring_allreduce_dma_local


def ring_allreduce_dma(mesh, axis_name: str, x, *, interpret: bool = False):
    """x: global (n*chunk,) array sharded over ``axis_name``; returns the
    all-reduced value with the same sharding (every shard = total sum of
    its position's blocks ... i.e. each device's block becomes the sum of
    all devices' blocks)."""
    n = mesh.shape[axis_name]

    def body(xl):
        return ring_allreduce_dma_local(xl, axis_name=axis_name, n=n,
                                        interpret=interpret)

    return shard_map(body, mesh=mesh, in_specs=P(axis_name),
                         out_specs=P(axis_name), check_vma=False)(x)
