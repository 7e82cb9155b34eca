"""Quickstart: the Shoal PGAS API in 60 lines.

Emulates an 8-kernel cluster on CPU, then: one-sided puts, a remote
accumulate, a get, a barrier, and a ring all-reduce built from puts.

    PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core import collectives, handlers as hd, ops
from repro.core.address_space import GlobalAddressSpace
from repro.core.state import ShoalContext
from repro.runtime import TCP, make_cpu_mesh

N = 8
mesh = make_cpu_mesh(N, ("kernel",))
ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                   segment_words=64)
gas = GlobalAddressSpace(ctx)
ring = [(i, (i + 1) % N) for i in range(N)]


def program(state):
    me = ctx.my_id()
    # 1. one-sided put: my rank, times 4 words, into my successor's segment
    payload = jnp.full((4,), me + 1, jnp.float32)
    state = ops.put_long(ctx, state, payload, ring, dst_addr=0, token=1)
    state = ops.wait_replies(ctx, state, token=1, n=1)
    # 2. remote accumulate (Long put with the ADD handler)
    state = ops.put_long(ctx, state, jnp.ones(4), ring, dst_addr=0,
                         handler=hd.H_ADD, token=2)
    state = ops.wait_replies(ctx, state, token=2, n=1)
    # 3. barrier, then one-sided get from my successor
    state = ops.barrier(ctx, state)
    state, fetched = ops.get_medium(ctx, state, ring, src_addr=0, nwords=4,
                                    token=3)
    state = ops.wait_replies(ctx, state, token=3, n=1)
    from repro.core.gascore import dataclasses_replace
    state = dataclasses_replace(
        state, segment=jax.lax.dynamic_update_slice(state.segment, fetched,
                                                    (8,)))
    return state


state = jax.jit(gas.spmd(program))(gas.make_global_state())
seg = np.asarray(state.segment)
print("segment[0:4] per kernel (predecessor rank+1, +1 accumulated):")
print(seg[:, 0:4])
print("fetched from successor (segment[8:12]):")
print(seg[:, 8:12])

# ring all-reduce built from one-sided puts
xs = jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4)
total = jax.jit(shard_map(
    lambda x: collectives.ring_all_reduce(x, ("kernel",), N), mesh=mesh,
    in_specs=P("kernel"), out_specs=P("kernel")))(xs)
print("ring all-reduce (every kernel holds the column sums):")
print(np.asarray(total)[0], "== expected", np.asarray(xs).sum(0))
assert np.allclose(np.asarray(total)[0], np.asarray(xs).sum(0))
print("quickstart OK")
