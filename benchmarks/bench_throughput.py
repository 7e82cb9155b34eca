import os

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

"""Fig. 6: sustained throughput across topologies.

Measured: K back-to-back async Long puts per compiled call (pipelined,
no per-message reply wait — the paper's non-blocking case), payload
goodput in MB/s on the CPU host.  Derived: modeled TPU link goodput
(header overhead included).  Also compares the shoal ring all-reduce vs
the fused XLA all-reduce (the backend delta the trainer exposes).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core import collectives as coll
from repro.core import ops
from repro.core.address_space import GlobalAddressSpace
from repro.core.state import ShoalContext
from repro.runtime import UDP, LinkClass, model_throughput_Bps
from repro.runtime.topology import make_mesh

from benchmarks._timing import time_fn

PAYLOAD_BYTES = [64, 512, 4096, 32768]
K = 16   # messages per call
N = 8


def main():
    mesh = make_mesh((2, 4), ("pod", "chip"))
    ctx = ShoalContext(mesh=mesh, axes=("pod", "chip"), transport=UDP,
                       segment_words=32768 // 4 + 8)
    gas = GlobalAddressSpace(ctx)
    state0 = gas.make_global_state()
    topos = [
        ("same-kernel", [(i, i) for i in range(N)], LinkClass.LOCAL),
        ("intra-pod", [(0, 1), (1, 2), (2, 3), (3, 0),
                       (4, 5), (5, 6), (6, 7), (7, 4)], LinkClass.ICI),
        ("inter-pod", [(i, (i + 4) % 8) for i in range(8)], LinkClass.DCN),
    ]
    for topo, pattern, link in topos:
        for pb in PAYLOAD_BYTES:
            nw = pb // 4

            def prog(st):
                pay = jnp.ones((nw,), jnp.float32)
                for t in range(K):
                    st = ops.put_long(ctx, st, pay, pattern, dst_addr=0,
                                      token=0, asynchronous=True)
                return st

            us = time_fn(jax.jit(gas.spmd(prog)), state0, iters=10)
            mbps = (K * pb) / (us / 1e6) / 1e6
            model_mbps = model_throughput_Bps(UDP, link, pb) / 1e6
            print(f"tput/long-async/{topo}/{pb}B,{us/K:.1f},{mbps:.1f}")
            print(f"tput/long-async-modelMBs/{topo}/{pb}B,0.0,{model_mbps:.1f}")

    # shoal ring vs fused XLA all-reduce (1 MB payload over all 8 kernels)
    x = jnp.ones((8, 32768), jnp.float32)
    ring = jax.jit(shard_map(
        lambda v: coll.ring_all_reduce(v, ("pod", "chip"), 8), mesh=mesh,
        in_specs=P(("pod", "chip")), out_specs=P(("pod", "chip"))))
    fused = jax.jit(shard_map(
        lambda v: jax.lax.psum(v, ("pod", "chip")), mesh=mesh,
        in_specs=P(("pod", "chip")), out_specs=P(("pod", "chip"))))
    us_ring = time_fn(ring, x, iters=10)
    us_fused = time_fn(fused, x, iters=10)
    print(f"allreduce/shoal-ring/1MB,{us_ring:.1f},{131072/us_ring:.1f}")
    print(f"allreduce/xla-fused/1MB,{us_fused:.1f},{131072/us_fused:.1f}")


if __name__ == "__main__":
    main()
