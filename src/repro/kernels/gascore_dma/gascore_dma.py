"""The GAScore as a Pallas kernel: ring all-reduce on one-sided RDMA.

This is the most literal TPU realization of the paper's contribution:
``pltpu.make_async_remote_copy`` *is* a one-sided Long AM put — a DMA
engine writes a payload into a remote chip's memory with no receiver
code — and the DMA semaphores are the AM reply/credit counters
(the GAScore's hold-buffer ordering becomes ``copy.wait()``).  The ring
all-reduce below is the Long-put-with-ADD-handler datapath (paper
Sec. III-C) scheduled around the ICI ring, the hardware twin of
:func:`repro.core.collectives.ring_all_reduce` (which expresses the same
schedule through XLA collective-permutes).

Algorithm (all-gather-reduce ring, n-1 steps): every device pushes its
``carry`` block to its right neighbor's inbox slot and accumulates what
arrived from the left.  Double-buffered inbox; in a production kernel a
reverse *capacity* semaphore ring would guard slot reuse beyond the
1-step slack (the AM credit counter, again) — interpret mode and
lockstep grids do not need it, so it is omitted here for clarity.

Validated in interpret mode (Pallas distributed interpret executes the
remote DMAs across the host devices); on real v5e this lowers to ICI
RDMA.  VMEM: 3 chunk-sized buffers + the output — chunks up to ~1 MW
f32 fit comfortably.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ring_kernel(x_ref, o_ref, carry, inbox, send_sem, recv_sem, *,
                 axis_name: str, n: int):
    me = lax.axis_index(axis_name)
    right = lax.rem(me + 1, n)

    o_ref[...] = x_ref[...]
    carry[...] = x_ref[...]

    def step(t, _):
        slot = lax.rem(t, 2)
        # one-sided Long put of my carry into the right neighbor's inbox
        copy = pltpu.make_async_remote_copy(
            src_ref=carry, dst_ref=inbox.at[slot],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)
        copy.start()
        copy.wait()          # send drained + my inbox filled (the "reply")
        carry[...] = inbox[slot]          # what my left neighbor sent
        o_ref[...] = o_ref[...] + carry[...]   # the ADD handler
        return 0

    lax.fori_loop(0, n - 1, step, 0)


@functools.partial(jax.jit, static_argnames=("axis_name", "n", "interpret"))
def ring_allreduce_dma_local(x, *, axis_name: str, n: int,
                             interpret: bool = False):
    """Per-device body (inside shard_map over ``axis_name``).
    x: (chunk,) local block -> (chunk,) sum over all n devices."""
    chunk = x.shape[0]
    return pl.pallas_call(
        functools.partial(_ring_kernel, axis_name=axis_name, n=n),
        out_shape=jax.ShapeDtypeStruct((chunk,), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((chunk,), x.dtype),        # carry
            pltpu.VMEM((2, chunk), x.dtype),      # double-buffered inbox
            pltpu.SemaphoreType.DMA,              # send
            pltpu.SemaphoreType.DMA,              # recv
        ],
        interpret=interpret,
    )(x)
