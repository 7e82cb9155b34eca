"""Strided AM pack/unpack kernels (Pallas, TPU target).

This is the GAScore's DataMover datapath for Strided Long AMs (paper
Sec. III-A/III-C): gathering a strided region of the shared-memory
segment into a contiguous wire payload, and scattering on ingress.

TPU adaptation: the FPGA DataMover issues one AXI burst per block; here
each grid step copies one block from the segment (kept whole in VMEM —
segments are small by construction; an HBM-resident variant would swap
the in_spec to ANY and ``pl.ds`` DMA per block) into its slot of the
packed payload.  ``blk_words`` is padded to the 128-lane boundary by the
wrapper so every copy is lane-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pack_kernel(seg_ref, out_ref, *, addr, stride, blk_words):
    i = pl.program_id(0)
    start = addr + i * stride
    out_ref[...] = jax.lax.dynamic_slice(seg_ref[...], (start,), (blk_words,))


def _unpack_kernel(pay_ref, seg_in_ref, seg_ref, *, addr, stride, blk_words,
                   nblocks):
    # single program: sequential scatter of all blocks (stride may alias)
    def body(i, seg):
        blk = jax.lax.dynamic_slice(pay_ref[...], (i * blk_words,), (blk_words,))
        return jax.lax.dynamic_update_slice(seg, blk, (addr + i * stride,))
    seg_ref[...] = jax.lax.fori_loop(0, nblocks, body, seg_in_ref[...])


@functools.partial(jax.jit, static_argnames=("addr", "stride", "blk_words",
                                             "nblocks", "interpret"))
def am_pack_pallas(segment: jnp.ndarray, addr: int, *, stride: int,
                   blk_words: int, nblocks: int,
                   interpret: bool = False) -> jnp.ndarray:
    S = segment.shape[0]
    return pl.pallas_call(
        functools.partial(_pack_kernel, addr=addr, stride=stride,
                          blk_words=blk_words),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((S,), lambda i: (0,))],
        out_specs=pl.BlockSpec((blk_words,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nblocks * blk_words,), segment.dtype),
        interpret=interpret,
    )(segment)


@functools.partial(jax.jit, static_argnames=("addr", "stride", "blk_words",
                                             "nblocks", "interpret"))
def am_unpack_pallas(segment: jnp.ndarray, payload: jnp.ndarray, addr: int, *,
                     stride: int, blk_words: int, nblocks: int,
                     interpret: bool = False) -> jnp.ndarray:
    S = segment.shape[0]
    P = payload.shape[0]
    return pl.pallas_call(
        functools.partial(_unpack_kernel, addr=addr, stride=stride,
                          blk_words=blk_words, nblocks=nblocks),
        grid=(1,),
        in_specs=[pl.BlockSpec((P,), lambda i: (0,)),
                  pl.BlockSpec((S,), lambda i: (0,))],
        out_specs=pl.BlockSpec((S,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((S,), segment.dtype),
        input_output_aliases={1: 0},   # in-place segment update
        interpret=interpret,
    )(payload, segment)
