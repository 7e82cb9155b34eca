"""jit'd wrappers: pallas kernel, or the jnp oracle with ``use_pallas=False``."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.am_pack.am_pack import am_pack_pallas, am_unpack_pallas
from repro.kernels.am_pack.ref import am_pack_ref, am_unpack_ref


def am_pack(segment: jnp.ndarray, addr: int, stride: int, blk_words: int,
            nblocks: int, *, use_pallas: bool = True,
            interpret: bool = False) -> jnp.ndarray:
    if not use_pallas:
        return am_pack_ref(segment, addr, stride, blk_words, nblocks)
    return am_pack_pallas(segment, addr, stride=stride, blk_words=blk_words,
                          nblocks=nblocks, interpret=interpret)


def am_unpack(segment: jnp.ndarray, payload: jnp.ndarray, addr: int,
              stride: int, blk_words: int, nblocks: int, *,
              use_pallas: bool = True, interpret: bool = False) -> jnp.ndarray:
    if not use_pallas:
        return am_unpack_ref(segment, payload, addr, stride, blk_words, nblocks)
    return am_unpack_pallas(segment, payload, addr, stride=stride,
                            blk_words=blk_words, nblocks=nblocks,
                            interpret=interpret)
