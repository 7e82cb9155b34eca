"""jit'd wrapper: pad to block multiples, reshape heads, kernel/oracle."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.attention.flash import flash_attention_pallas
from repro.kernels.attention.ref import attention_ref


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, use_pallas: bool = True,
                    interpret: bool = False):
    """q,k,v: (BH, S, dh).  Pads S up to a block multiple (padded key rows
    are masked out by causality given padded queries are discarded)."""
    if not use_pallas:
        return attention_ref(q, k, v, causal=causal)
    bh, s, dh = q.shape
    blk = max(block_q, block_k)
    pad = (-s) % blk
    if pad:
        padf = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        out = flash_attention_pallas(padf(q), padf(k), padf(v), causal=True,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret)
        return out[:, :s]
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)
