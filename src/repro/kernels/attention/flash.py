"""Blocked causal flash attention (Pallas, TPU target).

The canonical Pallas-TPU pattern: grid (batch*heads, n_q_blocks,
n_k_blocks) with the k axis innermost; the output block index map
ignores the k coordinate so the same (BQ, dh) output tile is revisited
across k steps while running max / normalizer / accumulator live in
VMEM scratch.  MXU alignment: BQ, BK, dh are multiples of 128 in the
production config (tests sweep smaller interpret-mode tiles).

VMEM working set per step: q (BQ x dh) + k,v (BK x dh each) + acc
(BQ x dh) + m,l (BQ) — at BQ=BK=512, dh=128, f32 accumulation that is
~1.3 MB, leaving room for double buffering in the 16 MB/core VMEM.

Causality is enforced by masking within the diagonal block and by
skipping (masking to zero contribution) fully-future k blocks; the
wrapper truncates the k grid per q block is left to the compiler's
revisit schedule (structurally simple version — the production variant
would use a triangular grid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # (BQ, dh)
    k = k_ref[0]                                  # (BK, dh)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1)
    acc = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(ki == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "sm_scale"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           sm_scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q,k,v: (BH, S, dh) -> (BH, S, dh).  S % block == 0 (wrapper pads)."""
    bh, s, dh = q.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(dh))
    n_q = s // block_q
    n_k = s // block_k
    grid = (bh, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # normalizer l
            pltpu.VMEM((block_q, dh), jnp.float32),   # accumulator
        ],
        interpret=interpret,
    )(q, k, v)
