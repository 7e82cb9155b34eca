"""Ring attention: sequence-parallel exact attention on Shoal puts.

For long-context prefill the baseline sharding (heads over ``model``)
all-gathers K/V per layer and materializes O(S^2 / tp) score blocks.
Ring attention shards the *sequence* over the model axis instead: each
device owns an S/n slice of q, k, v; K/V blocks then rotate around the
ring — one ``lax.ppermute`` hop per step, i.e. exactly a Shoal one-sided
neighbor put (DESIGN.md: collective-permute *is* the AM Long put on
ICI) — while each device accumulates online-softmax partials for its
q slice.  n-1 hops of S/n-sized blocks replace the all-gathers, memory
falls from O(S^2) to O((S/n)^2) per step, and weights stay replicated
(this mode targets models whose weights fit per-device, cfg.tp=False).

This is the paper's technique applied where the paper could not go: the
same one-sided-put primitive, scheduled as a software systolic ring over
a pod.  Numerically exact (tested against the oracle); fully manual
shard_map so every collective is explicit and f32-safe.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _block_attend(q, k, v, q_pos, k_pos, scale):
    """Partial attention of a q block against one k/v block.

    q: (B,Sq,K,G,dh) k,v: (B,Sk,K,dh); returns (num (B,Sq,K,G,dh),
    denom (B,Sq,K,G), m (B,Sq,K,G)) in f32.
    """
    s = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    mask = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :] <= q_pos[:, :, None])
    s = jnp.where(mask[:, None, None], s, -1e30)
    m = jnp.max(s, axis=-1)                                  # (B,K,G,Sq)
    p = jnp.exp(s - m[..., None])
    denom = jnp.sum(p, axis=-1)
    num = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v).astype(jnp.float32)
    # reorder m, denom to (B,Sq,K,G)
    m = jnp.moveaxis(m, 3, 1)
    denom = jnp.moveaxis(denom, 3, 1)
    return num, denom, m


def ring_attention_local(q, k, v, q_pos, k_pos, *, axis: str, n: int,
                         scale: float):
    """Per-device body (inside fully-manual shard_map over ``axis``).

    q: (B,Sq,K,G,dh) local slice; k,v: (B,Sk,K,dh) local slice;
    q_pos/k_pos: (B,Sq)/(B,Sk) absolute positions (-1 = invalid).
    Returns (B,Sq,K,G,dh) exact causal attention output.
    """
    B, Sq, K, G, dh = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        k_cur, kp_cur, num, den, m = carry
        n_new, d_new, m_new = _block_attend(q, k_cur[0], k_cur[1], q_pos,
                                            kp_cur, scale)
        m_tot = jnp.maximum(m, m_new)
        a_old = jnp.exp(m - m_tot)
        a_new = jnp.exp(m_new - m_tot)
        num = num * a_old[..., None] + n_new * a_new[..., None]
        den = den * a_old + d_new * a_new
        # rotate the K/V block one hop around the ring (one-sided put)
        k_nxt = (lax.ppermute(k_cur[0], axis, perm),
                 lax.ppermute(k_cur[1], axis, perm))
        kp_nxt = lax.ppermute(kp_cur, axis, perm)
        return (k_nxt, kp_nxt, num, den, m_tot), ()

    num0 = jnp.zeros((B, Sq, K, G, dh), jnp.float32)
    den0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    m0 = jnp.full((B, Sq, K, G), -1e30, jnp.float32)
    (_, _, num, den, _), _ = lax.scan(
        step, ((k, v), k_pos, num0, den0, m0), None, length=n)
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(mesh, seq_axis: str, dp_axes: tuple, q, k, v, positions,
                   *, scale: float | None = None):
    """Global entry: q (B,S,K,G,dh), k/v (B,S,K,dh), positions (B,S); S
    sharded over ``seq_axis``, batch over ``dp_axes``.  Exact causal
    attention, O(S/n) resident K/V per device."""
    n = mesh.shape[seq_axis]
    dh = q.shape[-1]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(dh))

    fn = functools.partial(ring_attention_local, axis=seq_axis, n=n,
                           scale=scale)
    qspec = P(dp_axes, seq_axis, None, None, None)
    kspec = P(dp_axes, seq_axis, None, None)
    pspec = P(dp_axes, seq_axis)
    return shard_map(fn, mesh=mesh,
                         in_specs=(qspec, kspec, kspec, pspec, pspec),
                         out_specs=qspec, check_vma=False)(
        q, k, v, positions, positions)
