"""Pipeline parallelism on Shoal Medium AMs (GPipe-style, 2+ stages).

The paper's Medium AM is point-to-point payload delivery straight to a
kernel — exactly a pipeline stage handoff.  Stages map onto consecutive
ranks of a mesh axis (e.g. the ``pod`` axis: stage boundary = the DCN
link, the classic reason to pipeline across pods); microbatches stream
through a ``lax.scan`` whose per-tick communication is one
``lax.ppermute`` hop (the Medium AM's wire op).

Forward-only schedule with the standard GPipe bubble; autodiff through
the scan + ppermute gives the backward schedule for free (the transpose
of a ppermute is the reverse ppermute — the backward bubble mirrors the
forward one).

This is the minimal composable form: ``stage_fn(stage_params, x)`` is
any per-stage function with matching x shapes (e.g. a slice of a layer
stack).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def pipeline_apply(mesh, axis: str, stage_fn, stage_params, mbs):
    """Run ``mbs`` (M, mb, ...) microbatches through n_stages stages.

    ``stage_params``: pytree whose leaves have a leading n_stages dim
    (stage i's slice lives on rank i of ``axis``).  Returns the stage
    outputs for every microbatch, (M, mb, ...), produced on the LAST
    rank and broadcast back (so the caller can compute a loss anywhere).
    """
    n = mesh.shape[axis]
    M = mbs.shape[0]
    perm = [(i, i + 1) for i in range(n - 1)]          # stage i -> i+1

    def per_device(params_slice, mbs_local):
        params_slice = jax.tree.map(lambda x: x[0], params_slice)
        me = lax.axis_index(axis)
        ticks = M + n - 1

        def tick(carry, t):
            # inject microbatch t at stage 0; everyone runs its stage on
            # whatever arrived last tick; hand off via the Medium-AM hop
            inbox = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            my_in = jnp.where(me == 0, mbs_local[mb_idx], inbox)
            my_out = stage_fn(params_slice, my_in)
            handed = lax.ppermute(my_out, axis, perm)
            # the last stage's output this tick corresponds to
            # microbatch t - (n - 1); collect it
            done = my_out
            return handed, done

        _, outs = lax.scan(tick, jnp.zeros_like(mbs_local[0]),
                           jnp.arange(ticks))
        # outs: (ticks, mb, ...); valid last-stage outputs are ticks
        # n-1 .. M+n-2 on rank n-1.  Broadcast them to all ranks.
        valid = lax.dynamic_slice_in_dim(outs, n - 1, M, axis=0)
        from repro.core import collectives as coll
        out = coll.broadcast_from(valid, axis, n, root=n - 1)
        return out[None]

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False)
    out = fn(stage_params, mbs)
    # out: (n, M, mb, ...) — every rank holds the broadcast copy
    return out[0]


def split_stages(params_stacked, n_stages: int):
    """Split a layer-stacked param tree (L, ...) into (n_stages, L/n, ...)."""
    def one(x):
        L = x.shape[0]
        assert L % n_stages == 0
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])
    return jax.tree.map(one, params_stacked)
