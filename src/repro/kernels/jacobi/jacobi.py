"""Blocked Jacobi stencil kernel (Pallas, TPU target).

TPU adaptation of the paper's VHDL compute core: instead of a
streaming-row systolic pipeline, we tile the grid into VMEM-resident
row bands sized for the vector unit.  Each program instance owns a
``(block_rows, N)`` band; the up/down halo rows arrive as two extra
row-shifted *views* of the padded input (three inputs, one standard
BlockSpec each — overlapping windows expressed as shifted views keeps
the index maps affine, which is what Mosaic wants).  Left/right
neighbors are in-band column shifts.

The input is one row band of a larger grid: ``top``/``bottom`` are the
halo rows just outside it and ``row0`` (scalar-prefetched) is the
global index of its first row, so the Dirichlet boundary mask stays
global when a Shoal kernel owns only a band.

VMEM budget: three input bands and one output band, each double
buffered, plus the kernel's band-sized temporaries.  N itself is never
blocked, so the wrapper (:mod:`repro.kernels.jacobi.ops`) shrinks
``block_rows`` as N grows: at N=4096 f32 a 256-row band (4 MiB each)
overflows v5e's scoped VMEM, 64 rows fit.  Rows are multiples of 8
(the f32 sublane tile); the column block is the full row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _jacobi_kernel(row0_ref, up_ref, mid_ref, down_ref, out_ref, *,
                   m_total: int, block_rows: int):
    i = pl.program_id(0)
    up = up_ref[...]
    mid = mid_ref[...]
    down = down_ref[...]
    rows, n = mid.shape

    left = jnp.roll(mid, 1, axis=1)     # column j-1
    right = jnp.roll(mid, -1, axis=1)   # column j+1
    stencil = 0.25 * (up + down + left + right)

    # masks: first/last global row and first/last column are boundary
    grow = (row0_ref[0] + i * block_rows
            + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0))
    gcol = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    interior = ((grow > 0) & (grow < m_total - 1)
                & (gcol > 0) & (gcol < n - 1))
    out_ref[...] = jnp.where(interior, stencil.astype(mid.dtype), mid)


@functools.partial(jax.jit,
                   static_argnames=("m_total", "block_rows", "interpret"))
def jacobi_step_pallas(x: jnp.ndarray, top: jnp.ndarray, bottom: jnp.ndarray,
                       row0, *, m_total: int, block_rows: int,
                       interpret: bool = False) -> jnp.ndarray:
    """One Jacobi iteration over the row band x (M, N) of an
    (m_total, N) grid; ``top``/``bottom`` (N,) are the rows above and
    below the band, ``row0`` the global row of ``x[0]``.
    M % block_rows == 0."""
    m, n = x.shape
    assert m % block_rows == 0, (m, block_rows)
    # row-shifted views with the halo rows attached
    up = jnp.concatenate([top[None].astype(x.dtype), x[:-1]], axis=0)
    down = jnp.concatenate([x[1:], bottom[None].astype(x.dtype)], axis=0)
    row0 = jnp.reshape(jnp.asarray(row0, jnp.int32), (1,))

    spec = pl.BlockSpec((block_rows, n), lambda i, r0: (i, 0))
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, m_total=m_total,
                          block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // block_rows,),
            in_specs=[spec, spec, spec],
            out_specs=spec),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype,
                                       vma=jax.typeof(x).vma),
        # the TPU-semantics interpreter: the generic one rejects the
        # kernel's mix of varying refs and invariant iotas in shard_map
        interpret=pltpu.InterpretParams() if interpret else False,
    )(row0, up, x, down)
