"""Blocked Jacobi stencil kernel (Pallas, TPU target).

TPU adaptation of the paper's VHDL compute core: instead of a
streaming-row systolic pipeline, we tile the grid into VMEM-resident
row bands sized for the vector unit.  Each program instance owns a
``(block_rows, N)`` band of the input, which it reads once.  The rows
just above and below the band come from the two 8-row tiles that touch
it (two more BlockSpecs on the same input), or, at the first and last
grid step, from ``top``/``bottom``; the kernel shifts the band by a row
in VMEM and puts the halo rows in place.  Nothing band-sized is built
outside the call.  Left/right neighbors are in-band column shifts.

The input is one row band of a larger grid: ``top``/``bottom`` are the
halo rows just outside it and ``row0`` (scalar-prefetched) is the
global index of its first row, so the Dirichlet boundary mask stays
global when a Shoal kernel owns only a band.  A stack of bands (the
bands of several Shoal kernels on one chip, each with its own halo rows
and first row) runs as one call whose grid has a leading band axis.

VMEM budget: one input band, two 8-row halo tiles, the two halo rows
and one output band, each double buffered, plus the kernel's
band-sized temporaries.  N itself is never blocked, so the wrapper
(:mod:`repro.kernels.jacobi.ops`) shrinks ``block_rows`` as N grows: at
N=4096 f32 a 256-row band (4 MiB) overflows v5e's scoped VMEM, 128 rows
fit.  Rows are multiples of 8 (the f32 sublane tile); the column block
is the full row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 8   # rows of a halo tile: the f32 sublane tile


def _jacobi_kernel(row0_ref, mid_ref, above_ref, below_ref, top_ref,
                   bottom_ref, out_ref, *, m_total: int, block_rows: int,
                   stacked: bool):
    # a stack of bands: grid axis 0 picks the band, axis 1 the step
    step = 1 if stacked else 0
    i = pl.program_id(step)
    mid = mid_ref[...]
    rows, n = mid.shape
    # the rows just outside the band: the neighbouring tiles' edge rows,
    # or the halo rows at the ends of the input
    above = jnp.where(i == 0, top_ref[...], above_ref[_TILE - 1:, :])
    below = jnp.where(i == pl.num_programs(step) - 1, bottom_ref[...],
                      below_ref[:1, :])
    # row shifts in f32, exact for narrower floats: Mosaic rotates
    # 32-bit data only
    wide = mid.astype(jnp.float32)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
    up = jnp.where(r == 0, above,                       # row above
                   pltpu.roll(wide, 1, 0).astype(mid.dtype))
    down = jnp.where(r == rows - 1, below,
                     pltpu.roll(wide, rows - 1, 0).astype(mid.dtype))

    left = jnp.roll(mid, 1, axis=1)     # column j-1
    right = jnp.roll(mid, -1, axis=1)   # column j+1
    stencil = 0.25 * (up + down + left + right)

    # masks: first/last global row and first/last column are boundary
    grow = row0_ref[pl.program_id(0) if stacked else 0] + i * block_rows + r
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
    below the band, ``row0`` the global row of ``x[0]``.  A stack of
    bands x (B, M, N) takes ``top``/``bottom`` (B, N) and ``row0`` (B,).
    M % block_rows == 0 and block_rows % 8 == 0."""
    stacked = x.ndim == 3
    m, n = x.shape[-2:]
    assert m % block_rows == 0 and block_rows % _TILE == 0, (m, block_rows)
    tiles = block_rows // _TILE     # halo tiles per band
    if stacked:
        b = (None,)                 # the band axis, squeezed in the kernel
        row0 = jnp.reshape(jnp.asarray(row0, jnp.int32), (x.shape[0],))
        grid = (x.shape[0], m // block_rows)
        at = lambda f: lambda k, i, r0: (k, *f(i))  # noqa: E731
        top, bottom = top[:, None], bottom[:, None]
    else:
        b = ()
        row0 = jnp.reshape(jnp.asarray(row0, jnp.int32), (1,))
        grid = (m // block_rows,)
        at = lambda f: lambda i, r0: f(i)  # noqa: E731
        top, bottom = top[None], bottom[None]

    band = pl.BlockSpec((*b, block_rows, n), at(lambda i: (i, 0)))
    above = pl.BlockSpec(
        (*b, _TILE, n), at(lambda i: (jnp.maximum(i * tiles - 1, 0), 0)))
    below = pl.BlockSpec(
        (*b, _TILE, n),
        at(lambda i: (jnp.minimum((i + 1) * tiles, m // _TILE - 1), 0)))
    edge = pl.BlockSpec((*b, 1, n), at(lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, m_total=m_total,
                          block_rows=block_rows, stacked=stacked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[band, above, below, edge, edge],
            out_specs=band),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       vma=jax.typeof(x).vma),
        # the TPU-semantics interpreter: the generic one rejects the
        # kernel's mix of varying refs and invariant iotas in shard_map
        interpret=pltpu.InterpretParams() if interpret else False,
    )(row0, x, x, x, top.astype(x.dtype), bottom.astype(x.dtype))
