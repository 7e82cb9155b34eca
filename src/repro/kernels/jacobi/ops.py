"""jit'd wrappers for the Jacobi kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.jacobi.jacobi import jacobi_step_pallas
from repro.kernels.jacobi.ref import jacobi_step_ref

# Bytes of one (block_rows, N) band.  The input and output bands, each
# double buffered, plus the kernel's band-sized temporaries must fit
# v5e's scoped VMEM; 4 MiB bands (256 rows at N=4096 f32) do not, 2 MiB
# bands do, and over-read half as much as 1 MiB bands for the two 8-row
# halo tiles each band step fetches (faster on a v5e).
_BAND_BYTES = 2 << 20


def _pick_block_rows(m: int, n: int, itemsize: int) -> int:
    """Largest multiple of 8 dividing ``m`` whose band fits the budget."""
    if m % 8:
        raise ValueError(f"the Pallas Jacobi stencil needs a multiple of 8 "
                         f"rows per band, got {m}")
    b = max(8, min(m, _BAND_BYTES // (n * itemsize)) // 8 * 8)
    while m % b:
        b -= 8
    return b


def jacobi_band_step(band: jnp.ndarray, top: jnp.ndarray, bottom: jnp.ndarray,
                     row0, *, m_total: int,
                     interpret: bool = False) -> jnp.ndarray:
    """One Pallas iteration over a row band of an (m_total, N) grid, given
    the halo rows just above/below it and its first global row.  Under
    ``vmap`` (several Shoal kernels on one chip) the bands run as one
    call over their stack."""
    m, n = band.shape
    return _band_stepper(m_total, _pick_block_rows(m, n, band.dtype.itemsize),
                         interpret)(band, top, bottom, row0)


@functools.cache
def _band_stepper(m_total: int, block_rows: int, interpret: bool):
    kw = dict(m_total=m_total, block_rows=block_rows, interpret=interpret)

    @custom_vmap
    def step(band, top, bottom, row0):
        return jacobi_step_pallas(band, top, bottom, row0, **kw)

    @step.def_vmap
    def _stack(axis_size, in_batched, *args):
        # the row0 of each band is prefetched: batching it would make
        # the generic rule loop over the bands, one call each
        args = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, batched in zip(map(jnp.asarray, args), in_batched)]
        return jacobi_step_pallas(*args, **kw), True

    return step


def jacobi_step(x: jnp.ndarray, *, use_pallas: bool = True,
                interpret: bool = False) -> jnp.ndarray:
    """One iteration over a whole grid; pallas kernel or jnp oracle."""
    if not use_pallas:
        return jacobi_step_ref(x)
    zero = jnp.zeros((x.shape[1],), x.dtype)
    return jacobi_band_step(x, zero, zero, 0, m_total=x.shape[0],
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("iters", "use_pallas", "interpret"))
def jacobi_run(x: jnp.ndarray, iters: int, *, use_pallas: bool = False,
               interpret: bool = False) -> jnp.ndarray:
    """``iters`` Jacobi iterations (lax.fori_loop over the step)."""
    def body(_, g):
        return jacobi_step(g, use_pallas=use_pallas, interpret=interpret)
    return jax.lax.fori_loop(0, iters, body, x)
