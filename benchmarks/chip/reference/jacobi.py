"""Plain reference of the paper's Jacobi solve (arXiv:2104.12350, Sec. IV-C).

Each iteration sets every interior cell of the ``(n, n)`` grid to the
mean of its four von Neumann neighbours, in the order up + down + left
+ right, and holds the boundary rows and columns fixed (Dirichlet).  One
whole grid on one device, no bands, no halos, no kernels: nothing of the
program under test.  ``dtype`` is the precision the iterations run in;
the benchmark's control runs it in bfloat16, one step below the float32
the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def step(x: jnp.ndarray) -> jnp.ndarray:
    interior = 0.25 * (x[:-2, 1:-1] + x[2:, 1:-1]
                       + x[1:-1, :-2] + x[1:-1, 2:])
    return x.at[1:-1, 1:-1].set(interior.astype(x.dtype))


@functools.partial(jax.jit, static_argnames=("iters", "dtype"))
def solve(grid: jnp.ndarray, iters: int, dtype=jnp.float32):
    """``iters`` iterations from ``grid``; returns ``(final, before_last)``
    in float32 (``before_last`` is the grid the last iteration read, whose
    rows are the halos that iteration exchanged)."""
    x = grid.astype(dtype)
    before = jax.lax.fori_loop(0, iters - 1, lambda _, g: step(g), x)
    return (step(before).astype(jnp.float32),
            before.astype(jnp.float32))
