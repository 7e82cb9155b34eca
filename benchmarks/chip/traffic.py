"""The one generator that turns a traffic file and a seed into inputs.

A traffic file (``traffic/<name>.json``) holds parameters only.  Its
``kind`` says which shape it takes:

* ``solves``: back-to-back solves of a grid; ``kernels`` splits the grid
  by rows, ``grids`` is how many distinct seeded grids the solves cycle
  through.

The sizes and order of the work are the same for every seed; the seed
draws the values.
"""

from __future__ import annotations

import numpy as np


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` 32-bit words from any whole-number seed (seeds may exceed 32
    bits, and may be negative)."""
    return np.random.SeedSequence(seed % (1 << 64)).generate_state(n)


def jax_key(seed: int):
    import jax

    w = seed_words(seed, 1)[0]
    return jax.random.key(int(w))


def solve_grids(n: int, count: int, seed: int, shape=None, sharding=None):
    """``count`` seeded ``(n, n)`` float32 grids on the device, made in one
    jitted call, each reshaped to ``shape`` and placed by ``sharding``
    when given."""
    import jax
    import jax.numpy as jnp

    def make(key):
        return tuple(jax.random.normal(k, (n, n), jnp.float32)
                     .reshape(shape or (n, n))
                     for k in jax.random.split(key, count))

    out = None if sharding is None else (sharding,) * count
    return jax.jit(make, out_shardings=out)(jax_key(seed))
