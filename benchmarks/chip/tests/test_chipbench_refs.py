"""The plain reference: against a loop written out by hand, and against
the program at a tiny size (Jacobi 64x64 on 1 kernel; the 4-kernel case
runs in chipbench_four_kernels.py)."""

import numpy as np

import chipbench_helpers as h
from reference import jacobi as jref


def test_jacobi_reference_matches_a_loop():
    g = np.random.default_rng(0).standard_normal((9, 7)).astype(np.float32)
    want = g.copy()
    for _ in range(3):
        prev = want.copy()
        for i in range(1, 8):
            for j in range(1, 6):
                want[i, j] = np.float32(0.25) * (
                    prev[i - 1, j] + prev[i + 1, j]
                    + prev[i, j - 1] + prev[i, j + 1])
    final, before = jref.solve(g, 3)
    np.testing.assert_allclose(np.asarray(final), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jref.step(before)),
                               np.asarray(final), rtol=0, atol=0)


def test_program_agrees_with_references_exactly():
    r = h.run_small("jacobi-4096.1chip")
    assert r["checks"]["grid_max_abs_err"]["value"] == 0.0, r["checks"]
    assert r["correct"]
