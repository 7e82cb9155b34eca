"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True: the kernel body executes on CPU; TPU is the target)."""

import jax.numpy as jnp

from jax import shard_map
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.am_pack import am_pack, am_pack_ref, am_unpack, am_unpack_ref
from repro.kernels.attention import attention_ref, flash_attention
from repro.kernels.jacobi import jacobi_band_step, jacobi_step, jacobi_step_ref

RNG = np.random.default_rng(42)


# -- jacobi -------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(16, 128), (64, 128), (256, 256), (128, 512),
                                 (40, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_matches_ref(m, n, dtype):
    x = jnp.asarray(RNG.standard_normal((m, n)), dtype)
    got = jacobi_step(x, use_pallas=True, interpret=True)
    want = jacobi_step_ref(x)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bands", [2, 4])
def test_jacobi_band_step_matches_whole_grid(bands):
    """Row bands stepped with their halo rows and global row offsets
    reassemble the whole-grid step (the Shoal Jacobi app's call)."""
    m, n = 64, 128
    x = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    rows = m // bands
    zero = jnp.zeros((n,), jnp.float32)
    parts = []
    for b in range(bands):
        lo = b * rows
        top = x[lo - 1] if b else zero
        bottom = x[lo + rows] if b < bands - 1 else zero
        parts.append(jacobi_band_step(x[lo:lo + rows], top, bottom, lo,
                                      m_total=m, interpret=True))
    np.testing.assert_allclose(np.concatenate(parts),
                               np.asarray(jacobi_step_ref(x)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block_rows", [8, 16, 32])
@pytest.mark.parametrize("row0", [16, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_step_pallas_halo_rows(block_rows, row0, dtype):
    """The kernel called directly on a 32-row band of a 64-row grid:
    grid steps inside the band take their halo rows from the neighbouring
    8-row tiles, the first and last from ``top``/``bottom``, and the
    Dirichlet mask follows the global rows (the band at row 32 ends on
    the grid's last row)."""
    from repro.kernels.jacobi.jacobi import jacobi_step_pallas
    m, m_total, n = 32, 64, 128
    g = jnp.asarray(RNG.standard_normal((m_total + 1, n)) + 1.0, dtype)
    band, top, bottom = g[row0:row0 + m], g[row0 - 1], g[row0 + m]
    got = jacobi_step_pallas(band, top, bottom, row0, m_total=m_total,
                             block_rows=block_rows, interpret=True)
    want = jacobi_step_ref(g[:m_total])[row0:row0 + m]
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_jacobi_block_rows_fit_tile_and_vmem():
    from repro.kernels.jacobi.ops import _BAND_BYTES, _pick_block_rows
    assert _pick_block_rows(4096, 4096, 4) == 128  # 256 overflows VMEM
    for m, n in [(4096, 4096), (1024, 4096), (2048, 2048), (512, 512),
                 (40, 128), (24, 1 << 16)]:
        b = _pick_block_rows(m, n, 4)
        assert b % 8 == 0 and m % b == 0, (m, n, b)
        assert b * n * 4 <= max(_BAND_BYTES, 8 * n * 4), (m, n, b)
    with pytest.raises(ValueError, match="multiple of 8"):
        _pick_block_rows(12, 128, 4)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_jacobi_app_matches_reference(use_pallas):
    """The Shoal Jacobi app on one kernel, jnp or Pallas stencil."""
    from repro.apps.jacobi import JacobiApp, jacobi_reference
    grid = RNG.standard_normal((64, 128)).astype(np.float32)[:, :64]
    app = JacobiApp(n=64, kernels=1, iters=5, use_pallas=use_pallas,
                    interpret=True)
    np.testing.assert_allclose(app.run(grid), jacobi_reference(grid, 5),
                               rtol=1e-5, atol=1e-5)


def test_jacobi_boundary_fixed():
    x = jnp.asarray(RNG.standard_normal((32, 128)), jnp.float32)
    out = jacobi_step(x, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out)[0], np.asarray(x)[0])
    np.testing.assert_array_equal(np.asarray(out)[-1], np.asarray(x)[-1])
    np.testing.assert_array_equal(np.asarray(out)[:, 0], np.asarray(x)[:, 0])
    np.testing.assert_array_equal(np.asarray(out)[:, -1], np.asarray(x)[:, -1])


def test_jacobi_converges_to_laplace():
    """1024 iterations drive the interior toward the harmonic solution."""
    n = 32
    x = jnp.zeros((n, 128), jnp.float32).at[0, :].set(1.0)
    from repro.kernels.jacobi import jacobi_run
    out = jacobi_run(x, 512, use_pallas=False)
    # top-adjacent interior rows approach the linear profile; just check
    # monotone decay and boundedness
    col = np.asarray(out)[:, 64]
    assert col[0] == 1.0
    assert np.all(np.diff(col[:n // 2]) <= 1e-6)
    assert np.all((col >= -1e-6) & (col <= 1.0 + 1e-6))


# -- am_pack ------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    addr=st.integers(0, 50),
    stride=st.integers(8, 40),
    blk=st.integers(1, 8),
    nblocks=st.integers(1, 6),
)
def test_am_pack_property(addr, stride, blk, nblocks):
    blk = min(blk, stride)   # non-overlapping blocks
    seg = jnp.asarray(RNG.standard_normal(512), jnp.float32)
    got = am_pack(seg, addr, stride, blk, nblocks, interpret=True)
    want = am_pack_ref(seg, addr, stride, blk, nblocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@settings(max_examples=30, deadline=None)
@given(
    addr=st.integers(0, 50),
    stride=st.integers(8, 40),
    blk=st.integers(1, 8),
    nblocks=st.integers(1, 6),
)
def test_am_unpack_property(addr, stride, blk, nblocks):
    blk = min(blk, stride)
    seg = jnp.asarray(RNG.standard_normal(512), jnp.float32)
    pay = jnp.asarray(RNG.standard_normal(blk * nblocks), jnp.float32)
    got = am_unpack(seg, pay, addr, stride, blk, nblocks, interpret=True)
    want = am_unpack_ref(seg, pay, addr, stride, blk, nblocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_pack_unpack_roundtrip():
    seg = jnp.asarray(RNG.standard_normal(1024), jnp.float32)
    pay = am_pack(seg, 100, 64, 32, 8, interpret=True)
    seg2 = am_unpack(jnp.zeros_like(seg), pay, 100, 64, 32, 8,
                     interpret=True)
    idx = (100 + 64 * np.arange(8)[:, None] + np.arange(32)[None]).reshape(-1)
    np.testing.assert_allclose(np.asarray(seg2)[idx], np.asarray(seg)[idx])


# -- flash attention ----------------------------------------------------------

@pytest.mark.parametrize("bh,s,dh,blk", [
    (2, 256, 64, 128), (4, 128, 128, 64), (1, 512, 64, 128),
    (2, 200, 64, 64),                       # padded path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(bh, s, dh, blk, dtype):
    q = jnp.asarray(RNG.standard_normal((bh, s, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((bh, s, dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((bh, s, dh)), dtype)
    got = flash_attention(q, k, v, block_q=blk, block_k=blk, interpret=True)
    want = attention_ref(q, k, v)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_gascore_dma_single_device_identity():
    """n=1 ring degenerates to identity (the multi-device RDMA path runs
    in tests/md_checks.py under 8 host devices)."""
    import jax
    from repro.kernels.gascore_dma.gascore_dma import ring_allreduce_dma_local
    from repro.runtime.topology import make_cpu_mesh
    from jax.sharding import PartitionSpec as P
    mesh = make_cpu_mesh(1, ("x",))
    x = jnp.asarray(RNG.standard_normal(128), jnp.float32)
    out = shard_map(
        lambda v: ring_allreduce_dma_local(v, axis_name="x", n=1,
                                           interpret=True),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_flash_is_causal():
    """Changing future keys must not change earlier outputs."""
    bh, s, dh = 1, 256, 64
    q = jnp.asarray(RNG.standard_normal((bh, s, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((bh, s, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((bh, s, dh)), jnp.float32)
    out1 = flash_attention(q, k, v, interpret=True)
    k2 = k.at[:, s // 2:].set(RNG.standard_normal((bh, s // 2, dh)))
    v2 = v.at[:, s // 2:].set(RNG.standard_normal((bh, s // 2, dh)))
    out2 = flash_attention(q, k2, v2, interpret=True)
    np.testing.assert_allclose(np.asarray(out1)[:, :s // 2],
                               np.asarray(out2)[:, :s // 2], rtol=1e-5)
