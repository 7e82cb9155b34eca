"""Math oracles for the recurrent blocks: the chunkwise/scan-parallel
forms must match naive sequential recurrences."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.recurrent import _lru_scan
from repro.models.xlstm import _chunk_mlstm

RNG = np.random.default_rng(0)


def test_lru_scan_matches_sequential():
    B, S, D = 2, 33, 8
    a = jnp.asarray(RNG.uniform(0.5, 0.99, (B, S, D)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((B, S, D)), jnp.float32)
    got = _lru_scan(a, b)
    h = np.zeros((B, D), np.float32)
    want = np.zeros((B, S, D), np.float32)
    an, bn = np.asarray(a), np.asarray(b)
    for t in range(S):
        h = an[:, t] * h + bn[:, t]
        want[:, t] = h
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def _mlstm_sequential(q, k, v, logf, logi):
    """Naive stabilized mLSTM recurrence (xLSTM paper eqs.)."""
    B, S, nh, dh = q.shape
    C = np.zeros((B, nh, dh, dh), np.float64)
    n = np.zeros((B, nh, dh), np.float64)
    m = np.full((B, nh), -1e30)
    out = np.zeros((B, S, nh, dh), np.float64)
    q, k, v = np.asarray(q, np.float64), np.asarray(k, np.float64), np.asarray(v, np.float64)
    logf, logi = np.asarray(logf, np.float64), np.asarray(logi, np.float64)
    for t in range(S):
        m_new = np.maximum(logf[:, t] + m, logi[:, t])
        f = np.exp(logf[:, t] + m - m_new)
        i = np.exp(logi[:, t] - m_new)
        C = f[..., None, None] * C + i[..., None, None] * np.einsum(
            "bhd,bhe->bhde", k[:, t], v[:, t])
        n = f[..., None] * n + i[..., None] * k[:, t]
        m = m_new
        num = np.einsum("bhd,bhde->bhe", q[:, t], C) / np.sqrt(dh)
        den = np.abs(np.einsum("bhd,bhd->bh", q[:, t], n)) / np.sqrt(dh)
        den = np.maximum(den, np.exp(-m))
        out[:, t] = num / den[..., None]
    return out


def test_chunk_mlstm_matches_sequential():
    B, S, nh, dh = 1, 32, 2, 8
    q = jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
    logf = jnp.asarray(np.log(RNG.uniform(0.6, 0.95, (B, S, nh))), jnp.float32)
    logi = jnp.asarray(RNG.standard_normal((B, S, nh)) * 0.5, jnp.float32)
    got, final = _chunk_mlstm(q, k, v, logf, logi, chunk=8)
    want = _mlstm_sequential(q, k, v, logf, logi)
    # the chunk form uses a per-sequence stabilizer (vs running max), so
    # the DENOMINATOR FLOOR can differ when |q.n| is tiny; tolerances are
    # loose there but the bulk must agree tightly.
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=2e-2, atol=2e-2)


def test_chunk_mlstm_grads_finite_at_long_chunks():
    """Above the diagonal the decay exponent reaches hundreds at chunk
    256 (the xlstm-350m setting); the masked weights must not overflow
    into the backward pass."""
    B, S, nh, dh = 1, 256, 2, 8
    q, k, v = (jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
               for _ in range(3))
    logf = jnp.asarray(np.log(RNG.uniform(0.4, 0.6, (B, S, nh))), jnp.float32)
    logi = jnp.asarray(RNG.standard_normal((B, S, nh)) * 0.5, jnp.float32)

    def loss(q, logf):
        return jnp.sum(_chunk_mlstm(q, k, v, logf, logi, chunk=S)[0])

    grads = jax.grad(loss, argnums=(0, 1))(q, logf)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_slstm_grads_bounded_at_full_width():
    """The recurrent weights are scaled by the per-head fan-in dh; scaled
    by the head count instead, the backward pass through a long scan at
    the xlstm-350m width (d 1024, 4 heads) grows to ~1e18."""
    from repro.models.xlstm import init_slstm, slstm_block
    d, nh, S = 1024, 4, 256
    p = init_slstm(jax.random.PRNGKey(0), d, nh)
    x = jnp.asarray(RNG.standard_normal((1, S, d)), jnp.float32)
    g = jax.grad(lambda x: jnp.sum(slstm_block(p, x, nh=nh)[0][:, -1]))(x)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.max(jnp.abs(g))) < 1e3


def test_chunk_mlstm_final_state_continues():
    """Chunked prefill final state == sequential recurrence state, so a
    decode continuation is consistent."""
    B, S, nh, dh = 1, 16, 2, 4
    q = jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, nh, dh)), jnp.float32)
    logf = jnp.asarray(np.log(RNG.uniform(0.7, 0.95, (B, S, nh))), jnp.float32)
    logi = jnp.asarray(RNG.standard_normal((B, S, nh)) * 0.3, jnp.float32)
    _, (C_T, n_T, m_T) = _chunk_mlstm(q, k, v, logf, logi, chunk=4)
    # sequential reference state (rescale both to the unstabilized frame)
    Cs = np.zeros((B, nh, dh, dh)); ns = np.zeros((B, nh, dh))
    lf, li = np.asarray(logf, np.float64), np.asarray(logi, np.float64)
    kn, vn = np.asarray(k, np.float64), np.asarray(v, np.float64)
    for t in range(S):
        f = np.exp(lf[:, t]); i = np.exp(li[:, t])
        Cs = f[..., None, None] * Cs + i[..., None, None] * np.einsum(
            "bhd,bhe->bhde", kn[:, t], vn[:, t])
        ns = f[..., None] * ns + i[..., None] * kn[:, t]
    scale = np.exp(np.asarray(m_T, np.float64))          # C_true = e^m C_stab
    np.testing.assert_allclose(np.asarray(C_T, np.float64)
                               * scale[..., None, None], Cs, rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(n_T, np.float64)
                               * scale[..., None], ns, rtol=1e-3, atol=1e-3)
