"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory), after
arXiv:2405.04517.  The assigned xlstm-350m config has d_ff = 0: the
"MLP" lives inside the blocks themselves (mLSTM up/down projection
factor 2; sLSTM with a 4/3 gated MLP after the cell).

mLSTM is evaluated *chunkwise* for training/prefill: within a chunk the
quadratic (attention-like) form, across chunks a recurrence on the
(nh, dh, dh) matrix memory — linear in sequence length, which is why
this arch runs the ``long_500k`` shape.  Decode carries (C, n, m) per
layer.  sLSTM has a genuine sequential dependency through its recurrent
weights R (the xLSTM paper notes it is not parallelizable); we evaluate
it with ``lax.scan`` over time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import blocks as bl


# --------------------------------------------------------------------------
# mLSTM
# --------------------------------------------------------------------------

def init_mlstm(key, d, nh, pf: float = 2.0, conv_width: int = 4):
    pd = int(d * pf)
    ks = jax.random.split(key, 8)
    return {
        "ln": jnp.ones((d,), jnp.float32),
        "w_up": bl.dense_init(ks[0], (d, pd)),
        "w_gate": bl.dense_init(ks[1], (d, pd)),
        "conv": bl.dense_init(ks[2], (conv_width, pd)) * 0.1,
        "wq": bl.dense_init(ks[3], (pd, pd)),
        "wk": bl.dense_init(ks[4], (pd, pd)),
        "wv": bl.dense_init(ks[5], (pd, pd)),
        "wif": bl.dense_init(ks[6], (pd, 2 * nh)),   # input & forget gates
        "gn": jnp.ones((pd,), jnp.float32),          # group norm scale
        "w_down": bl.dense_init(ks[7], (pd, d)),
    }


def _chunk_mlstm(q, k, v, logf, logi, chunk: int, init=None):
    """Chunkwise-parallel mLSTM. q,k,v: (B,S,nh,dh); logf/logi: (B,S,nh).

    Returns (h (B,S,nh,dh), final_state (C, n, m)).  Stabilization: we
    subtract the per-sequence input-gate max M = max_s logi (per
    batch/head) from every i weight and floor the denominator at
    exp(-M) — a whole-sequence variant of the paper's running-max m_t
    (documented fidelity simplification; the single-step decode path
    implements the exact stabilized recurrence).  All decay weights are
    then <= 1, so no exp can overflow.  ``init``: optional carried
    stabilized state (C0, n0, m0) for chunked prefill continuation.
    """
    B, S, nh, dh = q.shape
    M = jnp.max(logi, axis=1, keepdims=True)          # (B,1,nh)
    if init is not None:
        M = jnp.maximum(M, init[2][:, None])          # include carried m0
    logi = logi - M
    floor = jnp.exp(-M[:, 0])                         # (B,nh)

    nc = S // chunk
    qc = q.reshape(B, nc, chunk, nh, dh)
    kc = k.reshape(B, nc, chunk, nh, dh)
    vc = v.reshape(B, nc, chunk, nh, dh)
    fc = logf.reshape(B, nc, chunk, nh)
    ic = logi.reshape(B, nc, chunk, nh)

    csum_f = jnp.cumsum(fc, axis=2)                   # within-chunk decay
    tot_f = csum_f[:, :, -1]                          # (B,nc,nh)

    # ---- intra-chunk (quadratic with decay mask) --------------------------
    # weight for pair (t, s<=t): exp(csum_f[t] - csum_f[s] + logi[s]) <= 1
    wq_ = csum_f[:, :, :, None, :]                    # (B,nc,T,1,nh)
    ws_ = (csum_f - ic)[:, :, None, :, :]             # (B,nc,1,T,nh)
    logw = wq_ - ws_                                  # (B,nc,T,T,nh)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE the exp: above the diagonal logw grows with the chunk
    # length and exp overflows to inf, whose gradient through a where
    # is 0 * inf = NaN
    w = jnp.exp(jnp.where(mask[None, None, :, :, None], logw, -jnp.inf))
    scores = jnp.einsum("bcthd,bcshd->bctsh", qc, kc) / jnp.sqrt(dh)
    h_intra = jnp.einsum("bctsh,bctsh,bcshd->bcthd",
                         scores.astype(jnp.float32), w, vc.astype(jnp.float32))
    norm_intra = jnp.einsum("bctsh,bctsh,bcshd->bcthd",
                            scores.astype(jnp.float32), w,
                            jnp.ones_like(vc, jnp.float32))

    # ---- inter-chunk: recurrence over chunk memories ----------------------
    # chunk memory delta: sum_s exp(tot_f - csum_f[s]) i_s k_s v_s^T
    decay_s = jnp.exp((tot_f[:, :, None] - csum_f + ic))      # (B,nc,T,nh)
    dC = jnp.einsum("bcshd,bcsh,bcshe->bchde", kc.astype(jnp.float32),
                    decay_s, vc.astype(jnp.float32))
    dn = jnp.einsum("bcshd,bcsh->bchd", kc.astype(jnp.float32), decay_s)

    def combine(l, r):
        fl, Cl, nl = l
        fr, Cr, nr = r
        return fl + fr, Cr + jnp.exp(fr)[..., None, None] * Cl, nr + jnp.exp(fr)[..., None] * nl

    f_tot = jnp.moveaxis(tot_f, 1, 0)                 # (nc,B,nh)
    C_all = jnp.moveaxis(dC, 1, 0)                    # (nc,B,nh,dh,dh)
    n_all = jnp.moveaxis(dn, 1, 0)                    # (nc,B,nh,dh)
    f_pre, C_pre, n_pre = jax.lax.associative_scan(
        combine, (f_tot, C_all, n_all))
    # memory *before* chunk c = scanned value of chunk c-1; shift by one
    C_prev = jnp.concatenate([jnp.zeros_like(C_pre[:1]), C_pre[:-1]])
    n_prev = jnp.concatenate([jnp.zeros_like(n_pre[:1]), n_pre[:-1]])
    if init is not None:
        # carried state contributes exp(prefix_f + m0 - M) * (C0, n0)
        C0, n0, m0 = init
        prefix_f = jnp.concatenate([jnp.zeros_like(f_pre[:1]), f_pre[:-1]])
        w0 = jnp.exp(prefix_f + (m0 - M[:, 0])[None])          # (nc,B,nh)
        C_prev = C_prev + w0[..., None, None] * C0.astype(jnp.float32)[None]
        n_prev = n_prev + w0[..., None] * n0.astype(jnp.float32)[None]
    C_prev = jnp.moveaxis(C_prev, 0, 1)               # (B,nc,nh,dh,dh)
    n_prev = jnp.moveaxis(n_prev, 0, 1)
    # final carried state (stabilized at scale exp(-M))
    C_T = C_pre[-1]
    n_T = n_pre[-1]
    if init is not None:
        wT = jnp.exp(f_pre[-1] + (m0 - M[:, 0]))
        C_T = C_T + wT[..., None, None] * C0.astype(jnp.float32)
        n_T = n_T + wT[..., None] * n0.astype(jnp.float32)
    final = (C_T, n_T, M[:, 0])

    # contribution of carried memory at step t: exp(csum_f[t]) q_t . C_prev
    decay_t = jnp.exp(csum_f)                         # (B,nc,T,nh)
    h_inter = jnp.einsum("bcthd,bchde,bcth->bcthe",
                         qc.astype(jnp.float32), C_prev, decay_t) / jnp.sqrt(dh)
    norm_inter = jnp.einsum("bcthd,bchd,bcth->bcth",
                            qc.astype(jnp.float32), n_prev, decay_t)[..., None] / jnp.sqrt(dh)

    h = h_intra + h_inter
    norm = jnp.abs(norm_intra + norm_inter)
    # denominator floor exp(-M): the stabilized max(|n^T q|, exp(-m)) form
    floor_b = floor.reshape(B, 1, 1, nh, 1)
    h = h / jnp.maximum(norm, floor_b)
    return h.reshape(B, S, nh, dh).astype(q.dtype), final


def mlstm_block(p, x, *, nh, chunk: int = 64, state=None):
    """x: (B,S,d) -> (B,S,d).  ``state`` (decode): dict C (B,nh,dh,dh),
    n (B,nh,dh), conv (B,W-1,pd)."""
    B, S, d = x.shape
    xi = bl.rms_norm(x, p["ln"])
    up = xi @ p["w_up"].astype(x.dtype)
    gate = jax.nn.silu(xi @ p["w_gate"].astype(x.dtype))
    pd = up.shape[-1]
    dh = pd // nh

    conv_state = None if state is None else state["conv"]
    from repro.models.recurrent import _conv1d_causal
    xc, new_conv = _conv1d_causal(up, p["conv"], conv_state)
    xc = jax.nn.silu(xc)

    q = (xc @ p["wq"].astype(x.dtype)).reshape(B, S, nh, dh)
    k = (xc @ p["wk"].astype(x.dtype)).reshape(B, S, nh, dh)
    v = (up @ p["wv"].astype(x.dtype)).reshape(B, S, nh, dh)
    gates = (xc @ p["wif"].astype(x.dtype)).astype(jnp.float32)
    logi, logf = gates[..., :nh], jax.nn.log_sigmoid(gates[..., nh:])

    if state is None or S > 1:
        init = None
        if state is not None:
            init = (state["C"].astype(jnp.float32),
                    state["n"].astype(jnp.float32),
                    state["m"].astype(jnp.float32))
        if S % chunk:  # pad to a chunk multiple (pad logf=0 => f=1 no-op decay)
            pad = chunk - S % chunk
            padf = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            h, fin = _chunk_mlstm(padf(q), padf(k), padf(v), padf(logf),
                                  padf(logi) - 1e9 * (jnp.arange(S + pad) >= S)[None, :, None],
                                  chunk, init=init)
            h = h[:, :S]
        else:
            h, fin = _chunk_mlstm(q, k, v, logf, logi, chunk, init=init)
        if state is None:
            new_state = None
        else:
            new_state = {"C": fin[0], "n": fin[1], "m": fin[2],
                         "conv": new_conv}
    else:
        # exact stabilized single-step recurrence (xLSTM paper, eq. 15/25)
        C0 = state["C"].astype(jnp.float32)
        n0 = state["n"].astype(jnp.float32)
        m0 = state["m"].astype(jnp.float32)
        lf, li = logf[:, 0], logi[:, 0]                # (B,nh)
        m = jnp.maximum(lf + m0, li)
        f = jnp.exp(lf + m0 - m)
        i = jnp.exp(li - m)
        kv = jnp.einsum("bhd,bhe->bhde", k[:, 0].astype(jnp.float32),
                        v[:, 0].astype(jnp.float32))
        C = f[..., None, None] * C0 + i[..., None, None] * kv
        n = f[..., None] * n0 + i[..., None] * k[:, 0].astype(jnp.float32)
        num = jnp.einsum("bhd,bhde->bhe", q[:, 0].astype(jnp.float32), C) / jnp.sqrt(dh)
        den = jnp.abs(jnp.einsum("bhd,bhd->bh", q[:, 0].astype(jnp.float32), n)) / jnp.sqrt(dh)
        den = jnp.maximum(den, jnp.exp(-m))[..., None]
        h = (num / den)[:, None].astype(x.dtype)
        new_state = {"C": C, "n": n, "m": m, "conv": new_conv}

    h = h.reshape(B, S, pd)
    h = bl.rms_norm(h, p["gn"]) * gate
    return x + h @ p["w_down"].astype(x.dtype), new_state


def make_mlstm_state(B, d, nh, pf: float = 2.0, conv_width: int = 4):
    pd = int(d * pf)
    dh = pd // nh
    return {
        "C": jnp.zeros((B, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((B, nh, dh), jnp.float32),
        "m": jnp.full((B, nh), -30.0, jnp.float32),
        "conv": jnp.zeros((B, conv_width - 1, pd), jnp.float32),
    }


# --------------------------------------------------------------------------
# sLSTM
# --------------------------------------------------------------------------

def init_slstm(key, d, nh, mlp_pf: float = 4.0 / 3.0):
    dh = d // nh
    ks = jax.random.split(key, 7)
    f = int(d * mlp_pf)
    return {
        "ln": jnp.ones((d,), jnp.float32),
        "w": bl.dense_init(ks[0], (d, 4 * d)),            # i,f,z,o pre-acts
        # block-diag recurrent; fan-in is dh (axis 1), not the head count
        "r": bl.dense_init(ks[1], (nh, dh, 4 * dh), in_axis=1) * 0.5,
        "gn": jnp.ones((d,), jnp.float32),
        "ln2": jnp.ones((d,), jnp.float32),
        "wg": bl.dense_init(ks[2], (d, f)),
        "wu": bl.dense_init(ks[3], (d, f)),
        "wd": bl.dense_init(ks[4], (f, d)),
    }


def slstm_block(p, x, *, nh, state=None):
    """Sequential sLSTM with exponential gating and block-diagonal
    recurrence.  state: dict h,c,n,m each (B,d)."""
    B, S, d = x.shape
    dh = d // nh
    xi = bl.rms_norm(x, p["ln"])
    pre = (xi @ p["w"].astype(x.dtype)).astype(jnp.float32)  # (B,S,4d)

    if state is None:
        h0 = jnp.zeros((B, d), jnp.float32)
        c0 = jnp.zeros((B, d), jnp.float32)
        n0 = jnp.zeros((B, d), jnp.float32)
        m0 = jnp.full((B, d), -1e30, jnp.float32)
    else:
        h0, c0, n0, m0 = (state[k].astype(jnp.float32) for k in ("h", "c", "n", "m"))

    r = p["r"].astype(jnp.float32)

    def step(carry, pre_t):
        h, c, n, m = carry
        hh = h.reshape(B, nh, dh)
        rec = jnp.einsum("bhd,hde->bhe", hh, r).reshape(B, 4 * d)
        zifo = pre_t + rec
        zi, zf, zz, zo = jnp.split(zifo, 4, axis=-1)
        log_i = zi
        log_f = jax.nn.log_sigmoid(zf)
        m_new = jnp.maximum(log_f + m, log_i)
        i = jnp.exp(log_i - m_new)
        f = jnp.exp(log_f + m - m_new)
        z = jnp.tanh(zz)
        o = jax.nn.sigmoid(zo)
        c_new = f * c + i * z
        n_new = f * n + i
        h_new = o * c_new / jnp.maximum(n_new, 1.0)
        return (h_new, c_new, n_new, m_new), h_new

    (hT, cT, nT, mT), hs = jax.lax.scan(step, (h0, c0, n0, m0),
                                        jnp.moveaxis(pre, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)        # (B,S,d)
    h = bl.rms_norm(h, p["gn"])
    y = x + h
    # gated MLP (the block's own FFN; config d_ff = 0)
    yi = bl.rms_norm(y, p["ln2"])
    y = y + bl.swiglu(yi, p["wg"], p["wu"], p["wd"])
    new_state = {"h": hT, "c": cT, "n": nT, "m": mT}
    return y, new_state


def make_slstm_state(B, d):
    return {
        "h": jnp.zeros((B, d), jnp.float32),
        "c": jnp.zeros((B, d), jnp.float32),
        "n": jnp.zeros((B, d), jnp.float32),
        "m": jnp.full((B, d), -1e30, jnp.float32),
    }
