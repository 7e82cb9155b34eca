"""Model assembly: config -> init / train forward / prefill / decode.

Layers are grouped into homogeneous *segments* (a superblock pattern x a
repeat count) and each segment is ``lax.scan``-ed over its stacked
params, so a 100-layer model lowers to a compact HLO whose collectives
appear once per superblock (the dry-run collective parser multiplies by
the recorded trip counts).

Families map to superblock plans:
  dense        [("dense",) * 1] x L
  moe          [("dense",)] x first_k_dense + [("moe",)] x rest
  vlm          [4 x "dense" + "cross"] x (L / 5)
  hybrid       [("rglru","rglru","attn_local")] x (L // 3) + remainder
  ssm (xlstm)  [7 x "mlstm" + "slstm"] x (L / 8)
  audio        dense with LayerNorm/GELU and an embedding-stub frontend
"""

from __future__ import annotations

import dataclasses

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models import attention as attn
from repro.models import blocks as bl
from repro.models import moe as moe_lib
from repro.models import recurrent as rec
from repro.models import xlstm as xl


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | vlm | hybrid | ssm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0
    qkv_bias: bool = False
    norm: str = "rms"            # rms | ln
    mlp: str = "swiglu"          # swiglu | gelu
    rope_base: float = 10000.0
    tie_embeddings: bool = False
    # moe
    moe: moe_lib.MoEDims | None = None
    first_k_dense: int = 0
    # mla
    mla: attn.MLADims | None = None
    # vlm
    cross_every: int = 0
    n_image_tokens: int = 0
    # hybrid (recurrentgemma)
    block_pattern: tuple[str, ...] = ()
    window: int = 0
    lru_width: int = 0
    # xlstm
    slstm_every: int = 0
    mlstm_pf: float = 2.0
    mlstm_chunk: int = 64
    # frontend: tokens | embeddings (audio frame / stubbed modality)
    frontend: str = "tokens"
    # policy
    dtype: Any = jnp.bfloat16
    fsdp: bool = False
    tp: bool = True              # False: no tensor parallelism — weights
                                 # replicated (or FSDP), model axis joins DP
    seq_shard: bool = False      # shard SEQUENCE over the model axis and
                                 # use ring attention (long prefill mode;
                                 # requires tp=False, full attention)
    remat: str = "none"          # none | full | dots (activation ckpt policy)
    aux_loss_weight: float = 0.01
    sub_quadratic: bool = False  # may run long_500k

    @property
    def dh(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def dr(self) -> int:
        return self.lru_width or self.d_model

    def segments(self) -> list[tuple[tuple[str, ...], int]]:
        L = self.n_layers
        if self.family in ("dense", "audio"):
            return [(("dense",), L)]
        if self.family == "moe":
            segs = []
            if self.first_k_dense:
                segs.append((("dense",), self.first_k_dense))
            segs.append((("moe",), L - self.first_k_dense))
            return segs
        if self.family == "vlm":
            k = self.cross_every
            assert L % k == 0
            return [(("dense",) * (k - 1) + ("cross",), L // k)]
        if self.family == "hybrid":
            pat = self.block_pattern or ("rglru", "rglru", "attn_local")
            full, rem = divmod(L, len(pat))
            segs = [(pat, full)]
            if rem:
                segs.append((pat[:rem], 1))
            return segs
        if self.family == "ssm":
            k = self.slstm_every
            if k:
                assert L % k == 0
                return [(("mlstm",) * (k - 1) + ("slstm",), L // k)]
            return [(("mlstm",), L)]
        raise ValueError(self.family)

    def num_params(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# per-block init / apply / cache / specs
# --------------------------------------------------------------------------

def _init_norm(cfg, key):
    if cfg.norm == "ln":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}


def _norm(cfg, p, x):
    if cfg.norm == "ln":
        return bl.layer_norm(x, p["scale"], p["bias"])
    return bl.rms_norm(x, p["scale"])


def _init_mlp(cfg, key):
    if cfg.mlp == "gelu":
        ks = jax.random.split(key, 2)
        return {"wi": bl.dense_init(ks[0], (cfg.d_model, cfg.d_ff)),
                "bi": jnp.zeros((cfg.d_ff,), jnp.float32),
                "wo": bl.dense_init(ks[1], (cfg.d_ff, cfg.d_model)),
                "bo": jnp.zeros((cfg.d_model,), jnp.float32)}
    ks = jax.random.split(key, 3)
    return {"wg": bl.dense_init(ks[0], (cfg.d_model, cfg.d_ff)),
            "wu": bl.dense_init(ks[1], (cfg.d_model, cfg.d_ff)),
            "wd": bl.dense_init(ks[2], (cfg.d_ff, cfg.d_model))}


def _mlp(cfg, p, x):
    if cfg.mlp == "gelu":
        return bl.gelu_mlp(x, p["wi"], p["bi"], p["wo"], p["bo"])
    return bl.swiglu(x, p["wg"], p["wu"], p["wd"])


def _init_block(cfg, kind: str, key):
    ks = jax.random.split(key, 4)
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    if kind == "dense":
        a = (attn.init_mla(ks[0], d, H, cfg.mla) if cfg.mla
             else attn.init_gqa(ks[0], d, H, K, dh, cfg.qkv_bias))
        return {"ln1": _init_norm(cfg, ks[1]), "attn": a,
                "ln2": _init_norm(cfg, ks[2]), "mlp": _init_mlp(cfg, ks[3])}
    if kind == "moe":
        a = (attn.init_mla(ks[0], d, H, cfg.mla) if cfg.mla
             else attn.init_gqa(ks[0], d, H, K, dh, cfg.qkv_bias))
        return {"ln1": _init_norm(cfg, ks[1]), "attn": a,
                "ln2": _init_norm(cfg, ks[2]),
                "moe": moe_lib.init_moe(ks[3], d, cfg.moe)}
    if kind == "cross":
        return {"ln1": _init_norm(cfg, ks[1]),
                "xattn": attn.init_cross(ks[0], d, H, K, dh),
                "ln2": _init_norm(cfg, ks[2]), "mlp": _init_mlp(cfg, ks[3])}
    if kind == "attn_local":
        return {"ln1": _init_norm(cfg, ks[1]),
                "attn": attn.init_gqa(ks[0], d, H, K, dh, cfg.qkv_bias),
                "ln2": _init_norm(cfg, ks[2]), "mlp": _init_mlp(cfg, ks[3])}
    if kind == "rglru":
        return {"ln1": _init_norm(cfg, ks[1]),
                "rnn": rec.init_rglru(ks[0], d, cfg.dr, cfg.n_heads),
                "ln2": _init_norm(cfg, ks[2]), "mlp": _init_mlp(cfg, ks[3])}
    if kind == "mlstm":
        return {"cell": xl.init_mlstm(ks[0], d, cfg.n_heads, cfg.mlstm_pf)}
    if kind == "slstm":
        return {"cell": xl.init_slstm(ks[0], d, cfg.n_heads)}
    raise ValueError(kind)


def _block_cache(cfg, kind: str, B: int, slots: int):
    K, dh = cfg.n_kv_heads, cfg.dh
    if kind in ("dense", "moe"):
        if cfg.mla:
            return attn.make_mla_cache(B, slots, cfg.mla, cfg.dtype)
        return attn.make_kv_cache(B, slots, K, dh, cfg.dtype)
    if kind == "attn_local":
        return attn.make_kv_cache(B, min(slots, cfg.window), K, dh, cfg.dtype)
    if kind == "rglru":
        return rec.make_rglru_state(B, cfg.dr)
    if kind == "mlstm":
        return xl.make_mlstm_state(B, cfg.d_model, cfg.n_heads, cfg.mlstm_pf)
    if kind == "slstm":
        return xl.make_slstm_state(B, cfg.d_model)
    if kind == "cross":
        return {}   # image kv is recomputed from the (static) image feats
    raise ValueError(kind)


def _apply_block(cfg, kind: str, p, x, positions, *, cache=None,
                 image_feats=None, ep_ctx=None, ring_ctx=None):
    """Returns (x, new_cache, aux)."""
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe", "attn_local"):
        window = cfg.window if kind == "attn_local" else 0
        h = _norm(cfg, p["ln1"], x)
        if cfg.mla and kind != "attn_local":
            a, cache = attn.mla(p["attn"], h, positions, H=cfg.n_heads,
                                dims=cfg.mla, cache=cache)
        else:
            a, cache = attn.gqa(p["attn"], h, positions, H=H, K=K, dh=dh,
                                window=window, rope_base=cfg.rope_base,
                                cache=cache,
                                ring_ctx=None if window else ring_ctx)
        x = x + a
        h = _norm(cfg, p["ln2"], x)
        if kind == "moe":
            if ep_ctx is not None:
                f, aux = ep_ctx(p["moe"], h)
                if cfg.moe.n_shared:   # shared experts: dense, GSPMD-sharded
                    B_, S_, d_ = h.shape
                    hf = h.reshape(B_ * S_, d_)
                    f = f + bl.swiglu(hf, p["moe"]["ws_g"], p["moe"]["ws_u"],
                                      p["moe"]["ws_d"]).reshape(B_, S_, d_)
            else:
                f, aux = moe_lib.moe_ffn(p["moe"], h, cfg.moe)
            x = x + f
        else:
            x = x + _mlp(cfg, p["mlp"], h)
        return x, cache, aux
    if kind == "cross":
        h = _norm(cfg, p["ln1"], x)
        x = x + attn.cross_attention(p["xattn"], h, image_feats, H=H, K=K, dh=dh)
        h = _norm(cfg, p["ln2"], x)
        x = x + _mlp(cfg, p["mlp"], h)
        return x, cache, aux
    if kind == "rglru":
        h = _norm(cfg, p["ln1"], x)
        r, cache = rec.rglru_block(p["rnn"], h, state=cache)
        x = x + r
        h = _norm(cfg, p["ln2"], x)
        x = x + _mlp(cfg, p["mlp"], h)
        return x, cache, aux
    if kind == "mlstm":
        x, cache = xl.mlstm_block(p["cell"], x, nh=cfg.n_heads,
                                  chunk=cfg.mlstm_chunk, state=cache)
        return x, cache, aux
    if kind == "slstm":
        x, cache = xl.slstm_block(p["cell"], x, nh=cfg.n_heads, state=cache)
        return x, cache, aux
    raise ValueError(kind)


# --------------------------------------------------------------------------
# the Model
# --------------------------------------------------------------------------

class Model:
    """Functional model: explicit params, no framework magic.

    ``mesh``/``axis_rules`` enable (a) the MoE expert-parallel shard_map
    island and (b) activation sharding constraints; both off for pure
    single-device use (smoke tests, oracles).
    """

    def __init__(self, cfg: ModelConfig, mesh=None,
                 dp_axes: tuple[str, ...] = ("data",),
                 model_axis: str = "model"):
        self.cfg = cfg
        self.mesh = mesh
        self.dp_axes = dp_axes
        self.model_axis = model_axis
        self.segs = cfg.segments()

    # -- init ---------------------------------------------------------------

    def init(self, key) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, len(self.segs) + 3)
        params: dict[str, Any] = {}
        params["embed"] = bl.embed_init(keys[0], (cfg.vocab, cfg.d_model))
        params["final_norm"] = _init_norm(cfg, keys[1])
        if not cfg.tie_embeddings:
            params["lm_head"] = bl.dense_init(keys[2], (cfg.d_model, cfg.vocab))
        params["segments"] = []
        for si, (pat, reps) in enumerate(self.segs):
            sk = jax.random.split(keys[3 + si], reps)

            def init_one(k):
                kk = jax.random.split(k, len(pat))
                return {f"b{i}_{kind}": _init_block(cfg, kind, kk[i])
                        for i, kind in enumerate(pat)}

            stacked = jax.vmap(init_one)(sk)
            params["segments"].append(stacked)
        return params

    # -- sharding specs -------------------------------------------------------

    def param_pspecs(self, params) -> Any:
        """PartitionSpec tree matching ``params`` (logical rules -> mesh)."""
        cfg = self.cfg
        fsdp = self.dp_axes[-1] if cfg.fsdp else None
        m = self.model_axis if cfg.tp else None

        def spec_for(path, leaf) -> P:
            names = [getattr(k, "key", str(k)) for k in path]
            name = names[-1]
            parent = names[-2] if len(names) >= 2 else ""
            stacked = "segments" in names
            if name == "embed":
                s = P(m, None)
            elif name == "lm_head":
                s = P(fsdp, m)
            elif parent == "rnn" and name in ("wr", "wi"):
                s = P(m, None, None)             # block-diag RG-LRU gates
            elif name in ("wq", "wk", "wv", "wg", "wu", "wi", "w_up",
                          "w_gate", "wx", "wy"):
                if parent == "moe":           # stacked experts (E, d, fe)
                    s = P(m, fsdp, None)
                else:
                    s = P(fsdp, m)
            elif name in ("wuq", "wuk", "wuv"):
                s = P(None, m)
            elif name in ("wdq", "wdkv"):
                s = P(fsdp, None)
            elif name in ("wo", "wd", "w_down", "ws_d"):
                if parent == "moe":           # (E, fe, d)
                    s = P(m, None, fsdp)
                else:
                    s = P(m, fsdp)
            elif name in ("ws_g", "ws_u"):
                s = P(fsdp, m)
            elif name in ("wr",) and leaf.ndim >= 3:
                s = P(m, None, None)             # block-diag gates
            elif name == "r":
                s = P(m, None, None)             # slstm block-diag recurrence
            elif name == "conv":
                s = P(None, m)
            elif name in ("bq", "bk", "bv", "bi"):
                s = P(m)
            elif name == "w" and leaf.ndim == 2:
                s = P(fsdp, m)                   # slstm gate proj
            elif name == "router":
                s = P(None, None)
            else:
                s = P(*([None] * leaf.ndim))
            if stacked:                           # leading scan dim
                s = P(None, *tuple(s))
            # pad/truncate to leaf rank
            t = tuple(s)
            if len(t) < leaf.ndim:
                t = t + (None,) * (leaf.ndim - len(t))
            return self._sanitize(P(*t[:leaf.ndim]), leaf.shape)

        return jax.tree_util.tree_map_with_path(spec_for, params)

    def _sanitize(self, spec: P, shape) -> P:
        """Drop mesh axes from dims they do not divide (e.g. 10 RG-LRU
        gate blocks over a 16-way model axis) — replicate those instead."""
        if self.mesh is None:
            return spec
        t = list(spec)
        for i, s in enumerate(t):
            if s is None:
                continue
            axes = s if isinstance(s, tuple) else (s,)
            sz = 1
            for a in axes:
                sz *= self.mesh.shape[a]
            if shape[i] % sz:
                t[i] = None
        return P(*t)

    def _constrain(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    def _ep_ctx(self):
        """The expert-parallel shard_map island (or None).

        FULLY manual over every mesh axis (partial-manual nesting trips
        XLA partitioner bugs at 3-D meshes): tokens split over the DP
        axes, expert slabs over model (+FSDP over data), combine psum'ed
        inside.  Boundaries are f32 so autodiff-inserted collectives are
        f32 (see moe.moe_routed_island).  Shared experts / aux weighting
        happen outside in plain GSPMD code (_apply_block).
        """
        cfg = self.cfg
        if (self.mesh is None or cfg.moe is None or not cfg.tp
                or self.mesh.shape[self.model_axis] == 1):
            return None
        msize = self.mesh.shape[self.model_axis]
        if cfg.moe.n_experts % msize:
            return None                           # not EP-shardable; dense TP

        m = self.model_axis
        fsdp = self.dp_axes[-1] if cfg.fsdp else None
        all_axes = tuple(self.mesh.axis_names)
        routed_spec = {
            "router": P(None, None),
            "wg": P(m, fsdp, None), "wu": P(m, fsdp, None),
            "wd": P(m, None, fsdp),
        }

        def island(p, h32):
            return moe_lib.moe_routed_island(
                p, h32, cfg.moe, model_axis=m, all_axes=all_axes,
                fsdp_axis=fsdp, compute_dtype=cfg.dtype)

        # a2a/rs dispatch want tokens sequence-sharded over the model axis
        # at the island boundary; psum wants them replicated over it.
        seq = m if cfg.moe.dispatch in ("a2a", "rs") else None
        smapped = shard_map(
            island, mesh=self.mesh,
            in_specs=(routed_spec, P(self.dp_axes, seq, None)),
            out_specs=(P(self.dp_axes, seq, None), P()),
            check_vma=False)

        def run(p_moe, h):
            routed = {k: p_moe[k] for k in ("router", "wg", "wu", "wd")}
            out32, aux = smapped(routed, h.astype(jnp.float32))
            return out32.astype(h.dtype), aux

        return run

    # -- forward -------------------------------------------------------------

    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.frontend == "embeddings":
            x = batch["embeddings"].astype(cfg.dtype)
        else:
            x = params["embed"].astype(cfg.dtype)[batch["tokens"]]
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
        return x

    def _unembed(self, params, x):
        cfg = self.cfg
        x = _norm(cfg, params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].astype(x.dtype).T
        else:
            logits = x @ params["lm_head"].astype(x.dtype)
        vocab_axis = self.model_axis if cfg.tp else None
        if vocab_axis in self.dp_axes or cfg.seq_shard:
            vocab_axis = None   # the model axis carries S (or DP) instead
        return self._constrain(
            logits, P(self.dp_axes, self._seq_axis(), vocab_axis))

    def _seq_axis(self):
        """The axis activations' S dim is sharded over (seq_shard mode)."""
        if self.cfg.seq_shard and self.mesh is not None:
            return self.model_axis
        return None

    def _ring_ctx(self):
        """Ring attention: only in the no-TP sequence-parallel mode.
        With TP + seq_shard (Megatron-SP), attention instead runs
        head-sharded with GSPMD-inserted bf16 all-gather/reduce-scatter
        around it — the sequence axis exists for the norms/MLP/MoE."""
        cfg = self.cfg
        if not cfg.seq_shard or cfg.tp or self.mesh is None:
            return None
        if self.mesh.shape[self.model_axis] == 1:
            return None
        return (self.mesh, self.model_axis, self.dp_axes)

    def _run_segments(self, params, x, positions, *, caches=None,
                      image_feats=None):
        """Scan each segment; returns (x, new_caches, aux_total)."""
        cfg = self.cfg
        ep_ctx = self._ep_ctx()
        ring_ctx = self._ring_ctx() if x.shape[1] > 1 else None
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = []
        for si, (pat, reps) in enumerate(self.segs):
            seg_params = params["segments"][si]
            seg_cache = None if caches is None else caches[si]

            def superblock(x, layer):
                p_layer, c_layer = layer
                aux_sb = jnp.zeros((), jnp.float32)
                c_out = {}
                for i, kind in enumerate(pat):
                    key = f"b{i}_{kind}"
                    c_in = None if c_layer is None else c_layer.get(key)
                    x2, c2, aux = _apply_block(
                        cfg, kind, p_layer[key], x, positions, cache=c_in,
                        image_feats=image_feats, ep_ctx=ep_ctx,
                        ring_ctx=ring_ctx)
                    x = self._constrain(
                        x2, P(self.dp_axes, self._seq_axis(), None))
                    c_out[key] = c2 if c2 is not None else {}
                    aux_sb = aux_sb + aux
                return x, (c_out, aux_sb)

            if seg_cache is None:
                def body(x, p_layer):
                    x, (_, aux_sb) = superblock(x, (p_layer, None))
                    return x, aux_sb

                if cfg.remat == "full":
                    body = jax.checkpoint(body)
                elif cfg.remat == "dots":
                    body = jax.checkpoint(
                        body, policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                x, auxs = jax.lax.scan(body, x, seg_params)
                new_caches.append(None)
                aux_total = aux_total + jnp.sum(auxs)
            else:
                def body_c(x, layer):
                    x, (c_out, aux_sb) = superblock(x, layer)
                    return x, (c_out, aux_sb)

                x, (c_new, auxs) = jax.lax.scan(body_c, x,
                                                (seg_params, seg_cache))
                new_caches.append(c_new)
                aux_total = aux_total + jnp.sum(auxs)
        return x, new_caches, aux_total

    def forward_train(self, params, batch):
        """batch: tokens/embeddings (+labels, +image_feats) -> (logits, aux)."""
        cfg = self.cfg
        x = self._embed_in(params, batch)
        x = self._constrain(x, P(self.dp_axes, self._seq_axis(), None))
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        image_feats = batch.get("image_feats")
        x, _, aux = self._run_segments(params, x, positions,
                                       image_feats=image_feats)
        return self._unembed(params, x), aux

    def loss(self, params, batch):
        logits, aux = self.forward_train(params, batch)
        ce = bl.softmax_xent(logits, batch["labels"])
        return ce + self.cfg.aux_loss_weight * aux

    # -- serving -------------------------------------------------------------

    def make_cache(self, B: int, slots: int):
        caches = []
        for pat, reps in self.segs:
            one = {f"b{i}_{kind}": _block_cache(self.cfg, kind, B, slots)
                   for i, kind in enumerate(pat)}
            stacked = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), one)
            caches.append(stacked)
        return caches

    def prefill(self, params, batch, cache):
        """Run the prompt through the model, filling the cache.

        Returns (logits_last (B, vocab), new_cache)."""
        cfg = self.cfg
        x = self._embed_in(params, batch)
        x = self._constrain(x, P(self.dp_axes, self._seq_axis(), None))
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, cache, _ = self._run_segments(params, x, positions, caches=cache,
                                         image_feats=batch.get("image_feats"))
        logits = self._unembed(params, x[:, -1:])
        return logits[:, 0], cache

    def decode_step(self, params, cache, token, pos, image_feats=None):
        """One decode step. token: (B, 1) ids (or (B,1,d) embeddings);
        pos: (B,) absolute positions.  VLM decode re-attends the static
        ``image_feats``.  Returns (logits (B, vocab), cache)."""
        cfg = self.cfg
        if cfg.frontend == "embeddings":
            x = token.astype(cfg.dtype)
        else:
            x = params["embed"].astype(cfg.dtype)[token]
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
        positions = pos[:, None].astype(jnp.int32)
        x, cache, _ = self._run_segments(params, x, positions, caches=cache,
                                         image_feats=image_feats)
        logits = self._unembed(params, x)
        return logits[:, 0], cache


def build_model(cfg: ModelConfig, mesh=None,
                dp_axes: tuple[str, ...] = ("data",)) -> Model:
    return Model(cfg, mesh=mesh, dp_axes=dp_axes)
