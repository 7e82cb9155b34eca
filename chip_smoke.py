"""Run Shoal's main paths once on a TPU, checked against plain references.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the cross-chip paths only

One chip:
  (a) Shoal AMs on the LOCAL path: one kernel, self pattern [(0, 0)], so
      packet build, GAScore ingress, handlers and credits all run on the
      chip with no collective: an acked multi-MTU put_long, an H_ADD
      put, get_medium, barrier and a 1024-send mixed mailbox flush,
      checked bit for bit against a numpy model of the same writes.
  (b) The paper's Jacobi app at its 4096^2 grid on one kernel, with the
      compiled Pallas stencil, against the jnp single-kernel reference.
  (c) tinyllama-1.1b at full width, random bf16 weights from --seed,
      through ServeEngine as repro.launch.serve drives it; the last
      decode step's logits of one request are checked against a full
      prefill over the same tokens.

Four chips (--chips 4):
  - Jacobi halo exchange: JacobiApp(n=4096, kernels=4), jnp and Pallas
    stencils, against the single-kernel reference; the PGAS segment must
    span the 4 devices.
  - KV migration: DisaggServeTier with 2 prefill and 2 decode chips,
    migrated decode token-identical to the in-place engine.
  - Gradient sync: 3 trainer steps with comm_backend="shoal" against
    "xla" on xlstm-350m, the largest config whose replicated training
    state fits a v5e's 16 GB, computed in f32.

Everything runs in this one process, which holds the chip(s).  It exits
non-zero, printing no result line, when JAX finds no TPU.  The last line
of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

JACOBI_N = 4096
JACOBI_ITERS = 30
JACOBI_TOL = 1e-5
SERVE_ARCH = "tinyllama-1.1b"
SERVE_LANES = 2
SERVE_SLOTS = 64
SERVE_PROMPT = 8
SERVE_MAX_NEW = 8
# bf16 weights and activations: a cached decode step and a full prefill
# round differently; bound the worst logit gap by a share of the logit
# range.
SERVE_REL_TOL = 5e-2
TRAIN_ARCH = "xlstm-350m"
TRAIN_BATCH = 8          # global: 2 sequences per chip
TRAIN_SEQ = 512
TRAIN_STEPS = 3
# Computed in f32 at full matmul precision, the two backends differ only
# in the order of the gradient sums.  (In bf16 they run different
# programs, and AdamW's first, sign-like step amplifies the rounding.)
TRAIN_LOSS_TOL = 1e-3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    log(f"{name}: {detail}")
    if not ok:
        raise AssertionError(f"{name} failed: {detail}")


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# -- (a) Shoal AMs on the LOCAL path ------------------------------------------

def phase_local_ams(seed: int) -> None:
    from repro.actors import Mailbox
    from repro.core import handlers as hd
    from repro.core import ops
    from repro.core.address_space import GlobalAddressSpace
    from repro.core.state import ShoalContext
    from repro.runtime import TCP
    from repro.runtime.topology import make_cpu_mesh

    local = [(0, 0)]
    seg_words = 1 << 16
    mtu = TCP.max_packet_words
    n_long = 8 * mtu + 1000            # 9 packets
    add_at, n_add = 1000, 3000
    get_at, n_get = 500, 4000
    mb_base, mb_span = 40000, 300
    ctx = ShoalContext(mesh=make_cpu_mesh(1, ("kernel",)), axes=("kernel",),
                       transport=TCP, segment_words=seg_words)
    gas = GlobalAddressSpace(ctx)
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(n_long).astype(np.float32)
    b = rng.standard_normal(n_add).astype(np.float32)

    # 1024 mailbox rows: Long WRITE/ADD rows of 1..4 words on overlapping
    # addresses, and Short H_ADD signals on credit word 7
    rows = []
    for i in range(1024):
        if i % 4 == 3:
            rows.append(("signal", None, 0))
        else:
            w = 1 + i % 4
            handler = hd.H_ADD if i % 4 == 1 else hd.H_WRITE
            rows.append((handler, rng.standard_normal(w).astype(np.float32),
                         mb_base + 4 * ((7 * i) % mb_span)))
    n_signal = sum(r[0] == "signal" for r in rows)

    def prog(st, a, b):
        st = ops.put_long(ctx, st, a, local, 0, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st = ops.put_long(ctx, st, b, local, add_at, handler=hd.H_ADD,
                          token=2)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        st, got = ops.get_medium(ctx, st, local, get_at, n_get, token=3)
        st = ops.wait_replies(ctx, st, token=3, n=1)
        st = ops.barrier(ctx, st)
        mb = Mailbox(ctx, local, msg_words=4, watermark=2048, token=5)
        for handler, pay, addr in rows:
            if handler == "signal":
                st = mb.send_signal(st, arg=1, token=7)
            else:
                st = mb.send(st, pay, dst_addr=addr, handler=handler)
        st = mb.flush(st)
        st = ops.wait_replies(ctx, st, token=5, n=1)
        st = ops.wait_replies(ctx, st, token=7, n=n_signal)
        return st, got

    def spmd(st, a, b):
        st = jax.tree.map(lambda x: x[0], st)
        st, got = prog(st, a, b)
        return jax.tree.map(lambda x: x[None], st), got[None]

    from jax.sharding import PartitionSpec as P
    spec = P(ctx.axes)
    fn = jax.jit(jax.shard_map(spmd, mesh=ctx.mesh,
                               in_specs=(spec, P(), P()),
                               out_specs=(spec, spec)))
    st0 = gas.make_global_state()
    t0 = time.perf_counter()
    compiled = fn.lower(st0, a, b).compile()
    log(f"(a) compiled in {time.perf_counter() - t0:.2f}s "
        f"({n_long}-word put = {-(-n_long // mtu)} packets, "
        f"{len(rows)} mailbox sends)")
    (st, got), dt = timed(compiled, st0, a, b)
    log(f"(a) ran in {dt:.4f}s")

    want = np.zeros(seg_words, np.float32)
    want[:n_long] = a
    want[add_at:add_at + n_add] += b
    want_got = want[get_at:get_at + n_get].copy()
    for handler, pay, addr in rows:
        if handler == hd.H_WRITE:
            want[addr:addr + pay.size] = pay
        elif handler == hd.H_ADD:
            want[addr:addr + pay.size] += pay
    seg = np.asarray(st.segment)[0]
    bad = int(np.sum(seg.view(np.int32) != want.view(np.int32)))
    check("(a) segment", bad == 0,
          f"{bad} of {seg_words} words differ from the numpy model")
    bad_get = int(np.sum(np.asarray(got)[0].view(np.int32)
                         != want_got.view(np.int32)))
    check("(a) get_medium", bad_get == 0,
          f"{bad_get} of {n_get} words differ from the numpy model")
    credits = np.asarray(st.credits)[0]
    err = int(np.asarray(st.error)[0])
    epoch = int(np.asarray(st.barrier_epoch)[0])
    check("(a) credits", not credits.any() and err == 0 and epoch == 1,
          f"credits left {credits.tolist()}, error bits {err}, "
          f"barrier epoch {epoch}")


# -- (b) the paper's Jacobi app -----------------------------------------------

def run_jacobi(app, grid):
    """Compile and run ``app`` once; returns (grid, final state, HLO)."""
    from repro.core.address_space import GlobalAddressSpace
    from repro.launch.hlo_analysis import parse_collectives

    st0 = GlobalAddressSpace(app.ctx).make_global_state()
    blocks = jnp.asarray(grid.reshape(app.kernels, app.rows, app.n))
    t0 = time.perf_counter()
    compiled = app.build().lower(st0, blocks).compile()
    t_compile = time.perf_counter() - t0
    hlo = compiled.as_text()
    cps = parse_collectives(hlo).ops.get("collective-permute", 0.0)
    (st, out), dt = timed(compiled, st0, blocks)
    log(f"Jacobi {app.n}^2 x{app.iters} on {app.kernels} kernel(s), "
        f"pallas={app.use_pallas}: compiled in {t_compile:.2f}s "
        f"({cps:.0f} collective-permutes), ran in {dt:.4f}s")
    return np.asarray(out).reshape(app.n, app.n), st, hlo


def check_jacobi(name, app, grid, ref):
    out, st, hlo = run_jacobi(app, grid)
    err = float(np.max(np.abs(out - ref)))
    check(name, err < JACOBI_TOL, f"max|err| vs reference = {err:.3e} "
          f"(limit {JACOBI_TOL:g})")
    if app.use_pallas:
        found = "tpu_custom_call" in hlo
        check(f"{name} kernel", found,
              f"compiled Pallas stencil in the program: {found}")
    return st


def jacobi_grid(seed: int):
    from repro.apps.jacobi import jacobi_reference

    grid = np.random.default_rng(seed).standard_normal(
        (JACOBI_N, JACOBI_N)).astype(np.float32)
    t0 = time.perf_counter()
    ref = jacobi_reference(grid, JACOBI_ITERS)
    log(f"Jacobi reference: {JACOBI_ITERS} jnp steps in "
        f"{time.perf_counter() - t0:.2f}s")
    return grid, ref


def phase_jacobi(seed: int) -> None:
    from repro.apps.jacobi import JacobiApp

    grid, ref = jacobi_grid(seed)
    check_jacobi("(b) Jacobi 1 kernel, Pallas",
                 JacobiApp(n=JACOBI_N, kernels=1, iters=JACOBI_ITERS,
                           use_pallas=True), grid, ref)


# -- (c) a model at full width through the serving path -----------------------

def phase_serve(seed: int) -> None:
    from repro import configs
    from repro.launch.serve import build_engine
    from repro.serving.engine import Request

    cfg = configs.full(SERVE_ARCH)
    t0 = time.perf_counter()
    engine = build_engine(cfg, seed=seed, lanes=SERVE_LANES,
                          slots=SERVE_SLOTS)
    params = engine.params
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"(c) {SERVE_ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{n_bytes / 2**30:.2f} GiB of {jnp.dtype(cfg.dtype).name} weights "
        f"made in {time.perf_counter() - t0:.2f}s")

    # record every decode step's logits (the engine samples on the host)
    decode, steps = engine._decode, []

    def recording_decode(params, cache, toks, pos):
        logits, cache = decode(params, cache, toks, pos)
        steps.append((np.asarray(pos), np.asarray(logits, np.float32)))
        return logits, cache

    engine._decode = recording_decode
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, SERVE_PROMPT)
                    .astype(np.int32), max_new=SERVE_MAX_NEW)
            for i in range(4)]
    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    check("(c) requests", len(done) == 4
          and all(len(r.out) == SERVE_MAX_NEW for r in done),
          f"{len(done)} requests answered, {toks} tokens, {len(steps)} "
          f"decode steps in {dt:.2f}s (compiles included)")

    # request 0 ran on lane 0; its last decode step fed out[-2] at this pos
    req = reqs[0]
    last_pos = SERVE_PROMPT + SERVE_MAX_NEW - 2
    got = next(lg[0] for pos, lg in steps if pos[0] == last_pos)
    tokens = np.concatenate([req.prompt, np.asarray(req.out[:-1], np.int32)])
    model = engine.model
    ref, _ = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(tokens)[None]},
        model.make_cache(1, SERVE_SLOTS))
    ref = np.asarray(ref, np.float32)[0]
    gap = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    check("(c) decode vs prefill", np.isfinite(got).all()
          and gap <= SERVE_REL_TOL * scale,
          f"max|logit gap| {gap:.4f} over {len(tokens)} tokens, "
          f"limit {SERVE_REL_TOL:g} x max|logit| {scale:.3f}; "
          f"argmax {int(got.argmax())} vs {int(ref.argmax())}")


# -- four chips ---------------------------------------------------------------

def phase_jacobi4(seed: int) -> None:
    from repro.apps.jacobi import JacobiApp

    grid, ref = jacobi_grid(seed)
    for use_pallas in (False, True):
        app = JacobiApp(n=JACOBI_N, kernels=4, iters=JACOBI_ITERS,
                        use_pallas=use_pallas)
        st = check_jacobi(f"Jacobi 4 kernels, pallas={use_pallas}", app,
                          grid, ref)
        devs = {s.device for s in st.segment.addressable_shards}
        check("PGAS segment placement", len(devs) == 4,
              f"segment shards on {len(devs)} devices: "
              f"{sorted(d.id for d in devs)}")


def phase_grad_sync(seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models.model import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.topology import make_mesh
    from repro.training.train import Trainer, TrainerConfig

    cfg = dataclasses.replace(configs.full(TRAIN_ARCH), dtype=jnp.float32)
    mesh = make_mesh((4, 1), ("data", "model"))
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, batch=TRAIN_BATCH,
                                    seq=TRAIN_SEQ, seed=seed))
    shard = NamedSharding(mesh, P("data"))
    batches = [{k: jax.device_put(v, shard)
                for k, v in pipe.next_batch(i)[0].items()}
               for i in range(TRAIN_STEPS)]
    losses = {}
    for backend in ("xla", "shoal"):
        shoal = backend == "shoal"
        model = build_model(cfg, mesh=mesh, dp_axes=() if shoal else ("data",))
        trainer = Trainer(model, AdamWConfig(lr=1e-3),
                          TrainerConfig(comm_backend=backend),
                          dp_axes=("data",))
        state = jax.jit(trainer.init_state,
                        out_shardings=NamedSharding(mesh, P()))(
            jax.random.PRNGKey(seed))
        with jax.default_matmul_precision("highest"):
            t0 = time.perf_counter()
            step = trainer.make_train_step().lower(state, batches[0]).compile()
            t_compile = time.perf_counter() - t0
        mem = step.memory_analysis()
        losses[backend], norms, times = [], [], []
        for batch in batches:
            (state, metrics), dt = timed(step, state, batch)
            losses[backend].append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
            times.append(dt)
        log(f"{TRAIN_ARCH} {backend}: compiled in {t_compile:.2f}s, "
            f"temp {mem.temp_size_in_bytes / 2**30:.2f} GiB/chip, steps "
            f"{times}s, losses {losses[backend]}, grad norms {norms}")
        del state, step
    gap = float(np.max(np.abs(np.subtract(losses["xla"], losses["shoal"]))))
    check("gradient sync shoal vs xla", bool(gap <= TRAIN_LOSS_TOL),
          f"max|loss gap| over {TRAIN_STEPS} steps {gap:.3e} "
          f"(limit {TRAIN_LOSS_TOL:g})")


def phase_kv_migration(seed: int) -> None:
    from repro.launch.mesh import ServingSlices
    from repro.models.model import ModelConfig, build_model
    from repro.serving import Request, ServeEngine
    from repro.serving.disagg import DisaggServeTier

    # the width of examples/serve_disagg.py
    cfg = ModelConfig(name="demo", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      dtype=jnp.float32)
    slots = 16
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    tier = DisaggServeTier(model, params, ServingSlices(n_prefill=2,
                                                        n_decode=2),
                           lanes_per_decode=2, slots=slots)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, int(n)).astype(np.int32)
               for n in rng.integers(2, 7, 6)]
    reqs = [Request(i, p, 5) for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    done = tier.run(reqs)
    dt = time.perf_counter() - t0
    err = np.asarray(jax.device_get(tier.state.error))
    devs = {s.device for s in tier.state.segment.addressable_shards}
    oracle = ServeEngine(model, params, lanes=1, slots=slots)
    mismatched = []
    for req in reqs:
        ref = Request(req.rid, req.prompt, req.max_new)
        oracle.run([ref])
        if req.out != ref.out:
            mismatched.append((req.rid, req.out, ref.out))
    check("KV migration", len(done) == len(reqs) and not mismatched
          and tier.migrations == len(reqs) and not err.any()
          and len(devs) == 4,
          f"{tier.migrations} migrations over {len(devs)} devices in "
          f"{dt:.2f}s, error bits {err.tolist()}, token mismatches vs the "
          f"in-place engine: {mismatched or 'none'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}, {len(devices)} x {dev.platform} "
        f"{dev.device_kind}, compile cache {cache}")
    if dev.platform != "tpu":
        log(f"no TPU found (platform {dev.platform!r}); not running")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} chips, "
            f"found {len(devices)}")
        return 1

    if args.chips == 1:
        phases = [("(a) local AMs", phase_local_ams),
                  ("(b) Jacobi", phase_jacobi),
                  ("(c) serving", phase_serve)]
    else:
        phases = [("Jacobi halo exchange", phase_jacobi4),
                  ("KV migration", phase_kv_migration),
                  ("gradient sync", phase_grad_sync)]
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        log(f"{name} passed in {time.perf_counter() - t0:.2f}s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
