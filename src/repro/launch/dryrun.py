import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: JAX locks the
# device count at first init, and the production meshes below need 512
# placeholder host devices (dry-run only — no tensor is ever allocated).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds the real train_step / prefill / decode_step,
  3. ``jit(...).lower(**ShapeDtypeStruct args).compile()`` — proving the
     sharding config is coherent at 512 chips,
  4. records memory_analysis / cost_analysis / trip-count-weighted
     collective bytes (launch/hlo_analysis.py) to a JSON lines file that
     §Roofline and §Perf read.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.configs import shapes as shp
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import dp_axes_of, make_production_mesh
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig
from repro.training.train import Trainer, TrainerConfig


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             comm_backend: str = "xla", override_cfg=None,
             save_hlo: str | None = None, microbatches: int = 8,
             serve_tp_only: bool = False) -> dict:
    """``serve_tp_only``: serve-path weights sharded TP-only (no FSDP) —
    inference wants gathered-once weights, not per-layer FSDP gathers."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes_of(mesh)
    cfg = override_cfg if override_cfg is not None else configs.full(arch)
    if not cfg.tp and not cfg.seq_shard:
        # no tensor parallelism: the model axis joins DP (with seq_shard
        # the model axis carries the sequence instead)
        dp = dp + ("model",)
    shape = shp.SHAPES[shape_name]
    if not shp.applicable(cfg, shape_name):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md Sec. 5)"}

    if serve_tp_only and shape.mode in ("prefill", "decode"):
        import dataclasses as _dc
        cfg = _dc.replace(cfg, fsdp=False)
    # the shoal backend runs the model inside a manual-DP shard_map, so
    # its activation constraints must not mention the DP axes
    model_dp = () if comm_backend == "shoal" else dp
    model = build_model(cfg, mesh=mesh, dp_axes=model_dp)
    t0 = time.time()
    scan_trips = [reps for _, reps in cfg.segments()]

    if shape.mode == "train":
        trainer = Trainer(model, AdamWConfig(),
                          TrainerConfig(comm_backend=comm_backend,
                                        microbatches=microbatches),
                          dp_axes=dp)
        state_sds, batch_sds = specs.train_args(model, trainer, shape, mesh)
        step = trainer.make_train_step()
        lowered = step.lower(state_sds, batch_sds)
    elif shape.mode == "prefill":
        params, batch, cache = specs.prefill_args(model, shape, mesh)
        lowered = jax.jit(model.prefill, donate_argnums=(2,)).lower(
            params, batch, cache)
    else:  # decode
        params, cache, token, pos = specs.decode_args(model, shape, mesh)
        if cfg.family == "vlm":
            from jax.sharding import NamedSharding, PartitionSpec as P
            imf = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_image_tokens, cfg.d_model),
                jax.numpy.bfloat16,
                sharding=NamedSharding(mesh, P(dp, None, None)))
            lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
                params, cache, token, pos, imf)
        else:
            lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
                params, cache, token, pos)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    coll = hlo_analysis.parse_collectives(hlo)

    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "backend": comm_backend, "mode": shape.mode, "status": "ok",
        "mesh": dict(mesh.shape),
        "scan_trips": scan_trips,
        "microbatches": microbatches if shape.mode == "train" else 0,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": cost.get("flops", 0.0),
            "dot_flops_weighted": coll.dot_flops,
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "output_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)
                           - getattr(mem, "alias_size_in_bytes", 0)),
            "collective_shape_bytes": coll.shape_bytes,
            "collective_wire_bytes": coll.wire_bytes,
            "collective_ops": coll.ops,
            "collective_by_kind": coll.by_kind,
        },
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--backend", default="xla", choices=["xla", "shoal"])
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--microbatches", type=int, default=8)
    args = ap.parse_args()

    cells = []
    archs = ([a.replace("_", "-") for a in configs.ARCH_IDS]
             if args.all or args.arch is None else [args.arch])
    shapes = (list(shp.SHAPES) if args.all or args.shape is None
              else [args.shape])
    pods = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in pods:
                cells.append((a, s, mp))

    n_ok = n_skip = n_fail = 0
    for a, s, mp in cells:
        label = f"{a} x {s} x {'2pod' if mp else '1pod'} [{args.backend}]"
        try:
            rec = run_cell(a, s, multi_pod=mp, comm_backend=args.backend,
                           save_hlo=args.save_hlo,
                           microbatches=args.microbatches)
        except Exception as e:  # a failing cell is a bug in the system
            rec = {"arch": a, "shape": s, "multi_pod": mp,
                   "backend": args.backend, "status": "FAILED",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        if rec["status"] == "ok":
            n_ok += 1
            pd = rec["per_device"]
            print(f"OK   {label}: compile {rec['compile_s']}s, "
                  f"{pd['flops']/1e9:.1f} GF/dev, "
                  f"peak {pd['peak_bytes']/1e9:.2f} GB/dev, "
                  f"wire {pd['collective_wire_bytes']/1e6:.1f} MB/dev",
                  flush=True)
        elif rec["status"] == "skipped":
            n_skip += 1
            print(f"SKIP {label}: {rec['reason']}", flush=True)
        else:
            n_fail += 1
            print(f"FAIL {label}: {rec['error']}", flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    print(f"dry-run: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
