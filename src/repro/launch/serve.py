"""Serving launcher: batched requests through the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 6 --lanes 2
"""

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.models.model import build_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.engine import Request, ServeEngine


def build_engine(cfg, *, seed: int, lanes: int, slots: int) -> ServeEngine:
    """Random weights from ``seed``, stored in the model's compute dtype."""
    model = build_model(cfg)

    @jax.jit
    def init(key):
        return jax.tree.map(lambda p: p.astype(cfg.dtype), model.init(key))

    return ServeEngine(model, init(jax.random.PRNGKey(seed)), lanes=lanes,
                       slots=slots)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.reduced(args.arch) if args.reduced else configs.full(args.arch)
    if cfg.frontend != "tokens":
        raise SystemExit("serving demo supports token-frontend archs")
    enable_compile_cache()
    engine = build_engine(cfg, seed=args.seed, lanes=args.lanes,
                          slots=args.slots)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        rng.integers(3, 10)).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    for r in done:
        print(f"req {r.rid}: prompt {list(r.prompt)} -> {r.out}")
    dev = jax.devices()[0]
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s on {dev.platform} {dev.device_kind}, "
          f"{args.lanes} lanes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
