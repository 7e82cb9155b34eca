"""Record the small profiler trace the trace-reduction test reads.

    python3 benchmarks/chip/tests/record_trace.py [--out DIR]   # one TPU chip

Traces three calls of a small program under the harness's profiler
options and host spans, and writes the trace gzipped to ``--out``
(default ``benchmarks/chip/testdata/``): ``jacobi512.xplane.pb.gz``
(JacobiApp, 512x512, 8 iterations, one kernel, the compiled Pallas
stencil).
"""

from __future__ import annotations

import argparse
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent.parent / "src"))

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402

OUT = BENCH_DIR / "testdata"


def traced(out: Path, name: str, fn) -> Path:
    import jax

    jax.block_until_ready(fn())
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        jax.profiler.start_trace(tmp,
                                 profiler_options=harness.profile_options())
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    res = fn()
                with jax.profiler.TraceAnnotation("bench.block"):
                    jax.block_until_ready(res)
        jax.profiler.stop_trace()
        (src,) = Path(tmp).rglob("*.xplane.pb")
        dst = out / f"{name}.xplane.pb.gz"
        with open(src, "rb") as f, gzip.open(dst, "wb") as g:
            shutil.copyfileobj(f, g)
        return dst
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.apps.jacobi import JacobiApp
    from repro.core.address_space import GlobalAddressSpace

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, default=OUT)
    out = ap.parse_args().out
    harness.find_devices(1)
    out.mkdir(parents=True, exist_ok=True)

    app = JacobiApp(n=512, kernels=1, iters=8, use_pallas=True)
    st0 = GlobalAddressSpace(app.ctx).make_global_state()
    grid = jax.random.normal(jax.random.key(0), (1, 512, 512), jnp.float32)
    fn = app.build()
    print(traced(out, "jacobi512", lambda: fn(st0, grid)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
