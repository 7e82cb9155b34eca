"""Record the trace of several Shoal kernels on one chip, with the
module that ran, that the colocated layer test reads.

    python3 benchmarks/chip/tests/record_colocated_trace.py [--out DIR]   # one TPU chip

Traces three calls of JacobiApp (512x512, 8 iterations, 8 kernels on
one chip, the compiled Pallas stencil) under the harness's profiler
options and host spans (``record_trace.traced``), and writes gzipped to
``--out`` (default ``benchmarks/chip/testdata/``) the trace,
``jacobi512x8.xplane.pb.gz``, and the executed module's compiled text,
``jacobi512x8.hlo.txt.gz``.
"""

from __future__ import annotations

import argparse
import gzip
import sys
from pathlib import Path

import record_trace as rec

KERNELS = 8


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.apps.jacobi import JacobiApp
    from repro.core.address_space import GlobalAddressSpace

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, default=rec.OUT)
    out = ap.parse_args().out
    rec.harness.find_devices(1)
    out.mkdir(parents=True, exist_ok=True)

    app = JacobiApp(n=512, kernels=KERNELS, iters=8, use_pallas=True,
                    chips=1)
    st0 = GlobalAddressSpace(app.ctx).make_global_state()
    grid = jax.device_put(
        jax.random.normal(jax.random.key(0), (KERNELS, 512 // KERNELS, 512),
                          jnp.float32),
        NamedSharding(app.mesh, P(("kernel",))))
    fn = app.build()
    name = f"jacobi512x{KERNELS}"
    print(rec.traced(out, name, lambda: fn(st0, grid)))
    hlo = out / f"{name}.hlo.txt.gz"
    with gzip.open(hlo, "wt") as f:
        f.write(fn.lower(st0, grid).compile().as_text())
    print(hlo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
