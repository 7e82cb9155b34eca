"""Table I analogue: per-component cost of the GAScore datapath.

The paper reports LUT/FF/BRAM per GAScore stage.  The TPU-native
equivalents of "hardware cost" are compiled FLOPs, bytes accessed, and
the kernels' VMEM working sets — extracted per stage from
``jit(stage).lower().compile().cost_analysis()``.  Runs on the single
real CPU device (the stages are per-kernel datapaths).
"""

import jax
import jax.numpy as jnp

from repro.core import am, gascore as gc, handlers as hd
from repro.core.state import PgasState, ShoalContext
from repro.runtime.topology import make_cpu_mesh

PKT = 1024  # words per packet for the costing


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis() or {}
    return c.get("flops", 0.0), c.get("bytes accessed", 0.0)


def main():
    mesh = make_cpu_mesh(1, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), segment_words=8192)
    st = PgasState.make(8192)
    # headers travel as raw int32 vectors; decode inside the jitted stage
    hdr_long = am.encode(type=am.make_type(am.LONG), nwords=PKT,
                         dst_addr=64, handler=hd.H_ADD)
    hdr_med = am.encode(type=am.make_type(am.MEDIUM), nwords=PKT)
    hdr_short = am.encode(type=am.make_type(am.SHORT), handler=hd.H_ADD,
                          token=1)
    hdr_get = am.encode(type=am.make_type(am.MEDIUM, get=True), nwords=PKT,
                        src_addr=0)
    pay = jnp.ones((PKT,), jnp.float32)

    rows = [
        ("gascore/am_rx+xpams_rx (ingress_long)",
         *_cost(lambda s, h, p: gc.ingress_long(ctx, s, am.decode(h), p, PKT),
                st, hdr_long, pay)),
        ("gascore/xpams_rx->kernels (ingress_medium)",
         *_cost(lambda s, h, p: gc.ingress_medium(s, am.decode(h), p, PKT),
                st, hdr_med, pay)),
        ("gascore/handler-wrapper (ingress_short)",
         *_cost(lambda s, h: gc.ingress_short(ctx, s, am.decode(h)),
                st, hdr_short)),
        ("gascore/datamover-read (egress mem)",
         *_cost(lambda s, h: gc.egress(ctx, s, am.decode(h), None, PKT),
                st, hdr_long)),
        ("gascore/get-responder (serve_get)",
         *_cost(lambda s, h: gc.serve_get(ctx, s, am.decode(h), PKT),
                st, hdr_get)),
        ("gascore/reply (ingress_reply)",
         *_cost(lambda s, h: gc.ingress_reply(s, am.decode(h)),
                st, hdr_short)),
    ]
    for name, flops, byts in rows:
        print(f"{name},0.0,flops={flops:.0f};bytes={byts:.0f}")

    # kernel VMEM working sets (the BRAM analogue)
    vmem = [
        ("kernels/jacobi 256x2048 f32 band x4", 4 * 256 * 2048 * 4),
        ("kernels/flash_attn BQ=BK=512 dh=128 f32", (2 * 512 * 128 * 4
                                                     + 2 * 512 * 128 * 4
                                                     + 512 * 4 * 2)),
        ("kernels/am_pack 8192-word segment f32", 8192 * 4),
    ]
    for name, b in vmem:
        print(f"{name},0.0,vmem_bytes={b}")


if __name__ == "__main__":
    main()
