"""How ``put_long_multi`` lands a received packet stack: checks run by
tests/test_landing.py in a subprocess with 8 host devices.

Each check runs on its own and prints one JSON line, ``{"check": name,
"ok": bool, "error": text}``.  The checks:

* equivalence: a program's final state (segment, credits, ledger,
  counters: every leaf) is bit for bit the same whether its stacks land
  in one pass (``gascore.ingress_long_stack``) or through the scanned
  ``gascore.ingress_stack`` on the same received stack, and each
  landing takes the path its plan allows (a traced address or a
  waivered overlap keeps the scan);
* the landing counter (``trace.Recorder.landings``): one Jacobi
  iteration at 4 and at 8 kernels lands its 2 halo stacks in one pass,
  and a mailbox flush or a waivered call still records the scan.
"""

import contextlib
import dataclasses
import json
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace
from repro.analysis import waiver
from repro.core import gascore as gc
from repro.core import handlers as hd
from repro.core import ops
from repro.core.address_space import GlobalAddressSpace
from repro.core.gascore import dataclasses_replace
from repro.core.state import ShoalContext
from repro.runtime import TCP
from repro.runtime.topology import make_cpu_mesh

K = 8
TINY_TCP = dataclasses.replace(TCP, max_packet_bytes=64)   # 16 words
RING = [(i, (i + 1) % K) for i in range(K)]
BACK = [(d, s) for s, d in RING]
UP = [(i, i - 1) for i in range(1, K)]
DOWN = [(i, i + 1) for i in range(K - 1)]
# two disjoint rings: sources and destinations disjoint, so one group
EVEN = [(i, i + 1) for i in range(0, K, 2)]
ODD = [(i, (i + 1) % K) for i in range(1, K, 2)]
LAYOUTS = {"8x1": (8, 1), "1x8": (1, 8), "2x4": (2, 4), "4x2": (4, 2)}

CHECKS = {}


def check(fn):
    CHECKS[fn.__name__] = fn
    return fn


@contextlib.contextmanager
def scan_only():
    """Land every ``put_long_multi`` stack through the scan."""
    one_pass = gc.ingress_long_stack
    gc.ingress_long_stack = (
        lambda ctx, st, hdr_r, pay_r, blocks, handler, w:
        gc.ingress_stack(ctx, st, hdr_r, pay_r, w))
    try:
        yield
    finally:
        gc.ingress_long_stack = one_pass


def host(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))


def run(layout, prog, segment_words=128, transport=TINY_TCP):
    """``prog(ctx, st) -> st`` on every kernel: the final state on the
    host (one row per kernel) and the landings its trace recorded."""
    devices, per_device = LAYOUTS[layout]
    ctx = ShoalContext(mesh=make_cpu_mesh(devices, ("kernel",)),
                       axes=("kernel",), transport=transport,
                       segment_words=segment_words,
                       kernels_per_device=per_device)
    gas = GlobalAddressSpace(ctx)
    with trace.record() as rec:
        st = jax.jit(gas.spmd(lambda s: prog(ctx, s)))(
            gas.make_global_state())
    return host(st), rec.landings


def assert_same(got, want):
    for name in want.__dataclass_fields__:
        a, b = getattr(got, name), getattr(want, name)
        assert np.array_equal(a, b), (name, a, b)


def same_as_scan(layout, prog, paths, **kw):
    """The program's state equals its all-scan run's, leaf for leaf, and
    its landings took ``paths``; returns the state."""
    got, landings = run(layout, prog, **kw)
    with scan_only():
        want, scans = run(layout, prog, **kw)
    assert not want.error.any(), want.error
    assert_same(got, want)
    assert [p for p, _ in landings] == paths, landings
    assert [r for _, r in landings] == [r for _, r in scans], \
        (landings, scans)
    return got


def scale(ctx):
    return (ctx.my_id() + 1).astype(jnp.float32)


def seeded(ctx, n, it=0):
    """A payload that differs by kernel and iteration."""
    return jnp.sin(jnp.arange(n, dtype=jnp.float32) * scale(ctx) + it)


# -- equivalence: one pass against the scan ---------------------------------------

def jacobi_case(kernels, chips, iters=3):
    """The Jacobi app's state and grid after ``iters`` iterations (halos
    with deferred, piggybacked acks, then the drain)."""
    from repro.apps.jacobi import JacobiApp

    app = JacobiApp(n=64, kernels=kernels, iters=iters, transport=TINY_TCP,
                    chips=chips)
    gas = GlobalAddressSpace(app.ctx)
    grid = np.random.default_rng(kernels * 10 + chips).standard_normal(
        (64, 64)).astype(np.float32)
    blocks = jnp.asarray(grid.reshape(kernels, app.rows, 64))

    def go():
        with trace.record() as rec:
            st, out = app.build()(gas.make_global_state(), blocks)
        return host(st), np.asarray(out), rec.landings

    st, out, landings = go()
    with scan_only():
        want_st, want_out, _ = go()
    assert not want_st.error.any(), want_st.error
    assert_same(st, want_st)
    assert np.array_equal(out, want_out)
    # a 64-word halo row is 4 rows at a 16-word MTU
    assert landings == [("one_pass", 4)] * 2, landings
    assert (st.credits == 0).all() and (st.deferred_acks == 0).all()


for _k, _c in ((4, 4), (8, 1), (8, 4)):
    def _case(k=_k, c=_c):
        jacobi_case(k, c)
    CHECKS[f"jacobi_{_k}k_{_c}dev"] = _case


@check
def merged_rings():
    """Two items in one group (disjoint rings), partial last rows, the
    immediate counted reply."""
    def prog(ctx, st):
        items = [(seeded(ctx, 40), EVEN, 0), (seeded(ctx, 24, 1), ODD, 64)]
        st = ops.put_long_multi(ctx, st, items, tokens=[1, 2])
        me = ctx.my_id()
        st = ops.wait_replies(ctx, st, 1, (me % 2 == 0).astype(jnp.int32))
        return ops.wait_replies(ctx, st, 2, (me % 2 == 1).astype(jnp.int32))

    st = same_as_scan("4x2", prog, ["one_pass"])
    assert (st.credits == 0).all()
    assert (st.rx_words == np.where(np.arange(K) % 2, 40, 24)).all()


@check
def handler_add():
    """H_ADD accumulates onto what the segment holds, on 8 kernels of
    one device."""
    def prog(ctx, st):
        st = dataclasses_replace(st, segment=st.segment.at[:60].set(
            seeded(ctx, 60, 2)))
        for it in range(2):
            st = ops.put_long_multi(
                ctx, st, [(seeded(ctx, 33, it), RING, 7)],
                handler=hd.H_ADD, token=3)
            st = ops.wait_replies(ctx, st, 3, 1)
        return st

    st = same_as_scan("1x8", prog, ["one_pass"] * 2)
    assert (st.credits == 0).all()


@check
def partial_last_segment():
    """A 50-word payload is rows of 16, 16, 16 and 2 words; the halo
    shape of Jacobi (up and down, deferred and piggybacked acks) over
    LOCAL and ICI pairs."""
    def prog(ctx, st):
        me = ctx.my_id()
        for it in range(3):
            items = [(seeded(ctx, 50, it), UP, 60),
                     (seeded(ctx, 50, -it), DOWN, 0)]
            st = ops.put_long_multi(ctx, st, items, tokens=[1, 2],
                                    defer_ack=True, piggyback_tokens=[2, 1])
            if it:
                st = ops.wait_replies(ctx, st, 1, (me > 0).astype(jnp.int32))
                st = ops.wait_replies(ctx, st, 2,
                                      (me < K - 1).astype(jnp.int32))
        return st

    st = same_as_scan("2x4", prog, ["one_pass"] * 6)
    assert st.deferred_acks.any()


@check
def traced_dst_addr():
    """A traced destination address keeps the scan."""
    def prog(ctx, st):
        addr = 8 + 4 * (ctx.my_id() % 2)
        st = ops.put_long_multi(ctx, st, [(seeded(ctx, 30), RING, addr)],
                                token=1)
        return ops.wait_replies(ctx, st, 1, 1)

    st = same_as_scan("4x2", prog, ["scan"])
    for k in range(K):
        src = (k - 1) % K
        a = 8 + 4 * (src % 2)
        assert st.segment[k, a:a + 30].any()


def overlap_prog(ctx, st):
    with waiver("the second item overwrites the first on purpose"):
        return ops.put_long_multi(
            ctx, st, [(jnp.full(20, scale(ctx)), RING, 0),
                      (-jnp.full(20, scale(ctx)), BACK, 10)],
            asynchronous=True)


@check
def waivered_overlap():
    """Items that alias under a waiver keep the scan; the later item
    wins where they overlap."""
    st = same_as_scan("8x1", overlap_prog, ["scan"] * 2)
    for k in range(K):
        np.testing.assert_array_equal(st.segment[k, :10], (k - 1) % K + 1)
        np.testing.assert_array_equal(st.segment[k, 10:30],
                                      -((k + 1) % K + 1))


# -- the landing counter ----------------------------------------------------------

def jacobi_landings(kernels, chips):
    """The landings one traced Jacobi iteration records at grid 4096
    (a 16-KiB halo row is 2 rows at TCP's MTU)."""
    from repro.apps.jacobi import JacobiApp

    app = JacobiApp(n=4096, kernels=kernels, iters=1, chips=chips)
    st = jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.zeros((kernels,) + x.shape, x.dtype),
        app.ctx.make_state()))
    blocks = jax.ShapeDtypeStruct((kernels, app.rows, app.n), jnp.float32)
    with trace.record() as rec:
        jax.eval_shape(app.build(), st, blocks)
    assert rec.landings == [("one_pass", 2)] * 2, rec.landings


@check
def counter_jacobi_4k_4dev():
    jacobi_landings(4, 4)


@check
def counter_jacobi_8k_1dev():
    jacobi_landings(8, 1)


@check
def counter_mailbox_flush():
    from repro.actors import Mailbox

    def prog(ctx, st):
        mb = Mailbox(ctx, RING, msg_words=4, watermark=64, token=5)
        for i in range(6):
            st = mb.send(st, np.arange(3.0) + i, dst_addr=4 * i,
                         handler=hd.H_ADD if i % 2 else hd.H_WRITE)
        st = mb.send_signal(st, arg=1, token=7)
        return mb.flush(st)

    st, landings = run("2x4", prog)
    assert landings == [("scan", 7)], landings
    assert (st.credits[:, 7] == 1).all()


@check
def counter_waiver():
    _, landings = run("8x1", overlap_prog)
    assert landings == [("scan", 2)] * 2, landings


def main(names):
    for name in names or CHECKS:
        try:
            CHECKS[name]()
            res = {"check": name, "ok": True, "error": ""}
        except Exception:  # noqa: BLE001 - every check reports
            res = {"check": name, "ok": False,
                   "error": traceback.format_exc()[-3000:]}
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
