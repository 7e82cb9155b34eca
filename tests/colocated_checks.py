"""Several Shoal kernels on one device: checks run by
tests/test_colocated.py in a subprocess with 8 host devices.

Each check runs on its own and prints one JSON line, ``{"check": name,
"ok": bool, "error": text}``, so that one failure does not hide the
others.  The checks:

* the Jacobi app on 8 kernels over 1 and 2 devices, on 4 kernels over
  1 and 4 and on 1 kernel, at 1, 3 and 4 iterations, equals the plain
  reference and consumes every reply credit;
* the AM ops between kernels that share a device leave every kernel's
  segment, credits, ledger and counters as the same program does with
  one kernel per device, on layouts where the patterns are all LOCAL
  (8 kernels on 1 device) and mixed (2 devices of 4);
* the same for the mailbox flush of a mixed Long and Short stack, the
  strided put (aliasing and not) and the vectored put;
* a pattern with LOCAL and ICI pairs at once, whose devices exchange
  with several devices (4 devices of 2), and one whose ICI pairs keep
  their slots, so that no in-device staging is built;
* the reliable put on a lossy transport whose faults fall on ICI links
  only (4 devices of 2);
* the collective budgets of the Jacobi programs, the colocated one
  (no collective-permute) and the two with one kernel per device.
"""

import dataclasses
import json
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

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
# LOCAL and ICI pairs at once: on 4 devices of 2 kernels, device 0
# sends to devices 1 and 2 and receives from both, so its ICI pairs
# need two rounds; two pairs are self-puts
MIXED = [(0, 2), (1, 4), (2, 3), (3, 0), (4, 5), (5, 1), (6, 6), (7, 7)]
LAYOUTS = {"8x1": (8, 1), "1x8": (1, 8), "2x4": (2, 4), "4x2": (4, 2)}

CHECKS = {}


def check(fn):
    CHECKS[fn.__name__] = fn
    return fn


def context(layout, segment_words=128, transport=TINY_TCP):
    devices, per_device = LAYOUTS[layout]
    return ShoalContext(mesh=make_cpu_mesh(devices, ("kernel",)),
                        axes=("kernel",), transport=transport,
                        segment_words=segment_words,
                        kernels_per_device=per_device)


def run(layout, prog, **kw):
    """``prog(ctx, st) -> st`` on every kernel; the final state on the
    host, one row per kernel."""
    ctx = context(layout, **kw)
    gas = GlobalAddressSpace(ctx)
    st = jax.jit(gas.spmd(lambda s: prog(ctx, s)))(gas.make_global_state())
    return jax.tree.map(np.asarray, jax.device_get(st))


def same_state(prog, layouts=("1x8", "2x4"), **kw):
    """Every leaf of the final state equals the one-kernel-per-device
    run's, on each layout; returns that run's state."""
    want = run("8x1", prog, **kw)
    assert not want.error.any(), want.error
    for layout in layouts:
        got = run(layout, prog, **kw)
        for name in want.__dataclass_fields__:
            a, b = getattr(want, name), getattr(got, name)
            assert np.array_equal(a, b), (layout, name, a, b)
    return want


def scale(ctx):
    return (ctx.my_id() + 1).astype(jnp.float32)


# -- the Jacobi app ---------------------------------------------------------------

def jacobi_case(kernels, chips, iters):
    from repro.apps.jacobi import JacobiApp, jacobi_reference

    grid = np.random.default_rng(kernels * 10 + chips).standard_normal(
        (64, 64)).astype(np.float32)
    app = JacobiApp(n=64, kernels=kernels, iters=iters, transport=TINY_TCP,
                    use_pallas=True, interpret=True, chips=chips)
    gas = GlobalAddressSpace(app.ctx)
    st, out = app.build()(gas.make_global_state(),
                          jnp.asarray(grid.reshape(kernels, app.rows, 64)))
    st = jax.tree.map(np.asarray, jax.device_get(st))
    err = np.abs(np.asarray(out).reshape(64, 64)
                 - jacobi_reference(grid, iters)).max()
    assert err <= 1e-5, err
    # after the drain every halo's reply credit is consumed
    assert not st.error.any(), st.error
    assert (st.credits == 0).all() and (st.deferred_acks == 0).all(), st
    links = app.links_per_iteration()
    # a 64-word halo row is 4 packets at a 16-word MTU
    boundaries = 2 * (kernels - 1)
    crossing = 2 * (chips - 1)
    assert links.get("LOCAL", {}).get("packets", 0) == \
        4 * (boundaries - crossing), links
    assert links.get("ICI", {}).get("packets", 0) == 4 * crossing, links


# odd and even iteration counts: the solve loop runs two a trip
for _k, _c in ((8, 1), (8, 2), (4, 1), (4, 4), (1, 1)):
    for _it in (1, 3, 4):
        def _case(k=_k, c=_c, it=_it):
            jacobi_case(k, c, it)
        CHECKS[f"jacobi_{_k}k_{_c}dev_{_it}it"] = _case


# -- the AM ops between kernels on one device -------------------------------------

@check
def put_long():
    def prog(ctx, st):
        f = scale(ctx)
        # 50 words at a 16-word MTU: 4 packets, one credit
        st = ops.put_long(ctx, st, jnp.arange(50, dtype=jnp.float32) * f,
                          RING, dst_addr=8, token=1)
        st = ops.put_long(ctx, st, jnp.ones(3, jnp.float32) * f, BACK,
                          dst_addr=70, handler=hd.H_ADD, token=2,
                          asynchronous=True)
        st = ops.put_long(ctx, st, None, RING, dst_addr=90,
                          from_segment_addr=8, nwords=20, token=3)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return ops.wait_replies(ctx, st, token=3, n=1)

    st = same_state(prog)
    for k in range(K):
        src = (k - 1) % K
        np.testing.assert_array_equal(st.segment[k, 8:58],
                                      np.arange(50) * (src + 1))
        np.testing.assert_array_equal(st.segment[k, 70:73], (k + 1) % K + 1)
    assert (st.credits == 0).all()


@check
def put_long_multi():
    """Jacobi's steady state by hand: up and down halos in one call,
    acks deferred and piggybacked on the next call, drained at the end."""
    def prog(ctx, st):
        me = ctx.my_id()
        f = scale(ctx)
        has_up = (me > 0).astype(jnp.int32)
        has_down = (me < K - 1).astype(jnp.int32)
        for it in range(3):
            items = [(jnp.full(20, f + it), UP, 40),
                     (jnp.full(20, -f - it), DOWN, 0)]
            st = ops.put_long_multi(ctx, st, items, tokens=[1, 2],
                                    defer_ack=True, piggyback_tokens=[2, 1])
            if it:
                st = ops.wait_replies(ctx, st, 1, has_up)
                st = ops.wait_replies(ctx, st, 2, has_down)
        st = ops.drain_deferred_acks(ctx, st, DOWN, token=1)
        st = ops.drain_deferred_acks(ctx, st, UP, token=2)
        st = ops.wait_replies(ctx, st, 1, has_up)
        return ops.wait_replies(ctx, st, 2, has_down)

    st = same_state(prog)
    assert (st.credits == 0).all() and (st.deferred_acks == 0).all()
    for k in range(K):
        if k < K - 1:
            np.testing.assert_array_equal(st.segment[k, 40:60], k + 2 + 2)
        if k > 0:
            np.testing.assert_array_equal(st.segment[k, 0:20], -(k + 2))


@check
def get_medium_and_get_long():
    def prog(ctx, st):
        seg = st.segment.at[:50].set(jnp.arange(50, dtype=jnp.float32)
                                     * scale(ctx))
        st = dataclasses_replace(st, segment=seg)
        st, data = ops.get_medium(ctx, st, RING, src_addr=0, nwords=50,
                                  token=2)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        st = dataclasses_replace(
            st, segment=jax.lax.dynamic_update_slice(st.segment, data, (60,)))
        st = ops.get_long(ctx, st, BACK, src_addr=0, nwords=5, dst_addr=115,
                          token=3)
        return ops.wait_replies(ctx, st, token=3, n=1)

    st = same_state(prog)
    for k in range(K):
        np.testing.assert_array_equal(st.segment[k, 60:110],
                                      np.arange(50) * ((k + 1) % K + 1))


@check
def barrier_short_and_medium():
    def prog(ctx, st):
        st = ops.barrier(ctx, st)
        st = ops.put_short(ctx, st, RING, arg=3, token=4,
                           asynchronous=True)
        st, got = ops.put_medium(ctx, st, jnp.full(30, scale(ctx)), BACK,
                                 token=5)
        st = ops.wait_replies(ctx, st, token=5, n=1)
        st = dataclasses_replace(st, segment=st.segment.at[:30].set(got))
        return ops.barrier(ctx, st)

    st = same_state(prog)
    assert (st.barrier_epoch == 2).all() and (st.credits[:, 4] == 3).all()
    for k in range(K):
        np.testing.assert_array_equal(st.segment[k, :30], (k + 1) % K + 1)


@check
def mailbox_flush_mixed_stack():
    """Long writes, a Long add and Short signals in one flush: the
    mixed-class ingress of the mailbox."""
    from repro.actors import Mailbox

    def prog(ctx, st):
        mb = Mailbox(ctx, RING, msg_words=4, watermark=64, token=5)
        f = scale(ctx)
        for i in range(5):
            st = mb.send(st, f * (jnp.arange(4.0) + 1) + 10 * i,
                         dst_addr=8 * i)
        st = mb.send(st, jnp.full((4,), 0.5), dst_addr=0, handler=hd.H_ADD)
        st = mb.send_signal(st, arg=3, token=7)
        st = mb.send_signal(st, arg=2, token=7)
        st = mb.flush(st)
        return ops.wait_replies(ctx, st, token=5, n=1)

    st = same_state(prog)
    for k in range(K):
        src = (k - 1) % K
        for i in range(5):
            want = (src + 1) * (np.arange(4.0) + 1) + 10 * i + (i == 0) * 0.5
            np.testing.assert_array_equal(st.segment[k, 8 * i:8 * i + 4],
                                          want)
    assert (st.credits[:, 7] == 5).all() and (st.credits[:, 5] == 0).all()


@check
def put_long_strided():
    """A stride that does not alias (segmented at block granularity)
    and one that does (the ordered, last-writer-wins ingress)."""
    def prog(ctx, st):
        f = scale(ctx)
        st = ops.put_long_strided(ctx, st, jnp.arange(24.0) * f, RING,
                                  dst_addr=4, stride=10, blk_words=4,
                                  nblocks=6, token=1)
        st = ops.put_long_strided(ctx, st, jnp.arange(16.0) * f + 100, BACK,
                                  dst_addr=70, stride=2, blk_words=4,
                                  nblocks=4, token=2)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return ops.wait_replies(ctx, st, token=2, n=1)

    st = same_state(prog)
    for k in range(K):
        f = (k - 1) % K + 1
        for b in range(6):
            np.testing.assert_array_equal(
                st.segment[k, 4 + 10 * b:8 + 10 * b],
                np.arange(4 * b, 4 * b + 4) * f)
        f = (k + 1) % K + 1
        want = np.zeros(10)
        for b in range(4):
            want[2 * b:2 * b + 4] = np.arange(4 * b, 4 * b + 4) * f + 100
        np.testing.assert_array_equal(st.segment[k, 70:80], want)


@check
def put_long_vectored():
    def prog(ctx, st):
        f = scale(ctx)
        blocks = [jnp.arange(3.0) * f, jnp.full(5, -f), jnp.ones(2) * f]
        st = ops.put_long_vectored(ctx, st, blocks, RING,
                                   dst_addrs=[30, 10, 60], token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    st = same_state(prog)
    for k in range(K):
        f = (k - 1) % K + 1
        np.testing.assert_array_equal(st.segment[k, 30:33], np.arange(3) * f)
        np.testing.assert_array_equal(st.segment[k, 10:15], -f)
        np.testing.assert_array_equal(st.segment[k, 60:62], f)


# -- LOCAL and ICI pairs in one pattern -------------------------------------------

@check
def mixed_pattern():
    from repro.analysis import trace
    from repro.launch.hlo_analysis import parse_collectives

    def prog(ctx, st):
        st = ops.put_long(ctx, st, jnp.arange(40, dtype=jnp.float32)
                          * scale(ctx), MIXED, dst_addr=4, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st, data = ops.get_medium(ctx, st, MIXED, src_addr=4, nwords=8,
                                  token=2)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        return dataclasses_replace(st, segment=st.segment.at[100:108]
                                   .set(data))

    st = same_state(prog, layouts=("1x8", "2x4", "4x2"))
    for s, d in MIXED:
        np.testing.assert_array_equal(st.segment[d, 4:44],
                                      np.arange(40) * (s + 1))
        np.testing.assert_array_equal(st.segment[s, 100:108],
                                      np.arange(8) * (s + 1))
    # the pairs that share a device move inside it: on 4 devices of 2,
    # device 0 exchanges with devices 1 and 2, two ppermute rounds per
    # traversal; on 1 device there is no collective-permute at all
    for layout, rounds in (("4x2", 2), ("1x8", 0)):
        ctx = context(layout)
        gas = GlobalAddressSpace(ctx)
        fn = jax.jit(gas.spmd(lambda s: prog(ctx, s)))
        hlo = fn.lower(gas.make_global_state()).compile().as_text()
        cps = parse_collectives(hlo).ops.get("collective-permute", 0)
        # put + reply + get request + get response: 4 traversals
        assert cps == 4 * rounds, (layout, cps)
        with trace.record() as rec:
            jax.eval_shape(gas.spmd(lambda s: prog(ctx, s)),
                           gas.make_global_state())
        local = 4 if layout == "4x2" else 8
        # put (3 packets at 40 words) + reply + get (1 request row and
        # 1 response row): 6 rows a pair
        assert rec.links["LOCAL"]["packets"] == 6 * local, rec.links
        assert rec.links.get("ICI", {}).get("packets", 0) == \
            6 * (8 - local), rec.links


@check
def in_slot_pattern():
    """On 4 devices of 2, pairs that keep their slot (kernel 0 to 2, 1
    to 3) cross the link as the device's slot stack as it is: no
    in-device staging (no ``local`` instruction) is built.  Pairs that
    swap slots need it."""
    from repro.launch.hlo_analysis import op_layers

    def prog_for(pattern):
        def prog(ctx, st):
            st = ops.put_long(ctx, st, jnp.arange(20.0) * scale(ctx),
                              pattern, dst_addr=4, token=1)
            sender = (ctx.my_id() < 2).astype(jnp.int32)
            return ops.wait_replies(ctx, st, token=1, n=sender)
        return prog

    for pattern in ([(0, 2), (1, 3)], [(0, 3), (1, 2)]):
        prog = prog_for(pattern)
        st = same_state(prog, layouts=("1x8", "2x4", "4x2"))
        for s, d in pattern:
            np.testing.assert_array_equal(st.segment[d, 4:24],
                                          np.arange(20) * (s + 1))
        ctx = context("4x2")
        gas = GlobalAddressSpace(ctx)
        hlo = jax.jit(gas.spmd(lambda s: prog(ctx, s))).lower(
            gas.make_global_state()).compile().as_text()
        layers = set(op_layers(hlo).values())
        assert "wire" in layers
        assert ("local" in layers) == (pattern[0] == (0, 3)), (pattern,
                                                               layers)


@check
def reliable_put_long_ici_faults():
    """The reliable put over the ring on 4 devices of 2 kernels, with
    faults on ICI links only: every segment lands as on a lossless
    link, the credits balance, no error bit is set, and only the pairs
    that cross devices retransmit."""
    from repro.core.faults import FaultModel
    from repro.runtime import LossyTransport
    from repro.runtime.transport import LinkClass

    def prog(ctx, st):
        pay = (jnp.arange(16.0) + 1) * scale(ctx)
        st = ops.put_long(ctx, st, pay, RING, dst_addr=10, token=1)
        return ops.wait_replies(ctx, st, token=1, n=1, timeout=True)

    # 4 payload words a packet: the put is 4 segments
    want = run("8x1", prog, transport=dataclasses.replace(
        TCP, max_packet_bytes=16)).segment
    retried = np.zeros(K, bool)
    for seed in (7, 11, 19):
        lossy = LossyTransport(faults=FaultModel(drop=0.1, seed=seed),
                               max_packet_bytes=16,
                               lossy_links=(LinkClass.ICI,),
                               link_of=lambda s, d: LinkClass.ICI)
        st = run("4x2", prog, transport=lossy)
        np.testing.assert_array_equal(st.segment, want)
        assert (st.credits == 0).all() and (st.dedup_seen == 0).all()
        assert (st.dedup_epoch[:, 1] == 1).all()
        assert not st.error.any(), st.error
        retried |= st.retransmits > 0
    # kernel k sends to k + 1: odd kernels cross a device boundary
    assert retried[1::2].any() and not retried[0::2].any(), retried


# -- collective budgets -----------------------------------------------------------

for _entry in ("jacobi-colocated", "jacobi", "jacobi-steady"):
    def _budget(entry=_entry):
        from repro.analysis import registry

        rep = registry.run_entry(entry)
        assert rep.ok, rep.render()
    CHECKS[f"budget_{_entry.replace('-', '_')}"] = _budget


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
