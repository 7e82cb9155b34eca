"""Handler table + GAScore datapath unit tests (single device; the
GAScore stages are pure functions over headers/payloads/state)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import am, gascore as gc, handlers as hd
from repro.core.state import PgasState, ShoalContext
from repro.runtime.topology import make_cpu_mesh


def make_ctx(segment_words=64):
    mesh = make_cpu_mesh(1, ("kernel",))
    return ShoalContext(mesh=mesh, axes=("kernel",),
                        segment_words=segment_words)


def test_builtin_handlers():
    t = hd.HandlerTable()
    r = jnp.asarray([1.0, 2.0])
    p = jnp.asarray([10.0, 20.0])
    np.testing.assert_allclose(t.dispatch(hd.H_NOP, r, p), [1, 2])
    np.testing.assert_allclose(t.dispatch(hd.H_WRITE, r, p), [10, 20])
    np.testing.assert_allclose(t.dispatch(hd.H_ADD, r, p), [11, 22])
    np.testing.assert_allclose(t.dispatch(hd.H_MAX, r, p), [10, 20])
    np.testing.assert_allclose(t.dispatch(hd.H_MIN, r, p), [1, 2])


def test_custom_handler_registration():
    t = hd.HandlerTable()
    hid = t.register("scale2", lambda r, p: r + 2 * p)
    assert hid == hd.NUM_BUILTIN
    out = t.dispatch(hid, jnp.asarray([1.0]), jnp.asarray([3.0]))
    np.testing.assert_allclose(out, [7.0])


@settings(max_examples=25, deadline=None)
@given(handler=st.integers(0, hd.NUM_BUILTIN - 1))
def test_dispatch_traced_id(handler):
    t = hd.HandlerTable()
    r = jnp.asarray([2.0])
    p = jnp.asarray([5.0])
    expected = [r[0], p[0], r[0] + p[0], jnp.maximum(r, p)[0],
                jnp.minimum(r, p)[0]][handler]
    out = t.dispatch(jnp.asarray(handler), r, p)
    np.testing.assert_allclose(out[0], expected)


def test_ingress_long_write_and_masking():
    ctx = make_ctx()
    st_ = PgasState.make(64)
    pay = jnp.arange(1.0, 5.0)
    hdr = am.decode(am.encode(type=am.make_type(am.LONG), nwords=4,
                              dst_addr=10, handler=hd.H_WRITE))
    out = gc.ingress_long(ctx, st_, hdr, pay, 4)
    np.testing.assert_allclose(out.segment[10:14], [1, 2, 3, 4])
    assert int(out.rx_words) == 4
    # NOP header leaves the segment bit-identical
    nop = am.decode(jnp.zeros((am.HDR_WORDS,), jnp.int32))
    out2 = gc.ingress_long(ctx, out, nop, pay, 4)
    np.testing.assert_array_equal(out2.segment, out.segment)
    assert int(out2.rx_words) == 4


def test_ingress_long_partial_lanes():
    """nwords < packet width: only valid lanes land."""
    ctx = make_ctx()
    st_ = PgasState.make(64)
    pay = jnp.arange(1.0, 9.0)
    hdr = am.decode(am.encode(type=am.make_type(am.LONG), nwords=3,
                              dst_addr=0, handler=hd.H_WRITE))
    out = gc.ingress_long(ctx, st_, hdr, pay, 8)
    np.testing.assert_allclose(out.segment[:8], [1, 2, 3, 0, 0, 0, 0, 0])


def test_ingress_long_accumulate():
    ctx = make_ctx()
    st_ = PgasState.make(64)
    st_ = gc.dataclasses_replace(st_, segment=st_.segment.at[5].set(10.0))
    hdr = am.decode(am.encode(type=am.make_type(am.LONG), nwords=1,
                              dst_addr=5, handler=hd.H_ADD))
    out = gc.ingress_long(ctx, st_, hdr, jnp.asarray([7.0]), 1)
    assert float(out.segment[5]) == 17.0


def test_serve_get_and_suppression():
    ctx = make_ctx()
    st_ = PgasState.make(64)
    st_ = gc.dataclasses_replace(
        st_, segment=st_.segment.at[20:24].set(jnp.arange(4.0)))
    hdr = am.decode(am.encode(type=am.make_type(am.MEDIUM, get=True),
                              nwords=4, src_addr=20, token=2))
    st2, resp_hdr, data = gc.serve_get(ctx, st_, hdr, 4)
    np.testing.assert_allclose(data, [0, 1, 2, 3])
    rh = am.decode(resp_hdr)
    assert bool(rh.flag(am.FLAG_REPLY))
    # non-get header produces a NOP response (no spurious credits)
    nop_hdr = am.decode(am.encode(type=am.make_type(am.MEDIUM), nwords=4))
    _, resp2, data2 = gc.serve_get(ctx, st_, nop_hdr, 4)
    assert int(am.decode(resp2).msg_class) == am.NOP
    np.testing.assert_allclose(data2, 0)


def test_reply_credits():
    st_ = PgasState.make(8)
    rep = am.decode(am.reply_for(am.decode(
        am.encode(type=am.make_type(am.LONG), src=0, dst=1, token=3))))
    out = gc.ingress_reply(st_, rep)
    assert int(out.credits[3]) == 1
    # non-replies do not bump credits
    out2 = gc.ingress_reply(out, am.decode(
        am.encode(type=am.make_type(am.SHORT), token=3)))
    assert int(out2.credits[3]) == 1


def test_ingress_short_semaphore():
    ctx = make_ctx()
    st_ = PgasState.make(8)
    hdr = am.decode(am.encode(type=am.make_type(am.SHORT), handler=hd.H_ADD,
                              token=2, dst_addr=5))
    out = gc.ingress_short(ctx, st_, hdr)
    assert int(out.credits[2]) == 5


def test_auto_reply_suppression():
    acked = am.decode(am.encode(type=am.make_type(am.LONG), src=1, dst=2))
    asyn = am.decode(am.encode(
        type=am.make_type(am.LONG, asynchronous=True), src=1, dst=2))
    assert int(am.decode(gc.auto_reply(acked)).msg_class) == am.SHORT
    assert int(am.decode(gc.auto_reply(asyn)).msg_class) == am.NOP
    nop = am.decode(jnp.zeros((am.HDR_WORDS,), jnp.int32))
    assert int(am.decode(gc.auto_reply(nop)).msg_class) == am.NOP


def test_egress_memory_sourced():
    ctx = make_ctx()
    st_ = PgasState.make(64)
    st_ = gc.dataclasses_replace(
        st_, segment=st_.segment.at[8:12].set(jnp.arange(4.0) + 1))
    hdr = am.decode(am.encode(type=am.make_type(am.LONG), nwords=4,
                              src_addr=8))
    buf = gc.egress(ctx, st_, hdr, None, 4)
    np.testing.assert_allclose(buf, [1, 2, 3, 4])


def test_put_calling_conventions_validated():
    """payload=None with no (from_segment_addr, nwords) is a usage error
    and must raise a ValueError naming both conventions, not crash with
    an opaque AttributeError on payload.reshape."""
    from repro.core import ops
    ctx = make_ctx()
    st_ = PgasState.make(64)
    for op in (lambda: ops.put_medium(ctx, st_, None, [(0, 0)]),
               lambda: ops.put_long(ctx, st_, None, [(0, 0)], dst_addr=0),
               lambda: ops.put_medium(ctx, st_, None, [(0, 0)], nwords=4),
               lambda: ops.put_long(ctx, st_, None, [(0, 0)], dst_addr=0,
                                    nwords=4)):
        with pytest.raises(ValueError, match="FIFO|memory-sourced"):
            op()


def test_egress_batch_matches_single():
    """The batched egress path agrees with per-row egress for both the
    FIFO and the memory-sourced variants."""
    ctx = make_ctx(segment_words=64)
    st_ = PgasState.make(64)
    st_ = gc.dataclasses_replace(
        st_, segment=st_.segment.at[:64].set(jnp.arange(64.0)))
    # memory-sourced rows, incl. a partial final row flush with the end
    rows = am.encode_batch(3, type=am.make_type(am.LONG), nwords=jnp.asarray([8, 8, 4]),
                           src_addr=jnp.asarray([44, 52, 60]))
    out = gc.egress_batch(ctx, st_, rows, None, 8)
    np.testing.assert_allclose(out[0], np.arange(44.0, 52.0))
    np.testing.assert_allclose(out[1], np.arange(52.0, 60.0))
    np.testing.assert_allclose(out[2], [60, 61, 62, 63, 0, 0, 0, 0])
    # FIFO rows: flat payload split row-wise, last row zero-padded
    fifo = gc.egress_batch(ctx, st_, rows, jnp.arange(20.0), 8)
    np.testing.assert_allclose(fifo.reshape(-1)[:20], np.arange(20.0))
    np.testing.assert_allclose(fifo[2][4:], 0.0)


def test_ingress_strided_vectorized_matches_ref():
    """The flat gather/scatter strided ingress lands blocks exactly
    where the am_pack oracle's index map says."""
    from repro.kernels.am_pack import am_unpack_ref
    ctx = make_ctx(segment_words=64)
    st_ = PgasState.make(64)
    pay = jnp.arange(1.0, 7.0)
    hdr = am.decode(am.encode(type=am.make_type(am.LONG, strided=True),
                              nwords=6, dst_addr=5, stride=9, blk_words=2,
                              nblocks=3, handler=hd.H_WRITE))
    out = gc.ingress_strided(ctx, st_, hdr, pay, 2, 3)
    want = am_unpack_ref(st_.segment, pay, 5, 9, 2, 3)
    np.testing.assert_allclose(out.segment, want)
    # dynamic nblocks below the static capacity: trailing blocks dropped
    hdr2 = am.decode(am.encode(type=am.make_type(am.LONG, strided=True),
                               nwords=4, dst_addr=5, stride=9, blk_words=2,
                               nblocks=2, handler=hd.H_WRITE))
    out2 = gc.ingress_strided(ctx, st_, hdr2, pay, 2, 3)
    np.testing.assert_allclose(out2.segment[5:7], [1, 2])
    np.testing.assert_allclose(out2.segment[14:16], [3, 4])
    np.testing.assert_allclose(out2.segment[23:25], 0.0)


def _strided_seq_ref(segment, payload, dst_addr, stride, blk_words, nblocks,
                     handler):
    """Numpy oracle: blocks applied strictly in order, so later blocks
    see (and overwrite / accumulate onto) earlier blocks' effects."""
    seg = np.array(segment, np.float64)
    pay = np.asarray(payload, np.float64)
    for i in range(nblocks):
        lo = dst_addr + i * stride
        blk = pay[i * blk_words:(i + 1) * blk_words]
        if handler == hd.H_WRITE:
            seg[lo:lo + blk_words] = blk
        elif handler == hd.H_ADD:
            seg[lo:lo + blk_words] += blk
        else:
            raise NotImplementedError(handler)
    return seg


@pytest.mark.parametrize("handler", [hd.H_WRITE, hd.H_ADD])
def test_ingress_strided_overlap_ordered(handler):
    """Regression: stride < blk_words aliases consecutive blocks.  The
    vectorized scatter applies aliased lanes in undefined order (and its
    single up-front gather makes read-modify-write handlers read stale
    values); the ordered variant must match the sequential oracle."""
    ctx = make_ctx(segment_words=64)
    st_ = PgasState.make(64)
    st_ = gc.dataclasses_replace(
        st_, segment=st_.segment.at[:64].set(jnp.arange(64.0) / 10))
    blk_words, nblocks, stride, dst_addr = 3, 4, 1, 5
    pay = jnp.arange(1.0, 1.0 + blk_words * nblocks)
    hdr = am.decode(am.encode(
        type=am.make_type(am.LONG, strided=True), nwords=blk_words * nblocks,
        dst_addr=dst_addr, stride=stride, blk_words=blk_words,
        nblocks=nblocks, handler=handler))
    out = gc.ingress_strided(ctx, st_, hdr, pay, blk_words, nblocks,
                             ordered=True)
    want = _strided_seq_ref(st_.segment, pay, dst_addr, stride, blk_words,
                            nblocks, handler)
    np.testing.assert_allclose(np.asarray(out.segment), want, rtol=1e-6)
    assert int(out.rx_words) == blk_words * nblocks


def test_ingress_strided_ordered_matches_vectorized_when_disjoint():
    """With non-aliasing strides both variants agree (same index map,
    same masking of dynamic nblocks below static capacity)."""
    ctx = make_ctx(segment_words=64)
    st_ = PgasState.make(64)
    pay = jnp.arange(1.0, 7.0)
    hdr = am.decode(am.encode(type=am.make_type(am.LONG, strided=True),
                              nwords=4, dst_addr=5, stride=9, blk_words=2,
                              nblocks=2, handler=hd.H_WRITE))
    vec = gc.ingress_strided(ctx, st_, hdr, pay, 2, 3)
    seq = gc.ingress_strided(ctx, st_, hdr, pay, 2, 3, ordered=True)
    np.testing.assert_array_equal(np.asarray(vec.segment),
                                  np.asarray(seq.segment))


def test_put_long_strided_overlap_autoselect():
    """The op layer detects aliasing strides statically and routes the
    put through the ordered ingress: an aliasing strided put must land
    with sequential last-writer-wins semantics end to end."""
    import jax
    from repro.core import ops
    from repro.core.address_space import GlobalAddressSpace

    ctx = make_ctx(segment_words=64)
    gas = GlobalAddressSpace(ctx)
    blk_words, nblocks, stride, dst_addr = 3, 4, 1, 5
    pay = np.arange(1.0, 1.0 + blk_words * nblocks, dtype=np.float32)

    def prog(st):
        st = ops.put_long_strided(ctx, st, jnp.asarray(pay), [(0, 0)],
                                  dst_addr=dst_addr, stride=stride,
                                  blk_words=blk_words, nblocks=nblocks,
                                  token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    out = jax.jit(gas.spmd(prog))(gas.make_global_state())
    want = _strided_seq_ref(np.zeros(64), pay, dst_addr, stride, blk_words,
                            nblocks, hd.H_WRITE)
    np.testing.assert_allclose(np.asarray(out.segment)[0], want, rtol=1e-6)
    assert int(np.asarray(out.error)[0]) == 0
    # detection: aliasing or traced strides -> ordered; disjoint -> not
    assert ops._strides_may_overlap(1, 3, 4)
    assert ops._strides_may_overlap(-2, 3, 4)
    assert not ops._strides_may_overlap(9, 3, 4)
    assert not ops._strides_may_overlap(1, 3, 1)  # single block never aliases
    seen = []
    jax.jit(lambda s: seen.append(ops._strides_may_overlap(s, 3, 4)) or s)(
        jnp.asarray(9))
    assert seen == [True]  # traced stride: conservatively ordered


def test_mailbox_flush_single_credit_mixed_flags():
    """Credit audit (satellite 3): one flushed stack earns exactly ONE
    credit on the mailbox token, even when the stack mixes handler
    classes and per-message tokens, and a second flush earns a second.
    The per-message tokens never see ack credits."""
    import jax
    from repro.core.address_space import GlobalAddressSpace

    ctx = make_ctx(segment_words=64)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        mb = ctx.mailbox([(0, 0)], msg_words=2, watermark=100, token=6)
        st = mb.send(st, np.asarray([1.0, 2.0]), dst_addr=0, token=1)
        st = mb.send(st, np.asarray([3.0]), dst_addr=4, handler=hd.H_ADD,
                     token=2)
        st = mb.send_signal(st, arg=5, token=9)   # Short row, its own token
        st = mb.flush(st)
        st = mb.send(st, np.asarray([7.0]), dst_addr=8, token=3)
        st = mb.flush(st)
        assert mb.flushes == 2
        return st

    out = jax.jit(gas.spmd(prog))(gas.make_global_state())
    cred = np.asarray(out.credits)[0]
    assert cred[6] == 2, cred          # exactly one ack credit per flush
    assert cred[9] == 5, cred          # the user Short ran its handler
    assert cred[1] == 0 and cred[2] == 0 and cred[3] == 0, cred
    seg = np.asarray(out.segment)[0]
    np.testing.assert_allclose(seg[0:2], [1, 2])
    np.testing.assert_allclose(seg[4:5], [3])
    np.testing.assert_allclose(seg[8:9], [7])


def test_egress_fifo_pads():
    ctx = make_ctx()
    st_ = PgasState.make(64)
    hdr = am.decode(am.encode(type=am.make_type(am.MEDIUM, fifo=True),
                              nwords=2))
    buf = gc.egress(ctx, st_, hdr, jnp.asarray([5.0, 6.0]), 2)
    np.testing.assert_allclose(buf, [5, 6])
