"""Property tests for the AM wire format (paper Sec. III-A)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import am

field_vals = st.integers(min_value=0, max_value=2**20)


@settings(max_examples=50, deadline=None)
@given(
    msg_class=st.sampled_from([am.NOP, am.SHORT, am.MEDIUM, am.LONG]),
    src=field_vals, dst=field_vals, nwords=field_vals,
    dst_addr=field_vals, src_addr=field_vals,
    handler=st.integers(0, 31), token=st.integers(0, 15),
    asynchronous=st.booleans(), get=st.booleans(), fifo=st.booleans(),
    strided=st.booleans(), vectored=st.booleans(), reply=st.booleans(),
)
def test_encode_decode_roundtrip(msg_class, src, dst, nwords, dst_addr,
                                 src_addr, handler, token, asynchronous,
                                 get, fifo, strided, vectored, reply):
    t = am.make_type(msg_class, asynchronous=asynchronous, get=get,
                     fifo=fifo, strided=strided, vectored=vectored,
                     reply=reply)
    hdr = am.encode(type=t, src=src, dst=dst, nwords=nwords,
                    dst_addr=dst_addr, src_addr=src_addr, handler=handler,
                    token=token)
    h = am.decode(hdr)
    assert int(h.msg_class) == msg_class
    assert int(h.src) == src and int(h.dst) == dst
    assert int(h.nwords) == nwords
    assert int(h.dst_addr) == dst_addr and int(h.src_addr) == src_addr
    assert int(h.handler) == handler and int(h.token) == token
    assert bool(h.flag(am.FLAG_ASYNC)) == asynchronous
    assert bool(h.flag(am.FLAG_GET)) == get
    assert bool(h.flag(am.FLAG_FIFO)) == fifo
    assert bool(h.flag(am.FLAG_STRIDED)) == strided
    assert bool(h.flag(am.FLAG_VECTORED)) == vectored
    assert bool(h.flag(am.FLAG_REPLY)) == reply


def test_zero_header_is_nop():
    h = am.decode(jnp.zeros((am.HDR_WORDS,), jnp.int32))
    assert bool(am.is_nop(h))
    assert not bool(h.flag(am.FLAG_ASYNC))


def test_reply_for_targets_source():
    hdr = am.encode(type=am.make_type(am.LONG), src=3, dst=7, token=5)
    rep = am.decode(am.reply_for(am.decode(hdr)))
    assert int(rep.src) == 7 and int(rep.dst) == 3
    assert int(rep.token) == 5
    assert bool(rep.flag(am.FLAG_REPLY))
    assert bool(rep.flag(am.FLAG_ASYNC))  # replies must not trigger replies


def test_unknown_field_rejected():
    with pytest.raises(ValueError):
        am.encode(bogus=1)


def test_header_width():
    hdr = am.encode(type=am.make_type(am.SHORT))
    assert hdr.shape == (am.HDR_WORDS,)
    assert hdr.dtype == jnp.int32


# -- fused packets ------------------------------------------------------------

def test_fused_packet_roundtrip_bit_exact():
    """header ++ payload fuse into ONE int32 packet and split back
    bit-exactly — even for payload bit patterns that are NaNs/denormals
    as float32 (bitcast, not value conversion)."""
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2**32, size=37, dtype=np.uint32)
    pay = jnp.asarray(bits.view(np.float32))
    hdr = am.encode(type=am.make_type(am.LONG, fifo=True), src=1, dst=2,
                    nwords=37, dst_addr=11, token=3)
    pkt = am.pack_packet(hdr, pay)
    assert pkt.dtype == jnp.int32
    assert pkt.shape == (am.HDR_WORDS + 37,)
    h2, p2 = am.unpack_packet(pkt, pay.dtype)
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(hdr))
    assert np.asarray(p2).tobytes() == np.asarray(pay).tobytes()


def test_fused_packet_extra_section():
    """Vectored AMs carry their address list as an int32 extra section
    between header and payload: header ++ addrs ++ payload."""
    pay = jnp.asarray([1.5, -2.25, 3.0], jnp.float32)
    addrs = jnp.asarray([50, 60, 70], jnp.int32)
    hdr = am.encode(type=am.make_type(am.LONG, vectored=True), nwords=3,
                    nblocks=3)
    pkt = am.pack_packet(hdr, pay, extra=addrs)
    assert pkt.shape == (am.HDR_WORDS + 3 + 3,)
    h2, e2, p2 = am.unpack_packet(pkt, pay.dtype, n_extra=3)
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(hdr))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(addrs))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(pay))


def test_fused_packet_batched_rows():
    """A segmentation plan fuses row-wise: (nseg, HDR + W) int32."""
    nseg, W = 4, 8
    hdrs = am.encode_batch(nseg, type=am.make_type(am.LONG),
                           nwords=jnp.full((nseg,), W), seq=jnp.arange(nseg) * W)
    pay = jnp.arange(nseg * W, dtype=jnp.float32).reshape(nseg, W)
    pkt = am.pack_packet(hdrs, pay)
    assert pkt.shape == (nseg, am.HDR_WORDS + W)
    h2, p2 = am.unpack_packet(pkt, pay.dtype)
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(hdrs))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(pay))


def test_encode_batch_broadcast_and_rows():
    hdrs = am.encode_batch(3, type=am.make_type(am.MEDIUM), src=7,
                           nwords=jnp.asarray([16, 16, 2]))
    assert hdrs.shape == (3, am.HDR_WORDS)
    for r in range(3):
        h = am.decode(hdrs[r])
        assert int(h.src) == 7
    assert [int(am.decode(hdrs[r]).nwords) for r in range(3)] == [16, 16, 2]
    with pytest.raises(ValueError):
        am.encode_batch(2, bogus=1)


_RT_DTYPES = (np.float32, np.int32, np.uint32)


@settings(max_examples=40, deadline=None)
@given(dtype_i=st.integers(0, len(_RT_DTYPES) - 1),
       n_extra=st.integers(0, 4),
       nseg=st.integers(1, 4),
       width=st.integers(1, 9))
def test_pack_unpack_roundtrip_property(dtype_i, n_extra, nseg, width):
    """Property: pack_packet/unpack_packet round-trip BIT-exactly over
    dtype x extra-section length x segment count x payload width —
    including payload bit patterns that are NaN/denormal as f32 (the
    wire is a bitcast, never a value conversion).  nseg == 1 exercises
    the unbatched single-packet shape, nseg > 1 the (nseg, ...) stack."""
    dtype = _RT_DTYPES[dtype_i]
    rng = np.random.default_rng(
        1 + dtype_i * 1000 + n_extra * 100 + nseg * 10 + width)
    pay_np = rng.integers(0, 2**32, size=(nseg, width),
                          dtype=np.uint32).view(dtype)
    extra_np = rng.integers(0, 2**20, size=(nseg, n_extra), dtype=np.int32)
    t = am.make_type(am.LONG, fifo=True, vectored=n_extra > 0)
    hdr = am.encode_batch(nseg, type=t, nwords=jnp.full((nseg,), width),
                          nblocks=n_extra, seq=jnp.arange(nseg) * width)
    pay, extra = jnp.asarray(pay_np), jnp.asarray(extra_np)
    if nseg == 1:  # cover the unbatched packet shape too
        hdr, pay, extra = hdr[0], pay[0], extra[0]
    pkt = am.pack_packet(hdr, pay, extra if n_extra else None)
    assert pkt.dtype == jnp.int32
    assert pkt.shape[-1] == am.HDR_WORDS + n_extra + width
    out = am.unpack_packet(pkt, pay.dtype, n_extra)
    h2, e2, p2 = out if n_extra else (out[0], None, out[1])
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(hdr))
    assert np.asarray(p2).tobytes() == pay_np.tobytes()
    assert np.asarray(p2).dtype == pay_np.dtype
    if n_extra:
        np.testing.assert_array_equal(np.asarray(e2), extra_np.reshape(e2.shape))


def test_wire_dtype_guard():
    assert am.wire_dtype_ok(jnp.float32) and am.wire_dtype_ok(jnp.int32)
    assert not am.wire_dtype_ok(jnp.bfloat16)
    with pytest.raises(TypeError):
        am.to_wire(jnp.zeros((4,), jnp.bfloat16))
