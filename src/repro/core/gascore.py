"""The GAScore: per-kernel AM engine (paper Sec. III-C, Fig. 3).

The hardware GAScore is a DMA engine shared by all kernels on an FPGA:
``xpams_tx``/``am_tx`` build outgoing packets (reading memory-sourced
payloads through the AXI DataMover), ``am_rx``/``xpams_rx`` parse
incoming packets, write Long payloads to memory, hand Medium payloads to
kernels, run handlers, and emit the automatic reply.

Here each stage is a pure function over ``(header, payload, state)``.
The correspondence:

    am_tx / DataMover read   -> :func:`egress`   (dynamic_slice from segment)
    am_rx / DataMover write  -> :func:`ingress_long` (dynamic_update_slice)
    xpams_rx handler+reply   -> :func:`ingress_*` + :func:`auto_reply`
    hold_buffer              -> dataflow ordering (a reply is data-dependent
                                on the segment write, so it cannot overtake it)

One deliberate refinement over the paper: the paper's GAScore is a
monolith that must decode every message class on every packet, and its
*future work* section proposes a modular API where only the datapaths an
application uses are instantiated.  We implement that refinement: each
``ingress_*`` below compiles only its own datapath, and an op call site
only lowers the stages it needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis import trace as _lint
from repro.core import am
from repro.core import handlers as hd
from repro.core.state import PgasState, ShoalContext, from_slot
from repro.kernels.am_pack.ref import strided_indices

_I_NWORDS = am.FIELDS.index("nwords")
_I_SRC_ADDR = am.FIELDS.index("src_addr")


def _lane_mask(nwords, width: int, dtype=jnp.bool_):
    """mask[i] = i < nwords   (valid payload lanes in a fixed-size buffer)."""
    return (lax.iota(jnp.int32, width) < nwords).astype(dtype)


def _pad_segment(segment: jnp.ndarray, packet_words: int) -> jnp.ndarray:
    """Append a packet-width zero tail so a partial final segment of a
    batched >MTU plan (lanes masked beyond ``nwords``, buffer still
    ``packet_words`` wide) can read/land flush against the segment end
    without the address clip sliding the window."""
    return jnp.concatenate(
        [segment, jnp.zeros((packet_words,), segment.dtype)])


def deliver_local(ctx: ShoalContext, pairs, x: jnp.ndarray) -> jnp.ndarray:
    """In-device delivery between kernels on one device (libGalapagos'
    software routing between kernels on one node): each destination of
    ``pairs``, every one on its source's device, receives its source's
    ``x``; every other kernel receives zeros, as from a ``ppermute``.  A
    move between slots: no collective."""
    src = np.full((ctx.num_kernels,), -1, np.int32)
    for s, d in pairs:
        src[d] = s % ctx.kernels_per_device
    return from_slot(x, jnp.asarray(src)[ctx.my_id()])


def egress(ctx: ShoalContext, state: PgasState, hdr: am.Header,
           fifo_payload: jnp.ndarray | None, packet_words: int):
    """Build the outgoing payload buffer (am_tx + DataMover read path).

    FIFO-variant AMs (paper Sec. III-A) carry payload straight from the
    kernel; memory-variant AMs read ``nwords`` at ``src_addr`` from the
    local segment.  Returns a (packet_words,) buffer.
    """
    if fifo_payload is not None:
        pay = fifo_payload.astype(state.segment.dtype)
        if pay.shape != (packet_words,):
            pay = jnp.pad(pay.reshape(-1), (0, packet_words - pay.size))
    else:
        addr = jnp.clip(hdr.src_addr, 0, ctx.segment_words - packet_words)
        pay = lax.dynamic_slice(state.segment, (addr,), (packet_words,))
    mask = _lane_mask(hdr.nwords, packet_words, pay.dtype)
    return pay * mask


def egress_batch(ctx: ShoalContext, state: PgasState, hdr_rows: jnp.ndarray,
                 fifo_payload: jnp.ndarray | None, packet_words: int):
    """Batched :func:`egress`: one ``(nseg, packet_words)`` buffer for a
    whole segmentation plan (am_tx reading every segment of one >MTU AM
    in a single pass).

    FIFO AMs slice the flat kernel payload row-wise (every row but the
    last is full, so a pad + reshape is exact); memory-sourced AMs
    gather each row at its own ``src_addr``.  Per-row lanes beyond that
    row's ``nwords`` are zeroed.
    """
    nseg = hdr_rows.shape[0]
    if fifo_payload is not None:
        flat = fifo_payload.astype(state.segment.dtype).reshape(-1)
        flat = jnp.pad(flat, (0, nseg * packet_words - flat.size))
        rows = flat.reshape(nseg, packet_words)
    else:
        seg_p = _pad_segment(state.segment, packet_words)
        addrs = jnp.clip(hdr_rows[:, _I_SRC_ADDR], 0, ctx.segment_words)
        rows = jax.vmap(
            lambda a: lax.dynamic_slice(seg_p, (a,), (packet_words,))
        )(addrs)
    lanes = lax.broadcasted_iota(jnp.int32, (nseg, packet_words), 1)
    mask = (lanes < hdr_rows[:, _I_NWORDS][:, None]).astype(rows.dtype)
    return rows * mask


def ingress_long(ctx: ShoalContext, state: PgasState, hdr: am.Header,
                 payload: jnp.ndarray, packet_words: int) -> PgasState:
    """Long-put ingress: payload -> shared memory via handler (am_rx path).

    The handler (write/add/max/min/custom) is applied to the destination
    region, so a Long put with H_ADD is a one-sided remote accumulate.
    Non-participating kernels see a NOP header and leave their segment
    bit-identical.
    """
    st = _ingress_long_padded(
        ctx, dataclasses_replace(state,
                                 segment=_pad_segment(state.segment,
                                                      packet_words)),
        hdr, payload, packet_words)
    return dataclasses_replace(st, segment=st.segment[:ctx.segment_words])


def _ingress_long_padded(ctx: ShoalContext, state: PgasState, hdr: am.Header,
                         payload: jnp.ndarray, packet_words: int,
                         gate=None) -> PgasState:
    """:func:`ingress_long` body over a state whose segment already has
    the packet-width pad (see :func:`_pad_segment`) — so a batched scan
    pads once outside the loop, not once per segment.  ``gate`` further
    restricts application (the reliable path passes its dedup verdict:
    already-seen rows must not re-apply)."""
    active = hdr.msg_class == am.LONG
    if gate is not None:
        active = active & gate
    addr = jnp.clip(hdr.dst_addr, 0, ctx.segment_words)
    region = lax.dynamic_slice(state.segment, (addr,), (packet_words,))
    new_region = ctx.handlers.dispatch(hdr.handler, region, payload)
    lanes = _lane_mask(hdr.nwords, packet_words)
    new_region = jnp.where(lanes & active, new_region, region)
    segment = lax.dynamic_update_slice(state.segment, new_region, (addr,))
    return dataclasses_replace(
        state, segment=segment,
        rx_words=state.rx_words + jnp.where(active, hdr.nwords, 0))


def ingress_long_batch(ctx: ShoalContext, state: PgasState,
                       hdr_rows: jnp.ndarray, pay_rows: jnp.ndarray,
                       packet_words: int) -> PgasState:
    """Absorb a whole ``(nseg, ...)`` segment stack: a ``lax.scan`` of
    :func:`ingress_long` over the rows (one fused segment update per
    row; no collectives inside the loop, and the packet-width pad is
    applied once around the scan, not per row)."""
    if hdr_rows.shape[0] == 1:
        return ingress_long(ctx, state, am.decode(hdr_rows[0]), pay_rows[0],
                            packet_words)

    def body(st, row):
        h, p = row
        return _ingress_long_padded(ctx, st, am.decode(h), p,
                                    packet_words), ()

    state = dataclasses_replace(
        state, segment=_pad_segment(state.segment, packet_words))
    state, _ = lax.scan(body, state, (hdr_rows, pay_rows))
    return dataclasses_replace(state,
                               segment=state.segment[:ctx.segment_words])


def ingress_medium_batch(state: PgasState, hdr_rows: jnp.ndarray,
                         pay_rows: jnp.ndarray, packet_words: int):
    """Batched :func:`ingress_medium`; returns ``(state, delivered)``
    with ``delivered`` the flattened ``(nseg * packet_words,)`` lane
    stream (full rows first, so the first ``nwords`` lanes are the
    message payload)."""
    if hdr_rows.shape[0] == 1:
        st, part = ingress_medium(state, am.decode(hdr_rows[0]), pay_rows[0],
                                  packet_words)
        return st, part

    def body(st, row):
        h, p = row
        st, part = ingress_medium(st, am.decode(h), p, packet_words)
        return st, part

    state, parts = lax.scan(body, state, (hdr_rows, pay_rows))
    return state, parts.reshape(-1)


def ingress_strided(ctx: ShoalContext, state: PgasState, hdr: am.Header,
                    payload: jnp.ndarray, blk_words: int, nblocks: int,
                    ordered: bool = False) -> PgasState:
    """Strided Long-put ingress: scatter blocks of ``blk_words`` to
    ``dst_addr + i*stride`` (paper carries strided AMs forward from
    THeGASNet).

    Vectorized as one flat gather -> handler -> scatter over the whole
    packed payload (the same index map as the :mod:`repro.kernels.am_pack`
    DataMover kernels) instead of a per-block ``fori_loop``.  ``nblocks``
    / ``blk_words`` are the *static* packet capacity; the actual block
    count is ``hdr.nblocks`` (lanes beyond it are dropped), so one shape
    serves every row of a batched segmentation plan.

    Overlapping blocks (``stride < blk_words``) gather the destination
    region ONCE and scatter duplicate indices in undefined lane order, so
    last-writer-wins and read-modify-write handlers are both wrong for
    them; pass ``ordered=True`` (the op layer does so automatically when
    the static stride can overlap) to take the block-sequential
    :func:`ingress_strided_seq` path instead.
    """
    if ordered:
        return ingress_strided_seq(ctx, state, hdr, payload, blk_words,
                                   nblocks)
    active = hdr.msg_class == am.LONG
    flat = nblocks * blk_words
    idx = strided_indices(hdr.dst_addr, hdr.stride, blk_words, nblocks)
    blk_i = lax.iota(jnp.int32, flat) // blk_words
    valid = active & (blk_i < hdr.nblocks) \
        & _lane_mask(hdr.nwords, flat) & (idx >= 0) \
        & (idx < ctx.segment_words)
    idx_c = jnp.clip(idx, 0, ctx.segment_words - 1)
    region = state.segment[idx_c]
    new = ctx.handlers.dispatch(hdr.handler, region, payload)
    # invalid lanes scatter out of bounds and are dropped
    scatter_idx = jnp.where(valid, idx_c, ctx.segment_words)
    segment = state.segment.at[scatter_idx].set(
        jnp.where(valid, new, region), mode="drop")
    return dataclasses_replace(state, segment=segment,
                               rx_words=state.rx_words + jnp.where(active, hdr.nwords, 0))


def ingress_strided_seq(ctx: ShoalContext, state: PgasState, hdr: am.Header,
                        payload: jnp.ndarray, blk_words: int,
                        nblocks: int) -> PgasState:
    """Block-sequential :func:`ingress_strided`: a ``lax.scan`` over the
    blocks so each block's gather sees every earlier block's scatter.
    This restores the sequential last-writer-wins semantics (and correct
    read-modify-write accumulation for H_ADD/H_MAX/H_MIN) when blocks
    alias (``stride < blk_words``), at the cost of a length-``nblocks``
    dependency chain instead of one flat scatter."""
    active = hdr.msg_class == am.LONG

    def body(segment, i):
        lane = lax.iota(jnp.int32, blk_words)
        idx = hdr.dst_addr + i * hdr.stride + lane
        flat_lane = i * blk_words + lane
        valid = active & (i < hdr.nblocks) & (flat_lane < hdr.nwords) \
            & (idx >= 0) & (idx < ctx.segment_words)
        idx_c = jnp.clip(idx, 0, ctx.segment_words - 1)
        region = segment[idx_c]
        blk_pay = lax.dynamic_slice(payload, (i * blk_words,), (blk_words,))
        new = ctx.handlers.dispatch(hdr.handler, region, blk_pay)
        # invalid lanes scatter out of bounds and are dropped; indices
        # within one block never alias, so .set is well-defined here
        scatter_idx = jnp.where(valid, idx_c, ctx.segment_words)
        segment = segment.at[scatter_idx].set(
            jnp.where(valid, new, region), mode="drop")
        return segment, ()

    segment, _ = lax.scan(body, state.segment,
                          jnp.arange(nblocks, dtype=jnp.int32))
    return dataclasses_replace(
        state, segment=segment,
        rx_words=state.rx_words + jnp.where(active, hdr.nwords, 0))


def ingress_strided_batch(ctx: ShoalContext, state: PgasState,
                          hdr_rows: jnp.ndarray, pay_rows: jnp.ndarray,
                          blk_words: int, nblocks: int,
                          ordered: bool = False) -> PgasState:
    """Scan of :func:`ingress_strided` over a batched segment stack
    (``nblocks`` = static per-row block capacity).  ``ordered`` selects
    the block-sequential variant for aliasing strides."""
    if hdr_rows.shape[0] == 1:
        return ingress_strided(ctx, state, am.decode(hdr_rows[0]), pay_rows[0],
                               blk_words, nblocks, ordered)

    def body(st, row):
        h, p = row
        return ingress_strided(ctx, st, am.decode(h), p, blk_words, nblocks,
                               ordered), ()

    state, _ = lax.scan(body, state, (hdr_rows, pay_rows))
    return state


def ingress_medium(state: PgasState, hdr: am.Header, payload: jnp.ndarray,
                   packet_words: int):
    """Medium-put ingress: deliver payload to the kernel (xpams_rx "To
    Kernels" path).  Returns (state, delivered) where ``delivered`` is
    zero-masked on non-participating kernels."""
    active = hdr.msg_class == am.MEDIUM
    lanes = _lane_mask(hdr.nwords, packet_words, payload.dtype)
    delivered = payload * lanes * active.astype(payload.dtype)
    state = dataclasses_replace(
        state, rx_words=state.rx_words + jnp.where(active, hdr.nwords, 0))
    return state, delivered


def ingress_short(ctx: ShoalContext, state: PgasState, hdr: am.Header,
                  handler: int | None = None) -> PgasState:
    """Short ingress: signaling.  The handler runs on a one-word region of
    the credit file at ``token`` with ``dst_addr`` as its argument, so
    H_ADD implements counting semaphores (the paper's primary Short use).
    Reply messages (FLAG_REPLY) bump the credit counter directly: reply
    management is absorbed into the runtime (paper Sec. III-A).

    ``handler`` is the sender's built-in handler where it is static (the
    runtime's own H_ADD counts): it then runs over the whole credit file
    and the token's word is selected, so neither a switch nor a
    gather/scatter at the traced token is compiled (under the slot
    ``vmap`` those become loops)."""
    is_short = hdr.msg_class == am.SHORT
    is_reply = is_short & hdr.flag(am.FLAG_REPLY)
    is_user = is_short & ~hdr.flag(am.FLAG_REPLY)

    token = jnp.clip(hdr.token, 0, hd.NUM_TOKENS - 1)
    arg = hdr.dst_addr.astype(state.credits.dtype)
    if handler is not None:
        assert 0 <= handler < hd.NUM_BUILTIN, handler
        hit = lax.iota(jnp.int32, hd.NUM_TOKENS) == token
        credits = state.credits + (hit & is_reply).astype(jnp.int32)
        new = ctx.handlers.fn(handler)(credits, arg)
        return dataclasses_replace(
            state, credits=jnp.where(hit & is_user, new, credits))
    # replies: credits[token] += 1
    credits = state.credits.at[token].add(is_reply.astype(jnp.int32))
    # user shorts: handler over credits[token] with arg payload [dst_addr]
    region = lax.dynamic_slice(credits, (token,), (1,))
    new_region = ctx.handlers.dispatch(hdr.handler, region, arg.reshape(1))
    new_region = jnp.where(is_user, new_region, region)
    credits = lax.dynamic_update_slice(credits, new_region, (token,))
    return dataclasses_replace(state, credits=credits)


def ingress_stack(ctx: ShoalContext, state: PgasState, hdr_rows: jnp.ndarray,
                  pay_rows: jnp.ndarray, packet_words: int) -> PgasState:
    """Mixed-class scanned ingress for a coalesced packet stack.

    Unlike :func:`ingress_long_batch`, whose rows are segments of ONE
    message, each row here is an independent tiny AM with its own class,
    handler, and token: Long rows land in the segment through their
    handler, Short rows run on the credit file (signals / coalesced
    credit returns / replies), NOP rows do nothing.  Both datapaths are
    class-gated per row, so one ``lax.scan`` absorbs a stack that mixes
    them freely — the dataflow analogue of the GAScore draining a burst
    of aggregated messages off one AXIS stream.

    Its callers are the actor-mailbox flush (:mod:`repro.actors`), whose
    stacks mix classes, and the ``put_long_multi`` stacks that
    :func:`ingress_long_stack` cannot land exactly: a traced or
    out-of-segment destination, a registered handler, or items that
    alias under a waiver.
    """
    _lint.landed("scan", hdr_rows.shape[0])

    def body(st, row):
        h, p = row
        hd_ = am.decode(h)
        st = _ingress_long_padded(ctx, st, hd_, p, packet_words)
        st = ingress_short(ctx, st, hd_)
        st = ingress_ack_lanes(st, hd_)
        return st, ()

    state = dataclasses_replace(
        state, segment=_pad_segment(state.segment, packet_words))
    state, _ = lax.scan(body, state, (hdr_rows, pay_rows))
    return dataclasses_replace(state,
                               segment=state.segment[:ctx.segment_words])


def ingress_long_stack(ctx: ShoalContext, state: PgasState,
                       hdr_rows: jnp.ndarray, pay_rows: jnp.ndarray,
                       blocks, handler: int,
                       packet_words: int) -> PgasState:
    """Land a Long-only packet stack in one pass, with no scan: the
    ``put_long_multi`` ingress when the sender's plan is static.

    ``blocks`` is that plan, one ``(row0, nseg, dst_addr, nwords)`` per
    item: the item's rows ``[row0, row0 + nseg)`` of the stack and its
    trace-time destination ``[dst_addr, dst_addr + nwords)`` inside the
    segment; ``handler`` is the items' built-in handler.  The rows' headers
    still decide what lands: a row lands only if it is LONG (a
    non-sender's rows are NOPs), and only its first ``nwords`` lanes.
    Rows are full but an item's last (:func:`egress_batch` builds them
    so), so an item's lanes flatten into its region with static slices;
    its handler runs once over the region, which is written with one
    masked update at the static offset.  Built-in handlers are
    elementwise, so this equals :func:`ingress_stack`'s row-by-row
    landing bit for bit.

    The ack lanes of :func:`ingress_ack_lanes` become one sum each over
    the rows, per token (integer additions commute, so the row order is
    immaterial; a sum of one-hot rows, where a scatter-add under the
    slot ``vmap`` would compile to a loop), and ``rx_words`` one sum.
    The stack holds no SHORT row, so the Short datapath is not built.
    """
    _lint.landed("one_pass", hdr_rows.shape[0])
    hdr = am.Header(*(hdr_rows[:, i] for i in range(am.HDR_WORDS)))
    live = hdr.msg_class == am.LONG
    segment = state.segment
    for row0, nseg, dst_addr, nwords in blocks:
        rows = slice(row0, row0 + nseg)
        lanes = lax.broadcasted_iota(jnp.int32, (nseg, packet_words), 1)
        mask = (lanes < hdr.nwords[rows, None]) & live[rows, None]
        mask = mask.reshape(-1)[:nwords]
        flat = pay_rows[rows].reshape(-1)[:nwords]
        region = segment[dst_addr:dst_addr + nwords]
        new = ctx.handlers.fn(handler)(region, flat)
        segment = lax.dynamic_update_slice(
            segment, jnp.where(mask, new, region), (dst_addr,))
    tok, defer, pb_tok, pb = _ack_lanes(hdr)
    return dataclasses_replace(
        state, segment=segment,
        rx_words=state.rx_words + jnp.sum(jnp.where(live, hdr.nwords, 0)),
        deferred_acks=state.deferred_acks + _per_token(tok, defer),
        credits=state.credits + _per_token(pb_tok, pb))


def _per_token(tok: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """``out[t]`` = the sum of ``counts`` over the rows whose token is
    ``t``: a scatter-add over the credit file, as a sum."""
    hit = tok[:, None] == lax.iota(jnp.int32, hd.NUM_TOKENS)[None, :]
    return jnp.sum(jnp.where(hit, counts[:, None], 0), axis=0,
                   dtype=jnp.int32)


def _serve_get_row(ctx: ShoalContext, seg_p: jnp.ndarray, hdr: am.Header,
                   packet_words: int):
    """Stateless get service for one packet over a segment that already
    has the packet-width pad (see :func:`_pad_segment`): returns
    ``(resp_hdr, data, tx_words)``."""
    is_get = hdr.flag(am.FLAG_GET)
    addr = jnp.clip(hdr.src_addr, 0, ctx.segment_words)
    data = lax.dynamic_slice(seg_p, (addr,), (packet_words,))
    data = data * _lane_mask(hdr.nwords, packet_words, data.dtype)
    data = data * is_get.astype(data.dtype)
    # Response header is NOP unless this really was a get request, so
    # non-participating kernels ship nothing back.
    resp_type = jnp.where(
        is_get,
        hdr.msg_class | am.FLAG_REPLY | am.FLAG_ASYNC,
        jnp.zeros((), jnp.int32),
    ).astype(jnp.int32)
    resp_hdr = am.encode(
        type=0, src=hdr.dst, dst=hdr.src, nwords=hdr.nwords,
        dst_addr=hdr.dst_addr, token=hdr.token,
        handler=hdr.handler, seq=hdr.seq,
    ).at[0].set(resp_type)
    resp_hdr = jnp.where(is_get, resp_hdr, jnp.zeros_like(resp_hdr))
    return resp_hdr, data, jnp.where(is_get, hdr.nwords, 0)


def serve_get(ctx: ShoalContext, state: PgasState, hdr: am.Header,
              packet_words: int):
    """Get-request service: read ``nwords`` at ``src_addr`` from the local
    segment and return (data_header, data_payload) to ship back.  The
    response is marked as a reply so the requester's credit bumps on
    receipt — for gets, the data return *is* the reply."""
    resp_hdr, data, tx = _serve_get_row(
        ctx, _pad_segment(state.segment, packet_words), hdr, packet_words)
    state = dataclasses_replace(state, tx_words=state.tx_words + tx)
    return state, resp_hdr, data


def serve_get_batch(ctx: ShoalContext, state: PgasState,
                    hdr_rows: jnp.ndarray, packet_words: int):
    """Vectorized get service over a ``(nseg, HDR_WORDS)`` request stack:
    every segment of a >MTU get is read in one pass and the whole
    response ships back as one fused packet stack."""
    seg_p = _pad_segment(state.segment, packet_words)
    resp_rows, data_rows, tx = jax.vmap(
        lambda h: _serve_get_row(ctx, seg_p, am.decode(h), packet_words)
    )(hdr_rows)
    state = dataclasses_replace(state, tx_words=state.tx_words + tx.sum())
    return state, resp_rows, data_rows


def auto_reply(hdr: am.Header) -> jnp.ndarray:
    """Build the automatic reply header for an acked AM; NOP (all-zero)
    when the message was asynchronous, a NOP, itself a reply, or
    defer-acked (the owed ack rides a later packet's piggyback lane)."""
    rep = am.reply_for(hdr)
    suppress = (hdr.msg_class == am.NOP) | hdr.flag(am.FLAG_ASYNC) \
        | hdr.flag(am.FLAG_REPLY) | hdr.flag(am.FLAG_DEFER_ACK)
    return jnp.where(suppress, jnp.zeros_like(rep), rep)


def ingress_ack_lanes(state: PgasState, hdr: am.Header) -> PgasState:
    """Process the deferred-ack / piggyback lanes of one ingressed packet.

    Two independent gates (a packet can carry both):

    * FLAG_DEFER_ACK on an acked (non-async) message: instead of a reply
      collective, ledger the owed ack — ``deferred_acks[token] += 1``.
      The ledger is keyed by the put's token, which the steady-state
      protocol uses as a link id: each link direction gets its own token
      so the acks ride home over the right reverse link.
    * FLAG_PIGGYBACK: this packet carries ``pb_count`` acks owed on
      ``pb_token`` from the sender's ledger — grant them:
      ``credits[pb_token] += pb_count``.
    """
    tok, defer, pb_tok, pb = _ack_lanes(hdr)
    return dataclasses_replace(
        state, deferred_acks=state.deferred_acks.at[tok].add(defer),
        credits=state.credits.at[pb_tok].add(pb))


def _ack_lanes(hdr: am.Header):
    """``(token, deferred, pb_token, granted)``: the ledger slot and the
    acks a packet ledgers there, and the credit slot and the acks it
    grants there (see :func:`ingress_ack_lanes`)."""
    live = hdr.msg_class != am.NOP
    defer = live & hdr.flag(am.FLAG_DEFER_ACK) \
        & ~hdr.flag(am.FLAG_ASYNC) & ~hdr.flag(am.FLAG_REPLY)
    carry = live & hdr.flag(am.FLAG_PIGGYBACK)
    return (jnp.clip(hdr.token, 0, hd.NUM_TOKENS - 1),
            defer.astype(jnp.int32),
            jnp.clip(hdr.pb_token, 0, hd.NUM_TOKENS - 1),
            jnp.where(carry, hdr.pb_count, 0).astype(jnp.int32))


def ingress_reply(state: PgasState, hdr: am.Header) -> PgasState:
    """Reply ingress at the original sender: bump credits[token]."""
    is_reply = hdr.flag(am.FLAG_REPLY)
    token = jnp.clip(hdr.token, 0, hd.NUM_TOKENS - 1)
    credits = state.credits.at[token].add(is_reply.astype(jnp.int32))
    return dataclasses_replace(state, credits=credits)


def ingress_reliable_stack(ctx: ShoalContext, state: PgasState,
                           hdr_rows: jnp.ndarray, pay_rows: jnp.ndarray,
                           packet_words: int, *, dedup: bool = True):
    """Dedup-gated Long-stack ingress for the lossy-transport path.

    Rows arrive out of a faulted exchange (drops already NOPed,
    CRC-failed rows already NOPed, duplicates materialised as extra
    rows — see :func:`repro.core.faults.deliver`), possibly REDELIVERED
    by a sender retransmitting after a lost ack.  The redelivery ledger
    makes application idempotent, keyed on (token, epoch, seq):

    * a row whose epoch is <= the last *completed* epoch on its token is
      stale — not applied, but a stale FINAL row still re-acks (the
      data landed earlier; it is the ack that keeps dying);
    * an in-flight row applies only if its segment bit is not yet in
      ``dedup_seen[token]``, then sets the bit;
    * when the final (non-async) row finds the arrival mask complete
      (bits 0..seg_final all set), the message completes:
      ``dedup_epoch[token]`` latches the epoch and the mask DRAINS TO
      ZERO — a quiescent receiver holds no ledger residue.

    One message per token may be in flight at a time (epochs on a token
    are totally ordered by the sender's ``send_epoch`` counter); the
    reliable put in :mod:`repro.core.ops` serialises this.  Segment
    stacks are limited to 31 rows so the arrival mask fits an int32.

    ``dedup=False`` keeps the CRC/drop handling but applies every
    delivered row unconditionally and acks every final row — the unsafe
    mode shoal-lint rule R5 exists to flag (a retransmitted H_ADD
    double-accumulates, a duplicated final row double-acks).

    Returns ``(state, ack_hdr)`` where ``ack_hdr`` is the reply header
    owed this round (NOP when no final row completed or re-acked).
    """
    def body(carry, row):
        st, ack = carry
        h_raw, p = row
        h = am.decode(h_raw)
        active = h.msg_class == am.LONG
        tok = jnp.clip(h.token, 0, hd.NUM_TOKENS - 1)
        seg_i = jnp.clip(h.seq // packet_words, 0, 30)
        bit = jnp.left_shift(jnp.int32(1), seg_i)
        is_final = active & ~h.flag(am.FLAG_ASYNC) & ~h.flag(am.FLAG_REPLY)

        if dedup:
            done = st.dedup_epoch[tok]
            stale = active & (h.epoch <= done)
            tracked = st.dedup_inflight[tok] == h.epoch
            seen = jnp.where(tracked, st.dedup_seen[tok], 0)
            fresh = active & ~stale & ((seen & bit) == 0)
            seen2 = jnp.where(active & ~stale, seen | bit, seen)
            # complete <=> final row present and bits 0..seg_i all set
            # (segments are contiguous, the final row has the top seq)
            complete = is_final & ~stale \
                & (seen2 == jnp.left_shift(bit, 1) - 1)
            track = active & ~stale
            st = dataclasses_replace(
                st,
                dedup_epoch=st.dedup_epoch.at[tok].set(
                    jnp.where(complete, h.epoch, done)),
                dedup_inflight=st.dedup_inflight.at[tok].set(
                    jnp.where(track, h.epoch, st.dedup_inflight[tok])),
                dedup_seen=st.dedup_seen.at[tok].set(
                    jnp.where(complete, 0,
                              jnp.where(track, seen2, st.dedup_seen[tok]))))
            ack_now = complete | (stale & is_final)
        else:
            fresh = active
            ack_now = is_final

        st = _ingress_long_padded(ctx, st, h, p, packet_words, gate=fresh)
        ack = jnp.where(ack_now, am.reply_for(h), ack)
        return (st, ack), ()

    state = dataclasses_replace(
        state, segment=_pad_segment(state.segment, packet_words))
    ack0 = hd.vary_like(jnp.zeros((am.HDR_WORDS,), jnp.int32),
                        state, hdr_rows, pay_rows)
    (state, ack_hdr), _ = lax.scan(body, (state, ack0), (hdr_rows, pay_rows))
    return dataclasses_replace(
        state, segment=state.segment[:ctx.segment_words]), ack_hdr


def dataclasses_replace(state: PgasState, **kw) -> PgasState:
    """dataclasses.replace for the registered-dataclass pytree."""
    import dataclasses as _dc

    return _dc.replace(state, **kw)
