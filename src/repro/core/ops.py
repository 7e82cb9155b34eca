"""The Shoal communication API (paper Sec. III-A).

Every function here is the SPMD-collectivized form of a Shoal AM call:
all kernels execute the same line; ``pattern`` is a static list of
``(src_kernel, dst_kernel)`` pairs naming who actually communicates this
call, and kernels outside the pattern contribute NOP headers (no action,
no reply).  This is the dataflow adaptation of one-sided messaging: a
put is ONE link traversal (plus an optional auto-reply), with no
rendezvous — contrast :mod:`repro.core.humboldt`, the two-sided baseline,
which costs four.

All ops must run inside ``shard_map`` over ``ctx.axes`` (use
``ctx.spmd``).  They thread :class:`PgasState` functionally.

Wire model: one collective per link traversal.  Header and payload are
fused into a single int32 packet (:func:`repro.core.am.pack_packet`) so
a whole AM crosses a link in ONE ``ppermute`` — the wire shape of the
paper's GAScore, which parses a single AXIS stream, never two.

Kernels on one device: every op is written once, for a lone kernel;
co-residence comes in only through :meth:`ShoalContext.kernel_map` and
the slot primitives' batching rules.  Pairs whose kernels share a
device take the LOCAL path, a move between slots with no collective
(:func:`repro.core.gascore.deliver_local`); the others a ``ppermute``.

Message-size segmentation: AMs whose payload exceeds the transport's
``max_packet_words`` are transparently split into sequence-numbered
packets.  The paper hits this limit (9000-byte jumbo frames) in the
Jacobi application and leaves segmentation as future work (footnote 2);
we implement it with a *batched plan*: all ``nseg`` packets are stacked
into one ``(nseg, HDR_WORDS + packet_words)`` buffer, shipped with a
single collective, and absorbed by a scanned GAScore ingress.  Replies
coalesce — every segment but the last is marked async — so an acked
>MTU message costs 2 link traversals total (1 batched packet + 1 reply)
and earns ONE credit per message, not one per packet.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis import trace as _lint
from repro.core import am
from repro.core import faults as flt
from repro.core import gascore as gc
from repro.core import handlers as hd
from repro.core.state import (ERR_CRC, ERR_RETRY_EXHAUSTED,
                              ERR_WAIT_UNDERFLOW, PgasState, ShoalContext,
                              from_slot, slots_total)
from repro.runtime.topology import split_local
from repro.runtime.transport import LinkClass
from repro.runtime.transport import is_lossy as _transport_is_lossy

Pattern = list[tuple[int, int]]


class VectoredAliasError(ValueError):
    """A vectored put's destination address list aliases itself.

    Two blocks of ONE packet land on overlapping (or duplicate) segment
    intervals, so the result depends on the receiver's scatter order —
    the intra-packet form of the R1 race.  Deliberately order-dependent
    packets must be wrapped in ``repro.analysis.waiver(reason)``, which
    downgrades this to a waived R4 lint finding.
    """


# --------------------------------------------------------------------------
# pattern plumbing
# --------------------------------------------------------------------------

def _reverse(pattern: Pattern) -> Pattern:
    return [(d, s) for (s, d) in pattern]


def _is_sender(ctx: ShoalContext, pattern: Pattern):
    me = ctx.my_id()
    srcs = jnp.asarray([s for s, _ in pattern] or [-1], jnp.int32)
    return jnp.any(me == srcs)


def _dst_of(ctx: ShoalContext, pattern: Pattern):
    """Per-kernel destination (or -1): a trace-time table lookup."""
    table = -jnp.ones((ctx.num_kernels,), jnp.int32)
    for s, d in pattern:
        table = table.at[s].set(d)
    return table[ctx.my_id()]


def _device_rounds(ctx: ShoalContext, remote: Pattern):
    """Group pairs across devices into rounds of one ``ppermute`` each:
    in a round every device sends to at most one device and receives
    from at most one.  Returns ``[(device perm, pairs), ...]``."""
    kpd = ctx.kernels_per_device
    rounds: list[tuple[dict, dict, Pattern]] = []
    for s, d in remote:
        a, b = s // kpd, d // kpd
        for to, frm, pairs in rounds:
            if to.get(a, b) == b and frm.get(b, a) == a:
                to[a], frm[b] = b, a
                pairs.append((s, d))
                break
        else:
            rounds.append(({a: b}, {b: a}, [(s, d)]))
    return [(sorted(to.items()), pairs) for to, _, pairs in rounds]


def _route(ctx: ShoalContext, pattern: Pattern, x: jnp.ndarray):
    """Move ``x`` along ``pattern``: what each destination receives from
    its source, zeros elsewhere.  Pairs on one device move between slots
    (``local``), and pairs across devices go by rounds of one
    ``ppermute`` each (``wire``).  In a round each packet is first moved
    into its destination's slot on its own device, so that the device's
    slot stack crosses the link at once; that staging is skipped where
    every kernel of a sending device already sends to its own slot, as
    with one kernel per device.
    """
    kpd, me = ctx.kernels_per_device, ctx.my_id()
    local, remote = split_local(pattern, kpd)
    out = None
    if local:
        with _lint.layer("local"):
            out = gc.deliver_local(ctx, local, x)
    for perm, pairs in _device_rounds(ctx, remote):
        stage = np.full((ctx.num_kernels,), -1, np.int32)
        dst = np.zeros((ctx.num_kernels,), bool)
        for s, d in pairs:
            stage[s - s % kpd + d % kpd] = s % kpd
            dst[d] = True
        staged = x
        if any(stage[a * kpd + j] != j for a, _ in perm for j in range(kpd)):
            with _lint.layer("local"):
                staged = from_slot(x, jnp.asarray(stage)[me])
        with _lint.layer("wire"):
            moved = lax.ppermute(staged, ctx.axes, perm)
        out = moved if out is None else jnp.where(jnp.asarray(dst)[me],
                                                  moved, out)
    return out


def _carried(ctx: ShoalContext, local: Pattern, remote: Pattern, *arrays):
    """Count an exchange's packets and bytes by link class for the
    recorder (:func:`repro.analysis.trace.carried`): every pair ships
    each kernel's packet rows."""
    arrays = [a for a in arrays if a is not None]
    rows = arrays[0].shape[0] if arrays[0].ndim == 2 else 1
    nbytes = sum(a.size * a.dtype.itemsize for a in arrays)
    for link, pairs in ((LinkClass.LOCAL, local), (LinkClass.ICI, remote)):
        if pairs:
            _lint.carried(link.name, rows * len(pairs), nbytes * len(pairs))


def _exchange(ctx: ShoalContext, pattern: Pattern, hdr: jnp.ndarray,
              payload: jnp.ndarray | None, extra: jnp.ndarray | None = None):
    """One link traversal: ship ``header ++ [extra ++] payload`` along
    ``pattern`` as ONE fused packet (a single ``ppermute``), batched or
    not.  Header-only messages are already single packets.

    Returns ``(hdr, payload)`` — plus ``extra`` in the middle when an
    extra section was given.  Patterns whose pairs all stay on one
    device issue no collective, mirroring libGalapagos' internal
    routing for same-node kernels: each section moves between the
    device's slots unpacked (:func:`repro.core.gascore.deliver_local`;
    with one kernel per device those pairs are self-puts).  A mixed
    pattern ships one fused packet over both paths (:func:`_route`).
    Non-32-bit payloads cannot bitcast onto the int32 wire and fall back
    to split sections.
    """
    local, remote = split_local(pattern, ctx.kernels_per_device)
    _carried(ctx, local, remote, hdr, extra, payload)
    if not remote:
        with _lint.layer("local"):
            hdr_r, extra_r, pay_r = (
                None if a is None else gc.deliver_local(ctx, local, a)
                for a in (hdr, extra, payload))
        return (hdr_r, extra_r, pay_r) if extra is not None else (hdr_r, pay_r)
    if payload is None and extra is None:
        return _route(ctx, pattern, hdr), None
    if payload is not None and not am.wire_dtype_ok(payload.dtype):
        hdr_r = _route(ctx, pattern, hdr)
        pay_r = _route(ctx, pattern, payload)
        if extra is None:
            return hdr_r, pay_r
        return hdr_r, _route(ctx, pattern, extra), pay_r
    n_extra = 0 if extra is None else extra.shape[-1]
    dtype = jnp.int32 if payload is None else payload.dtype
    with _lint.layer("egress"):
        pkt = am.pack_packet(hdr, payload, extra)
    pkt_r = _route(ctx, pattern, pkt)
    with _lint.layer("ingress"):
        out = am.unpack_packet(pkt_r, dtype, n_extra)
    if payload is None and extra is not None:
        return out[0], out[1], None
    return out


def _mask_nonparticipants(ctx: ShoalContext, pattern: Pattern, hdr: jnp.ndarray):
    return jnp.where(_is_sender(ctx, pattern), hdr, jnp.zeros_like(hdr))


def _deliver_reply(ctx: ShoalContext, state: PgasState, pattern: Pattern,
                   hdr_at_dst: am.Header, *, asynchronous: bool = False,
                   token=0, reply_via=None) -> PgasState:
    """Ship the auto-reply back along the reversed pattern and absorb it.

    For batched >MTU plans this is called once with the *final* segment's
    header — the only acked one — so a whole message costs one reply.

    Statically-async messages short-circuit here: previously an acked
    transport still shipped the (all-NOP, reply-suppressed) header back,
    wasting a collective XLA cannot DCE.  When ``reply_via`` (a reply
    mailbox, see :mod:`repro.actors`) is given, the reply is *deferred*
    instead of shipped: the mailbox records one owed credit for
    ``(pattern, token)`` and its flush returns all owed credits for a
    destination as ONE coalesced Short AM."""
    if not ctx.transport.acked or asynchronous:
        return state
    if reply_via is not None:
        reply_via.note(pattern, token)
        return state
    with _lint.layer("egress"):
        rep = gc.auto_reply(hdr_at_dst)
    rep_back, _ = _exchange(ctx, _reverse(pattern), rep, None)
    with _lint.layer("ingress"):
        return gc.ingress_reply(state, am.decode(rep_back))


def _segments(nwords: int, limit: int):
    """Static segmentation plan: [(offset, words), ...]."""
    if nwords <= limit:
        return [(0, nwords)]
    out, off = [], 0
    while off < nwords:
        w = min(limit, nwords - off)
        out.append((off, w))
        off += w
    return out


def _resolve_nwords(payload, from_segment_addr, nwords, op_name: str) -> int:
    """Validate the two calling conventions and return the message size."""
    if payload is not None:
        return int(payload.size)
    if from_segment_addr is None or nwords is None:
        raise ValueError(
            f"{op_name}: pass either `payload` (FIFO variant: data from "
            "the kernel) or `from_segment_addr` AND `nwords` "
            "(memory-sourced variant: data read from the local segment)")
    return int(nwords)


def _seg_types(msg_class: int, nseg: int, *, asynchronous: bool,
               defer_ack: bool = False, **flags):
    """Per-segment type words: every segment but the last is async, so
    an acked message triggers exactly one (coalesced) reply.  With
    ``defer_ack`` the final segment asks the receiver to ledger that one
    ack for a later packet's piggyback lane instead of replying."""
    t_last = am.make_type(msg_class, asynchronous=asynchronous,
                          defer_ack=defer_ack, **flags)
    t_tail = am.make_type(msg_class, asynchronous=True, **flags)
    if nseg == 1:
        return t_last
    return jnp.where(jnp.arange(nseg) == nseg - 1, t_last, t_tail)


def _check_ack_lanes(op: str, ctx: ShoalContext, *, asynchronous,
                     defer_ack, piggyback_token, reply_via) -> None:
    """Trace-time validation of the deferred-ack / piggyback kwargs."""
    if defer_ack:
        if asynchronous:
            raise ValueError(
                f"{op}: defer_ack defers the ack of an *acked* message; "
                "asynchronous=True has no ack to defer")
        if not ctx.transport.acked:
            raise ValueError(
                f"{op}: defer_ack needs an acked transport — this "
                "transport never replies, so there is no ack to defer")
        if reply_via is not None:
            raise ValueError(
                f"{op}: defer_ack (receiver-side ledger) and reply_via "
                "(sender-side reply mailbox) are two different deferred-"
                "ack mechanisms; pick one")
    if piggyback_token is not None:
        if _lint.static_int(piggyback_token) is None:
            raise ValueError(
                f"{op}: piggyback_token must be trace-time static (the "
                "header lane and the lint schedule are built at trace "
                "time)")
        if not 0 <= int(piggyback_token) < hd.NUM_TOKENS:
            raise ValueError(
                f"{op}: piggyback_token {int(piggyback_token)} outside "
                f"[0, {hd.NUM_TOKENS})")


# header column indices used when patching encoded rows in place
_I_TYPE = am.FIELDS.index("type")
_I_TOKEN = am.FIELDS.index("token")
_I_PB_TOKEN = am.FIELDS.index("pb_token")
_I_PB_COUNT = am.FIELDS.index("pb_count")
_I_EPOCH = am.FIELDS.index("epoch")


def _attach_piggyback(ctx: ShoalContext, state: PgasState, pattern: Pattern,
                      hdrs: jnp.ndarray, pb_token):
    """Load this sender's deferred-ack ledger for ``pb_token`` into the
    final row's piggyback lane and zero the ledger slot (senders only).

    Must run BEFORE :func:`_mask_nonparticipants`: non-senders' rows are
    zeroed afterwards anyway, and their ledger slot is left untouched.
    Returns ``(state, hdrs)``.
    """
    tok = int(pb_token)
    count = state.deferred_acks[tok]
    hdrs = hdrs.at[-1, _I_TYPE].set(hdrs[-1, _I_TYPE] | am.FLAG_PIGGYBACK)
    hdrs = hdrs.at[-1, _I_PB_TOKEN].set(tok)
    hdrs = hdrs.at[-1, _I_PB_COUNT].set(count)
    sender = _is_sender(ctx, pattern)
    ledger = state.deferred_acks.at[tok].set(
        jnp.where(sender, 0, state.deferred_acks[tok]))
    return gc.dataclasses_replace(state, deferred_acks=ledger), hdrs


# --------------------------------------------------------------------------
# lossy-transport plumbing: sealed + faulted exchanges, bounded retransmit
# --------------------------------------------------------------------------

def _require_lossless(op: str, ctx: ShoalContext) -> None:
    """Ops without a reliability protocol refuse lossy transports at
    trace time rather than silently pretending the link is perfect
    (the plain :func:`_exchange` path injects no faults)."""
    if _transport_is_lossy(ctx.transport):
        raise NotImplementedError(
            f"{op}: no retransmit/dedup protocol on a lossy transport — "
            "only put_long (and wait_replies) defend against loss; use a "
            "lossless transport or route this op over put_long")


def _lossy_recv_probs(ctx: ShoalContext, remote: Pattern):
    """Per-receiver (drop, dup, corrupt) scalars for one traversal of
    the pairs across devices: each receiver's incoming link is
    classified statically (LOCAL/ICI links stay lossless even inside a
    lossy collective).  Pairs on one device cross no link: lossless."""
    tbl = np.zeros((ctx.num_kernels, 3), np.float32)
    for s, d in remote:
        tbl[d] = ctx.transport.probs_for(s, d)
    row = jnp.asarray(tbl)[ctx.my_id()]
    return row[0], row[1], row[2]


def _lossy_exchange(ctx: ShoalContext, state: PgasState, pattern: Pattern,
                    pkt: jnp.ndarray, dtype, *, token, epoch, rnd: int,
                    direction: int):
    """One sealed link traversal over a lossy transport.

    ``pkt`` is the fused ``(nseg, HDR_WORDS + W)`` int32 stack (``W`` may
    be 0 for header-only acks).  The stack is CRC-sealed, shipped,
    faulted receiver-side (deterministically — see
    :mod:`repro.core.faults`), CRC-checked, and rows failing the check
    are NOPed with ``ERR_CRC`` latched (a corrupt packet degenerates to
    a drop the retransmit loop recovers from).  Returns
    ``(state, hdr_rows, pay_rows)`` where the stacks are ``(2 * nseg,
    ...)`` with duplicate deliveries materialised in the second half.
    """
    with _lint.layer("egress"):
        pkt = am.seal_packet(pkt)
    local, remote = split_local(pattern, ctx.kernels_per_device)
    _carried(ctx, local, remote, pkt)
    pkt_r = _route(ctx, pattern, pkt)
    # the fault emulator stands in for the link: no layer of its own
    drop, dup, corrupt = _lossy_recv_probs(ctx, remote)
    key = flt.fault_key(ctx.transport.faults, ctx.my_id(), token, epoch,
                        rnd, direction)
    delivered = flt.deliver(pkt_r, key, drop, dup, corrupt)
    with _lint.layer("ingress"):
        ok = am.packet_crc_ok(delivered)
        state = gc.dataclasses_replace(
            state, error=state.error | jnp.where(jnp.any(~ok), ERR_CRC, 0)
            .astype(jnp.int32))
        delivered = jnp.where(ok[:, None], delivered, 0)
        hdr_rows = delivered[:, :am.HDR_WORDS]
        pay_rows = am.from_wire(delivered[:, am.HDR_WORDS:], dtype)
    return state, hdr_rows, pay_rows


def _put_long_reliable(ctx: ShoalContext, state: PgasState, pattern: Pattern,
                       hdrs: jnp.ndarray, buf: jnp.ndarray, W: int,
                       nwords: int, token, *, acked: bool,
                       dedup: bool) -> PgasState:
    """Bounded-retransmit delivery of one sealed Long packet stack.

    Senders re-ship the (NOP-masked, so only still-pending senders pay
    wire words) stack until the receiver's ack survives the reverse
    link, up to ``max_retries`` extra rounds — the collectivized form of
    host-side retransmit with backoff: every round IS a full round-trip
    later, so waiting happens by construction, and the per-kernel
    ``retransmits`` counter records the rounds actually re-sent in (the
    dynamic cost; compiled collective counts are static).  Receivers run
    the dedup-gated ingress so redelivery is idempotent; a completed (or
    stale-redelivered final) row re-acks, covering the lost-ack case.
    On success the sender grants itself the message's ONE credit on
    ``token`` (the protocol consumed the wire ack); on exhaustion it
    latches ``ERR_RETRY_EXHAUSTED`` instead and the credit never
    appears — ``wait_replies(..., timeout=True)`` is the graceful way
    to observe that.
    """
    tok_c = jnp.clip(jnp.asarray(token, jnp.int32), 0, hd.NUM_TOKENS - 1)
    sender = _is_sender(ctx, pattern)
    epoch = state.send_epoch[tok_c] + 1
    state = gc.dataclasses_replace(
        state, send_epoch=state.send_epoch.at[tok_c].add(
            sender.astype(jnp.int32)))
    with _lint.layer("egress"):
        hdrs = hdrs.at[:, _I_EPOCH].set(
            jnp.where(hdrs[:, _I_TYPE] != 0, epoch, 0))
    attempts = 1 + (ctx.transport.max_retries if acked else 0)
    pending = sender
    # tx under loss counts FULL wire cost (headers + payload per data
    # round, header-only acks) so goodput = payload / tx_words is honest
    wire = am.wire_words(buf.dtype, nwords) + hdrs.shape[0] * am.HDR_WORDS
    for rnd in range(attempts):
        if rnd:
            state = gc.dataclasses_replace(
                state, retransmits=state.retransmits
                + pending.astype(jnp.int32))
        with _lint.layer("egress"):
            rows = jnp.where(pending, hdrs, 0)
            pay = jnp.where(pending, buf, jnp.zeros_like(buf))
            state = gc.dataclasses_replace(
                state, tx_words=state.tx_words + jnp.where(pending, wire, 0))
            pkt = am.pack_packet(rows, pay)
        state, hdr_r, pay_r = _lossy_exchange(
            ctx, state, pattern, pkt, buf.dtype,
            token=tok_c, epoch=epoch, rnd=rnd, direction=flt.DIR_DATA)
        with _lint.layer("ingress"):
            state, ack_hdr = gc.ingress_reliable_stack(ctx, state, hdr_r,
                                                       pay_r, W, dedup=dedup)
        if not acked:
            return state
        state = gc.dataclasses_replace(
            state, tx_words=state.tx_words + jnp.where(
                ack_hdr[_I_TYPE] != 0, am.HDR_WORDS, 0))
        state, rep_r, _ = _lossy_exchange(
            ctx, state, _reverse(pattern), ack_hdr[None, :], jnp.int32,
            token=tok_c, epoch=epoch, rnd=rnd, direction=flt.DIR_REPLY)
        with _lint.layer("ingress"):
            t_col = rep_r[:, _I_TYPE]
            got = jnp.any(((t_col & am._CLASS_MASK) == am.SHORT)
                          & ((t_col & am.FLAG_REPLY) != 0)
                          & (rep_r[:, _I_TOKEN] == tok_c))
        pending = pending & ~got
    delivered = sender & ~pending
    return gc.dataclasses_replace(
        state,
        credits=state.credits.at[tok_c].add(delivered.astype(jnp.int32)),
        error=state.error | jnp.where(pending, ERR_RETRY_EXHAUSTED, 0)
        .astype(jnp.int32))


# --------------------------------------------------------------------------
# Short AMs
# --------------------------------------------------------------------------

def put_short(ctx: ShoalContext, state: PgasState, pattern: Pattern, *,
              handler=hd.H_ADD, arg=1, token=0,
              asynchronous: bool = False, reply_via=None) -> PgasState:
    """Short AM: signal the destination (no payload).

    The handler runs on the destination's credit word ``token`` with
    ``arg``; the default (H_ADD, 1) is a counting semaphore.
    """
    _require_lossless("put_short", ctx)
    h_s, a_s, t_s = (_lint.static_int(handler), _lint.static_int(arg),
                     _lint.static_int(token))
    grants = ((t_s, a_s),) if (h_s == hd.H_ADD and a_s is not None
                               and t_s is not None) else ()
    tag = _lint.emit(
        "put_short", pattern, token=t_s,
        acked=ctx.transport.acked and not asynchronous,
        asynchronous=asynchronous, deferred_reply=reply_via is not None,
        credit_grants=grants, handler=h_s, segment_words=ctx.segment_words)
    with _lint.scope(tag):
        with _lint.layer("egress"):
            t = am.make_type(am.SHORT, asynchronous=asynchronous)
            hdr = am.encode(type=t, src=ctx.my_id(),
                            dst=_dst_of(ctx, pattern), handler=handler,
                            token=token, dst_addr=arg)
            hdr = _mask_nonparticipants(ctx, pattern, hdr)
        hdr_r, _ = _exchange(ctx, pattern, hdr, None)
        with _lint.layer("ingress"):
            h = am.decode(hdr_r)
            state = gc.ingress_short(ctx, state, h)
        return _deliver_reply(ctx, state, pattern, h,
                              asynchronous=asynchronous, token=token,
                              reply_via=reply_via)


# --------------------------------------------------------------------------
# Medium AMs (payload -> destination kernel)
# --------------------------------------------------------------------------

def put_medium(ctx: ShoalContext, state: PgasState, payload: jnp.ndarray | None,
               pattern: Pattern, *, handler=hd.H_NOP, token=0,
               asynchronous: bool = False, from_segment_addr=None,
               nwords: int | None = None, reply_via=None):
    """Medium AM: point-to-point payload straight to the destination
    kernel (returned value).  ``from_segment_addr`` selects the
    memory-sourced variant (payload read from the local segment by the
    GAScore at that address, ``nwords`` long, i.e. the non-FIFO case);
    default is the FIFO variant with ``payload`` from the kernel.

    Returns ``(state, delivered)``; ``delivered`` is zeros on kernels
    that receive nothing this call.  >MTU payloads ship as one batched
    packet stack: a single collective plus (if acked) a single
    coalesced reply.
    """
    _require_lossless("put_medium", ctx)
    nwords = _resolve_nwords(payload, from_segment_addr, nwords, "put_medium")
    fifo = from_segment_addr is None
    tag = _lint.emit(
        "put_medium", pattern, token=_lint.static_int(token),
        acked=ctx.transport.acked and not asynchronous,
        asynchronous=asynchronous, deferred_reply=reply_via is not None,
        handler=_lint.static_int(handler), segment_words=ctx.segment_words,
        detail={"nwords": nwords})
    with _lint.scope(tag):
        segs = _segments(nwords, ctx.transport.max_packet_words)
        nseg, W = len(segs), segs[0][1]
        offs = jnp.asarray([o for o, _ in segs], jnp.int32)
        ws = jnp.asarray([w for _, w in segs], jnp.int32)
        with _lint.layer("egress"):
            hdrs = am.encode_batch(
                nseg,
                type=_seg_types(am.MEDIUM, nseg, asynchronous=asynchronous,
                                fifo=fifo),
                src=ctx.my_id(), dst=_dst_of(ctx, pattern), nwords=ws,
                handler=handler, token=token,
                src_addr=0 if fifo else from_segment_addr + offs, seq=offs)
            hdrs = _mask_nonparticipants(ctx, pattern, hdrs)
            buf = gc.egress_batch(ctx, state, hdrs,
                                  payload if fifo else None, W)
            state = gc.dataclasses_replace(
                state, tx_words=state.tx_words +
                jnp.where(_is_sender(ctx, pattern),
                          am.wire_words(state.segment.dtype, nwords), 0))
        hdr_r, pay_r = _exchange(ctx, pattern, hdrs, buf)
        with _lint.layer("ingress"):
            state, delivered = gc.ingress_medium_batch(state, hdr_r, pay_r, W)
        state = _deliver_reply(ctx, state, pattern, am.decode(hdr_r[-1]),
                               asynchronous=asynchronous, token=token,
                               reply_via=reply_via)
        return state, delivered[:nwords]


# --------------------------------------------------------------------------
# Long AMs (payload -> destination shared memory)
# --------------------------------------------------------------------------

def put_long(ctx: ShoalContext, state: PgasState, payload: jnp.ndarray | None,
             pattern: Pattern, dst_addr, *, handler=hd.H_WRITE, token=0,
             asynchronous: bool = False, from_segment_addr=None,
             nwords: int | None = None, reply_via=None,
             defer_ack: bool = False, piggyback_token=None,
             dedup: bool = True) -> PgasState:
    """Long AM: one-sided put into the destination kernel's segment at
    ``dst_addr``, applied through ``handler`` (H_WRITE = plain put,
    H_ADD = remote accumulate, ...).  FIFO variant when ``payload`` is
    given; memory-sourced variant when ``from_segment_addr`` is.

    >MTU payloads ship as one ``(nseg, HDR+W)`` packet stack — a single
    collective — and are absorbed by a scanned GAScore ingress; an acked
    message earns ONE credit (the final segment carries the ack).

    ``defer_ack=True`` removes even the reply collective: the receiver
    ledgers the owed ack (``state.deferred_acks[token]``) and a later
    packet crossing the reverse link carries it home — either another
    put with ``piggyback_token=token`` or :func:`drain_deferred_acks`.
    ``piggyback_token=t`` loads THIS packet's piggyback lane with the
    sender's ledgered acks for ``t`` (acks this kernel owes for puts it
    *received* over the link this packet now travels in reverse).

    On a lossy transport (:class:`repro.runtime.transport.LossyTransport`
    with a non-zero fault model) the put runs the reliability protocol
    instead: packets are CRC-sealed and epoch-stamped, receivers dedup
    redelivery, and (if acked) senders retransmit up to ``max_retries``
    rounds before latching ``ERR_RETRY_EXHAUSTED`` — see
    :func:`_put_long_reliable`.  ``dedup=False`` disables the receiver
    ledger (shoal-lint rule R5 flags that combination).  The ack-lane
    machinery (defer_ack / piggyback / reply_via) presumes a lossless
    reply and is rejected on lossy transports.
    """
    nwords = _resolve_nwords(payload, from_segment_addr, nwords, "put_long")
    fifo = from_segment_addr is None
    _check_ack_lanes("put_long", ctx, asynchronous=asynchronous,
                     defer_ack=defer_ack, piggyback_token=piggyback_token,
                     reply_via=reply_via)
    lossy = _transport_is_lossy(ctx.transport)
    acked = ctx.transport.acked and not asynchronous
    if lossy and (defer_ack or piggyback_token is not None
                  or reply_via is not None):
        raise NotImplementedError(
            "put_long: deferred/piggybacked acks assume a lossless reply "
            "path and cannot ride a lossy transport (a dropped piggyback "
            "lane would strand the ledger); use plain acked puts")
    tag = _lint.emit(
        "put_long", pattern,
        writes=(_lint.Interval(_lint.static_int(dst_addr), nwords),),
        token=_lint.static_int(token),
        acked=acked,
        asynchronous=asynchronous, deferred_reply=reply_via is not None,
        defer_ack=defer_ack,
        piggyback_token=(None if piggyback_token is None
                         else int(piggyback_token)),
        handler=_lint.static_int(handler), segment_words=ctx.segment_words,
        lossy=lossy,
        retries=(ctx.transport.max_retries if lossy and acked else 0),
        dedup=dedup if lossy else True)
    with _lint.scope(tag):
        segs = _segments(nwords, ctx.transport.max_packet_words)
        nseg, W = len(segs), segs[0][1]
        if lossy and nseg > 31:
            raise NotImplementedError(
                f"put_long: {nseg} segments > 31 — the dedup ledger's "
                "arrival bitmask is one int32 per token; raise the MTU or "
                "split the message")
        offs = jnp.asarray([o for o, _ in segs], jnp.int32)
        ws = jnp.asarray([w for _, w in segs], jnp.int32)
        with _lint.layer("egress"):
            hdrs = am.encode_batch(
                nseg,
                type=_seg_types(am.LONG, nseg, asynchronous=asynchronous,
                                defer_ack=defer_ack, fifo=fifo),
                src=ctx.my_id(), dst=_dst_of(ctx, pattern), nwords=ws,
                dst_addr=dst_addr + offs,
                src_addr=0 if fifo else from_segment_addr + offs,
                handler=handler, token=token, seq=offs)
            if piggyback_token is not None:
                state, hdrs = _attach_piggyback(ctx, state, pattern, hdrs,
                                                piggyback_token)
            hdrs = _mask_nonparticipants(ctx, pattern, hdrs)
            buf = gc.egress_batch(ctx, state, hdrs,
                                  payload if fifo else None, W)
        if lossy:
            if not am.wire_dtype_ok(buf.dtype):
                raise NotImplementedError(
                    "put_long: the lossy-transport seal covers the fused "
                    "int32 packet; sub-32-bit payloads use the split "
                    "fallback and have no integrity protection yet")
            return _put_long_reliable(ctx, state, pattern, hdrs, buf, W,
                                      nwords, token, acked=acked,
                                      dedup=dedup)
        with _lint.layer("egress"):
            state = gc.dataclasses_replace(
                state, tx_words=state.tx_words +
                jnp.where(_is_sender(ctx, pattern),
                          am.wire_words(state.segment.dtype, nwords), 0))
        hdr_r, pay_r = _exchange(ctx, pattern, hdrs, buf)
        with _lint.layer("ingress"):
            state = gc.ingress_long_batch(ctx, state, hdr_r, pay_r, W)
            # the final row is the only non-async one: it carries the ack
            # lanes (defer ledger bump and/or piggybacked ack grant)
            state = gc.ingress_ack_lanes(state, am.decode(hdr_r[-1]))
        return _deliver_reply(ctx, state, pattern, am.decode(hdr_r[-1]),
                              asynchronous=asynchronous or defer_ack,
                              token=token, reply_via=reply_via)


def group_disjoint_patterns(patterns: list[Pattern]) -> list[list[int]]:
    """Greedily group patterns into valid union permutations.

    Two patterns may share one ``ppermute`` only when BOTH their source
    sets and their destination sets are disjoint — ``lax.ppermute``
    allows each kernel to send at most one buffer and receive at most
    one.  Disjoint rings (even->odd and odd->even) merge; Jacobi's
    up/down halo pair does not (every interior kernel sends on both
    links), which is exactly why its steady state needs reply
    piggybacking rather than more merging.  Returns index lists into
    ``patterns``, first-fit in input order.
    """
    groups: list[list[int]] = []
    gsrcs: list[set[int]] = []
    gdsts: list[set[int]] = []
    for i, pat in enumerate(patterns):
        srcs = {s for s, _ in pat}
        dsts = {d for _, d in pat}
        for g in range(len(groups)):
            if not (gsrcs[g] & srcs) and not (gdsts[g] & dsts):
                groups[g].append(i)
                gsrcs[g] |= srcs
                gdsts[g] |= dsts
                break
        else:
            groups.append([i])
            gsrcs.append(set(srcs))
            gdsts.append(set(dsts))
    return groups


def _counted_group_reply(ctx: ShoalContext, state: PgasState, union: Pattern,
                         hdr_r: jnp.ndarray, *, token=None,
                         classes: tuple[int, ...] | None = (am.LONG,)
                         ) -> PgasState:
    """ONE reply collective for a whole grouped packet stack.

    Each receiver folds over the rows it just absorbed, counts the acked
    ones (non-async, non-reply, non-deferred — exactly one per message,
    since tail segments are async), and ships the count back as a Short
    H_ADD over the reversed union.  The union permutation guarantees a
    kernel received rows from at most one sender, so the dynamic token
    read off the acked rows is single-valued per receiver; a static
    ``token`` overrides it (mailbox flushes ack on the mailbox token
    regardless of per-row tokens).  ``classes`` restricts which message
    classes count (``None`` = any non-NOP row).
    """
    rev = _reverse(union)
    with _lint.layer("ingress"):
        t_col = hdr_r[:, _I_TYPE]
        cls = t_col & am._CLASS_MASK
        if classes is None:
            is_cls = cls != am.NOP
        else:
            is_cls = jnp.zeros(t_col.shape, bool)
            for c in classes:
                is_cls = is_cls | (cls == c)
        needs = is_cls & ((t_col & (am.FLAG_ASYNC | am.FLAG_REPLY
                                    | am.FLAG_DEFER_ACK)) == 0)
        cnt = jnp.sum(needs.astype(jnp.int32))
        tok = (jnp.max(jnp.where(needs, hdr_r[:, _I_TOKEN], 0))
               if token is None else token)
    with _lint.layer("egress"):
        hdr = am.encode(type=am.make_type(am.SHORT, asynchronous=True),
                        src=ctx.my_id(), dst=_dst_of(ctx, rev),
                        handler=hd.H_ADD, token=tok, dst_addr=cnt)
        hdr = _mask_nonparticipants(ctx, rev, hdr)
    hdr_back, _ = _exchange(ctx, rev, hdr, None)
    with _lint.layer("ingress"):
        return gc.ingress_short(ctx, state, am.decode(hdr_back),
                                handler=hd.H_ADD)


def put_long_multi(ctx: ShoalContext, state: PgasState, items, *,
                   handler=hd.H_WRITE, token=0, tokens=None,
                   asynchronous: bool = False, defer_ack: bool = False,
                   piggyback_tokens=None, reply_via=None) -> PgasState:
    """Multi-destination Long put: batch several puts over different
    patterns into as few collectives as possible.

    ``items`` is ``[(payload, pattern, dst_addr), ...]`` (FIFO variant).
    Patterns whose source AND destination sets are disjoint form a valid
    union permutation: their per-destination ``(nseg, HDR+W)`` packet
    stacks concatenate and the whole group crosses the links as ONE
    ``ppermute``.  Patterns that share a source or destination (Jacobi's
    up+down halo pair) cannot legally merge and land in separate groups
    — see :func:`group_disjoint_patterns`.

    A group's stack lands in one pass
    (:func:`repro.core.gascore.ingress_long_stack`: one masked write per
    item at its static ``dst_addr``) when every item's destination is a
    trace-time constant inside the segment, the handler is a static
    built-in, and no items alias under a waiver.  Otherwise the scanned
    :func:`repro.core.gascore.ingress_stack` lands it row by row.

    Ack accounting: one credit per item, on that item's token
    (``tokens`` gives per-item tokens; default all ``token``).  On the
    immediate-ack path each group costs ONE extra reply collective
    total (:func:`_counted_group_reply`), not one per item.  With
    ``defer_ack=True`` no reply collective exists at all: receivers
    ledger the acks and ``piggyback_tokens[i]`` loads item *i*'s final
    packet with the sender's ledgered acks for that token (the steady-
    state loop shape: each direction's data packet carries the opposite
    direction's acks home).

    Destination intervals that overlap across items sharing a
    destination kernel raise :class:`VectoredAliasError` — the landed
    value would depend on stack order — unless the call is wrapped in
    ``repro.analysis.waiver(reason)``.
    """
    if not items:
        raise ValueError("put_long_multi: empty item list")
    _require_lossless("put_long_multi", ctx)
    k = len(items)
    toks = list(tokens) if tokens is not None else [token] * k
    if len(toks) != k:
        raise ValueError(
            f"put_long_multi: {k} items but {len(toks)} tokens")
    pbs = (list(piggyback_tokens) if piggyback_tokens is not None
           else [None] * k)
    if len(pbs) != k:
        raise ValueError(
            f"put_long_multi: {k} items but {len(pbs)} piggyback_tokens")
    for pb in pbs:
        _check_ack_lanes("put_long_multi", ctx, asynchronous=asynchronous,
                         defer_ack=defer_ack, piggyback_token=pb,
                         reply_via=reply_via)
    parsed = []
    for i, item in enumerate(items):
        try:
            payload, pattern, dst_addr = item
        except (TypeError, ValueError):
            raise ValueError(
                "put_long_multi: items are (payload, pattern, dst_addr) "
                f"triples; item {i} is {item!r}") from None
        if payload is None:
            raise ValueError(
                f"put_long_multi: item {i} has no payload (only the "
                "FIFO variant batches; use put_long for memory-sourced)")
        pat = [(int(s), int(d)) for s, d in pattern]
        parsed.append((payload, pat, dst_addr, int(payload.size)))
    ivs = [_lint.Interval(_lint.static_int(a), nw)
           for _, _, a, nw in parsed]
    alias = None
    for i in range(k):
        for j in range(i + 1, k):
            common = ({d for _, d in parsed[i][1]}
                      & {d for _, d in parsed[j][1]})
            if common and ivs[i].known and ivs[j].known \
                    and ivs[i].overlaps(ivs[j]):
                alias = (i, j, sorted(common))
                break
        if alias:
            break
    if alias is not None and _lint.current_waiver() is None:
        i, j, common = alias
        raise VectoredAliasError(
            f"put_long_multi: items {i} ({ivs[i]}) and {j} ({ivs[j]}) "
            f"overlap at destination kernel(s) {common} within one "
            "batched call, so the landed value depends on stack order "
            "(silent last-writer-wins). Give the items disjoint "
            "intervals, or wrap the call in "
            "repro.analysis.waiver(reason) if the overlap is deliberate.")
    groups = group_disjoint_patterns([p for _, p, _, _ in parsed])
    acked = ctx.transport.acked and not asynchronous
    mtu = ctx.transport.max_packet_words
    h_static = _lint.static_int(handler)
    # the stack lands in one pass only where its plan is static and the
    # scan's row order is not what the caller relies on
    one_pass = (alias is None and h_static is not None
                and 0 <= h_static < hd.NUM_BUILTIN)
    for gi, grp in enumerate(groups):
        # one packet width for the whole group so stacks concatenate;
        # re-planning every item at this width keeps egress's pad +
        # reshape exact (all rows but an item's last are full)
        W = min(mtu, max(parsed[i][3] for i in grp))
        group_tag = None
        hdr_rows, pay_rows, union, blocks, row0 = [], [], [], [], 0
        for i in grp:
            payload, pat, dst_addr, nw = parsed[i]
            tag = _lint.emit(
                "put_long_multi", pat, writes=(ivs[i],),
                token=_lint.static_int(toks[i]), acked=acked,
                asynchronous=asynchronous,
                deferred_reply=reply_via is not None,
                defer_ack=defer_ack,
                piggyback_token=None if pbs[i] is None else int(pbs[i]),
                handler=_lint.static_int(handler),
                segment_words=ctx.segment_words,
                self_overlap=alias is not None and i in alias[:2],
                detail={"group": gi, "item": i, "n_items": k})
            group_tag = group_tag or tag
            union.extend(pat)
            segs = _segments(nw, W)
            nseg = len(segs)
            blocks.append((row0, nseg, ivs[i].start, nw))
            row0 += nseg
            offs = jnp.asarray([o for o, _ in segs], jnp.int32)
            ws = jnp.asarray([w for _, w in segs], jnp.int32)
            with _lint.scope(tag), _lint.layer("egress"):
                hdrs = am.encode_batch(
                    nseg,
                    type=_seg_types(am.LONG, nseg,
                                    asynchronous=asynchronous,
                                    defer_ack=defer_ack, fifo=True),
                    src=ctx.my_id(), dst=_dst_of(ctx, pat), nwords=ws,
                    dst_addr=dst_addr + offs, handler=handler,
                    token=toks[i], seq=offs)
                if pbs[i] is not None:
                    state, hdrs = _attach_piggyback(ctx, state, pat,
                                                    hdrs, pbs[i])
                hdrs = _mask_nonparticipants(ctx, pat, hdrs)
                pay_rows.append(gc.egress_batch(ctx, state, hdrs,
                                                payload, W))
                hdr_rows.append(hdrs)
                state = gc.dataclasses_replace(
                    state, tx_words=state.tx_words +
                    jnp.where(_is_sender(ctx, pat),
                              am.wire_words(state.segment.dtype, nw), 0))
        union = sorted(set(union))
        with _lint.scope(group_tag):
            with _lint.layer("egress"):
                hdr_all = jnp.concatenate(hdr_rows, axis=0)
                pay_all = jnp.concatenate(pay_rows, axis=0)
            hdr_r, pay_r = _exchange(ctx, union, hdr_all, pay_all)
            with _lint.layer("ingress"):
                if one_pass and all(
                        a is not None and 0 <= a <= ctx.segment_words - nw
                        for _, _, a, nw in blocks):
                    state = gc.ingress_long_stack(ctx, state, hdr_r, pay_r,
                                                  blocks, h_static, W)
                else:
                    state = gc.ingress_stack(ctx, state, hdr_r, pay_r, W)
            if acked and not defer_ack:
                if reply_via is not None:
                    for i in grp:
                        reply_via.note(parsed[i][1], toks[i])
                else:
                    state = _counted_group_reply(ctx, state, union, hdr_r)
    return state


def drain_deferred_acks(ctx: ShoalContext, state: PgasState,
                        pattern: Pattern, token) -> PgasState:
    """Ship this kernel's residual deferred-ack ledger for ``token``
    home as one header-only Short H_ADD along ``pattern`` (1
    collective) and zero the ledger slot.

    Loop exit for the piggyback protocol: in steady state, iteration
    *k*'s acks ride iteration *k+1*'s reverse-link data packet, so when
    the loop ends the final iteration's acks are still ledgered at the
    receivers.  ``pattern`` must be the REVERSE link of the defer-acked
    puts: its senders are the kernels holding the ledger, its
    destinations the kernels whose ``wait_replies(token, ...)`` is
    still owed.  The count rides in the handler-arg word (dynamic), so
    one drain balances any number of outstanding puts.
    """
    _require_lossless("drain_deferred_acks", ctx)
    t_s = _lint.static_int(token)
    if t_s is None:
        raise ValueError("drain_deferred_acks: token must be trace-time "
                         "static (it names the ledger slot)")
    if not 0 <= t_s < hd.NUM_TOKENS:
        raise ValueError(
            f"drain_deferred_acks: token {t_s} outside [0, {hd.NUM_TOKENS})")
    tag = _lint.emit("drain_deferred_acks", pattern, token=t_s,
                     acked=False, asynchronous=True, drains_deferred=True,
                     handler=hd.H_ADD, segment_words=ctx.segment_words)
    with _lint.scope(tag), _lint.layer("sync"):
        count = state.deferred_acks[t_s]
        hdr = am.encode(type=am.make_type(am.SHORT, asynchronous=True),
                        src=ctx.my_id(), dst=_dst_of(ctx, pattern),
                        handler=hd.H_ADD, token=token, dst_addr=count)
        hdr = _mask_nonparticipants(ctx, pattern, hdr)
        sender = _is_sender(ctx, pattern)
        ledger = state.deferred_acks.at[t_s].set(
            jnp.where(sender, 0, state.deferred_acks[t_s]))
        state = gc.dataclasses_replace(state, deferred_acks=ledger)
        hdr_r, _ = _exchange(ctx, pattern, hdr, None)
        with _lint.layer("ingress"):
            return gc.ingress_short(ctx, state, am.decode(hdr_r),
                                    handler=hd.H_ADD)


def _strides_may_overlap(stride, blk_words: int, nblocks: int) -> bool:
    """Static overlap detection for strided puts: True when consecutive
    blocks can alias (``|stride| < blk_words``).  A traced stride is
    conservatively treated as overlapping — the caller can override with
    the ``overlap`` kwarg when it knows better."""
    if nblocks <= 1:
        return False
    try:
        return abs(int(stride)) < blk_words
    except Exception:  # traced stride: cannot prove blocks disjoint
        return True


def put_long_strided(ctx: ShoalContext, state: PgasState, payload: jnp.ndarray,
                     pattern: Pattern, dst_addr, stride, *,
                     blk_words: int, nblocks: int, handler=hd.H_WRITE,
                     token=0, asynchronous: bool = False,
                     overlap: bool | None = None, reply_via=None) -> PgasState:
    """Strided Long put: ``nblocks`` blocks of ``blk_words`` land at
    ``dst_addr + i*stride`` (THeGASNet's strided access, carried forward
    by the paper).  ``payload`` is the packed (nblocks*blk_words,)
    buffer — see :mod:`repro.kernels.am_pack` for the packing hot path.
    Block geometry is static; stride may be traced.

    >MTU messages segment at block granularity into one batched packet
    stack (single collective, one coalesced reply).

    Aliasing strides (``|stride| < blk_words``) are detected statically
    and ingress switches to the block-sequential scan that preserves
    last-writer-wins ordering; a traced stride is conservatively treated
    as aliasing.  ``overlap`` overrides the detection either way.
    """
    _require_lossless("put_long_strided", ctx)
    ordered = (_strides_may_overlap(stride, blk_words, nblocks)
               if overlap is None else bool(overlap))
    nwords = blk_words * nblocks
    base_s, stride_s = _lint.static_int(dst_addr), _lint.static_int(stride)
    if base_s is not None and stride_s is not None:
        w_ivs = tuple(_lint.Interval(base_s + i * stride_s, blk_words)
                      for i in range(nblocks))
    else:
        w_ivs = (_lint.Interval(None, nwords),)
    may_alias = _strides_may_overlap(stride, blk_words, nblocks)
    tag = _lint.emit(
        "put_long_strided", pattern, writes=w_ivs,
        token=_lint.static_int(token),
        acked=ctx.transport.acked and not asynchronous,
        asynchronous=asynchronous, deferred_reply=reply_via is not None,
        handler=_lint.static_int(handler), segment_words=ctx.segment_words,
        ordered_ingress=ordered, self_overlap=may_alias and not ordered,
        detail={"stride": stride_s, "blk_words": blk_words,
                "nblocks": nblocks})
    with _lint.scope(tag):
        # blocks per packet; >MTU plans segment at block granularity
        per = max(1, ctx.transport.max_packet_words // blk_words)
        nseg = -(-nblocks // per)
        nb = jnp.minimum(per,
                         nblocks - per * jnp.arange(nseg)).astype(jnp.int32)
        W = min(per, nblocks) * blk_words
        offs = jnp.arange(nseg, dtype=jnp.int32) * (per * blk_words)
        with _lint.layer("egress"):
            hdrs = am.encode_batch(
                nseg,
                type=_seg_types(am.LONG, nseg, asynchronous=asynchronous,
                                fifo=True, strided=True),
                src=ctx.my_id(), dst=_dst_of(ctx, pattern),
                nwords=nb * blk_words,
                dst_addr=dst_addr + jnp.arange(nseg) * per * stride,
                handler=handler, token=token, stride=stride,
                blk_words=blk_words, nblocks=nb, seq=offs)
            hdrs = _mask_nonparticipants(ctx, pattern, hdrs)
            buf = gc.egress_batch(ctx, state, hdrs, payload, W)
            state = gc.dataclasses_replace(
                state, tx_words=state.tx_words +
                jnp.where(_is_sender(ctx, pattern),
                          am.wire_words(state.segment.dtype, nwords), 0))
        hdr_r, pay_r = _exchange(ctx, pattern, hdrs, buf)
        with _lint.layer("ingress"):
            state = gc.ingress_strided_batch(ctx, state, hdr_r, pay_r,
                                             blk_words, min(per, nblocks),
                                             ordered)
        return _deliver_reply(ctx, state, pattern, am.decode(hdr_r[-1]),
                              asynchronous=asynchronous, token=token,
                              reply_via=reply_via)


def put_long_vectored(ctx: ShoalContext, state: PgasState,
                      blocks: list[jnp.ndarray], pattern: Pattern,
                      dst_addrs, *, handler=hd.H_WRITE, token=0,
                      asynchronous: bool = False, reply_via=None) -> PgasState:
    """Vectored Long put: ``blocks[i]`` lands at ``dst_addrs[i]``.  One
    AM on the wire: the destination address list rides inside the fused
    packet as an extra int32 section (``header ++ addrs ++ payload``),
    so the whole message is a single collective; the receiver scatters.
    Block sizes are static; addresses may be traced."""
    _require_lossless("put_long_vectored", ctx)
    try:
        n_addrs = len(dst_addrs)
    except TypeError:
        n_addrs = int(jnp.shape(jnp.asarray(dst_addrs))[0])
    if n_addrs != len(blocks):
        # jnp indexing clamps, so a short address list would silently
        # alias trailing blocks onto the last address
        raise ValueError(
            f"put_long_vectored: {len(blocks)} blocks but {n_addrs} "
            "dst_addrs — one destination address per block")
    nwords = sum(int(b.size) for b in blocks)
    if nwords + len(blocks) > ctx.transport.max_packet_words:
        raise ValueError(
            f"put_long_vectored: {nwords} payload words + {len(blocks)} "
            f"in-packet addresses exceed the transport MTU "
            f"({ctx.transport.max_packet_words} words); vectored puts do "
            "not segment — split the block list across messages")
    sizes = [int(b.size) for b in blocks]
    ivs = _lint.intervals_for_blocks(list(dst_addrs), sizes)
    alias = next(((i, j) for i in range(len(ivs))
                  for j in range(i + 1, len(ivs))
                  if ivs[i].known and ivs[j].known
                  and ivs[i].overlaps(ivs[j])), None)
    if alias is not None and _lint.current_waiver() is None:
        i, j = alias
        raise VectoredAliasError(
            f"put_long_vectored: destination blocks {i} ({ivs[i]}) and "
            f"{j} ({ivs[j]}) overlap inside one packet, so the landed "
            "value depends on the receiver's scatter order (duplicate "
            "addresses are the degenerate case). Give each block a "
            "disjoint interval, or wrap the call in "
            "repro.analysis.waiver(reason) if the overlap is deliberate.")
    tag = _lint.emit(
        "put_long_vectored", pattern, writes=ivs,
        token=_lint.static_int(token),
        acked=ctx.transport.acked and not asynchronous,
        asynchronous=asynchronous, deferred_reply=reply_via is not None,
        handler=_lint.static_int(handler), segment_words=ctx.segment_words,
        self_overlap=alias is not None,
        detail={} if alias is None else
        {"alias": f"blocks {alias[0]} and {alias[1]} overlap"})
    with _lint.scope(tag):
        with _lint.layer("egress"):
            payload = jnp.concatenate([b.reshape(-1) for b in blocks])
            t = am.make_type(am.LONG, asynchronous=asynchronous, fifo=True,
                             vectored=True)
            hdr = am.encode(type=t, src=ctx.my_id(),
                            dst=_dst_of(ctx, pattern), nwords=nwords,
                            handler=handler, token=token,
                            nblocks=len(blocks))
            hdr = _mask_nonparticipants(ctx, pattern, hdr)
            buf = gc.egress(ctx, state, am.decode(hdr), payload, nwords)
            state = gc.dataclasses_replace(
                state, tx_words=state.tx_words +
                jnp.where(_is_sender(ctx, pattern),
                          am.wire_words(state.segment.dtype, nwords), 0))
            addrs = jnp.asarray(dst_addrs, jnp.int32)
        hdr_r, addrs_r, pay_r = _exchange(ctx, pattern, hdr, buf, extra=addrs)
        with _lint.layer("ingress"):
            h = am.decode(hdr_r)
            off = 0
            for i, b in enumerate(blocks):
                w = int(b.size)
                sub_hdr = am.Header(
                    type=h.type, src=h.src, dst=h.dst,
                    nwords=jnp.asarray(w, jnp.int32),
                    dst_addr=addrs_r[i], src_addr=h.src_addr,
                    handler=h.handler, token=h.token, stride=h.stride,
                    blk_words=h.blk_words, nblocks=h.nblocks, seq=h.seq,
                    pb_token=h.pb_token, pb_count=h.pb_count,
                    epoch=h.epoch, crc=h.crc)
                state = gc.ingress_long(
                    ctx, state, sub_hdr,
                    lax.dynamic_slice(pay_r, (off,), (w,)), w)
                off += w
        return _deliver_reply(ctx, state, pattern, h,
                              asynchronous=asynchronous, token=token,
                              reply_via=reply_via)


# --------------------------------------------------------------------------
# Gets (one round trip: request header out, data back)
# --------------------------------------------------------------------------

def get_medium(ctx: ShoalContext, state: PgasState, pattern: Pattern,
               src_addr, nwords: int, *, token=0):
    """Medium get: fetch ``nwords`` at ``src_addr`` in the *destination*
    kernel's segment, delivered to the requesting kernel.  Returns
    ``(state, data)``.  The data return doubles as the reply (credits
    bump ONCE per message, on the final segment).  >MTU gets batch all
    request headers into one collective and the whole response into a
    second: 2 link traversals regardless of segment count."""
    _require_lossless("get_medium", ctx)
    tag = _lint.emit(
        "get_medium", pattern,
        reads=(_lint.Interval(_lint.static_int(src_addr), int(nwords)),),
        token=_lint.static_int(token), acked=True,
        segment_words=ctx.segment_words)
    with _lint.scope(tag):
        segs = _segments(nwords, ctx.transport.max_packet_words)
        nseg, W = len(segs), segs[0][1]
        offs = jnp.asarray([o for o, _ in segs], jnp.int32)
        ws = jnp.asarray([w for _, w in segs], jnp.int32)
        with _lint.layer("egress"):
            hdrs = am.encode_batch(
                nseg, type=am.make_type(am.MEDIUM, get=True),
                src=ctx.my_id(), dst=_dst_of(ctx, pattern), nwords=ws,
                src_addr=src_addr + offs, token=token, seq=offs)
            hdrs = _mask_nonparticipants(ctx, pattern, hdrs)
        hdr_r, _ = _exchange(ctx, pattern, hdrs, None)
        with _lint.layer("ingress"):
            state, resp_rows, data_rows = gc.serve_get_batch(ctx, state,
                                                             hdr_r, W)
        back_hdr, back_data = _exchange(ctx, _reverse(pattern), resp_rows,
                                        data_rows)
        with _lint.layer("ingress"):
            state = gc.ingress_reply(state, am.decode(back_hdr[-1]))
            state, data = gc.ingress_medium_batch(state, back_hdr,
                                                  back_data, W)
        return state, data[:nwords]


def get_long(ctx: ShoalContext, state: PgasState, pattern: Pattern,
             src_addr, nwords: int, dst_addr, *, handler=hd.H_WRITE,
             token=0) -> PgasState:
    """Long get: fetch remote segment words into the *local* segment at
    ``dst_addr`` (one-sided read).  Same batched 2-traversal wire plan
    as :func:`get_medium`; one credit per message."""
    _require_lossless("get_long", ctx)
    tag = _lint.emit(
        "get_long", pattern,
        reads=(_lint.Interval(_lint.static_int(src_addr), int(nwords)),),
        token=_lint.static_int(token), acked=True,
        handler=_lint.static_int(handler), segment_words=ctx.segment_words,
        detail={"local_dst_addr": _lint.static_int(dst_addr)})
    with _lint.scope(tag):
        segs = _segments(nwords, ctx.transport.max_packet_words)
        nseg, W = len(segs), segs[0][1]
        offs = jnp.asarray([o for o, _ in segs], jnp.int32)
        ws = jnp.asarray([w for _, w in segs], jnp.int32)
        with _lint.layer("egress"):
            hdrs = am.encode_batch(
                nseg, type=am.make_type(am.LONG, get=True),
                src=ctx.my_id(), dst=_dst_of(ctx, pattern), nwords=ws,
                src_addr=src_addr + offs, dst_addr=dst_addr + offs,
                token=token, handler=handler, seq=offs)
            hdrs = _mask_nonparticipants(ctx, pattern, hdrs)
        hdr_r, _ = _exchange(ctx, pattern, hdrs, None)
        with _lint.layer("ingress"):
            state, resp_rows, data_rows = gc.serve_get_batch(ctx, state,
                                                             hdr_r, W)
        back_hdr, back_data = _exchange(ctx, _reverse(pattern), resp_rows,
                                        data_rows)
        with _lint.layer("ingress"):
            state = gc.ingress_reply(state, am.decode(back_hdr[-1]))
            # land in local segment through the handler (class LONG on
            # the wire)
            is_rep = (back_hdr[:, 0] & am.FLAG_REPLY) != 0
            land_rows = back_hdr.at[:, 0].set(
                jnp.where(is_rep, am.LONG, am.NOP).astype(jnp.int32))
            return gc.ingress_long_batch(ctx, state, land_rows, back_data, W)


# --------------------------------------------------------------------------
# synchronization
# --------------------------------------------------------------------------

def barrier(ctx: ShoalContext, state: PgasState) -> PgasState:
    """Global barrier over all kernels (paper Sec. III: "barriers for
    synchronization").  A psum of a unit scalar is the dataflow barrier:
    no kernel's successor ops can be scheduled before every kernel's
    contribution arrives.  Kernels that share a device add up their
    arrivals inside it first, and only devices join the psum.  The
    barrier epoch counts completions."""
    tag = _lint.emit("barrier", [])
    with _lint.scope(tag), _lint.layer("sync"):
        one = jnp.ones((), jnp.int32)
        with _lint.layer("local"):
            one = slots_total(one)
        with _lint.layer("wire"):
            arrived = lax.psum(one, ctx.axes)
        epoch = state.barrier_epoch + (arrived // arrived)  # data-dependent
        return gc.dataclasses_replace(state, barrier_epoch=epoch)


def wait_replies(ctx: ShoalContext, state: PgasState, token, n, *,
                 timeout: bool = False) -> PgasState:
    """Wait for ``n`` replies on ``token`` then consume them.

    Replies coalesce across >MTU segmentation, so ``n`` counts
    *messages*, not packets.  In SPMD dataflow, arrival is guaranteed by
    data dependence, so this is bookkeeping: it drains ``n`` credits and
    raises a sticky error bit if fewer than ``n`` were present — the
    observable equivalent of a hang in the threaded original (tests
    assert on it).  On the host, :func:`repro.core.state.raise_on_error`
    converts the bit into a named :class:`~repro.core.state.
    WaitUnderflowError` carrying the offending token id(s).

    ``timeout=True`` is the lossy-transport path: a reliable put whose
    retransmits were exhausted never granted its credit, so a plain
    wait would latch ``ERR_WAIT_UNDERFLOW`` forever on top of the
    already-latched ``ERR_RETRY_EXHAUSTED``.  The timeout path instead
    drains ``min(have, n)`` — the waits that *did* complete — and latches
    nothing: the threaded original's bounded-timeout wait, where giving
    up is a normal outcome the caller inspects (via the error word)
    rather than a schedule bug.
    """
    tag = _lint.emit("wait_replies", [], token=_lint.static_int(token),
                     wait_n=_lint.static_int(n), timeout=timeout)
    with _lint.scope(tag), _lint.layer("sync"):
        token = jnp.clip(jnp.asarray(token, jnp.int32), 0, hd.NUM_TOKENS - 1)
        have = state.credits[token]
        if timeout:
            take = jnp.minimum(have, jnp.asarray(n, jnp.int32))
            take = jnp.maximum(take, 0)
            credits = hd.drain_credits(state.credits, token, take)
            return gc.dataclasses_replace(state, credits=credits)
        err = jnp.where(have < n, ERR_WAIT_UNDERFLOW, 0).astype(jnp.int32)
        credits = hd.drain_credits(state.credits, token, n)
        return gc.dataclasses_replace(state, credits=credits,
                                      error=state.error | err)
