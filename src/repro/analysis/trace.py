"""Trace-time comm-event recording (pass 1 of shoal-lint).

Every Shoal op call site (:mod:`repro.core.ops`, the actor mailboxes in
:mod:`repro.actors`) reports one :class:`CommEvent` here while a
recorder is active, carrying the *static* operands the analyzer needs:
per-destination address intervals, tokens, ack semantics, segmentation.
Because Shoal programs are SPMD dataflow, the Python trace of the
program IS its communication schedule — recording during ``make_jaxpr``
sees exactly the ops the compiled program will issue (a ``lax.scan``
body is traced once, so the recorded schedule is one loop instance).

Each event also tags its op's equations in the jaxpr/HLO via
``jax.named_scope`` with a ``shoal.<op>#e<seq>`` scope, so call sites
are recoverable *post-trace*: :func:`recover_tags` walks a closed
jaxpr's equations and maps them back to events by tag.  The same tags
show up as ``op_name`` metadata in compiled HLO, which is how a budget
finding in pass 2 can name the op that emitted the collective.

Layer scopes: :func:`layer` wraps the equations of one layer of the
comm path in a ``layer.<name>`` named scope, which also lands in the
compiled HLO's ``op_name`` metadata, so an instruction -- and through
:func:`repro.launch.hlo_analysis.op_layers` a profiler's device event --
can be charged to its layer.  The names are fixed (:data:`LAYERS`):

* ``compute``: the application's own compute step (for Jacobi, the
  stencil: its halo-row selects and the Pallas kernel's call);
* ``egress``: packet build (header encode, piggyback lane, NOP masking,
  the GAScore's payload egress, the fused packet);
* ``wire``: the collectives that cross the links, and nothing else;
* ``local``: the moves of packets between kernels on one device (the
  LOCAL path, which issues no collective);
* ``ingress``: the GAScore's ingress of data, replies and ack lanes
  (the fused packet's unpacking included);
* ``sync``: barrier, ``wait_replies`` and the deferred-ack drain.

Scopes nest and the innermost wins: a barrier's ``psum`` is ``wire``
inside ``sync``.  They are metadata only, so they change no instruction
and no fusion, and they use a ``layer.`` prefix so that
:func:`recover_tags` counts the ``shoal.*`` tags alone.

Traced (non-concrete) operands degrade conservatively: an interval
whose start is unknown is recorded with ``start=None`` and treated by
the rules as potentially overlapping everything in its segment.

Deliberate hazards are annotated inline with :func:`waiver`::

    with analysis.waiver("double-write is idempotent here"):
        state = ops.put_long(ctx, state, pay, pattern, dst_addr=0)

Events emitted under a waiver still produce findings, but the findings
are marked waived and do not fail ``lint_clean`` / the CLI.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Iterator

import jax

# ops that write destination segment memory
WRITE_OPS = ("put_long", "put_long_strided", "put_long_vectored",
             "put_long_multi", "mailbox_flush")
# ops that read remote segment memory
READ_OPS = ("get_medium", "get_long")
# ordering / bookkeeping ops
SYNC_OPS = ("wait_replies", "barrier")


@dataclasses.dataclass(frozen=True)
class Interval:
    """A destination-segment word range ``[start, start + words)``.

    ``start=None`` means the address was traced: the analyzer must
    assume the interval may alias anything in the segment.
    """

    start: int | None
    words: int

    @property
    def known(self) -> bool:
        return self.start is not None

    def overlaps(self, other: "Interval") -> bool:
        if not (self.known and other.known):
            return True          # conservatively aliasing
        return (self.start < other.start + other.words
                and other.start < self.start + self.words)

    def __str__(self) -> str:
        if not self.known:
            return f"[?, ?+{self.words})"
        return f"[{self.start}, {self.start + self.words})"


@dataclasses.dataclass
class CommEvent:
    """One comm-op call site, as recorded at trace time."""

    seq: int                            # event index in trace order
    op: str                             # op name ("put_long", ...)
    pattern: tuple[tuple[int, int], ...]
    writes: tuple[Interval, ...] = ()   # intervals written at each dst
    reads: tuple[Interval, ...] = ()    # intervals read at each remote src
    token: int | None = None            # None = traced token
    acked: bool = False                 # earns one credit on `token`
    asynchronous: bool = False
    deferred_reply: bool = False        # ack routed through a ReplyMailbox
    defer_ack: bool = False             # ack ledgered at the receiver
    piggyback_token: int | None = None  # this packet carries that token's
                                        # deferred acks home (grants them)
    drains_deferred: bool = False       # drain_deferred_acks: ships the
                                        # residual ledger for `token`
    wait_n: int | None = None           # wait_replies count (None = traced)
    timeout: bool = False               # wait_replies: partial-drain path
    lossy: bool = False                 # traverses a fault-injecting link
    retries: int = 0                    # retransmit bound (0 = no retry)
    dedup: bool = True                  # receiver dedups redelivery (R5)
    credit_grants: tuple[tuple[int, int], ...] = ()  # (token, count) grants
    handler: int | None = None
    segment_words: int = 0
    mailbox_id: int | None = None       # id() of the flushing Mailbox
    ordered_ingress: bool = True        # strided: sequential-scan ingress?
    self_overlap: bool = False          # intra-op aliasing possible
    waiver: str | None = None
    tag: str = ""                       # "shoal.<op>#e<seq>" named scope
    detail: dict = dataclasses.field(default_factory=dict)

    @property
    def dsts(self) -> tuple[int, ...]:
        return tuple(sorted({d for _, d in self.pattern}))

    @property
    def srcs(self) -> tuple[int, ...]:
        return tuple(sorted({s for s, _ in self.pattern}))

    def site(self) -> str:
        return f"{self.op}#e{self.seq}"


class Recorder:
    """Collects :class:`CommEvent`s while installed (see :func:`record`),
    the packets and bytes each link class carried (:func:`carried`), and
    how each packet stack was landed (:func:`landed`)."""

    def __init__(self) -> None:
        self.events: list[CommEvent] = []
        self.links: dict[str, dict[str, int]] = {}
        self.landings: list[tuple[str, int]] = []

    def next_seq(self) -> int:
        return len(self.events)


_RECORDERS: list[Recorder] = []
_WAIVERS: list[str] = []
_TAG_COUNTER = [0]


def active() -> bool:
    return bool(_RECORDERS)


def current_waiver() -> str | None:
    return _WAIVERS[-1] if _WAIVERS else None


@contextlib.contextmanager
def record() -> Iterator[Recorder]:
    """Install a fresh recorder for the duration of a trace."""
    rec = Recorder()
    _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        _RECORDERS.remove(rec)


@contextlib.contextmanager
def waiver(reason: str) -> Iterator[None]:
    """Mark comm ops in this block as deliberate (inline waiver).

    Findings whose every involved event carries a waiver are reported
    as waived and do not fail the lint.  The waiver also downgrades the
    op layer's *runtime* aliasing rejections (e.g. overlapping vectored
    destination addresses) to analyzer findings, so a deliberately
    order-dependent packet can be expressed at all.
    """
    if not reason or not str(reason).strip():
        raise ValueError("waiver() needs a non-empty reason string")
    _WAIVERS.append(str(reason))
    try:
        yield
    finally:
        _WAIVERS.pop()


def static_int(x) -> int | None:
    """``int(x)`` when ``x`` is trace-time concrete, else ``None``."""
    try:
        return int(x)
    except Exception:
        return None


def emit(op: str, pattern, **kw) -> str:
    """Record one comm event (if a recorder is active) and return the
    ``shoal.<op>#e<seq>`` scope tag for :func:`scope`.

    Tagging is unconditional — compiled programs always carry the call
    sites in their op metadata — but events are only stored while a
    :func:`record` block is active.
    """
    pat = tuple((int(s), int(d)) for s, d in pattern)
    if _RECORDERS:
        rec = _RECORDERS[-1]
        seq = rec.next_seq()
        tag = f"shoal.{op}#e{seq}"
        ev = CommEvent(seq=seq, op=op, pattern=pat, tag=tag,
                       waiver=current_waiver(), **kw)
        rec.events.append(ev)
        return tag
    _TAG_COUNTER[0] += 1
    return f"shoal.{op}#e{_TAG_COUNTER[0] - 1}"


def carried(link: str, packets: int, nbytes: int) -> None:
    """Count one exchange's packets and bytes on a link class (``LOCAL``
    between kernels on one device, ``ICI`` between devices) while a
    :func:`record` block is active."""
    if _RECORDERS:
        tally = _RECORDERS[-1].links.setdefault(
            link, {"packets": 0, "bytes": 0})
        tally["packets"] += packets
        tally["bytes"] += nbytes


LANDINGS = ("one_pass", "scan")


def landed(path: str, rows: int) -> None:
    """Record one GAScore landing of a packet stack while a
    :func:`record` block is active: ``path`` is ``one_pass`` (static
    writes per item) or ``scan`` (a ``lax.scan`` over the rows), and
    ``rows`` the stack's height."""
    if path not in LANDINGS:
        raise ValueError(f"unknown landing {path!r}; one of {LANDINGS}")
    if _RECORDERS:
        _RECORDERS[-1].landings.append((path, int(rows)))


def scope(tag: str):
    """Named scope wrapping an op's equations with its event tag."""
    return jax.named_scope(tag)


LAYERS = ("compute", "egress", "wire", "local", "ingress", "sync")
LAYER_PREFIX = "layer."


def layer(name: str):
    """Named scope ``layer.<name>`` charging the equations inside it to
    one of the :data:`LAYERS`."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; one of {LAYERS}")
    return jax.named_scope(LAYER_PREFIX + name)


# --------------------------------------------------------------------------
# post-trace recovery: map jaxpr equations back to tagged call sites
# --------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(v):
    vals = v if isinstance(v, (tuple, list)) else (v,)
    for item in vals:
        inner = getattr(item, "jaxpr", None)
        if inner is not None:
            # ClosedJaxpr -> Jaxpr, or already a Jaxpr-like
            yield getattr(inner, "jaxpr", inner) if hasattr(inner, "eqns") \
                else inner
        elif hasattr(item, "eqns"):
            yield item


def recover_tags(closed_jaxpr) -> dict[str, int]:
    """Walk a (closed) jaxpr and count equations per ``shoal.*`` tag.

    Returns ``{tag: eqn_count}`` — the post-trace view of which comm
    call sites made it into the program.  Used by the linter to
    cross-check that every recorded event is recoverable from the jaxpr
    alone (and by debugging tools to attribute equations to ops).
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    tags: dict[str, int] = {}
    for eqn in _iter_eqns(jaxpr):
        try:
            stack = str(eqn.source_info.name_stack)
        except Exception:
            continue
        for part in stack.split("/"):
            if part.startswith("shoal."):
                tags[part] = tags.get(part, 0) + 1
    return tags


def intervals_for_blocks(addrs, sizes) -> tuple[Interval, ...]:
    """Per-block :class:`Interval`s for a vectored address list; traced
    addresses become unknown intervals."""
    out = []
    for a, w in zip(addrs, sizes):
        out.append(Interval(static_int(a), int(w)))
    return tuple(out)
