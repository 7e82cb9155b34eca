"""Per-kernel PGAS state and the Shoal context.

``PgasState`` is the functional analogue of everything the GAScore /
handler thread owns per kernel in the paper: the shared-memory segment
(this kernel's partition of the global address space), the reply/credit
counter file, and a few counters we keep for the Table-I-style cost
accounting.  All Shoal ops thread it explicitly (dataflow has no mutable
runtime).

``ShoalContext`` is the trace-time configuration: which mesh axes
enumerate kernels, how many kernels each device holds, the transport
(acked/async + packet limit), and the handler table.  It is the analogue
of a linked Shoal library instance.

Several kernels on one device (the paper's several kernels on one node)
run as a ``vmap`` over the device's slots inside the ``shard_map``
(:meth:`ShoalContext.kernel_map`): each kernel still sees only its own
``PgasState``, so its segment, credits, ack ledger and counters are its
own, and its ID is ``device * kernels_per_device + slot``.  Data moves
between slots only through the two slot primitives at the end of this
module, whose batching rules see the device's whole slot stack: nothing
crosses a link.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import custom_vmap

from jax import shard_map

from repro.core import handlers as hd
from repro.runtime.transport import Transport, TCP

# the slot of the kernel being traced, innermost kernel_map last: a
# value of the slot vmap, since ``axis_index`` of a vmap axis cannot
# mix with mesh-varying values inside loops
_SLOTS: list = []


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PgasState:
    """Per-kernel runtime state (a pytree; leaves are per-device arrays)."""

    segment: jnp.ndarray          # (segment_words,) shared-memory partition
    credits: jnp.ndarray          # (NUM_TOKENS,) int32 reply counters
    barrier_epoch: jnp.ndarray    # () int32
    rx_words: jnp.ndarray         # () int32 total words received
    tx_words: jnp.ndarray         # () int32 total words sent
    error: jnp.ndarray            # () int32 sticky error bits
    deferred_acks: jnp.ndarray    # (NUM_TOKENS,) int32 acks owed per link
    # deferred_acks is the receiver-side piggyback ledger: a put flagged
    # FLAG_DEFER_ACK bumps deferred_acks[token] here instead of shipping
    # a reply collective; the next packet this kernel sends over the
    # reverse link carries the count home in its pb_token/pb_count lane.

    # -- lossy-transport reliability state (PR 10) ----------------------
    # send_epoch stamps outgoing messages with a per-(sender, token)
    # sequence number; the three dedup_* arrays are the receiver's
    # redelivery ledger: dedup_epoch[t] is the last *completed* epoch on
    # token t, dedup_inflight[t] the epoch the partial-arrival bitmask
    # dedup_seen[t] (bit i = segment i arrived) belongs to.  When the
    # final segment completes the mask, dedup_epoch latches and the mask
    # drains back to zero.  retransmits counts retry rounds this kernel
    # actually re-sent in (the dynamic cost of loss — compiled CP counts
    # are static, this is not).
    send_epoch: jnp.ndarray       # (NUM_TOKENS,) int32 per-token msg counter
    dedup_epoch: jnp.ndarray      # (NUM_TOKENS,) int32 last completed epoch
    dedup_inflight: jnp.ndarray   # (NUM_TOKENS,) int32 epoch of dedup_seen
    dedup_seen: jnp.ndarray       # (NUM_TOKENS,) int32 segment-arrival bitmask
    retransmits: jnp.ndarray      # () int32 retry rounds this kernel sent in

    @staticmethod
    def make(segment_words: int, dtype=jnp.float32) -> "PgasState":
        return PgasState(
            segment=jnp.zeros((segment_words,), dtype),
            credits=jnp.zeros((hd.NUM_TOKENS,), jnp.int32),
            barrier_epoch=jnp.zeros((), jnp.int32),
            rx_words=jnp.zeros((), jnp.int32),
            tx_words=jnp.zeros((), jnp.int32),
            error=jnp.zeros((), jnp.int32),
            deferred_acks=jnp.zeros((hd.NUM_TOKENS,), jnp.int32),
            send_epoch=jnp.zeros((hd.NUM_TOKENS,), jnp.int32),
            dedup_epoch=jnp.zeros((hd.NUM_TOKENS,), jnp.int32),
            dedup_inflight=jnp.zeros((hd.NUM_TOKENS,), jnp.int32),
            dedup_seen=jnp.zeros((hd.NUM_TOKENS,), jnp.int32),
            retransmits=jnp.zeros((), jnp.int32),
        )


# -- sticky error bits + host-side decode registry ---------------------------
ERR_WAIT_UNDERFLOW = 1    # wait_replies saw fewer credits than expected
ERR_CRC = 2               # a received packet failed its CRC seal
ERR_RETRY_EXHAUSTED = 4   # a reliable put ran out of retransmit rounds


class ShoalError(RuntimeError):
    """Base of host-side errors decoded from the sticky device error
    word.  ``kernels`` names the kernels that latched the bit (empty
    when the state was already reduced to a single error word)."""

    def __init__(self, message: str, kernels=()):
        self.kernels = tuple(int(k) for k in kernels)
        super().__init__(message)


class WaitUnderflowError(ShoalError):
    """A ``wait_replies`` drained more credits than the schedule issued.

    The device-side error word is sticky (kernels cannot raise), so this
    is the host-side debug surface: :func:`raise_on_error` decodes the
    error bits *and* names the offending token(s) — a drained wait
    leaves its token's credit counter negative, which is exactly the
    trace-time R3 underflow condition shoal-lint reports statically.
    """

    def __init__(self, tokens, kernels, where: str = ""):
        self.tokens = tuple(int(t) for t in tokens)
        at = f" in {where}" if where else ""
        tok = (f"token(s) {list(self.tokens)}" if self.tokens
               else "an unidentified token (counters were rebalanced)")
        kernels = tuple(int(k) for k in kernels)
        ker = (f" on kernel(s) {list(kernels)}" if kernels
               else "")
        super().__init__(
            f"ERR_WAIT_UNDERFLOW{at}: wait_replies consumed more credits "
            f"than were issued on {tok}{ker} — the threaded original "
            "would hang here; shoal-lint rule R3 catches this schedule "
            "at trace time (scripts/comm_lint.py)", kernels)


class CrcError(ShoalError):
    """A receiver saw a packet whose CRC seal failed (bit corruption on
    a lossy link).  The row was NOPed — i.e. treated as a drop — so on
    an acked transport the retransmit path recovers; the sticky bit is
    the observability surface."""


class RetryExhaustedError(ShoalError):
    """A reliable put gave up after ``max_retries`` retransmissions
    without seeing an ack.  The destination may or may not hold the
    data (the ack, not the data, may be what kept dying); the sender's
    credit was NOT granted.  `training/elastic.py` uses this bit to
    drop the kernel out of the quorum mask."""


def _build_wait_underflow(state, kernels, where):
    import numpy as np

    credits = np.asarray(jax.device_get(state.credits))
    credits = credits.reshape(-1, hd.NUM_TOKENS)
    # an over-drained wait leaves its token negative on the waiting kernel
    tokens = np.nonzero((credits < 0).any(axis=0))[0]
    return WaitUnderflowError(tokens, kernels, where=where)


def _generic_builder(name, exc):
    def build(state, kernels, where):
        kernels = tuple(int(k) for k in kernels)
        at = f" in {where}" if where else ""
        ker = f" on kernel(s) {list(kernels)}" if kernels else ""
        return exc(f"{name}{at}: sticky device error bit latched{ker} "
                   "(see repro.core.state docs for semantics)", kernels)
    return build


# bit -> (name, exception class, builder(state, kernels, where) -> exc).
# Future PRs extend via register_error_bit; raise_on_error decodes all
# registered bits, lowest bit first.
ERROR_BITS: dict[int, tuple[str, type, Any]] = {}


def register_error_bit(bit: int, name: str, exc: type = ShoalError,
                       builder=None) -> None:
    """Register a sticky error bit so :func:`raise_on_error` can decode
    and name it.  ``bit`` must be a fresh power of two."""
    if bit <= 0 or bit & (bit - 1):
        raise ValueError(f"error bit must be a power of two, got {bit}")
    if bit in ERROR_BITS:
        raise ValueError(f"error bit {bit} already registered "
                         f"as {ERROR_BITS[bit][0]}")
    ERROR_BITS[bit] = (name, exc, builder or _generic_builder(name, exc))


register_error_bit(ERR_WAIT_UNDERFLOW, "ERR_WAIT_UNDERFLOW",
                   WaitUnderflowError, _build_wait_underflow)
register_error_bit(ERR_CRC, "ERR_CRC", CrcError)
register_error_bit(ERR_RETRY_EXHAUSTED, "ERR_RETRY_EXHAUSTED",
                   RetryExhaustedError)


def error_names(err: int) -> tuple[str, ...]:
    """Names of the registered bits set in an error word."""
    return tuple(name for bit, (name, _, _) in sorted(ERROR_BITS.items())
                 if err & bit)


def raise_on_error(state: PgasState, *, where: str = "",
                   ignore: int = 0) -> PgasState:
    """Host-side debug check: raise if any kernel latched an error bit.

    Call on a state fetched back to the host (after ``spmd`` execution).
    Accepts per-kernel ``(...,)`` or stacked global ``(kernels, ...)``
    leaves; returns ``state`` unchanged when clean so it can sit inline
    in a host-side pipeline.  Every bit in the registry is decoded to
    its named exception class, lowest bit first; ``ignore`` masks bits
    the caller expects (e.g. ``ignore=ERR_CRC`` under deliberate fault
    injection).
    """
    import numpy as np

    err = np.asarray(jax.device_get(state.error)).reshape(-1)
    pending = int(np.bitwise_or.reduce(err)) & ~ignore if err.size else 0
    for bit, (name, _, build) in sorted(ERROR_BITS.items()):
        if pending & bit:
            kernels = np.nonzero(err & bit)[0] if err.size > 1 else ()
            raise build(state, kernels, where)
    if pending:
        raise ShoalError(f"unregistered error bit(s) 0x{pending:x}"
                         + (f" in {where}" if where else ""))
    return state


@dataclasses.dataclass(frozen=True)
class ShoalContext:
    """Trace-time Shoal configuration.

    Attributes:
      mesh: the device mesh (cluster).
      axes: mesh axis name(s) that enumerate devices, row-major.
      transport: delivery semantics + packet limit (TCP/UDP analogue).
      handlers: the frozen handler table.
      segment_words: words in each kernel's segment.
      kernels_per_device: Shoal kernels on each device (slots); kernel
        ``k`` lives on device ``k // kernels_per_device``.
    """

    mesh: Any
    axes: tuple[str, ...]
    transport: Transport = TCP
    handlers: hd.HandlerTable = dataclasses.field(default_factory=lambda: hd.DEFAULT_TABLE)
    segment_words: int = 4096
    kernels_per_device: int = 1

    @property
    def num_devices(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.axes)

    @property
    def num_kernels(self) -> int:
        return self.num_devices * self.kernels_per_device

    def my_id(self):
        """Kernel ID of the executing kernel (inside :meth:`kernel_map`):
        ``device * kernels_per_device + slot``; a lone kernel's slot is 0."""
        if not _SLOTS and self.kernels_per_device > 1:
            raise RuntimeError("my_id() of kernels that share a device is "
                               "defined inside ShoalContext.kernel_map")
        slot = _SLOTS[-1] if _SLOTS else 0
        return lax.axis_index(self.axes) * self.kernels_per_device + slot

    def make_state(self, dtype=jnp.float32) -> PgasState:
        return PgasState.make(self.segment_words, dtype)

    def mailbox(self, pattern, **kw):
        """Per-destination coalescing mailbox over this context (the
        actor layer, :mod:`repro.actors`): N tiny sends along
        ``pattern`` flush as ONE collective."""
        from repro.actors import Mailbox  # deferred: actors imports core

        return Mailbox(self, pattern, **kw)

    def reply_mailbox(self):
        """Deferred-ack mailbox: pass as ``reply_via=`` to put ops so
        their acks coalesce into one Short AM per destination at
        flush."""
        from repro.actors import ReplyMailbox  # deferred: actors imports core

        return ReplyMailbox(self)

    def kernel_map(self, fn, in_specs, out_specs, **shard_map_kwargs):
        """``shard_map`` of a per-kernel ``fn`` over the mesh: ``fn``
        sees one kernel's block (leading dim 1) of each argument, as
        under a plain ``shard_map`` with one kernel per device.  Where
        devices hold several kernels, every argument and result is split
        over the kernels, and ``fn`` runs once per slot under a
        ``vmap``: the one place that chooses a path by the kernel count."""
        if self.kernels_per_device == 1:
            return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, **shard_map_kwargs)

        def one_kernel(slot, *blocks):
            _SLOTS.append(slot)
            try:
                out = fn(*jax.tree.map(lambda x: x[None], blocks))
            finally:
                _SLOTS.pop()
            return jax.tree.map(lambda x: x[0], out)

        @functools.wraps(fn)
        def per_device(*args):
            slots = jnp.arange(self.kernels_per_device, dtype=jnp.int32)
            return jax.vmap(one_kernel)(slots, *args)

        return shard_map(per_device, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, **shard_map_kwargs)

    def spmd(self, fn, state_spec=None, **shard_map_kwargs):
        """Wrap ``fn`` in :meth:`kernel_map` over the kernel axes.

        Every PgasState leaf is per-kernel, i.e. sharded over the
        (flattened) device axes on its leading dim when viewed globally;
        we use rank-preserving specs: leading dim split over axes.
        """
        from jax.sharding import PartitionSpec as P

        spec = P(self.axes) if state_spec is None else state_spec
        return self.kernel_map(fn, spec, spec, **shard_map_kwargs)


# -- the slot primitives ------------------------------------------------------
# The only code that sees kernels share a device.  Outside the slot
# ``vmap`` a kernel is alone on its device (the unbatched rules); under
# it, their batching rules see every kernel of the device at once.

@custom_vmap
def from_slot(x, src):
    """What this kernel receives from slot ``src`` of its device (zeros
    where ``src`` is -1): an in-device move, no collective."""
    return jnp.where(src == 0, x, jnp.zeros_like(x))


@from_slot.def_vmap
def _from_slot_stack(axis_size, in_batched, x, src):
    x = x if in_batched[0] else jnp.broadcast_to(x, (axis_size, *x.shape))
    src = src if in_batched[1] else jnp.broadcast_to(src, (axis_size,))
    got = jnp.take(x, jnp.clip(src, 0, axis_size - 1), axis=0)
    keep = (src >= 0).reshape((axis_size,) + (1,) * (x.ndim - 1))
    return jnp.where(keep, got, jnp.zeros_like(got)), True


@custom_vmap
def slots_total(x):
    """The sum of ``x`` over the kernels of this device."""
    return x


@slots_total.def_vmap
def _slots_total_stack(axis_size, in_batched, x):
    total = x.sum(axis=0) if in_batched[0] else x * axis_size
    return jnp.broadcast_to(total, (axis_size, *total.shape)), True
