"""Handler functions and reply/credit counters (paper Secs. II-C1, III-A).

GASNet-style AMs carry a handler ID; the receiver runs the handler on
arrival.  The paper keeps user-defined handlers in software but restricts
hardware kernels to a built-in set, with reply bookkeeping absorbed into
the runtime.  We take the same position for *all* kernels: handlers are
pure functions ``(region, payload) -> region`` fixed at trace time and
dispatched with ``lax.switch`` — the dataflow analogue of the GAScore's
handler wrapper, and the only form that maps onto an SPMD accelerator.

``region`` is the destination-segment slice the payload lands on, so the
built-ins express the classic one-sided verbs: overwrite (plain put),
accumulate (put-with-reduce), min/max.  Reply counting does not go
through this table: replies are consumed by the GAScore ingress stage
itself (:mod:`repro.core.gascore`), as in the paper.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

# Built-in handler IDs (stable ABI; configs and tests use these).
H_NOP = 0
H_WRITE = 1
H_ADD = 2
H_MAX = 3
H_MIN = 4
NUM_BUILTIN = 5

# Credit-counter file size per kernel: tokens index into this.
NUM_TOKENS = 16

HandlerFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

_BUILTINS: tuple[tuple[str, HandlerFn], ...] = (
    ("nop", lambda region, payload: region),
    ("write", lambda region, payload: payload.astype(region.dtype)),
    ("add", lambda region, payload: region + payload.astype(region.dtype)),
    ("max", lambda region, payload: jnp.maximum(region, payload.astype(region.dtype))),
    ("min", lambda region, payload: jnp.minimum(region, payload.astype(region.dtype))),
)


class HandlerTable:
    """Trace-time-frozen handler registry.

    Users may register additional pure handlers before tracing (the
    software-kernel freedom the paper preserves); the table is then
    baked into the compiled program via ``lax.switch``.
    """

    def __init__(self):
        self._entries: list[tuple[str, HandlerFn]] = list(_BUILTINS)

    def register(self, name: str, fn: HandlerFn) -> int:
        """Register a custom handler; returns its handler ID."""
        self._entries.append((name, fn))
        return len(self._entries) - 1

    def __len__(self) -> int:
        return len(self._entries)

    def names(self) -> Sequence[str]:
        return [n for n, _ in self._entries]

    def fn(self, handler_id: int) -> HandlerFn:
        """The function of a static handler ID: a trace-time lookup, so
        the compiled program holds no switch."""
        return self._entries[handler_id][1]

    def dispatch(self, handler_id, region: jnp.ndarray, payload: jnp.ndarray):
        """Run handler ``handler_id`` on (region, payload) -> new region.

        ``handler_id`` may be traced; dispatch is a ``lax.switch`` over
        the frozen table, exactly one branch of which executes.
        """
        branches = [
            (lambda r, p, f=fn: f(r, p)) for _, fn in self._entries
        ]
        idx = jnp.clip(handler_id, 0, len(branches) - 1)
        # Inside shard_map every branch must vary over the same manual
        # axes: a NOP returns the region, a WRITE the payload.
        region, payload = vary_like((region, payload), region, payload)
        return jax.lax.switch(idx, branches, region, payload)


def vary_like(tree, *like):
    """Mark every leaf of ``tree`` as varying over each manual mesh axis
    that any leaf of ``like`` varies over (a no-op outside ``shard_map``).

    ``lax.switch`` branches and ``lax.scan`` carries must agree on their
    varying manual axes; values built from constants start invariant.
    """
    axes = frozenset().union(
        *(jax.typeof(x).vma for x in jax.tree_util.tree_leaves(like)))

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree_util.tree_map(cast, tree)


DEFAULT_TABLE = HandlerTable()


def bump_credit(credits: jnp.ndarray, token, n=1) -> jnp.ndarray:
    """credits[token] += n  (reply bookkeeping; paper Sec. III-A)."""
    return credits.at[token].add(jnp.asarray(n, credits.dtype))


def drain_credits(credits: jnp.ndarray, token, n) -> jnp.ndarray:
    """Consume ``n`` credits after a wait (GASNet wait-reply semantics)."""
    return credits.at[token].add(jnp.asarray(-n, credits.dtype))
