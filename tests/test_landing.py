"""How ``put_long_multi`` lands a received packet stack: in one pass
(``gascore.ingress_long_stack``) where its plan is static, else through
the scanned ``gascore.ingress_stack``, with the same result bit for bit.
The multi-device checks run once, in a subprocess with 8 host devices
(tests/landing_checks.py); each is a case here."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess_checks
from landing_checks import CHECKS
from repro.analysis import trace
from repro.core import am, gascore as gc, handlers as hd
from repro.core.state import PgasState, ShoalContext
from repro.runtime.topology import make_cpu_mesh


@pytest.fixture(scope="module")
def results():
    out = run_subprocess_checks("landing_checks.py", n_devices=8,
                                timeout=600)
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    return {r["check"]: r for r in lines}


@pytest.mark.parametrize("name", sorted(CHECKS))
def test_landing(results, name):
    assert name in results, f"{name} did not report"
    assert results[name]["ok"], results[name]["error"]


W = 8
LONG = am.make_type(am.LONG, asynchronous=True, fifo=True)
FINAL = am.make_type(am.LONG, fifo=True, defer_ack=True) | am.FLAG_PIGGYBACK


def _stack(handler):
    """Two items at W = 8: 20 words at 4 (rows of 8, 8, 4; the middle
    row is MEDIUM, so it must not land, but its piggyback lane counts)
    and 10 words at 40 (8, 2); each final row defers an ack on token 3,
    and it carries piggybacked acks on token 5."""
    rows = [(LONG, 8, 4), (am.MEDIUM | am.FLAG_PIGGYBACK, 8, 12),
            (FINAL, 4, 20), (LONG, 8, 40), (FINAL, 2, 48)]
    hdr = np.stack([np.asarray(am.encode(
        type=t, nwords=n, dst_addr=a, handler=handler, token=3,
        pb_token=5, pb_count=1 + i)) for i, (t, n, a) in enumerate(rows)])
    pay = np.random.default_rng(handler).standard_normal((len(rows), W))
    return jnp.asarray(hdr), jnp.asarray(pay, jnp.float32)


@pytest.mark.parametrize("handler", range(hd.NUM_BUILTIN))
def test_one_pass_matches_scan_on_a_stack(handler):
    """On one received stack, the one-pass landing leaves every state
    leaf as the scan does: the segment through each built-in handler,
    credits, the deferred-ack ledger and ``rx_words``."""
    ctx = ShoalContext(mesh=make_cpu_mesh(1, ("kernel",)), axes=("kernel",),
                       segment_words=64)
    seg = np.random.default_rng(99).standard_normal(64).astype(np.float32)
    st = gc.dataclasses_replace(PgasState.make(64), segment=jnp.asarray(seg))
    hdr, pay = _stack(handler)
    blocks = [(0, 3, 4, 20), (3, 2, 40, 10)]
    with trace.record() as rec:
        got = gc.ingress_long_stack(ctx, st, hdr, pay, blocks, handler, W)
        want = gc.ingress_stack(ctx, st, hdr, pay, W)
    assert rec.landings == [("one_pass", 5), ("scan", 5)]
    for name in want.__dataclass_fields__:
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), err_msg=name)
    assert int(got.rx_words) == 22
    assert int(got.deferred_acks[3]) == 2 and int(got.credits[5]) == 10
    np.testing.assert_array_equal(got.segment[12:20], seg[12:20])


@pytest.mark.parametrize("msg_type", [
    am.make_type(am.SHORT, asynchronous=True),
    am.make_type(am.SHORT, asynchronous=True, reply=True),
    am.make_type(am.LONG), am.NOP])
def test_static_short_handler_matches_dispatch(msg_type):
    """A Short whose sender's handler is a static H_ADD (the drain and
    the counted reply) changes the credit file as the dispatched
    handler does, for a user Short, a reply and rows of other classes."""
    ctx = ShoalContext(mesh=make_cpu_mesh(1, ("kernel",)), axes=("kernel",),
                       segment_words=16)
    credits = jnp.arange(hd.NUM_TOKENS, dtype=jnp.int32) * 3
    st = gc.dataclasses_replace(PgasState.make(16), credits=credits)
    hdr = am.decode(am.encode(type=msg_type, handler=hd.H_ADD, token=6,
                              dst_addr=5))
    got = gc.ingress_short(ctx, st, hdr, handler=hd.H_ADD)
    want = gc.ingress_short(ctx, st, hdr)
    np.testing.assert_array_equal(got.credits, want.credits)
