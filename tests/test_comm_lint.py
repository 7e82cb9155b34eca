"""shoal-lint: behavioural checks (subprocess) + property test.

The subprocess half runs tests/comm_lint_checks.py on 8 host devices —
rules R1-R4 against real op-layer programs, the PR 6 strided-race
regression, registry cleanliness, and the host-side
``WaitUnderflowError`` debug path.

The property half fuzzes schedules of puts (plain, defer_ack, and
two-stack put_long_multi calls), piggyback/drain ack grants, waits, and
barriers, and cross-checks the analyzer's verdicts against
``sequential_schedule_oracle`` in tests/actor_checks.py — an
independent numpy executor that *runs* the schedule under every
admissible arrival reorder:

* R1 verdicts must equal the oracle's unordered-overlap pairs exactly
  (for a deferred ack, a wait orders only once a piggyback/drain grant
  sits between put and wait);
* an R1-clean schedule must be arrival-order independent (every
  admissible reorder leaves final memory bit-identical);
* R3 underflow/leak/stranded-ledger verdicts must match the oracle's
  credit and ledger counters.
"""

import random

from hypothesis import given, settings, strategies
from conftest import run_subprocess_checks


def test_comm_lint_rules():
    out = run_subprocess_checks("comm_lint_checks.py", n_devices=8,
                                timeout=900)
    assert "COMM_LINT_CHECKS_ALL_PASS" in out


# --------------------------------------------------------------------------
# property: analyzer race/credit verdicts vs the numpy sequential oracle
# --------------------------------------------------------------------------

SEG = 16


def _random_schedule(rng: random.Random):
    n_ops = rng.randint(2, 10)
    sched, value, group = [], 1.0, 0
    while len(sched) < n_ops:
        r = rng.random()
        if r < 0.42:
            words = rng.randint(1, 5)
            sched.append(("put", rng.randrange(0, SEG - words), words,
                          value, rng.randint(0, 2), rng.random() < 0.7))
            value += 1.0           # distinct values: overlap is observable
        elif r < 0.56:
            # defer_ack put: the ack pools in the receiver ledger until a
            # piggyback/drain grant ships it home
            words = rng.randint(1, 5)
            sched.append(("put_defer", rng.randrange(0, SEG - words), words,
                          value, rng.randint(0, 2)))
            value += 1.0
        elif r < 0.66:
            kind = "piggyback" if rng.random() < 0.5 else "drain"
            sched.append((kind, rng.randint(0, 2)))
        elif r < 0.74:
            # one put_long_multi call: two stacks crossing as ONE
            # collective.  Same-call intervals are always disjoint — the
            # op raises VectoredAliasError for overlap at trace time.
            w1, w2 = rng.randint(1, 3), rng.randint(1, 3)
            s1 = rng.randrange(0, SEG - w1 - w2)
            s2 = rng.randrange(s1 + w1, SEG - w2 + 1)
            acked = rng.random() < 0.7
            sched.append(("put", s1, w1, value, rng.randint(0, 2), acked,
                          group))
            sched.append(("put", s2, w2, value + 1.0, rng.randint(0, 2),
                          acked, group))
            value += 2.0
            group += 1
        elif r < 0.9:
            sched.append(("wait", rng.randint(0, 2), rng.randint(1, 2)))
        else:
            sched.append(("barrier",))
    return sched


def _to_events(sched):
    from repro.analysis import CommEvent, Interval

    events = []
    for i, row in enumerate(sched):
        if row[0] == "put":
            start, words, _value, token, acked = row[1:6]
            grp = row[6] if len(row) > 6 else None
            events.append(CommEvent(
                seq=i, op="put_long" if grp is None else "put_long_multi",
                pattern=((0, 1),), writes=(Interval(start, words),),
                token=token, acked=acked, segment_words=SEG,
                detail={} if grp is None else {"group": grp}))
        elif row[0] == "put_defer":
            start, words, _value, token = row[1:5]
            events.append(CommEvent(
                seq=i, op="put_long", pattern=((0, 1),),
                writes=(Interval(start, words),), token=token, acked=True,
                defer_ack=True, segment_words=SEG))
        elif row[0] == "piggyback":
            # the reverse-link data packet whose header lane carries the
            # ledgered acks home; the carrier itself earns no credit
            events.append(CommEvent(
                seq=i, op="put_long", pattern=((1, 0),), writes=(),
                token=row[1], acked=False, asynchronous=True,
                piggyback_token=row[1], segment_words=SEG))
        elif row[0] == "drain":
            events.append(CommEvent(
                seq=i, op="drain_deferred_acks", pattern=((1, 0),),
                token=row[1], acked=False, asynchronous=True,
                drains_deferred=True))
        elif row[0] == "wait":
            events.append(CommEvent(seq=i, op="wait_replies", pattern=(),
                                    token=row[1], wait_n=row[2]))
        else:
            events.append(CommEvent(seq=i, op="barrier", pattern=()))
    return events


@settings(max_examples=120, deadline=None)
@given(seed=strategies.integers(min_value=0, max_value=2**20))
def test_race_verdicts_match_sequential_oracle(seed):
    from actor_checks import sequential_schedule_oracle
    from repro.analysis import ERROR, WARNING, lint_events

    sched = _random_schedule(random.Random(seed))
    oracle = sequential_schedule_oracle(sched, SEG)
    rep = lint_events(_to_events(sched), name=f"fuzz-{seed}")

    r1_pairs = {f.events for f in rep.findings if f.rule == "R1"}
    want = {(i, j) for i, j in oracle["unordered_overlaps"]}
    assert r1_pairs == want, (
        f"seed {seed}: R1 verdicts {sorted(r1_pairs)} != oracle "
        f"unordered overlaps {sorted(want)}\nschedule: {sched}")

    if not r1_pairs:
        # clean verdict is a *semantic* guarantee: executing the schedule
        # under any admissible arrival reorder gives identical memory
        assert not oracle["divergent"], (
            f"seed {seed}: analyzer clean but reorder changes memory: "
            f"{oracle['divergent']}\nschedule: {sched}")

    r3_under = {f.events[0] for f in rep.findings
                if f.rule == "R3" and f.severity == ERROR}
    assert r3_under == set(oracle["underflow_events"]), (
        f"seed {seed}: R3 underflows {sorted(r3_under)} != oracle "
        f"{oracle['underflow_events']}\nschedule: {sched}")

    # R3 warnings = one per leaked token (credits never waited) + one
    # per stranded token (deferred acks never piggybacked/drained)
    n_warn = sum(1 for f in rep.findings
                 if f.rule == "R3" and f.severity == WARNING)
    want_warn = len(oracle["leaked_tokens"]) + len(oracle["stranded_acks"])
    assert n_warn == want_warn, (
        f"seed {seed}: {n_warn} R3 warnings != oracle leaked "
        f"{oracle['leaked_tokens']} + stranded {oracle['stranded_acks']}"
        f"\nschedule: {sched}")
