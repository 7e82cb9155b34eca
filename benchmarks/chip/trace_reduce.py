"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO instruction, named by
the instruction's text (``%fusion.45 = f32[...] fusion(...)``); the ops
of a loop body sit inside the loop's own event.  Host planes carry the
benchmark's spans (``bench.window``, ``bench.dispatch``,
``bench.block``, ``bench.keep``).  Everything here works on plain
``(start_ns, end_ns)`` intervals on the trace's common clock:

* an op's name is its instruction's name (``fusion.45``);
* busy time: the union of a device's op intervals inside the window
  (nested events, such as a loop and the ops inside it, count once);
* self time: each instant of busy time belongs to the innermost event
  that covers it, so a loop op is charged only what its body ops leave;
* idle gaps: the stretches of the window not covered by any op, each
  named by the host span that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    devices: dict          # device id -> [Event] on its op line, by start
    host_spans: list       # [Event] whose name starts with "bench."
    _own: dict = dataclasses.field(default_factory=dict, repr=False)

    def own(self, lo: float, hi: float) -> dict:
        """Per device: ``[(event, self ns)]`` of the ops inside
        ``[lo, hi)`` (computed once per window)."""
        if (lo, hi) not in self._own:
            self._own[(lo, hi)] = {d: self_times(clip(evs, lo, hi))
                                   for d, evs in self.devices.items()}
        return self._own[(lo, hi)]


def op_name(text: str) -> str:
    """``%fusion.45 = f32[8] fusion(...)`` -> ``fusion.45``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path_or_bytes) -> Trace:
    """Read the device op lines and the benchmark's host spans."""
    from jax.profiler import ProfileData

    if isinstance(path_or_bytes, (bytes, bytearray)):
        pd = ProfileData.from_serialized_xspace(bytes(path_or_bytes))
    else:
        pd = ProfileData.from_file(str(path_or_bytes))
    devices, spans, names = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OP_LINE:
                evs = []
                for ev in line.events:
                    text, s = ev.name, ev.start_ns
                    name = names.get(text)
                    if name is None:
                        name = names[text] = op_name(text)
                    evs.append(Event(name, s, s + ev.duration_ns))
                evs.sort(key=lambda e: (e.start_ns, -e.end_ns))
                devices[int(m.group(1))] = evs
            elif not m and plane.name.startswith("/host"):
                spans.extend(Event(ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(HOST_SPAN_PREFIX))
    spans.sort(key=lambda e: e.start_ns)
    return Trace(devices, spans)


def window(trace: Trace) -> tuple[float, float]:
    """The traced window: the one ``bench.window`` host span."""
    wins = [s for s in trace.host_spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, "
                         f"found {len(wins)}")
    return wins[0].start_ns, wins[0].end_ns


def clip(events, lo: float, hi: float) -> list[Event]:
    out = []
    for e in events:
        if e.start_ns >= lo and e.end_ns <= hi:
            out.append(e)
        elif e.end_ns > lo and e.start_ns < hi:
            out.append(Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi)))
    return out


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_t = 0.0, None, None
    for s, t in sorted(intervals):
        if cur_t is None or s > cur_t:
            if cur_t is not None:
                total += cur_t - cur_s
            cur_s, cur_t = s, t
        else:
            cur_t = max(cur_t, t)
    if cur_t is not None:
        total += cur_t - cur_s
    return total


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of ``[lo, hi)`` that no event covers."""
    out, cur = [], lo
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > cur:
            out.append((cur, e.start_ns))
        cur = max(cur, e.end_ns)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(events) -> list[tuple[Event, float]]:
    """Each event with the busy time that belongs to it and to none of
    the events nested inside it (events on one line nest or are
    disjoint)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    own = [e.end_ns - e.start_ns for e in evs]
    stack: list[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(e.end_ns, evs[parent].end_ns) - e.start_ns
        stack.append(i)
    return list(zip(evs, own))


def busy_ns(trace: Trace, lo: float, hi: float) -> dict[int, float]:
    """Per device: busy nanoseconds inside ``[lo, hi)``."""
    return {d: union_ns((e.start_ns, e.end_ns) for e, _ in evs)
            for d, evs in trace.own(lo, hi).items()}


def self_ns_where(trace: Trace, lo: float, hi: float, pred) -> dict[int, float]:
    """Per device: self time inside ``[lo, hi)`` of the ops for which
    ``pred(name)`` holds."""
    return {d: sum(own for e, own in evs if pred(e.name))
            for d, evs in trace.own(lo, hi).items()}


def count_where(trace: Trace, lo: float, hi: float, pred) -> dict[int, int]:
    """Per device: number of ops inside ``[lo, hi)`` for which
    ``pred(name)`` holds."""
    return {d: sum(1 for e, _ in evs if pred(e.name))
            for d, evs in trace.own(lo, hi).items()}


def mean(per_device: dict) -> float:
    return sum(per_device.values()) / max(len(per_device), 1)


def idle_pct(trace: Trace, lo: float, hi: float) -> float | None:
    """Share of ``[lo, hi)`` with no op on the device, in %, averaged
    over the devices (None where the trace has no device)."""
    if not trace.devices:
        return None
    return 100.0 * (1.0 - mean(busy_ns(trace, lo, hi)) / (hi - lo))


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most self time (seconds, averaged over
    the devices) and the longest idle gaps, each named by the host span
    at its midpoint."""
    n = max(len(trace.devices), 1)
    per_op: dict[str, float] = defaultdict(float)
    idle: list[tuple[str, float]] = []
    for d, evs in trace.own(lo, hi).items():
        for e, own in evs:
            per_op[e.name] += own / n
        for s, t in gaps([e for e, _ in evs], lo, hi):
            idle.append((f"{host_span_at(trace, (s + t) / 2)} (device "
                         f"{d})", (t - s) * 1e-9))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle[:top]]}


def host_span_at(trace: Trace, t_ns: float) -> str:
    """Innermost benchmark host span covering ``t_ns``."""
    best = None
    for s in trace.host_spans:
        if s.start_ns <= t_ns < s.end_ns and s.name != WINDOW_SPAN:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best else "host loop"
