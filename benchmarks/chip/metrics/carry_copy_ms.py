"""Device self time per iteration of the copies of the solve loop's band
carry, mean over the cell's chips, in ms.

The solve loop is the ``while`` of the executed module's entry
computation whose carry holds the largest floating-point array: the
band, ``(n / chips) * n`` elements on a chip.  Its copies are the
``copy``, ``copy-start``, ``copy-done`` and copy fusions (a fusion the
compiler names ``copy...``) in the loop's body whose result holds as
many elements of the band's type: the compiler moving the band between
memories once an iteration.  Copies before and after the loop, once a
solve, are not counted.  The module is the one ``layers.module``
rebuilds for the cell; where its loop body holds no such copy the
reading is 0.0, and nothing is read without a trace or a module."""

import math
import re

import layers
import trace_reduce as tr

_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?)\s([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")
_BODY = re.compile(r"\bbody=%([\w.\-]+)")
_FLOATS = ("f16", "bf16", "f32", "f64")
_COPIES = ("copy", "copy-start", "copy-done")


def _arrays(shape: str) -> list[tuple[str, int]]:
    """``(type, elements)`` of each array in a result shape, in order."""
    return [(t, math.prod(int(d) for d in dims.split(",") if d))
            for t, dims in _ARRAY.findall(shape)]


def band_copies(text: str) -> set[str]:
    """The names of the copies of the solve loop's band carry in a
    compiled module's text."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        if m := _COMPUTATION.match(line):
            current = m.group(2)
            computations[current] = []
            if m.group(1):
                entry = current
        elif (m := _INSTRUCTION.match(line)) and current is not None:
            computations[current].append(
                (m.group(1), m.group(3), m.group(2), line))
    band = None  # (elements, type, body) of the largest carried array
    for _, op, shape, line in computations.get(entry, ()):
        body = _BODY.search(line)
        if op != "while" or not body:
            continue
        for t, size in _arrays(shape):
            if t in _FLOATS and (band is None or size > band[0]):
                band = (size, t, body.group(1))
    if band is None:
        return set()
    size, t, body = band
    return {name for name, op, shape, _ in computations.get(body, ())
            if (op in _COPIES or (op == "fusion" and name.startswith("copy")))
            and _arrays(shape)[:1] == [(t, size)]}


def read(run):
    if not run.trace or not run.trace.devices:
        return None
    text = layers.module(run)
    if text is None:
        return None
    copies = band_copies(text)
    per_dev = tr.self_ns_where(run.trace, *run.trace_window,
                               lambda name: name in copies)
    return tr.mean(per_dev) * 1e-6 / run.work("iters")
