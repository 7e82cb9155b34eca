"""Post-optimization HLO analysis: collective bytes, trip-count-aware.

``compiled.cost_analysis()`` gives FLOPs and memory traffic but not
collective traffic, so we parse ``compiled.as_text()``:

* every ``all-reduce / all-gather / reduce-scatter / all-to-all /
  collective-permute`` op contributes its shape bytes;
* ops inside ``while`` bodies (scan-over-layers!) are multiplied by the
  loop trip count, recovered from the loop-condition computation's
  ``compare(..., constant(K))`` pattern — models here scan over layer
  segments, so this weighting is what makes per-step totals correct;
* *wire* bytes additionally weight each op by its algorithmic transfer
  factor on a ring (all-reduce moves 2(n-1)/n bytes/byte, all-gather and
  reduce-scatter (n-1)/n, all-to-all (n-1)/n, collective-permute 1).

Group size is parsed from ``replica_groups={{...}}`` or the iota form
``replica_groups=[G,N]<=[...]``.

:func:`op_layers` maps each instruction to the layer scope
(:func:`repro.analysis.trace.layer`) its ``op_name`` metadata names: a
profiler's device events carry only the instruction's name, so this map
turns a profile of a Shoal program into layers.
"""

from __future__ import annotations

import dataclasses
import re

from repro.analysis.trace import LAYER_PREFIX, LAYERS

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(?)([a-z0-9]+)\[([\d,]*)\]"
    r"[^=]*?\b(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(",
)
_TUPLE_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\(.*?\)\s*,?\s*condition=%?([\w.\-]+)\s*,\s*body=%?([\w.\-]+)")
_KNOWN_TRIP_RE = re.compile(r"known_trip_count[\"':{\s]+n[\"':\s]+(\d+)")
_CALL_RE = re.compile(
    r"(?:call|fusion)\(.*?(?:to_apply|calls)=%?([\w.\-]+)")
_CONST_CMP_RE = re.compile(r"constant\((\d+)\)")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


@dataclasses.dataclass
class CollectiveStats:
    ops: dict                    # kind -> count (trip-weighted)
    shape_bytes: float           # trip-weighted sum of output-shape bytes
    wire_bytes: float            # ring-model wire traffic per device
    by_kind: dict                # kind -> wire bytes
    dot_flops: float = 0.0       # trip-weighted matmul FLOPs per device


_LHS_SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([\d,]*)\]")
_DOT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*\bdot\(\s*%?([\w.\-]+)")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _wire_factor(kind: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return (n - 1) / n
    return 1.0  # collective-permute


def split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        s = line.strip()
        if cur is None:
            m = _COMP_HDR_RE.match(s)
            if m and s.endswith("{") and "->" in s:
                cur = m.group(1)
                comps[cur] = []
        else:
            if s == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    """Largest compare-constant in the condition: scans compare the
    induction variable against the trip count."""
    best = 1
    for line in cond_lines:
        if "compare" in line or "constant" in line:
            for m in _CONST_CMP_RE.finditer(line):
                best = max(best, int(m.group(1)))
    return best


def parse_collectives(hlo: str) -> CollectiveStats:
    comps = split_computations(hlo)

    # weight of each computation = product of enclosing trip counts
    weights: dict[str, float] = {}

    entry = None
    for name in comps:
        if "entry" in name.lower() or name.startswith("main"):
            entry = name
    if entry is None and comps:
        entry = next(iter(comps))

    def visit(name: str, w: float, depth=0):
        if name not in comps or depth > 32:
            return
        weights[name] = weights.get(name, 0.0) + w
        for line in comps[name]:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                km = _KNOWN_TRIP_RE.search(line)
                trips = (int(km.group(1)) if km
                         else _trip_count(comps.get(cond, [])))
                visit(body, w * trips, depth + 1)
                visit(cond, w * trips, depth + 1)
            else:
                cm = _CALL_RE.search(line)
                if cm:
                    visit(cm.group(1), w, depth + 1)

    if entry:
        visit(entry, 1.0)

    ops: dict[str, float] = {}
    shape_bytes = 0.0
    wire = 0.0
    dot_flops = 0.0
    by_kind: dict[str, float] = {}
    seen_started: set[str] = set()
    for name, lines in comps.items():
        w = weights.get(name, 1.0 if name == entry else 0.0)
        if w == 0.0:
            continue
        # per-computation symbol table: op name -> dims (for dot operands)
        symtab: dict[str, list[int]] = {}
        for line in lines:
            sm = _LHS_SHAPE_RE.match(line)
            if sm:
                symtab[sm.group(1)] = [int(d) for d in sm.group(3).split(",") if d]
        for line in lines:
            dm = _DOT_RE.search(line)
            if dm:
                out_dims = [int(d) for d in dm.group(2).split(",") if d]
                lhs_name = dm.group(3)
                cm = _LHS_CDIMS_RE.search(line)
                csize = 1
                if cm and lhs_name in symtab:
                    lhs_dims = symtab[lhs_name]
                    for ci in cm.group(1).split(","):
                        if ci:
                            csize *= lhs_dims[int(ci)]
                out_n = 1
                for d in out_dims:
                    out_n *= d
                dot_flops += w * 2.0 * out_n * csize
        for line in lines:
            m = _OP_RE.match(line)
            if not m:
                continue
            opname, dtype, dims, kind = m.groups()
            if opname.endswith(".done") or "-done" in line.split("=")[1][:40]:
                # async pairs: count the start only
                if opname in seen_started:
                    continue
            seen_started.add(opname)
            b = _shape_bytes(dtype, dims)
            # tuple shapes: sum all components
            lhs = line.split("=", 1)[1]
            if lhs.strip().startswith("("):
                b = sum(_shape_bytes(d, s) for d, s in
                        _TUPLE_SHAPE_RE.findall(lhs.split(")")[0]))
            gm = _GROUPS_BRACE_RE.search(line)
            if gm:
                n = len(gm.group(1).split(","))
            else:
                gi = _GROUPS_IOTA_RE.search(line)
                n = int(gi.group(2)) if gi else 2
            ops[kind] = ops.get(kind, 0.0) + w
            shape_bytes += w * b
            wb = w * b * _wire_factor(kind, n)
            wire += wb
            by_kind[kind] = by_kind.get(kind, 0.0) + wb
    return CollectiveStats(ops=ops, shape_bytes=shape_bytes,
                           wire_bytes=wire, by_kind=by_kind,
                           dot_flops=dot_flops)


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')
_LAYER_RE = re.compile(r"(?<![\w.])" + re.escape(LAYER_PREFIX)
                       + r"(" + "|".join(LAYERS) + r")(?![\w.])")


def op_layers(hlo: str) -> dict[str, str | None]:
    """``{instruction name: layer}`` over every computation of a compiled
    module's text (``compiled.as_text()``): the innermost ``layer.<name>``
    scope in the instruction's ``op_name`` metadata, whatever tags
    (``shoal.<op>#e<seq>``) or transforms surround it.  ``None`` where
    the metadata names no layer, or where there is none, as on the
    copies and fusions the compiler makes itself."""
    out: dict[str, str | None] = {}
    for line in hlo.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        op = _OP_NAME_RE.search(line)
        found = _LAYER_RE.findall(op.group(1)) if op else []
        out[m.group(1)] = found[-1] if found else None
    return out
