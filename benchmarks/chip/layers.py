"""Charge a traced window's device time to the program's layer scopes.

The program wraps each layer of its comm path and its compute step in a
``layer.<name>`` named scope (``repro.analysis.trace.layer``), which
lands in the compiled module's ``op_name`` metadata;
``repro.launch.hlo_analysis.op_layers`` maps each instruction to its
layer.  A TPU trace names each op by its instruction alone, so the map
of the module the window ran turns the trace into layers:

* an op whose metadata names no layer (the compiler's own copies and
  some of its fusions carry none) takes the layer of the innermost
  enclosing op that has one, or else counts as :data:`UNSCOPED`;
* an op the map does not know counts as :data:`UNMAPPED`: a map of
  another program than the one traced.  Where that passes
  :data:`UNMAPPED_LIMIT` of a chip's busy time, nothing is read.

The map is the executed module's: the harness frees the program after
the window, so :func:`module` has the app build its program again from
the cell's configuration and compile it for the window's devices and
shapes (a hit in the compile cache), and :func:`op_layers` maps it.
Each app that has layer readers brings that rebuild as
``apps/<app>_module.py``, found by name as apps and metrics are; this
module names no app.  A program without the map (no ``op_layers``, or
no layer scopes) gives no reading, nor does an app without a rebuild.
"""

from __future__ import annotations

import importlib.util
from collections import defaultdict
from pathlib import Path

import trace_reduce as tr

APPS_DIR = Path(__file__).resolve().parent / "apps"

UNSCOPED = "unscoped"
UNMAPPED = "unmapped"
UNMAPPED_LIMIT = 0.01


def self_ns_by_layer(trace: tr.Trace, lo: float, hi: float,
                     op_layers: dict) -> dict[int, dict[str, float]]:
    """Per device: self time inside ``[lo, hi)`` per layer, with
    :data:`UNSCOPED` and :data:`UNMAPPED`; computed once per window and
    map (kept on the trace)."""
    cache = vars(trace).setdefault("_by_layer", {})
    key = (lo, hi, id(op_layers))
    if key not in cache or cache[key][0] is not op_layers:
        cache[key] = (op_layers, {d: _by_layer(evs, op_layers)
                                  for d, evs in trace.own(lo, hi).items()})
    return cache[key][1]


def _by_layer(own, op_layers: dict) -> dict[str, float]:
    per: dict[str, float] = defaultdict(float)
    stack: list[tuple[float, str | None]] = []  # (end, layer inherited)
    for e, ns in own:
        while stack and stack[-1][0] <= e.start_ns:
            stack.pop()
        outer = stack[-1][1] if stack else None
        if e.name not in op_layers:
            name, inherit = UNMAPPED, outer
        else:
            inherit = op_layers[e.name] or outer
            name = inherit or UNSCOPED
        per[name] += ns
        stack.append((e.end_ns, inherit))
    return dict(per)


def layer_ns(trace: tr.Trace, lo: float, hi: float, op_layers: dict | None,
             layer: str) -> dict[int, float] | None:
    """Per device: self time of ``layer`` inside ``[lo, hi)``; None where
    the trace has no device, where there is no map or it names no layer
    at all, or where the unmapped share of a chip's busy time (the sum
    of its self times) passes :data:`UNMAPPED_LIMIT`."""
    if not trace.devices or not op_layers or not any(op_layers.values()):
        return None
    per = self_ns_by_layer(trace, lo, hi, op_layers)
    if any(p.get(UNMAPPED, 0.0) > UNMAPPED_LIMIT * sum(p.values())
           for p in per.values()):
        return None
    return {d: p.get(layer, 0.0) for d, p in per.items()}


def module_text(config: dict, traffic: dict) -> str | None:
    """The compiled text of the module a cell's window runs, from the
    app's ``apps/<app>_module.py`` (``module_text(config, traffic)``);
    None for an app without one."""
    path = APPS_DIR / f"{config['app']}_module.py"
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(
        f"chipbench_module_{config['app']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.module_text(config, traffic)


def module(run) -> str | None:
    """The compiled text of the module the run's window executed, once
    per run (kept on the run); None for an app without a rebuild."""
    if "_module_text" not in vars(run):
        run._module_text = module_text(run.cell.config, run.cell.traffic)
    return run._module_text


def op_layers(run) -> dict | None:
    """The layer map of the module the run's window executed, once per
    run (kept on the run); None where the program under test has no
    map."""
    if "_layer_map" not in vars(run):
        from repro.launch import hlo_analysis

        parse = getattr(hlo_analysis, "op_layers", None)
        text = module(run) if parse is not None else None
        run._layer_map = parse(text) if text is not None else None
    return run._layer_map


def read(run, layer: str) -> float | None:
    """A layer's self time an iteration (mean over the chips), in ms.
    Writes every layer's, ``unscoped`` and ``unmapped`` included, to the
    run's file (``layer_ms_per_iter``)."""
    if not run.trace or not run.trace.devices:
        return None
    lo, hi = run.trace_window
    layers = op_layers(run)
    if not layers:
        return None
    iters = run.work("iters")
    per = self_ns_by_layer(run.trace, lo, hi, layers)
    names = sorted({k for p in per.values() for k in p})
    run.details["layer_ms_per_iter"] = {
        k: tr.mean({d: p.get(k, 0.0) for d, p in per.items()}) * 1e-6 / iters
        for k in names}
    per_dev = layer_ns(run.trace, lo, hi, layers, layer)
    return None if per_dev is None else tr.mean(per_dev) * 1e-6 / iters
