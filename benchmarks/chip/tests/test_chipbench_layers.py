"""Device time charged to the program's layer scopes (``layers.py``), on
hand-made events, on the two traces recorded on a TPU v5e, and the layer
map rebuilt for a cell against the module its window runs."""

import gzip
import random

import pytest

import chipbench_helpers as h
import layers
import trace_reduce as tr

TESTDATA = h.BENCH_DIR / "testdata"


def ev(name, s, t):
    return tr.Event(name, float(s), float(t))


def layered_trace():
    # device 0: a solve loop (no layer) holding an ingress loop, whose
    # body ops have no metadata, a stencil fusion, a compiler copy and an
    # op the map does not know; device 1 busy [0, 100) in one collective
    dev0 = [ev("while.1", 0, 100), ev("while.2", 10, 50),
            ev("copy.3", 12, 20), ev("fusion.4", 30, 45),
            ev("fusion.5", 60, 80), ev("copy.6", 80, 90),
            ev("stray.7", 90, 92)]
    dev1 = [ev("collective-permute-start.1", 0, 100)]
    return tr.Trace({0: dev0, 1: dev1}, [ev("bench.window", 0, 100)])


LAYERS = {"while.1": None, "while.2": "ingress", "copy.3": None,
          "fusion.4": "egress", "fusion.5": "compute", "copy.6": None,
          "collective-permute-start.1": "wire"}


def test_self_time_by_layer_inherits_the_enclosing_layer():
    t = layered_trace()
    per = layers.self_ns_by_layer(t, 0, 100, LAYERS)
    # while.2's own 17 + copy.3's 8 inherited; fusion.4 keeps its own
    # layer inside the ingress loop; copy.6 under the layerless solve
    # loop and while.1's own time are unscoped; stray.7 is unmapped
    assert per[0] == {"unscoped": 28.0 + 10.0, "ingress": 17.0 + 8.0,
                      "egress": 15.0, "compute": 20.0, "unmapped": 2.0}
    assert sum(per[0].values()) == tr.busy_ns(t, 0, 100)[0]
    assert per[1] == {"wire": 100.0}
    # clipped to a sub-window, the same rule
    assert layers.self_ns_by_layer(t, 15, 35, LAYERS)[0] == {
        "unscoped": 0.0, "ingress": 5.0 + 10.0, "egress": 5.0}
    # another map on the same window is not served from the first's table
    assert layers.self_ns_by_layer(t, 0, 100, {})[1] == {"unmapped": 100.0}


def test_layer_reading_needs_a_map_that_fits():
    t = layered_trace()
    # stray.7 is 2 of device 0's 100 busy ns unmapped: over 1%
    assert layers.layer_ns(t, 0, 100, LAYERS, "compute") is None
    assert layers.layer_ns(t, 0, 100, {**LAYERS, "stray.7": None},
                           "compute") == {0: 20.0, 1: 0.0}
    # a program without layer scopes, no map, no device: nothing read
    assert layers.layer_ns(t, 0, 100, dict.fromkeys(LAYERS),
                           "compute") is None
    assert layers.layer_ns(t, 0, 100, None, "compute") is None
    assert layers.layer_ns(tr.Trace({}, []), 0, 1, LAYERS, "compute") is None


# -- the traces recorded on a TPU v5e ------------------------------------------

# every reader and the breakdown on ``jacobi512``, as the code before the
# layer readers read them
JACOBI512_READINGS = {
    "stencil_kernel_ms": 0.0009916666666666665,
    "jacobi_collective_ms": None,
    "jacobi_roofline": 98.50452597134652,
    "device_idle_pct.jacobi": 98.66436882499652,
}
JACOBI512_OPS = [
    ["jacobi_step_pallas.9", 2.3800000000000003e-05],
    ["fusion.2", 1.0522e-05],
    ["pad.6", 6.3880000000000005e-06],
    ["copy-done.1", 5.3620000000000005e-06],
    ["copy.34", 5.0070000000000005e-06],
    ["pad.7", 4.989e-06],
    ["copy.27", 7.140000000000001e-07],
    ["copy.30", 6.92e-07],
    ["copy.32", 6.92e-07],
    ["copy.28", 6.91e-07],
]
JACOBI512_GAPS = [
    ["bench.block (device 0)", 0.001543717],
    ["bench.block (device 0)", 0.0012748450000000001],
    ["bench.block (device 0)", 0.0009397530000000001],
    ["bench.dispatch (device 0)", 0.0008434810000000001],
    ["bench.dispatch (device 0)", 1.955e-06],
    ["bench.dispatch (device 0)", 1.9540000000000003e-06],
    ["bench.dispatch (device 0)", 1.8110000000000001e-06],
    ["bench.dispatch (device 0)", 3.53e-07],
    ["bench.dispatch (device 0)", 3.5200000000000003e-07],
    ["bench.dispatch (device 0)", 3.5200000000000003e-07],
]
LAYER_READERS = ("stencil_ms", "am_ingress_ms", "am_egress_ms")


def recorded_run(name, kernels):
    """A run of a recorded trace (3 calls of 8 iterations, 512x512) as
    the harness hands it to the readers, with the map of the module
    recorded beside it (none for ``jacobi512``)."""
    from repro.launch.hlo_analysis import op_layers

    run = h.harness.Run(cell=None, seed=0,
                        peaks=h.harness.pk.peaks_for("TPU v5 lite"))
    run.calls = [(0.0, 1.0)] * 3
    run.work_per_call = {"iters": 8}
    run.trace = tr.load(gzip.open(TESTDATA / f"{name}.xplane.pb.gz").read())
    run.trace_window = tr.window(run.trace)
    run.details = {"n": 512, "kernels": kernels}
    hlo = TESTDATA / f"{name}.hlo.txt.gz"
    run._layer_map = (op_layers(gzip.open(hlo, "rt").read())
                      if hlo.is_file() else None)
    return run


def test_readers_read_the_committed_trace_as_before():
    run = recorded_run("jacobi512", kernels=1)
    for name, value in JACOBI512_READINGS.items():
        assert h.harness.load_reader(name).read(run) == value, name
    b = tr.breakdown(run.trace, *run.trace_window)
    assert b == {"device_ops": JACOBI512_OPS, "idle_gaps": JACOBI512_GAPS}
    # no module text was recorded with it: the layer readers read nothing
    for name in LAYER_READERS:
        assert h.harness.load_reader(name).read(run) is None
    assert "layer_ms_per_iter" not in run.details


def opcodes(hlo: str) -> dict:
    """``{instruction: opcode}`` of a compiled module's text."""
    import re

    instr = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
    return {m.group(1): m.group(2) for line in hlo.splitlines()
            if (m := instr.match(line))}


def test_recorded_four_kernel_trace_by_layer():
    """``jacobi512x4`` (4 kernels, Pallas, ``record_layers_trace.py``)
    with the module that ran: the map covers the trace, and the ``wire``
    scope is the collectives found by opcode, the barrier's all-reduce
    (which the compiler names ``psum_invariant.N``) included.  The module
    text was recorded when the ``compute`` scope was named ``stencil``;
    its 25 ``op_name`` entries were renamed to match, metadata only."""
    run = recorded_run("jacobi512x4", kernels=4)
    t, (lo, hi) = run.trace, run.trace_window
    assert list(t.devices) == [0, 1, 2, 3]
    per = layers.self_ns_by_layer(t, lo, hi, run._layer_map)
    busy = tr.busy_ns(t, lo, hi)
    ops = opcodes(gzip.open(TESTDATA / "jacobi512x4.hlo.txt.gz",
                            "rt").read())
    coll = {k for k, op in ops.items() if op.startswith(
        ("all-reduce", "collective-permute", "all-gather", "reduce-scatter",
         "all-to-all"))}
    assert any(ops[k] == "all-reduce" and k.startswith("psum_invariant")
               for k in coll)
    by_opcode = tr.self_ns_where(t, lo, hi, lambda n: n in coll)
    for d in t.devices:
        # the trace's clock rounds to the ns: a body op can overhang its
        # loop by one
        assert sum(per[d].values()) == pytest.approx(busy[d], abs=10)
        assert busy[d] - per[d].get(layers.UNMAPPED, 0.0) >= 0.99 * busy[d]
        assert set(per[d]) >= {"compute", "egress", "wire", "ingress",
                               "sync"}
        assert per[d]["wire"] == pytest.approx(by_opcode[d], rel=0.05)
    for name in LAYER_READERS:
        value = h.harness.load_reader(name).read(run)
        assert value is not None and value > 0, name
    table = run.details["layer_ms_per_iter"]
    assert set(table) >= {"compute", "ingress", "egress", "wire", "unscoped"}
    assert sum(table.values()) == pytest.approx(
        tr.mean(busy) * 1e-6 / run.work("iters"), rel=1e-4)
    # a map of another program leaves ops unmapped: nothing is read
    other = {k: v for k, v in run._layer_map.items()
             if not k.startswith("copy")}
    assert layers.layer_ns(t, lo, hi, other, "compute") is None


# -- the map of the module a cell's window runs --------------------------------

def test_rebuilt_map_is_the_window_modules():
    """The map ``layers`` rebuilds from the cell's configuration is the
    map of the module the app's session runs, before and after its
    state has been through a call (one kernel here; four in
    ``chipbench_layers_four.py``)."""
    import jax

    from repro.launch.hlo_analysis import op_layers

    cell = h.small_cell("jacobi-4096.1chip")
    s = cell.app.setup(cell.config, cell.traffic, h.SEED, jax.devices()[:1],
                       sample=2, rng=random.Random(h.SEED))
    first = op_layers(s.fn.lower(s.st, s.blocks[0]).compile().as_text())
    s.call()
    later = op_layers(s.fn.lower(s.st, s.blocks[1]).compile().as_text())
    rebuilt = op_layers(layers.module_text(cell.config, cell.traffic))
    assert rebuilt == first == later
    assert {"compute", "sync"} <= set(rebuilt.values())


def test_rebuilt_map_on_four_kernels():
    out = h.run_script(h.BENCH_DIR / "tests" / "chipbench_layers_four.py",
                       n_devices=4)
    assert "four-kernel layer map matches" in out, out


def test_no_map_without_the_programs_parser(monkeypatch):
    """A program without ``hlo_analysis.op_layers`` (the parent of the
    layer scopes) gives no map and no reading."""
    from repro.launch import hlo_analysis

    run = recorded_run("jacobi512x4", kernels=4)
    monkeypatch.delattr(hlo_analysis, "op_layers")
    del run._layer_map
    run.cell = h.small_cell("jacobi-4096.4chip")
    assert layers.op_layers(run) is None
    for name in LAYER_READERS:
        assert h.harness.load_reader(name).read(run) is None


def test_no_map_for_an_app_without_a_rebuild():
    """The rebuild is the app's own ``apps/<app>_module.py``, found by
    the app's name: an app that brings none gives no map, and its layer
    readers read nothing."""
    import dataclasses

    run = recorded_run("jacobi512x4", kernels=4)
    del run._layer_map
    cell = h.small_cell("jacobi-4096.4chip")
    run.cell = dataclasses.replace(cell, config={**cell.config,
                                                 "app": "no-such-app"})
    assert layers.module_text(run.cell.config, run.cell.traffic) is None
    for name in LAYER_READERS:
        assert h.harness.load_reader(name).read(run) is None
    assert "layer_ms_per_iter" not in run.details
