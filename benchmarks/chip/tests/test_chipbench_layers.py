"""Device time charged to the program's layer scopes (``layers.py``), on
hand-made events, on the traces recorded on a TPU v5e, and the layer
map rebuilt for a cell against the module its window runs; the device
layer's band rate and the copies of the solve loop's band carry."""

import gzip
import random
import re

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
    # the share of HBM's 819 GB/s it read as ``jacobi_roofline``
    "jacobi_band_gb_per_s": pytest.approx(98.50452597134652 / 100 * 819,
                                          rel=1e-9),
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


# each recorded trace, and the cell whose layout it has at 512x512
RECORDED = {"jacobi512": "jacobi-4096.1chip",
            "jacobi512x4": "jacobi-4096.4chip",
            "jacobi512x8": "jacobi-4096x8.1chip"}


def recorded_run(name):
    """A run of a recorded trace (3 calls of 8 iterations, 512x512) as
    the harness hands it to the readers, in the cell whose layout it has,
    with the module recorded beside it and its map (none for
    ``jacobi512``)."""
    from repro.launch.hlo_analysis import op_layers

    cell = h.harness.find_cell(RECORDED[name])
    run = h.harness.Run(cell=cell, seed=0,
                        peaks=h.harness.pk.peaks_for("TPU v5 lite"))
    run.calls = [(0.0, 1.0)] * 3
    run.work_per_call = {"iters": 8}
    run.trace = tr.load(gzip.open(TESTDATA / f"{name}.xplane.pb.gz").read())
    run.trace_window = tr.window(run.trace)
    run.details = {"n": 512, "kernels": cell.traffic["kernels"]}
    hlo = TESTDATA / f"{name}.hlo.txt.gz"
    run._module_text = (gzip.open(hlo, "rt").read() if hlo.is_file()
                        else None)
    run._layer_map = (op_layers(run._module_text)
                      if run._module_text is not None else None)
    return run


def test_readers_read_the_committed_trace_as_before():
    run = recorded_run("jacobi512")
    for name, value in JACOBI512_READINGS.items():
        assert h.harness.load_reader(name).read(run) == value, name
    b = tr.breakdown(run.trace, *run.trace_window)
    assert b == {"device_ops": JACOBI512_OPS, "idle_gaps": JACOBI512_GAPS}
    # no module text was recorded with it: the layer readers read nothing
    for name in LAYER_READERS + ("carry_copy_ms",):
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
    run = recorded_run("jacobi512x4")
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

    run = recorded_run("jacobi512x4")
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

    run = recorded_run("jacobi512x4")
    del run._layer_map, run._module_text
    cell = h.small_cell("jacobi-4096.4chip")
    run.cell = dataclasses.replace(cell, config={**cell.config,
                                                 "app": "no-such-app"})
    assert layers.module_text(run.cell.config, run.cell.traffic) is None
    for name in LAYER_READERS + ("carry_copy_ms",):
        assert h.harness.load_reader(name).read(run) is None
    assert "layer_ms_per_iter" not in run.details


# -- the device layer: the band's rate and the copies of its carry ------------

def test_band_bytes_per_chip():
    """An iteration reads and writes the chip's rows once, however many
    kernels share the chip: the 64-MiB grid twice on one kernel and on 8
    kernels of one chip, a quarter of it twice on each of 4 chips."""
    reader = h.harness.load_reader("jacobi_band_gb_per_s")
    moved = {}
    for name in RECORDED.values():
        cell = h.harness.find_cell(name)
        moved[name] = reader.bytes_per_chip(cell.config["n"], cell.chips,
                                            cell.config["dtype"])
    assert moved == {"jacobi-4096.1chip": 2 * 4096 * 4096 * 4,
                     "jacobi-4096.4chip": 2 * 1024 * 4096 * 4,
                     "jacobi-4096x8.1chip": 2 * 4096 * 4096 * 4}


def test_band_rate_counts_rows_per_chip():
    """On 8 kernels of one chip the rate counts the chip's 512 rows, not
    one kernel's 64; on 4 chips each chip's 128 rows, mean over chips."""
    reader = h.harness.load_reader("jacobi_band_gb_per_s")
    for name, rows in (("jacobi512x8", 512), ("jacobi512x4", 128)):
        run = recorded_run(name)
        busy = tr.busy_ns(run.trace, *run.trace_window)
        want = tr.mean({d: 2 * rows * 512 * 4 * 24 / b
                        for d, b in busy.items()})
        assert reader.read(run) == pytest.approx(want, rel=1e-12), name
    run.trace = None
    assert reader.read(run) is None


BAND_LOOP = """HloModule solve

%copy_computation (param_0: f32[8,128]) -> f32[1024] {
  %param_0 = f32[8,128]{1,0} parameter(0)
  %copy.1 = f32[8,128]{0,1} copy(%param_0)
  ROOT %bitcast.1 = f32[1024]{0} bitcast(%copy.1)
}

%body (p: (s32[], f32[8,128], f32[8])) -> (s32[], f32[8,128], f32[8]) {
  %p = (s32[], f32[8,128]{1,0}, f32[8]{0}) parameter(0)
  %gte.1 = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %gte.2 = f32[8]{0} get-tuple-element(%p), index=2
  %copy.2 = f32[8,128]{1,0:T(8,128)S(1)} copy(%gte.1)
  %copy_bitcast_fusion.3 = f32[1024]{0} fusion(%copy.2), kind=kLoop, calls=%copy_computation
  %copy-start.4 = (f32[8,128]{1,0}, f32[8,128]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%gte.1)
  %copy-done.4 = f32[8,128]{1,0} copy-done(%copy-start.4)
  %copy.5 = f32[8]{0} copy(%gte.2)
  %copy.6 = s32[1024]{0} copy(%bitcast.9)
  ROOT %tuple.1 = (s32[], f32[8,128]{1,0}, f32[8]{0}) tuple(%c, %copy-done.4, %copy.5)
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128]{1,0} parameter(0)
  %copy.7 = f32[8,128]{1,0:S(1)} copy(%a)
  %while.1 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)S(1)}, f32[8]{0}) while(%tuple.0), condition=%cond, body=%body
  ROOT %copy.8 = f32[8,128]{1,0} copy(%gte.9)
}
"""


def test_band_copies_are_the_solve_loops():
    """The copies of the band the solve loop carries, in the loop's body:
    a copy, a copy fusion of another shape with as many elements, an
    asynchronous copy's two halves; not a copy of a smaller array or of
    another type, and not the band's copies before and after the loop,
    once a solve."""
    reader = h.harness.load_reader("carry_copy_ms")
    assert reader.band_copies(BAND_LOOP) == {
        "copy.2", "copy_bitcast_fusion.3", "copy-start.4", "copy-done.4"}
    # a loop body that keeps the band in place copies none of it; a
    # module with no loop carries no band
    assert reader.band_copies(re.sub(r"\n  %copy[^\n]*", "",
                                     BAND_LOOP)) == set()
    assert reader.band_copies(BAND_LOOP.replace(" while(", " call(")
                              .replace("body=", "to_apply=")) == set()


@pytest.mark.parametrize("name,copies", [
    ("jacobi512x4", {"copy.152"}),
    ("jacobi512x8", {"copy.357", "copy.217"}),
])
def test_carry_copy_on_recorded_traces(name, copies):
    """In the modules recorded on a TPU v5e the solve loop copies its
    band each iteration (in ``S(1)``; on 8 kernels once more to another
    layout), and the copies took device time."""
    reader = h.harness.load_reader("carry_copy_ms")
    run = recorded_run(name)
    assert reader.band_copies(run._module_text) == copies
    assert reader.read(run) > 0


def test_carry_copy_reads_zero_without_a_band_copy():
    """A trace whose loop ran no copy of the band reads 0.0: the stencil,
    a halo-row copy in the loop and the band's copies before and after
    the loop take time, none of it the carry's.  No trace, no reading."""
    reader = h.harness.load_reader("carry_copy_ms")
    run = recorded_run("jacobi512x8")
    run.trace = tr.Trace({0: [ev("jacobi_step_pallas.17", 0, 50),
                              ev("copy.223", 50, 60),
                              ev("copy-done.5", 60, 80),
                              ev("copy-done.4", 80, 90)]},
                         [ev("bench.window", 0, 100)])
    run.trace_window = (0.0, 100.0)
    assert reader.read(run) == 0.0
    run.trace = None
    assert reader.read(run) is None
