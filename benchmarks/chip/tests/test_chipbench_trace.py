"""The reduction from a profiler trace to device metrics, on hand-made
events and on a small trace recorded on a TPU v5e."""

import pytest

import chipbench_helpers as h
import trace_reduce as tr


def ev(name, s, t):
    return tr.Event(name, float(s), float(t))


def make_trace():
    # device 0: a loop op [10, 50) holding two body ops, a kernel op, idle
    # gaps at [0, 10), [50, 60) and [80, 100); device 1 busy [0, 100)
    dev0 = [ev("while.3", 10, 50), ev("fusion.1", 12, 20),
            ev("fusion.2", 30, 45), ev("jacobi_step_pallas.9", 60, 80)]
    dev1 = [ev("collective-permute-start.1", 0, 100)]
    spans = [ev("bench.window", 0, 100), ev("bench.dispatch", 0, 55),
             ev("bench.block", 55, 100)]
    return tr.Trace({0: dev0, 1: dev1}, spans)


def test_union_gaps_and_self_time():
    t = make_trace()
    assert tr.window(t) == (0.0, 100.0)
    assert tr.busy_ns(t, 0, 100) == {0: 60.0, 1: 100.0}
    assert tr.gaps(t.devices[0], 0, 100) == [(0, 10), (50, 60), (80, 100)]
    own = {e.name: s for e, s in tr.self_times(t.devices[0])}
    assert own == {"while.3": 17.0, "fusion.1": 8.0, "fusion.2": 15.0,
                   "jacobi_step_pallas.9": 20.0}
    assert sum(own.values()) == tr.busy_ns(t, 0, 100)[0]
    assert tr.idle_pct(t, 0, 100) == pytest.approx(20.0)
    # clipping to a sub-window
    assert tr.busy_ns(t, 15, 35) == {0: 20.0, 1: 20.0}


def test_selected_and_counted_ops():
    t = make_trace()
    fusions = tr.self_ns_where(t, 0, 100, lambda n: n.startswith("fusion"))
    assert fusions == {0: 23.0, 1: 0}
    starts = tr.count_where(t, 0, 100, lambda n: n.startswith("collective"))
    assert starts == {0: 0, 1: 1}
    assert tr.op_name("%fusion.45 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "fusion.45"


def test_breakdown_names_ops_and_gaps():
    b = tr.breakdown(make_trace(), 0, 100)
    ops = dict(b["device_ops"])
    assert ops["collective-permute-start.1"] == pytest.approx(50e-9)
    assert ops["jacobi_step_pallas.9"] == pytest.approx(10e-9)
    gaps = b["idle_gaps"]
    assert gaps[0] == ["bench.block (device 0)", pytest.approx(20e-9)]
    assert ["bench.dispatch (device 0)", pytest.approx(10e-9)] in gaps


def test_no_device_no_idle_share():
    assert tr.idle_pct(tr.Trace({}, []), 0, 1) is None


# -- traces recorded on a TPU v5e by record_trace.py ---------------------------

def recorded(name):
    import gzip

    path = h.BENCH_DIR / "testdata" / f"{name}.xplane.pb.gz"
    return tr.load(gzip.open(path).read())


def test_recorded_jacobi_trace():
    t = recorded("jacobi512")
    lo, hi = tr.window(t)
    assert list(t.devices) == [0] and len(t.devices[0]) == 198
    assert hi - lo == 4671050.0
    assert tr.busy_ns(t, lo, hi) == {0: 62388.0}
    pallas = tr.self_ns_where(t, lo, hi,
                              lambda n: n.startswith("jacobi_step_pallas"))
    assert pallas == {0: 23800.0}
    # one solve of 8 iterations is one loop: three calls, three loops
    assert tr.count_where(t, lo, hi, lambda n: n.startswith("while")) == {0: 3}
    own = t.own(lo, hi)[0]
    assert sum(s for _, s in own) == pytest.approx(62388.0)
    b = tr.breakdown(t, lo, hi)
    assert b["device_ops"][0][0] == "jacobi_step_pallas.9"
    assert {g[0].split()[0] for g in b["idle_gaps"]} <= {
        "bench.dispatch", "bench.block", "host"}
