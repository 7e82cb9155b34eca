"""The cell of several Shoal kernels on one chip
(``jacobi-4096x8.1chip``): its entries, the check on the CPU at a small
size (sound runs correct; the control and a run with the halo exchange
left out not correct), a program that cannot place kernels together
refused at set-up, the layer map rebuilt for it, and the trace of 8
kernels on one TPU v5e read by layer."""

import dataclasses
import random

import pytest

import chipbench_helpers as h
import harness
import layers
import trace_reduce as tr

NAME = "jacobi-4096x8.1chip"


def small_cell() -> harness.Cell:
    """The cell at a CPU test's size: 64x64 over its 8 kernels (8 rows a
    band, the Pallas stencil's least), 8 iterations a solve."""
    cell = harness.find_cell(NAME)
    return dataclasses.replace(cell, config={**cell.config, "n": 64,
                                             "iters_per_solve": 8})


def test_cell_entries():
    cell = harness.find_cell(NAME)
    assert cell.chips == 1 and cell.config["app"] == "jacobi_colocated"
    assert cell.traffic["kernels"] == cell.config["kernels"] == 8
    assert cell.traffic["kernels_per_chip"] == cell.config["kernels_per_chip"]
    per_layer = {m["name"] for m in cell.per_layer}
    assert per_layer == {"stencil_kernel_ms", "stencil_ms", "am_ingress_ms",
                         "am_egress_ms", "am_local_ms",
                         "device_idle_pct.jacobi", "jacobi_band_gb_per_s",
                         "carry_copy_ms"}
    assert {m["name"] for m in cell.end_to_end} == {"jacobi_iter_ms",
                                                    "setup_s"}


def test_sound_run_is_correct_over_every_boundary():
    r = h.run_small(NAME, cell=small_cell())
    assert r["correct"], r["checks"]
    assert r["checks"]["grid_max_abs_err"]["value"] == 0.0
    assert r["checks"]["halo_max_abs_err"]["value"] == 0.0


def test_control_and_a_missing_exchange_are_not_correct(monkeypatch):
    import control

    with h.on_cpu():
        prog, ctrl, calls = control.readings(small_cell(), h.SEED, 0.3)
    assert calls >= 1 and harness.correct(prog), prog
    assert not harness.correct(ctrl), ctrl

    from repro.apps.jacobi import JacobiApp
    monkeypatch.setattr(JacobiApp, "_halo_exchange",
                        lambda self, st, block, it=None: st)
    r = h.run_small(NAME, cell=small_cell())
    assert not r["correct"], r["checks"]


def test_one_kernel_per_chip_program_is_refused(monkeypatch):
    """A program whose ``JacobiApp`` takes no ``chips`` (one kernel per
    chip) fails at set-up, before any window."""
    import repro.apps.jacobi as jacobi_app

    @dataclasses.dataclass
    class OneEach:
        n: int
        kernels: int
        iters: int

    monkeypatch.setattr(jacobi_app, "JacobiApp", OneEach)
    cell = small_cell()
    with pytest.raises(NotImplementedError, match="one Shoal kernel per"):
        with h.on_cpu():
            harness.set_up(cell, h.SEED, 0.0)


def test_rebuilt_map_is_the_window_modules():
    import jax

    from repro.launch.hlo_analysis import op_layers

    cell = small_cell()
    s = cell.app.setup(cell.config, cell.traffic, h.SEED, jax.devices()[:1],
                       sample=2, rng=random.Random(h.SEED))
    s.call()
    ran = op_layers(s.fn.lower(s.st, s.blocks[1]).compile().as_text())
    rebuilt = op_layers(layers.module_text(cell.config, cell.traffic))
    assert rebuilt == ran
    assert {"compute", "egress", "local", "ingress", "sync"} <= set(
        rebuilt.values())
    # at 64 words a halo row is one packet: 7 boundaries, 2 directions
    assert s.details()["links_per_iter"] == {
        "LOCAL": {"packets": 14, "bytes": 14 * (16 + 64) * 4}}


# -- the trace recorded on a TPU v5e --------------------------------------------

def recorded_run():
    """``jacobi512x8`` (512x512, 8 kernels on one chip, 8 iterations a
    call, 3 calls; ``record_colocated_trace.py``) as the harness hands
    it to the readers, with the map of the module that ran."""
    from test_chipbench_layers import recorded_run as recorded

    return recorded("jacobi512x8")


def test_recorded_colocated_trace_by_layer():
    run = recorded_run()
    t, (lo, hi) = run.trace, run.trace_window
    assert list(t.devices) == [0]
    per = layers.self_ns_by_layer(t, lo, hi, run._layer_map)[0]
    assert per.get(layers.UNMAPPED, 0.0) == 0.0
    # one chip: nothing crosses a link
    assert "wire" not in per
    assert tr.count_where(t, lo, hi, lambda n: n.startswith(
        ("collective-permute", "all-reduce"))) == {0: 0}
    for name in ("am_local_ms", "am_ingress_ms", "am_egress_ms",
                 "stencil_ms", "stencil_kernel_ms"):
        value = h.harness.load_reader(name).read(run)
        assert value is not None and value > 0, name
    assert sum(run.details["layer_ms_per_iter"].values()) == pytest.approx(
        tr.mean(tr.busy_ns(t, lo, hi)) * 1e-6 / run.work("iters"), rel=1e-4)


def test_recorded_trace_counts_local_packets():
    """The program the trace recorded carries 14 LOCAL halo packets an
    iteration, one per 2-KiB row at a 9000-byte MTU over 7 boundaries
    both ways, and none over ICI."""
    from repro.apps.jacobi import JacobiApp

    links = JacobiApp(n=512, kernels=8, iters=8, use_pallas=True,
                      interpret=True, chips=1).links_per_iteration()
    assert links["LOCAL"]["packets"] == 14 and "ICI" not in links


def test_no_local_reading_without_the_local_scope():
    """The trace of 4 kernels, one a chip, has no ``local`` scope: its
    ``am_local_ms`` reads nothing."""
    from test_chipbench_layers import recorded_run as four_kernels

    run = four_kernels("jacobi512x4")
    assert h.harness.load_reader("am_local_ms").read(run) is None
