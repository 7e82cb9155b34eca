"""The harness: the contract of BENCHMARK.json, finding a cell's pieces
by name, adding a cell by files alone, the peaks table, and refusing to
run without a chip or without the program."""

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

import chipbench_helpers as h
import harness
import peaks

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1:] == ["benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert os.path.getsize(h.ROOT / "BENCHMARK.json") <= 64 * 1024


def test_configs_and_cells_follow_the_contract():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert (h.ROOT / c["file"]).is_file()
        assert c["name"] in {w["config"] for w in BENCH["workloads"]}
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
    for cell in CELLS:
        found = harness.find_cell(cell)
        names = {m["name"] for m in found.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert found.per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_cell_pieces_found_by_name(cell):
    found = harness.find_cell(cell)
    assert found.name == cell and callable(found.app.setup)
    assert found.traffic["kind"] == "solves"
    for m in found.end_to_end + found.per_layer:
        assert callable(harness.load_reader(m["name"]).read)


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new traffic mix, cell and per-layer metric need only new files
    and new BENCHMARK.json entries."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(h.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    (bench_dir / "traffic" / "solves-4grids.json").write_text(json.dumps(
        dict(harness.find_cell("jacobi-4096.1chip").traffic, grids=4)))
    (bench_dir / "metrics" / "calls_in_window.py").write_text(
        "def read(run):\n    return run.n_calls\n")
    bench["workloads"].append({"name": "jacobi-4096.4grids",
                               "config": "jacobi-4096",
                               "traffic": "solves-4grids", "chips": 1,
                               "why": "more distinct grids"})
    bench["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "jacobi_iter_ms",
                               "workloads": ["jacobi-4096.4grids"]})
    bench["end_to_end"][0]["workloads"].append("jacobi-4096.4grids")
    cell = harness.find_cell("jacobi-4096.4grids", bench, bench_dir)
    assert cell.traffic == {**harness.find_cell("jacobi-4096.1chip").traffic,
                            "grids": 4}
    assert [m["name"] for m in cell.end_to_end] == ["jacobi_iter_ms",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["calls_in_window"]
    reader = harness.load_reader("calls_in_window", bench_dir)
    run = harness.Run(cell, 0, {}, calls=[(0, 1), (1, 2)])
    assert reader.read(run) == 2


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_Bps"] == 819e9
    assert peaks.SOURCE
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "jacobi-4096.1chip", "--seed", str(h.SEED), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    return proc.returncode != 0 and not any(
        line.startswith("{") for line in proc.stdout.splitlines())


def test_no_chip_no_result():
    proc = _run(h.ROOT)
    assert _no_result(proc), (proc.returncode, proc.stdout, proc.stderr)
    assert "no TPU" in proc.stderr


def test_no_program_no_result(tmp_path):
    shutil.copy(h.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(h.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert _no_result(proc), (proc.returncode, proc.stdout, proc.stderr)


def test_seed_words_take_any_whole_number():
    import traffic

    for seed in (0, 1, 2**31 + 7, 2**40, -3):
        w = traffic.seed_words(seed, 2)
        assert w.shape == (2,) and math.isfinite(float(w[0]))
    assert (traffic.seed_words(2**31 + 7) != traffic.seed_words(7)).any()


def test_reservoir_sample_is_seeded_and_keeps_the_last_call():
    import random

    from sample import Reservoir

    def draw(seed):
        r = Reservoir(8, random.Random(seed))
        for i in range(500):
            r.offer(i, i)
        return r.chosen()
    a = draw(7)
    assert a == draw(7) and a != draw(8)
    assert len(a) == 9 and 499 in a and all(k == v for k, v in a.items())
