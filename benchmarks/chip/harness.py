"""The on-chip benchmark harness.

Every piece of a cell is found by name, so a later change adds a cell,
a configuration, a traffic mix or a metric by adding files and
``BENCHMARK.json`` entries, never by editing one that exists:

* ``BENCHMARK.json`` at the checkout root names the cell, its
  configuration, its traffic and its metrics;
* ``configs/<file>`` (the configuration's ``file``) is JSON; its ``app``
  key names the module ``apps/<app>.py`` that builds and drives the
  program under test and checks what it produced;
* ``traffic/<traffic>.json`` holds the mix's parameters, read by
  ``traffic.py``;
* ``metrics/<metric>.py`` is a reader with ``read(run) -> float | None``.

A run: look for the chips, set up (build inputs from the seed, compile,
warm up: ``setup_s``), run the closed-loop window for ``seconds``, read
the peak memory, check what the window produced against the plain
reference, then print the metrics.  With ``trace=1`` a profiler window
takes the place of the timed one and the per-layer metrics are printed.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

import peaks as pk
import trace_reduce as tr

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
# each run's own numbers (per-chip values, counts), one file a run
RUNS_DIR = ROOT / ".chipbench"
# the profiler's window (it closes with the first call that ends after
# it): whole calls of every cell, and a trace small and quick to read
# (on four TPU v5e chips a 1-s window of jacobi-4096.4chip wrote 299 MB
# of trace and took 184 s to read)
TRACE_SECONDS = 0.25
# sampled calls whose results the check compares, besides the last
SAMPLE_CALLS = 8


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# -- finding a cell's pieces by name --------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces loaded."""

    name: str
    entry: dict
    config: dict
    traffic: dict
    app: object
    end_to_end: list
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: dict | None = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_benchmark() if bench is None else bench
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    entry = entries[0]
    conf = [c for c in bench["configs"] if c["name"] == entry["config"]][0]
    config = json.loads((bench_dir.parent.parent / conf["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{entry['traffic']}.json").read_text())
    app = _load_module(bench_dir / "apps" / f"{config['app']}.py",
                       f"chipbench_app_{config['app']}")
    return Cell(name, entry, config, traffic, app,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    return _load_module(bench_dir / "metrics" / f"{metric}.py",
                        f"chipbench_metric_{metric.replace('.', '_')}")


# -- the run ---------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a run measured; metric readers take their numbers from it."""

    cell: Cell
    seed: int
    peaks: dict
    setup_s: float = 0.0
    calls: list = dataclasses.field(default_factory=list)  # (t0, t1) s
    window_s: float = 0.0
    work_per_call: dict = dataclasses.field(default_factory=dict)
    trace: object = None            # trace_reduce.Trace of the window
    trace_window: tuple = ()        # (lo_ns, hi_ns) on the trace clock
    details: dict = dataclasses.field(default_factory=dict)

    @property
    def n_calls(self) -> int:
        return len(self.calls)

    def work(self, key: str) -> float:
        """Total of one kind of work over the window's calls."""
        return self.n_calls * self.work_per_call[key]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, unless the environment names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def find_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


class CompileCounter:
    """Counts the backend compiles, and the programs loaded from the
    persistent cache, that JAX reports while it is active."""

    active = None

    def __init__(self):
        self.count = 0

    def __enter__(self):
        import jax

        if CompileCounter.active is None:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
        CompileCounter.active = self
        return self

    def __exit__(self, *exc):
        CompileCounter.active = None

    @staticmethod
    def _listen(event: str, duration: float, **kw) -> None:
        if CompileCounter.active is not None and (
                "backend_compile" in event or "cache_retrieval" in event):
            CompileCounter.active.count += 1


def run_window(session, run: Run, seconds: float) -> None:
    """Closed loop, one caller: the next call is issued when the last
    one's result is ready.  The window ends with the first call that
    completes after ``seconds``."""
    with CompileCounter() as compiles:
        _calls(session, run, seconds)
    run.details["compiles_in_window"] = compiles.count


def _calls(session, run: Run, seconds: float) -> None:
    import jax

    calls = run.calls
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t1 = t_start
    while t1 < deadline:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out = session.call()
        with jax.profiler.TraceAnnotation("bench.block"):
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        calls.append((t0, t1))
        with jax.profiler.TraceAnnotation("bench.keep"):
            session.keep(out)
    run.window_s = t1 - t_start


def _traced_window(session, run: Run, seconds: float) -> None:
    import jax

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=profile_options())
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                run_window(session, run, seconds)
        finally:
            jax.profiler.stop_trace()
        files = list(Path(tmp).rglob("*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        run.details["trace_bytes"] = files[0].stat().st_size
        run.trace = tr.load(files[0])
        run.trace_window = tr.window(run.trace)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def set_up(cell: Cell, seed: int, t_setup: float):
    """Find the chips and set the cell up from the seed: ``(devices,
    session, run)``, with ``run.setup_s`` counted from ``t_setup``.
    Raises :class:`NoChip` or :class:`peaks.UnknownDevice` before any
    work."""
    devices = find_devices(cell.chips)
    run = Run(cell, seed, pk.peaks_for(devices[0].device_kind))
    session = cell.app.setup(cell.config, cell.traffic, seed, devices,
                             sample=SAMPLE_CALLS, rng=random.Random(seed))
    run.work_per_call = dict(session.work_per_call)
    run.setup_s = time.perf_counter() - t_setup
    return devices, session, run


def correct(checks: dict) -> bool:
    """Every number compared is within its limit."""
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None):
    """One run of ``cell``; returns the result line's dict, whose
    ``checks`` are the numbers compared with their limits.  The run's own
    numbers go to a file in :data:`RUNS_DIR`."""
    t_setup = time.perf_counter() if t_process is None else t_process
    devices, session, run = set_up(cell, seed, t_setup)
    dev = devices[0]

    failed = 0
    try:
        if trace:
            _traced_window(session, run, min(seconds, TRACE_SECONDS))
        else:
            run_window(session, run, seconds)
    except Exception as e:  # noqa: BLE001 - a failed call is a result
        failed = 1
        print(f"[chipbench] window failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    mem = memory_peak_bytes(devices)
    checks = {}
    if not failed:
        try:
            checks = session.check()
        except Exception as e:  # noqa: BLE001 - a check that fails is a result
            print(f"[chipbench] check failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            checks = {"check_errors": {"value": 1, "limit": 0}}
    run.details.update(session.details())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(m["name"]).read(run) if not failed else None
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct(checks), "attempted": run.n_calls + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        lo, hi = run.trace_window
        busy = tr.busy_ns(run.trace, lo, hi)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = tr.breakdown(run.trace, lo, hi)
        run.details["busy_s_per_device"] = {str(d): b * 1e-9
                                            for d, b in busy.items()}
    result["checks"] = checks
    _write_details(cell.name, seed, trace, run, result)
    return result


def _write_details(name, seed, trace, run, result) -> None:
    """Per-run numbers too long for the result line (per-chip values,
    counts) go to a file of their own."""
    try:
        RUNS_DIR.mkdir(parents=True, exist_ok=True)
        path = RUNS_DIR / f"{name}.seed{seed}.trace{int(trace)}.json"
        path.write_text(json.dumps({
            "setup_s": run.setup_s, "window_s": run.window_s,
            "calls": run.n_calls, "work_per_call": run.work_per_call,
            "details": run.details, "result": result}, indent=1,
            default=str))
    except OSError as e:
        print(f"[chipbench] could not write run details: {e}",
              file=sys.stderr)


def format_checks(checks: dict) -> list[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]
