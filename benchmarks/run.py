"""Drive all benchmarks; print ``name,us_per_call,derived`` CSV and
write machine-readable ``BENCH_comm.json`` next to the repo root.

Comm/Jacobi benchmarks need a multi-device host platform, so each runs
in its own subprocess with XLA_FLAGS=...device_count=8 (the main process
keeps the single real device, and the production 512-device mesh exists
only inside dry-run processes).  The roofline section is only emitted if
a dry-run results file exists.

``BENCH_comm.json`` is the perf trajectory across PRs: for every bench
the measured ``us_per_call``, and for the comm-layer benches
(``benchmarks/bench_comm.py``) additionally the ``collective-permute``
count parsed out of the compiled HLO.  The
``baseline_pre_fused_wire`` section is frozen — it records the
measurements taken immediately *before* the fused single-packet wire
format landed — while ``current`` is overwritten by every run, so any
future regression is visible as a diff against both.

``--smoke`` is the fast pre-merge mode driven by ``scripts/ci_check.sh``:
it runs only ``bench_comm`` (with ``BENCH_SMOKE=1``, few timing iters,
no big Jacobi grid), asserts every comm row's collective-permute budget
including the mailbox messages-per-collective floor, then runs
``scripts/comm_lint.py`` (shoal-lint, both passes) over every
registered entry point — failing on any finding — and merges the
analyzer wall-time + HLO budget table into ``BENCH_comm.json`` under
``current.comm_lint`` (the comm/benches/baseline sections are left
untouched).

``--faults`` is the loss-resilience mode: it runs
``bench_faults`` (the 0/1/5%-drop goodput sweep over the reliable-put
protocol), asserts every drop rate still delivers bit-identical data
with a drained dedup ledger, gates the 1%-drop retransmit cost and
goodput against the ``[faults]`` section of ``comm_budgets.toml``, and
merges the rows into ``BENCH_comm.json`` under ``current.faults``.

``--serving`` is the disaggregated-serving smoke mode: it runs
``bench_serving`` (mixed prefill/decode arrival trace through the
admission front-end), asserts the KV-migration collective budget, the
bounded admission-queue depth and a nonzero sustained tokens/s, and
merges the rows into ``BENCH_comm.json`` under ``current.serving``.
"""

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH_JSON = os.path.join(REPO, "BENCH_comm.json")

SUBPROCESS_BENCHES = [
    ("benchmarks.bench_comm", 8),
    ("benchmarks.bench_latency", 8),
    ("benchmarks.bench_throughput", 8),
    ("benchmarks.bench_jacobi", 8),
]
INPROCESS_BENCHES = ["benchmarks.bench_utilization"]

_ROW_RE = re.compile(r"^([\w/.+-]+),(-?[\d.]+),(.*)$")


def run_sub(mod: str, devices: int, extra_env=None):
    env = dict(os.environ)
    # CPU emulation: a child must never try to take an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run([sys.executable, "-m", mod], env=env,
                          capture_output=True, text=True, cwd=REPO)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stdout.write(f"{mod},FAILED,rc={proc.returncode}\n")
        sys.stderr.write(proc.stderr[-2000:] + "\n")
    return proc.returncode, proc.stdout


def parse_rows(stdout: str):
    rows = []
    for line in stdout.splitlines():
        m = _ROW_RE.match(line.strip())
        if m:
            rows.append((m.group(1), float(m.group(2)), m.group(3)))
    return rows


def write_bench_json(rows) -> None:
    """Merge this run into BENCH_comm.json, preserving the frozen
    pre-fused-wire baseline section.

    Merge means MERGE: rows update ``current.comm``/``current.benches``
    key-by-key and every other ``current`` sub-section (``comm_lint``,
    ``serving``) is left alone — a partial run must not wipe sections it
    did not produce (that was exactly the stray-diff noise of PR 7's
    bench-only commit).  Key order is canonicalized by ``sort_keys`` so
    reruns with identical numbers are byte-identical.
    """
    doc = {"schema": "bench_comm/v1"}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            sys.stderr.write(
                f"WARNING: existing {BENCH_JSON} unreadable ({e}); "
                "restore it from git or the frozen pre-fused-wire "
                "baseline will be re-seeded from THIS run's numbers\n")
    comm, benches = {}, {}
    for name, us, derived in rows:
        if name.startswith("comm/"):
            # bench_comm's derived column is the HLO collective-permute
            # count of the compiled program
            comm[name] = {"us_per_call": us,
                          "collective_permutes": float(derived)}
        else:
            benches[name] = {"us_per_call": us, "derived": derived}
    cur = doc.setdefault("current", {})
    cur.setdefault("comm", {}).update(comm)
    cur.setdefault("benches", {}).update(benches)
    if "baseline_pre_fused_wire" not in doc:
        sys.stderr.write(
            "WARNING: BENCH_comm.json had no baseline_pre_fused_wire "
            "section; seeding it from this (post-fused-wire) run. The "
            "true pre-change numbers live in git history.\n")
        doc["baseline_pre_fused_wire"] = comm
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.relpath(BENCH_JSON, REPO)} "
          f"({len(comm)} comm rows, {len(benches)} bench rows)")


# collective-permute ceilings per comm bench row: the measured HLO count
# must not exceed these, or the fused-wire / mailbox aggregation has
# regressed.  (floor) rows assert the value is AT LEAST the budget.
SMOKE_BUDGETS = {
    "comm/put_long/acked/1seg": 2.0,
    "comm/put_long/acked/4seg": 2.0,
    "comm/put_long/async/1seg": 1.0,
    "comm/put_long/async/4seg": 1.0,
    "comm/get_medium/acked/4seg": 2.0,
    "comm/mailbox/1k-4word-sends": 2.0,
    # the one-collective-steady-state gate: data packets only, acks
    # piggybacked on the next iteration's reverse-link packet
    "comm/jacobi-steady/per-iter": 2.0,
}
SMOKE_FLOORS = {
    "mailbox/msgs-per-collective": 512.0,
}


def smoke() -> None:
    print("name,us_per_call,derived")
    code, out = run_sub("benchmarks.bench_comm", 8,
                        extra_env={"BENCH_SMOKE": "1"})
    if code:
        raise SystemExit(f"bench_comm failed (rc={code})")
    rows = {name: (us, derived) for name, us, derived in parse_rows(out)}
    failures = []
    for name, budget in SMOKE_BUDGETS.items():
        if name not in rows:
            failures.append(f"{name}: row missing from bench output")
            continue
        us, derived = rows[name]
        cps = float(derived.split()[0]) if derived else float("nan")
        if not cps <= budget:
            failures.append(f"{name}: {cps:.0f} collective-permutes "
                            f"> budget {budget:.0f}")
    for name, floor in SMOKE_FLOORS.items():
        if name not in rows:
            failures.append(f"{name}: row missing from bench output")
            continue
        us, _ = rows[name]
        if not us >= floor:
            failures.append(f"{name}: {us:.1f} < floor {floor:.1f}")
    if failures:
        for f in failures:
            print(f"SMOKE_FAIL {f}")
        raise SystemExit(1)
    lint = run_comm_lint()
    print(f"SMOKE_OK ({len(SMOKE_BUDGETS)} collective budgets, "
          f"{len(SMOKE_FLOORS)} aggregation floors, "
          f"{len(lint['entries'])} lint entries in "
          f"{lint['total_wall_time_s']:.1f}s)")


def run_comm_lint() -> dict:
    """Run scripts/comm_lint.py (both analyzer passes over every
    registered entry point) in a subprocess, fail the smoke on findings,
    and merge the analyzer wall-time + HLO budget table into
    BENCH_comm.json under ``current.comm_lint`` (other sections and the
    frozen baseline are left untouched)."""
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tmp:
        path = tmp.name
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "comm_lint.py"),
             "--json", path],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=900)
        sys.stdout.write(proc.stdout)
        if proc.returncode:
            sys.stderr.write(proc.stderr[-4000:])
            raise SystemExit(
                f"SMOKE_FAIL shoal-lint found issues (rc={proc.returncode})")
        with open(path) as f:
            lint = json.load(f)
    finally:
        os.unlink(path)
    # Wall-clock times vary run to run; keep them out of the committed
    # JSON so a re-run with identical analyzer results diffs clean.  The
    # full doc (times included) is still returned for the SMOKE_OK line.
    stable = {"entries": {
        name: {k: v for k, v in entry.items() if k != "wall_time_s"}
        for name, entry in lint.get("entries", {}).items()}}
    doc = {"schema": "bench_comm/v1"}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            doc = json.load(f)
    doc.setdefault("current", {})["comm_lint"] = stable
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return lint


# --serving gates: the KV migration's collective budget (1 fused
# vectored packet + 1 coalesced reply) and the admission bound
SERVING_CP_BUDGETS = {
    "comm/kv-migrate/vectored-lane": 2.0,
}


def serving() -> None:
    """Disaggregated-serving smoke: run the mixed-arrival trace bench,
    assert the migration collective budget / bounded queue depth /
    nonzero sustained throughput, and merge the rows into
    BENCH_comm.json under ``current.serving`` (the comm/benches/baseline
    sections are left untouched)."""
    print("name,us_per_call,derived")
    code, out = run_sub("benchmarks.bench_serving", 4,
                        extra_env={"BENCH_SMOKE": "1"})
    if code:
        raise SystemExit(f"bench_serving failed (rc={code})")
    rows = {name: (us, derived) for name, us, derived in parse_rows(out)}
    failures = []
    for name, budget in SERVING_CP_BUDGETS.items():
        if name not in rows:
            failures.append(f"{name}: row missing from bench output")
            continue
        cps = float(rows[name][1].split()[0])
        if not cps <= budget:
            failures.append(f"{name}: {cps:.0f} collective-permutes "
                            f"> budget {budget:.0f}")
    tps = rows.get("serving/mixed-trace/tokens-per-s")
    if tps is None:
        failures.append("serving/mixed-trace/tokens-per-s: row missing")
    elif not tps[0] > 0:
        failures.append(f"tokens-per-s: {tps[0]} not > 0")
    depth = rows.get("serving/mixed-trace/peak-queue-depth")
    if depth is None:
        failures.append("serving/mixed-trace/peak-queue-depth: row missing")
    else:
        bound = float(depth[1].split("=")[1])
        if not depth[0] <= bound:
            failures.append(f"peak-queue-depth: {depth[0]:.0f} "
                            f"> admission bound {bound:.0f}")
    if failures:
        for f in failures:
            print(f"SERVING_FAIL {f}")
        raise SystemExit(1)
    doc = {"schema": "bench_comm/v1"}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            doc = json.load(f)
    doc.setdefault("current", {})["serving"] = {
        name: {"value": us, "derived": derived}
        for name, (us, derived) in rows.items()}
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"SERVING_OK ({len(rows)} rows merged into "
          f"{os.path.relpath(BENCH_JSON, REPO)})")


def _load_fault_budgets() -> dict:
    """The [faults] section of comm_budgets.toml (gates for --faults)."""
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.analysis.hlo_budget import load_budgets
    return load_budgets().get("faults", {})


def faults() -> None:
    """Loss-resilience smoke: run the 0/1/5%-drop goodput sweep, assert
    delivery stayed correct at every rate, gate the 1%-drop retransmit
    cost + goodput against comm_budgets.toml [faults], and merge the
    rows into BENCH_comm.json under ``current.faults`` (other sections
    and the frozen baseline are left untouched)."""
    print("name,value,derived")
    code, out = run_sub("benchmarks.bench_faults", 8)
    if code:
        raise SystemExit(f"bench_faults failed (rc={code})")
    rows = {name: (us, derived) for name, us, derived in parse_rows(out)}
    budgets = _load_fault_budgets()
    failures = []
    for pct in ("0pct", "1pct", "5pct"):
        ok = rows.get(f"faults/delivered-ok/{pct}")
        if ok is None:
            failures.append(f"faults/delivered-ok/{pct}: row missing")
        elif ok[0] != 1.0:
            failures.append(
                f"faults/delivered-ok/{pct}: delivery broke under loss "
                "(not bit-identical / ledger not drained / retries "
                "exhausted)")
    rounds = rows.get("faults/retransmit-rounds/1pct")
    cap = float(budgets.get("retransmit_rounds_at_1pct_max", 0.5))
    if rounds is None:
        failures.append("faults/retransmit-rounds/1pct: row missing")
    elif not rounds[0] <= cap:
        failures.append(f"retransmit-rounds at 1%: {rounds[0]:.3f} "
                        f"> budget {cap}")
    good = rows.get("faults/goodput/1pct")
    floor = float(budgets.get("goodput_at_1pct_min", 0.0))
    if good is None:
        failures.append("faults/goodput/1pct: row missing")
    elif not good[0] >= floor:
        failures.append(f"goodput at 1%: {good[0]:.3f} < floor {floor}")
    if failures:
        for f in failures:
            print(f"FAULTS_FAIL {f}")
        raise SystemExit(1)
    doc = {"schema": "bench_comm/v1"}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            doc = json.load(f)
    doc.setdefault("current", {})["faults"] = {
        name: {"value": us, "derived": derived}
        for name, (us, derived) in rows.items()}
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"FAULTS_OK ({len(rows)} rows merged into "
          f"{os.path.relpath(BENCH_JSON, REPO)}; retransmit-rounds "
          f"{rounds[0]:.3f} <= {cap}, goodput {good[0]:.3f} >= {floor})")


def main() -> None:
    if "--smoke" in sys.argv[1:]:
        smoke()
        return
    if "--faults" in sys.argv[1:]:
        faults()
        return
    if "--serving" in sys.argv[1:]:
        serving()
        return
    print("name,us_per_call,derived")
    rc = 0
    rows = []
    for mod, devs in SUBPROCESS_BENCHES:
        code, out = run_sub(mod, devs)
        rc |= code
        rows.extend(parse_rows(out))
    for mod in INPROCESS_BENCHES:
        code, out = run_sub(mod, 1)
        rc |= code
        rows.extend(parse_rows(out))
    results = os.path.join(REPO, "dryrun_results.jsonl")
    if os.path.exists(results):
        code, out = run_sub("benchmarks.roofline", 1)
        rc |= code
        rows.extend(parse_rows(out))
    else:
        print("roofline,SKIPPED,no dryrun_results.jsonl (run "
              "scripts/run_dryrun_sweep.sh)")
    write_bench_json(rows)
    if rc:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
