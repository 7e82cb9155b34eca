"""One real dry-run cell end-to-end in a subprocess (512 host devices):
proves the production-mesh lowering path stays green in CI.  Uses the
fastest cell (xlstm decode)."""

import json
import os
import subprocess
import sys

from conftest import REPO


def test_dryrun_one_cell(tmp_path):
    out = tmp_path / "cell.jsonl"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"   # placeholder host devices only
    env.pop("XLA_FLAGS", None)   # dryrun sets its own device count
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "xlstm-350m", "--shape", "decode_32k",
         "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rec = json.loads(out.read_text().splitlines()[-1])
    assert rec["status"] == "ok"
    pd = rec["per_device"]
    assert pd["flops"] > 0
    assert pd["peak_bytes"] > 0
    assert rec["mesh"] == {"data": 16, "model": 16}
