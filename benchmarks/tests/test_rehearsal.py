"""`run.py --rehearse` is a fixture, not a measurement: numbers only under
`rehearsal.*` names and `correct` false, whatever the checks said."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest


def test_rehearsal_prints_no_device_metric():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "train-medium-1024", "--seed", "2147483659",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is False
    assert last["metrics"] and all(k.startswith("rehearsal.")
                                   for k in last["metrics"])
    assert last["device"]["platform"] == "cpu"
    checks = [json.loads(ln) for ln in out.stdout.splitlines()
              if ln.startswith('{"phase": "checks"')][-1]["checks"]
    assert all(checks.values()), checks
