"""A traced rehearsal of the serve cell (the real cluster, toy size, the
CPU): the metrics that read the engine's launch stamps are printed, under
`rehearsal.*` names as every CPU number is, and hold together — a share
below the whole, and a step's two parts inside the step."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

NEW = ("engine.dispatch_share.chat", "engine.step_dispatch_ms.chat",
       "engine.step_wait_ms.chat")


def test_traced_serve_rehearsal_prints_the_launch_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-large-chat-loaded", "--seed", "3141592653",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["failed"] == 0
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in metrics)
    share, dispatch, wait = (metrics["rehearsal." + n] for n in NEW)
    assert 0 <= share < 100
    assert 0 < dispatch and 0 < wait
    # medians of nested spans of the same records
    assert dispatch + wait <= metrics["rehearsal.engine.decode_step_ms.chat"]
