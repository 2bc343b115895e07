"""A traced rehearsal of the serve cell, through the real cluster at toy
size on the CPU: the ring metrics that read what the engine records of
itself are printed (under `rehearsal.*` names, as every CPU number is);
the device-trace metric finds no device plane and is left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("engine.ttft_queue_share.chat",
                "engine.prefill_ms_per_token.chat",
                "engine.prefill_pad_share.chat",
                "engine.decode_blocked_share.chat", "engine.host_share.chat")


def test_traced_serve_rehearsal_prints_the_ring_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-large-chat-loaded", "--seed", "2147483659",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    metrics = last["metrics"]
    assert all(k.startswith("rehearsal.") for k in metrics)
    for name in RING_METRICS:
        v = metrics["rehearsal." + name]["value"]
        assert 0.0 <= v < (100.0 if name.endswith("share.chat") else 1e4), name
    assert metrics["rehearsal.engine.prefill_pad_share.chat"]["value"] > 0
    # the toy's max_total is one block of keys: every step reads all of it
    assert metrics["rehearsal.model.kv_read_share.chat"]["value"] == 100.0
    assert "rehearsal.engine.decode_step_device_ms.chat" not in metrics
    checks = [json.loads(ln) for ln in out.stdout.splitlines()
              if ln.startswith('{"phase": "checks"')][-1]["checks"]
    assert checks["no_compile_in_window"], checks
    assert checks["every_request_full_length"], checks
