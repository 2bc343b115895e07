"""A traced rehearsal of `serve-brumby-streams`, through the real cluster
at toy size on the CPU: chunked prompts through the state arena, the
served tokens held to the plain reference, and the ring metrics that read
what the engine and the model's programs count printed under `rehearsal.*`
names; the device-trace metrics find no device plane and are left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("retention.dead_state_share", "engine.decode_step_ms",
                "engine.host_share", "engine.decode_blocked_share",
                "engine.prefill_ms_per_token", "engine.prefill_pad_share")


def test_traced_rehearsal_of_the_streams_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-brumby-streams", "--seed", "2147483659",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    metrics = last["metrics"]
    assert all(k.startswith("rehearsal.") for k in metrics)
    for name in RING_METRICS:
        assert metrics[f"rehearsal.{name}.streams"]["value"] >= 0.0, name
    assert 0 < metrics["rehearsal.retention.dead_state_share.streams"][
        "value"] < 100
    for name in ("retention.time_share", "retention.step_roofline",
                 "retention.chunk_roofline", "engine.decode_step_device_ms"):
        assert f"rehearsal.{name}.streams" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    # the numbers compared stand beside their limits, last on stderr
    assert out.stderr.strip().splitlines()[-1].startswith(
        "bench: reference argmax_share=")
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] > eng["prefills"] > 0      # some prompt was chunked
    assert eng["states_live"] == 0 and eng["states_free"] == 4
    assert eng["shared_pages"] == 0 and eng["state_arena_bytes"] > 0
    # the programs' compiled text names instructions under every scope,
    # though the CPU's trace has no device plane to charge them on
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 2
