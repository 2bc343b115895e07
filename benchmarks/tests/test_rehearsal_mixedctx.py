"""A traced rehearsal of `serve-commandaplus-mixedctx-loaded`, through the
real cluster at toy size on the CPU: chunked prompts through both page pools,
the served tokens held to the plain reference, and the ring metrics that
read what the engine and the model's programs count printed under
`rehearsal.*` names; the device-trace metrics find no device plane and are
left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("moe.load_max_over_mean", "cache.window_pages_share",
                "engine.chunk_blocked_share", "engine.prefill_ms_per_token",
                "engine.ttft_queue_share", "engine.host_share")


def test_traced_rehearsal_of_the_mixed_context_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-commandaplus-mixedctx-loaded",
         "--seed", "2147483659",
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
        assert metrics[f"rehearsal.{name}.mixedctx"]["value"] >= 0.0, name
    assert 0 < metrics["rehearsal.cache.window_pages_share.mixedctx"][
        "value"] < 100
    for name in ("moe.time_share", "moe.experts_roofline", "attn.time_share",
                 "engine.decode_step_device_ms"):
        assert f"rehearsal.{name}.mixedctx" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] > eng["prefills"] > 0      # some prompt was chunked
    assert eng["window_pages_returned"] > 0 and eng["shared_pages"] == 0
    # the programs' compiled text names instructions under every scope,
    # though the CPU's trace has no device plane to charge them on
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 2
