"""A traced rehearsal of `serve-deepseekv3-longctx`, through the real
cluster at toy size on the CPU: prompts of two to six chunks through the
latent pages (chunks expanded, steps absorbed), the served tokens and the
replayed logits held to the plain reference's own draw of the weights, and
the ring metrics that read what the engine and the model's programs count
printed under `rehearsal.*` names; the device-trace metrics find no device
plane and are left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("moe.load_max_over_mean", "engine.decode_step_ms",
                "engine.prefill_ms_per_token", "engine.prefill_share",
                "engine.prefill_pad_share", "engine.ttft_queue_share",
                "engine.chunk_blocked_share", "engine.decode_blocked_share",
                "engine.host_share", "engine.dispatch_share",
                "engine.step_dispatch_ms", "engine.step_wait_ms",
                "engine.admit_iter_ms", "router.hop_p50_ms")


def test_traced_rehearsal_of_the_longctx_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-deepseekv3-longctx", "--seed", "2147483659",
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
        assert metrics[f"rehearsal.{name}.longctx"]["value"] >= 0.0, name
    for name in ("mla.time_share", "mla.step_roofline", "mla.chunk_roofline",
                 "moe.time_share", "moe.experts_roofline",
                 "engine.decode_step_device_ms"):
        assert f"rehearsal.{name}.longctx" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    ref = next(ln for ln in lines if ln.get("phase") == "serve.reference")
    # float32 on both sides, the reference's weights its own draw
    assert ref["argmax_share"] == 1.0 and ref["logit_rel_rms"] < 1e-5
    assert ref["replay_matches_served"] == 1.0 and ref["checked"] == 2
    # the numbers compared stand beside their limits, last on stderr
    assert out.stderr.strip().splitlines()[-1].startswith(
        "bench: reference argmax_share=")
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] >= 2 * eng["prefills"] > 0   # every prompt chunked
    assert eng["shared_pages"] == 0 and eng["free_pages"] == 64
    # the programs' compiled text names instructions under every scope,
    # though the CPU's trace has no device plane to charge them on
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 1
