"""A traced rehearsal of `serve-falconh1-chatburst`, through the real
cluster at toy size on the CPU: gamma arrivals, prompts of one to several
chunks through pages AND a state entry in every layer (both prefill
programs; steps by the gather / scatter body), the served tokens and the
replayed logits held to the plain reference's own draw of the weights and
its token-by-token recurrence, and the ring metrics that read what the
engine counts printed under `rehearsal.*` names; the device-trace metrics
find no device plane and are left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("cache.state_bytes_share", "engine.decode_step_ms",
                "engine.prefill_ms_per_token", "engine.prefill_pad_share",
                "engine.decode_blocked_share", "engine.host_share",
                "engine.dispatch_share", "engine.step_dispatch_ms",
                "engine.step_wait_ms", "engine.admit_iter_ms")


def test_traced_rehearsal_of_the_chatburst_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-falconh1-chatburst", "--seed", "2147483659",
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
        assert metrics[f"rehearsal.{name}.reasoning"]["value"] >= 0.0, name
    assert 0.0 < metrics["rehearsal.cache.state_bytes_share.reasoning"][
        "value"] < 100.0
    assert "rehearsal.engine.decode_step_device_ms.reasoning" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    ref = next(ln for ln in lines if ln.get("phase") == "serve.reference")
    # float32 on both sides, the reference's weights its own draw
    assert ref["argmax_share"] == 1.0 and ref["logit_rel_rms"] < 1e-4
    # the replayed entry's first layer against the recurrence's own state
    # at that position, and its bits
    assert 0.0 < ref["state_rel_rms"] < 1e-5
    assert ref["state_half_share"] < 0.01
    assert ref["replay_matches_served"] == 1.0 and ref["checked"] == 3
    # the numbers compared stand beside their limits, last on stderr
    assert out.stderr.strip().splitlines()[-1].startswith(
        "bench: reference argmax_share=")
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] >= eng["prefills"] > 0
    # a step's counters: the gather / scatter body moves every slot's
    # state, six... three layers' bytes each
    assert eng["ssd_live"] > 0 and eng["ssd_state_bytes"] == \
        eng["ssd_live"] * 3 * 4 * 4 * 16 * 32
    assert eng["states_live"] == 0 and eng["states_free"] == 4
    assert eng["state_arena_bytes"] > 0 and eng["free_pages"] == 64
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 2
    assert not [ln for ln in lines if ln.get("phase") == "serve.layers"]
