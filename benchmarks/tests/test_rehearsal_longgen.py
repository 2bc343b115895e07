"""A traced rehearsal of `serve-phi4flash-longgen-loaded`, through the
real cluster at toy size on the CPU: prompts of two to six chunks through full
pages, a ring of window pages AND a state entry (chunks through the scan,
steps by the gather / scatter body, the cross-decoder on a prompt's last
chunk alone), the served tokens and the replayed logits held to the plain
reference's own draw of the weights and its token-by-token recurrence, and
the ring metrics that read what the engine and the model's programs count
printed under `rehearsal.*` names; the device-trace metrics find no device
plane and are left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("prefill.cross_rows_share", "cache.state_bytes_share",
                "cache.window_pages_share", "engine.decode_step_ms",
                "engine.prefill_ms_per_token", "engine.prefill_pad_share",
                "engine.decode_blocked_share", "engine.host_share",
                "engine.dispatch_share", "engine.step_dispatch_ms",
                "engine.step_wait_ms", "engine.admit_iter_ms")


def test_traced_rehearsal_of_the_longgen_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-phi4flash-longgen-loaded",
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
        assert metrics[f"rehearsal.{name}.longgen"]["value"] >= 0.0, name
    for name in ("cache.state_bytes_share", "cache.window_pages_share"):
        assert 0.0 < metrics[f"rehearsal.{name}.longgen"]["value"] < 100.0
    for name in ("mamba.time_share", "mamba.step_roofline",
                 "mamba.chunk_roofline", "attn.shared_kv_time_share",
                 "attn.shared_kv_roofline", "attn.window_time_share",
                 "engine.decode_step_device_ms"):
        assert f"rehearsal.{name}.longgen" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    ref = next(ln for ln in lines if ln.get("phase") == "serve.reference")
    # float32 on both sides, the reference's weights its own draw
    assert ref["argmax_share"] == 1.0 and ref["logit_rel_rms"] < 1e-4
    # the replayed entry's first Mamba layer against the recurrence's own
    # state at that position, and its bits
    assert 0.0 < ref["state_rel_rms"] < 1e-5
    assert ref["state_half_share"] < 0.01
    assert ref["replay_matches_served"] == 1.0 and ref["checked"] == 2
    # the numbers compared stand beside their limits, last on stderr
    assert out.stderr.strip().splitlines()[-1].startswith(
        "bench: reference argmax_share=")
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] >= 2 * eng["prefills"] > 0   # every prompt chunked
    # the cross-decoder ran on ONE row of every prompt's last chunk and on
    # nothing else of a prefill: its share of the prefill's rows
    assert eng["chunk_cross_rows"] == eng["prefills"]
    share = metrics["rehearsal.prefill.cross_rows_share.longgen"]["value"]
    assert abs(share - 100.0 * eng["prefills"] / eng["prefill_tokens"]) < 1.0
    assert eng["shared_pages"] == 0 and eng["free_pages"] == 64
    assert eng["states_live"] == 0 and eng["states_free"] == 4
    assert eng["state_arena_bytes"] > 0 and eng["window_pages_returned"] > 0
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 1
