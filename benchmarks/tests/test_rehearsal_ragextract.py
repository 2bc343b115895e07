"""A traced rehearsal of `serve-lfm2moe-ragextract`, through the real
cluster at toy size on the CPU: Poisson arrivals, prompts of one to
several chunks through pages (the attention layer) AND a tail entry (the
conv layers), every expert held, the served tokens and the replayed logits
held to the plain reference's own draw of the weights, the replayed
entry's tails to the reference's last two rows of z, and the ring metrics
that read what the engine counts printed under `rehearsal.*` names; the
device-trace metrics find no device plane and are left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest

RING_METRICS = ("cache.state_bytes_share", "engine.decode_step_ms",
                "engine.prefill_ms_per_token", "engine.prefill_pad_share",
                "engine.decode_blocked_share", "engine.host_share",
                "engine.dispatch_share", "engine.step_dispatch_ms",
                "engine.step_wait_ms", "engine.admit_iter_ms",
                "moe.reads_per_touched")


def test_traced_rehearsal_of_the_ragextract_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-lfm2moe-ragextract", "--seed", "2147483659",
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
    # the tails are a sliver of a slot's cache: pages are the rest
    assert 0.0 < metrics["rehearsal.cache.state_bytes_share.reasoning"][
        "value"] < 50.0
    # no trip of products reads an expert twice at these sizes
    assert metrics["rehearsal.moe.reads_per_touched.reasoning"][
        "value"] == 1.0
    for name in ("engine.decode_step_device_ms", "moe.time_share",
                 "moe.experts_roofline"):
        assert f"rehearsal.{name}.reasoning" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    assert set(checks) >= {"served_tokens_are_reference_argmax",
                           "served_tokens_within_reference_margin",
                           "program_logits_near_reference",
                           "program_tails_near_reference",
                           "first_attention_keys_near_reference",
                           "keys_behind_experts_near_reference",
                           "every_pair_computed"}
    ref = next(ln for ln in lines if ln.get("phase") == "serve.reference")
    # float32 on both sides, the reference's weights its own draw
    assert ref["argmax_share"] == 1.0 and ref["logit_rel_rms"] < 1e-4
    # the replayed entry's tails against the reference's own rows of z,
    # its pages' key rows against the reference's keys
    assert 0.0 < ref["tail_rel_rms"] <= ref["tail_rel_rms_deepest"] < 1e-4
    assert 0.0 < ref["second_keys_q25"] <= ref["second_keys_median"] < 1e-4
    assert 0.0 < ref["first_keys_max"] < 1e-4
    assert ref["replay_matches_served"] == 1.0 and ref["checked"] == 3
    # the numbers compared stand beside their limits, last on stderr
    assert out.stderr.strip().splitlines()[-1].startswith(
        "bench: reference argmax_share=")
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] >= eng["prefills"] > 0
    # every pair is held: top-2 a live row and expert layer (4 of them)
    assert eng["moe_pairs"] > 0 and eng["moe_reads"] == eng["moe_touched"]
    assert eng["conv_live"] > 0
    assert eng["states_live"] == 0 and eng["states_free"] == 4
    assert eng["state_arena_bytes"] > 0 and eng["free_pages"] == 64
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 2
    assert not [ln for ln in lines if ln.get("phase") == "serve.layers"]
