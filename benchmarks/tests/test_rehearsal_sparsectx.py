"""A traced rehearsal of `serve-dots3note-sparsectx`, through the real
cluster at toy size on the CPU: prompts of two to six chunks through BOTH
pools of latent pages (the full kind's with the indexer's rows, the sliding
kind's ring wrapping), every prompt past the toy `index_topk`, the served
tokens and the replayed logits held to the plain reference's own draw of
the weights, and the ring metrics that read what the programs count printed
under `rehearsal.*` names; the device-trace metrics find no device plane
and are left out."""

import json
import os
import subprocess
import sys

from benchmarks.lib import manifest


def test_traced_rehearsal_of_the_sparsectx_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "serve-dots3note-sparsectx", "--seed", "2147483659",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    metrics = last["metrics"]
    assert all(k.startswith("rehearsal.") for k in metrics)
    share = metrics["rehearsal.dsa.selected_share.sparsectx"]["value"]
    assert 0.0 < share < 100.0          # every prompt is past index_topk
    assert metrics["rehearsal.dsa.walked_over_selected.sparsectx"][
        "value"] >= 1.0
    # the engine's own split of a first token's wait
    assert 0.0 <= metrics["rehearsal.engine.ttft_queue_share.sparsectx"][
        "value"] < 100.0
    assert metrics["rehearsal.engine.prefill_ms_per_token.sparsectx"][
        "value"] > 0.0
    for name in ("dsa.index_time_share", "dsa.index_chunk_roofline",
                 "mla.time_share", "mla.chunk_roofline", "moe.time_share",
                 "attn.window_latent_roofline"):
        assert f"rehearsal.{name}.sparsectx" not in metrics
    checks = next(ln for ln in lines if ln.get("phase") == "checks")["checks"]
    assert all(checks.values()), checks
    ref = next(ln for ln in lines if ln.get("phase") == "serve.reference")
    # float32 on both sides, the reference's weights its own draw
    assert ref["argmax_share"] == 1.0 and ref["logit_rel_rms"] < 1e-5
    assert ref["replay_matches_served"] == 1.0 and ref["checked"] == 2
    # the reference's pieces stood before the engine's first request: the
    # loader started them and `start_cluster` waited (nothing of them
    # traces beside a request, where it starved the engine's thread)
    assert ref["pieces_built_ahead"] is True
    built = [ln for ln in lines if ln.get("phase") == "serve.reference_built"]
    assert len(built) == 1 and built[0]["waited_s"] >= 0.0
    setup = next(i for i, ln in enumerate(lines)
                 if ln.get("phase") == "serve.setup")
    assert lines.index(built[0]) < setup
    assert out.stderr.strip().splitlines()[-1].startswith(
        "bench: reference argmax_share=")
    window = next(ln for ln in lines if ln.get("phase") == "serve.window")
    eng = window["engine"]
    assert eng["chunks"] >= 2 * eng["prefills"] > 0   # every prompt chunked
    assert eng["shared_pages"] == 0 and eng["window_pages_returned"] > 0
    assert eng["dsa_keys_selected"] < eng["dsa_keys_visible"]
    assert eng["chunk_dsa_keys_selected"] < eng["chunk_dsa_keys_visible"]
    scopes = next(ln for ln in lines if ln.get("phase") == "serve.scopes")
    assert scopes["seconds_by_scope"] == {}
    assert min(scopes["instructions"]["jit_serve_step"]) > 20
    assert len(scopes["instructions"]["jit_serve_prefill"]) == 1
