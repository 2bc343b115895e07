"""Driver for traffic of kind `serve_open_chatburst`: the open loop of
`serve_open` (arrivals on a schedule fixed by the traffic file, each
request timed from when it was DUE) in front of a replica that serves a
`falcon_h1` (Falcon-H1) configuration — hundreds of short chat turns a
window that arrive in BURSTS (gamma gaps), prompts of a couple of hundred
tokens through one or two prefill programs, answers of a hundred or two,
dozens of streams at once: a slot holds pages AND a state entry in every
layer, and below two thousand positions the entry is the larger part.

`serve_open_longgen`'s driver with what is wired to the model exchanged:
the model imported before the cluster starts, the loader and the replica
class (drivers/replica_falcon_h1.py), the rehearsal's sizes and the
reference's shape (lib/falconh1cfg.py), a warm-up that runs BOTH prefill
programs.  The window — arrivals to its last second, the streams still
running then CUT and no failures — is `serve_open_reasoning`'s (`window`,
imported, with the sample's choice `pick_sample`); the client side of a
request, the thread pool and the cluster's end are `_serve`'s.
`start_cluster`, `finish` and `run` are written out again: `finish`
imports its model's `reference_shape` by name and names its checks'
limits, `run` calls its module's `start_cluster` and `finish` by name, and
the harness's drivers take no such argument.

A traced run also prints `serve.layers`: the readings of the metric files
under benchmarks/metrics/ that this cell's kernels have and BENCHMARK.json
has no room to list (LAYER_METRICS: `per_layer` stands at the contract's
128 entries), by their own readers.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from typing import Dict, List

from . import _common as C
from . import _serve as S
from .serve_open_reasoning import pick_sample, window

LAYER_METRICS = ("ssd.time_share.chatburst", "ssd.step_roofline.chatburst",
                 "ssd.chunk_roofline.chatburst", "attn.time_share.chatburst",
                 "engine.admits_per_iter.chatburst")


def _rehearsal(ctx: Dict):
    """A rehearsal (test fixture, CPU) runs this model at its own toy
    sizes: `tests/rehearsal_chatburst.json` over the cell's files."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "tests", "rehearsal_chatburst.json")) as f:
        toy = json.load(f)
    ctx["config"].update(toy["config"])
    ctx["traffic"].update(toy["traffic"])
    return toy["engine_kwargs"]


def start_cluster(ctx: Dict):
    """`_serve.start_cluster`'s sequence with this model's replica.  The
    model is imported BEFORE the cluster starts: a program that lacks it
    ends here, at once, with nothing to stop."""
    import ray_tpu.models.falcon_h1  # noqa: F401

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve._deployment import deployment

    from benchmarks.lib.falconh1cfg import reference_shape

    from . import replica_falcon_h1 as rep

    toy = _rehearsal(ctx) if ctx["rehearse"] else None
    conf = ctx["config"]
    # the reference's programs compile beside the weights' draw (a thread
    # of the replica's loader, waited for there; set-up, not the window,
    # pays for them)
    reference = (reference_shape(conf), ctx["traffic"]["reference"],
                 int(ctx["traffic"]["output_len"]["max"]),
                 conf.get("weights", {}), ctx["seed"])
    sv = dict(conf["serve"])
    ek = dict(sv["engine_kwargs"])
    if ctx["rehearse"]:
        os.environ["RAY_TPU_NUM_CHIPS"] = "1"
        ek.update(toy)
    else:
        from ray_tpu._private.accelerators import num_tpu_chips

        if num_tpu_chips() < ctx["chips"]:    # counted without touching jax
            C.fail(f"the cell needs {ctx['chips']} chip(s), this machine "
                   f"shows {num_tpu_chips()}", 3)
    # traced or not: on an empty compile cache the compiler's host work
    # keeps the replica from answering a probe for seconds (PR 51)
    os.environ.setdefault("RAY_TPU_SERVE_HEALTH_CHECK_TIMEOUT_S", "600")
    ray_tpu.init()
    dep = deployment(rep.FalconH1Server, name="LLMServer",
                     ray_actor_options={"resources": {"TPU": 1}},
                     max_ongoing_requests=512)
    h = serve.run(
        dep.bind(params_loader=rep.make_loader(conf, ctx["seed"], {},
                                               reference),
                 max_seq=sv["max_seq"], engine=sv["engine"],
                 engine_kwargs=ek),
        name=S.APP, route_prefix=None, blocking_timeout_s=900)
    return h, ek


def warm_up(handle, ek: Dict, traffic: Dict, vocab: int) -> Dict:
    """Every program this traffic uses, once: a prompt of a chunk and a
    short tail runs `serve.prefill:<chunk>` from a start of 0 and
    `serve.prefill:<bucket>` from a later one, then `serve.setrow` and
    `serve.step`; where the tail's program is the chunk's own (a
    rehearsal's sizes) it is one program.  The requests are greedy, so no
    key program exists."""
    import numpy as np

    chunk, bucket = int(ek["prefill_chunk"]), int(ek["prefill_bucket"])
    n = chunk + bucket // 2
    hi = min(vocab, int(traffic["token_id_max"]))
    t0 = time.time()
    r = S.Request(-1, {"tokens": np.random.default_rng(0).integers(
        0, hi, n).tolist(), "max_new_tokens": 4}, time.time())
    S.send(handle, r)
    if r.error:
        C.fail(f"warm-up request of {n} tokens failed: {r.error}")
    return {"prompts": [n], "seconds": time.time() - t0}


def _layer_readings(ctx: Dict, result: Dict) -> Dict:
    """LAYER_METRICS by their readers, from what this run observed."""
    from benchmarks.lib import manifest
    from benchmarks.trace import reduce as R

    obs = dict(result, trace=R.reduce_trace(ctx["trace_dir"]))
    rctx = {"config": ctx["config"], "traffic": ctx["traffic"],
            "chips": ctx["chips"], "device": result["device"]}
    out = {}
    for name in LAYER_METRICS:
        with open(os.path.join(manifest.BENCH_DIR, "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            "benchmarks.metrics.readers." + spec["reader"])
        out[name] = reader.read(obs, spec.get("params", {}), rctx)
    return out


def finish(ctx: Dict, handle, reqs: List[S.Request], w_start: float,
           w_end: float, snap0: Dict, cut=frozenset()) -> Dict:
    """`serve_open_longgen.finish` with this model's reference shape: a
    request in `cut` (its stream ended by the window's end) is no failure
    and no sample of the reference's — only a request that ran to its end
    is held to it — and of every request the result keeps the tokens that
    arrived INSIDE the window, so that `itl_p99_ms` pools the window's
    gaps and none of the cut's."""
    from benchmarks.lib.falconh1cfg import reference_shape
    from benchmarks.lib.stats import percentile as pct

    t_fin = time.time()
    spec = ctx["traffic"]["reference"]
    snap1 = handle.bench_snapshot.remote(True).result(timeout_s=180)
    ident = snap1["identity"]
    if not ctx["rehearse"] and ident["platform"] != "tpu":
        C.fail(f"the replica computed on {ident['platform']!r}", 3)
    compiled = {n: c - snap0["counts"].get(n, 0)
                for n, c in snap1["counts"].items()
                if c != snap0["counts"].get(n, 0)}
    off = snap1["wall"] - snap1["perf"]
    ring = [dict(r, ts=r["ts"] + off) for r in snap1["ring"]
            if w_start <= r["ts"] + off <= w_end]
    failed = [r for r in reqs if r.error and r.rid not in cut]
    ok = [r for r in reqs if not r.error]
    sample = pick_sample(ctx, ok)
    ref = {"argmax_share": 0.0, "worst_gap": float("inf"),
           "logit_rel_rms": float("inf"), "state_half_share": 1.0,
           "state_rel_rms": float("inf"), "per_request": []}
    if sample:
        ref = list(handle.options(stream=True).bench_reference.remote(
            sample, reference_shape(ctx["config"]), spec,
            int(ctx["traffic"]["output_len"]["max"]),
            ctx["config"].get("weights", {}), ctx["seed"]))[-1]
    scopes = None
    if ctx["trace"]:            # device seconds by named scope (trace/scopes)
        from benchmarks.trace.scopes import scope_seconds

        programs = list(handle.options(
            stream=True).bench_program_scopes.remote())[-1]
        try:
            scopes = scope_seconds(ctx["trace_dir"], programs)
        except FileNotFoundError:           # a run that wrote no trace
            scopes = None
        C.say(phase="serve.scopes", seconds_by_scope=scopes,
              instructions={k: [len(m) for m in v]
                            for k, v in programs.items()})
    checks = {"no_compile_in_window": not compiled,
              "every_request_full_length": not failed,
              "requests_completed": len(ok) > 0,
              "served_tokens_are_reference_argmax":
                  ref["argmax_share"] >= spec["min_argmax_share"],
              "served_tokens_within_reference_margin":
                  ref["worst_gap"] <= spec["logit_margin"],
              "program_logits_near_reference":
                  ref["logit_rel_rms"] <= spec["max_logit_rel_rms"],
              "program_state_near_reference":
                  ref["state_rel_rms"] <= spec["max_state_rel_rms"],
              "state_kept_in_float32":
                  ref["state_half_share"] <= spec["max_state_half_share"]}
    C.say(phase="serve.reference", margin=spec["logit_margin"],
          min_argmax_share=spec["min_argmax_share"], **ref)
    inside = {r.rid: [t for t in r.times if t <= w_end] for r in reqs}
    ttft = [1000.0 * (r.times[0] - r.due) for r in reqs if inside[r.rid]]
    itl = [1000.0 * (b - a) for r in reqs
           for a, b in zip(inside[r.rid], inside[r.rid][1:])]
    if ttft and itl:         # context for choosing percentiles, not results
        C.say(phase="serve.tails", requests=len(ttft), gaps=len(itl),
              ttft_ms={p: pct(ttft, p) for p in (50, 75, 90, 99)},
              itl_ms={p: pct(itl, p) for p in (50, 95, 98, 99, 99.5)},
              after_window_s=time.time() - w_end,
              snapshot_and_reference_s=time.time() - t_fin)
    C.say(phase="serve.window", attempted=len(reqs), failed=len(failed),
          completed=len(ok), cut_at_window_end=len(cut),
          errors=sorted({r.error for r in failed})[:5],
          compiled_in_window=compiled, checks=checks,
          engine=snap1["engine"], ring_iterations=len(ring),
          replica_init_s=snap1["init_wall"][1] - snap1["init_wall"][0],
          persistent_cache=snap1["persistent_cache"],
          compile_s=snap1["compile_s"])
    # the numbers compared, each beside its limit: the last line of stderr
    print(f"bench: reference argmax_share={ref['argmax_share']:.4f} "
          f"(at least {spec['min_argmax_share']}) worst_gap="
          f"{ref['worst_gap']:.4f} (at most {spec['logit_margin']}) "
          f"logit_rel_rms={ref['logit_rel_rms']:.5f} (at most "
          f"{spec['max_logit_rel_rms']}) state_rel_rms="
          f"{ref['state_rel_rms']:.6f} (at most "
          f"{spec['max_state_rel_rms']}) state_half_share="
          f"{ref['state_half_share']:.4f} (at most "
          f"{spec['max_state_half_share']}) failed={len(failed)} (0) "
          f"compiled_in_window={len(compiled)} (0)",
          file=sys.stderr, flush=True)
    stamps = {int(k): v for k, v in snap1["stamps"].items()}
    result = {
        "kind": "serve",
        "device": {**ident, "memory_peak_bytes": snap1["memory_peak_bytes"]},
        "correct": all(checks.values()), "checks": checks,
        "attempted": len(reqs), "failed": len(failed),
        "setup_s": w_start - ctx["t0"], "window_s": w_end - w_start,
        "window": [w_start, w_end],
        "serve": {"requests": [dict(r.record(), replica=stamps.get(r.rid),
                                    times=inside[r.rid],
                                    error=None if r.rid in cut else r.error,
                                    cut=r.rid in cut)
                               for r in reqs],
                  "ring": ring, "max_slots": snap1["max_slots"],
                  "traced": ctx.get("traced"), "scopes": scopes,
                  "timeout_ms": 1000.0 * (S.REQUEST_TIMEOUT_S
                                          + (w_end - w_start))},
    }
    if scopes:
        C.say(phase="serve.layers", **_layer_readings(ctx, result))
    return result


def run(ctx: Dict) -> Dict:
    handle, ek = start_cluster(ctx)
    try:
        traffic = ctx["traffic"]
        seconds = float(ctx["seconds"])
        if ctx["trace"]:   # a traced run is shorter: its trace is cut and
            seconds = min(seconds, float(traffic["traced_window_seconds"]))
        warm = warm_up(handle, ek, traffic, ctx["config"]["vocab_size"])
        C.say(phase="serve.setup", warm_up=warm,
              rate_per_s=traffic["arrivals"]["rate_per_s"], engine_kwargs=ek)
        w = window(ctx, handle, traffic, seconds)
        C.say(phase="serve.generator", **w["generator"])
        return finish(ctx, handle, w["reqs"], w["w_start"], w["w_end"],
                      w["snap0"], w["cut"])
    finally:
        S.stop_cluster()
