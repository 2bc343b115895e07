"""Driver for traffic of kind `serve_open_ragextract`: the open loop of
`serve_open` (arrivals on a schedule fixed by the traffic file, each
request timed from when it was DUE) in front of a replica that serves an
`lfm2_moe` (LFM2-8B-A1B) configuration — retrieval and extraction: a
retrieved document or a tool's output of a few thousand tokens in, through
512-row prefill chunks, a short structured answer out, dozens of streams
at once: a slot holds pages in the attention layers and ONE entry of conv
tails, and every expert of every layer is held, so a step and a chunk are
both the experts' read.

`serve_open_chatburst`'s driver with what is wired to the model exchanged:
the model imported before the cluster starts, the loader and the replica
class (drivers/replica_lfm2_moe.py), the rehearsal's sizes and the
reference's shape (lib/lfm2moecfg.py), the checks' names and limits.  The
warm-up (BOTH prefill programs) is that driver's own, imported; the window
— arrivals to its last second, the streams still running then CUT and no
failures — is `serve_open_reasoning`'s (`window`, imported, with the
sample's choice `pick_sample`); the client side of a request, the thread
pool and the cluster's end are `_serve`'s.  `start_cluster`, `finish` and
`run` are written out again: `finish` imports its model's
`reference_shape` by name and names its checks' limits, `run` calls its
module's `start_cluster` and `finish` by name, and the harness's drivers
take no such argument.

A traced run also prints `serve.layers`: the readings of the metric files
under benchmarks/metrics/ that this cell's layers have and BENCHMARK.json
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
from .serve_open_chatburst import warm_up
from .serve_open_reasoning import pick_sample, window

LAYER_METRICS = ("conv.time_share.ragextract", "attn.time_share.ragextract",
                 "moe.load_max_over_mean.ragextract")


def _rehearsal(ctx: Dict):
    """A rehearsal (test fixture, CPU) runs this model at its own toy
    sizes: `tests/rehearsal_ragextract.json` over the cell's files."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "tests", "rehearsal_ragextract.json")) as f:
        toy = json.load(f)
    ctx["config"].update(toy["config"])
    ctx["traffic"].update(toy["traffic"])
    return toy["engine_kwargs"]


def start_cluster(ctx: Dict):
    """`_serve.start_cluster`'s sequence with this model's replica.  The
    model is imported BEFORE the cluster starts: a program that lacks it
    ends here, at once, with nothing to stop."""
    import ray_tpu.models.lfm2_moe  # noqa: F401

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve._deployment import deployment

    from benchmarks.lib.lfm2moecfg import reference_shape

    from . import replica_lfm2_moe as rep

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
    dep = deployment(rep.Lfm2MoeServer, name="LLMServer",
                     ray_actor_options={"resources": {"TPU": 1}},
                     max_ongoing_requests=512)
    h = serve.run(
        dep.bind(params_loader=rep.make_loader(conf, ctx["seed"], {},
                                               reference),
                 max_seq=sv["max_seq"], engine=sv["engine"],
                 engine_kwargs=ek),
        name=S.APP, route_prefix=None, blocking_timeout_s=900)
    return h, ek


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


def _pairs_a_row(conf: Dict, eng: Dict):
    """Token-expert pairs the programs computed a routed row and expert
    layer, (steps', chunks'): `num_experts_per_tok` both, where every
    expert is held and every pair computed."""
    layers = conf["num_hidden_layers"] - conf["num_dense_layers"]
    return (eng["moe_pairs"] / max(eng["conv_live"] * layers, 1),
            eng["chunk_moe_pairs"] / max(eng["prefill_tokens"] * layers, 1))


def _every_pair(conf: Dict, eng: Dict) -> bool:
    held = conf.get("deployment_share", {}).get("experts_held",
                                                conf["num_experts"])
    if held != conf["num_experts"]:       # a share computes its own pairs
        return True
    return _pairs_a_row(conf, eng) == (conf["num_experts_per_tok"],) * 2


def finish(ctx: Dict, handle, reqs: List[S.Request], w_start: float,
           w_end: float, snap0: Dict, cut=frozenset()) -> Dict:
    """`serve_open_longgen.finish` with this model's reference shape: a
    request in `cut` (its stream ended by the window's end) is no failure
    and no sample of the reference's — only a request that ran to its end
    is held to it — and of every request the result keeps the tokens that
    arrived INSIDE the window, so that `itl_p99_ms` pools the window's
    gaps and none of the cut's."""
    from benchmarks.lib.lfm2moecfg import reference_shape
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
           "logit_rel_rms": float("inf"), "tail_rel_rms": float("inf"),
           "first_keys_max": float("inf"), "second_keys_q25": float("inf"),
           "per_request": []}
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
              "program_tails_near_reference":
                  ref["tail_rel_rms"] <= spec["max_tail_rel_rms"],
              "first_attention_keys_near_reference":
                  ref["first_keys_max"] <= spec["max_first_keys"],
              "keys_behind_experts_near_reference":
                  ref["second_keys_q25"] <= spec["max_second_keys_q25"],
              "every_pair_computed": _every_pair(ctx["config"],
                                                 snap1["engine"])}
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
          f"{spec['max_logit_rel_rms']}) tail_rel_rms="
          f"{ref['tail_rel_rms']:.6f} (at most "
          f"{spec['max_tail_rel_rms']}) first_keys_max="
          f"{ref['first_keys_max']:.5f} (at most {spec['max_first_keys']}) "
          f"second_keys_q25={ref['second_keys_q25']:.5f} (at most "
          f"{spec['max_second_keys_q25']}) pairs_a_row_and_layer="
          f"{_pairs_a_row(ctx['config'], snap1['engine'])} "
          f"failed={len(failed)} (0) "
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
