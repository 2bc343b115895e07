"""Driver for traffic of kind `serve_open_reasoning`: the open loop of
`serve_open` (arrivals on a schedule fixed by the traffic file, each
request timed from when it was DUE) in front of a replica that serves a
`bailing_hybrid` (Ling-3.0) configuration — prompts of about a thousand
tokens with a tail of long documents, prefilled in 512-token chunks, and
answers of many hundreds of tokens, dozens of streams at once: a slot holds
a state entry (six KDA layers' state matrices and conv tails) AND latent
pages.

`serve_open_longctx`'s driver with what is wired to the model exchanged:
the model imported before the cluster starts, the loader and the replica
class (drivers/replica_ling3.py), the rehearsal's sizes and the
reference's shape (lib/ling3cfg.py).  The warm-up is that driver's own
(ONE prefill program, the chunk, and no key program: imported), the sample
`serve_open_streams`'s; the client side of a request, the thread pool and
the cluster's end are `_serve`'s.  `finish` and `run` are written out
again because they call this module's `start_cluster` and `finish` by
name: the harness's drivers take no such argument.

What this driver does that no sibling does: arrivals come to the window's
LAST second and answers run to 2,048 tokens (forty to sixty seconds of
streaming), so at the window's end the streams still running are CUT
(`window`, `Ling3Server.bench_cut`) instead of waited for: a run has sixty
seconds beside its window.  A cut stream is no failure; the result keeps
of every request the tokens that arrived inside the window; the reference
holds requests that ran to their end.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import time
from typing import Dict, List

from . import _common as C
from . import _serve as S
from .serve_open_longctx import warm_up

CUT_TIMEOUT_S = 15.0     # the cut's own deadline: a 16,384-token prompt in
                         # mid-prefill has 32 chunks of ~50 ms to end


def _rehearsal(ctx: Dict):
    """A rehearsal (test fixture, CPU) runs this model at its own toy
    sizes: `tests/rehearsal_reasoning.json` over the cell's files."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "tests", "rehearsal_reasoning.json")) as f:
        toy = json.load(f)
    ctx["config"].update(toy["config"])
    ctx["traffic"].update(toy["traffic"])
    return toy["engine_kwargs"]


def pick_sample(ctx: Dict, ok: List[S.Request]) -> List[Dict]:
    """The requests held to the reference, `checked` in all, of those that
    ran to their end inside the window: the one with the longest context
    the reference's `max_context` rows hold (prompt + served: most latent
    pages, the state that has carried most), then the one with the SHORTEST prompt (what
    its entry's last holder left has faded least when its first token is
    drawn), then others in an order drawn from the seed."""
    import numpy as np

    n = int(ctx["traffic"]["reference"]["checked"])
    rng = np.random.default_rng(ctx["seed"] & 0xFFFFFFFF)
    rows = int(ctx["traffic"]["reference"]["max_context"])
    ok = [r for r in ok if len(r.body["tokens"]) + len(r.tokens) <= rows]
    rest = sorted(ok, key=lambda r: (-(len(r.body["tokens"]) + len(r.tokens)),
                                     r.rid))
    longest, rest = rest[:1], rest[1:]
    rest.sort(key=lambda r: (len(r.body["tokens"]), r.rid))
    shortest, rest = rest[:1], rest[1:]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    return [{"rid": r.rid, "tokens": r.body["tokens"], "served": r.tokens}
            for r in (longest + shortest + rest)[:n]]


def start_cluster(ctx: Dict):
    """`_serve.start_cluster`'s sequence with this model's replica.  The
    model is imported BEFORE the cluster starts: a program that lacks it
    ends here, at once, with nothing to stop."""
    import ray_tpu.models.ling3  # noqa: F401

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve._deployment import deployment

    from .replica_ling3 import Ling3Server, make_loader

    toy = _rehearsal(ctx) if ctx["rehearse"] else None
    conf = ctx["config"]
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
    if ctx["trace"]:
        os.environ.setdefault("RAY_TPU_SERVE_HEALTH_CHECK_TIMEOUT_S", "600")
    ray_tpu.init()
    dep = deployment(Ling3Server, name="LLMServer",
                     ray_actor_options={"resources": {"TPU": 1}},
                     max_ongoing_requests=256)
    h = serve.run(
        dep.bind(params_loader=make_loader(conf, ctx["seed"], {}),
                 max_seq=sv["max_seq"], engine=sv["engine"],
                 engine_kwargs=ek),
        name=S.APP, route_prefix=None, blocking_timeout_s=900)
    return h, ek


def finish(ctx: Dict, handle, reqs: List[S.Request], w_start: float,
           w_end: float, snap0: Dict, cut=frozenset()) -> Dict:
    """`serve_open_longctx.finish` with this model's reference shape and
    the window's cut: a request in `cut` (its stream ended by the window's
    end, `window`) is no failure and no sample of the reference's — only a
    request that ran to its end is held to it — and of every request the
    result keeps the tokens that arrived INSIDE the window, so that
    `itl_p99_ms` pools the window's gaps and none of the cut's."""
    from benchmarks.lib.ling3cfg import reference_shape
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
           "state_rel_rms": float("inf"),
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
        recs = sorted((q for r in ring for q in r["requests"]),
                      key=lambda q: q["rid"])     # the engine's: in order sent
        parts = ({r.rid: q for r, q in zip(reqs, recs)}
                 if len(recs) == len(reqs) else {})
        C.say(phase="serve.tails", requests=len(ttft), gaps=len(itl),
              ttft_ms={p: pct(ttft, p) for p in (50, 75, 90)},
              # each request's TTFT by the engine's parts
              by_request=[{"rid": r.rid, "plen": len(r.body["tokens"]),
                           "ttft_ms": round(1000.0 * (r.times[0] - r.due), 1),
                           **{k[:-2] + "_ms": round(1000.0 * v, 1)
                              for k, v in parts.get(r.rid, {}).items()
                              if k.endswith("_s")}}
                          for r in reqs if inside[r.rid]],
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
          f"{ref['state_rel_rms']:.5f} (at most "
          f"{spec['max_state_rel_rms']}) state_half_share="
          f"{ref['state_half_share']:.4f} (at most "
          f"{spec['max_state_half_share']}) failed={len(failed)} (0) "
          f"compiled_in_window={len(compiled)} (0)",
          file=sys.stderr, flush=True)
    stamps = {int(k): v for k, v in snap1["stamps"].items()}
    return {
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


def window(ctx: Dict, handle, traffic: Dict, seconds: float) -> Dict:
    """ONE window of `seconds` against a warm replica: arrivals at the
    file's rate from its first second to its last, and at its end every
    stream still running is CUT (`bench_cut`: a run has sixty seconds
    beside its window, and a 2,048-token answer begun in the last second
    would take fifty of them).  What a window measures is what streamed
    inside it.  -> {"reqs", "cut" (the rids cut short, no failures),
    "w_start", "w_end", "snap0", "generator" (the sender's lateness, the
    knee rule's two halves)}.  `scripts/sweep_ling3_knee.py` calls this
    several times on one replica."""
    from benchmarks.lib import stats
    from benchmarks.lib import traffic as T

    plan = T.open_schedule(traffic, ctx["seed"], seconds,
                           ctx["config"]["vocab_size"])
    w_start = time.time() + 0.5
    w_end = w_start + seconds
    reqs = [S.Request(i, {"tokens": p["tokens"],
                          "max_new_tokens": p["max_new_tokens"]},
                      w_start + p["due"])
            for i, p in enumerate(plan)]
    trace_s = float(traffic.get("trace_seconds", 4))
    offset = float(traffic.get("trace_offset_seconds", 0))
    q: "queue.Queue" = queue.Queue()

    def worker(_k):
        while True:
            r = q.get()
            if r is None:
                return
            S.send(handle, r)

    threads = S.run_pool(int(traffic["max_in_flight"]), worker)
    snap0 = handle.bench_snapshot.remote().result(timeout_s=60)
    state = {"tracing": 0, "t": 0.0}

    def sleep_until(t):
        """To time t, through the trace's start (`offset` into the
        window) and its end (`trace_s` later) where they fall first."""
        while True:
            event = t
            if ctx["trace"] and state["tracing"] == 0:
                event = min(t, w_start + offset)
            elif state["tracing"] == 1:
                event = min(t, state["t"] + trace_s)
            time.sleep(max(0.0, event - time.time()))
            if event == t:
                return
            if state["tracing"] == 0:
                handle.bench_trace_start.remote(
                    ctx["trace_dir"]).result(timeout_s=60)
                state.update(tracing=1, t=time.time())  # took seconds
            else:
                handle.bench_trace_stop.remote().result(timeout_s=60)
                state["tracing"] = 2
                ctx["traced"] = [state["t"], time.time()]

    for r in reqs:                       # the one sender
        sleep_until(r.due)
        q.put(r)
    sleep_until(w_end)
    if state["tracing"] == 1:            # a window shorter than the trace
        handle.bench_trace_stop.remote().result(timeout_s=60)
        ctx["traced"] = [state["t"], time.time()]
    streaming = sum(1 for r in reqs if r.done is None)
    t_cut = time.time()
    deadline = t_cut + CUT_TIMEOUT_S
    while any(r.done is None for r in reqs) and time.time() < deadline:
        # again each round: a request on its way to the engine when the
        # last one ran is cut by this one
        handle.bench_cut.remote().result(timeout_s=60)
        time.sleep(0.2)
    for r in reqs:
        if r.done is None:
            r.error = r.error or "no answer before the cut's deadline"
    for _ in threads:
        q.put(None)
    # cut short and no failure: ended by the cut, with fewer tokens than
    # asked and no other fault
    cut = {r.rid for r in reqs
           if r.done is not None and r.done >= t_cut and r.error
           and r.error.startswith("returned ")
           and len(r.tokens) < r.body["max_new_tokens"]}
    late = [1000.0 * (r.sent - r.due) for r in reqs if r.sent is not None]
    # a request with no token inside the window counts as its wait so far
    first = lambda half: stats.percentile(
        [1000.0 * (min(r.times[:1] + [w_end]) - r.due) for r in half]
        or [float("nan")], 50)
    gen = {"lateness_ms_p50": stats.percentile(late, 50),
           "lateness_ms_p99": stats.percentile(late, 99),
           "lateness_ms_max": max(late), "requests": len(reqs),
           "streaming_at_window_end": streaming, "cut": len(cut),
           "no_first_token_in_window": sum(
               1 for r in reqs if not r.times or r.times[0] > w_end),
           "cut_seconds": time.time() - t_cut,
           "ttft_ms_p50_by_half": [first(reqs[:len(reqs) // 2]),
                                   first(reqs[len(reqs) // 2:])]}
    return {"reqs": reqs, "cut": cut, "w_start": w_start, "w_end": w_end,
            "snap0": snap0, "generator": gen}


def run(ctx: Dict) -> Dict:
    handle, ek = start_cluster(ctx)
    try:
        traffic = ctx["traffic"]
        seconds = float(ctx["seconds"])
        if ctx["trace"]:   # a traced run is shorter: its trace is cut and
            seconds = min(seconds, float(traffic["traced_window_seconds"]))
        # the reference's programs compile beside the warm-up's (a
        # thread of the replica's; set-up, not the window, pays for them)
        from benchmarks.lib.ling3cfg import reference_shape

        handle.bench_prepare_reference.remote(
            reference_shape(ctx["config"]), traffic["reference"],
            int(traffic["output_len"]["max"])).result(timeout_s=60)
        warm = warm_up(handle, ek, traffic, ctx["config"]["vocab_size"])
        C.say(phase="serve.setup", warm_up=warm,
              rate_per_s=traffic["arrivals"]["rate_per_s"], engine_kwargs=ek)
        w = window(ctx, handle, traffic, seconds)
        C.say(phase="serve.generator", **w["generator"])
        return finish(ctx, handle, w["reqs"], w["w_start"], w["w_end"],
                      w["snap0"], w["cut"])
    finally:
        S.stop_cluster()
