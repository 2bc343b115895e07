"""Driver for traffic of kind `serve_open_sparsectx`: the open loop of
`serve_open` (arrivals on a schedule fixed by the traffic file, each
request timed from when it was DUE) in front of a replica that serves a
`dots3_note` configuration — prompts of thousands to tens of thousands of
tokens, every one past the indexer's `index_topk`, prefilled in 512-row
chunks over two pools of latent pages (the full kind's with the indexer's
rows under the same table, the sliding kind's a ring), answers of a few
hundred tokens.

`serve_open_longctx`'s driver with what is wired to the model exchanged:
the model imported before the cluster starts, the loader and the replica
class (drivers/replica_dots3.py), the rehearsal's sizes and the
reference's shape (lib/dots3cfg.py).  The warm-up is that driver's own
(ONE prefill program, the chunk, and no key program: imported), the sample
`serve_open_streams`'s; the client side of a request, the thread pool and
the cluster's end are `_serve`'s.  `finish` and `run` are written out
again because they call this module's `start_cluster` and `finish` by
name: the harness's drivers take no such argument.
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


def _rehearsal(ctx: Dict):
    """A rehearsal (test fixture, CPU) runs this model at its own toy
    sizes: `tests/rehearsal_sparsectx.json` over the cell's files."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "tests", "rehearsal_sparsectx.json")) as f:
        toy = json.load(f)
    ctx["config"].update(toy["config"])
    ctx["traffic"].update(toy["traffic"])
    return toy["engine_kwargs"]


def pick_sample(ctx: Dict, ok: List[S.Request]) -> List[Dict]:
    """The requests held to the reference, `checked` in all, of those that
    ran to their end and whose context (prompt + served) the reference's
    `max_context` rows hold: the one with the longest context, then others
    in an order drawn from the seed.  At the cell's `max_context` 18,432
    and `checked` 1 that is the cycle's 17,710-token request (a prompt of
    17,262: 8.6 x index_topk, past the widest of the selection's four
    widths), in every run.  The cycle's four longer ones (to 29,589) stay
    outside the cell's clock — the reference's own draw of the weights is
    4.3 s of every pass and a pass over 29,317 tokens 16.0 s, PERF.md
    section 6, PR 56; `scripts/study_dots3_parity.py --plen 29301` holds
    the programs to the reference there."""
    import numpy as np

    spec = ctx["traffic"]["reference"]
    rng = np.random.default_rng(ctx["seed"] & 0xFFFFFFFF)
    size = lambda r: len(r.body["tokens"]) + len(r.tokens)
    ok = [r for r in ok if size(r) <= int(spec["max_context"])]
    order = sorted(ok, key=lambda r: (-size(r), r.rid))
    longest, rest = order[:1], order[1:]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    return [{"rid": r.rid, "tokens": r.body["tokens"], "served": r.tokens}
            for r in (longest + rest)[:int(spec["checked"])]]


def start_cluster(ctx: Dict):
    """`_serve.start_cluster`'s sequence with this model's replica.  The
    model is imported BEFORE the cluster starts: a program that lacks it
    ends here, at once, with nothing to stop."""
    import ray_tpu.models.dots3  # noqa: F401

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve._deployment import deployment

    from benchmarks.lib.dots3cfg import reference_shape

    from .replica_dots3 import Dots3Server, make_loader

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
    dep = deployment(Dots3Server, name="LLMServer",
                     ray_actor_options={"resources": {"TPU": 1}},
                     max_ongoing_requests=256)
    # the reference's pieces compile in the replica from its loader on,
    # beside the weights' draw and the engine's construction
    traffic = ctx["traffic"]
    reference = (reference_shape(conf), traffic["reference"],
                 int(traffic["output_len"]["max"]))
    h = serve.run(
        dep.bind(params_loader=make_loader(conf, ctx["seed"], {}, reference),
                 max_seq=sv["max_seq"], engine=sv["engine"],
                 engine_kwargs=ek),
        name=S.APP, route_prefix=None, blocking_timeout_s=900)
    # ... and stand before the engine is handed its first request: what of
    # them the replica's start did not cover is waited for here
    waited = list(h.options(stream=True).bench_reference_built.remote())[-1]
    C.say(phase="serve.reference_built", waited_s=waited)
    return h, ek


def finish(ctx: Dict, handle, reqs: List[S.Request], w_start: float,
           w_end: float, snap0: Dict) -> Dict:
    """`serve_open_streams.finish` with this model's reference (handed the
    seed and the configuration's `weights`, not an array)."""
    from benchmarks.lib.dots3cfg import reference_shape
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
    failed = [r for r in reqs if r.error]
    ok = [r for r in reqs if not r.error]
    sample = pick_sample(ctx, ok)
    ref = {"argmax_share": 0.0, "worst_gap": float("inf"),
           "logit_rel_rms": float("inf"), "per_request": []}
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
                  ref["logit_rel_rms"] <= spec["max_logit_rel_rms"]}
    C.say(phase="serve.reference", margin=spec["logit_margin"],
          min_argmax_share=spec["min_argmax_share"], **ref)
    ttft = [1000.0 * (r.times[0] - r.due) for r in ok if r.times]
    itl = [1000.0 * (b - a) for r in ok for a, b in zip(r.times, r.times[1:])]
    if ttft and itl:         # context for choosing percentiles, not results
        recs = sorted((q for r in ring for q in r["requests"]),
                      key=lambda q: q["rid"])     # the engine's: in order sent
        parts = ({r.rid: q for r, q in zip(reqs, recs)}
                 if len(recs) == len(reqs) else {})
        C.say(phase="serve.tails", requests=len(ttft), gaps=len(itl),
              ttft_ms={p: pct(ttft, p) for p in (50, 75, 90)},
              # a window holds ten requests: each one's TTFT by the
              # engine's parts, so a run says WHICH request a rank read
              by_request=[{"rid": r.rid, "plen": len(r.body["tokens"]),
                           "ttft_ms": round(1000.0 * (r.times[0] - r.due), 1),
                           **{k[:-2] + "_ms": round(1000.0 * v, 1)
                              for k, v in parts.get(r.rid, {}).items()
                              if k.endswith("_s")}}
                          for r in ok if r.times],
              itl_ms={p: pct(itl, p) for p in (50, 95, 98, 99, 99.5)},
              after_window_s=time.time() - w_end,
              snapshot_and_reference_s=time.time() - t_fin)
    C.say(phase="serve.window", attempted=len(reqs), failed=len(failed),
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
          f"{spec['max_logit_rel_rms']}) failed={len(failed)} (0) "
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
        "serve": {"requests": [dict(r.record(), replica=stamps.get(r.rid))
                               for r in reqs],
                  "ring": ring, "max_slots": snap1["max_slots"],
                  "traced": ctx.get("traced"), "scopes": scopes,
                  "timeout_ms": 1000.0 * (S.REQUEST_TIMEOUT_S
                                          + (w_end - w_start))},
    }


def run(ctx: Dict) -> Dict:
    from benchmarks.lib import stats
    from benchmarks.lib import traffic as T

    handle, ek = start_cluster(ctx)
    try:
        traffic = ctx["traffic"]
        seconds = float(ctx["seconds"])
        if ctx["trace"]:   # a traced run is shorter: its trace is cut and
            seconds = min(seconds, float(traffic["traced_window_seconds"]))
        vocab = ctx["config"]["vocab_size"]
        plan = T.open_schedule(traffic, ctx["seed"], seconds, vocab)
        warm = warm_up(handle, ek, traffic, vocab)
        w_start = time.time() + 0.5
        w_end = w_start + seconds
        reqs = [S.Request(i, {"tokens": p["tokens"],
                              "max_new_tokens": p["max_new_tokens"]},
                          w_start + p["due"])
                for i, p in enumerate(plan)]
        trace_s = float(traffic.get("trace_seconds", 4))
        offset = float(traffic.get("trace_offset_seconds", 0))
        C.say(phase="serve.setup", warm_up=warm, requests=len(reqs),
              rate_per_s=traffic["arrivals"]["rate_per_s"],
              engine_kwargs=ek)
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
            window) and its end (`trace_s` later) where they fall first:
            at this rate arrivals lie seconds apart, and both fall between
            two of them."""
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
        backlog = sum(1 for r in reqs if r.done is None)
        deadline = time.time() + S.REQUEST_TIMEOUT_S
        while any(r.done is None for r in reqs) and time.time() < deadline:
            time.sleep(0.1)
        for r in reqs:
            if r.done is None:
                r.error = r.error or "no answer before the drain deadline"
        for _ in threads:
            q.put(None)
        late = [1000.0 * (r.sent - r.due) for r in reqs
                if r.sent is not None]
        C.say(phase="serve.generator", lateness_ms_p50=stats.percentile(
            late, 50), lateness_ms_p99=stats.percentile(late, 99),
            lateness_ms_max=max(late), requests=len(reqs),
            unfinished_at_window_end=backlog,
            ttft_ms_p50_by_half=[stats.percentile(
                [1000.0 * (r.times[0] - r.due) for r in half if r.times]
                or [float("nan")], 50)
                for half in (reqs[:len(reqs) // 2],
                             reqs[len(reqs) // 2:])])
        return finish(ctx, handle, reqs, w_start, w_end, snap0)
    finally:
        S.stop_cluster()
