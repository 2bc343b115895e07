"""Driver for traffic of kind `serve_open`: arrivals on a schedule fixed
by the traffic file, whatever the system does; each request timed from
when it was DUE."""

from __future__ import annotations

import queue
import time
from typing import Dict

from . import _common as C
from . import _serve as S



def run(ctx: Dict) -> Dict:
    from benchmarks.lib import stats
    from benchmarks.lib import traffic as T

    traffic = ctx["traffic"]
    seconds = float(ctx["seconds"])
    if ctx["trace"]:       # a traced run is shorter: its trace is cut and
        seconds = min(seconds, float(traffic["traced_window_seconds"]))
    handle, ek = S.start_cluster(ctx)
    try:
        vocab = ctx["config"]["vocab_size"]
        plan = T.open_schedule(traffic, ctx["seed"], seconds, vocab)
        warm = S.warm_up(handle, ek, traffic, vocab,
                         [p["max_new_tokens"] for p in plan])
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
        tracing, t_traced = 0, 0.0
        for r in reqs:                       # the one sender
            if ctx["trace"] and tracing == 0 and r.due >= w_start + offset:
                handle.bench_trace_start.remote(
                    ctx["trace_dir"]).result(timeout_s=60)
                tracing, t_traced = 1, time.time()  # starting takes seconds
            if tracing == 1 and r.due >= t_traced + trace_s:
                handle.bench_trace_stop.remote().result(timeout_s=60)
                tracing = 2
            delay = r.due - time.time()
            if delay > 0:
                time.sleep(delay)
            q.put(r)
        if tracing == 1:
            handle.bench_trace_stop.remote().result(timeout_s=60)
        time.sleep(max(0.0, w_end - time.time()))
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
        return S.finish(ctx, handle, reqs, w_start, w_end, snap0)
    finally:
        S.stop_cluster()
