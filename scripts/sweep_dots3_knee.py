"""The knee of `serve-dots3note-sparsectx`: the cell's own driver, replica
and traffic file at one arrival rate after another, ON ONE REPLICA (a
process a rate would spend a minute of set-up a rate; PERF.md section 4 has
the table, PR 56).

    python scripts/sweep_dots3_knee.py <rate>[,<rate>...] [seed] [--toy]

What `benchmarks/run.py`'s child does up to the warm-up
(`serve_open_sparsectx.start_cluster`, `warm_up`), then one window a rate
as `serve_open_sparsectx.run` offers it — the cycle of the traffic file at
that rate from `lib/traffic.open_schedule`, one sender, `_serve.send` on
the thread pool, every request waited for after the window — with the seed
raised by 4 a window (other token ids; the weights stay the first seed's:
they are the replica's).  A rate given twice is run twice.  No reference
check: a sweep asks what the replica sustains, a run of the cell whether it
is right.

A `rate` line a window: the knee rule's two numbers (`ttft_ms_p50_by_half`:
the median TTFT of the window's first and second half of requests; PERF.md
section 4's rule: the knee is the highest rate at which the second half's
is at most 1.5 x the first's), `ttft_ms` percentiles, how many requests
completed inside the window and how many were still running at its end,
the decode step's and the chunk's median from the engine's ring.  Written
to chiprun_out/pr56/knee.json too.  `--toy` runs the cell's rehearsal sizes
on the CPU.
"""
import argparse
import importlib
import json
import os
import queue
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "serve-dots3note-sparsectx"
OUT = os.path.join(ROOT, "chiprun_out", "pr56")
TOY = "--toy" in sys.argv


def say(**kw):
    print(json.dumps(kw), flush=True)


def window(handle, traffic, seed: int, seconds: float, vocab: int):
    """One window of the open loop at the traffic's rate; returns (requests,
    start, end) once every request has its answer."""
    from benchmarks.drivers import _serve as S
    from benchmarks.lib import traffic as T

    plan = T.open_schedule(traffic, seed, seconds, vocab)
    w_start = time.time() + 0.5
    reqs = [S.Request(i, {"tokens": p["tokens"],
                          "max_new_tokens": p["max_new_tokens"]},
                      w_start + p["due"]) for i, p in enumerate(plan)]
    q: "queue.Queue" = queue.Queue()

    def worker(_k):
        while True:
            r = q.get()
            if r is None:
                return
            S.send(handle, r)

    threads = S.run_pool(int(traffic["max_in_flight"]), worker)
    for r in reqs:
        time.sleep(max(0.0, r.due - time.time()))
        q.put(r)
    time.sleep(max(0.0, w_start + seconds - time.time()))
    running = sum(1 for r in reqs if r.done is None)
    deadline = time.time() + S.REQUEST_TIMEOUT_S
    while any(r.done is None for r in reqs) and time.time() < deadline:
        time.sleep(0.1)
    for _ in threads:
        q.put(None)
    return reqs, w_start, w_start + seconds, running


def sweep(rates, seed: int):
    import benchmarks.run as R
    from benchmarks.drivers import _serve as S
    from benchmarks.lib import manifest
    from benchmarks.lib.stats import percentile as pct

    cell = manifest.resolve(manifest.load(), CELL)
    drv = importlib.import_module(
        f"benchmarks.drivers.{cell['traffic']['kind']}")
    rundir = os.path.join(R.RUN_DIR, "knee")
    os.makedirs(rundir, exist_ok=True)
    ctx = R._context(argparse.Namespace(
        seed=seed, seconds=3.0 if TOY else None, rehearse=TOY,
        t0=time.time(), rundir=rundir, trace=0), cell)
    handle, ek = drv.start_cluster(ctx)
    rows = []
    try:
        vocab = ctx["config"]["vocab_size"]
        warm = drv.warm_up(handle, ek, ctx["traffic"], vocab)
        say(phase="setup", seconds=time.time() - ctx["t0"], warm_up=warm)
        for i, rate in enumerate(rates):
            traffic = dict(ctx["traffic"], arrivals=dict(
                ctx["traffic"]["arrivals"], rate_per_s=rate))
            reqs, w0, w1, running = window(handle, traffic, seed + 4 * i,
                                           float(ctx["seconds"]), vocab)
            snap = handle.bench_snapshot.remote(True).result(timeout_s=180)
            off = snap["wall"] - snap["perf"]
            ring = [r for r in snap["ring"] if w0 <= r["ts"] + off <= w1]
            steps = [r for r in ring if r.get("active")]
            chunks = [r for r in ring if r.get("chunks")]
            ttft = [1000.0 * (r.times[0] - r.due) for r in reqs if r.times]
            by_half = [pct([1000.0 * (r.times[0] - r.due) for r in half
                            if r.times] or [float("nan")], 50)
                       for half in (reqs[:len(reqs) // 2],
                                    reqs[len(reqs) // 2:])]
            failed = [r.error for r in reqs if r.error]
            row = dict(
                phase="rate", rate_per_s=rate, seed=seed + 4 * i,
                requests=len(reqs), failed=len(failed),
                errors=sorted(set(failed))[:3],
                completed_in_window=sum(
                    1 for r in reqs if r.done is not None and r.done <= w1
                    and not r.error),
                running_at_window_end=running,
                drain_s=max([r.done or w1 for r in reqs]) - w1,
                ttft_ms_p50_by_half=by_half,
                half_ratio=by_half[1] / by_half[0],
                ttft_ms={p: pct(ttft, p) for p in (50, 75, 90, 99)}
                if ttft else None,
                prompt_tokens=sum(len(r.body["tokens"]) for r in reqs),
                active_p50=pct([r["active"] for r in steps], 50)
                if steps else 0,
                active_max=max([r["active"] for r in steps] or [0]),
                decode_step_ms_p50=pct(
                    [1000.0 * r["decode_s"] for r in steps], 50)
                if steps else None,
                chunk_ms_p50=pct(
                    [1000.0 * r["prefill_s"] / r["chunks"] for r in chunks],
                    50) if chunks else None,
                busy_share=sum(r["device_wait_s"] for r in ring)
                / max(w1 - w0, 1e-9))
            say(**row)
            rows.append(row)
            time.sleep(1.0)          # the engine idle before the next rate
    finally:
        S.stop_cluster()
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "knee.json"), "w") as f:
        json.dump(rows, f, indent=1)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    rates = [float(r) for r in args[0].split(",")]
    seed = int(args[1]) if len(args) > 1 else 3000001001
    if "--child" not in sys.argv:
        # the environment `benchmarks/run.py` gives its child
        import subprocess

        import benchmarks.run as R

        env = R._child_env(argparse.Namespace(rehearse=TOY), 1)
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"]
            + sys.argv[1:], env=env, cwd=ROOT).returncode)
    sweep(rates, seed)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
