"""The knee of `serve-ling3flash-reasoning`: the cell's own driver and
traffic file at one arrival rate after another, ON ONE REPLICA (a rate a
process would spend 35 s of set-up a rate; PERF.md section 4 has the table,
PR 47).

    python scripts/sweep_ling3_knee.py <rate>[,<rate>...] [seed] [--toy]
        [--cell serve-phi4flash-longgen-loaded --out pr51]

`--cell` names another cell whose driver has this one's `start_cluster`,
`warm_up` and `window` (PR 51 swept `serve-phi4flash-longgen`, retired at
PR 55: today's cell is `-loaded`; the driver is found by the traffic
file's `kind`), `--out` the directory under
chiprun_out/ its table goes to.

What `benchmarks/run.py`'s child does up to the warm-up, then
`serve_open_reasoning.window` once a rate — arrivals to the window's last
second, the streams still running cut at its end, exactly as in a run of
the cell — with the seed raised by 4 a window (other token ids; the
weights stay the first seed's: they are the replica's).  A rate given
twice is run twice.  No reference check: a sweep asks what the replica
sustains, a run of the cell whether it is right.

A `rate` line a window: the knee rule's two numbers (`ttft_ms_p50_by_half`:
the median TTFT of the window's first and second half of requests, a
request with no token yet counted as its wait so far; `lateness_ms_p99`),
what was left (`streaming_at_window_end`, `no_first_token_in_window`:
requests still queued or in prefill at the cut; `slots_held_max`: of 64),
the gaps' percentiles and the streams at once.  Written
to chiprun_out/pr47/knee.json too.  `--toy` runs the cell's rehearsal sizes
on the CPU.
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)



def _option(name: str, default: str) -> str:
    """`--name value` off the command line (and out of it)."""
    if name not in sys.argv:
        return default
    at = sys.argv.index(name)
    value = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    return value


CELL = _option("--cell", "serve-ling3flash-reasoning")
OUT = os.path.join(ROOT, "chiprun_out", _option("--out", "pr47"))
TOY = "--toy" in sys.argv


def say(**kw):
    print(json.dumps(kw), flush=True)


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
        warm = drv.warm_up(handle, ek, ctx["traffic"],
                           ctx["config"]["vocab_size"])
        say(phase="setup", seconds=time.time() - ctx["t0"], warm_up=warm)
        for i, rate in enumerate(rates):
            traffic = dict(ctx["traffic"], arrivals=dict(
                ctx["traffic"]["arrivals"], rate_per_s=rate))
            w = drv.window(dict(ctx, seed=seed + 4 * i), handle, traffic,
                           float(ctx["seconds"]))
            snap = handle.bench_snapshot.remote(True).result(timeout_s=180)
            off = snap["wall"] - snap["perf"]
            ring = [r for r in snap["ring"]
                    if w["w_start"] <= r["ts"] + off <= w["w_end"]]
            steps = [r for r in ring if r.get("active")]
            inside = [[t for t in r.times if t <= w["w_end"]]
                      for r in w["reqs"]]
            gaps = [1000.0 * (b - a) for ts in inside
                    for a, b in zip(ts, ts[1:])]
            ttft = [1000.0 * (ts[0] - r.due)
                    for r, ts in zip(w["reqs"], inside) if ts]
            failed = [r.error for r in w["reqs"]
                      if r.error and r.rid not in w["cut"]]
            half = w["generator"]["ttft_ms_p50_by_half"]
            row = dict(
                w["generator"], phase="rate", rate_per_s=rate,
                seed=seed + 4 * i, half_ratio=half[1] / half[0],
                failed=len(failed), errors=sorted(set(failed))[:3],
                completed=sum(1 for r in w["reqs"] if not r.error),
                gaps=len(gaps),
                itl_ms={p: pct(gaps, p) for p in (50, 95, 98, 99, 99.5)}
                if gaps else None,
                ttft_ms={p: pct(ttft, p) for p in (50, 75, 90, 99)}
                if ttft else None,
                active_p50=pct([r["active"] for r in steps], 50)
                if steps else 0,
                active_max=max([r["active"] for r in steps] or [0]),
                # slots held, streaming or in prefill: all 64 is saturation
                slots_held_max=max(
                    [r.get("states_live", 0) for r in ring] or [0]),
                decode_step_ms_p50=pct(
                    [1000.0 * r["decode_s"] for r in steps], 50)
                if steps else None)
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
            [sys.executable, os.path.abspath(__file__), "--child", "--cell",
             CELL, "--out", os.path.basename(OUT)] + sys.argv[1:], env=env,
            cwd=ROOT).returncode)
    sweep(rates, seed)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
