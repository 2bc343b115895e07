#!/usr/bin/env python3
"""`setup_s` of a serve cell by parts, from what the program says of its
own start (PR 59).

    python3 scripts/study_setup_parts.py --cell serve-brumby-streams --seed 7
    python3 scripts/study_setup_parts.py --read chiprun_out/setup/<run>

Runs ONE untraced run of a cell (on the chip: through `chiprun`;
`--rehearse` for the control flow on the CPU) and keeps what the run
leaves nowhere else: the logs of ITS OWN session — the one a process
below this command opened —, copied out every second while it runs (the
session directory goes with the cluster), and `result.json` with the
ring.  Then it reads what the program shipped, and nothing else:

  the session's stamp (its directory's name) -> the raylet up (its log)
  -> the replica's start / connect / register / pool / actor_wait /
  actor_init (its `worker boot:` log line) -> the engine's thread
  (`serve.window`'s `engine.ready`) -> a program at a time trace / lower
  / backend (cache load) / run (`serve.window`'s `compile_s`: the
  ledger's exclusive parts from `t_first_call_wall`) -> the window
  (`result.json`);

each part is a stretch between two stamps of one clock, so the parts and
the gaps between them (the benchmark's own work, named as such) sum to
the run's `setup_s`.  Beside it: every `stood still` line of the session
by the instant it ended, and the ring's slow iterations beside the
ticker's `stall_s`.

A train cell starts no cluster and its driver prints `persistent_cache`
alone: its programs are read from a scratch copy of the driver until a
`benchmark` PR prints them (ROADMAP C19).
"""

from __future__ import annotations

import argparse
import calendar
import glob
import gzip
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "setup")
sys.path.insert(0, ROOT)


def _descends(pid: int, ancestor: int) -> bool:
    while pid > 1:
        if pid == ancestor:
            return True
        try:
            with open(f"/proc/{pid}/stat") as f:
                pid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            return False
    return False


def run(cell: str, seed: int, rehearse: bool) -> str:
    """One run of the cell -> the directory that keeps it."""
    from ray_tpu._private.bootstrap import _SESSION_ROOT

    out = os.path.join(OUT, f"{cell}.{seed}")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(os.path.join(out, "logs"))
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell,
           "--seed", str(seed), "--seconds", "3" if rehearse else "50",
           "--trace", "0"] + (["--rehearse"] if rehearse else [])
    with open(os.path.join(out, "stdout"), "w") as so, \
            open(os.path.join(out, "stderr"), "w") as se:
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=so, stderr=se)
        own = set()          # sessions named for a process below `proc`
        while True:
            try:
                rc = proc.wait(timeout=1.0)
            except subprocess.TimeoutExpired:
                rc = None
            for d in glob.glob(os.path.join(_SESSION_ROOT, "session-*-*")):
                pid = d.rsplit("-", 1)[1]
                if d not in own and pid.isdigit() \
                        and _descends(int(pid), proc.pid):
                    own.add(d)
            for d in own:
                for path in glob.glob(d + "/**/*.log", recursive=True):
                    name = os.path.relpath(path, _SESSION_ROOT)
                    try:
                        shutil.copy2(path, os.path.join(
                            out, "logs", name.replace("/", "__")))
                    except OSError:
                        pass
            if rc is not None:
                break
    res = os.path.join(ROOT, ".bench_run", f"{cell}-0", "result.json")
    if os.path.exists(res):
        with open(res, "rb") as src, \
                gzip.open(os.path.join(out, "result.json.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    # the ring is in the result file; the stdout keeps the other lines
    with open(os.path.join(out, "stdout")) as f:
        kept = [ln for ln in f if '"phase": "serve.ring"' not in ln]
    with open(os.path.join(out, "stdout"), "w") as f:
        f.writelines(kept)
    print(f"run of {cell} seed {seed}: exit {rc}, kept in {out}")
    return out


def _lines(path):
    out = {}
    with open(path) as f:
        for ln in f:
            try:
                d = json.loads(ln)
            except ValueError:
                continue
            out[d.get("phase", "LAST")] = d
    return out


BOOT_LINE = re.compile(
    r"worker boot: start ([\d.]+) connect ([\d.]+) register ([\d.]+) pool "
    r"([\d.]+) wait ([\d.]+) init ([\d.]+) s from ([\d.]+)")
STILL_LINE = re.compile(r" stood still ([\d.]+) s until ([\d.]+)")
UP_LINE = re.compile(r" raylet \S+ up at ")
ASCTIME = re.compile(r"^(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}) ")
BOOT_PARTS = ("start", "connect", "register", "pool", "actor_wait",
              "actor_init")


def _asctime(ln):
    """A log line's own time as if it were UTC (-> `_session` finds the
    zone from a line that carries a wall stamp too)."""
    m = ASCTIME.match(ln)
    return m and calendar.timegm(time.strptime(
        m.group(1), "%Y-%m-%d %H:%M:%S")) + int(m.group(2)) / 1e3


def _session(out):
    """The session's own words: when it was named, when its raylet was
    up, its `worker boot:` and `stood still` lines."""
    boots, still, up, zone = [], [], None, None
    files = sorted(glob.glob(os.path.join(out, "logs", "*")))
    named = [int(os.path.basename(p).split("-")[1]) / 1e3 for p in files]
    for path in files:
        who = os.path.basename(path).split("__")[-1]
        with open(path, errors="replace") as f:
            for ln in f:
                m = BOOT_LINE.search(ln)
                if m:
                    v = [float(x) for x in m.groups()]
                    boots.append({"who": who, "start_wall": v[-1],
                                  **{p + "_s": s for p, s
                                     in zip(BOOT_PARTS, v)}})
                    continue
                m = STILL_LINE.search(ln)
                if m:
                    still.append({"who": who, "late_s": float(m.group(1)),
                                  "until": float(m.group(2))})
                    if zone is None and _asctime(ln):
                        # the line was written at `until`: a quarter hour
                        zone = round((still[-1]["until"] - _asctime(ln))
                                     / 900.0) * 900.0
                elif up is None and UP_LINE.search(ln):
                    up = _asctime(ln)
    if up is not None:
        # no stall, no zone: this machine's own
        up += zone if zone is not None else -time.localtime(up).tm_gmtoff
    return (min(named) if named else None), up, boots, still


def _chain(rows, t0):
    """Print parts laid end to end; a stretch no part covers is a gap."""
    t = t0
    total = 0.0
    for label, start, dur, src, gap in rows:
        if start - t > 0.0005:
            print(f"  {t - t0:8.2f} s  +{start - t:7.2f}    (gap) {gap}")
            total += start - t
        print(f"  {start - t0:8.2f} s  +{dur:7.2f}  {label}   [{src}]")
        total += dur
        t = start + dur
    return total


def _programs(compile_s, before):
    progs = sorted(compile_s.items(),
                   key=lambda kv: kv[1].get("t_first_call_wall", 0.0))
    return [(n, p) for n, p in progs
            if 0.0 < p.get("t_first_call_wall", 0.0) < before]


def _program_row(n, p, gap):
    return (f"{n}: trace {p.get('trace_s', 0):.2f} lower "
            f"{p.get('lower_s', 0):.2f} backend {p.get('backend_s', 0):.2f}"
            f" (cache load {p['cache_load_s']:.2f}) run {p['run_s']:.2f}",
            p["t_first_call_wall"], p["call_s"], "compile_s[program]", gap)


def _stills(still, t0, w0):
    by = {}
    for x in still:
        by.setdefault(round(x["until"], 1), []).append(x)
    for until, xs in sorted(by.items()):
        where = "in set-up" if until <= w0 else "AFTER the window opened"
        kinds = sorted({re.sub(r"[-.].*", "", x["who"]) for x in xs})
        print(f"  stood still until T0+{until - t0:.2f} ({where}): "
              f"{len(xs)} line(s), {min(x['late_s'] for x in xs):.1f}-"
              f"{max(x['late_s'] for x in xs):.1f} s, ends within "
              f"{max(x['until'] for x in xs) - min(x['until'] for x in xs):.3f}"
              f" s of each other; {kinds}")


def read(out):
    L = _lines(os.path.join(out, "stdout"))
    with gzip.open(os.path.join(out, "result.json.gz")) as f:
        res = json.load(f)
    setup, w0 = res["setup_s"], res["window"][0]
    t0 = w0 - setup
    named, up, boots, still = _session(out)
    win, su = L["serve.window"], L["serve.setup"]
    eng = win["engine"]
    print(f"== {out}\nsetup_s {setup:.3f}")
    rows = []
    if named is not None and up is not None:
        rows.append(("cluster start", named, up - named,
                     "the session's name -> its raylet's `up at` line",
                     "run.py, its child's start and imports"))
    if boots:
        rep = max(boots, key=lambda b: b["actor_init_s"])   # the replica's
        s = rep["start_wall"]
        for p in BOOT_PARTS:
            rows.append((f"replica {p}", s, rep[p + "_s"],
                         "its `worker boot:` line",
                         "serve.run: controller, proxy, deployment"))
            s += rep[p + "_s"]
    rows.append(("engine thread started", eng["ready"]["thread_start_wall"],
                 0.0, "engine.ready", "serve.run returns; the first "
                 "warm-up request reaches the engine"))
    progs = _programs(win["compile_s"], w0)
    rows += [_program_row(n, p, "warm-up requests: round trips, their "
                          "prefill and steps") for n, p in progs]
    rows.append(("window opens", w0, 0.0, "result.window", "the rest of "
                 "the warm-up, key programs, the first snapshot"))
    total = _chain(rows, t0)
    tot = {k: round(sum(p.get(k, 0.0) for _, p in progs), 2)
           for k in ("trace_s", "lower_s", "backend_s", "cache_load_s",
                     "run_s", "call_s")}
    print(f"  sum of parts {total:.3f}  setup_s {setup:.3f}")
    print(f"  programs in set-up {tot}  persistent_cache "
          f"{win['persistent_cache']}")
    print("  warm_up", {k: v for k, v in su["warm_up"].items()
                        if not isinstance(v, dict)})
    _stills(still, t0, w0)
    ring = res["serve"]["ring"]

    def kind(r):
        return ("admit" if r["admitted"] or r["chunks"] else "step") \
            + str(int(bool(r["active"])))

    med = {}
    for r in ring:
        med.setdefault(kind(r), []).append(r["iter_s"])
    med = {k: statistics.median(v) for k, v in med.items()}
    slow = [r for r in ring if r["iter_s"] > 1.5 * med[kind(r)]]
    big = [r for r in ring if r["iter_s"] > med[kind(r)] + 0.060]
    stalled = [r for r in ring if r.get("stall_s", 0.0) > 0.0]
    print(f"  ring, the window's {len(ring)} iterations (medians, ms: "
          f"{ {k: round(v * 1e3, 2) for k, v in med.items()} }): over 1.5x "
          f"their kind's median {len(slow)}, of them with stall_s > 0 "
          f"{sum(1 for r in slow if r.get('stall_s', 0) > 0)} and with gc_s "
          f"> 0 {sum(1 for r in slow if r['gc_s'] > 0)}; over the median + "
          f"60 ms {len(big)}, with stall_s > 0 "
          f"{sum(1 for r in big if r.get('stall_s', 0) > 0)}; records with "
          f"stall_s > 0: {len(stalled)}, "
          f"{sum(r['stall_s'] for r in stalled):.3f} s in all")
    for r in sorted(big, key=lambda r: -r["iter_s"])[:6]:
        print(f"    iter {r['iter']} {kind(r)} at T0+{r['ts'] - t0:.1f}: "
              f"iter_s {r['iter_s'] * 1e3:.1f} ms, stall_s "
              f"{r.get('stall_s', 0) * 1e3:.1f}, gc_s {r['gc_s'] * 1e3:.1f},"
              f" step_wait_s {r['step_wait_s'] * 1e3:.1f}, host_s "
              f"{r['host_s'] * 1e3:.1f}")
    print(f"  engine, since its thread started: stalls {eng['stalls']}, "
          f"stall_s {eng['stall_s']:.3f}, stall_max_s "
          f"{eng['stall_max_s']:.3f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", help="a serve cell to run once")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--read", nargs="*", default=[],
                    help="directories of runs kept earlier")
    args = ap.parse_args()
    outs = list(args.read)
    if args.cell:
        outs.append(run(args.cell, args.seed, args.rehearse))
    for out in outs:
        read(out)


if __name__ == "__main__":
    main()
