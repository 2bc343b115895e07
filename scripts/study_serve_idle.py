#!/usr/bin/env python3
"""What the host was doing while the device idled, by hand, from one kept
run of a serve cell (`BENCH_KEEP_TRACE=1 python3 benchmarks/run.py
--workload <cell> --trace 1 ...`, then `python scripts/study_serve_idle.py
.bench_run/<cell>-1`; `--ring-only` for an untraced run's directory).

Two readings of the same iterations, which should agree:

* the trace: the device's idle time (the traced stretch less the union of
  its `XLA Ops`), cut at every boundary of the engine thread's
  `serve.engine.*` annotations and charged to the INNERMOST one — inside
  a running program (between two ops of one `XLA Modules` event) and
  between programs apart;
* the ring (`result.json`, the whole window): an iteration's wall as
  Python (`host_s`), launches (`dispatch_s`) and waits (`ready_wait_s`,
  `waits` of them), plain iterations and admitting ones apart, the
  step's own two parts beside the step's device time — joined to the
  trace by the `iter=` stat of `serve.engine.admit`, not by clock.  Since
  PR 52 one step stays in flight, and an iteration is launches -> fetch ->
  stamp: the admission's programs (`admission_dispatch_ms` = `dispatch_s`
  less `step_dispatch_s`) and THIS iteration's step enqueued, then the
  tokens of the step launched an iteration AGO fetched (`step_wait_s`: how
  long the thread was blocked for them) and emitted, then the prefill's
  ready stamp (`admission_wait_ms` = `ready_wait_s` less `step_wait_s`).
  `steps` counts the steps launched (`stepped`) and those launched with
  the one before them unfetched (`ahead`); `gaps` is what a stream sees:
  emit to emit between two plain iterations, and across an iteration that
  admits one request (its own tail behind the emit plus the next
  iteration up to its emit).  A ring without `stepped` is a tree's from
  before PR 52 (launches -> stamp -> fetch, nothing in flight between
  iterations) and is read by that order.

It also lists the window's iterations that stand out from their kind by
50 ms or more with their `gc_s` (was that stall a collection?).  Prints
one JSON object; a study's tool, read by nothing."""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

PREFIX = "serve.engine."


def _stats(ev):
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # noqa: BLE001 - a stat the reader cannot decode
        return {}


def innermost_segments(notes):
    """Properly nested (start, end, name) -> disjoint (start, end, path)
    pieces in time order, `path` outermost first."""
    cuts = sorted({t for s, e, _ in notes for t in (s, e)})
    notes = sorted(notes, key=lambda n: (n[0], -n[1]))
    out, stack, i = [], [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while stack and stack[-1][1] <= lo:
            stack.pop()
        while i < len(notes) and notes[i][0] <= lo:
            if notes[i][1] > lo:
                stack.append(notes[i])
            i += 1
        while stack and stack[-1][1] <= lo:
            stack.pop()
        if stack:
            out.append((lo, hi, tuple(n[2] for n in stack)))
    return out


def overlap_by(gaps, pieces):
    """Seconds of `gaps` (sorted, disjoint) under each piece's label, and
    the seconds under no piece."""
    by, j, covered = {}, 0, 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                by[pieces[k][2]] = by.get(pieces[k][2], 0) + ov
                covered += ov
            k += 1
    total = sum(e - s for s, e in gaps)
    return by, total - covered


# the device programs one launch runs, first to last, by the ledger's name
MODULES = {"serve.step": ("jit_serve_step",),
           "serve.prefill": ("jit_serve_prefill",),
           "serve.setrow": ("jit_serve_setrow",),
           "serve.copy_page": ("jit_serve_copy_page",),
           "serve.keys": ("jit_convert_element_type", "jit__threefry_split")}


def join_launches(launches, notes, mods):
    """Each `dispatch` annotation beside the program it launched (the
    module event of that name nearest its end) and the wait that follows:
    medians, in ms, of program start less call entered, call returned less
    program start (positive: the device started before the call came
    back), and wait's end less program end (the way back to Python).  A
    program cannot start before its launch began: a negative first number
    would say the two clocks are offset."""
    by_name = {}
    for s, e, nm in mods:
        by_name.setdefault(nm.split("(")[0], []).append((s, e))
    # what ends a program's wait: a step's tokens are fetched; the keys'
    # `wait` lies under a `keys` annotation; a prefill's is the first
    # other `wait` behind its launch — its ready stamp, behind the step's
    # launch (before it, in a tree from before PR 43)
    keyed = [(s, e) for s, e, n in notes if n == "keys"]
    waits = {"serve.step": [], "serve.keys": [], "serve.prefill": []}
    for s, e, n in sorted(notes):
        if n == "fetch":
            waits["serve.step"].append((s, e))
        elif n == "wait":
            under = any(ks <= s and e <= ke for ks, ke in keyed)
            waits["serve.keys" if under else "serve.prefill"].append((s, e))
    out = {}
    for ls, le, pr in sorted(launches):
        if pr not in MODULES:
            continue
        first, last = (by_name.get(m, []) for m in (MODULES[pr][0],
                                                    MODULES[pr][-1]))
        if not first or not last:
            continue
        a = min(first, key=lambda m: abs(m[0] - le))
        z = min(last, key=lambda m: abs(m[0] - le))
        w = next(((s, e) for s, e in waits.get(pr, ()) if s >= le), None)
        row = out.setdefault(pr, {"start_after_entered": [],
                                  "returned_after_start": [],
                                  "wait_end_after_program_end": []})
        row["start_after_entered"].append((a[0] - ls) * 1e-6)
        row["returned_after_start"].append((le - a[0]) * 1e-6)
        # a row's wait is its prefill's; a copy has none of its own
        if w is not None:
            row["wait_end_after_program_end"].append((w[1] - z[1]) * 1e-6)
    return {pr: {"n": len(r["start_after_entered"]), **{
        k: {"median": statistics.median(v), "min": min(v), "max": max(v)}
        for k, v in r.items() if v}} for pr, r in out.items()}


def trace_side(path):
    from benchmarks.trace import reduce as R

    pd = R.load(path)
    dev = next(p for p in pd.planes if R.DEVICE_PLANE.match(p.name)
               and R._line(p, R.OPS_LINE) is not None)
    ops = R.merged((e.start_ns, e.start_ns + e.duration_ns)
                   for e in R._line(dev, R.OPS_LINE).events)
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in R._line(dev, R.MODULES_LINE).events)
    t_lo, t_hi = ops[0][0], ops[-1][1]
    notes, iters, programs, launches = [], set(), {}, []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if not e.name.startswith(PREFIX):
                    continue
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= t_lo or s >= t_hi:
                    continue
                notes.append((s, t, e.name[len(PREFIX):]))
                st = _stats(e)
                if e.name == PREFIX + "admit" and "iter" in st:
                    iters.add(int(st["iter"]))
                if e.name == PREFIX + "dispatch":
                    pr = str(st.get("program", "?")).split(":")[0]
                    d = programs.setdefault(pr, [0, 0.0])
                    d[0] += 1
                    d[1] += (t - s) * 1e-9
                    launches.append((s, t, pr))
    pieces = innermost_segments(notes)
    running = R.merged((s, e) for s, e, _ in mods)
    gaps = [(a[1], b[0]) for a, b in zip(ops, ops[1:]) if b[0] > a[1]]
    # a gap is inside a program or between two: cut it at the programs' edges
    inside, between = [], []
    j = 0
    for gs, ge in gaps:
        while j < len(running) and running[j][1] <= gs:
            j += 1
        cur, k = gs, j
        while k < len(running) and running[k][0] < ge:
            if running[k][0] > cur:
                between.append((cur, running[k][0]))
            lo, hi = max(cur, running[k][0]), min(ge, running[k][1])
            if hi > lo:
                inside.append((lo, hi))
            cur = max(cur, hi)
            k += 1
        if cur < ge:
            between.append((cur, ge))

    def table(gs):
        by, bare = overlap_by(gs, pieces)
        inner, paths = {}, {}
        for path_, ns in by.items():
            inner[path_[-1]] = inner.get(path_[-1], 0) + ns
            paths[">".join(path_)] = ns
        return ({k: v * 1e-9 for k, v in sorted(inner.items(),
                                                key=lambda kv: -kv[1])},
                {k: v * 1e-9 for k, v in sorted(paths.items(),
                                                key=lambda kv: -kv[1])},
                bare * 1e-9)

    idle = sum(e - s for s, e in gaps) * 1e-9
    all_inner, all_paths, bare = table(gaps)
    # the host's tracer may start after the device's and stop before it:
    # idle time outside the span the annotations cover is the session's
    h_lo, h_hi = min(n[0] for n in notes), max(n[1] for n in notes)
    edges = sum(max(0, min(e, h_lo) - s) + max(0, e - max(s, h_hi))
                for s, e in gaps) * 1e-9
    module_ms = {}
    for s, e, nm in mods:
        d = module_ms.setdefault(nm.split("(")[0], [0, 0.0])
        d[0] += 1
        d[1] += (e - s) * 1e-6
    return {
        "launch_to_program": join_launches(launches, notes, mods),
        "window_s": (t_hi - t_lo) * 1e-9,
        "busy_s": sum(e - s for s, e in ops) * 1e-9, "idle_s": idle,
        "idle_under_an_annotation_share": 1 - bare / idle if idle else None,
        "idle_before_first_or_after_last_annotation_s": edges,
        "idle_under_an_annotation_share_where_annotated": (
            1 - (bare - edges) / (idle - edges) if idle > edges else None),
        "idle_by_innermost_s": all_inner,
        "idle_by_innermost_share": {k: v / idle for k, v in
                                    all_inner.items()} if idle else {},
        "idle_by_path_s": all_paths,
        "idle_inside_programs_s": sum(e - s for s, e in inside) * 1e-9,
        "idle_between_programs_by_innermost_s": table(between)[0],
        "dispatch_annotations": {k: {"n": n, "s": s, "ms_each": 1e3 * s / n}
                                 for k, (n, s) in programs.items()},
        "modules_ms_each": {k: {"n": n, "ms": ms / n}
                            for k, (n, ms) in module_ms.items()},
        "iterations": sorted(iters),
    }


def _med(xs):
    return statistics.median(xs) if xs else None


def ring_side(ring, only=None):
    """The split of an iteration by kind; `only`: the ordinals to keep."""
    if only is not None:
        ring = [r for r in ring if r.get("iter") in only]
    if not ring:
        return {"iterations": 0}
    have = [k for k in ("iter_s", "host_s", "dispatch_s", "ready_wait_s",
                        "step_dispatch_s", "step_wait_s", "decode_s",
                        "swap_s", "prefill_s") if k in ring[0]]
    # one step in flight (PR 52)?  A plain iteration then launches a step
    # AND emits one; the first after an idle stretch only launches
    # (`filling`), the last only emits (`draining`)
    ahead = "stepped" in ring[0]
    kinds = {"plain": [r for r in ring if r["active"] and not r["admitted"]
                       and not r["chunks"] and r.get("stepped", 1)],
             "admitting": [r for r in ring if r["admitted"]],
             "admitting_one": [r for r in ring if r["admitted"] == 1],
             "chunk_only": [r for r in ring if r["chunks"]
                            and not r["admitted"]]}
    out = {"iterations": len(ring)}
    if ahead:
        kinds["filling"] = [r for r in ring if r["stepped"]
                            and not r["ahead"]]
        kinds["draining"] = [r for r in ring if r["active"]
                             and not r["stepped"]]
        n, a = (sum(r[k] for r in ring) for k in ("stepped", "ahead"))
        out["steps"] = {"stepped": n, "ahead": a,
                        "ahead_share": a / n if n else None}
    out["gaps"] = stream_gaps(ring, ahead)
    for kind, rs in kinds.items():
        out[kind] = {"n": len(rs), **{
            k + "_ms": 1e3 * _med([r[k] for r in rs]) if rs else None
            for k in have},
            "launches": _med([r.get("launches", 0) for r in rs]),
            # the parent's ring has no `waits`: None there
            "waits": _med([r["waits"] for r in rs if "waits" in r])}
        if rs and "step_wait_s" in have:
            out[kind]["admission_wait_ms"] = 1e3 * _med(
                [r["ready_wait_s"] - r["step_wait_s"] for r in rs])
            out[kind]["admission_dispatch_ms"] = 1e3 * _med(
                [r["dispatch_s"] - r["step_dispatch_s"] for r in rs])
    # what lies between one record's close and the next one's start: the
    # loop's own turn, or the engine asleep with nothing to run
    ends = [(r["t0"], r["t0"] + r["iter_s"]) for r in ring]
    between = [b[0] - a[1] for a, b in zip(ends, ends[1:])]
    out["between_iterations_s"] = sum(between)
    out["between_iterations_over_1ms"] = sum(1 for g in between if g > 1e-3)
    out["between_iterations_median_us"] = 1e6 * _med(between) if between else None
    tot = {k: sum(r[k] for r in ring) for k in (
        "iter_s", "host_s", "device_wait_s", "dispatch_s", "ready_wait_s",
        "gc_s") if k in ring[0]}
    out["sums_s"] = tot
    if "dispatch_s" not in tot:
        return out
    out["dispatch_plus_wait_over_device_wait"] = (
        (tot["dispatch_s"] + tot["ready_wait_s"]) / tot["device_wait_s"])
    out["host_plus_device_wait_over_iter"] = (
        (tot["host_s"] + tot["device_wait_s"]) / tot["iter_s"])
    out["dispatch_share"] = tot["dispatch_s"] / tot["iter_s"]
    plain = kinds["plain"]
    if plain:
        out["plain_decode_minus_step_parts_ms"] = 1e3 * (
            _med([r["decode_s"] for r in plain])
            - _med([r["step_dispatch_s"] + r["step_wait_s"] for r in plain]))
    return out


def stream_gaps(ring, ahead):
    """Emit to emit, in ms, as a stream sees it: between two plain
    iterations, and across an iteration that admits ONE request (from its
    emit to the next iteration's).  An iteration's emit ends at `ts`, but
    for one that admits with a step in flight: there the prefill's stamp
    comes behind the emit (`ts` less that wait)."""
    def quiet(r):
        return not r["admitted"] and not r["chunks"]

    def emitted(r):
        if ahead and not quiet(r):
            return r["ts"] - (r["ready_wait_s"] - r["step_wait_s"])
        return r["ts"]

    plain, across = [], []
    for p, r in zip(ring, ring[1:]):
        if not (p["active"] and r["active"]
                and r.get("iter", 0) == p.get("iter", -2) + 1
                # (the engine did not sleep between the two)
                and r["t0"] - p["t0"] - p["iter_s"] < 1e-3):
            continue
        gap = 1e3 * (emitted(r) - emitted(p))
        if quiet(p) and quiet(r):
            plain.append(gap)
        # with a step in flight the admission's prefill runs between the
        # emit of the iteration that launched it and the next one's; with
        # none it ran inside the admitting iteration, ahead of its emit
        one = p if ahead else r
        if one["admitted"] == 1 and quiet(r if ahead else p):
            across.append(gap)

    def dist(xs):
        xs = sorted(xs)
        return {"n": len(xs), **({} if not xs else {
            "median": statistics.median(xs),
            "p90": xs[int(0.9 * (len(xs) - 1))], "max": xs[-1]})}

    return {"plain_ms": dist(plain), "across_one_admission_ms": dist(across)}


def stalls(ring, by_ms=50.0):
    """Iterations at least `by_ms` longer than the median of their kind
    (same admissions, chunks, and a step or none)."""
    kinds = {}
    for r in ring:
        kinds.setdefault((r["admitted"], r.get("chunks", 0),
                          bool(r["active"])), []).append(r["iter_s"])
    med = {k: statistics.median(v) for k, v in kinds.items()}
    out = []
    for r in ring:
        k = (r["admitted"], r.get("chunks", 0), bool(r["active"]))
        over = 1e3 * (r["iter_s"] - med[k])
        if over >= by_ms:
            out.append({"iter": r.get("iter"), "over_ms": over, **{
                key + "_ms": 1e3 * r[key] for key in (
                    "iter_s", "host_s", "dispatch_s", "ready_wait_s", "gc_s")
                if key in r}, "admitted": r["admitted"],
                "chunks": r.get("chunks", 0)})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("rundir")
    ap.add_argument("--ring-only", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(args.rundir, "result.json")) as f:
        ring = json.load(f)["serve"]["ring"]
    out = {"window": ring_side(ring), "stalls": stalls(ring),
           "longest_iter_ms": 1e3 * max(r["iter_s"] for r in ring),
           "gc_s_max_in_an_iteration_ms": 1e3 * max(
               (r.get("gc_s", 0.0) for r in ring), default=0.0)}
    if not args.ring_only:
        tr = trace_side(os.path.join(args.rundir, "trace"))
        traced = set(tr.pop("iterations"))
        out["trace"] = tr
        out["traced_iterations"] = ring_side(ring, traced)
        t = out["traced_iterations"].get("sums_s")
        if t and tr["idle_s"]:
            # the host's whole dispatch time over the device's idle time,
            # beside the idle time the trace finds under `dispatch`
            out["ring_dispatch_s_over_trace_idle_s"] = (
                t["dispatch_s"] / tr["idle_s"])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
