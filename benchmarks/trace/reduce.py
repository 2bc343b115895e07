"""From a profiler trace (.xplane.pb) to the numbers the metrics read.

Reads with `jax.profiler.ProfileData` and nothing else.  What the planes
and lines are called on this JAX (0.9.0, libtpu of this installation), as
seen in a real trace of a train step on a v5e — PERF.md section 3 has the
same notes:

  * one plane per chip, `/device:TPU:<n>` (beside `#Chip<n> Host
    Interface` planes, which are not read); its line `XLA Ops` holds one
    event per executed HLO instruction, `XLA Modules` one per executed
    program (`jit_step(<fingerprint>)`), `Steps` one per step;
  * an `XLA Ops` event's NAME IS THE WHOLE HLO TEXT of the instruction
    (`%fusion.33 = bf16[...]{...} fusion(...), kind=kLoop, ...`), so the
    instruction's name and opcode are parsed out of it (`op_name`,
    `opcode`) and metric patterns are matched against the whole text;
  * events nest: a `while` (the scan over layers) is one event that
    contains the events of its body.  Busy time is therefore the UNION of
    intervals, and an operation's time is its SELF time (its duration
    minus its children's), so that operations sum to the busy time;
  * a Pallas (Mosaic) kernel is a `custom-call` whose instruction is named
    after the jax scope it was traced in (see PERF.md section 3 for the
    names of the attention kernels);
  * host threads are lines of the plane `/host:CPU`; a
    `jax.profiler.TraceAnnotation("bench.step")` is an event of that name
    on the calling thread's line.

Everything is averaged over the chips that have an `XLA Ops` line.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?(\.|$)")
_NAME = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"[\]})]\s([a-z][a-z0-9\-]*)\(")
_STAT_KEYS = ("tf_op", "long_name", "hlo_op", "name", "kernel_details",
              "hlo_category")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract_length(a: List[Tuple[float, float]],
                    b: List[Tuple[float, float]]) -> float:
    """Length of (union of a) minus (union of b); both merged+sorted."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _stat_text(ev) -> str:
    if " = " in ev.name:        # the name is the whole HLO text already, and
        return ""               # reading a million events' stats takes a minute
    parts = []
    try:
        for k, v in ev.stats:
            if k in _STAT_KEYS and isinstance(v, str):
                parts.append(v)
    except Exception:
        pass
    return " ".join(parts)


def op_name(text: str) -> str:
    """`%all-gather.7 = f32[...] all-gather(...)` -> `all-gather.7`; a
    text that is already a bare name is returned as it is."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def opcode(text: str) -> str:
    """The HLO opcode of an instruction's text ('' if it has none)."""
    m = _NAME.match(text)
    if not m:
        return ""
    m = _OPCODE.search(text, m.end() - 1)
    return m.group(1) if m else ""


def is_collective(text: str) -> bool:
    return bool(COLLECTIVE.match(opcode(text) or op_name(text)))


def self_times(evs: List[Tuple[float, float, str, str]]) -> List[float]:
    """Each event's duration minus that of the events nested in it (events
    of one line nest properly or are disjoint)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1]))
    selfs = [evs[i][1] - evs[i][0] for i in range(len(evs))]
    stack: List[int] = []
    for i in order:
        while stack and evs[stack[-1]][1] <= evs[i][0]:
            stack.pop()
        if stack and evs[i][1] <= evs[stack[-1]][1]:
            selfs[stack[-1]] -= evs[i][1] - evs[i][0]
        stack.append(i)
    return selfs


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def reduce_trace(path: str, annotation_prefix: str = "bench.") -> Dict:
    """The whole reduction; every time in seconds, per-chip averages."""
    pd = load(path)
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    devs = []
    for p in planes:
        ln = _line(p, OPS_LINE)
        if ln is None:
            continue
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                _stat_text(e)) for e in ln.events]
        if evs:
            mods = _line(p, MODULES_LINE)
            devs.append((p.name, evs, [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in (mods.events if mods is not None else ())]))
    out = {"n_devices": len(devs), "window_s": 0.0, "busy_s": 0.0,
           "ops": {}, "modules": {}, "collective_s": 0.0,
           "collective_exposed_s": 0.0, "gaps": [], "planes":
           [p.name for p in pd.planes]}
    if not devs:
        return out
    n = len(devs)
    t_lo = min(e[0] for _, evs, _ in devs for e in evs)
    t_hi = max(e[1] for _, evs, _ in devs for e in evs)
    out["window_s"] = (t_hi - t_lo) * 1e-9
    ops: Dict[str, Dict] = {}
    mods: Dict[str, Dict] = {}
    for _, evs, mevs in devs:
        out["busy_s"] += union_length((s, e) for s, e, _, _ in evs) * 1e-9 / n
        coll = merged((s, e) for s, e, nm, _ in evs if is_collective(nm))
        comp = merged((s, e) for s, e, nm, _ in evs if not is_collective(nm))
        out["collective_s"] += sum(e - s for s, e in coll) * 1e-9 / n
        out["collective_exposed_s"] += subtract_length(coll, comp) * 1e-9 / n
        for (s, e, nm, txt), own in zip(evs, self_times(evs)):
            o = ops.setdefault(op_name(nm), {
                "s": 0.0, "n": 0, "op": opcode(nm),
                "text": (nm + " " + txt)[:4000]})
            o["s"] += max(0.0, own) * 1e-9 / n
            o["n"] += 1.0 / n
        for s, e, nm in mevs:
            m = mods.setdefault(nm, {"s": 0.0, "n": 0})
            m["s"] += (e - s) * 1e-9 / n
            m["n"] += 1.0 / n
    out["ops"], out["modules"] = ops, mods
    out["gaps"] = _gaps(pd, devs[0][1], annotation_prefix)
    return out


def _gaps(pd, dev_events, prefix: str, top: int = 10) -> List[List]:
    """Idle gaps of the first chip, each charged to the host annotation
    (an event named `<prefix>...` on any host line) that overlaps it most;
    summed by label, longest first."""
    busy = merged((s, e) for s, e, _, _ in dev_events)
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] > busy[i][1]]
    notes = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    notes.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    notes.sort()
    by: Dict[str, float] = {}
    for gs, ge in gaps:
        best, best_ov = "host, unattributed", 0.0
        for ns, ne, nm in notes:
            if ns >= ge:
                break
            ov = min(ge, ne) - max(gs, ns)
            if ov > best_ov:
                best, best_ov = nm, ov
        by[best] = by.get(best, 0.0) + (ge - gs) * 1e-9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])
            ][:top]


def ops_matching(red: Dict, pattern: str) -> Tuple[float, float]:
    """(self seconds, count) of ops whose HLO text (+ stats) matches."""
    rx = re.compile(pattern)
    s = c = 0.0
    for nm, o in red["ops"].items():
        if rx.search(o.get("text") or nm):
            s += o["s"]
            c += o["n"]
    return s, c


def module_time(red: Dict, pattern: str) -> Tuple[float, float]:
    """(seconds, executions) of the programs (`XLA Modules` events) whose
    name matches; per chip."""
    rx = re.compile(pattern)
    s = n = 0.0
    for name, m in red["modules"].items():
        if rx.search(name):
            s += m["s"]
            n += m["n"]
    return s, n


def top_ops(red: Dict, top: int = 10) -> List[List]:
    """Device operations by self time, instruction numbers folded
    (`fusion.12`, `fusion.7` -> `fusion`) and the opcode added where the
    name does not say it, longest first."""
    by: Dict[str, float] = {}
    for nm, o in red["ops"].items():
        key = re.sub(r"[.\d]+$", "", nm) or nm
        if o.get("op") and o["op"] not in key:
            key = f"{key} ({o['op']})"
        by[key] = by.get(key, 0.0) + o["s"]
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])
            ][:top]
