"""One scope's share of its roofline in one serve program, from the trace
and the engine's counters: the least time the chip could take for what the
program's records count (`costs`: a module of benchmarks/lib with
`least_seconds(program, record, config, peak)` — the larger of operations
/ peak FLOP/s and bytes / peak bytes/s) over the self time the trace shows
under the `jax.named_scope` `scope` (the driver's seconds by scope,
trace/scopes.py).

The trace covers a few seconds of the window, the ring all of it.  Each
ring record inside the traced stretch that ran the program (`count`:
`active` for a decode step, `chunks` for prefill chunks) gives its least
time; they are averaged per program and multiplied by the executions the
trace itself counts (`XLA Modules` events matching `module`), as
trace_retention_roofline does, so a program cut by the trace's edge is not
counted on one side only.

A run without scopes, or a ring without these counters — a program without
this layer — is nothing to read: None."""

import importlib

from benchmarks.lib import peaks
from benchmarks.trace.reduce import module_time


def read(obs, params, ctx):
    sv = obs["serve"]
    span = sv.get("traced")
    measured = (sv.get("scopes") or {}).get(params["scope"], 0.0)
    if not measured or not span:
        return None
    costs = importlib.import_module("benchmarks.lib." + params["costs"])
    pk = peaks.peak(ctx["device"]["kind"])
    count = params["count"]
    try:
        recs = [r for r in sv["ring"]
                if span[0] <= r["ts"] <= span[1] and r[count]]
        least = sum(costs.least_seconds(params["program"], r, ctx["config"],
                                        pk) for r in recs)
        programs = sum(1 if count == "active" else r[count] for r in recs)
    except KeyError:
        return None
    if not programs:
        return None
    need = least / programs * module_time(obs["trace"], params["module"])[1]
    return 100.0 * need / measured if need else None
