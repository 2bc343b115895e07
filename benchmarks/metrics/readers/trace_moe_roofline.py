"""The grouped expert products' share of their roofline, from the trace
and the engine's counters: the least time the chip could take for the
token-expert pairs and the experts touched (lib/costs_moe.py: the larger
of operations / peak FLOP/s and bytes / peak bytes/s) over the self time
the trace shows for the ops matching `ops` (the `ragged-dot` custom calls
and their metadata kernel).

The trace covers a few seconds of the window, the ring all of it.  Each
ring record inside the traced stretch gives the pairs and touched experts
of the programs that run grouped products (`programs`: module pattern,
the record's keys for pairs and touched experts, and the key that counts
the program's executions in the record — a prefill chunk's `chunk_moe_*`
and `chunks`; a decode step's `moe_*` and `active` where its experts are
grouped too); their least times are averaged per program and multiplied
by the executions the trace itself counts (`XLA Modules` events matching
the pattern), so a program cut by the trace's edge is not counted on one
side only.

A ring without these counters, or a trace without these ops — a program
without the expert layer — is nothing to read: None."""

from benchmarks.lib import costs_moe, peaks
from benchmarks.trace.reduce import module_time, ops_matching


def read(obs, params, ctx):
    red, sv = obs["trace"], obs["serve"]
    measured, n = ops_matching(red, params["ops"])
    span = sv.get("traced")
    if not n or not span:
        return None
    pk = peaks.peak(ctx["device"]["kind"])
    ring = [r for r in sv["ring"] if span[0] <= r["ts"] <= span[1]]
    need = 0.0
    for module, pairs, touched, count in params["programs"]:
        try:
            recs = [r for r in ring if r[count]]
            least = sum(costs_moe.least_seconds(r[pairs], r[touched],
                                                ctx["config"], pk)
                        for r in recs)
            programs = sum(1 if count == "active" else r[count]
                           for r in recs)
        except KeyError:
            return None
        if programs:
            need += least / programs * module_time(red, module)[1]
    return 100.0 * need / measured if measured and need else None
