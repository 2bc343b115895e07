"""Device time by `jax.named_scope`, for programs whose scopes the trace
does not show.

On this JAX an `XLA Ops` event's name is its instruction's HLO text
WITHOUT `metadata={op_name=...}`, and its stats are offsets and durations
only (looked at on a v5e trace, PR 27): a scope such as `attn_window` is
nowhere in the trace.  It is in the compiled program's text, instruction
by instruction.  So the process that owns the programs hands over, per
program, {instruction name: scope} (`scope_map` of its compiled text), and
`scope_seconds` charges every `XLA Ops` event's SELF time (reduce.py's
rule: its duration less its children's) to the scope of its instruction in
the program (`XLA Modules` event) that was running.

Programs are told apart by name prefix (`jit_serve_step`); where several
share one (a prefill program per chunk size) an executed program takes the
map that knows most of its instructions.  An instruction of no scope, or
of a program without a map, is charged to no scope.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Sequence

from . import reduce as R

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_map(hlo_text: str, scopes: Sequence[str],
              by_name: Dict[str, str] = None) -> Dict[str, str]:
    """{instruction: scope} for the instructions of a compiled program's
    text whose `op_name` runs through one of `scopes` (the innermost, where
    they nest); `by_name` charges instructions by a prefix of their own
    name (a custom call that lost its op_name)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        hit = next((s for p, s in (by_name or {}).items()
                    if name.startswith(p)), None)
        if hit is None:
            op = _OP_NAME.search(line)
            parts = op.group(1).split("/") if op else ()
            hit = next((p for p in reversed(parts) if p in scopes), None)
        if hit is not None:
            out[name] = hit
    return out


def scope_seconds(trace_dir: str, programs: Dict[str, List[Dict[str, str]]]
                  ) -> Dict[str, float]:
    """{scope: self seconds} over the first chip's `XLA Ops` line;
    `programs` maps a module-name prefix to its candidate maps."""
    pd = R.load(trace_dir)
    plane = next((p for p in pd.planes if R.DEVICE_PLANE.match(p.name)
                  and R._line(p, R.OPS_LINE) is not None), None)
    if plane is None:
        return {}
    mods = R._line(plane, R.MODULES_LINE)
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in (mods.events if mods is not None else ()))
    starts = [s for s, _, _ in spans]
    evs = [(e.start_ns, e.start_ns + e.duration_ns, R.op_name(e.name), "")
           for e in R._line(plane, R.OPS_LINE).events]
    selfs = R.self_times(evs)
    by_module: Dict[str, List[int]] = {}
    for i, (s, _, _, _) in enumerate(evs):
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < spans[k][1]:
            by_module.setdefault(spans[k][2], []).append(i)
    out: Dict[str, float] = {}
    for module, idx in by_module.items():
        maps = next((m for p, m in programs.items()
                     if module.startswith(p)), None)
        if not maps:
            continue
        names = {evs[i][2] for i in idx}
        best = max(maps, key=lambda m: len(names & m.keys()))
        for i in idx:
            scope = best.get(evs[i][2])
            if scope is not None:
                out[scope] = out.get(scope, 0.0) + max(0.0, selfs[i]) * 1e-9
    return out
