#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and the events that took
most time with their stats, and the full HLO text of custom calls and
collectives.  `python benchmarks/trace/inspect.py <dir|file>`; a traced run
keeps its trace under `.bench_run/<cell>-1/trace` when BENCH_KEEP_TRACE=1 is
in its environment (a debugging aid, read nowhere else)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    from benchmarks.trace import reduce as R

    pd = R.load(sys.argv[1])
    for p in pd.planes:
        print("PLANE", p.name)
        for ln in p.lines:
            evs = list(ln.events)
            if not evs:
                continue
            by = {}
            for e in evs:
                b = by.setdefault(e.name, [0.0, 0, None])
                b[0] += e.duration_ns
                b[1] += 1
                if b[2] is None:
                    try:
                        b[2] = [(k, str(v)[:160]) for k, v in e.stats]
                    except Exception as ex:  # noqa: BLE001
                        b[2] = repr(ex)
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {ln.name!r}: {len(evs)} events, {len(by)} names, "
                  f"span {(t1 - t0) * 1e-9:.4f}s from {t0}")
            for nm, (d, c, st) in sorted(by.items(),
                                         key=lambda kv: -kv[1][0])[:25]:
                print(f"    {d * 1e-9:10.6f}s x{c:<6} {nm[:90]}  {st}")
            seen = set()
            for nm, (d, c, st) in sorted(by.items(),
                                         key=lambda kv: -kv[1][0]):
                op = R.opcode(nm)
                key = (R.op_name(nm).rstrip("0123456789."), op)
                if (op == "custom-call" or R.is_collective(nm)) \
                        and key not in seen and len(seen) < 40:
                    seen.add(key)
                    print(f"    FULL {d * 1e-9:.6f}s x{c} {nm[:1500]}")


if __name__ == "__main__":
    main()
