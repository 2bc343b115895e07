"""Writes tests/fixture_trace.textproto: a hand-made XSpace with known
answers (times in microseconds below; the file holds picoseconds).

Chip 0 `XLA Ops`:   fusion.1 [0,40)  all-gather.1 [30,60)  fusion.2 [70,100)
                    flash_fwd custom-call.3 [100,120)   all-reduce.2 [150,170)
  busy = [0,60) + [70,120) + [150,170) = 130 of a 170 window
  collectives = 30 + 20 = 50; exposed (no compute beside) = [40,60) + 20 = 40
  gaps: [60,70) under host `bench.fetch`, [120,150) under `bench.fence`
Chip 1: the same events shifted by nothing (so per-chip averages equal chip 0).
`XLA Modules`: jit_step [0,120) and jit_step [150,170) -> 2 executions, 140 us.
"""

import os

US = 1_000_000  # picoseconds in a microsecond

OPS = [("fusion.1", 0, 40, ""), ("all-gather.1", 30, 30, ""),
       ("fusion.2", 70, 30, ""),
       ("custom-call.3", 100, 20, "jit(step)/pallas_call[name=flash_fwd]"),
       ("all-reduce.2", 150, 20, "")]
MODS = [("jit_step(123)", 0, 120), ("jit_step(123)", 150, 20)]
HOST = [("bench.step", 0, 55), ("bench.fetch", 58, 14), ("bench.fence", 118, 40)]


def plane(pid, name, lines):
    meta, out = {}, [f'planes {{\n  id: {pid}\n  name: "{name}"']
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f'  lines {{\n    id: {lid}\n    name: "{lname}"\n'
                   f'    timestamp_ns: 0')
        for ev in events:
            nm, start, dur = ev[0], ev[1], ev[2]
            mid = meta.setdefault(nm, len(meta) + 1)
            stat = ""
            if len(ev) > 3 and ev[3]:
                stat = (f' stats {{ metadata_id: 1 str_value: "{ev[3]}" }}')
            out.append(f'    events {{ metadata_id: {mid} offset_ps: '
                       f'{start * US} duration_ps: {dur * US}{stat} }}')
        out.append("  }")
    for nm, mid in meta.items():
        out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{nm}" }} }}')
    out.append('  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }')
    out.append("}")
    return "\n".join(out)


def main():
    text = "\n".join([
        plane(1, "/device:TPU:0", [("XLA Ops", OPS), ("XLA Modules", MODS)]),
        plane(2, "/device:TPU:1", [("XLA Ops", OPS), ("XLA Modules", MODS)]),
        plane(3, "/host:CPU", [("python", HOST)])]) + "\n"
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "fixture_trace.textproto"), "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
