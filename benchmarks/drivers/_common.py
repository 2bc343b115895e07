"""What a driver needs in the process it runs in."""

from __future__ import annotations

import json
import os
import sys
from typing import Dict


def say(**fields):
    """An earlier line of stdout (never the last): progress and context."""
    print(json.dumps(fields), flush=True)


def fail(msg: str, code: int = 1):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def device_identity(chips: int, rehearse: bool) -> Dict:
    """Initialises the backend of THIS process.  No TPU, or fewer chips
    than the cell asks for, ends the run with a non-zero exit and no
    result — never a CPU number under a device metric's name."""
    import jax

    devs = jax.devices()
    ident = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if not rehearse and ident["platform"] != "tpu":
        fail(f"jax found no TPU: platform is {ident['platform']!r}", 3)
    if len(devs) < chips:
        fail(f"the cell needs {chips} chip(s), jax sees {len(devs)}", 3)
    return ident


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip of this process: the allocator's
    `peak_bytes_in_use` (live arrays) plus `peak_bytes_reserved`, the region
    the runtime reserves for a running program's temporaries — on this
    libtpu the first does not contain the second (a train step's 8 GB of
    scratch shows only there).  0 where the backend reports neither, as
    the CPU's does not."""
    import jax

    peak = 0
    for d in jax.local_devices():
        try:
            st = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - a backend without the call
            st = {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def start_trace(trace_dir: str):
    """Device trace + host TraceMe annotations, without the Python call
    tracer (tens of thousands of events a second, and it slows the host
    that the run is measuring)."""
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_trace():
    import jax

    jax.profiler.stop_trace()


def write_json(path: str, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)

