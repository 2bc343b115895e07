"""Reading a cell's metrics through their readers, and the last line."""

from __future__ import annotations

import importlib
import json
import math
import sys
from typing import Dict, List

from .peaks import UnknownDevice


def read_metrics(metrics: List[Dict], obs: Dict, ctx: Dict,
                 prefix: str = "", lenient: bool = False
                 ) -> Dict[str, Dict]:
    """Each metric through `benchmarks/metrics/readers/<reader>.py`'s
    `read(obs, params, ctx)`.  A reader that finds nothing to read returns
    None and the metric is left out of the line."""
    out = {}
    for m in metrics:
        mod = importlib.import_module(
            f"benchmarks.metrics.readers.{m['reader']}")
        try:
            v = mod.read(obs, m["params"], ctx)
        except (KeyError, IndexError, ZeroDivisionError, UnknownDevice) as e:
            if isinstance(e, UnknownDevice) and not lenient:
                raise       # a chip without a published peak is an error
            print(f"bench: reader {m['reader']!r} for {m['name']!r} found "
                  f"nothing to read ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
            v = None
        if v is None or not math.isfinite(v):
            continue
        out[prefix + m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def last_line(correct: bool, attempted: int, failed: int, metrics: Dict,
              device: Dict, breakdown: Dict = None) -> str:
    obj = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        obj["breakdown"] = breakdown
    return json.dumps(obj)
