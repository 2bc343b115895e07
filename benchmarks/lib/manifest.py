"""BENCHMARK.json -> the files one cell is made of.

Everything a cell needs is found by NAME: `workloads[name]` gives the
configuration (`configs[*].file`), the traffic mix
(`benchmarks/traffic/<traffic>.json`) and, through the metric entries that
list the cell (or list no cells), the metric files
(`benchmarks/metrics/<metric>.json`).  A later PR adds a cell by adding such
files and entries; nothing here is edited.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _check_name(what: str, name: Any):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(
            f"{what} {name!r}: a name starts with a letter, a digit or '_' "
            f"and has at most 64 of letters, digits, '_', '.', '-'")


def _check_metric(m: Dict[str, Any], kind: str):
    _check_name(f"{kind} metric", m.get("name"))
    unit = m.get("unit")
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(
            f"metric {m['name']!r}: unit {unit!r} must be 1-16 of letters, "
            f"digits, '_', '/', '%', '.', '-'")
    if m.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {m['name']!r}: better must be "
                            f"'lower' or 'higher'")
    if m.get("source") not in SOURCES:
        raise ManifestError(f"metric {m['name']!r}: source must be one of "
                            f"{SOURCES}")
    for w in m.get("workloads", ()):
        _check_name(f"metric {m['name']!r} workload", w)


def validate(man: Dict[str, Any]) -> Dict[str, Any]:
    """Refuse what the contract refuses, as far as names, units and the
    links between entries go; returns the manifest."""
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in man:
            raise ManifestError(f"BENCHMARK.json lacks {key!r}")
    configs, cells = {}, {}
    for c in man["configs"]:
        _check_name("config", c.get("name"))
        if c["name"] in configs:
            raise ManifestError(f"config {c['name']!r} appears twice")
        for k in c.get("reduced", ()):
            _check_name(f"config {c['name']!r} reduced key", k)
        configs[c["name"]] = c
    for w in man["workloads"]:
        _check_name("workload", w.get("name"))
        _check_name(f"workload {w['name']!r} traffic", w.get("traffic"))
        if w["name"] in cells:
            raise ManifestError(f"workload {w['name']!r} appears twice")
        if w.get("config") not in configs:
            raise ManifestError(f"workload {w['name']!r} names no config "
                                f"of this file: {w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            raise ManifestError(f"workload {w['name']!r}: chips is 1 or 4")
        cells[w["name"]] = w
    seen = set()
    e2e = {m["name"] for m in man["end_to_end"] if isinstance(m, dict)
           and isinstance(m.get("name"), str)}
    for kind in ("end_to_end", "per_layer"):
        for m in man[kind]:
            _check_metric(m, kind)
            if m["name"] in seen:
                raise ManifestError(f"metric {m['name']!r} appears twice")
            seen.add(m["name"])
            for w in m.get("workloads", ()):
                if w not in cells:
                    raise ManifestError(f"metric {m['name']!r} lists an "
                                        f"unknown workload {w!r}")
            if kind == "per_layer" and m.get("moves") not in e2e:
                raise ManifestError(f"metric {m['name']!r} moves "
                                    f"{m.get('moves')!r}, which is no "
                                    f"end-to-end metric")
    return man


def load(path: str = None) -> Dict[str, Any]:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return validate(json.load(f))


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def metrics_for(man: Dict[str, Any], cell: str, kind: str) -> List[Dict]:
    return [m for m in man[kind]
            if "workloads" not in m or cell in m["workloads"]]


def resolve(man: Dict[str, Any], cell: str) -> Dict[str, Any]:
    """The cell with its files read: config, traffic, and each metric's
    entry joined with its `metrics/<name>.json` (reader key + params)."""
    w = next((w for w in man["workloads"] if w["name"] == cell), None)
    if w is None:
        raise ManifestError(f"no workload {cell!r} in BENCHMARK.json "
                            f"(have {[x['name'] for x in man['workloads']]})")
    c = next(c for c in man["configs"] if c["name"] == w["config"])
    out = {"cell": w, "config_entry": c,
           "config": _read_json(os.path.join(ROOT, c["file"])),
           "traffic": _read_json(os.path.join(BENCH_DIR, "traffic",
                                              w["traffic"] + ".json")),
           "run_seconds": man["run_seconds"]}
    for kind in ("end_to_end", "per_layer"):
        out[kind] = []
        for m in metrics_for(man, cell, kind):
            spec = _read_json(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".json"))
            out[kind].append({**m, "reader": spec["reader"],
                              "params": spec.get("params", {})})
    return out
