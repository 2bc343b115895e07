"""`ops/moe.held_expert_ffn` alone at the MoE cells' shapes, by the sorted
rows one grouped product takes (PERF.md section 6, PR 53) and by what a
trip costs outside its products (PR 64).

On the chip, one process: one expert layer's held part, jitted, bf16,
seeded uniform top-k routing at the cell's held share, for a Ling chunk
and step, a DeepSeek chunk and step, a Command A+ chunk and an LFM2 chunk
and step (SHAPES).  Each
side is called `--iters` times under a profiler trace; the `ragged-dot`
self time a call comes from the trace by the benchmark's own reduction
(`benchmarks/trace/reduce.py`, the pattern of `moe.experts_roofline.*`),
the whole program's device time from the trace's `XLA Modules` line, the
least time from `benchmarks/lib/costs_moe.least_seconds` on the call's own
pairs and touched experts.  `outside_ms` is the program less its
`ragged-dot*` ops, `outside_us_a_trip` that over the call's trips (walked
on the host from the loads, by the function's own rule: a trip ends where
an expert ends) and `outside_ops` the instructions that make it, each
with its count a call, its ms a call and the head of its HLO text.

A side is a module and the rows it gives a product: a module WITHOUT a
`ROW_BLOCK` (the function before PR 53: trips cut at fixed offsets, `tile`
rows each) is read at `--tiles`; a module WITH one (trips end where an
expert ends) at `--blocks`, the constant patched, under the cells' own
`tile` of 512.  `--parent DIR` adds the `ray_tpu/ops/moe.py` of another
checkout beside the tree's, `--module LABEL=PATH` (repeatable) a variant's
`moe.py`.  A module whose `held_expert_ffn` takes gate and
up as ONE leaf [held, D, 2F] with no up operand (PR 62: its `w_up` is
Optional) is read in both layouts, `<label>` with the two apart (three
products a trip) and `<label>+one_leaf` (two).  Every side's result is
held to the first's.

    python scripts/study_moe_row_block.py [--parent _parent [--no-tree]]
        [--module cand=/path/moe.py] [--iters 10]
        [--tiles 64 128 256 512] [--blocks 64 128 256] [--only ling_chunk]
        [--hlo]

`--toy` runs the control flow at toy sizes on the CPU (no times).  Writes
chiprun_out/<--out, pr64>/study_moe_row_block[.<tag>].json.  Not wired into the
benchmark.
"""
import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
import typing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers._common import start_trace, stop_trace
from benchmarks.lib import costs_moe, peaks
from benchmarks.trace.reduce import (module_time, ops_matching, reduce_trace,
                                     top_ops)

# name, rows N, top-k, held experts, experts in all, D, F, live rows
SHAPES = (
    ("ling_chunk", 512, 8, 128, 512, 2560, 768, None),
    ("ling_step", 64, 8, 128, 512, 2560, 768, 12),
    ("deepseek_chunk", 512, 8, 16, 256, 7168, 2048, None),
    ("deepseek_step", 32, 8, 16, 256, 7168, 2048, 8),
    ("commandaplus_chunk", 512, 8, 16, 128, 4096, 4096, None),
    ("lfm2_chunk", 512, 4, 32, 32, 2048, 1792, None),
    ("lfm2_step", 64, 4, 32, 32, 2048, 1792, 13),
)
TOY = (("toy_chunk", 64, 4, 8, 16, 32, 16, None),
       ("toy_step", 8, 4, 8, 16, 32, 16, 3))
OPS = "^%?ragged-dot"                  # moe.experts_roofline.*'s pattern
CELL_TILE = 512                        # `program.moe_tile` of the cells


def load_moe(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(shape, seed, dtype):
    _, N, k, held, E, D, F, n_live = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = jax.random.normal(ks[0], (N, D), dtype)
    _, idx = jax.lax.top_k(jax.random.uniform(ks[1], (N, E)), k)
    w = jax.random.uniform(ks[2], (N, k), jnp.float32, 0.5, 1.5)
    w = w / w.sum(-1, keepdims=True)
    wg = (jax.random.normal(ks[3], (held, D, F), jnp.float32)
          * D ** -0.5).astype(dtype)
    wu = (jax.random.normal(ks[4], (held, D, F), jnp.float32)
          * D ** -0.5).astype(dtype)
    wd = (jax.random.normal(ks[5], (held, F, D), jnp.float32)
          * F ** -0.5).astype(dtype)
    live = None
    if n_live is not None:
        live = jnp.zeros(N, bool).at[
            jnp.arange(n_live) * (N // n_live)].set(True)
    return h, w, idx.astype(jnp.int32), wg, wu, wd, live


def takes_one_leaf(mod) -> bool:
    hint = typing.get_type_hints(mod.held_expert_ffn).get("w_up")
    return type(None) in typing.get_args(hint)


def sides_of(mods, tiles, blocks):
    """[(label, module, tile, block or None, gate and up in one leaf)]."""
    out = []
    for label, mod in mods:
        if hasattr(mod, "ROW_BLOCK"):
            layouts = (False, True) if takes_one_leaf(mod) else (False,)
            out += [(f"{label}{'+one_leaf' if one else ''}@block={b}", mod,
                     CELL_TILE, b, one) for one in layouts for b in blocks]
        else:
            out += [(f"{label}@tile={t}", mod, t, None, False)
                    for t in tiles]
    return out


def trips_of(loads, rows):
    """The trips `held_expert_ffn` makes over sorted pairs of these loads
    at `rows` sorted rows a trip, ending where an expert ends."""
    ends = np.cumsum(loads)
    lo = trips = 0
    while lo < ends[-1]:
        whole = ends[ends <= lo + rows].max(initial=0)
        lo = whole if whole > lo else lo + rows
        trips += 1
    return trips


def outside_ops(red, iters, top=14):
    """The instructions that are no grouped product, longest first: [name,
    executions a call, ms a call, the head of the HLO text]."""
    rx = re.compile(OPS)
    rest = [(o["s"], nm, o) for nm, o in red["ops"].items()
            if not rx.search(o.get("text") or nm)]
    return [[nm, o["n"] / iters, 1e3 * s / iters, o["text"][:160]]
            for s, nm, o in sorted(rest, key=lambda r: -r[0])[:top]]


def measure(fn, args, iters, toy):
    out = jax.block_until_ready(fn(*args))         # compile + warm
    if toy:
        return out, {}
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        o = fn(*args)
    jax.block_until_ready(o)
    host_ms = 1e3 * (time.perf_counter() - t0) / iters
    d = tempfile.mkdtemp(prefix="moe_study_")
    try:
        start_trace(d)
        try:
            for _ in range(iters):
                o = fn(*args)
            jax.block_until_ready(o)
        finally:
            stop_trace()
        red = reduce_trace(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    dots, n = ops_matching(red, OPS)
    whole, runs = module_time(red, "^jit_")
    return out, {"host_ms": host_ms, "ragged_dot_ms": 1e3 * dots / iters,
                 "ragged_dot_ops": n / iters,
                 "program_ms": 1e3 * whole / max(runs, 1),
                 "top_ops_ms": [[k, 1e3 * v / iters]
                                for k, v in top_ops(red, 8)],
                 "outside_ops": outside_ops(red, iters)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--no-tree", action="store_true",
                    help="read --parent's module alone")
    ap.add_argument("--module", action="append", default=[],
                    metavar="LABEL=PATH",
                    help="a variant's moe.py beside them (repeatable)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=53)
    ap.add_argument("--tiles", type=int, nargs="*",
                    default=[64, 128, 256, 512])
    ap.add_argument("--blocks", type=int, nargs="*", default=[64, 128, 256])
    ap.add_argument("--only", nargs="*")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="pr64")
    ap.add_argument("--hlo", action="store_true",
                    help="keep each side's compiled text beside the json")
    ap.add_argument("--toy", action="store_true")
    a = ap.parse_args()

    dev = jax.devices()[0]
    pk = None if a.toy else peaks.peak(dev.device_kind)
    mods = [("tree", load_moe(os.path.join(ROOT, "ray_tpu", "ops", "moe.py"),
                              "moe_tree"))]
    if a.parent:
        mods.insert(0, ("parent", load_moe(os.path.join(
            a.parent, "ray_tpu", "ops", "moe.py"), "moe_parent")))
        if a.no_tree:
            del mods[1:]
    for i, spec in enumerate(a.module):
        label, path = spec.split("=", 1)
        mods.append((label, load_moe(path, f"moe_variant{i}")))
    rows = []
    record = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "iters": a.iters, "seed": a.seed, "rows": rows}
    d = os.path.join(ROOT, "chiprun_out", a.out)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "study_moe_row_block"
                        + (f".{a.tag}" if a.tag else "") + ".json")
    for shape in TOY if a.toy else SHAPES:
        if a.only and shape[0] not in a.only:
            continue
        args = inputs(shape, a.seed, jnp.float32 if a.toy else jnp.bfloat16)
        *tensors, live = args
        one_leaf = None
        ref = None
        for label, mod, tile, block, one in sides_of(mods, a.tiles, a.blocks):
            if block is not None:
                mod.ROW_BLOCK = block
            if one and one_leaf is None:
                one_leaf = tensors[:3] + [
                    jnp.concatenate(tensors[3:5], axis=-1), None, tensors[5]]
            # a new jit a side: the constant is read while tracing
            fn = jax.jit(lambda *t, mod=mod, tile=tile: mod.held_expert_ffn(
                *t, first=0, tile=tile, live=live))
            operands = one_leaf if one else tensors
            got, ms = measure(fn, operands, a.iters, a.toy)
            if a.hlo:
                with open(os.path.join(
                        d, f"{shape[0]}.{label}.hlo.txt"), "w") as f:
                    f.write(fn.lower(*operands).compile().as_text())
            out, loads = np.asarray(got[0]), np.asarray(got[1])
            if ref is None:
                ref = out
            pairs, touched = int(loads.sum()), int((loads > 0).sum())
            row = {"shape": shape[0], "side": label, "pairs": pairs,
                   "touched": touched, **ms,
                   "gap": float(np.max(np.abs(out - ref))
                                / max(np.max(np.abs(ref)), 1e-30))}
            if len(got) > 2:
                row["reads"] = int(got[2])
            row["trips"] = (
                trips_of(loads, min(tile, shape[1] * shape[2], block))
                if block is not None else -(-pairs // tile))
            if ms:
                row["outside_ms"] = ms["program_ms"] - ms["ragged_dot_ms"]
                row["outside_us_a_trip"] = (1e3 * row["outside_ms"]
                                            / max(row["trips"], 1))
                least = costs_moe.least_seconds(
                    pairs, touched, {"hidden_size": shape[5],
                                     "intermediate_size": shape[6]}, pk)
                row["least_ms"] = 1e3 * least
                if ms["ragged_dot_ms"]:         # no pair fell here: no trip
                    row["roofline_pct"] = 1e5 * least / ms["ragged_dot_ms"]
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(path, "w") as f:
                json.dump(record, f, indent=1)
        del args, tensors, one_leaf
    print(json.dumps({"ok": True, "device": record["device"]}))


if __name__ == "__main__":
    main()
