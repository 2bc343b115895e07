"""The three flash-attention kernels alone, parent against change (PERF.md
section 6, PR 41).

On the chip, one process: `attention(q, k, v, causal=True, impl="pallas")`
forward + backward on seeded bf16 inputs at the shapes the train cells run
a chip (`[8,16,1024,64]` medium, `[8,25,1024,64]` the xl shard) and at
`[2,16,8192,64]`, under a profiler trace; the device self time of
`flash_attention_fwd`, `flash_attention_bwd_dkv` and `flash_attention_bwd_dq`
comes from the trace by the benchmark's own reduction
(`benchmarks/trace/reduce.py`), per call, beside the share of
`costs.flash_attention_flops(causal=True)` / peak each reaches (the forward
alone; dkv + dq together against the backward's 2.5x) and the host clock
around the whole call.  The change's gradients are held to the parent's.

    python scripts/study_flash_attention.py [--parent _parent] [--iters 20]
        [--shape 8,16,1024,64 ...] [--variant SUB[,BQ,BK[,CAP]] ...]

`--parent DIR` is a checkout of the parent commit (`git archive` into
`_parent/`); without one only the change is timed.  `--variant` reads the
change under another sub-block width (and blocks, and backward block cap)
than the ones `ops/attention.py` derives: how those were chosen.  `--toy`
runs the control flow at toy sizes in interpret mode on the CPU (no times).

Writes chiprun_out/pr41/study.json.  Not wired into the benchmark.
"""
import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers._common import start_trace, stop_trace
from benchmarks.lib import costs, peaks
from benchmarks.trace.reduce import reduce_trace

SHAPES = ((8, 16, 1024, 64), (8, 25, 1024, 64), (2, 16, 8192, 64))
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
           "flash_attention_bwd_dq")


def load_attention(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(shape, seed, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def grad_fn(mod, impl, blocks):
    def loss(q, k, v):
        return mod.attention(q, k, v, causal=True, impl=impl,
                             block_q=blocks[0], block_k=blocks[1]
                             ).astype(jnp.float32).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def kernel_ms(trace_dir, iters):
    """Per-call device self time of each kernel, ms; the forward runs
    once a call here (no remat)."""
    red = reduce_trace(trace_dir)
    out = {}
    for name in KERNELS:
        hits = [o for nm, o in red["ops"].items()
                if name in nm]
        out[name] = 1e3 * sum(o["s"] for o in hits) / iters if hits else None
    if None in out.values():
        print("kernels missing from the trace; its planes and ops:",
              red["planes"], sorted(red["ops"])[:40], file=sys.stderr)
    return out


def measure(fn, args, iters, toy):
    grads = jax.block_until_ready(fn(*args))       # compile + warm
    if toy:
        return grads, {}, None
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    host_ms = 1e3 * (time.perf_counter() - t0) / iters
    d = tempfile.mkdtemp(prefix="flash_study_")
    try:
        start_trace(d)
        try:
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        finally:
            stop_trace()
        ms = kernel_ms(d, iters)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return grads, ms, host_ms


def shares(shape, ms, peak_flops):
    """Share of costs.flash_attention_flops / peak: forward, backward."""
    if not ms or None in ms.values():
        return None, None
    f = costs.flash_attention_flops(*shape, causal=True) / peak_flops
    b = costs.flash_attention_flops(*shape, causal=True,
                                    backward=True) / peak_flops
    return (100 * f / (ms[KERNELS[0]] * 1e-3),
            100 * b / ((ms[KERNELS[1]] + ms[KERNELS[2]]) * 1e-3))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=os.path.join(ROOT, "_parent"))
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--shape", action="append")
    ap.add_argument("--variant", action="append", default=[])
    ap.add_argument("--toy", action="store_true")
    a = ap.parse_args()

    dev = jax.devices()[0]
    if a.toy:
        shapes, impl, dtype = ((1, 2, 512, 32),), "pallas_interpret", jnp.float32
        peak_flops = None
    else:
        shapes = tuple(tuple(int(x) for x in s.split(","))
                       for s in a.shape) if a.shape else SHAPES
        impl, dtype = "pallas", jnp.bfloat16
        peak_flops = peaks.peak(dev.device_kind)["flops_per_s"]

    change = load_attention(
        os.path.join(ROOT, "ray_tpu", "ops", "attention.py"), "attn_change")
    sides = [("change", change, (None, None), None)]
    ppath = os.path.join(a.parent, "ray_tpu", "ops", "attention.py")
    if os.path.exists(ppath):
        sides.insert(0, ("parent", load_attention(ppath, "attn_parent"),
                         (None, None), None))
    for v in a.variant:
        n = [int(x) for x in v.split(",")]
        sides.append((f"change[{v}]", change,
                      tuple(n[1:3]) if len(n) >= 3 else (None, None),
                      (n[0], n[3] if len(n) > 3 else 1024)))

    rows = []
    for shape in shapes:
        args = inputs(shape, a.seed, dtype)
        ref = None
        for label, mod, blocks, patch in sides:
            was = None
            if patch:
                was = (mod._SUB, mod._BWD_BLOCK)
                mod._SUB, mod._BWD_BLOCK = patch
            try:
                grads, ms, host_ms = measure(grad_fn(mod, impl, blocks),
                                             args, a.iters, a.toy)
            except Exception as e:  # noqa: BLE001 - a variant Mosaic refuses
                print(json.dumps({"shape": list(shape), "side": label,
                                  "error": str(e)[:300]}), flush=True)
                continue
            finally:
                if was:
                    mod._SUB, mod._BWD_BLOCK = was
            grads = [np.asarray(g.astype(jnp.float32)) for g in grads]
            if ref is None:
                ref = grads
            # largest gradient difference from the first side, in units
            # of that side's largest gradient
            gap = max(float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
                      for g, r in zip(grads, ref))
            fwd, bwd = shares(shape, ms, peak_flops)
            row = {"shape": list(shape), "side": label, "ms": ms,
                   "host_ms": host_ms, "fwd_share_pct": fwd,
                   "bwd_share_pct": bwd, "grad_gap": gap}
            if hasattr(mod, "causal_work") and not patch:
                s = shape[2]
                row["work_fwd"] = mod.causal_work(s, s)
                row["work_bwd"] = mod.causal_work(s, s, backward=True)
            rows.append(row)
            print(json.dumps(row), flush=True)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "iters": a.iters, "seed": a.seed, "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out", "pr41"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pr41", "study.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"ok": True, "device": out["device"]}))


if __name__ == "__main__":
    main()
