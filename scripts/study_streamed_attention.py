"""`ops/attention.streamed_attention` alone at the two chunk callers'
shapes, the Pallas block kernel beside the XLA body (PERF.md section 6,
PR 46).

On the chip, one process: a 512-row chunk against `--blocks` key blocks of
512 (a context of blocks x 512), bf16, Command A+'s grouping
(`[1, 8, 16, 512, 128]`, with and without the 4,096 window) and DeepSeek's
per-head form (`[1, 128, 1, 512, 192]`, values 128 wide); each body is
jitted, run once, then timed `--iters` times by the host's clock around
`block_until_ready` (a call is 5-30 ms of device work, so the launch is
a small part).  `--rows N ...` reads the kernel under other program
heights than `_BLOCK_ROWS`.  The kernel's result is held to the XLA
body's.  `--toy` runs the control flow at toy sizes on the CPU, the kernel
in interpret mode (no times).

    python scripts/study_streamed_attention.py [--blocks 8] [--iters 10]
        [--rows 256 512 1024]

Writes chiprun_out/pr46/study_streamed.json.  Not wired into the benchmark.
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

A = importlib.import_module("ray_tpu.ops.attention")

# (name, Hkv, G, dh, v_dim, window)
CASES = (("cohere_full", 8, 16, 128, 128, None),
         ("cohere_window", 8, 16, 128, 128, 4096),
         ("deepseek_chunk", 128, 1, 192, 128, None))


def make(case, T, S, blocks, dtype, toy):
    _, Hkv, G, dh, dv, window = case
    if toy:
        Hkv, G = min(Hkv, 2), min(G, 2)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, Hkv, G, T, dh), dtype)
    K = jax.random.normal(ks[1], (1, Hkv, blocks * S, dh), dtype)
    V = jax.random.normal(ks[2], (1, Hkv, blocks * S, dv), dtype)
    qpos = (blocks * S - T + jnp.arange(T, dtype=jnp.int32))[None]

    def call(kernel, interpret=False):
        def f(q, K, V, n):
            def fetch(i):
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * S, S, 2)
                kpos = (i * S + jnp.arange(S, dtype=jnp.int32))[None]
                return sl(K), sl(V), kpos
            if kernel:
                return A._streamed_kernel_loop(q, qpos, fetch, n, window,
                                               dh ** -0.5, dv, interpret)
            return A._streamed_xla(q, qpos, fetch, n, window, dh ** -0.5, dv)
        return jax.jit(f)

    return call, (q, K, V, jnp.int32(blocks))


def timed(fn, args, iters):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, first, (time.perf_counter() - t) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--rows", type=int, nargs="*", default=[])
    ap.add_argument("--toy", action="store_true")
    a = ap.parse_args()
    toy = a.toy
    T = S = 128 if toy else 512
    blocks = 2 if toy else a.blocks
    platform = jax.default_backend()
    out = {"platform": platform, "blocks": blocks, "cases": []}
    for case in CASES:
        call, args = make(case, T, S, blocks, jnp.bfloat16, toy)
        rec = {"case": case[0]}
        if toy:
            ref = call(False)(*args)
            got = call(True, True)(*args)
        else:
            ref, rec["xla_first_s"], rec["xla_s"] = timed(call(False), args,
                                                          a.iters)
            default = A._BLOCK_ROWS
            for rows in a.rows + [default]:
                A._BLOCK_ROWS = rows
                A._streamed_block.clear_cache()
                got, first, s = timed(call(True), args, a.iters)
                rec[f"kernel_{rows}_first_s"], rec[f"kernel_{rows}_s"] = first, s
        rec["max_abs_diff"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - ref.astype(jnp.float32))))
        print(json.dumps(rec), flush=True)
        out["cases"].append(rec)
    d = os.path.join(ROOT, "chiprun_out", "pr46")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "study_streamed.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
