"""`ops/attention.latent_decode_attention` alone at the absorbed decode
step's shapes, the Pallas walk beside the XLA body it replaces (PERF.md
section 6, PR 48).

On the chip, one process: one latent layer's attention of a step of
`serve-deepseekv3-longctx` (32 slots, 128 heads, a table 136 pages wide
over an arena of 4,353 pages of 128 positions, bf16) with the live slots
the cell holds (3 streams of 7-15k keys; 1; and all 32 at 4k), and of
`serve-ling3flash-reasoning` (64 slots, 144 pages wide).  The XLA body
is `_streamed_xla` fed as `deepseek_v3.page_io` feeds it: every slot,
every block of 4 pages up to the longest live context, gathered and
transposed.  Each body is jitted as `--reps` calls in a row, each call's
queries a function of the last one's result, run once and then timed
`--iters` times by the host's clock around `block_until_ready`: a call of
the kernel is tens of microseconds, so one launch carries many.
`--pages N ...` reads the kernel under other block sizes than
`_WALK_PAGES`.  The kernel's result is held to the XLA body's.  `--toy`
runs the control flow at toy sizes on the CPU, the kernel in interpret
mode (no times).

    python scripts/study_latent_decode.py [--reps 20] [--iters 3]
        [--pages 2 8]

Writes chiprun_out/pr48/study_latent_decode.json.  Not wired into the
benchmark.
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

H, D, V_DIM, PS, SCALE = 128, 576, 512, 128, 0.1147
# (name, slots, table width, {slot: context})
CASES = (
    ("longctx_3_live", 32, 136, {3: 9143, 11: 14659, 20: 6978}),
    ("longctx_1_live", 32, 136, {5: 9000}),
    ("longctx_32_live_4k", 32, 136, {b: 4096 - 7 * b for b in range(32)}),
    ("reasoning_10_live", 64, 144, {6 * b: 1500 + 211 * b for b in range(10)}),
)


def make(case, toy):
    _, B, R, live = case
    h, d, v, ps = (8, 24, 16, 8) if toy else (H, D, V_DIM, PS)
    if toy:
        R = 12
        live = {b: 1 + c % (R * ps) for b, c in live.items()}
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (B, h, d), jnp.bfloat16)
    arena = jax.random.normal(ks[1], (1 + B * R, d, ps), jnp.bfloat16)
    ptab = 1 + jnp.arange(B * R, dtype=jnp.int32).reshape(B, R)
    ctx = jnp.zeros(B, jnp.int32).at[jnp.asarray(list(live))].set(
        jnp.asarray(list(live.values()), jnp.int32))
    return (q, arena, ptab, ctx), v


def bodies(v, reps, interpret):
    def kernel(q, arena, ptab, ctx):
        return A.latent_decode_attention(q, arena, ptab, ctx, scale=SCALE,
                                         v_dim=v, interpret=interpret)

    def xla(q, arena, ptab, ctx):
        B, _, d = q.shape
        ps, npb = arena.shape[-1], A._WALK_PAGES
        tabp = jnp.pad(ptab, ((0, 0), (0, -ptab.shape[1] % npb)))

        def fetch(i):
            t = jax.lax.dynamic_slice_in_dim(tabp, i * npb, npb, 1)
            rows = jnp.swapaxes(arena[t], 2, 3).reshape(B, npb * ps, d)
            kpos = jnp.broadcast_to(
                i * npb * ps + jnp.arange(npb * ps, dtype=jnp.int32),
                (B, npb * ps))
            return rows[:, None], rows[:, None, :, :v], kpos

        n_blocks = -(-jnp.max(ctx) // (npb * ps))
        return A._streamed_xla(q[:, None, :, None], ctx[:, None] - 1, fetch,
                               n_blocks, None, SCALE, v)[:, 0, :, 0]

    def in_a_row(f):
        def many(q, arena, ptab, ctx):
            def turn(_, o):
                return f(q + (o[..., :1] * 1e-9).astype(q.dtype), arena,
                         ptab, ctx)
            return jax.lax.fori_loop(0, reps - 1, turn,
                                     f(q, arena, ptab, ctx))
        return jax.jit(many)

    return jax.jit(kernel), jax.jit(xla), in_a_row(kernel), in_a_row(xla)


def timed(fn, args, iters, reps):
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return first, (time.perf_counter() - t) / iters / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--pages", type=int, nargs="*", default=[])
    ap.add_argument("--toy", action="store_true")
    a = ap.parse_args()
    out = {"platform": jax.default_backend(), "reps": a.reps, "cases": []}
    default = A._WALK_PAGES
    for case in CASES:
        args, v = make(case, a.toy)
        ps = args[1].shape[-1]
        rec = {"case": case[0], "live": len(case[3]),
               "keys": int(args[3].sum()),
               "walked": int(A.latent_walked_keys(args[3], ps)),
               "xla_fetched": int(
                   case[1] * -(-int(args[3].max()) // (default * ps))
                   * default * ps)}
        kernel, xla, kernels, xlas = bodies(v, a.reps, a.toy)
        want = xla(*args)
        if not a.toy:
            rec["xla_first_s"], rec["xla_s"] = timed(xlas, args, a.iters,
                                                     a.reps)
        for pages in a.pages + [default]:
            A._WALK_PAGES = pages
            A._latent_decode.clear_cache()
            kernel, _, kernels, _ = bodies(v, a.reps, a.toy)
            got = kernel(*args)
            if not a.toy:
                (rec[f"kernel_{pages}_first_s"],
                 rec[f"kernel_{pages}_s"]) = timed(kernels, args, a.iters,
                                                   a.reps)
            live = args[3] > 0
            rec[f"kernel_{pages}_max_abs_diff"] = float(jnp.max(jnp.abs(
                (got.astype(jnp.float32) - want.astype(jnp.float32))[live])))
            assert not bool(jnp.any(got[~live] != 0))
        print(json.dumps(rec), flush=True)
        out["cases"].append(rec)
    d = os.path.join(ROOT, "chiprun_out", "pr48")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "study_latent_decode.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
