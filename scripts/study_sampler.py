"""Time the serve step's sampler alone (`ray_tpu/ops/sampling.sample`) at
the serve cells' shapes, beside the recipe it replaced (PR 49): a batch
in which nobody samples, one in which one slot draws (top-k off, then
on), and all slots drawing; the two recipes' tokens must be equal.  On
the chip, ~2 min; `--toy` runs the control flow at a small shape on the
CPU (its times are XLA:CPU's).

    python scripts/study_sampler.py [--toy]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.sampling import sample  # noqa: E402

# slots x vocabulary of each serve cell (BENCHMARK.json's configurations)
CELLS = {"chat": (8, 50304), "mixedctx": (16, 32768),
         "streams": (16, 151936), "longctx": (32, 16160),
         "reasoning": (64, 39296)}


def sorted_recipe(logits, keys, temps, topks, dtype):
    """`sample` as it stood before PR 49: the whole vocabulary sorted for
    one threshold a row, every row drawn, the greedy rows' draws thrown
    away."""
    V = logits.shape[-1]
    lg = logits.astype(dtype)
    t = jnp.where(temps > 0, temps, 1.0).astype(dtype)
    scaled = lg / t[:, None]
    k_eff = jnp.where(topks > 0, topks, V)
    kth = jnp.take_along_axis(jnp.sort(scaled, axis=-1),
                              (V - k_eff)[:, None], axis=-1)
    filt = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.vmap(jax.random.categorical)(keys, filt)
    return jnp.where(temps > 0, sampled,
                     jnp.argmax(lg, axis=-1)).astype(jnp.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args()
    dev = jax.devices()[0]
    dtype = jnp.float32 if args.toy else jnp.bfloat16
    cells = {"toy": (4, 1000)} if args.toy else CELLS
    for cell, (B, V) in cells.items():
        logits = jax.random.normal(jax.random.PRNGKey(1), (B, V)) * 4
        keys = jax.random.split(jax.random.PRNGKey(2), B)
        one = jnp.zeros(B).at[B // 2].set(0.8)
        batches = {
            "greedy": (jnp.zeros(B), jnp.zeros(B, jnp.int32)),
            "one_draws": (one, jnp.zeros(B, jnp.int32)),
            "one_draws_top_k": (one, jnp.full(B, 40, jnp.int32)),
            "all_draw_top_k": (jnp.full(B, 0.8), jnp.full(B, 40, jnp.int32)),
        }
        tokens = {}
        for name, fn in (("selected", sample), ("sorted", sorted_recipe)):
            jitted = jax.jit(lambda *a, fn=fn: fn(*a, dtype))
            t0 = time.perf_counter()
            jitted.lower(logits, keys, *batches["greedy"]).compile()
            row = {"cell": cell, "slots": B, "vocab": V, "recipe": name,
                   "platform": dev.platform, "device_kind": dev.device_kind,
                   "compile_s": round(time.perf_counter() - t0, 2)}
            for batch, (temps, topks) in batches.items():
                out = jitted(logits, keys, temps, topks).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = jitted(logits, keys, temps, topks)
                out.block_until_ready()
                row[batch + "_ms"] = round(
                    1e3 * (time.perf_counter() - t0) / args.calls, 4)
                tokens[name, batch] = out.tolist()
            print(json.dumps(row), flush=True)
        # the same tokens under the same keys, whichever finds the threshold
        assert all(tokens["selected", b] == tokens["sorted", b]
                   for b in batches), cell


if __name__ == "__main__":
    main()
