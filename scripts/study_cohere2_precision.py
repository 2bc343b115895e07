"""Where the limits of `serve-commandaplus-mixedctx-loaded`'s reference
check come from, and what that check can and cannot see (PERF.md section 6,
PR 27, read on `serve-commandaplus-mixedctx`, retired at PR 55: the same
check and limits).

At the configuration's published widths, on the CPU, with the weights the
benchmark's loader makes (`weights.scales` applied), S random tokens go
through the f32 plain reference and through variants of the served side.
Each variant is held to the reference as the benchmark holds served
tokens: the token a variant would serve next at a position is its own
argmax there; the share of positions where that is the reference's argmax,
and the largest distance of it below the reference's maximum.

  program_bf16        the program's full forward in bf16: the sound reading
  reference_fp8       the reference with weights rounded to fp8-e4m3: the
                      control, which must fail
  window_plus_one     the program with `sliding_window` + 1
  page_returned_early the program with the window a page (128) short: what
                      a sliding page returned a page too early reads as
  pair_dropped        the program with every token's first pair on a held
                      expert left out

The window's variants are judged on the positions past the window only.

    JAX_PLATFORMS=cpu python scripts/study_cohere2_precision.py [S] [seed ...]

S defaults to 4,352 (256 positions past the 4,096-token window); needs
~50 GB of host memory and about twelve minutes a seed on 8 cores; writes
nothing.
"""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers.replica_cohere2_moe import scale_weights
from benchmarks.lib.cohere2cfg import model_config, reference_shape
from benchmarks.reference import cohere2_moe_plain as ref
from ray_tpu.models import cohere2_moe as cm

PAGE = 128


def held_stats(lr, l, rows):
    """`l`'s argmax held to the reference logits `lr`, over `rows`."""
    lr, l = lr[rows], l[rows]
    am = l.argmax(-1)
    gap = lr.max(-1) - np.take_along_axis(lr, am[:, None], 1)[:, 0]
    srt = np.sort(lr, -1)
    return {"positions": int(len(am)),
            "argmax_share": float((gap <= 0).mean()),
            "worst_gap": float(gap.max()),
            "gap_p99": float(np.quantile(gap, 0.99)),
            "median_logit_diff": float(np.median(np.abs(l - lr))),
            "max_logit_diff": float(np.abs(l - lr).max()),
            "reference_top2_median": float(np.median(srt[:, -1] - srt[:, -2])),
            "reference_logit_std": float(lr.std())}


def drop_first_held_pair(real):
    def dropping(h, w, idx, *a, first, **kw):
        count = a[0].shape[0]
        held = (idx >= first) & (idx < first + count)
        hit = held & (jnp.cumsum(held, axis=1) == 1)
        return real(h, w, jnp.where(hit, 10 ** 6, idx), *a, first=first, **kw)
    return dropping


def main():
    conf = json.load(open(os.path.join(
        sys.path[0], "benchmarks", "configs", "command-a-plus-l4-e16.json")))
    S = int(sys.argv[1]) if len(sys.argv) > 1 else 4352
    shape = reference_shape(conf)
    cfg = model_config(conf)
    W = cfg.sliding_window
    every = np.arange(S)
    past = every[every >= W] if S > W else every
    for seed in (int(a) for a in sys.argv[2:] or ["11"]):
        t0 = time.time()
        params = scale_weights(cm.init(jax.random.PRNGKey(seed), cfg),
                               conf.get("weights", {}).get("scales", {}))
        toks = jnp.asarray(np.random.default_rng(seed).integers(
            0, cfg.vocab_size, S).astype(np.int32))

        def program(c=cfg):
            return np.asarray(jax.jit(
                lambda p, t: cm.apply(p, t[None], c)[0])(params, toks))

        def reference(p):
            return np.asarray(jax.jit(
                lambda p, t: ref.logits(p, t, shape, 256))(p, toks))

        def say(name, l, rows):
            print(json.dumps({"seed": seed, "S": S, "variant": name,
                              "s": round(time.time() - t0), **held_stats(
                                  lr, l, rows)}), flush=True)

        lr = reference(params)
        lp = program()
        say("program_bf16", lp, every)
        if S > W:
            say("program_bf16.past_window", lp, past)
        say("reference_fp8", reference(jax.tree.map(
            lambda a: a if a.ndim < 2 else
            a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)), every)
        say("window_plus_one", program(dataclasses.replace(
            cfg, sliding_window=W + 1)), past)
        say("page_returned_early", program(dataclasses.replace(
            cfg, sliding_window=W - PAGE)), past)
        real = cm.held_expert_ffn
        cm.held_expert_ffn = drop_first_held_pair(real)
        try:
            say("pair_dropped", program(), every)
        finally:
            cm.held_expert_ffn = real


if __name__ == "__main__":
    main()
