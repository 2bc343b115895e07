"""Where the limits of `serve-falconh1-chatburst`'s reference check come
from, and what that check sees (PERF.md section 6, PR 61; the readings
stand in benchmarks/traffic/open-chatburst.json).

scripts/study_ling3_controls.py's scheme and its code (`cell_run`,
`cell_runs`, `main`: imported, this cell's names set on that module): every
reading is a RUN OF THE CELL by its own driver — what `benchmarks/run.py`'s
child does, word for word — with one fault put in from outside the
benchmark's files, so that `correct` is the cell's own verdict:

  sound           the program as it is: must come out correct
  -- faults put into the PROGRAM (in the replica, before its engine is
  -- built: the loader handed to `LLMServer` sets them and then loads)
  ssm_dropped     the SSM branch adds nothing to the stream
  attn_dropped    the attention branch adds nothing to the stream
  state_bf16      the state arena's entries rounded to bfloat16 at every
                  write (a chunk's and a step's)
  tail_dropped    a chunk's convolution starts from a zero tail and leaves
                  none: dropped between chunks and at the hand-over
  stale_entry     a first chunk reads what its entry's last holder left
  no_key_mult     `key_multiplier` left out of the fold (keys 90 x louder)
  rope_shifted    queries rotated at their position + 1, keys at their own
  group_mixed     group 0's B and C handed to group 1's heads (so C B^T of
                  group 0, and its state update, for heads 16-31)
  -- a fault put into the REFERENCE (`reference_shape(..)["control"]`)
  fp8_weights     every matrix rounded to fp8-e4m3: the nearest precision
                  below the configuration's

    python scripts/study_falconh1_controls.py [--only a,b] [seed]

runs each variant in a child of its own (a chip belongs to one replica at
a time; ~2 min each), prints a `reading` line each and writes
chiprun_out/pr61/controls.json.  `--toy` runs the same through the cell's
rehearsal on the CPU.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import study_ling3_controls as base  # noqa: E402


def faulty(loader, variant):
    """`loader` behind a fault set in the process that calls it — the
    replica, before its engine traces a program (models/falcon_h1.py looks
    these names up in its module when it traces)."""
    def load():
        import dataclasses

        import jax.numpy as jnp

        from ray_tpu.models import falcon_h1 as fm

        half = lambda s: s.astype(jnp.bfloat16).astype(s.dtype)
        if variant == "ssm_dropped":
            out = fm._ssd_out
            fm._ssd_out = lambda *a: jnp.zeros_like(out(*a))
        elif variant == "attn_dropped":
            out = fm._attn_out
            fm._attn_out = lambda *a: jnp.zeros_like(out(*a))
        elif variant == "state_bf16":
            chunk, step = fm.ssd_chunk, fm.ssd_step

            def chunk_half(*a, **kw):
                y, state = chunk(*a, **kw)
                return y, half(state)

            def step_half(x, dt, a, b, c, d, state, layer, idx, live, **kw):
                y, state = step(x, dt, a, b, c, d, state, layer, idx, live,
                                **kw)
                return y, state.at[layer, idx].set(half(state[layer][idx]))

            fm.ssd_chunk, fm.ssd_step = chunk_half, step_half
        elif variant == "tail_dropped":
            conv, prefill = fm.conv_chunk, fm.paged_prefill
            fm.conv_chunk = lambda rows, tail, *rest: conv(
                rows, jnp.zeros_like(tail), *rest)

            def tailless(*a, **kw):
                logits, cache, stats = prefill(*a, **kw)
                return logits, dict(cache, tail=jnp.zeros_like(
                    cache["tail"])), stats

            fm.paged_prefill = tailless
        elif variant == "stale_entry":
            carried = fm.carried_at
            fm.carried_at = lambda first, arena, j, idx: carried(
                jnp.bool_(False), arena, j, idx)
        elif variant == "no_key_mult":
            # the loader folds a layer at a time (`fold_layer`, which
            # `serve_view` calls too)
            fold = fm.fold_layer
            fm.fold_layer = lambda lp, cfg: fold(
                lp, dataclasses.replace(cfg, key_multiplier=1.0))
        elif variant == "rope_shifted":
            rope = fm.apply_rope_halves
            fm.apply_rope_halves = lambda x, pos, theta: rope(
                x, pos + (1 if x.shape[1] > 4 else 0), theta)
        else:
            assert variant == "group_mixed", variant
            operands = fm._ssd_operands

            def mixed(*a):
                x, dt, b, c = operands(*a)
                first = lambda g: jnp.broadcast_to(g[..., :1, :], g.shape)
                return x, dt, first(b), first(c)

            fm._ssd_operands = mixed
        return loader()

    return load


base.CELL = "serve-falconh1-chatburst"
base.REFERENCE_SIDE = ("fp8_weights",)
base.PROGRAM_SIDE = ("ssm_dropped", "attn_dropped", "state_bf16",
                     "tail_dropped", "stale_entry", "no_key_mult",
                     "rope_shifted", "group_mixed")
base.OUT = os.path.join(ROOT, "chiprun_out", "pr61")
base.REPLICA, base.CFG = "replica_falcon_h1", "falconh1cfg"
base.SCRIPT = os.path.abspath(__file__)
base.faulty = faulty

if __name__ == "__main__":
    base.main()
