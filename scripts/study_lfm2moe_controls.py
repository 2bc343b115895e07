"""Where the limits of `serve-lfm2moe-ragextract`'s reference check come
from, and what that check sees (PERF.md section 6, PR 63; the readings
stand in benchmarks/traffic/open-ragextract.json).

scripts/study_ling3_controls.py's scheme and its code (`cell_run`,
`cell_runs`, `main`: imported, this cell's names set on that module): every
reading is a RUN OF THE CELL by its own driver — what `benchmarks/run.py`'s
child does, word for word — with one fault put in from outside the
benchmark's files, so that `correct` is the cell's own verdict:

  sound           the program as it is: must come out correct
  -- faults put into the PROGRAM (in the replica, before its engine is
  -- built: the loader handed to `LLMServer` sets them and then loads)
  conv_dropped    the conv mixer adds nothing to the stream
  no_out_gate     the gate after the taps (c *) left out
  silu_on_taps    SiLU put on the taps' sum (the other models' form)
  tail_dropped    a chunk's convolution starts from a zero tail and leaves
                  none: dropped between chunks and at the hand-over
  stale_entry     a first chunk reads what its entry's last holder left
  no_head_norms   the head norms of q and k left out
  rope_shifted    queries rotated at their position + 1, keys at their own
  bias_unselecting  the expert bias left out of the SELECTION
  bias_weighing   the expert bias added to the WEIGHTS too
  no_normaliser   the chosen scores weigh as they are (not over their sum)
  top3_of_4       three experts a token, not four
  -- a fault put into the REFERENCE (`reference_shape(..)["control"]`)
  fp8_weights     every matrix rounded to fp8-e4m3: the nearest precision
                  below the configuration's

    python scripts/study_lfm2moe_controls.py [--only a,b] [seed]

runs each variant in a child of its own (a chip belongs to one replica at
a time; ~2 min each), prints a `reading` line each and writes
chiprun_out/pr63/controls.json.  `--toy` runs the same through the cell's
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
    replica, before its engine traces a program (models/lfm2_moe.py looks
    these names up in its module when it traces)."""
    def load():
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import lfm2_moe as lm
        from ray_tpu.ops.moe import _sigmoid_scores

        route = lm.route_sigmoid_topk

        def rerouted(weigh):
            """The router with the chosen experts' weights `weigh(s [N, k]
            the chosen scores, b [N, k] their biases, eps)`."""
            def routed(h, w, k, *, bias, eps):
                s = _sigmoid_scores(h, w)
                _, idx = jax.lax.top_k(s + bias, k)
                take = lambda a: jnp.take_along_axis(a, idx, axis=-1)
                return (weigh(take(s), take(jnp.broadcast_to(bias, s.shape)),
                              eps), idx.astype(jnp.int32))
            return routed

        if variant == "conv_dropped":
            out = lm._conv_out
            lm._conv_out = lambda *a: jnp.zeros_like(out(*a))
        elif variant == "no_out_gate":
            out = lm._conv_out
            lm._conv_out = lambda y, c, *a: out(y, jnp.ones_like(c), *a)
        elif variant == "silu_on_taps":
            chunk, step = lm.conv_chunk, lm.conv_step
            lm.conv_chunk = lambda rows, tail, w, b, act, scope: chunk(
                rows, tail, w, b, jax.nn.silu, scope)
            lm.conv_step = lambda row, tail, w, b, act, scope: step(
                row, tail, w, b, jax.nn.silu, scope)
        elif variant == "tail_dropped":
            conv, prefill = lm.conv_chunk, lm.paged_prefill
            lm.conv_chunk = lambda rows, tail, *rest: conv(
                rows, jnp.zeros_like(tail), *rest)

            def tailless(*a, **kw):
                logits, cache, stats = prefill(*a, **kw)
                return logits, dict(cache, tail=jnp.zeros_like(
                    cache["tail"])), stats

            lm.paged_prefill = tailless
        elif variant == "stale_entry":
            carried = lm.carried_at
            lm.carried_at = lambda first, arena, j, idx: carried(
                jnp.bool_(False), arena, j, idx)
        elif variant == "no_head_norms":
            norm = lm.rms_norm          # q and k come [B, heads, T, dh]
            lm.rms_norm = lambda x, w, eps: (
                x if x.ndim == 4 else norm(x, w, eps))
        elif variant == "rope_shifted":
            import itertools

            # `_qkv` turns q, then k: every other call is a query's
            rope, calls = lm.apply_rope_halves, itertools.count()
            lm.apply_rope_halves = lambda x, pos, theta: rope(
                x, pos + (1 - next(calls) % 2), theta)
        elif variant == "bias_unselecting":
            lm.route_sigmoid_topk = lambda h, w, k, *, bias, eps: route(
                h, w, k, bias=jnp.zeros_like(bias), eps=eps)
        elif variant == "bias_weighing":
            lm.route_sigmoid_topk = rerouted(
                lambda s, b, eps: (s + b) / (jnp.sum(s + b, -1, keepdims=True)
                                             + eps))
        elif variant == "no_normaliser":
            lm.route_sigmoid_topk = rerouted(lambda s, b, eps: s)
        else:
            assert variant == "top3_of_4", variant
            lm.route_sigmoid_topk = lambda h, w, k, **kw: route(
                h, w, k - 1, **kw)
        return loader()

    return load


base.CELL = "serve-lfm2moe-ragextract"
base.REFERENCE_SIDE = ("fp8_weights",)
base.PROGRAM_SIDE = ("conv_dropped", "no_out_gate", "silu_on_taps",
                     "tail_dropped", "stale_entry", "no_head_norms",
                     "rope_shifted", "bias_unselecting", "bias_weighing",
                     "no_normaliser", "top3_of_4")
base.OUT = os.path.join(ROOT, "chiprun_out", "pr63")
base.REPLICA, base.CFG = "replica_lfm2_moe", "lfm2moecfg"
base.SCRIPT = os.path.abspath(__file__)
base.faulty = faulty

if __name__ == "__main__":
    base.main()
