"""Where the limits of `serve-phi4flash-longgen-loaded`'s reference check
come from, and what that check sees (PERF.md section 6, PR 51, read on
`serve-phi4flash-longgen`, retired at PR 55: the same check and limits;
the readings stand in benchmarks/traffic/open-longgen.json).

scripts/study_ling3_controls.py's scheme and its code (`cell_run`,
`cell_runs`, `main`: imported, this cell's names set on that module): every
reading is a RUN OF THE CELL by its own driver — what `benchmarks/run.py`'s
child does, word for word — with one fault put in from outside the
benchmark's files, so that `correct` is the cell's own verdict:

  sound            the program as it is: must come out correct
  -- faults put into the PROGRAM (in the replica, before its engine is
  -- built: the loader handed to `LLMServer` sets them and then loads)
  state_bf16       the state arena's entries rounded to bfloat16 at every
                   write (a chunk's and a step's)
  tail_dropped     a chunk's convolution starts from a zero tail and
                   leaves none
  one_softmax      the second softmax left out (lam forced to 0)
  cross_null_page  every cross layer's table holds the null page where
                   layer 17's earlier keys lie (all but the query's own
                   page)
  gmu_other_row    a GMU gated by another row's memory (the batch rolled
                   by one: a slot reads its neighbour's)
  window_unmasked  a window layer with no window: it reads whatever its
                   ring's entries point at — pages the window has passed
                   and the engine has returned
  stale_entry      a first chunk reads what its entry's last holder left
  -- a fault put into the REFERENCE (`reference_shape(..)["control"]`)
  fp8_weights      every matrix rounded to fp8-e4m3: the nearest precision
                   below the configuration's

    python scripts/study_phi4flash_controls.py [--only a,b] [seed]

runs each variant in a child of its own (a chip belongs to one replica at
a time), prints a `reading` line each and writes
chiprun_out/pr51/controls.json.  `--toy` runs the same through the cell's
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
    replica, before its engine traces a program (models/phi4flash.py looks
    these names up in its module when it traces)."""
    def load():
        import jax.numpy as jnp

        from ray_tpu.models import phi4flash as pm

        half = lambda s: s.astype(jnp.bfloat16).astype(s.dtype)
        if variant == "state_bf16":
            chunk, step = pm.selective_scan_chunk, pm.selective_step

            def chunk_half(*a, **kw):
                y, state = chunk(*a, **kw)
                return y, half(state)

            def step_half(u, dt, a, bm, cm, state, layer, idx, live, **kw):
                y, state = step(u, dt, a, bm, cm, state, layer, idx, live,
                                **kw)
                return y, state.at[layer, idx].set(half(state[layer][idx]))

            pm.selective_scan_chunk, pm.selective_step = chunk_half, step_half
        elif variant == "tail_dropped":
            conv, prefill = pm.conv_chunk, pm.paged_prefill
            pm.conv_chunk = lambda rows, tail, *rest: conv(
                rows, jnp.zeros_like(tail), *rest)

            def tailless(*a, **kw):
                logits, cache, stats = prefill(*a, **kw)
                return logits, dict(cache, tail=jnp.zeros_like(
                    cache["tail"])), stats

            pm.paged_prefill = tailless
        elif variant == "one_softmax":
            pm._lambda = lambda l, layer, cfg: 0.0
        elif variant == "cross_null_page":
            cross = pm._cross_decoder

            def blind(params, full, x, m, pos, io, *rest):
                tab, bases, write_at, n_blocks = io
                ps = full["k"].shape[1]
                own = jnp.arange(tab.shape[1])[None] >= pos // ps
                return cross(params, full, x, m, pos,
                             (jnp.where(own, tab, 0), bases, write_at,
                              n_blocks), *rest)

            pm._cross_decoder = blind
        elif variant == "gmu_other_row":
            gmu = pm._gmu
            pm._gmu = lambda x, h, m, layer, cfg: gmu(
                x, h, jnp.roll(m, 1, axis=0), layer, cfg)
        elif variant == "window_unmasked":
            pm._window = lambda kind, cfg: None
        else:
            carried = pm.carried_at
            pm.carried_at = lambda first, arena, j, idx: carried(
                jnp.bool_(False), arena, j, idx)
        return loader()

    return load


base.CELL = "serve-phi4flash-longgen-loaded"
base.REFERENCE_SIDE = ("fp8_weights",)
base.PROGRAM_SIDE = ("state_bf16", "tail_dropped", "one_softmax",
                     "cross_null_page", "gmu_other_row", "window_unmasked",
                     "stale_entry")
base.OUT = os.path.join(ROOT, "chiprun_out", "pr51")
base.REPLICA, base.CFG = "replica_phi4flash", "phi4flashcfg"
base.SCRIPT = os.path.abspath(__file__)
base.faulty = faulty

if __name__ == "__main__":
    base.main()
