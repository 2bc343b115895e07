"""Where the limits of `serve-ling3flash-reasoning`'s reference check come
from, and what that check sees (PERF.md section 6, PR 47; the readings
stand in benchmarks/traffic/open-reasoning.json).

Every reading is a RUN OF THE CELL by its own driver — what
`benchmarks/run.py`'s child does, word for word — with one fault put in
from outside the benchmark's files, so that `correct` is the cell's own
verdict (the scheme of scripts/study_deepseek_v3_controls.py):

  sound         the program as it is: must come out correct
  -- faults put into the PROGRAM (in the replica, before its engine is
  -- built: the loader handed to `LLMServer` sets them and then loads)
  state_bf16    the state arena's entries rounded to bfloat16 at every
                write (a chunk's and a step's): an arena kept in bfloat16
  chunk_bf16    a chunk's q, k and v rounded to bfloat16 before the WY
                form (operands in half precision, the state's accumulation
                and its arena float32).  A READING, NOT A CONTROL: it
                comes out `correct` (`state_rel_rms` 0.00359 against the
                sound 0.00353-0.00360) — q~ k~ v~ leave a bfloat16 product
                already and a second rounding of that size is not seen
  tail_dropped  a chunk's convolution starts from a zero tail and leaves
                none: the conv tail dropped between chunks and at the
                hand-over to the first step
  stale_entry   a first chunk reads what its entry's last holder left
  lost_chunks   a prefill chunk whose page table holds the null page
                wherever an earlier chunk's latents lie
  -- faults put into the REFERENCE (`reference_shape(..)["control"]`)
  fp8_weights   every matrix rounded to fp8-e4m3: the nearest precision
                below the configuration's
  no_groups     plain top-8 over 512 experts

    python scripts/study_ling3_controls.py [--only a,b] [seed]

runs each variant in a child of its own (a chip belongs to one replica at
a time), prints a `reading` line each — the numbers compared, the checks,
`correct` — and writes chiprun_out/pr47/controls.json.  `--toy` runs the
same through the cell's rehearsal on the CPU.
"""
import argparse
import importlib
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "serve-ling3flash-reasoning"
TOY = "--toy" in sys.argv
REFERENCE_SIDE = ("fp8_weights", "no_groups")
PROGRAM_SIDE = ("state_bf16", "chunk_bf16", "tail_dropped", "stale_entry",
                "lost_chunks")
OUT = os.path.join(ROOT, "chiprun_out", "pr47")
# what another cell's study exchanges (scripts/study_phi4flash_controls.py
# sets these names and `faulty`, then calls `main`): the modules of
# benchmarks/drivers and benchmarks/lib that hold the cell's `make_loader`
# and `reference_shape`, and the script a variant's child runs
REPLICA, CFG, SCRIPT = "replica_ling3", "ling3cfg", os.path.abspath(__file__)


def say(**kw):
    print(json.dumps(kw), flush=True)


def faulty(loader, variant):
    """`loader` behind a fault set in the process that calls it — the
    replica, before its engine traces a program (models/ling3.py looks
    these names up in its module when it traces)."""
    def load():
        import jax.numpy as jnp

        from ray_tpu.models import ling3 as lm

        half = lambda s: s.astype(jnp.bfloat16).astype(s.dtype)
        if variant == "state_bf16":
            chunk, step = lm.kda_chunk, lm.kda_step

            def chunk_half(*a, **kw):
                o, state = chunk(*a, **kw)
                return o, half(state)

            def step_half(q, k, v, log_a, beta, state, layer, idx, live,
                          **kw):
                o, state = step(q, k, v, log_a, beta, state, layer, idx,
                                live, **kw)
                return o, state.at[layer, idx].set(half(state[layer][idx]))

            lm.kda_chunk, lm.kda_step = chunk_half, step_half
        elif variant == "chunk_bf16":
            chunk = lm.kda_chunk
            lm.kda_chunk = lambda q, k, v, *a, **kw: chunk(
                half(q), half(k), half(v), *a, **kw)
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
        else:
            prefill = lm.paged_prefill

            def blind(params, cache, toks, ptab_rows, start, last_idx, cfg):
                tab = ptab_rows[lm.FULL]
                ps = cache["latent"][0].shape[2]
                tab = jnp.where(jnp.arange(tab.shape[0]) >= start // ps,
                                tab, 0)
                return prefill(params, cache, toks,
                               dict(ptab_rows, **{lm.FULL: tab}), start,
                               last_idx, cfg)

            lm.paged_prefill = blind
        return loader()

    return load


def cell_run(variant: str, seed: int):
    """This process as `benchmarks/run.py --child`: the cell's driver, once,
    with `variant` put in from here."""
    import benchmarks.run as R
    from benchmarks.lib import manifest

    rep = importlib.import_module(f"benchmarks.drivers.{REPLICA}")
    ling3cfg = importlib.import_module(f"benchmarks.lib.{CFG}")
    cell = manifest.resolve(manifest.load(), CELL)
    rundir = os.path.join(R.RUN_DIR, f"control-{variant}")
    os.makedirs(rundir, exist_ok=True)
    ctx = R._context(argparse.Namespace(
        seed=seed, seconds=3.0 if TOY else None, rehearse=TOY,
        t0=time.time(), rundir=rundir, trace=0), cell)
    if variant in REFERENCE_SIDE:
        shape = ling3cfg.reference_shape
        ling3cfg.reference_shape = lambda conf: dict(shape(conf),
                                                     control=variant)
    elif variant in PROGRAM_SIDE:
        make = rep.make_loader
        rep.make_loader = lambda *a: faulty(make(*a), variant)
    drv = importlib.import_module(
        f"benchmarks.drivers.{cell['traffic']['kind']}")
    out = drv.run(ctx)
    say(phase="verdict", variant=variant, seed=seed, correct=out["correct"],
        checks=out["checks"], failed=out["failed"],
        attempted=out["attempted"], setup_s=out["setup_s"],
        seconds_total=time.time() - ctx["t0"])
    sys.stdout.flush()
    os._exit(0)


def cell_runs(variants, seed: int):
    """Each variant in a child of its own, one after the other, with the
    environment `benchmarks/run.py` gives its child."""
    import benchmarks.run as R

    env = R._child_env(argparse.Namespace(rehearse=TOY), 1)
    readings = []
    for variant in variants:
        cmd = [sys.executable, SCRIPT, "--cell", variant,
               str(seed)] + (["--toy"] if TOY else [])
        t0 = time.time()
        proc = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                              text=True, start_new_session=True)
        lines = [json.loads(ln) for ln in proc.stdout.splitlines()
                 if ln.startswith("{")]
        by = {ln.get("phase"): ln for ln in lines}
        if proc.returncode or "verdict" not in by:
            say(phase="reading", variant=variant, seed=seed,
                exit=proc.returncode, stderr=proc.stderr[-1500:])
            continue
        ref, tails = by["serve.reference"], by.get("serve.tails", {})
        r = {"phase": "reading", "variant": variant, "seed": seed,
             "correct": by["verdict"]["correct"],
             "checks": by["verdict"]["checks"],
             "argmax_share": ref["argmax_share"],
             "worst_gap": ref["worst_gap"],
             "logit_rel_rms": ref["logit_rel_rms"],
             "state_rel_rms": ref.get("state_rel_rms"),
             "state_rel_rms_by_layer": ref.get("state_rel_rms_by_layer"),
             "state_half_share": ref.get("state_half_share"),
             "tail_rel_rms": ref.get("tail_rel_rms"),
             "first_keys_max": ref.get("first_keys_max"),
             "second_keys_q25": ref.get("second_keys_q25"),
             "checked": ref.get("checked"),
             "tokens_checked": ref.get("tokens_checked"),
             "per_request": [(p["context"], p["n_argmax"] / p["n"],
                              p["max_gap"]) for p in ref["per_request"]],
             "itl_ms": tails.get("itl_ms"),
             "after_window_s": tails.get("after_window_s"),
             "setup_s": by["verdict"]["setup_s"],
             "run_s": time.time() - t0,
             "limits": proc.stderr.strip().splitlines()[-1]}
        say(**r)
        readings.append(r)
    return readings


def main():
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [11]
    os.makedirs(OUT, exist_ok=True)
    if "--cell" in sys.argv:
        return cell_run(sys.argv[sys.argv.index("--cell") + 1], seeds[0])
    variants = ("sound",) + PROGRAM_SIDE + REFERENCE_SIDE
    if "--only" in sys.argv:
        variants = tuple(sys.argv[sys.argv.index("--only") + 1].split(","))
    readings = cell_runs(variants, seeds[0])
    with open(os.path.join(OUT, "controls.json"), "w") as f:
        json.dump(readings, f, indent=1)
    if len(readings) < len(variants):
        sys.exit(1)


if __name__ == "__main__":
    main()
