"""Where the limits of `serve-dots3note-sparsectx`'s reference check come
from, and what that check sees (PERF.md section 6, PR 56; the readings
stand in benchmarks/traffic/open-sparsectx.json).

Every reading is a RUN OF THE CELL by its own driver — what
`benchmarks/run.py`'s child does, word for word: the manifest's cell, the
driver's `run(ctx)`, the cluster, the replica, the cell's traffic at its
rate, the sample, the replay and the three limits — with one fault put in
from outside the benchmark's files (`reference_shape(..)["control"]`: the
sound program's tokens against a faulty reference; the distance is the
same whichever side carries the fault), so that `correct` is the cell's
own verdict:

  sound            the program and the reference as they are: correct
  no_selection     every causal key attended in the full layers
  topk_half        index_topk 1,024
  no_index_rope    the indexer's queries and keys not turned
  window_512       the sliding layers' window one position short
  swa_theta_full   the sliding layers turned at the full layers' theta
  no_gate          the heads' sigmoid gate dropped
  no_lora_rescale  the latents' sqrt(hidden / rank) dropped
  fp8_weights      every matrix rounded to fp8-e4m3 in arithmetic: the
                   nearest precision below the configuration's
  index_8bit       the indexer's key rows rounded to fp8-e4m3

    python scripts/study_dots3_controls.py [--only a,b] [--seconds s] [seed]

runs each variant in a child of its own (a chip belongs to one replica at a
time), prints a `reading` line each — the three numbers, the checks,
`correct` — and writes chiprun_out/pr56/controls.json.  `--toy` runs the
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

CELL = "serve-dots3note-sparsectx"
TOY = "--toy" in sys.argv
CONTROLS = ("no_selection", "topk_half", "no_index_rope", "window_512",
            "swa_theta_full", "no_gate", "no_lora_rescale", "fp8_weights",
            "index_8bit")
OUT = os.path.join(ROOT, "chiprun_out", "pr56")


def _option(name: str, default):
    """`--name value` off the command line (and out of it)."""
    if name not in sys.argv:
        return default
    at = sys.argv.index(name)
    value = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    return value


SECONDS = _option("--seconds", None)


def say(**kw):
    print(json.dumps(kw), flush=True)


def cell_run(variant: str, seed: int):
    """This process as `benchmarks/run.py --child`: the cell's driver, once,
    with `variant` put in from here."""
    import benchmarks.run as R
    from benchmarks.lib import dots3cfg, manifest

    cell = manifest.resolve(manifest.load(), CELL)
    rundir = os.path.join(R.RUN_DIR, f"control-{variant}")
    os.makedirs(rundir, exist_ok=True)
    ctx = R._context(argparse.Namespace(
        seed=seed, seconds=3.0 if TOY else
        (float(SECONDS) if SECONDS else None), rehearse=TOY, t0=time.time(),
        rundir=rundir, trace=0), cell)
    if variant in CONTROLS:
        shape = dots3cfg.reference_shape
        dots3cfg.reference_shape = lambda conf: dict(shape(conf),
                                                     control=variant)
    drv = importlib.import_module(
        f"benchmarks.drivers.{cell['traffic']['kind']}")
    out = drv.run(ctx)
    say(phase="verdict", variant=variant, seed=seed, correct=out["correct"],
        checks=out["checks"], failed=out["failed"],
        attempted=out["attempted"], setup_s=out["setup_s"],
        memory_peak_bytes=out["device"].get("memory_peak_bytes"),
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
        cmd = [sys.executable, os.path.abspath(__file__), "--cell", variant,
               str(seed)] + (["--toy"] if TOY else []) + (
                   ["--seconds", SECONDS] if SECONDS else [])
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
             "checked": ref.get("checked"),
             "tokens_checked": ref.get("tokens_checked"),
             "per_request": [(p["context"], p["n_argmax"] / p["n"],
                              p["max_gap"], p["seconds"])
                             for p in ref["per_request"]],
             "reference_s": ref.get("seconds"),
             "ttft_ms": tails.get("ttft_ms"),
             "requests": tails.get("requests"),
             "after_window_s": tails.get("after_window_s"),
             "setup_s": by["verdict"]["setup_s"],
             "memory_peak_bytes": by["verdict"]["memory_peak_bytes"],
             "run_s": time.time() - t0,
             "limits": proc.stderr.strip().splitlines()[-1]}
        say(**r)
        readings.append(r)
    return readings


def main():
    only = _option("--only", None)
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--cell" in sys.argv:
        return cell_run(args[0], int(args[1]))
    seed = int(args[0]) if args else 3000000019
    variants = only.split(",") if only else ("sound",) + CONTROLS
    readings = cell_runs(variants, seed)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "controls.json"), "w") as f:
        json.dump(readings, f, indent=1)


if __name__ == "__main__":
    main()
