"""Where the limits of `serve-deepseekv3-longctx`'s reference check come
from, and what that check sees (PERF.md section 6, PR 44; the readings
stand in benchmarks/traffic/open-longctx.json).

Every reading is a RUN OF THE CELL by its own driver — what
`benchmarks/run.py`'s child does, word for word: the manifest's cell, the
driver's `run(ctx)`, the cluster, the replica, the cell's traffic at its
rate, the sample, the replay and the three limits — with one fault put in
from outside the benchmark's files, so that `correct` is the cell's own
verdict:

  sound           the program as it is: must come out correct
  -- faults put into the PROGRAM (in the replica, before its engine is
  -- built: the loader handed to `LLMServer` sets them and then loads)
  latent_8bit     the rows the latent arena keeps rounded to fp8-e4m3
  lost_chunks     a prefill chunk whose table holds the null page wherever
                  an earlier chunk's latents lie
  -- faults put into the REFERENCE (`reference_shape(..)["control"]`: the
  -- sound program's tokens against it; the distance is the same
  -- whichever side carries the fault)
  fp8_weights     every matrix rounded to fp8-e4m3: the nearest precision
                  below the configuration's
  no_rope_score   the rope part dropped from the score
  no_yarn_scale   the score scaled by 192^-1/2 alone
  no_groups       plain top-8 over 256 experts

    python scripts/study_deepseek_v3_controls.py [--only a,b] [seed]

runs each variant in a child of its own (a chip belongs to one replica at
a time), prints a `reading` line each — the three numbers, the checks,
`correct` — and writes chiprun_out/pr44/controls.json.  `--toy` runs the
same through the cell's rehearsal on the CPU.

`--time` instead times, in this process and without a cluster,
`serve.step` over contexts and a 512-row chunk over starts with attention
absorbed and expanded (how `deepseek_v3.ABSORB_ROWS` was set); writes
chiprun_out/pr44/study_time.json.
"""
import argparse
import functools
import importlib
import json
import os
import subprocess
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "serve-deepseekv3-longctx"
TOY = "--toy" in sys.argv
REFERENCE_SIDE = ("fp8_weights", "no_rope_score", "no_yarn_scale",
                  "no_groups")
PROGRAM_SIDE = ("latent_8bit", "lost_chunks")
OUT = os.path.join(ROOT, "chiprun_out", "pr44")


def say(**kw):
    print(json.dumps(kw), flush=True)


def faulty(loader, variant):
    """`loader` behind a fault set in the process that calls it — the
    replica, before its engine traces a program (`dm._block` and the
    engine look both names up in the module when they trace)."""
    def load():
        import jax.numpy as jnp

        from benchmarks.reference.deepseek_v3_plain import _fp8
        from ray_tpu.models import deepseek_v3 as dm

        if variant == "latent_8bit":
            rows = dm.latent_rows

            def rounded(h, layer, pos, cfg):
                r = rows(h, layer, pos, cfg)
                return _fp8(r.astype(jnp.float32)).astype(r.dtype)

            dm.latent_rows = rounded
        else:
            prefill = dm.paged_prefill

            def blind(params, cache, toks, ptab_rows, start, last_idx, cfg):
                tab = dm._only(ptab_rows)
                ps = cache[0].shape[2]
                tab = jnp.where(jnp.arange(tab.shape[0]) >= start // ps,
                                tab, 0)
                return prefill(params, cache, toks, tab, start, last_idx,
                               cfg)

            dm.paged_prefill = blind
        return loader()

    return load


def cell_run(variant: str, seed: int):
    """This process as `benchmarks/run.py --child`: the cell's driver, once,
    with `variant` put in from here."""
    import benchmarks.run as R
    from benchmarks.drivers import replica_deepseek_v3 as rep
    from benchmarks.lib import deepseekcfg, manifest

    cell = manifest.resolve(manifest.load(), CELL)
    rundir = os.path.join(R.RUN_DIR, f"control-{variant}")
    os.makedirs(rundir, exist_ok=True)
    ctx = R._context(argparse.Namespace(
        seed=seed, seconds=3.0 if TOY else None, rehearse=TOY,
        t0=time.time(),
        rundir=rundir, trace=0), cell)
    if variant in REFERENCE_SIDE:
        shape = deepseekcfg.reference_shape
        deepseekcfg.reference_shape = lambda conf: dict(shape(conf),
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
        cmd = [sys.executable, os.path.abspath(__file__), "--cell", variant,
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
             "checked": ref.get("checked"),
             "tokens_checked": ref.get("tokens_checked"),
             "per_request": [(p["context"], p["n_argmax"] / p["n"],
                              p["max_gap"]) for p in ref["per_request"]],
             "ttft_ms": tails.get("ttft_ms"),
             "after_window_s": tails.get("after_window_s"),
             "setup_s": by["verdict"]["setup_s"],
             "run_s": time.time() - t0,
             "limits": proc.stderr.strip().splitlines()[-1]}
        say(**r)
        readings.append(r)
    return readings


def by_form(dm, absorbed: bool):
    """`dm` with a prefill that attends in the named form whatever its
    rows (the engine's own goes by `dm.ABSORB_ROWS`)."""
    return types.SimpleNamespace(**{**vars(dm), "paged_prefill":
                                    functools.partial(dm.paged_prefill,
                                                      absorbed=absorbed)})


def time_programs(conf, seed, ek):
    """Median seconds of `serve.step` at 32 live slots over contexts, and
    of `serve.prefill:512` over starts, attention absorbed and expanded."""
    import jax
    import numpy as np

    from benchmarks.drivers.replica_deepseek_v3 import shape_weights
    from benchmarks.lib.deepseekcfg import model_config
    from ray_tpu.models import deepseek_v3 as dm
    from ray_tpu.serve._engine import ContinuousEngine

    say(phase="device", platform=jax.devices()[0].platform,
        kind=jax.devices()[0].device_kind)
    out = {}
    cfg = model_config(conf)
    for name, absorbed in (("expanded", False), ("absorbed", True)):
        params = shape_weights(dm.init(jax.random.PRNGKey(seed % 2 ** 31),
                                       cfg), conf["weights"], seed)
        eng = ContinuousEngine(by_form(dm, absorbed), cfg, params, **ek)
        del params
        eng._ensure_device_state()
        kind, B, W = eng._main, eng.max_slots, eng._widths[eng._main]
        per = W if TOY else 136
        tab = np.arange(1, 1 + per, dtype=np.int32)
        T = eng.prefill_chunk
        for start in ((0, 16) if TOY else (0, 2048, 6144, 12288, 15872)):
            ts = []
            for _ in range(4):
                t0 = time.perf_counter()
                row, eng._cache, _ = eng._fn(("prefill", T))(
                    eng._params, eng._cache, np.ones(T, np.int32),
                    {kind: np.pad(tab, (0, W - per))}, np.int32(start),
                    np.int32(T - 1))
                jax.block_until_ready(row)
                ts.append(time.perf_counter() - t0)
            out[f"chunk_{name}_start{start}"] = float(np.median(ts[1:]))
            say(phase="time", program=f"prefill:{T}", form=name, start=start,
                seconds=ts)
        if name == "expanded":      # the step is absorbed either way
            ptabs = np.zeros((B, W), np.int32)
            for b in range(B):
                ptabs[b, :per] = 1 + (np.arange(per) + b * per) % (
                    eng.num_pages - 1)
            for ctx in ((8, 40) if TOY else (1024, 4096, 8192, 16384)):
                ts = []
                for _ in range(6):
                    t0 = time.perf_counter()
                    tok, eng._logits, eng._cache, _ = eng._fn("step")(
                        eng._params, eng._cache, eng._logits,
                        np.zeros((B, 2), np.uint32), np.zeros(B, np.float32),
                        np.zeros(B, np.int32), {kind: ptabs},
                        np.full(B, ctx, np.int32))
                    jax.block_until_ready(tok)
                    ts.append(time.perf_counter() - t0)
                out[f"step_ctx{ctx}"] = float(np.median(ts[1:]))
                say(phase="time", program="step", context=ctx, seconds=ts)
        eng.stop()
        eng._cache = eng._logits = eng._params = None
    return out


def main():
    from benchmarks.lib import manifest

    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [11]
    os.makedirs(OUT, exist_ok=True)
    if "--cell" in sys.argv:
        return cell_run(sys.argv[sys.argv.index("--cell") + 1], seeds[0])
    if "--time" in sys.argv:
        conf = manifest.resolve(manifest.load(), CELL)["config"]
        ek = dict(conf["serve"]["engine_kwargs"])
        if TOY:
            with open(os.path.join(ROOT, "benchmarks", "tests",
                                   "rehearsal_longctx.json")) as f:
                toy = json.load(f)
            conf = {**conf, **toy["config"]}
            ek.update(toy["engine_kwargs"])
        ek.pop("ring_size", None)
        out = time_programs(conf, seeds[0], ek)
        with open(os.path.join(OUT, "study_time.json"), "w") as f:
            json.dump(out, f, indent=1)
        return
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
