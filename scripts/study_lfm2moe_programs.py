"""What `lfm2-8b-a1b-l13`'s two serve programs cost by their load (PERF.md
section 5, PR 63): the step's time against the streams that are live (1, 4,
8, 16, 32, 64 of 64 slots, contexts of 2,048: below ~24 streams a step's
bytes follow the experts the batch touches), and ONE prefill program's time
at 512, 1,024 and 2,048 rows (a first chunk: its keys are its own rows)
with `moe_reads` over `moe_touched` beside each — at 256 rows an expert a
128-row trip reads an expert twice.

    python scripts/study_lfm2moe_programs.py [seed]

on the chip (~2 min, no cluster: the model's own programs under `jax.jit`
on the replica's loader's weights; host clock around `block_until_ready`,
the median of 20 calls); `--toy` at the rehearsal's sizes on the CPU.
Writes chiprun_out/pr63/programs.json.
"""
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOY = "--toy" in sys.argv


def main():
    import jax
    import numpy as np

    from benchmarks.drivers.replica_lfm2_moe import make_loader
    from benchmarks.lib import manifest
    from ray_tpu.models import lfm2_moe as lm

    seed = ([int(a) for a in sys.argv[1:] if a.isdigit()] or [63])[0]
    conf = manifest.resolve(manifest.load(),
                            "serve-lfm2moe-ragextract")["config"]
    ek = dict(conf["serve"]["engine_kwargs"])
    lives, rows, ctx = (1, 4, 8, 16, 32, 64), (512, 1024, 2048), 2048
    if TOY:
        with open(os.path.join(ROOT, "benchmarks", "tests",
                               "rehearsal_ragextract.json")) as f:
            toy = json.load(f)
        conf.update(toy["config"])
        conf["serve"] = dict(conf["serve"], max_seq=128)
        ek.update(toy["engine_kwargs"])
        lives, rows, ctx = (1, 2, 4), (16, 32), 24
    cfg, params = make_loader(conf, seed, {})()
    B, ps = ek["max_slots"], ek["page_size"]
    per = ek["max_total"] // ps
    cache = lm.init_paged_cache(cfg, ek["num_pages"], ps)
    step = jax.jit(lambda p, c, t, tabs, pos: lm.paged_decode_step(
        p, c, t, tabs, pos, cfg), donate_argnums=1)
    stat = lambda v: dict(zip(lm.STEP_STATS, np.asarray(v).tolist()))
    rng = np.random.default_rng(seed)
    out = {"seed": seed, "device": jax.devices()[0].device_kind,
           "step": [], "prefill": []}

    def timed(call, n=20):
        ms = []
        for _ in range(n):
            t0 = time.perf_counter()
            res = call()
            jax.block_until_ready(res)
            ms.append(1000.0 * (time.perf_counter() - t0))
        return statistics.median(ms), min(ms), res

    for live in lives:
        tabs = {"full": np.zeros((B, per), np.int32),
                "conv": np.zeros((B, 1), np.int32)}
        pos = np.zeros(B, np.int32)
        for s in range(live):
            tabs["full"][s] = 1 + s * per + np.arange(per)
            tabs["conv"][s, 0] = 1 + s
            pos[s] = ctx
        toks = rng.integers(0, cfg.vocab_size, B).astype(np.int32)
        state = {"c": cache}

        def call():
            lg, state["c"], st = step(params, state["c"], toks, tabs, pos)
            return lg, st

        call()                                   # compile
        med, low, (_, st) = timed(call)
        cache = state["c"]
        s = stat(st)
        row = {"live": live, "ms_median": med, "ms_min": low,
               "moe_touched": s["moe_touched"], "moe_reads": s["moe_reads"],
               "experts_touched_a_layer": s["moe_touched"]
               / (cfg.n_layers - cfg.n_dense)}
        print(json.dumps({"phase": "step", **row}), flush=True)
        out["step"].append(row)
    for T in rows:
        prefill = jax.jit(lambda p, c, t, tabs, a, b: lm.paged_prefill(
            p, c, t, tabs, a, b, cfg), donate_argnums=1)
        tabs = {"full": (1 + np.arange(per)).astype(np.int32),
                "conv": np.ones(1, np.int32)}
        toks = rng.integers(0, cfg.vocab_size, T).astype(np.int32)
        state = {"c": cache}

        def call():
            lg, state["c"], st = prefill(params, state["c"], toks, tabs,
                                         np.int32(0), np.int32(T - 1))
            return lg, st

        call()
        med, low, (_, st) = timed(call)
        cache = state["c"]
        s = stat(st)
        row = {"rows": T, "ms_median": med, "ms_min": low,
               "moe_pairs": s["moe_pairs"], "moe_touched": s["moe_touched"],
               "moe_reads": s["moe_reads"],
               "reads_per_touched": s["moe_reads"] / max(s["moe_touched"], 1),
               "load_max_over_mean": s["moe_load_max"] * cfg.n_experts
               / max(s["moe_pairs"], 1)}
        print(json.dumps({"phase": "prefill", **row}), flush=True)
        out["prefill"].append(row)
    dest = os.path.join(ROOT, "chiprun_out", "pr63")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, "programs.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
