"""Where the limits of `serve-brumby-streams`'s reference check come from,
and what that check sees (PERF.md section 6, PR 40; the readings stand in
benchmarks/traffic/open-streams.json).

On the chip, one process, no cluster: the configuration's model with the
loader's weights behind a `ContinuousEngine` driven by hand; a seeded set
of requests (prompts of one to five chunks, outputs of 64-512 tokens) is
served greedily and the served tokens are held to the plain reference
exactly as the benchmark holds them (`check_brumby.served_gaps`: share of
served tokens that are the reference's argmax, largest distance of one
below its position's maximum; and the longest prompt once more through
the engine's programs, 192 tokens far, the logits of the last 64 as a root
mean square distance from the reference's in units of their spread:
`replay_logits`).

  sound            the program as it is: the reading that must pass
  unzeroed         a sequence's first chunk reads what its entry's last
                   holder left there (the engine re-uses entries)
  dropped_carry    every chunk starts from an empty state
  state_bf16       the state arena kept in bfloat16 (`state_dtype`)
  reference_fp8    the sound program's tokens against the reference with
                   every matrix rounded to fp8-e4m3: the control in the
                   nearest precision below the configuration's

It also times `serve.step` at 0-16 live slots and a 512-token chunk.

    python scripts/study_brumby_controls.py [seed ...]    (on a TPU)

`--scales wo,w_down,embed,gate_bias ...` reads `sound` and `state_bf16`
under each of the given `weights` settings instead of the configuration's
(how the configuration's were chosen): how many distinct tokens a greedy
stream holds, its longest run of one token, and the two readings.

Writes chiprun_out/pr40/study.json.  `--toy` runs the control flow at toy
sizes on the CPU.
"""
import dataclasses
import functools
import json
import os
import sys
import threading
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers.replica_brumby import shape_weights
from benchmarks.lib.brumbycfg import model_config, reference_shape
from benchmarks.reference.check_brumby import replay_logits, served_gaps
from ray_tpu.models import brumby as bm
from ray_tpu.serve._engine import ContinuousEngine

TOY = "--toy" in sys.argv
PLENS = (100, 384, 700, 1100, 2048, 64, 300, 520)
OLENS = (128, 256, 96, 160, 512, 64, 224, 128)
REPLAY = (12, 4) if TOY else (192, 64)      # tokens replayed, rows compared


def say(**kw):
    print(json.dumps(kw), flush=True)


def faulty(mode):
    """`bm` with a prefill that reads its entry wrongly."""
    def prefill(params, cache, toks, ptab_rows, start, last_idx, cfg):
        T = toks.shape[0]
        t = jnp.arange(T, dtype=jnp.int32)
        idx = ptab_rows[bm.KIND][0]
        states = jax.lax.dynamic_index_in_dim(cache, idx, 1, keepdims=False)
        if mode == "dropped_carry":
            states = jnp.zeros_like(states)
        x, states = bm._chunk_pass(params, toks, start + t, t <= last_idx,
                                   states, cfg)
        cache = jax.lax.dynamic_update_index_in_dim(
            cache, states.astype(cache.dtype), idx, 1)
        x = jax.lax.dynamic_index_in_dim(x, last_idx, 0, keepdims=False)
        return bm._logits(params, x, cfg), cache, jnp.ones((1,), jnp.float32)

    return types.SimpleNamespace(**{**vars(bm), "paged_prefill": prefill})


def serve(mod, cfg, params, ek, batches, replay=REPLAY):
    """Each batch of (prompt, n) served to its end, one after the other
    through ONE engine; the last batch's served tokens, and its longest
    prompt once more through the same engine's programs, `replay[0]`
    tokens far, with the logits of the last `replay[1]` of them
    (`replay_logits`, as the benchmark's check)."""
    eng = ContinuousEngine(mod, cfg, params, **ek)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    eng._thread = t
    try:
        for batch in batches:
            seqs = [eng.submit(p, n) for p, n in batch]
            t0 = time.time()
            while not all(s.result.done() for s in seqs):
                eng._iteration()
            took = time.time() - t0
        out = [s.result.result()["completion"] for s in seqs]
        steps = [r["decode_s"] for r in eng.phase_ring() if r["active"]]
        k = max(range(len(batch)), key=lambda i: len(batch[i][0]))
        rows, toks = replay_logits(eng, batch[k][0], *replay)
        again = {"rid": "replay", "tokens": batch[k][0], "served": toks}
        return (out, took, float(np.median(steps)) if steps else 0.0,
                (again, ("replay", replay[0] - replay[1], rows)))
    finally:
        eng.stop()
        eng._cache = eng._logits = None


def time_programs(cfg, params, ek):
    eng = ContinuousEngine(bm, cfg, params, **ek)
    try:
        eng._ensure_device_state()
        out = {}
        for live in (0, 4, 8, 12, 16):
            eng._pos[:] = 0
            eng._pos[:live] = 100
            eng._ptabs[bm.KIND][:, 0] = 0
            eng._ptabs[bm.KIND][:live, 0] = 1 + np.arange(live)
            ts = []
            for i in range(12):
                t0 = time.perf_counter()
                toks, eng._logits, eng._cache, _ = eng._fn("step")(
                    eng._params, eng._cache, eng._logits, eng._toks_keys,
                    eng._temps, eng._topks, eng._ptabs, eng._pos)
                np.asarray(toks)
                ts.append(time.perf_counter() - t0)
            out[f"step_ms_live{live}"] = 1000 * float(np.median(ts[2:]))
        T = int(ek["prefill_chunk"])
        for start in (0, T):
            ts = []
            for i in range(6):
                t0 = time.perf_counter()
                lg, eng._cache, _ = eng._fn(("prefill", T))(
                    eng._params, eng._cache, np.ones(T, np.int32),
                    {bm.KIND: np.array([1], np.int32)}, np.int32(start),
                    np.int32(T - 1))
                jax.block_until_ready(lg)
                ts.append(time.perf_counter() - t0)
            out[f"chunk{T}_ms_start{start}"] = 1000 * float(np.median(ts[2:]))
        return out
    finally:
        eng.stop()
        eng._cache = eng._logits = None


def fp8(params):
    """Every matrix rounded to fp8-e4m3's grid (4 significant bits, the
    smallest step 2^-9, the largest value 448), a leaf at a time, in
    arithmetic: the v5e has no fp8 type and its compiler makes a pair of
    converts through one the identity (the first study's control read
    exactly what the sound reference read)."""
    @functools.partial(jax.jit, donate_argnums=0)
    def rnd(w):
        x = jnp.clip(w.astype(jnp.float32), -448.0, 448.0)
        m, e = jnp.frexp(x)
        normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
        return jnp.where(jnp.abs(x) < 2.0 ** -6,
                         jnp.round(x * 512.0) / 512.0, normal).astype(w.dtype)

    def leaf(w):
        return rnd(w) if w.ndim >= 2 and w.dtype != jnp.float32 else w

    return jax.tree.map(leaf, params)


def rescale(params, old: dict, new: dict):
    """The leaves of `old`'s factors at `new`'s, in place (powers of two)."""
    mul = jax.jit(lambda w, r: (w * r).astype(w.dtype), donate_argnums=0)
    layers = dict(params["layers"])
    out = dict(params, layers=layers)
    for name in set(old) | set(new):
        r = new.get(name, 1.0) / old.get(name, 1.0)
        if r != 1.0 and name == "embed":
            out["embed"] = mul(out["embed"], r)
        elif r != 1.0:
            layers[name] = mul(layers[name], r)
    return out


def longest_run(tokens):
    best = run = 1
    for a, b in zip(tokens, tokens[1:]):
        run = run + 1 if a == b else 1
        best = max(best, run)
    return best


def main():
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [11]
    settings = [tuple(float(x) for x in a.split(","))
                for a in sys.argv[1:] if "," in a]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "brumby-14b-l8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "open-streams.json")) as f:
        spec = json.load(f)["reference"]
    plens, olens, n_logits = PLENS, OLENS, 512
    if TOY:
        with open(os.path.join(ROOT, "benchmarks", "tests",
                               "rehearsal_streams.json")) as f:
            toy = json.load(f)
        conf.update(toy["config"])
        conf["serve"]["engine_kwargs"].update(toy["engine_kwargs"])
        spec = toy["traffic"]["reference"]
        plens, olens, n_logits = (10, 24, 40, 8), (12, 8, 16, 6), 16
    ek = {k: v for k, v in conf["serve"]["engine_kwargs"].items()
          if k != "ring_size"}
    shape = reference_shape(conf)
    results = []
    for seed in seeds:
        cfg = model_config(conf)
        params = shape_weights(bm.init(jax.random.PRNGKey(seed), cfg),
                               conf["weights"])
        jax.block_until_ready(params)
        say(phase="weights", seed=seed, device=jax.devices()[0].device_kind)
        if seed == seeds[0] and not TOY and not settings:
            say(phase="times", **time_programs(cfg, params, ek))
        rng = np.random.default_rng(seed)
        draw = lambda: [(rng.integers(0, cfg.vocab_size, p).tolist(), n)
                        for p, n in zip(plens, olens)]
        before, batch = draw(), draw()

        def reading(name, served, against, replay):
            sample = [{"rid": i, "tokens": p, "served": s}
                      for i, ((p, _), s) in enumerate(zip(batch, served))]
            per = served_gaps(against, sample + [replay[0]], shape,
                              spec["rows"], spec["max_context"], n_logits,
                              replay=replay[1])
            n = sum(p["n"] for p in per)
            r = {"variant": name, "seed": seed,
                 "logit_rel_rms": per[-1]["logit_rel_rms"],
                 "logit_max_abs": per[-1]["logit_max_abs"],
                 "argmax_share": sum(p["n_argmax"] for p in per) / n,
                 "worst_gap": max(p["max_gap"] for p in per),
                 "per_request": [(p["context"], round(p["n_argmax"] / p["n"],
                                                      3),
                                  round(p["max_gap"], 3),
                                  round(p["median_top2_gap"], 3))
                                 for p in per],
                 "distinct_tokens": [len(set(s)) for s in served],
                 "longest_run": [longest_run(s) for s in served]}
            del per
            results.append(r)
            say(phase="reading", **r)

        low = dataclasses.replace(cfg, state_dtype=jnp.bfloat16,
                                  retention_impl="xla")
        now = dict(conf["weights"]["scales"])
        bias = conf["weights"]["gate_bias"]
        for wo, w_down, embed, gate_bias in settings:
            new = {"wo": wo, "w_down": w_down, "embed": embed}
            params = rescale(params, now, new)
            params["layers"]["bg"] = params["layers"]["bg"] + (gate_bias
                                                               - bias)
            now, bias = new, gate_bias
            for name, c in (("sound", cfg), ("state_bf16", low)):
                served, _, _, rows = serve(bm, c, params, ek, [batch])
                reading(f"{name} wo={wo:g} w_down={w_down:g} embed={embed:g}"
                        f" bias={gate_bias:g}", served, params, rows)
        if settings:
            continue
        runs = [before, before, batch]      # every entry has had a holder
        sound, took, step, sound_rows = serve(bm, cfg, params, ek, runs)
        say(phase="served", variant="sound", seconds=took,
            median_step_ms=1000 * step)
        reading("sound", sound, params, sound_rows)
        for mode in ("unzeroed", "dropped_carry"):
            served, _, _, rows = serve(faulty(mode), cfg, params, ek, runs)
            reading(mode, served, params, rows)
        served, _, _, rows = serve(bm, low, params, ek, [batch])
        reading("state_bf16", served, params, rows)
        params = fp8(params)
        reading("reference_fp8", sound, params, sound_rows)
        del params
    out = os.path.join(ROOT, "chiprun_out", "pr40")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "study_toy.json" if TOY else "study.json"),
              "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
