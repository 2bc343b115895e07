"""The replica body of the cells that serve a `bailing_hybrid` (Ling-3.0)
configuration: `replica.BenchLLMServer` (time stamps, profiler, snapshot —
inherited whole) with this model's loader, scopes and reference check."""

from __future__ import annotations

import time
from typing import Dict, List

from .replica import BenchLLMServer


def shape_weights(params, weights: Dict, seed: int, gate_lower: float):
    """The configuration's `weights` over the program's plain draw: every
    leaf named in `scales` multiplied by its factor; every expert layer's
    correction bias drawn (normal x `router_bias_std`); every KDA layer's
    `a_log` = log of a uniform draw in `a_range` a head and `dt_bias` such
    that a fresh gate (n Wf = 0) sits at log a = -t, t log-uniform in
    `fresh_log_a` a channel — float32, by the program's own piece-wise
    draw at the places after a layer's last leaf, a uniform the normal
    distribution's own function of a normal.  Weights are this benchmark's
    data; what makes random ones stand in for trained ones (the
    configuration file says why each number) is set here and not in the
    program's `init`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ling3 import LEAVES, _draw

    scales = weights.get("scales", {})
    std = float(weights.get("router_bias_std", 0.0))
    root = jax.random.PRNGKey(seed % (2 ** 31))
    uniform = lambda l, place, shape: jax.scipy.special.ndtr(
        _draw(root, l, len(LEAVES) + place, shape, 1.0, jnp.float32))

    def one(l, layer):
        out = {k: v * scales[k] if k in scales else v
               for k, v in layer.items()}
        if "router_bias" in out:
            out["router_bias"] = _draw(root, l, len(LEAVES),
                                       out["router_bias"].shape, std,
                                       out["router_bias"].dtype)
        if "a_log" in out:
            lo, hi = weights["a_range"]
            t0, t1 = weights["fresh_log_a"]
            a_log = jnp.log(lo + (hi - lo) * uniform(l, 1, out["a_log"].shape))
            t = t0 * (t1 / t0) ** uniform(l, 2, out["dt_bias"].shape)
            p = t / -gate_lower
            out.update(a_log=a_log, dt_bias=jnp.log(p / (1.0 - p))
                       / jnp.exp(a_log)[:, None])
        return out

    top = {k: v * scales[k] if k in scales else v
           for k, v in params.items() if k != "layers"}
    return dict(top, layers=[one(l, layer)
                             for l, layer in enumerate(params["layers"])])


def make_loader(conf: Dict, seed: int, overrides: Dict):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed."""

    def loader():
        import jax

        from benchmarks.lib.ling3cfg import model_config
        from ray_tpu.models import ling3 as lm

        cfg = model_config(conf, **overrides)
        params = shape_weights(
            lm.init(jax.random.PRNGKey(seed % (2 ** 31)), cfg),
            conf.get("weights", {}), seed, cfg.gate_lower)
        jax.block_until_ready(params)
        return cfg, params

    return loader


SCOPES = ("kda_proj", "kda_conv", "kda_chunk", "kda_step", "kda_out",
          "mla_q", "mla_kv", "mla_attend_step", "mla_attend_chunk",
          "mla_out", "moe_router", "moe_experts", "moe_shared", "mlp",
          "unembed")


class Ling3Server(BenchLLMServer):
    def bench_program_scopes(self):
        """{module-name prefix: [{instruction: scope}, ...]} of the serve
        programs this engine has built, from their compiled text (see
        replica_cohere2_moe.Cohere2MoEServer.bench_program_scopes)."""
        import numpy as np

        from benchmarks.trace.scopes import scope_map

        eng = self._engine
        own = {"ragged-dot": "moe_experts"}
        out = {"jit_serve_step": [], "jit_serve_prefill": []}
        for key, fn in list(eng._fns.items()):
            if key == "step":
                args = (eng._params, eng._cache, eng._logits, eng._toks_keys,
                        eng._temps, eng._topks, eng._ptabs, eng._pos)
            elif isinstance(key, tuple) and key[0] == "prefill":
                rows = {k: np.zeros(w, np.int32)
                        for k, w in eng._widths.items()}
                args = (eng._params, eng._cache, np.zeros(key[1], np.int32),
                        rows, np.int32(0), np.int32(0))
            else:
                continue
            text = fn.lower(*args).compile().as_text()
            out["jit_serve_" + (key if key == "step" else key[0])].append(
                scope_map(text, SCOPES, own))
        yield out

    def bench_cut(self) -> int:
        """The window has closed (serve_open_reasoning.window): every
        sequence the engine queues ends now, with nothing streamed, and
        every one it holds in a slot with its next token — a sequence in
        mid-prefill after its last chunk — by the engine's own way out
        (`_emit`: as many tokens as were asked for).  -> how many were
        cut.  Called again it finds nothing."""
        eng = self._engine
        with eng._lock:
            waiting = list(eng._waiting)
            eng._waiting.clear()
            held = [s for s in eng._slots if s is not None and s.max_new]
            for s in held:
                s.max_new = 0
        for s in waiting:
            eng._finish(s)
        eng._wake.set()
        return len(waiting) + len(held)

    def bench_prepare_reference(self, shape: Dict, spec: Dict, n_logits: int):
        """During set-up: start tracing and compiling the reference's six
        programs on a thread (nothing of it runs on the device), so that on
        a checkout's first run their compiles overlap the serve programs'
        and do not follow the window.  `bench_reference` takes what this
        built."""
        import concurrent.futures

        from benchmarks.reference.check_ling3 import build_programs

        self._bench_built = concurrent.futures.ThreadPoolExecutor(1).submit(
            build_programs, shape, spec, n_logits)
        return True

    def bench_reference(self, sample: List[Dict], shape: Dict, spec: Dict,
                        n_logits: int, weights: Dict, seed: int):
        """See replica_deepseek_v3.DeepSeekV3Server.bench_reference: a
        generator of one item; the reference is handed the SEED and draws
        its own weights."""
        import jax.numpy as jnp

        from benchmarks.reference.check_ling3 import (join_replays,
                                                      replay_logits,
                                                      served_gaps,
                                                      state_layers)

        t0 = time.time()
        # the first sampled request's prompt (the longest context: chunk
        # after chunk over its pages and its entry) and the second's (the
        # shortest prompt: what its entry held before has faded least)
        # once more through the engine's own programs, greedy: the logits
        # of their first `replay_keep` tokens are held to the reference's,
        # and what the replay leaves in its entry (entry 1) of the KDA
        # layers that no expert layer precedes to the recurrence's state
        # at that position.  The engine is idle.
        steps, keep = int(spec["replay_steps"]), int(spec["replay_keep"])
        n_held = len(state_layers(shape))
        replays = []
        for k in range(min(2, len(sample))):
            got, toks = replay_logits(self._engine, sample[k]["tokens"],
                                      steps, keep)
            replays.append((k, toks, steps - keep, got,
                            self._engine._cache["state"][:n_held, 1]))
        # the entry the replays carried (entry 1 of the state kind): the
        # share of its state matrices' nonzero values that bfloat16 holds
        # exactly — a handful in a million of float32's own, every one of
        # an arena kept or rounded in half precision
        held = self._engine._cache["state"][:, 1]
        same = held.astype(jnp.bfloat16).astype(held.dtype) == held
        half = float(jnp.sum(same & (held != 0)) / jnp.maximum(
            jnp.sum(held != 0), 1))
        t1 = time.time()
        entries, joined = join_replays(sample, replays)
        built = getattr(self, "_bench_built", None)
        per = served_gaps(int(seed), shape, weights, entries, spec, n_logits,
                          replays=joined,
                          built=built.result() if built else None)
        n = sum(p["n"] for p in per)
        held = [p for p in per if "logit_rel_rms" in p]
        yield {"state_rel_rms": max(p["state_rel_rms"] for p in held),
               "state_rel_rms_by_layer": [p["state_rel_rms_by_layer"]
                                          for p in held],
               "logit_rel_rms": max(p["logit_rel_rms"] for p in held),
               "logit_max_abs": max(p["logit_max_abs"] for p in held),
               "state_half_share": half,
               "replay_matches_served": float(len(entries) == len(sample)),
               "replay_seconds": t1 - t0,
               "worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
