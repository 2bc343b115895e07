"""The replica body of the cells that serve a `deepseek_v3` configuration:
`replica.BenchLLMServer` (time stamps, profiler, snapshot — inherited
whole) with this model's loader, scopes and reference check."""

from __future__ import annotations

import time
from typing import Dict, List

from .replica import BenchLLMServer


def shape_weights(params, weights: Dict, seed: int):
    """The configuration's `weights` over the program's plain draw: every
    layer's leaf named in `scales` multiplied by its factor, and every
    expert layer's correction bias drawn — normal x `router_bias_std`,
    float32, by the program's own piece-wise draw at the place after a
    layer's last leaf.
    Weights are this benchmark's data; what makes random ones stand in for
    trained ones (the configuration file says why each number) is set here
    and not in the program's `init`."""
    import jax

    from ray_tpu.models.deepseek_v3 import LEAVES, _draw

    scales = weights.get("scales", {})
    std = float(weights.get("router_bias_std", 0.0))
    root = jax.random.PRNGKey(seed % (2 ** 31))

    def one(l, layer):
        out = {k: v * scales[k] if k in scales else v
               for k, v in layer.items()}
        if "router_bias" in out:
            out["router_bias"] = _draw(root, l, len(LEAVES),
                                       out["router_bias"].shape, std,
                                       out["router_bias"].dtype)
        return out

    top = {k: v * scales[k] if k in scales else v
           for k, v in params.items() if k != "layers"}
    return dict(top, layers=[one(l, layer)
                             for l, layer in enumerate(params["layers"])])


def make_loader(conf: Dict, seed: int, overrides: Dict):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed, a layer a program."""

    def loader():
        import jax

        from benchmarks.lib.deepseekcfg import model_config
        from ray_tpu.models import deepseek_v3 as dm

        cfg = model_config(conf, **overrides)
        params = shape_weights(
            dm.init(jax.random.PRNGKey(seed % (2 ** 31)), cfg),
            conf.get("weights", {}), seed)
        jax.block_until_ready(params)
        return cfg, params

    return loader


SCOPES = ("mla_q", "mla_kv", "mla_attend_step", "mla_attend_chunk",
          "mla_out", "moe_router", "moe_experts", "moe_shared", "mlp",
          "unembed")


class DeepSeekV3Server(BenchLLMServer):
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

    def bench_reference(self, sample: List[Dict], shape: Dict, spec: Dict,
                        n_logits: int, weights: Dict, seed: int):
        """See BenchLLMServer.bench_reference: a generator of one item.
        `spec` is the traffic file's `reference`, `weights` the
        configuration's.  The reference is handed the SEED and draws its
        own weights: no array of this replica's reaches it."""
        from benchmarks.reference.check_deepseek_v3 import (join_replay,
                                                            replay_logits,
                                                            served_gaps)

        t0 = time.time()
        # the first sampled request's prompt (the longest context) once
        # more through the engine's own programs, greedy: the logits of
        # its last `replay_keep` tokens are held to the reference's.  The
        # engine is idle.
        steps, keep = int(spec["replay_steps"]), int(spec["replay_keep"])
        got, toks = replay_logits(self._engine, sample[0]["tokens"], steps,
                                  keep)
        t1 = time.time()
        entries, replay = join_replay(sample, toks, steps - keep, got)
        per = served_gaps(int(seed), shape, weights, entries, spec, n_logits,
                          replay=replay)
        n = sum(p["n"] for p in per)
        held = next(p for p in per if "logit_rel_rms" in p)
        yield {"logit_rel_rms": held["logit_rel_rms"],
               "logit_max_abs": held["logit_max_abs"],
               "replay_matches_served": float(len(entries) == len(sample)),
               "replay_seconds": t1 - t0,
               "worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
