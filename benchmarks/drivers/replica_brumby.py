"""The replica body of the cells that serve a `brumby` configuration:
`replica.BenchLLMServer` (time stamps, profiler, snapshot — inherited
whole) with this model's loader, scopes and reference check."""

from __future__ import annotations

import time
from typing import Dict, List

from .replica import BenchLLMServer


def shape_weights(params, weights: Dict):
    """The configuration's `weights`: every layer's leaf named in `scales`
    multiplied by its factor, and `gate_bias` added to every layer's gate
    bias.  Weights are this benchmark's data; what makes random ones stand
    in for trained ones (the configuration file says why each number) is
    set here and not in the program's `init`."""
    scales, bias = weights.get("scales", {}), weights.get("gate_bias", 0.0)

    def one(name, a):
        if name == "bg":
            return a + bias
        return a * scales[name] if name in scales else a

    return dict(params, layers={k: one(k, v)
                                for k, v in params["layers"].items()})


def make_loader(conf: Dict, seed: int, overrides: Dict):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed."""

    def loader():
        import jax

        from benchmarks.lib.brumbycfg import model_config
        from ray_tpu.models import brumby as bm

        cfg = model_config(conf, **overrides)
        params = shape_weights(
            bm.init(jax.random.PRNGKey(seed % (2 ** 31)), cfg),
            conf.get("weights", {}))
        jax.block_until_ready(params)
        return cfg, params

    return loader


SCOPES = ("retention_step", "retention_chunk", "mlp", "unembed")


class BrumbyServer(BenchLLMServer):
    def bench_program_scopes(self):
        """{module-name prefix: [{instruction: scope}, ...]} of the serve
        programs this engine has built, from their compiled text (see
        replica_cohere2_moe.Cohere2MoEServer.bench_program_scopes).  The
        step's kernel is a custom call named after itself."""
        import numpy as np

        from benchmarks.trace.scopes import scope_map

        eng = self._engine
        own = {"retention_step": "retention_step"}
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
                        n_logits: int):
        """See BenchLLMServer.bench_reference: a generator of one item.
        `spec` is the traffic file's `reference`."""
        from benchmarks.reference.check_brumby import (replay_logits,
                                                       served_gaps)

        t0 = time.time()
        # the first sampled request's prompt (the longest context) once
        # more through the engine's own programs, greedy, `replay_steps`
        # tokens far: its tokens join the sample, and the logits of its
        # last `replay_keep` tokens — by when a state that lost precision
        # at every step has drifted furthest — are held to the reference's.
        # The engine is idle.
        steps, keep = int(spec["replay_steps"]), int(spec["replay_keep"])
        got, toks = replay_logits(self._engine, sample[0]["tokens"], steps,
                                  keep)
        t1 = time.time()
        again = {"rid": "replay", "tokens": sample[0]["tokens"],
                 "served": toks}
        per = served_gaps(self._params, sample + [again], shape,
                          spec["rows"], spec["max_context"], n_logits,
                          replay=("replay", steps - keep, got))
        n = sum(p["n"] for p in per)
        served = sample[0]["served"][:steps]
        yield {"logit_rel_rms": per[-1]["logit_rel_rms"],
               "logit_max_abs": per[-1]["logit_max_abs"],
               "replay_matches_served": sum(
                   a == b for a, b in zip(toks, served)) / len(served),
               "replay_seconds": t1 - t0,
               "worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
