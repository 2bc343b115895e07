"""The replica body of the cells that serve a `cohere2_moe` configuration:
`replica.BenchLLMServer` (time stamps, profiler, snapshot — inherited
whole) with this model's loader and this model's reference check."""

from __future__ import annotations

import time
from typing import Dict, List

from .replica import BenchLLMServer


def scale_weights(params, scales: Dict):
    """The configuration's `weights.scales`: {leaf name: factor} applied
    to the program's plain random draw — `embed` at the top of the tree,
    any other name in every layer.  Weights are this benchmark's data;
    what makes random ones stand in for trained ones (the configuration
    file says why each factor) is set here and not in the program's
    `init`."""
    def one(name, a):
        return a * scales[name] if name in scales else a

    out = {k: one(k, v) for k, v in params.items() if k != "layers"}
    out["layers"] = [{k: one(k, v) for k, v in layer.items()}
                     for layer in params["layers"]]
    return out


def make_loader(conf: Dict, seed: int, overrides: Dict):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed, a layer a program."""

    def loader():
        import jax

        from benchmarks.lib.cohere2cfg import model_config
        from ray_tpu.models import cohere2_moe as cm

        cfg = model_config(conf, **overrides)
        params = scale_weights(
            cm.init(jax.random.PRNGKey(seed % (2 ** 31)), cfg),
            conf.get("weights", {}).get("scales", {}))
        jax.block_until_ready(params)
        return cfg, params

    return loader


SCOPES = ("attn_window", "attn_full", "moe_router", "moe_experts",
          "moe_shared")


class Cohere2MoEServer(BenchLLMServer):
    def bench_program_scopes(self):
        """{module-name prefix: [{instruction: scope}, ...]} of the serve
        programs this engine has built, from their compiled text (the
        trace does not show a scope: benchmarks/trace/scopes.py).  Lowered
        again on the engine's own operands, found in the compile cache;
        after the window, so nothing of it is timed.  A generator of one
        item, as `bench_reference`."""
        import numpy as np

        from benchmarks.trace.scopes import scope_map

        eng = self._engine
        # the grouped products are custom calls whose op_name is their own
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
                        n_logits: int):
        """See BenchLLMServer.bench_reference: a generator of one item.
        `spec` is the traffic file's `reference`."""
        from benchmarks.reference.check_cohere2_moe import served_gaps

        t0 = time.time()
        per = served_gaps(self._params, sample, shape, spec["q_block"],
                          spec["rows"], spec["max_context"], n_logits)
        n = sum(p["n"] for p in per)
        yield {"worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
