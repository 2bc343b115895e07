"""The replica body of the cells that serve an `lfm2_moe` configuration:
`replica.BenchLLMServer` (time stamps, profiler, snapshot — inherited
whole) with this model's loader, scopes and reference check; the window's
cut is `replica_ling3.Ling3Server.bench_cut`, taken as it is (it asks the
engine and nothing of the model)."""

from __future__ import annotations

import logging
import time
from typing import Dict, List

from .replica import BenchLLMServer
from .replica_ling3 import Ling3Server

# the reference's programs, being built since the replica's loader ran
# (`make_loader(.., reference=)`): what `bench_reference` takes
_BUILT = None


def _scaled(tree: Dict, weights: Dict):
    scales = weights.get("scales", {})
    return {k: v * scales[k] if k in scales else v for k, v in tree.items()}


def shape_layer(l: int, layer: Dict, weights: Dict, seed: int):
    """The configuration's `weights` over layer l's plain draw: every leaf
    named in `scales` multiplied by its factor — the experts' gate and up,
    one leaf `wgu` in the tree, by `scales.wg` / `scales.wu` a half — and
    every expert layer's bias drawn (normal x `router_bias_std`, float32,
    at the place after a layer's last leaf).  Weights are this benchmark's
    data; what makes random ones stand in for trained ones (the
    configuration file says why each number) is set here and not in the
    program's `init`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import LEAVES
    from ray_tpu.models.ling3 import _draw

    out = _scaled(layer, weights)
    scales = weights.get("scales", {})
    if "wgu" in out and ("wg" in scales or "wu" in scales):
        F = out["wgu"].shape[-1] // 2
        half = jnp.concatenate([jnp.full((F,), scales.get("wg", 1)),
                                jnp.full((F,), scales.get("wu", 1))])
        out["wgu"] = out["wgu"] * half.astype(out["wgu"].dtype)
    if "router_bias" in out:
        out["router_bias"] = _draw(
            jax.random.PRNGKey(seed % (2 ** 31)), l, len(LEAVES),
            out["router_bias"].shape,
            float(weights.get("router_bias_std", 0.0)), jnp.float32)
    return out


def shape_weights(params, weights: Dict, seed: int):
    """`shape_layer` over a whole tree, its embedding scaled too (the CPU
    tests' sizes: the replica makes its tree a layer at a time)."""
    top = _scaled({k: v for k, v in params.items() if k != "layers"},
                  weights)
    return dict(top, layers=[shape_layer(l, layer, weights, seed)
                             for l, layer in enumerate(params["layers"])])


def make_loader(conf: Dict, seed: int, overrides: Dict, reference=None):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed, a layer at a time (a layer's
    plain draw stands beside its shaped copy only until the next one's
    turn).  With `reference` = (shape, spec, n_logits, weights, seed) the
    reference's programs are traced and compiled on a thread that starts
    HERE (nothing of it runs on the device) and is WAITED FOR before the
    loader returns: a thread of the replica's that still traces when the
    engine takes its first request keeps the engine's thread from the
    interpreter (PR 56, PR 58)."""

    def loader():
        import jax

        from benchmarks.lib.lfm2moecfg import model_config
        from ray_tpu.models import lfm2_moe as lm

        from ._common import memory_peak_bytes

        if reference is not None:
            import concurrent.futures

            from benchmarks.reference.check_lfm2_moe import build_programs

            global _BUILT
            _BUILT = concurrent.futures.ThreadPoolExecutor(1).submit(
                build_programs, *reference)
        cfg = model_config(conf, **overrides)
        weights = conf.get("weights", {})
        root = jax.random.PRNGKey(seed % (2 ** 31))
        params = lm.serve_view(_scaled(lm.init_top(root, cfg), weights), cfg)
        params["layers"] = [
            jax.block_until_ready(lm.serve_view(shape_layer(
                l, lm.init_layer(root, cfg, l), weights, seed), cfg))
            for l in range(cfg.n_layers)]
        jax.block_until_ready(params)
        if reference is not None:
            _BUILT.result()
        logging.getLogger(__name__).warning(
            "loader: memory_peak_bytes %s after the layers",
            memory_peak_bytes())
        return cfg, params

    return loader


SCOPES = ("conv_proj", "short_conv", "conv_out", "attn_proj", "attn_step",
          "attn_chunk", "mlp", "moe_router", "moe_experts", "lm_head")


class Lfm2MoeServer(BenchLLMServer):
    bench_cut = Ling3Server.bench_cut

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
        """See replica_falcon_h1.FalconH1Server.bench_reference: a
        generator of one item; the reference is handed the SEED and draws
        its own weights (`weights` is the configuration's description of
        the recipe: both sides write it out)."""
        from benchmarks.reference.check_lfm2_moe import (join_replays,
                                                         replay_logits,
                                                         served_gaps)

        t0 = time.time()
        # the first sampled request's prompt (the longest context: chunk
        # after chunk over its pages and its entry) once more through the
        # engine's own programs, greedy: the logits of its first
        # `replay_keep` tokens are held to the reference's, and what the
        # replay leaves behind — its entry's tails (entry 1: a conv
        # layer's last two rows of z) and its pages' key rows — to the
        # reference's own rows at those positions.  The engine is idle.
        keep = int(spec["replay_keep"])
        got, toks, left = replay_logits(self._engine, sample[0]["tokens"],
                                        keep, keep)
        replays = [(0, toks, 0, got, left)]
        t1 = time.time()
        entries, joined = join_replays(sample, replays)
        per = served_gaps(int(seed), shape, weights, entries, spec, n_logits,
                          replays=joined,
                          built=_BUILT.result() if _BUILT else None)
        n = sum(p["n"] for p in per)
        held = [p for p in per if "logit_rel_rms" in p]
        yield {**{k: max(p[k] for p in held) for k in (
                   "tail_rel_rms", "tail_rel_rms_deepest", "first_keys_max",
                   "second_keys_q25", "second_keys_median")},
               "logit_rel_rms": max(p["logit_rel_rms"] for p in held),
               "logit_max_abs": max(p["logit_max_abs"] for p in held),
               "replay_matches_served": float(len(entries) == len(sample)),
               "replay_seconds": t1 - t0,
               "worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
