"""The replica body of the cells that serve a `falcon_h1` configuration:
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
TABLE_PARTS = 8      # slices of its rows a vocabulary table is made in


def shape_layer(l: int, layer: Dict, weights: Dict, seed: int):
    """The configuration's `weights` over layer l's plain draw: every leaf
    named in `scales` multiplied by its factor; W_in's columns by
    `in_proj_scales` (z, x, B, C, dt: in float32, rounded once); and,
    where `memory_tokens` is given, dt_bias the inverse of softplus at a
    step size log-uniform in `dt_range` a head and A_log such that the
    head forgets over a number of tokens log-uniform in `memory_tokens` —
    float32, by the program's own piece-wise draw at the two places after
    a layer's last leaf, a uniform the normal distribution's own function
    of a normal.  Weights are this benchmark's data; what makes random
    ones stand in for trained ones (the configuration file says why each
    number) is set here and not in the program's `init`."""
    import math

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import LEAVES, inv_softplus
    from ray_tpu.models.ling3 import _draw

    out = _scaled(layer, weights)
    Hs = out["a_log"].shape[0]
    if "in_proj_scales" in weights:
        ds, dc = out["ssm_norm"].shape[0], out["conv_w"].shape[1]
        five = jnp.concatenate([
            jnp.full((n,), m, jnp.float32) for n, m in zip(
                (ds, ds, (dc - ds) // 2, (dc - ds) // 2, Hs),
                weights["in_proj_scales"])])
        out["w_in"] = (out["w_in"].astype(jnp.float32) * five).astype(
            out["w_in"].dtype)
    if "memory_tokens" in weights:
        root = jax.random.PRNGKey(seed % (2 ** 31))
        log_uniform = lambda place, lo, hi: jnp.exp(
            jax.scipy.special.ndtr(_draw(
                root, l, len(LEAVES) + place, (Hs,), 1.0, jnp.float32))
            * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = log_uniform(0, *weights["dt_range"])
        out.update(dt_bias=inv_softplus(dt), a_log=-jnp.log(
            dt * log_uniform(1, *weights["memory_tokens"])))
    return out


def _scaled(tree: Dict, weights: Dict):
    scales = weights.get("scales", {})
    return {k: v * scales[k] if k in scales else v for k, v in tree.items()}


def shape_weights(params, weights: Dict, seed: int):
    """`shape_layer` over a whole tree, its tables scaled too (the CPU
    tests' sizes: the replica makes its tree a layer at a time)."""
    top = _scaled({k: v for k, v in params.items() if k != "layers"},
                  weights)
    return dict(top, layers=[shape_layer(l, layer, weights, seed)
                             for l, layer in enumerate(params["layers"])])


def make_loader(conf: Dict, seed: int, overrides: Dict, reference=None):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed — and handed over FOLDED, a
    layer at a time (`falcon_h1.fold_layer`: the program's own
    `serve_view`, piecewise): every folded matrix is a new array, and the
    unfolded tree beside its view would be 21e9 B on a 16.9e9 B chip; the
    engine's `serve_view` of a view is that view.  With `reference` =
    (shape, spec, n_logits, weights, seed) the reference's programs are
    traced and compiled on a thread that starts HERE (nothing of it runs
    on the device) and is WAITED FOR before the loader returns: a thread
    of the replica's that still traces when the engine takes its first
    request keeps the engine's thread from the interpreter (PR 56, PR
    58)."""

    def loader():
        import jax
        import jax.numpy as jnp

        from benchmarks.lib.falconh1cfg import model_config
        from ray_tpu.models import falcon_h1 as fm

        if reference is not None:
            import concurrent.futures

            from benchmarks.reference.check_falcon_h1 import build_programs

            global _BUILT
            _BUILT = concurrent.futures.ThreadPoolExecutor(1).submit(
                build_programs, *reference)
        cfg = model_config(conf, **overrides)
        weights = conf.get("weights", {})
        root = jax.random.PRNGKey(seed % (2 ** 31))
        from ._common import memory_peak_bytes

        def table(name):
            """A vocabulary table drawn, scaled and folded an eighth of
            its rows at a time: whole, its draw, its scaled copy and its
            fold stood beside each other (16.06e9 B of the chip's 16.9e9 B
            by `memory_peak_bytes`, my chip run, PR 61)."""
            factor = weights.get("scales", {}).get(name, 1)
            parts = []
            for i in range(TABLE_PARTS):
                w = fm.table_rows(root, cfg, name, i, TABLE_PARTS)
                parts.append(jax.block_until_ready(fm.fold_table(
                    w * factor if factor != 1 else w, cfg, name)))
            return jnp.concatenate(parts)

        params = {"embed": table("embed"), "lm_head": table("lm_head"),
                  "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype)}
        tables_peak = memory_peak_bytes()
        params["layers"] = [
            jax.block_until_ready(fm.fold_layer(shape_layer(
                l, fm.init_layer(root, cfg, l), weights, seed), cfg))
            for l in range(cfg.n_layers)]
        jax.block_until_ready(params)
        if reference is not None:
            _BUILT.result()
        # the loader's own high-water marks: the tables' transient (drawn,
        # scaled and folded beside each other) and the finished tree
        logging.getLogger(__name__).warning(
            "loader: memory_peak_bytes %s after the tables, %s after the "
            "layers", tables_peak, memory_peak_bytes())
        return cfg, params

    return loader


SCOPES = ("ssd_proj", "ssd_conv", "ssd_chunk", "ssd_step", "ssd_out",
          "attn_proj", "attn_step", "attn_chunk", "mlp", "lm_head")


class FalconH1Server(BenchLLMServer):
    bench_cut = Ling3Server.bench_cut

    def bench_program_scopes(self):
        """{module-name prefix: [{instruction: scope}, ...]} of the serve
        programs this engine has built, from their compiled text (see
        replica_cohere2_moe.Cohere2MoEServer.bench_program_scopes)."""
        import numpy as np

        from benchmarks.trace.scopes import scope_map

        eng = self._engine
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
                scope_map(text, SCOPES, {}))
        yield out

    def bench_reference(self, sample: List[Dict], shape: Dict, spec: Dict,
                        n_logits: int, weights: Dict, seed: int):
        """See replica_phi4flash.Phi4FlashServer.bench_reference: a
        generator of one item; the reference is handed the SEED and draws
        its own weights (`weights` is the configuration's description of
        the recipe: both sides write it out)."""
        import jax.numpy as jnp

        from benchmarks.reference.check_falcon_h1 import (join_replays,
                                                          replay_logits,
                                                          served_gaps)

        t0 = time.time()
        # the first sampled request's prompt (the longest context: chunk
        # after chunk over its pages and its entry) once more through the
        # engine's own programs, greedy: the logits of its first
        # `replay_keep` tokens are held to the reference's, and what the
        # replay leaves in its entry (entry 1) of the FIRST layer to the
        # recurrence's own state at that position.  The engine is idle.
        keep = int(spec["replay_keep"])
        got, toks = replay_logits(self._engine, sample[0]["tokens"], keep,
                                  keep)
        replays = [(0, toks, 0, got, self._engine._cache["state"][0, 1])]
        # the entry the replay carried (entry 1 of the state kind): the
        # share of its states' nonzero values that bfloat16 holds exactly
        # — a handful in a million of float32's own, every one of an
        # arena kept or rounded in half precision
        held = self._engine._cache["state"][:, 1]
        same = held.astype(jnp.bfloat16).astype(held.dtype) == held
        half = float(jnp.sum(same & (held != 0)) / jnp.maximum(
            jnp.sum(held != 0), 1))
        t1 = time.time()
        entries, joined = join_replays(sample, replays)
        per = served_gaps(int(seed), shape, weights, entries, spec, n_logits,
                          replays=joined,
                          built=_BUILT.result() if _BUILT else None)
        n = sum(p["n"] for p in per)
        held = [p for p in per if "logit_rel_rms" in p]
        yield {"state_rel_rms": max(p["state_rel_rms"] for p in held),
               "logit_rel_rms": max(p["logit_rel_rms"] for p in held),
               "logit_max_abs": max(p["logit_max_abs"] for p in held),
               "state_half_share": half,
               "replay_matches_served": float(len(entries) == len(sample)),
               "replay_seconds": t1 - t0,
               "worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
