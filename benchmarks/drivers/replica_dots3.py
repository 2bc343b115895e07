"""The replica body of the cells that serve a `dots3_note` configuration:
`replica.BenchLLMServer` (time stamps, profiler, snapshot — inherited
whole) with this model's loader, scopes and reference check."""

from __future__ import annotations

import concurrent.futures
import time
from typing import Dict, List

from .replica import BenchLLMServer


def shape_weights(params, weights: Dict, seed: int):
    """The configuration's `weights` over the program's plain draw: every
    layer's leaf named in `scales` multiplied by its factor, and every
    expert layer's correction bias drawn — normal x `router_bias_std`,
    float32, by the program's own piece-wise draw at the place after a
    layer's last leaf (replica_deepseek_v3.shape_weights, over this
    model's leaves)."""
    import jax

    from ray_tpu.models.dots3 import LEAVES, _draw

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


# the reference's pieces, being compiled since the replica's loader ran
# (`make_loader(.., reference=)`): what `bench_reference` takes
_BUILT = None


def _built():
    """`_BUILT` of the module as the replica's process imports it: the
    loader's closure and the deployment's class both travel there by
    value, each with a copy of this module's globals."""
    from benchmarks.drivers import replica_dots3 as here

    return here._BUILT


def make_loader(conf: Dict, seed: int, overrides: Dict, reference=None):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed.  The draw is not waited for
    (4 s of the chip for 8.18 GB): the replica goes on to build its engine
    while the chip draws.  With `reference` = (shape, spec, n_logits) the
    reference's pieces are traced and compiled on a thread that starts
    HERE, as `replica_phi4flash.make_loader` starts its own (a checkout's
    first run compiles the reference's thirteen programs and its draw's
    eleven for 21-30 s; no weights, no device memory), while the replica
    is still STARTING and its engine holds no request.  NOT beside the
    warm-up, where the review session had put them: two dozen threads
    tracing under one interpreter lock kept the engine's thread from its
    own work between two of its first compiles for 11.4 s with a slot
    taken, `check_health` read that as a stalled engine (`stall_s` 10) and
    the controller replaced the replica under the warm-up request — two
    of four first runs of a checkout ended so, exit 1, and the check's did
    (PERF.md section 6, PR 56).  `Dots3Server.bench_reference_built` waits
    for them; the driver calls it before its warm-up."""

    def loader():
        import jax

        from benchmarks.lib.dots3cfg import model_config
        from ray_tpu.models import dots3 as m

        if reference is not None:
            # (`_built` reads it there)
            from benchmarks.drivers import replica_dots3 as here
            from benchmarks.reference.check_dots3 import compile_pieces

            here._BUILT = concurrent.futures.ThreadPoolExecutor(1).submit(
                compile_pieces, *reference)
        cfg = model_config(conf, **overrides)
        return cfg, shape_weights(
            m.init(jax.random.PRNGKey(seed % (2 ** 31)), cfg),
            conf.get("weights", {}), seed)

    return loader


SCOPES = ("mla_q", "mla_kv", "dsa_index_step", "dsa_index_chunk",
          "dsa_select", "mla_attend_step", "mla_attend_chunk",
          "swa_latent_attend_step", "swa_latent_attend_chunk", "attn_gate",
          "mla_out", "moe_router", "moe_experts", "moe_shared", "mlp",
          "unembed")


class Dots3Server(BenchLLMServer):
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

    def bench_reference_built(self):
        """Waits until the reference's pieces stand (the loader started
        them): a generator of one item, the seconds waited, so that the
        wait is an executor thread's and not the replica's event loop's.
        The driver calls it BEFORE its warm-up: nothing of the reference
        traces or compiles while the engine holds a request."""
        t0 = time.time()
        if _built() is not None:
            _built().result()
        yield time.time() - t0

    def bench_reference(self, sample: List[Dict], shape: Dict, spec: Dict,
                        n_logits: int, weights: Dict, seed: int):
        """See BenchLLMServer.bench_reference: a generator of one item.
        `spec` is the traffic file's `reference`, `weights` the
        configuration's.  The reference is handed the SEED and draws its
        own weights: no array of this replica's reaches it."""
        from benchmarks.reference.check_dots3 import (replay_logits,
                                                      served_gaps)

        t0 = time.time()
        built = _built()
        # the first sampled request's prompt (the longest context) once
        # more through the engine's own programs, greedy, `replay_steps`
        # tokens far: the logits rows are held to that request's own
        # reference pass as far as the replayed tokens are the ones it
        # was served (row i is formed from the prompt and tokens 0..i-1:
        # the first row always is) — no second pass over the longest
        # context, whatever the replay yields.  The engine is idle.
        head, steps = sample[0], int(spec["replay_steps"])
        got, toks = replay_logits(self._engine, head["tokens"], steps)
        t1 = time.time()
        same = next((i for i, (a, b) in enumerate(zip(toks, head["served"]))
                     if a != b), min(steps, len(head["served"])))
        per = served_gaps(int(seed), shape, weights, sample, spec, n_logits,
                          replay=(head["rid"], 0, got[:same + 1]),
                          built=built and built.result())
        n = sum(p["n"] for p in per)
        held = next(p for p in per if "logit_rel_rms" in p)
        yield {"logit_rel_rms": held["logit_rel_rms"],
               "logit_max_abs": held["logit_max_abs"],
               "replay_matches_served": same / min(steps,
                                                   len(head["served"])),
               "replay_seconds": t1 - t0,
               "pieces_built_ahead": built is not None,
               "worst_gap": max([p["max_gap"] for p in per] or [0.0]),
               "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
               "tokens_checked": n, "checked": len(per), "per_request": per,
               "seconds": time.time() - t0}
