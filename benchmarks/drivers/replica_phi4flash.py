"""The replica body of the cells that serve a `phi4flash` (Phi-4-mini-flash)
configuration: `replica.BenchLLMServer` (time stamps, profiler, snapshot —
inherited whole) with this model's loader, scopes and reference check; the
window's cut is `replica_ling3.Ling3Server.bench_cut`, taken as it is (it
asks the engine and nothing of the model)."""

from __future__ import annotations

import time
from typing import Dict, List

from .replica import BenchLLMServer
from .replica_ling3 import Ling3Server


# the reference's programs, being built since the replica's loader ran
# (`make_loader(.., reference=)`): what `bench_reference` takes
_BUILT = None


def make_loader(conf: Dict, seed: int, overrides: Dict, reference=None):
    """params_loader for the replica: the configuration's config and its
    weights made ON THE DEVICE from the seed — the program's `init` is the
    configuration's whole recipe (`weights.made`): no leaf is re-shaped
    here.  With `reference` = (shape, spec, n_logits, seed) the reference's
    programs are traced and compiled on a thread that starts HERE (nothing
    of it runs on the device): the host is idle while the device draws
    7.7e9 B of weights, and busy — on the engine's own tracing, under one
    interpreter lock — during the warm-up, where `replica_ling3`'s
    `bench_prepare_reference` starts them (a warm run's warm-up read 21.7
    s that way and the whole run 109 s of the check's 110; PERF.md section
    6, PR 51)."""

    def loader():
        import jax

        from benchmarks.lib.phi4flashcfg import model_config
        from ray_tpu.models import phi4flash as pm

        if reference is not None:
            import concurrent.futures

            from benchmarks.reference.check_phi4flash import build_programs

            global _BUILT
            _BUILT = concurrent.futures.ThreadPoolExecutor(1).submit(
                build_programs, *reference)
        cfg = model_config(conf, **overrides)
        params = pm.init(jax.random.PRNGKey(seed % (2 ** 31)), cfg)
        jax.block_until_ready(params)
        return cfg, params

    return loader


SCOPES = ("mamba_proj", "kda_conv", "mamba_chunk", "mamba_step", "mamba_out",
          "attn_proj", "swa_attend_step", "swa_attend_chunk",
          "shared_kv_attend_step", "shared_kv_attend_row",
          "full_attend_chunk", "attn_out", "gmu",
          "mlp", "lm_head")


class Phi4FlashServer(BenchLLMServer):
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
                        rows, np.int32(0), np.int32(0), np.bool_(True))
            else:
                continue
            text = fn.lower(*args).compile().as_text()
            out["jit_serve_" + (key if key == "step" else key[0])].append(
                scope_map(text, SCOPES, {}))
        yield out

    def bench_reference(self, sample: List[Dict], shape: Dict, spec: Dict,
                        n_logits: int, weights: Dict, seed: int):
        """See replica_ling3.Ling3Server.bench_reference: a generator of
        one item; the reference is handed the SEED and draws its own
        weights (`weights` is the configuration's description of the
        recipe: both sides write it out)."""
        import jax.numpy as jnp

        from benchmarks.reference.check_phi4flash import (join_replays,
                                                          replay_logits,
                                                          served_gaps)

        t0 = time.time()
        # the first sampled request's prompt (the longest context: chunk
        # after chunk over its pages, its ring and its entry) and the
        # second's (the shortest prompt: what its entry held before has
        # faded least) once more through the engine's own programs,
        # greedy: the logits of their first `replay_keep` tokens are held
        # to the reference's, and what the replay leaves in its entry
        # (entry 1) of the FIRST Mamba layer to the recurrence's own state
        # at that position.  The engine is idle.
        keep = int(spec["replay_keep"])
        replays = []
        for k in range(min(2, len(sample))):
            got, toks = replay_logits(self._engine, sample[k]["tokens"],
                                      keep, keep)
            replays.append((k, toks, 0, got,
                            self._engine._cache["state"][0, 1]))
        # the entry the replays carried (entry 1 of the state kind): the
        # share of its states' nonzero values that bfloat16 holds exactly
        # — a handful in a million of float32's own, every one of an
        # arena kept or rounded in half precision
        held = self._engine._cache["state"][:, 1]
        same = held.astype(jnp.bfloat16).astype(held.dtype) == held
        half = float(jnp.sum(same & (held != 0)) / jnp.maximum(
            jnp.sum(held != 0), 1))
        t1 = time.time()
        entries, joined = join_replays(sample, replays)
        per = served_gaps(int(seed), shape, entries, spec, n_logits,
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
