"""LLM serving: a continuous-batching inference engine behind Serve.

The reference serves LLMs by delegating to an external engine (vLLM) and
wiring it into Serve; here decoding is the framework's own jit program
(models/gpt.py), and by default each replica hosts a **continuous-
batching engine** over a **paged KV cache** (serve/_engine.py): one
fixed-shape compiled step program over a slot batch, sequences joining
at prefill and leaving at EOS/max-tokens at every decode step, pages
refcounted with live prompt-prefix sharing and copy-on-write.  A
prompt is prefilled in one pass of its padded chunk through the layers
(gpt.paged_prefill), a long one in chunks of `prefill_chunk` tokens
between decode steps.  A loaded config is served by its own module
(models/gpt.py, models/cohere2_moe.py: the engine's interface).  Both the request/response route and token
streaming ride the same engine, so a short request never waits behind a
long one.

Engine selection (``RAY_TPU_SERVE_ENGINE`` or ``engine=`` at bind time):

  * ``paged`` (default) — continuous batching, paged KV arena;
  * ``contiguous`` — continuous batching over per-slot contiguous
    caches (the bitwise-parity baseline for the paged path);
  * ``static`` — the legacy ``serve.batch`` micro-batching path:
    requests grouped by (prompt_len, max_new, sampling params, seed),
    each group one stacked ``generate()`` call, streaming via a
    dedicated per-request prefill + fused sample/decode step loop.

All engine sizing knobs (slots, page size, arena pages, admission
watermarks) are the ``RAY_TPU_SERVE_*`` flags in _private/config.py.

Prompts and completions are token-id lists: tokenizers are deliberately
out of scope (bring your own; nothing here depends on one).
"""

from __future__ import annotations

import functools
import sys
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from .._private.config import cfg as _config
from ._deployment import deployment
from .api import run
from .batching import batch

__all__ = ["LLMServer", "build_llm_app"]


def _bucket(n: int, step: int = 128) -> int:
    return ((n + step - 1) // step) * step


class _LLMServerImpl:
    """Deployment body.  cfg_kwargs are GPTConfig fields (or pass
    `preset="gpt2_small"`); params_loader() runs ON THE REPLICA (the
    driver never materializes the weights) and may return either a
    params tree, or a (GPTConfig, params) pair — which is exactly what
    models/hf.from_hf_gpt2 returns, so serving an HF checkpoint is
    `LLMServer().bind(params_loader=lambda: from_hf_gpt2("gpt2"))`."""

    def __init__(self, preset: str = "nano", cfg_kwargs: Optional[dict] = None,
                 params_loader=None, max_seq: int = 512,
                 engine: Optional[str] = None,
                 engine_kwargs: Optional[dict] = None):
        import jax

        from ray_tpu.models import gpt

        self._gpt = gpt
        user_cfg_kwargs = dict(cfg_kwargs or {})
        cfg_kwargs = dict(user_cfg_kwargs)
        cfg_kwargs.setdefault("max_seq", max_seq)
        self._cfg = getattr(gpt.GPTConfig, preset)(**cfg_kwargs)
        loaded = params_loader() if params_loader is not None else None
        if params_loader is not None and loaded is None:
            raise ValueError("params_loader returned None (missing "
                             "return?) — refusing to serve random "
                             "weights in its place")
        if isinstance(loaded, tuple):
            self._cfg, self._params = loaded
            if user_cfg_kwargs:
                # user overrides still apply on top of the loaded config
                import dataclasses

                self._cfg = dataclasses.replace(self._cfg,
                                                **user_cfg_kwargs)
        elif loaded is not None:
            self._params = loaded
        else:
            self._params = gpt.init(jax.random.PRNGKey(0), self._cfg)
        # a loaded config is served by its own module where that module
        # implements the engine's interface (models/cohere2_moe.py beside
        # models/gpt.py): which model runs is the loader's data
        mod = sys.modules.get(type(self._cfg).__module__)
        if hasattr(mod, "paged_decode_step"):
            self._gpt = mod
        self._jax = jax
        # per-instance (NOT lru_cache on the method: a class-level cache
        # keyed by self would pin replaced replicas' full weights), and
        # bounded: a long-lived replica facing varied (max_new, temp,
        # top_k) tuples must not grow compile-cache memory without limit
        self._gen_cache: "OrderedDict[tuple, Any]" = OrderedDict()
        self._gen_cache_cap = _config().serve_gen_cache_cap
        self._engine_mode = engine or _config().serve_engine
        if self._engine_mode not in ("paged", "contiguous", "static"):
            raise ValueError(f"unknown engine {self._engine_mode!r}")
        self._engine_kwargs = dict(engine_kwargs or {})
        self._engine = None   # built lazily: direct construction (tests,
        #                       tooling) must not allocate the device arena

    def _get_engine(self):
        if self._engine is None:
            from ._engine import ContinuousEngine

            c = _config()
            kw = dict(cache=self._engine_mode,
                      max_slots=c.serve_max_slots,
                      page_size=c.serve_page_size,
                      num_pages=c.serve_num_pages,
                      max_total=c.serve_max_total,
                      queue_cap=c.serve_queue_cap,
                      shed_queue_depth=c.serve_shed_queue_depth,
                      retry_after_s=c.serve_retry_after_s,
                      prefill_bucket=c.serve_prefill_bucket,
                      stall_s=c.serve_engine_stall_s)
            kw.update(self._engine_kwargs)
            self._engine = ContinuousEngine(self._gpt, self._cfg,
                                            self._params, **kw)
        return self._engine

    def engine_stats(self) -> Optional[Dict[str, Any]]:
        """Scheduler snapshot for the replica metrics poll (None until
        the engine has processed its first request, or in static mode)."""
        if self._engine is None:
            return None
        return self._engine.engine_stats()

    def check_health(self):
        """Engine-level liveness probe (controller health loop): a hung
        jit step or dead scheduler thread raises here, which gets this
        replica restarted instead of timing out every request forever."""
        if self._engine is not None:
            self._engine.check_health()

    def prepare_shutdown(self, drain_s: float = 5.0) -> bool:
        """Graceful drain: stop admitting and let active decode slots
        finish before the controller kills the actor."""
        if self._engine is not None:
            return self._engine.drain(drain_s)
        return True

    @staticmethod
    def _request_id() -> Optional[str]:
        """Per-request id from the replica request context (proxy/handle
        propagate it via metadata) — threads it into engine stats so a
        replayed request is traceable across replicas."""
        from ._replica import _request_context

        ctx = _request_context.get()
        return (ctx or {}).get("request_id") if isinstance(ctx, dict) \
            else None

    def _cached(self, key, build):
        """LRU-bounded compiled-program cache (every jitted variant a
        replica ever builds goes through here, so the cap holds no
        matter which routes a client exercises)."""
        fn = self._gen_cache.get(key)
        if fn is None:
            fn = self._gen_cache[key] = build()
            while len(self._gen_cache) > self._gen_cache_cap:
                self._gen_cache.popitem(last=False)
        else:
            self._gen_cache.move_to_end(key)
        return fn

    def _gen_fn(self, max_new: int, temperature: float,
                top_k: Optional[int], max_seq: int):
        return self._cached(
            (max_new, temperature, top_k, max_seq),
            lambda: self._jax.jit(functools.partial(
                self._gpt.generate, cfg=self._cfg, max_new_tokens=max_new,
                temperature=temperature, top_k=top_k, max_seq=max_seq)))

    def _check_capacity(self, plen: int, max_new: int):
        if self._cfg.pos == "learned" and plen + max_new > self._cfg.max_seq:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({max_new}) exceeds "
                f"the model's learned-position capacity "
                f"({self._cfg.max_seq})")

    async def generate_batch(self, requests: List[Dict[str, Any]]
                             ) -> List[Dict[str, Any]]:
        """Group by (prompt_len, max_new, temperature, top_k): each group
        is one stacked generate() call."""
        import numpy as np

        groups: Dict[tuple, List[int]] = {}
        for i, r in enumerate(requests):
            key = (len(r["tokens"]), int(r.get("max_new_tokens", 16)),
                   float(r.get("temperature", 0.0)),
                   r.get("top_k"), int(r.get("seed", 0)))
            groups.setdefault(key, []).append(i)
        out: List[Optional[Dict[str, Any]]] = [None] * len(requests)
        for (plen, max_new, temp, top_k, seed), idxs in groups.items():
            self._check_capacity(plen, max_new)
            prompts = np.asarray([requests[i]["tokens"] for i in idxs],
                                 np.int32)
            fn = self._gen_fn(max_new, temp, top_k,
                              _bucket(plen + max_new))
            toks = np.asarray(fn(self._params, prompt=prompts,
                                 rng=self._jax.random.PRNGKey(seed)))
            for row, i in enumerate(idxs):
                out[i] = {"tokens": toks[row].tolist(),
                          "completion": toks[row, plen:].tolist(),
                          "batch_size": len(idxs)}
        return out

    def _prefill_fn(self, total: int):
        """One jit program for the whole prompt (a per-token Python
        prefill loop costs one dispatch + host sync per position; on the
        attached chip that cost is not measured).  Hidden-only through the stack; the D x V vocab
        projection (the fattest matmul in a small-model decode step)
        runs once, on the final position."""
        jax, gpt, cfg = self._jax, self._gpt, self._cfg

        def build():
            def prefill(params, cache, toks):        # toks [S] int32
                def body(c, t):
                    x, c = gpt._decode_hidden(params, c, t[None], cfg)
                    return c, x

                cache, xs = jax.lax.scan(body, cache, toks)
                logits = jax.numpy.einsum(
                    "bd,dv->bv", xs[-1].astype(cfg.dtype),
                    gpt._unembed_table(params, cfg))
                return logits, cache

            return jax.jit(prefill)

        return self._cached(("prefill", total), build)

    def _sample_body(self, logits, rkey, temperature, top_k):
        # gpt.sample_logits is the one sampling recipe — sharing it is
        # what makes stream/batched seed parity structural, not luck
        return self._gpt.sample_logits(logits, rkey, temperature, top_k)

    def _stream_step_fn(self, temperature: float, top_k: Optional[int],
                        total: int):
        """Fused sample+decode step: returns (token [1], next logits,
        cache).  Sampling runs ON DEVICE so the stream loop transfers a
        4-byte token id per step, not [1, V] logits; the sample recipe
        mirrors gpt.generate's exactly (same key schedule => identical
        completions for the same seed)."""
        jax, gpt, cfg = self._jax, self._gpt, self._cfg

        def build():
            def step(params, cache, logits, rkey):
                tok = self._sample_body(logits, rkey, temperature, top_k)
                new_logits, cache = gpt.decode_step(params, cache, tok,
                                                    cfg)
                return tok, new_logits, cache

            return jax.jit(step)

        return self._cached(("stream_step", temperature, top_k, total),
                            build)

    def _sample_fn(self, temperature: float, top_k: Optional[int]):
        """Sample-only program for the LAST token of a stream — it
        needs no further forward pass or cache write."""
        return self._cached(
            ("sample", temperature, top_k),
            lambda: self._jax.jit(functools.partial(
                self._sample_body, temperature=temperature,
                top_k=top_k)))

    def stream_tokens(self, tokens: List[int], max_new_tokens: int = 16,
                      temperature: float = 0.0, seed: int = 0,
                      top_k: Optional[int] = None,
                      eos_id: Optional[int] = None,
                      key_offset: int = 0):
        """Yield one sampled token id at a time (generator => Serve
        streams it as SSE/chunked over HTTP, itemwise over handles).
        Under the continuous engine the stream is fed by the shared
        slot-batch step loop (tokens appear as the scheduler emits
        them); in static mode it is a dedicated per-request decode
        loop.  Sampling shares gpt.sample_logits and the batched
        route's key schedule either way (token-exact in f32; at bf16,
        fusion-order rounding can flip near-tie logits)."""
        import numpy as np

        if self._engine_mode != "static":
            eng = self._get_engine()
            seq = eng.submit(tokens, max_new_tokens, temperature, seed,
                             top_k, eos_id=eos_id, stream=True,
                             request_id=self._request_id(),
                             key_offset=key_offset)
            yield from eng.stream(seq)
            return
        jax, gpt, cfg = self._jax, self._gpt, self._cfg
        if not tokens:
            raise ValueError("empty prompt: stream_tokens needs at "
                             "least one prompt token")
        self._check_capacity(len(tokens), max_new_tokens)
        total = _bucket(len(tokens) + max_new_tokens)
        cache = gpt.init_cache(cfg, 1, total)
        logits, cache = self._prefill_fn(total)(
            self._params, cache, np.asarray(tokens, np.int32))
        # same key schedule as the batched route (gpt.generate splits
        # rng into max_new_tokens keys up front): seed parity holds for
        # sampled decodes, not just greedy.  key_offset (router resume
        # continuation) re-derives the original request's schedule and
        # skips the keys its delivered tokens consumed.
        keys = jax.random.split(jax.random.PRNGKey(seed),
                                key_offset + max_new_tokens)[key_offset:]
        step = self._stream_step_fn(temperature, top_k, total)
        for i in range(max_new_tokens - 1):
            tok, logits, cache = step(self._params, cache, logits,
                                      keys[i])
            yield int(tok[0])
        if max_new_tokens > 0:   # the last sample needs no further
            tok = self._sample_fn(temperature, top_k)(  # forward pass
                logits, keys[max_new_tokens - 1])
            yield int(tok[0])

    async def _engine_generate(self, body: Dict[str, Any]
                               ) -> Dict[str, Any]:
        """Request/response through the continuous engine: submit is a
        queue append; the result future resolves on the engine thread
        when the sequence leaves its slot."""
        import asyncio

        seq = self._get_engine().submit(
            body["tokens"], int(body.get("max_new_tokens", 16)),
            float(body.get("temperature", 0.0)),
            int(body.get("seed", 0)), body.get("top_k"),
            eos_id=body.get("eos_id"), request_id=self._request_id())
        return await asyncio.wrap_future(seq.result)

    async def __call__(self, request):
        # handle calls pass the body dict directly; HTTP passes a Request
        is_http = not isinstance(request, dict)
        body = await request.json() if is_http else request
        if body.get("stream"):
            if is_http:
                # the HTTP proxy streams only ingresses whose __call__
                # is itself a generator function — that is the dedicated
                # stream app build_llm_app deploys next door
                raise ValueError(
                    "token streaming over HTTP lives on the companion "
                    "'<route>-stream' endpoint; this route is the "
                    "micro-batched JSON API")
            return self.stream_tokens(
                body["tokens"], int(body.get("max_new_tokens", 16)),
                float(body.get("temperature", 0.0)),
                int(body.get("seed", 0)), body.get("top_k"),
                body.get("eos_id"))
        if self._engine_mode != "static":
            return await self._engine_generate(body)
        return await self.generate_batch(body)


def LLMServer(**deployment_kwargs):
    """`LLMServer().bind(preset=..., ...)`-style factory: returns the
    deployment (decorate-once so serve.batch wraps generate_batch)."""
    cls = type("LLMServer", (_LLMServerImpl,), {})
    cls.generate_batch = batch(
        _LLMServerImpl.generate_batch,
        max_batch_size=deployment_kwargs.pop("max_batch_size", 8),
        batch_wait_timeout_s=deployment_kwargs.pop(
            "batch_wait_timeout_s", 0.02))
    return deployment(cls, **deployment_kwargs) \
        if deployment_kwargs else deployment(cls)


class _LLMStreamIngress:
    """HTTP token-streaming ingress: an async-GENERATOR __call__ (the
    proxy streams chunked/SSE only for generator ingresses), relaying
    the shared engine's stream_tokens through a streaming handle —
    weights live once, in the engine deployment."""

    def __init__(self, engine_app: str):
        self._engine_app = engine_app
        self._h = None

    async def __call__(self, request):
        import json as _json

        from .api import get_app_handle

        body = request if isinstance(request, dict) else \
            await request.json()
        if self._h is None:
            self._h = get_app_handle(self._engine_app)
        # resume="llm_tokens": if the engine replica dies mid-stream the
        # router replays prompt+tokens_so_far on a survivor, so the
        # client stream continues instead of restarting from token 0
        gen = self._h.options(
            stream=True, resume="llm_tokens").stream_tokens.remote(
            body["tokens"], int(body.get("max_new_tokens", 16)),
            float(body.get("temperature", 0.0)),
            int(body.get("seed", 0)), body.get("top_k"),
            body.get("eos_id"))
        async for tok in gen:
            yield _json.dumps({"token": int(tok)}) + "\n"


def build_llm_app(preset: str = "nano", *, route_prefix: str = "/llm",
                  name: str = "llm", stream: bool = True, **init_kwargs):
    """Deploy a generation endpoint: POST {tokens, max_new_tokens, ...}
    -> {tokens, completion} at `route_prefix` (micro-batched), plus a
    token-streaming endpoint at `route_prefix`-stream."""
    dep = LLMServer()
    h = run(dep.bind(preset=preset, **init_kwargs), name=name,
            route_prefix=route_prefix)
    if stream:
        run(deployment(_LLMStreamIngress).bind(name),
            name=f"{name}-stream", route_prefix=f"{route_prefix}-stream")
    return h
