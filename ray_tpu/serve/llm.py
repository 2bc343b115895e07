"""LLM serving: a continuous-batching inference engine behind Serve.

The reference serves LLMs by delegating to an external engine (vLLM) and
wiring it into Serve; here decoding is the framework's own jit program
(models/gpt.py), and each replica hosts a **continuous-
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

All engine sizing knobs (slots, page size, arena pages, admission
watermarks) are the ``RAY_TPU_SERVE_*`` flags in _private/config.py.

Prompts and completions are token-id lists: tokenizers are deliberately
out of scope (bring your own; nothing here depends on one).
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional

from .._private.config import cfg as _config
from ._deployment import deployment
from .api import run

__all__ = ["LLMServer", "build_llm_app"]


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

        # before the loader runs: a bad argument costs no weights
        if engine not in (None, "paged"):
            raise ValueError(
                f"unknown engine {engine!r}: every replica serves through "
                f"the paged continuous-batching engine (the 'static' and "
                f"'contiguous' modes were removed)")
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
        # has the engine's paged programs (serve/_engine.py, "The model
        # interface"; the engine holds it to the rest when it is made):
        # which model runs is the loader's data
        mod = sys.modules.get(type(self._cfg).__module__)
        if hasattr(mod, "paged_decode_step"):
            self._gpt = mod
        self._engine_kwargs = dict(engine_kwargs or {})
        self._engine = None   # built lazily: direct construction (tests,
        #                       tooling) must not allocate the device arena

    def _get_engine(self):
        if self._engine is None:
            from ._engine import ContinuousEngine

            c = _config()
            kw = dict(max_slots=c.serve_max_slots,
                      page_size=c.serve_page_size,
                      num_pages=c.serve_num_pages,
                      max_total=c.serve_max_total,
                      queue_cap=c.serve_queue_cap,
                      shed_queue_depth=c.serve_shed_queue_depth,
                      retry_after_s=c.serve_retry_after_s,
                      prefill_bucket=c.serve_prefill_bucket,
                      stall_s=c.serve_engine_stall_s)
            kw.update(self._engine_kwargs)
            # the engine serves its own view of the tree (the model's
            # serve_view: compute-dtype weights); self._params stays what
            # the loader delivered
            self._engine = ContinuousEngine(self._gpt, self._cfg,
                                            self._params, **kw)
        return self._engine

    def engine_stats(self) -> Optional[Dict[str, Any]]:
        """Scheduler snapshot for the replica metrics poll (None until
        the engine has processed its first request)."""
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

    def stream_tokens(self, tokens: List[int], max_new_tokens: int = 16,
                      temperature: float = 0.0, seed: int = 0,
                      top_k: Optional[int] = None,
                      eos_id: Optional[int] = None,
                      key_offset: int = 0):
        """Yield one sampled token id at a time (generator => Serve
        streams it as SSE/chunked over HTTP, itemwise over handles).
        The stream is fed by the engine's shared slot-batch step loop
        (tokens appear as the scheduler emits them).  Sampling shares
        gpt.sample_logits and the request/response route's key schedule
        (token-exact in f32; at bf16, fusion-order rounding can flip
        near-tie logits)."""
        eng = self._get_engine()
        seq = eng.submit(tokens, max_new_tokens, temperature, seed,
                         top_k, eos_id=eos_id, stream=True,
                         request_id=self._request_id(),
                         key_offset=key_offset)
        yield from eng.stream(seq)

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
                    "request/response JSON API")
            return self.stream_tokens(
                body["tokens"], int(body.get("max_new_tokens", 16)),
                float(body.get("temperature", 0.0)),
                int(body.get("seed", 0)), body.get("top_k"),
                body.get("eos_id"))
        return await self._engine_generate(body)


def LLMServer(**deployment_kwargs):
    """`LLMServer().bind(preset=..., ...)`-style factory: returns the
    deployment."""
    cls = type("LLMServer", (_LLMServerImpl,), {})
    return deployment(cls, **deployment_kwargs)


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
    -> {tokens, completion} at `route_prefix`, plus a
    token-streaming endpoint at `route_prefix`-stream."""
    dep = LLMServer()
    h = run(dep.bind(preset=preset, **init_kwargs), name=name,
            route_prefix=route_prefix)
    if stream:
        run(deployment(_LLMStreamIngress).bind(name),
            name=f"{name}-stream", route_prefix=f"{route_prefix}-stream")
    return h
