"""LLM serving (serve/llm.py): continuous-batching generation + token
streaming behind a Serve deployment, on the nano GPT config.

Reference shape: the reference integrates an external engine into
Serve; here the engine IS the framework's own jit decode (models/gpt.py),
so these tests exercise the full models->serve path.
"""

import json
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMServer, build_llm_app


@pytest.fixture
def serve_instance(ray_cluster):
    yield
    serve.shutdown()


PROMPT = [3, 14, 15, 92, 6, 5]


def _expected(cfg_kwargs, n_new):
    cfg = gpt.GPTConfig.nano(max_seq=256, **cfg_kwargs)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    out = gpt.generate(params, cfg, jnp.asarray([PROMPT]), n_new,
                       max_seq=128)
    return np.asarray(out)[0].tolist()


def test_llm_handle_completion_matches_direct(serve_instance):
    h = serve.run(LLMServer().bind(preset="nano", max_seq=256),
                  name="llm_t", route_prefix=None)
    got = h.remote({"tokens": PROMPT, "max_new_tokens": 8}).result(
        timeout_s=180)
    assert got["tokens"][:len(PROMPT)] == PROMPT
    assert len(got["completion"]) == 8
    # greedy through the deployment == greedy straight through the model
    assert got["tokens"] == _expected({}, 8)
    serve.delete("llm_t")


def test_llm_concurrent_requests_batch_together(serve_instance):
    h = serve.run(LLMServer().bind(preset="nano", max_seq=256),
                  name="llm_b", route_prefix=None)
    # warm the programs so the six land in the engine's slots together
    h.remote({"tokens": PROMPT, "max_new_tokens": 4}).result(timeout_s=180)
    rs = [h.remote({"tokens": PROMPT, "max_new_tokens": 4})
          for _ in range(6)]
    results = [r.result(timeout_s=180) for r in rs]
    # requests fired together: at least one shared the slot batch with a
    # peer (batch_size is the most slots occupied while it decoded)
    assert max(r["batch_size"] for r in results) >= 2
    assert all(r["tokens"] == results[0]["tokens"] for r in results)
    serve.delete("llm_b")


def test_llm_streaming_tokens(serve_instance):
    h = serve.run(LLMServer().bind(preset="nano", max_seq=256),
                  name="llm_s", route_prefix=None)
    toks = list(h.options(stream=True).remote(
        {"stream": True, "tokens": PROMPT, "max_new_tokens": 6}))
    assert len(toks) == 6
    # streamed greedy tokens == the request/response route's completion
    full = h.remote({"tokens": PROMPT, "max_new_tokens": 6}).result(
        timeout_s=180)
    assert toks == full["completion"]
    serve.delete("llm_s")


def test_llm_http_endpoint_and_stream_route(serve_instance):
    build_llm_app(preset="nano", max_seq=256, name="llm_http",
                  route_prefix="/llm")
    host, port = serve.start(proxy=True)
    body = json.dumps({"tokens": PROMPT, "max_new_tokens": 5}).encode()
    req = urllib.request.Request(f"http://{host}:{port}/llm",
                                 data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=180) as r:
        out = json.loads(r.read().decode())
    assert len(out["completion"]) == 5
    # companion stream route: newline-delimited token JSON, chunked
    req2 = urllib.request.Request(f"http://{host}:{port}/llm-stream",
                                  data=body,
                                  headers={"Content-Type":
                                           "application/json"})
    with urllib.request.urlopen(req2, timeout=180) as r:
        lines = [json.loads(l) for l in r.read().decode().splitlines()]
    assert [d["token"] for d in lines] == out["completion"]
    serve.delete("llm_http")
    serve.delete("llm_http-stream")
