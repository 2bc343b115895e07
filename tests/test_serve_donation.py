"""The serve programs consume the engine's device state (serve/_engine.py
`_fn`: `serve.step` donates the cache and the carried logits,
`serve.prefill:<T>` and `serve.copy_page` the cache, `serve.setrow` the
logits) and write their rows into the buffers they were given.

Both served models (models/gpt.py, models/cohere2_moe.py) at nano size,
float32, on the CPU: (a) every program's compilation-ledger analysis
reports `alias_bytes` covering the state it returns; (b) an engine call
deletes the arrays it was given and the next call goes on from its
outputs; (c) a program that raises after consuming its input fails the
requests in flight, and the next request is served from a fresh arena;
(d) the tokens of a mixed batch are still those of `gpt.generate` / the
module's `apply`.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import cohere2_moe as cm
from ray_tpu.models import gpt
from ray_tpu.serve._engine import ContinuousEngine
from ray_tpu.telemetry import device as devtel

MAX_SEQ, PS, BUCKET, CHUNK = 64, 8, 8, 8

# the served models (both through the paged arena)
ENGINES = {"gpt-paged": gpt, "cohere2_moe-paged": cm}


@pytest.fixture(scope="module")
def models():
    gcfg = gpt.GPTConfig.nano(max_seq=MAX_SEQ, dtype=jnp.float32)
    ccfg = cm.Cohere2MoEConfig.nano(dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    return {gpt: (gcfg, gpt.init(jax.random.PRNGKey(0), gcfg)),
            cm: (ccfg, cm.init(jax.random.PRNGKey(0), ccfg))}


def _engine(models, which, **kw):
    mod = ENGINES[which]
    cfg, params = models[mod]
    defaults = dict(max_slots=3, page_size=PS,
                    max_total=MAX_SEQ, prefill_bucket=BUCKET)
    defaults.update(kw)
    return ContinuousEngine(mod, cfg, params, **defaults)


def _by_hand(eng):
    """Drive the engine's iterations from the test: a thread that has
    already ended stands where the loop's would be started."""
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    eng._thread = t
    return eng


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 250, n).tolist()


def _nbytes(tree):
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _operands(eng, program):
    """(ledger key, the engine's own operands, the state it returns) of a
    program, as `_step` / `_prefill_next` / `_admit_one` pass them."""
    eng._ensure_device_state()
    i32 = np.int32
    if program == "step":
        return "step", (eng._params, eng._cache, eng._logits,
                        eng._toks_keys, eng._temps, eng._topks,
                        eng._ptabs, eng._pos), (eng._cache, eng._logits)
    if program == "prefill":
        chunk = np.zeros(BUCKET, np.int32)
        rows = {k: np.zeros(w, np.int32) for k, w in eng._widths.items()}
        return (("prefill", BUCKET),
                (eng._params, eng._cache, chunk, rows, i32(0), i32(4)),
                eng._cache)
    if program == "copy_page":
        return "copy_page", (eng._cache, i32(2), i32(1)), eng._cache
    row = jnp.zeros((eng._cfg.vocab_size,), jnp.float32)
    return "setrow", (eng._logits, row, i32(1)), eng._logits


def _expected(models, mod, prompt, completion):
    """The greedy continuation of `prompt` by the model alone: gpt's
    `generate`; the other module's `apply`, teacher-forced over what the
    engine gave (each token the argmax of the position before it)."""
    cfg, params = models[mod]
    n = len(completion)
    if mod is gpt:
        out = gpt.generate(params, cfg, jnp.asarray([prompt]), n,
                           max_seq=MAX_SEQ)
        return np.asarray(out)[0, len(prompt):].tolist()
    toks = jnp.asarray(prompt + completion)[None]
    logits = np.asarray(cm.apply(params, toks, cfg))[0]
    return np.argmax(logits[len(prompt) - 1:-1], axis=-1).tolist()


# -- (a) every program aliases the state it returns ---------------------------


PROGRAMS = [(w, p) for w in ENGINES
            for p in ("step", "prefill", "copy_page", "setrow")
            if p != "copy_page" or w == "gpt-paged"]   # where pages are shared


@pytest.mark.parametrize("which,program", PROGRAMS,
                         ids=[f"{w}-{p}" for w, p in PROGRAMS])
def test_program_aliases_the_state_it_returns(models, which, program):
    eng = _engine(models, which)
    try:
        key, args, state = _operands(eng, program)
        mem = devtel._analyze_executable(eng._fn(key)._fn, args,
                                         {})["memory"]
    finally:
        eng.stop()
    assert mem["alias_bytes"] >= _nbytes(state) > 0, mem
    assert mem["alias_bytes"] <= mem["output_bytes"]


# -- (b) a call consumes what it was given; the next goes on ------------------


@pytest.mark.parametrize("which", list(ENGINES))
def test_engine_calls_consume_their_state_and_go_on(models, which):
    eng = _by_hand(_engine(models, which))
    try:
        eng._ensure_device_state()
        prompt = _tokens(11, seed=1)
        seq = eng.submit(prompt, max_new_tokens=5)
        for n in range(5):
            given = jax.tree.leaves((eng._cache, eng._logits))
            eng._iteration()        # the first admits (prefill, setrow) too
            assert all(a.is_deleted() for a in given), n
            assert not any(a.is_deleted() for a in
                           jax.tree.leaves((eng._cache, eng._logits)))
        # the fifth step's tokens are in flight; the iteration that
        # fetches them launches nothing and consumes nothing
        assert not seq.result.done()
        given = jax.tree.leaves((eng._cache, eng._logits))
        eng._iteration()
        assert not any(a.is_deleted() for a in given)
        out = seq.result.result(timeout=0)["completion"]
    finally:
        eng.stop()
    assert out == _expected(models, ENGINES[which], prompt, out)


# -- (c) a program that fails after taking its input --------------------------


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("which", ["gpt-paged", "cohere2_moe-paged"])
def test_failed_program_costs_the_requests_in_flight_not_the_engine(
        models, which, program):
    eng = _engine(models, which)
    key = "step" if program == "step" else ("prefill", BUCKET)
    real = eng._fn(key)

    def consumes_then_raises(*args):
        real(*args)                 # the state given is gone after this
        raise RuntimeError("device fault")

    prompt = _tokens(6, seed=2)
    try:
        eng._fns[key] = consumes_then_raises
        a, b = eng.submit(prompt, 4), eng.submit(_tokens(7, seed=3), 4)
        for s in (a, b):
            with pytest.raises(RuntimeError, match="device fault"):
                eng.collect(s, timeout=120)
        eng._fns[key] = real
        out = eng.collect(eng.submit(prompt, 6), timeout=120)["completion"]
        # served from a fresh arena; every page is back in its pool
        assert not any(a.is_deleted() for a in
                       jax.tree.leaves((eng._cache, eng._logits)))
        for alloc in eng._allocs.values():
            assert alloc.used_pages == 0 and alloc.reserved == 0
    finally:
        eng.stop()
    assert out == _expected(models, ENGINES[which], prompt, out)


# -- (d) the tokens of a mixed batch are the model's --------------------------


@pytest.mark.parametrize("which", list(ENGINES))
def test_mixed_batch_greedy_tokens_are_the_models(models, which):
    """Side by side in one engine: a prompt of two full pages and its
    exact duplicate (where pages are shared, the duplicate takes a copy
    of the last one to write into), a prompt prefilled in four chunks, and
    a plain short one."""
    mod = ENGINES[which]
    eng = _engine(models, which, max_slots=4, prefill_chunk=CHUNK)
    twice = _tokens(2 * PS, seed=4)
    prompts = [twice, list(twice), _tokens(29, seed=5), _tokens(5, seed=6)]
    try:
        seqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        outs = [eng.collect(s, timeout=180)["completion"] for s in seqs]
        st = eng.engine_stats()
    finally:
        eng.stop()
    if eng._share:
        assert st["cow_copies"] == 1 and st["shared_pages"] == 1
    assert seqs[2].chunks == 4 and st["chunks"] > st["prefills"]
    for p, out in zip(prompts, outs):
        assert len(out) == 9 and out == _expected(models, mod, p, out)
