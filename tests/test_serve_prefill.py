"""The serve engine's prefill program (models/gpt.py paged_prefill, and
the contiguous reference's slot_prefill, tests/slot_reference.py): one
pass of the padded chunk through the layers, held against a
token-by-token walk that the tests build from the decode steps.

In-process and on the CPU, f32 `nano` as tests/test_serve_continuous.py:
a chunk-wide matmul moves an f32 logit by ~1e-6, so the comparisons
are tolerances set from the dtype, not token equality.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt
from ray_tpu.serve._engine import ContinuousEngine
from slot_reference import slot_decode_step, slot_prefill
from ray_tpu.telemetry import device as devtel

PS, MAXP, NUM_PAGES, SLOTS = 8, 8, 24, 3
S = PS * MAXP                                  # 64 = max_total = max_seq
# this sequence's pages, in sequence order (scattered through the arena
# on purpose); the other pages belong to "someone else"
ROW = [5, 9, 2, 17, 11, 20, 3, 14]


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.nano(max_seq=S, dtype=jnp.float32)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _noise(shape, seed):
    """A cache full of stale rows: whatever the masks let through shows."""
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _prompt(n, seed=1):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


_paged_step = jax.jit(gpt.paged_decode_step, static_argnames="cfg")
_slot_step = jax.jit(slot_decode_step, static_argnames="cfg")


def _walk(model, cache, toks, start, row=None):
    """Token by token through the decode step at batch 1 (the paged one
    given a page-table row): the last token's logits (None for no token)
    and the cache."""
    cfg, params = model
    logits = None
    for i, t in enumerate(toks):
        pos = jnp.asarray([start + i], jnp.int32)
        if row is None:
            logits, cache = _slot_step(params, cache, jnp.asarray([t]), pos,
                                       cfg=cfg)
        else:
            logits, cache = _paged_step(params, cache, jnp.asarray([t]),
                                        jnp.asarray([row]), pos, cfg=cfg)
    return logits, cache


def _pad(toks, bucket):
    T = -(-len(toks) // bucket) * bucket
    out = np.zeros(T, np.int32)
    out[:len(toks)] = toks
    return jnp.asarray(out)


# (start, count, bucket): what the engine calls shared_len, the prompt's
# own tokens, and prefill_bucket
CASES = {
    "start0_full_bucket": (0, 8, 8),
    "prefix_shared_pages": (16, 5, 8),
    "padded": (0, 5, 8),
    "crosses_page_unaligned_start": (5, 9, 8),
    "ends_at_max_total": (40, 24, 32),       # rows 40..63, pads past S
}


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
@pytest.mark.parametrize("case", list(CASES))
def test_one_pass_prefill_matches_token_walk(model, layout, case):
    cfg, params = model
    start, count, bucket = CASES[case]
    prefix, toks = _prompt(start, seed=2), _prompt(count)
    shape = (cfg.n_layers, NUM_PAGES, PS, cfg.n_heads * cfg.d_head)
    if layout == "paged":
        cache = {"k": _noise(shape, 3), "v": _noise(shape, 4)}
        # positions before `start` hold valid K/V, as shared pages do
        _, cache = _walk(model, cache, prefix, 0, ROW)
        want_logits, want = _walk(model, cache, toks, start, ROW)
        got_logits, got = gpt.paged_prefill(
            params, cache, _pad(toks, bucket), jnp.asarray(ROW),
            jnp.int32(start), jnp.int32(count - 1), cfg)

        def rows(c, side):                     # [L, H, S, dh] of ROW
            g = np.asarray(c[side])[:, ROW]    # [L, maxp, ps, H * dh]
            return g.reshape(cfg.n_layers, S, cfg.n_heads,
                             cfg.d_head).transpose(0, 2, 1, 3)
    else:
        slot = 1
        shape = (cfg.n_layers, SLOTS, cfg.n_heads, S, cfg.d_head)
        cache = {"k": _noise(shape, 3), "v": _noise(shape, 4)}
        one = {s: cache[s][:, slot:slot + 1] for s in ("k", "v")}
        _, one = _walk(model, one, prefix, 0)
        cache = {s: cache[s].at[:, slot].set(one[s][:, 0])
                 for s in ("k", "v")}
        want_logits, want = _walk(model, one, toks, start)
        got_logits, got = slot_prefill(
            params, cache, _pad(toks, bucket), jnp.int32(start),
            jnp.int32(count - 1), jnp.int32(slot), cfg)
        for s in ("k", "v"):                   # the frozen slots
            others = [b for b in range(SLOTS) if b != slot]
            assert np.array_equal(np.asarray(got[s])[:, others],
                                  np.asarray(cache[s])[:, others])

        def rows(c, side):
            a = np.asarray(c[side])
            return a[:, slot if a.shape[1] > 1 else 0]

    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits)[0], atol=1e-4,
                               rtol=0)
    real = slice(0, start + count)             # prefix kept, prompt written
    for side in ("k", "v"):
        np.testing.assert_allclose(rows(got, side)[:, :, real],
                                   rows(want, side)[:, :, real],
                                   atol=1e-5, rtol=0)


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_one_pass_prefill_rope_recipe(layout):
    """The other recipe (rope, RMSNorm, SwiGLU): per-row rotary positions
    go through _slot_rope, which the decode step shares."""
    cfg = gpt.GPTConfig.nano(max_seq=S, dtype=jnp.float32, pos="rope",
                             norm="rms", act="swiglu")
    rope_model = cfg, gpt.init(jax.random.PRNGKey(1), cfg)
    test_one_pass_prefill_matches_token_walk(
        rope_model, layout, "crosses_page_unaligned_start")


@pytest.mark.parametrize("start,count,bucket,own", [
    (0, 13, 32, 3),       # pads run past the 3 allocated pages: null page
    (48, 9, 16, 8),       # pads run past the end of the table (S = 64)
], ids=["past_allocation", "past_max_total"])
def test_padded_prefill_leaves_other_sequences_pages_alone(model, start,
                                                           count, bucket,
                                                           own):
    cfg, params = model
    shape = (cfg.n_layers, NUM_PAGES, PS, cfg.n_heads * cfg.d_head)
    cache = {"k": _noise(shape, 5), "v": _noise(shape, 6)}
    row = ROW[:own] + [0] * (MAXP - own)       # unused entries: page 0
    _, got = gpt.paged_prefill(
        params, cache, _pad(_prompt(count), bucket), jnp.asarray(row),
        jnp.int32(start), jnp.int32(count - 1), cfg)
    others = [p for p in range(1, NUM_PAGES) if p not in row]
    assert len(others) == NUM_PAGES - 1 - own
    for side in ("k", "v"):
        assert np.array_equal(np.asarray(got[side])[:, others],
                              np.asarray(cache[side])[:, others])
        # and the prefix it attended to (pages before `start`) is kept
        kept = row[:start // PS]
        assert np.array_equal(np.asarray(got[side])[:, kept],
                              np.asarray(cache[side])[:, kept])


def test_same_bucket_other_start_and_length_compiles_nothing(model):
    """The program a prompt compiles is a function of its padded length
    alone: `start`, `last_idx` and the page-table row are operands."""
    cfg, params = model
    mark = devtel.get_ledger().counts()
    eng = ContinuousEngine(gpt, cfg, params, max_slots=4,
                           page_size=PS, prefill_bucket=8)
    try:
        long = list(range(100, 120))           # 20 tokens, bucket 24
        a = eng.submit(long, max_new_tokens=30)
        deadline = time.time() + 120
        while eng.engine_stats()["prefills"] < 1:
            assert time.time() < deadline
            time.sleep(0.005)
        # shares a's two full pages: start 16, 5 tokens of its own
        b = eng.submit(long[:16] + [7, 8, 9, 10, 11], max_new_tokens=3)
        c = eng.submit([3, 1, 4, 1, 5, 9, 2], max_new_tokens=3)  # start 0
        for s in (b, c, a):
            eng.collect(s, timeout=120)
        ring = eng.phase_ring()
        st = eng.engine_stats()
    finally:
        eng.stop()
    reqs = {r["rid"]: r for rec in ring for r in rec["requests"]}
    assert (reqs[b.rid]["shared_tokens"], reqs[b.rid]["scanned_tokens"]) \
        == (16, 8)
    assert (reqs[c.rid]["shared_tokens"], reqs[c.rid]["scanned_tokens"]) \
        == (0, 8)
    assert st["prefills"] == 3
    new = devtel.get_ledger().compiles_since(mark)
    assert new["serve.prefill:8"] == 1 and new["serve.prefill:24"] == 1
