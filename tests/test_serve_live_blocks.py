"""The paged serve programs' attention (models/gpt.py _paged_attention):
the keys are read block by block where the pages stand, as far as the
contexts are live, and folded into an online softmax.  Held here to the
plain recipe — the contiguous cache's `slot_decode_step`
(tests/slot_reference.py), every position scored at once under the mask —
walked token by token.

In-process and on the CPU, f32 `nano` as tests/test_serve_prefill.py;
`kv_block` 32 (4 pages of 8) so that a table of 16 pages is four blocks.
"""

import dataclasses
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt
from ray_tpu.serve._engine import ContinuousEngine
from slot_reference import slot_decode_step
from ray_tpu.telemetry import device as devtel

PS, MAXP, NUM_PAGES, BLOCK = 8, 16, 40, 32
S = PS * MAXP                                  # 128 = max_total = max_seq
# two sequences' pages in sequence order, scattered through the arena;
# the third slot is empty: position 0, every entry the null page
ROW_A = [5, 9, 2, 17, 11, 20, 3, 14, 30, 22, 7, 35, 26, 19, 33, 12]
ROW_B = [8, 4] + [0] * (MAXP - 2)
LEN_B = 11                                     # slot B's live length
LIVE = [1, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK + 1, S]


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.nano(max_seq=S, dtype=jnp.float32, kv_block=BLOCK)
    return cfg, gpt.init(jax.random.PRNGKey(0), cfg)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


_slot_step = jax.jit(slot_decode_step, static_argnames="cfg")
_paged_step = jax.jit(gpt.paged_decode_step, static_argnames="cfg")
_paged_prefill = jax.jit(gpt.paged_prefill, static_argnames="cfg")


@pytest.fixture(scope="module")
def walked(model):
    """Both sequences walked token by token through the plain recipe at
    batch 1: toks, logits after every token [n, V], and the contiguous
    cache's rows as the arena keeps them, [L, n, H*dh] a side."""
    cfg, params = model
    out = {}
    for name, n, seed in (("a", S, 1), ("b", LEN_B, 2)):
        toks = _tokens(n, seed)
        shape = (cfg.n_layers, 1, cfg.n_heads, S, cfg.d_head)
        cache = {"k": jnp.zeros(shape, jnp.float32),
                 "v": jnp.zeros(shape, jnp.float32)}
        logits = []
        for i, t in enumerate(toks):
            lg, cache = _slot_step(params, cache, jnp.asarray([t]),
                                   jnp.asarray([i], jnp.int32), cfg=cfg)
            logits.append(np.asarray(lg[0]))
        rows = {s: np.asarray(cache[s])[:, 0].transpose(0, 2, 1, 3).reshape(
            cfg.n_layers, S, -1)[:, :n] for s in ("k", "v")}
        out[name] = toks, np.stack(logits), rows
    return out


def _arena(cfg, walked, live_a, fill=None):
    """An arena of stale noise that holds sequence A's first live_a rows
    and B's first LEN_B - 1 on their pages (the rows a step or a chunk
    finds there).  With `fill`, every page that no slot's table names
    within its first `fill` entries is NaN on both sides, the null page
    apart."""
    shape = (cfg.n_layers, NUM_PAGES, PS, cfg.n_heads * cfg.d_head)
    rng = np.random.default_rng(7)
    arena = {s: rng.normal(size=shape).astype(np.float32) for s in ("k", "v")}
    for name, row, n in (("a", ROW_A, live_a), ("b", ROW_B, LEN_B - 1)):
        rows = walked[name][2]
        for p in range(n):
            for s in ("k", "v"):
                arena[s][:, row[p // PS], p % PS] = rows[s][:, p]
    if fill is not None:
        named = {0, *ROW_A[:fill], *ROW_B[:fill]}
        dead = [p for p in range(NUM_PAGES) if p not in named]
        assert dead
        for s in ("k", "v"):
            arena[s][:, dead] = np.nan
    return {s: jnp.asarray(a) for s, a in arena.items()}


def _step_logits(model, walked, n, fill=None):
    """The paged decode step with slot A at live length n (its token n-1
    at position n-1), slot B at LEN_B and an empty third slot."""
    cfg, params = model
    (ta, _, _), (tb, _, _) = walked["a"], walked["b"]
    logits, _ = _paged_step(
        params, _arena(cfg, walked, n - 1, fill),
        jnp.asarray([ta[n - 1], tb[LEN_B - 1], 0]),
        jnp.asarray([ROW_A, ROW_B, [0] * MAXP]),
        jnp.asarray([n - 1, LEN_B - 1, 0], jnp.int32), cfg=cfg)
    return np.asarray(logits)


def _prefill_logits(model, walked, n, fill=None):
    """The paged prefill of A's last tokens (a chunk of 8 rows, or n of
    them where n is shorter) behind its earlier rows."""
    cfg, params = model
    ta = walked["a"][0]
    start = max(0, n - 8)
    toks = np.zeros(8, np.int32)
    toks[:n - start] = ta[start:n]
    logits, _ = _paged_prefill(
        params, _arena(cfg, walked, start, fill), jnp.asarray(toks),
        jnp.asarray(ROW_A), jnp.int32(start), jnp.int32(n - start - 1),
        cfg=cfg)
    return np.asarray(logits)


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("n", LIVE)
def test_live_blocks_match_the_plain_recipe(model, walked, program, n):
    """Live lengths around the block edges, slots at different lengths in
    one batch, an empty slot on the null page, stale noise everywhere
    outside the sequences' own rows."""
    want_a, want_b = walked["a"][1][n - 1], walked["b"][1][LEN_B - 1]
    if program == "step":
        got = _step_logits(model, walked, n)
        np.testing.assert_allclose(got[0], want_a, atol=1e-4, rtol=0)
        np.testing.assert_allclose(got[1], want_b, atol=1e-4, rtol=0)
    else:
        np.testing.assert_allclose(_prefill_logits(model, walked, n),
                                   want_a, atol=1e-4, rtol=0)


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("n", [24, 25, S])
def test_table_that_is_no_whole_number_of_blocks(model, walked, program, n):
    """Blocks of 3 pages over a table of 16: the last block runs two
    entries past the table, onto the null page, behind every query."""
    cfg, params = model
    odd = dataclasses.replace(cfg, kv_block=3 * PS), params
    got = (_step_logits(odd, walked, n)[0] if program == "step"
           else _prefill_logits(odd, walked, n))
    np.testing.assert_allclose(got, walked["a"][1][n - 1], atol=1e-4,
                               rtol=0)


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("n", [1, BLOCK - 1, BLOCK + 1, 2 * BLOCK + 1])
def test_what_is_not_live_is_not_read(model, walked, program, n):
    """Every page that lies wholly past the last live block is NaN, V's
    side too: a gather of the whole table carries `0 x NaN` into the
    output; a loop that stops at the last live block does not see it."""
    last = n - 1 if program == "step" else max(0, n - 8) + 7
    fill = (last // BLOCK + 1) * (BLOCK // PS)     # entries of live blocks
    want = walked["a"][1][n - 1]
    if program == "step":
        got = _step_logits(model, walked, n, fill)
        assert np.isfinite(got[:2]).all()
        np.testing.assert_allclose(got[1], walked["b"][1][LEN_B - 1],
                                   atol=1e-4, rtol=0)
        got = got[0]
    else:
        got = _prefill_logits(model, walked, n, fill)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_contexts_crossing_a_block_compile_one_step(model):
    """The trip count is an operand's doing, not a shape: streams that
    cross a block boundary mid-stream run the one `serve.step`, and give
    `generate`'s tokens."""
    cfg, params = model
    prompts = [_tokens(BLOCK - 4, 3).tolist(), _tokens(5, 4).tolist()]
    mark = devtel.get_ledger().counts()
    eng = ContinuousEngine(gpt, cfg, params, max_slots=3, page_size=PS,
                           max_total=S, prefill_bucket=8)
    try:
        seqs = [eng.submit(p, max_new_tokens=BLOCK + 8) for p in prompts]
        outs = [eng.collect(s, timeout=300)["completion"] for s in seqs]
        crossed = max(r["kv_read"] for r in eng.phase_ring())
    finally:
        eng.stop()
    new = devtel.get_ledger().compiles_since(mark)
    assert new["serve.step"] == 1, new
    assert crossed == 3 * 3 * BLOCK             # the long one reached 68
    for p, out in zip(prompts, outs):
        want = gpt.generate(params, cfg, jnp.asarray([p]), BLOCK + 8,
                            max_seq=S)
        assert out == np.asarray(want)[0, len(p):].tolist()


def test_ring_records_carry_the_positions_read(model):
    """`kv_read` / `kv_span` of an iteration's record: slots x live
    blocks x block against slots x max_total, from the positions the
    step was given.  One sequence at a time through three slots: a
    30-token prompt decodes at positions 30..34 (one block, then two), a
    70-token one at 70..71 (three)."""
    cfg, params = model
    eng = ContinuousEngine(gpt, cfg, params, max_slots=3, page_size=PS,
                           max_total=S, prefill_bucket=8)
    t = threading.Thread(target=lambda: None)   # iterations driven by hand
    t.start()
    t.join()
    eng._thread = t
    try:
        for plen, new in ((30, 5), (70, 2)):
            seq = eng.submit(_tokens(plen, plen).tolist(), max_new_tokens=new)
            for _ in range(50):
                eng._iteration()
                if seq.result.done():
                    break
            assert seq.result.done()
        eng._iteration()                        # an idle one: no step
        ring = eng.phase_ring()
    finally:
        eng.stop()
    steps = [r for r in ring if r["active"]]
    assert [r["kv_read"] for r in steps] == [
        3 * 1 * BLOCK, 3 * 1 * BLOCK, 3 * 2 * BLOCK, 3 * 2 * BLOCK,
        3 * 2 * BLOCK, 3 * 3 * BLOCK, 3 * 3 * BLOCK]
    assert all(r["kv_span"] == 3 * S for r in steps)
    idle = [r for r in ring if not r["active"]]
    assert idle and all(r["kv_read"] == 0 == r["kv_span"] for r in idle)
    assert gpt.step_kv_read(cfg, np.asarray([S + 5, 0, 3]), PS, MAXP) \
        == (3 * S, 3 * S)                       # clamped as the program's
