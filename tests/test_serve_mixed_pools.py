"""A model whose layers keep two kinds of KV state (models/cohere2_moe.py:
three sliding-window RoPE layers to one full NoPE layer, grouped-query
heads, a parallel block with a share of the routed experts and averaged
shared experts) through the serving engine: two page pools, ring tables
for the windowed one, chunked prefill.

Held against the plain reference (benchmarks/reference/cohere2_moe_plain.py,
float32, written from the published equations) on LOGITS.  Both sides
compute in float32 here, so what separates them is the order of sums (a
chunk-wide matmul, an online softmax, grouped expert products): 1e-6 on
logits of size 0.5.  The tolerance is 1e-4; every mutation below moves a
logit by more than 2e-3.
"""

import dataclasses
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import cohere2_moe_plain as ref
from ray_tpu.models import cohere2_moe as cm
from ray_tpu.models import gpt
from ray_tpu.ops.moe import held_expert_ffn, route_sigmoid_topk
from ray_tpu.serve._engine import ContinuousEngine

TOL = 1e-4
PS, CHUNK, BUCKET = 4, 8, 4


def _shape(cfg, **kw):
    out = dict(layer_types=cfg.layer_types, window=cfg.sliding_window,
               theta=cfg.rope_theta, top_k=cfg.top_k,
               first=cfg.experts_first, n_shared=cfg.n_shared,
               logit_scale=cfg.logit_scale)
    out.update(kw)
    return out


@pytest.fixture(scope="module")
def model():
    cfg = cm.Cohere2MoEConfig.nano(dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    return cfg, cm.init(jax.random.PRNGKey(0), cfg)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


def _ref_logits(model, toks, **shape_kw):
    cfg, params = model
    pad = -(-len(toks) // 8) * 8
    t = np.zeros(pad, np.int32)
    t[:len(toks)] = toks
    return np.asarray(ref.logits(params, jnp.asarray(t),
                                 _shape(cfg, **shape_kw), q_block=8))


def _engine(model, **kw):
    cfg, params = model
    defaults = dict(max_slots=3, page_size=PS, max_total=64,
                    prefill_bucket=BUCKET, prefill_chunk=CHUNK)
    defaults.update(kw)
    return ContinuousEngine(cm, cfg, params, **defaults)


def _by_hand(eng):
    """Drive the engine's iterations from the test: a thread that has
    already ended stands where the loop's would be started."""
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    eng._thread = t
    return eng


def _pools_idle(eng):
    for k, a in eng._allocs.items():
        assert a.free_pages == a.num_pages - 1, k
        assert a.reserved == 0, k
    assert eng._prefilling is None


# -- (a) the program against the reference, on logits ------------------------


def test_full_forward_matches_reference(model):
    cfg, params = model
    toks = _tokens(40)
    got = np.asarray(cm.apply(params, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(got - _ref_logits(model, toks)[:40]).max() < TOL


@pytest.mark.parametrize("rows", [30, 12, 2])
def test_reference_row_bound_changes_nothing(model, rows):
    """The benchmark's reference gathers an expert's rows to a static
    bound and computes a hotter expert over every row: the logits are the
    unbounded ones whether no, some or every expert overflows."""
    toks = _tokens(40, seed=9)
    want = _ref_logits(model, toks)
    assert np.abs(_ref_logits(model, toks, expert_rows=rows) - want).max() \
        < 1e-6


def test_chunked_prefill_then_decode_through_both_pools(model):
    """Two sequences side by side: a 29-token prompt in four chunks and a
    6-token one in one program, each decoded far past the 8-token window
    (the ring of the sliding pool wraps several times).  After every
    program the engine's logits row of the slot is the reference's row
    for that position under teacher forcing."""
    eng = _by_hand(_engine(model))
    plens, new = (29, 6), 22
    seqs = [eng.submit(_tokens(p, seed=p).tolist(), new) for p in plens]
    rows = [{}, {}]
    for _ in range(200):
        eng._iteration()
        for i, s in enumerate(seqs):
            # after an iteration a decoding slot's row is the one the NEXT
            # step launched will draw from, as many steps in as were
            # launched for it (the first token's row is used up inside
            # the iteration that ends the prefill)
            if s.t_ready and eng._slots[s.slot] is s:
                rows[i][s.launched] = np.asarray(
                    eng._logits)[s.slot].copy()
        if all(s.result.done() for s in seqs):
            break
    assert seqs[0].chunks == 4 and seqs[1].chunks == 1
    for s, p, got in zip(seqs, plens, rows):
        out = s.result.result()["completion"]
        assert len(out) == new and sorted(got) == list(range(1, new))
        want = _ref_logits(model, list(s.tokens) + out)
        assert int(np.argmax(want[p - 1])) == out[0]
        for j, row in got.items():         # row j predicts generated[j]
            assert np.abs(row - want[p - 1 + j]).max() < TOL, (p, j)
            assert int(np.argmax(row)) == out[j]
    assert eng._totals["window_pages_returned"] > 10
    _pools_idle(eng)
    eng.stop()


# -- (b) the shares add up ----------------------------------------------------


def test_expert_shares_add_up_to_the_uncut_layer(model):
    """Four chips of four experts each: the parts their held experts give,
    with the shared experts (computed alike on every chip) counted once,
    sum to the reference's layer over all sixteen."""
    cfg, _ = model
    full = cm.init_layer(jax.random.PRNGKey(5), dataclasses.replace(
        cfg, experts_first=0, experts_held=cfg.n_experts))
    h = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.d_model))
    want = ref.feed_forward(h, full, _shape(cfg, first=0))
    total, shared = 0.0, None
    for first in range(0, cfg.n_experts, 4):
        share = dict(full, **{k: full[k][first:first + 4]
                              for k in ("wg", "wu", "wd")})
        c = dataclasses.replace(cfg, experts_first=first, experts_held=4)
        out, (loads, _) = cm._ffn(h, share, c)
        w, idx = route_sigmoid_topk(h, full["router"], cfg.top_k)
        routed, _, _ = held_expert_ffn(h, w, idx, share["wg"], share["wu"],
                                       share["wd"], first=first, tile=16)
        assert int(loads.sum()) == int(((idx >= first)
                                        & (idx < first + 4)).sum())
        shared = out - routed
        total = total + routed
    with jax.default_matmul_precision("highest"):
        assert float(jnp.abs(total + shared - want).max()) < TOL


# -- (c) mutations the comparison must catch ----------------------------------


def _drop_first_held_pair(monkeypatch):
    real = cm.held_expert_ffn

    def dropping(h, w, idx, *a, first, **kw):
        count = a[0].shape[0]
        held = ((idx >= first) & (idx < first + count)).reshape(-1)
        flat = idx.reshape(-1).at[jnp.argmax(held)].set(10 ** 6)
        return real(h, w, flat.reshape(idx.shape), *a, first=first, **kw)

    monkeypatch.setattr(cm, "held_expert_ffn", dropping)


@pytest.mark.parametrize("mutation", [
    "shared_summed_not_averaged", "rope_on_the_full_layer",
    "window_off_by_one", "a_dropped_pair"])
def test_the_comparison_catches(model, monkeypatch, mutation):
    cfg, params = model
    toks = _tokens(40, seed=3)
    shape_kw = {}
    if mutation == "shared_summed_not_averaged":
        shape_kw["n_shared"] = 1        # one expert of the whole width: a sum
    elif mutation == "window_off_by_one":
        shape_kw["window"] = cfg.sliding_window + 1
    elif mutation == "rope_on_the_full_layer":
        real = ref.block
        monkeypatch.setattr(ref, "block", lambda x, lp, kind, shape, qb: real(
            x, lp, "sliding", dict(shape, window=10 ** 9) if kind == "full"
            else shape, qb))
    else:
        _drop_first_held_pair(monkeypatch)
    got = np.asarray(cm.apply(params, jnp.asarray(toks)[None], cfg))[0]
    gap = np.abs(got - _ref_logits(model, toks, **shape_kw)[:40]).max()
    assert gap > 20 * TOL, gap


# -- (d) the pools -------------------------------------------------------------


def test_sliding_pages_return_as_the_window_passes(model):
    eng = _by_hand(_engine(model, max_slots=1))
    width = eng._widths["sliding"]
    assert width == (8 + CHUNK) // PS + 1 and eng._widths["full"] == 16
    seq = eng.submit(_tokens(30).tolist(), 20)
    held = []
    while not seq.result.done():
        eng._iteration()
        a = eng._allocs["sliding"]
        held.append(a.used_pages)
        assert a.used_pages + a.reserved <= width
        if seq.t_ready and eng._slots[seq.slot] is seq:
            live = sorted(seq.win["sliding"])
            pos = int(eng._pos[seq.slot])
            # just the pages a query at `pos` can still see
            assert live[0] == max(0, pos - 8 + 1) // PS, (live, pos)
            assert live[-1] >= (pos - 1) // PS
    # (the pages go back where the last step by count is launched, an
    # iteration before the one that fetches its token)
    assert max(held) <= width and min(held[:-2]) >= 2 and held[-2:] == [0, 0]
    # 50 positions went through, the pool never held more than 5 pages
    assert eng._totals["window_pages_returned"] >= 50 // PS - width
    assert eng._allocs["full"].used_pages == 0
    ring = eng.phase_ring()
    assert sum(r["pages_returned"] for r in ring) \
        == eng._totals["window_pages_returned"]
    assert all(r["pages_sliding"] <= width for r in ring)
    assert max(r["pages_full"] for r in ring) == -(-50 // PS)
    _pools_idle(eng)
    eng.stop()


def test_a_starved_windowed_pool_holds_admission_back(model):
    """The sliding pool has room for one sequence's ring only: the second
    request waits for the first to leave, and both are served whole."""
    eng = _engine(model, num_pages={"full": 40, "sliding": 6})
    a, b = (eng.submit(_tokens(20, seed=s).tolist(), 12) for s in (1, 2))
    ra, rb = eng.collect(a, timeout=120), eng.collect(b, timeout=120)
    assert len(ra["completion"]) == len(rb["completion"]) == 12
    assert ra["batch_size"] == rb["batch_size"] == 1
    _pools_idle(eng)
    eng.stop()


def test_prefix_sharing_holds_for_one_kind_and_is_off_with_a_window(model):
    """gpt2-style traffic (one full kind) still shares a common prompt
    prefix; a model with a windowed kind shares nothing — a shared prefix
    is not prefilled, and the window's pages of it may have been returned
    — and still gives both requests the same tokens."""
    gcfg = gpt.GPTConfig.nano(max_seq=64, dtype=jnp.float32)
    geng = ContinuousEngine(gpt, gcfg, gpt.init(jax.random.PRNGKey(0), gcfg),
                            max_slots=2, page_size=8, prefill_bucket=8)
    prompt = list(range(1, 20))
    a, b = geng.submit(prompt, 24), geng.submit(prompt + [7], 24)
    geng.collect(a, timeout=120), geng.collect(b, timeout=120)
    assert geng._totals["shared_pages"] == 2 and geng._share
    geng.stop()

    eng = _engine(model)
    a, b = eng.submit(prompt, 10), eng.submit(prompt, 10)
    ra, rb = eng.collect(a, timeout=120), eng.collect(b, timeout=120)
    assert eng._totals["shared_pages"] == 0 and not eng._share
    assert ra["completion"] == rb["completion"]
    _pools_idle(eng)
    eng.stop()


# -- (e) chunked and unchunked give the same tokens ---------------------------


@pytest.mark.parametrize("plen", [5, 8, 21, 40])
def test_chunked_and_unchunked_prompts_give_the_same_tokens(model, plen):
    prompt = _tokens(plen, seed=plen).tolist()
    outs = []
    for chunk in (CHUNK, 0):
        eng = _engine(model, prefill_chunk=chunk)
        seq = eng.submit(prompt, 14)
        outs.append(eng.collect(seq, timeout=120)["completion"])
        want = 1 if not chunk else -(-plen // CHUNK)
        assert seq.chunks == want, (chunk, seq.chunks)
        eng.stop()
    assert outs[0] == outs[1]


# -- (f) a full kind whose pages hold latent rows, not keys and values --------


def test_a_latent_page_is_copied_on_write():
    """models/deepseek_v3 keeps one full kind, so the engine shares a
    common prompt's pages: two live sequences with ONE prompt of two whole
    pages share the first page, and the second — shared too, but the last
    prompt position must be computed again to give logits — is copied
    (`copy_page` over [pages, 576-like, page] arenas: no K side, no V
    side) before the newcomer writes into it.  Both are served what a
    sequence alone is served, and the programs' counters reach the
    engine's ring."""
    from ray_tpu.models import deepseek_v3 as dm

    cfg = dm.DeepSeekV3Config.nano(dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    params = dm.init(jax.random.PRNGKey(0), cfg)
    kw = dict(max_slots=3, page_size=8, max_total=64, prefill_bucket=4,
              prefill_chunk=8)
    prompt = [int(t) for t in _tokens(16, seed=3)]
    alone = ContinuousEngine(dm, cfg, params, **kw)
    want = alone.collect(alone.submit(prompt, 12), timeout=120)["completion"]
    alone.stop()

    eng = _by_hand(ContinuousEngine(dm, cfg, params, **kw))
    assert eng._share and eng._kinds == {"full": None}
    a = eng.submit(prompt, 12)
    for _ in range(3):              # two chunks, then it decodes
        eng._iteration()
    b = eng.submit(prompt, 12)
    eng._iteration()
    assert eng._totals["cow_copies"] == 1 and eng._totals["shared_pages"] == 1
    assert b.pages[0] == a.pages[0] and b.pages[1] != a.pages[1]
    assert b.shared == 15           # all but the last prompt position
    cache = [np.asarray(c) for c in eng._cache]
    for arena in cache:             # the copy, before b's own row went in
        assert arena.shape[1:] == (cfg.d_latent, 8)
        np.testing.assert_array_equal(arena[b.pages[1]][:, :7],
                                      arena[a.pages[1]][:, :7])
    while not (a.result.done() and b.result.done()):
        eng._iteration()
    assert a.result.result()["completion"] == want
    assert b.result.result()["completion"] == want
    rec = eng.phase_ring()
    assert sum(r["mla_keys"] for r in rec) > 0
    assert sum(r["chunk_mla_pairs"] for r in rec) > 0
    assert sum(r["chunk_moe_pairs"] + r["moe_pairs"] for r in rec) > 0
    _pools_idle(eng)
    eng.stop()
