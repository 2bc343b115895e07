"""models/lfm2_moe.py against benchmarks/reference/lfm2_moe_plain.py on
logits, at toy size in float32 on the CPU.  The reference draws its OWN
weights from the seed by the recipe the configuration states, forms the
convolution as three shifted sums over the whole sequence, attention as
explicit masked scores and the experts as a loop with a dense mask whose
selection COUNTS who stands before whom; the program draws its by `init`,
runs the conv a chunk at a time over a tail entry, streams pages, sorts
token-expert pairs into grouped products.

Tolerances: both sides compute in float32, so they differ by summation
order: logits of standard deviation ~0.3 agree to ~2e-6.  TOL = 3e-5
leaves that room and fails a conv tail dropped between chunks, a first
chunk that reads its entry's last holder, the gate after the taps left
out, SiLU on the taps' sum and the bias left out of the selection, each of
which a test says."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3_plain as dsp
from benchmarks.reference import lfm2_moe_plain as ref
from ray_tpu.models import lfm2_moe as lm
from ray_tpu.models import served
from ray_tpu.ops.moe import route_sigmoid_topk

TOL = 3e-5
SEED = 2147483659            # past 2**31: both draws fold it
PS, CHUNK, PAGES = 8, 16, 8
# what a cell's configuration may ask of the draw: every kind once
WEIGHTS = {"scales": {"q_norm": 2.0, "wo": 2.0, "wd": 2.0, "wg": 0.5,
                      "embed": 2.0},
           "router_bias_std": 0.1}


def _sizes(cfg, **kw):
    out = {"eps": cfg.eps, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "d_head": cfg.d_head,
           "d_ff": cfg.d_ff, "d_expert": cfg.d_expert,
           "n_experts": cfg.n_experts, "first": cfg.experts_first,
           "held": cfg.experts_held, "top_k": cfg.top_k,
           "routed_scale": cfg.routed_scale, "theta": cfg.rope_theta,
           "layer_types": cfg.layer_types, "n_dense": cfg.n_dense,
           "n_layers": cfg.n_layers, "vocab": cfg.vocab_size,
           "param_dtype": "float32"}
    out.update(kw)
    return out


@pytest.fixture(scope="module", autouse=True)
def small_pieces():
    """The draw's piece at 4,096 values while this file's tests run (both
    writings of the recipe): toy leaves then span several pieces."""
    mp = pytest.MonkeyPatch()
    mp.setattr(served, "DRAW_PIECE", 4096)
    mp.setattr(dsp, "DRAW_PIECE", 4096)
    yield
    mp.undo()


def _cfg(**kw):
    return lm.Lfm2MoeConfig.nano(dtype=jnp.float32, param_dtype=jnp.float32,
                                 **kw)


@pytest.fixture(scope="module")
def model():
    from benchmarks.drivers.replica_lfm2_moe import shape_weights

    cfg = _cfg()
    plain = lm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg)
    return cfg, shape_weights(plain, WEIGHTS, SEED), plain


@pytest.fixture(scope="module")
def drawn(model):
    return ref.draw(SEED, _sizes(model[0]), WEIGHTS)


@pytest.fixture(scope="module")
def tokens(model):
    return np.random.default_rng(5).integers(0, model[0].vocab_size, 56)


@pytest.fixture(scope="module")
def want(model, drawn, tokens):
    sz = _sizes(model[0])
    return np.asarray(jax.jit(lambda p, t: ref.logits(p, t, sz))(
        drawn, jnp.asarray(tokens)))


def test_the_published_sizes():
    full = lm.Lfm2MoeConfig()
    assert full.n_layers == 24 and len(full.conv_layers) == 18
    assert full.attn_layers == (2, 6, 10, 14, 18, 21)
    assert full.conv_tile == (16, 128) and full.conv_taps == 3
    assert (full.n_heads // full.n_kv_heads, full.d_head) == (4, 64)
    assert lm.cache_kinds(full) == {"full": None, "conv": "state"}
    with pytest.raises(ValueError, match="layer_types"):
        lm.Lfm2MoeConfig(layer_types=("conv", "sliding"))
    with pytest.raises(ValueError, match="past n_experts"):
        lm.Lfm2MoeConfig(experts_first=8, experts_held=32)


@pytest.mark.parametrize("weights", [{}, WEIGHTS], ids=["plain", "shaped"])
def test_the_two_draws_agree_leaf_for_leaf(model, weights):
    """The program's `init` (+ the loader's `shape_weights`) and the
    reference's own `draw`: the same leaves bit for bit, the experts' gate
    and up — drawn apart on both sides — side by side in the program's one
    leaf."""
    from benchmarks.drivers.replica_lfm2_moe import shape_weights

    cfg, _, plain = model
    params = shape_weights(plain, weights, SEED)
    mine = ref.draw(SEED, _sizes(cfg), weights)
    for name in ("embed", "final_norm"):
        np.testing.assert_array_equal(np.asarray(params[name]),
                                      np.asarray(mine[name]))
    for l, (got, exp) in enumerate(zip(params["layers"], mine["layers"])):
        for name, leaf in got.items():
            if name == "wgu":
                leaf, wanted = np.asarray(leaf), np.concatenate(
                    [exp["wg"], exp["wu"]], axis=-1)
            else:
                wanted = np.asarray(exp[name])
            np.testing.assert_array_equal(np.asarray(leaf), wanted,
                                          err_msg=f"layer {l} {name}")
        assert set(exp) - set(got) <= {"wg", "wu", "q_norm", "k_norm"}
    if weights:
        bias = np.asarray(params["layers"][1]["router_bias"])
        assert 0.02 < bias.std() < 0.3


def test_apply_is_the_reference(model, tokens, want):
    cfg, params, _ = model
    got = jax.jit(lambda p, t: lm.apply(p, t, cfg))(
        params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    assert want.std() > 0.05


def _serve(cfg, params, tokens, plen, entry=2, dirt=0.0):
    """Chunked prefill of `plen` tokens, then the rest a step at a time
    with slot 1 of 3 live, through the paged cache -> logits [S - plen + 1,
    V] (the prefill's last row first), the cache."""
    # jitted HERE: a program a call of this helper, so that a test's
    # patched module is what gets traced
    prefill = jax.jit(lambda *a: lm.paged_prefill(*a, cfg))
    step = jax.jit(lambda *a: lm.paged_decode_step(*a, cfg))
    view = lm.serve_view(params, cfg)
    cache = lm.init_paged_cache(cfg, {"full": PAGES + 1, "conv": 4}, PS)
    cache["tail"] = cache["tail"].at[:, entry].set(dirt)
    cache["k"] = [a.at[1:].set(dirt) for a in cache["k"]]
    tabs = {"full": jnp.arange(1, PAGES + 1, dtype=jnp.int32),
            "conv": jnp.asarray([entry], jnp.int32)}
    start, rows = 0, []
    while start < plen:
        m = min(CHUNK, plen - start)
        chunk = np.zeros(CHUNK, np.int32)
        chunk[:m] = tokens[start:start + m]
        lg, cache, stats = prefill(view, cache, jnp.asarray(chunk), tabs,
                                   jnp.int32(start), jnp.int32(m - 1))
        start += m
    rows.append(lg)
    B = 3
    ptabs = {"full": jnp.zeros((B, PAGES), jnp.int32).at[1].set(tabs["full"]),
             "conv": jnp.zeros((B, 1), jnp.int32).at[1, 0].set(entry)}
    for i in range(plen, len(tokens)):
        pos = jnp.zeros(B, jnp.int32).at[1].set(i)
        tok = jnp.zeros(B, jnp.int32).at[1].set(int(tokens[i]))
        lg, cache, stats = step(view, cache, tok, ptabs, pos)
        rows.append(lg[1])
    return np.stack([np.asarray(r) for r in rows]), cache, stats


@pytest.mark.parametrize("plen", [37, 16, 3], ids=["3chunks", "1chunk",
                                                   "short"])
def test_chunked_prefill_then_decode_is_the_references_full_forward(
        model, tokens, want, plen):
    """Prefill in chunks of 16 (a short last one: pad rows past
    `last_idx`) through pages and an entry DIRTIED first, then decode with
    two empty slots beside the live one: the reference's rows plen-1.. of
    its full forward; the null entry stays zeros and the live entry holds
    the sequence's last two rows of z."""
    cfg, params, _ = model
    got, cache, stats = _serve(cfg, params, tokens, plen, dirt=1e3)
    np.testing.assert_allclose(got, want[plen - 1:], rtol=0, atol=TOL)
    tail = np.asarray(cache["tail"])
    assert tail.shape == (4, 4, 2) + cfg.conv_tile
    np.testing.assert_array_equal(tail[:, 0], 0.0)
    np.testing.assert_array_equal(tail[:, 1], 0.0)
    assert np.abs(tail[:, 2]).max() < 100       # none of the dirt is left
    s = dict(zip(lm.STEP_STATS, np.asarray(stats)))
    # one live row: top_k pairs a layer on 4 expert layers, all held
    assert (s["moe_pairs"], s["conv_live"]) == (4 * cfg.top_k, 1)
    assert s["moe_reads"] == s["moe_touched"] == 4 * cfg.top_k


def test_the_kept_tail_is_the_references_z_rows(model, drawn, tokens):
    """What a sequence keeps of a conv layer after 37 + 3 positions: rows
    38 and 39 of the reference's z (`conv_layer(.., stops=[40])`), for the
    first conv layer, whose input is the embedding's own rows."""
    cfg, params, _ = model
    _, cache, _ = _serve(cfg, params, tokens[:40], 37)
    sz = _sizes(cfg)
    x = drawn["embed"][jnp.asarray(tokens[:40])].astype(jnp.float32)
    _, z = ref.conv_layer(x, drawn["layers"][0], sz, stops=jnp.asarray([40]))
    np.testing.assert_allclose(
        np.asarray(cache["tail"][0, 2]).reshape(2, cfg.d_model), z[0],
        rtol=0, atol=1e-5)


FAULTS = {
    "tail_dropped": ("conv_chunk", lambda f: lambda rows, tail, *a: f(
        rows, jnp.zeros_like(tail), *a)),
    "stale_entry": ("carried_at", lambda f: lambda first, *a: f(
        jnp.bool_(False), *a)),
    "no_out_gate": ("_conv_out", lambda f: lambda y, c, *a: f(
        y, jnp.ones_like(c), *a)),
    "silu_on_taps": ("conv_chunk", lambda f: lambda r, t, w, b, act, s: f(
        r, t, w, b, jax.nn.silu, s)),
    "bias_unselecting": ("route_sigmoid_topk", lambda f: lambda h, w, k, *,
                         bias, eps: f(h, w, k, bias=None, eps=eps)),
    "no_head_norms": ("rms_norm", lambda f: lambda x, w, eps: (
        x if x.ndim == 4 else f(x, w, eps))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_path_is_past_the_tolerance(model, tokens, want, fault,
                                             monkeypatch):
    cfg, params, _ = model
    name, wrap = FAULTS[fault]
    monkeypatch.setattr(lm, name, wrap(getattr(lm, name)))
    got, _, _ = _serve(cfg, params, tokens, 37, dirt=1.0)
    assert np.abs(got - want[36:]).max() > 30 * TOL


# -- the router: the bias chooses and does not weigh ---------------------------


def test_the_bias_chooses_and_does_not_weigh():
    """Scores 0.6, 0.5, 0.4, 0.3 with a bias that lifts the last over the
    first two: the chosen are experts 3 and 2... by score + bias; their
    weights are their SCORES over the scores' sum, the bias nowhere in
    them."""
    s = np.array([[0.6, 0.5, 0.4, 0.3]])
    logit = jnp.asarray(np.log(s / (1 - s)), jnp.float32)
    h, w = jnp.ones((1, 1), jnp.float32), logit        # h @ w = the logits
    bias = jnp.asarray([0.0, 0.0, 0.15, 0.4])
    wts, idx = route_sigmoid_topk(h, w, 2, bias=bias, eps=1e-6)
    assert idx.tolist() == [[3, 0]]                    # 0.70, 0.60 | 0.55, 0.5
    np.testing.assert_allclose(wts, [[0.3 / 0.9, 0.6 / 0.9]], rtol=1e-5)
    plain, at = route_sigmoid_topk(h, w, 2)
    assert at.tolist() == [[0, 1]]
    np.testing.assert_allclose(plain, [[0.6 / 1.1, 0.5 / 1.1]], rtol=1e-5)
    # the reference's own selection, written by counting
    sz = {"n_experts": 4, "top_k": 2, "routed_scale": 1.0}
    dense = ref.route(h, w, bias, sz)
    np.testing.assert_allclose(dense, [[0.6 / 0.9, 0, 0, 0.3 / 0.9]],
                               rtol=1e-5, atol=1e-7)


def test_a_tie_goes_to_the_lower_index():
    """Four experts with the SAME score + bias: the two lowest indices are
    chosen, in the program's router and in the reference's."""
    h = jnp.ones((1, 1), jnp.float32)
    w = jnp.zeros((1, 4), jnp.float32)                 # every score 0.5
    bias = jnp.zeros(4)
    wts, idx = route_sigmoid_topk(h, w, 2, bias=bias, eps=1e-6)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(wts, [[0.5, 0.5]], rtol=1e-5)
    dense = ref.route(h, w, bias, {"n_experts": 4, "top_k": 2,
                                   "routed_scale": 1.0})
    np.testing.assert_allclose(dense, [[0.5, 0.5, 0, 0]], rtol=1e-5)
    # a tie made by the bias: 0.5 + 0.1 twice, against 0.6 + 0 at index 0
    s = np.array([[0.6, 0.5, 0.5, 0.2]])
    w = jnp.asarray(np.log(s / (1 - s)), jnp.float32)
    _, idx = route_sigmoid_topk(h, w, 2, bias=jnp.asarray([0, 0.1, 0.1, 0]),
                                eps=1e-6)
    assert set(idx[0].tolist()) <= {0, 1, 2} and 1 in idx[0].tolist()


# -- a share of the experts ------------------------------------------------


def test_four_shares_of_the_experts_add_up_to_the_whole_layer(model, drawn,
                                                              tokens):
    """The share test of the model-configs guide (section 4): an expert
    layer's feed-forward on the same normed rows, computed by a config that
    holds experts 0-1, 2-3, 4-5, 6-7 of 8 (`experts_first` 0, 2, 4, 6 — at
    the published sizes 0, 8, 16, 24 of 32: one number away) with that
    share's slices of the leaves — the four parts add up to what the
    config that holds all 8 gives, and to the uncut reference's layer."""
    cfg, params, _ = model
    l = 2                                       # a conv mixer, experts
    layer, lp = params["layers"][l], drawn["layers"][l]
    sz = _sizes(cfg)
    x = jnp.asarray(np.random.default_rng(9).standard_normal(
        (24, cfg.d_model)), jnp.float32)
    h = lm._normed(x, layer["ffn_norm"], cfg)
    whole, (loads, _) = lm.layer_ffn(h, layer, cfg)
    parts, pairs = [], 0
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(cfg, experts_first=first, experts_held=2)
        mine = dict(layer, wgu=layer["wgu"][first:first + 2],
                    wd=layer["wd"][first:first + 2])
        part, (ld, _) = lm.layer_ffn(h, mine, share)
        np.testing.assert_array_equal(ld, loads[first:first + 2])
        parts.append(part)
        pairs += int(ld.sum())
        # the reference's own share: the same part
        cut = dict(lp, wg=lp["wg"][first:first + 2],
                   wu=lp["wu"][first:first + 2], wd=lp["wd"][first:first + 2])
        theirs = ref.ffn_layer(x, cut, dict(sz, first=first, held=2),
                               False) - x
        np.testing.assert_allclose(part, theirs, rtol=0, atol=TOL)
    assert pairs == 24 * cfg.top_k              # every pair on one share
    np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=TOL)
    uncut = ref.ffn_layer(x, lp, sz, False) - x
    np.testing.assert_allclose(sum(parts), uncut, rtol=0, atol=TOL)
    assert float(jnp.abs(uncut).max()) > 100 * TOL


def test_a_share_of_the_model_runs_end_to_end(tokens):
    """A config that holds 2 of 8 experts (`experts_first` 4) through
    `apply` and the paged programs: the reference given the same share."""
    cfg = _cfg(experts_first=4, experts_held=2)
    params = lm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg)
    mine = ref.draw(SEED, _sizes(cfg), {})
    sz = _sizes(cfg)
    want = np.asarray(jax.jit(lambda p, t: ref.logits(p, t, sz))(
        mine, jnp.asarray(tokens[:40])))
    got = jax.jit(lambda p, t: lm.apply(p, t, cfg))(
        params, jnp.asarray(tokens[:40])[None])[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    served, _, stats = _serve(cfg, params, tokens[:40], 30)
    np.testing.assert_allclose(served, want[29:], rtol=0, atol=TOL)
    assert dict(zip(lm.STEP_STATS, np.asarray(stats)))["moe_pairs"] <= 8


# -- through the serving engine, by hand ---------------------------------------


def test_the_engine_serves_the_references_rows_and_counts_both_pools(model):
    """Two sequences side by side through `ContinuousEngine` (a 37-token
    prompt in three chunks, a 6-token one in one program), each decoded,
    then a third that takes a returned slot, entry and pages: every logits
    row is the reference's (an entry's next holder starts from zeros); the
    engine counts the tails' bytes apart from the pages' and every
    token-expert pair once."""
    from ray_tpu.serve._engine import ContinuousEngine

    from test_serve_state_kind import _by_hand, _run

    cfg, params, _ = model
    eng = _by_hand(ContinuousEngine(
        lm, cfg, params, max_slots=2, page_size=PS, max_total=64,
        prefill_bucket=8, prefill_chunk=CHUNK))
    assert eng._kinds == {"full": None, "conv": "state"}
    assert (eng._state_kinds, eng._main, eng._share) == (["conv"], "full",
                                                         False)
    assert eng._pool_pages == {"full": 1 + 2 * 8, "conv": 1 + 2}
    toks = lambda n, seed: np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).tolist()
    plens, new = (37, 6, 21), 6
    seqs = [eng.submit(toks(p, p), new) for p in plens]
    rows = [{}, {}, {}]
    _run(eng, seqs, rows)
    assert [s.chunks for s in seqs] == [3, 1, 2]
    sz = _sizes(cfg)
    mine = ref.draw(SEED, sz, WEIGHTS)
    fwd = jax.jit(lambda p, t: ref.logits(p, t, sz))
    for s, p, got in zip(seqs, plens, rows):
        out = s.result.result()["completion"]
        seq = list(s.tokens) + out
        pad = jnp.zeros(48, jnp.int32).at[:len(seq)].set(jnp.asarray(seq))
        want = np.asarray(fwd(mine, pad))
        assert int(np.argmax(want[p - 1])) == out[0]
        assert got, "no row was read"
        for j, row in got.items():          # row j predicts generated[j]
            assert np.abs(row - want[p - 1 + j]).max() < TOL, (p, j)
    entry = len(cfg.conv_layers) * 2 * cfg.d_model * 4
    page = 2 * cfg.n_kv_heads * cfg.d_head * PS * 4 * len(cfg.attn_layers)
    assert (eng._entry_bytes, eng._page_bytes) == (entry, {"full": page})
    st = eng.engine_stats()
    assert st["state_arena_bytes"] == 3 * entry and st["states_live"] == 0
    expert_layers = cfg.n_layers - cfg.n_dense
    assert st["moe_pairs"] == st["conv_live"] * cfg.top_k * expert_layers
    assert st["chunk_moe_pairs"] == \
        st["prefill_tokens"] * cfg.top_k * expert_layers
    assert st["prefill_tokens"] == sum(plens)
    eng.stop()
