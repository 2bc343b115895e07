"""models/deepseek_v3.py against benchmarks/reference/deepseek_v3_plain.py
on logits, at toy size in float32 on the CPU.  The reference draws its OWN
weights from the seed by the recipe the configuration states; the program
draws its by `init` and the benchmark's loader: the first test holds the
two draws leaf for leaf, the others hold the arithmetic.

Tolerances: both sides compute in float32, so they differ by summation
order alone — a few 1e-6 on logits of standard deviation 1.  TOL = 1e-4
leaves that two orders of room and would still fail bfloat16 arithmetic
anywhere in the program (8 mantissa bits: 4e-3 relative a product, ~1e-2
on a logit).  The grouped router (`ops/moe.route_sigmoid_grouped`) is held
to a loop-written one here too: `tests/test_moe.py` is not in the quick
tier."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers.replica_deepseek_v3 import shape_weights
from benchmarks.reference import deepseek_v3_plain as ref
from held_leaf import apart, laid
from ray_tpu.models import deepseek_v3 as dm
from ray_tpu.models import served
from ray_tpu.ops.layers import yarn_frequencies
from ray_tpu.ops.moe import route_sigmoid_grouped

TOL = 1e-4
SEED = 2147483659            # past 2**31: the loader folds it
WEIGHTS = {"scales": {"wq_b": 2}, "router_bias_std": 0.05}
PS = 8


def _sizes(cfg, **kw):
    out = {"eps": cfg.rms_eps, "theta": cfg.rope_theta,
           "yarn": list(cfg.yarn) if cfg.yarn else None,
           "d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "q_rank": cfg.q_rank, "kv_rank": cfg.kv_rank,
           "d_nope": cfg.d_nope, "d_rope": cfg.d_rope, "d_v": cfg.d_v,
           "d_ff": cfg.d_ff, "d_expert": cfg.d_expert,
           "n_experts": cfg.n_experts, "first": cfg.experts_first,
           "held": cfg.experts_held, "top_k": cfg.top_k,
           "n_group": cfg.n_group, "topk_group": cfg.topk_group,
           "routed_scale": cfg.routed_scale, "n_shared": cfg.n_shared,
           "n_layers": cfg.n_layers, "n_dense": cfg.n_dense,
           "vocab": cfg.vocab_size, "param_dtype": "float32"}
    out.update(kw)
    return out


def _make(**kw):
    cfg = dm.DeepSeekV3Config.nano(dtype=jnp.float32,
                                   param_dtype=jnp.float32, **kw)
    params = shape_weights(
        dm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg), WEIGHTS, SEED)
    return cfg, params


@pytest.fixture(scope="module", autouse=True)
def small_pieces():
    """The draw's piece (4,194,304 values in both writings of the recipe)
    at 4,096 while this file's tests run: toy leaves of up to 6,144
    values then span two pieces, so the joins are crossed."""
    mp = pytest.MonkeyPatch()
    mp.setattr(served, "DRAW_PIECE", 4096)
    mp.setattr(ref, "DRAW_PIECE", 4096)
    yield
    mp.undo()


@pytest.fixture(scope="module")
def model():
    return _make()


@pytest.fixture(scope="module")
def drawn(model):
    return ref.draw(SEED, _sizes(model[0]), WEIGHTS)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


def test_the_reference_draws_the_loaders_weights(model, drawn):
    """Two writings of one recipe: every leaf bit for bit, the correction
    bias drawn and not zero, the scaled leaf scaled."""
    cfg, params = model
    for name in ("embed", "unembed"):
        np.testing.assert_array_equal(params[name], drawn[name])
    for mine, theirs in zip(params["layers"], map(laid, drawn["layers"])):
        assert set(mine) == set(theirs)
        for name in mine:
            np.testing.assert_array_equal(mine[name], theirs[name], name)
    bias = np.asarray(params["layers"][1]["router_bias"])
    assert 0.02 < bias.std() < 0.1 and "router" not in params["layers"][0]
    plain = dm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg)
    np.testing.assert_array_equal(params["layers"][0]["wq_b"],
                                  2 * plain["layers"][0]["wq_b"])


def test_full_forward_matches_reference(model, drawn):
    cfg, params = model
    toks = np.stack([_tokens(40, s) for s in (0, 1)])
    got = np.asarray(dm.apply(params, jnp.asarray(toks), cfg))
    for g, t in zip(got, toks):
        want = np.asarray(ref.logits(drawn, jnp.asarray(t), _sizes(cfg)))
        assert want.std() > 0.5
        np.testing.assert_allclose(g, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("heads,rows", [(1, 8), (2, 24), (4, 4)])
def test_reference_in_blocks_changes_nothing(model, drawn, heads, rows):
    """The check on the chip forms a few heads' keys and values at a time
    and walks the rows in blocks, a block against the key blocks it can
    see; rows past `blocks` come back as they came."""
    cfg, _ = model
    sz = _sizes(cfg)
    lp = drawn["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.d_model))
    c_kv, k_pe = ref.latents(x, lp["attn_norm"], lp["wkv_a"], lp["kv_norm"],
                             sz)
    args = (x, c_kv, k_pe, lp["attn_norm"], lp["wq_a"], lp["q_norm"],
            lp["wq_b"], lp["wkv_b"], lp["wo"], sz)
    whole = ref.attend(*args)
    np.testing.assert_allclose(ref.attend(*args, heads, rows), whole,
                               atol=1e-5, rtol=0)
    if rows == 8:
        part = ref.attend(*args, heads, rows, 2)
        np.testing.assert_allclose(part[:16], whole[:16], atol=1e-5, rtol=0)
        np.testing.assert_array_equal(part[16:], x[16:])


def _walk_pages_as_on_the_chip(monkeypatch):
    """The absorbed step takes `latent_decode_attention` as it does on a
    TPU — the kernel interpreted here: the predicate and the kernel are
    the names deepseek_v3 calls them by."""
    monkeypatch.setattr(dm, "latent_decode_uses_kernel",
                        lambda rows, platform=None: rows == 1)
    monkeypatch.setattr(dm, "latent_decode_attention", functools.partial(
        dm.latent_decode_attention, interpret=True))


@pytest.mark.parametrize("forms", [(False, True), (False, False), (None, None),
                                   (False, "walked")],
                         ids=["as-served", "all-expanded", "all-absorbed",
                              "as-served-on-the-chip"])
def test_chunked_prefill_then_decode_through_latent_pages(model, drawn, forms,
                                                          monkeypatch):
    """A prompt in chunks of 16, 13 (+3 pad rows) and 3 (+1), then eight
    decode steps beside an empty slot, every row's logits against the
    reference's full forward.  As served at the real sizes the chunks
    expand the latents and the steps absorb: the two 16-row chunks are
    told to expand here (at most `ABSORB_ROWS` = 128 rows would absorb);
    the other two cases run every program through one form — the last as
    the rows decide at this size; the fourth is the first with the step's
    attention the kernel that walks the pages (what a TPU takes).  A
    program's last counter is the key positions it fetched: every row of
    the batch every block of 16 to the longest context, or, walked, the
    live slot's own pages."""
    at = dm.STEP_STATS.index
    cfg, params = model
    long_form, short_form = forms
    walked = short_form == "walked"
    if walked:
        _walk_pages_as_on_the_chip(monkeypatch)
        short_form = True
    assert dm.ABSORB_ROWS == 128
    prefill = jax.jit(dm.paged_prefill, static_argnames=("cfg", "absorbed"))
    # a program of this case's own: what the step calls is looked up when
    # it is traced
    step = jax.jit(functools.partial(dm.paged_decode_step),
                   static_argnames=("cfg", "absorbed"))
    view = dm.serve_view(params, cfg)
    assert "wkv_b" not in view["layers"][0]
    seq = _tokens(40)
    want = np.asarray(ref.logits(drawn, jnp.asarray(seq), _sizes(cfg)))
    maxp = 8
    cache = dm.init_paged_cache(cfg, {"full": 1 + 2 * maxp}, PS)
    assert [a.shape for a in cache] == [(17, cfg.d_latent, PS)] * 3
    tab = np.arange(3, 3 + maxp, dtype=np.int32)
    start = 0
    for n, T in ((16, 16), (13, 16), (3, 4)):
        chunk = np.zeros(T, np.int32)
        chunk[:n] = seq[start:start + n]
        row, cache, stats = prefill(
            view, cache, jnp.asarray(chunk), {"full": tab}, np.int32(start),
            np.int32(n - 1), cfg=cfg,
            absorbed=long_form if T == 16 else short_form)
        start += n
        np.testing.assert_allclose(row, want[start - 1], atol=TOL, rtol=0)
        pairs = sum(range(start - n + 1, start + 1)) * cfg.n_layers
        assert (stats[at("mla_pairs")], stats[at("mla_keys")]) == (
            pairs, start * cfg.n_layers)
        assert stats[at("mla_walked_keys")] == (
            -(-start // 16) * 16 * cfg.n_layers)
    ptab = np.zeros((2, maxp), np.int32)
    ptab[1] = tab
    for i in range(start, 40):
        lg, cache, stats = step(
            view, cache, jnp.asarray([0, seq[i]], jnp.int32), {"full": ptab},
            jnp.asarray([0, i], jnp.int32), cfg=cfg, absorbed=short_form)
        np.testing.assert_allclose(lg[1], want[i], atol=TOL, rtol=0)
        assert (stats[at("mla_pairs")] == stats[at("mla_keys")]
                == (i + 1) * cfg.n_layers)
        assert stats[at("mla_walked_keys")] == cfg.n_layers * (
            (i // PS + 1) * PS if walked else 2 * (i // 16 + 1) * 16)
    # the null page is as it was made: pad rows and the empty slot wrote
    # nothing anywhere
    assert not any(np.asarray(a[0]).any() for a in cache)
    # a page holds its positions along the last axis: c_kv | k_pe a column
    lp = drawn["layers"][0]
    x = drawn["embed"][seq[:PS]].astype(jnp.float32)
    c_kv, k_pe = ref.latents(x, lp["attn_norm"], lp["wkv_a"], lp["kv_norm"],
                             _sizes(cfg))
    np.testing.assert_allclose(cache[0][3].T,
                               jnp.concatenate([c_kv, k_pe], -1), atol=1e-5)


@pytest.mark.parametrize("walked", [False, True],
                         ids=["block-loop", "walked-pages"])
def test_the_ring_counts_the_keys_a_step_fetched(model, walked, monkeypatch):
    """Two live slots of 8 through a `ContinuousEngine`: a step's ring
    record carries `mla_walked_keys` beside `mla_keys` — on the XLA path
    slots x blocks to the longest context x block size a layer, on the
    kernel's at most a page a live slot a layer past what was needed —
    `engine_stats()` sums both, and both paths serve the same tokens."""
    import threading

    from ray_tpu.serve._engine import ContinuousEngine

    if walked:
        _walk_pages_as_on_the_chip(monkeypatch)
    cfg, params = model
    eng = ContinuousEngine(dm, cfg, params, max_slots=8, page_size=PS,
                           max_total=96, prefill_bucket=4, prefill_chunk=16)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    eng._thread = t                 # the test drives the iterations
    seqs = [eng.submit(_tokens(n, seed=n).tolist(), 24) for n in (37, 6)]
    for _ in range(200):
        eng._iteration()
        if all(s.result.done() for s in seqs):
            break
    steps = [r for r in eng.phase_ring() if r["active"] == 2]
    assert len(steps) >= 20
    L, block = cfg.n_layers, cfg.kv_block
    for r in steps:
        if walked:
            assert r["mla_keys"] <= r["mla_walked_keys"] < (
                r["mla_keys"] + 2 * PS * L)
            assert r["mla_walked_keys"] % (PS * L) == 0
        else:
            assert r["mla_walked_keys"] % (8 * block * L) == 0
            assert r["mla_walked_keys"] >= 8 * block * L > r["mla_keys"] / 4
    stats = eng.engine_stats()
    assert stats["mla_walked_keys"] >= sum(r["mla_walked_keys"]
                                           for r in steps)
    assert 0 < stats["mla_keys"] <= stats["mla_walked_keys"]
    assert stats["chunk_mla_walked_keys"] >= stats["chunk_mla_keys"] > 0
    eng.stop()
    served = tuple(tuple(s.result.result()["completion"]) for s in seqs)
    assert _SERVED.setdefault("tokens", served) == served


_SERVED = {}


def test_expert_shares_add_up_to_the_uncut_layer(model):
    """What the four chips of a layer compute of 16 experts, 4 each — the
    shared expert counted once — adds up to the layer with every expert
    held, in the program and in the reference alike."""
    cfg, _ = model
    whole = dataclasses.replace(cfg, experts_first=0, experts_held=16)
    layer = dm.init_layer(jax.random.PRNGKey(5), whole, 1)
    layer["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6),
                                                    (16,))
    h = jax.random.normal(jax.random.PRNGKey(7), (24, cfg.d_model))
    full, (loads, _) = dm.layer_ffn(h, layer, whole)
    assert int(loads.sum()) == 24 * cfg.top_k
    shared = dm.swiglu(h, layer["shared_gate"], layer["shared_up"],
                       layer["shared_down"])
    total = shared
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_first=first, experts_held=4)
        part = dict(layer, **{k: layer[k][first:first + 4]
                              for k in ("wgu", "wd")})
        out, _ = dm.layer_ffn(h, part, share)
        total = total + (out - shared)
    np.testing.assert_allclose(total, full, atol=TOL, rtol=0)
    want = ref.feed_forward(h, apart(layer), _sizes(whole))
    np.testing.assert_allclose(full, want, atol=TOL, rtol=0)


def test_yarn_frequencies_against_hand_computed_values():
    """dim 64, theta 10,000, factor 40, beta 32 / 1, 4,096 trained
    positions: pair i turns at 10000^(-i/32).  A pair completes `t` turns
    in 4,096 positions where i = 32 ln(4096 / (2 pi t)) / ln 10000: 10.47
    for t = 32, 22.51 for t = 1, so pairs 0-10 keep their frequency, pairs
    23-31 are slowed 40 times, and pair 16 is 6/13 of the way."""
    f = yarn_frequencies(64, 10000.0, 40.0, 32.0, 1.0, 4096)
    plain = lambda i: 10000.0 ** (-i / 32.0)
    assert f.shape == (32,) and f.dtype == np.float32
    np.testing.assert_allclose(f[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(f[10], plain(10), rtol=1e-6)     # 0.05623
    np.testing.assert_allclose(f[10], 0.0562341, rtol=1e-5)
    np.testing.assert_allclose(f[23], plain(23) / 40, rtol=1e-6)
    np.testing.assert_allclose(f[31], 1.3335e-4 / 40, rtol=1e-4)
    np.testing.assert_allclose(
        f[16], 0.01 * (7 / 13) + 0.01 / 40 * (6 / 13), rtol=1e-5)
    # the reference writes the same recipe a second time
    sz = {"d_rope": 64, "theta": 10000.0, "yarn": [40.0, 32.0, 1.0, 4096]}
    np.testing.assert_allclose(ref.yarn_inv_freq(sz), f, rtol=1e-5)
    cfg = dm.DeepSeekV3Config()
    np.testing.assert_allclose(cfg.softmax_scale,
                               192 ** -0.5 * 1.3688879 ** 2, rtol=1e-6)


@pytest.mark.parametrize("control", ["no_rope_score", "no_yarn_scale",
                                     "no_groups", "chunk_blind",
                                     "latent_8bit", "fp8_weights"])
def test_the_comparison_catches(model, drawn, control):
    """Each fault the chip's control runs put into the reference moves
    toy-size logits by far more than TOL."""
    cfg, params = model
    toks = _tokens(40)
    got = np.asarray(dm.apply(params, jnp.asarray(toks[None]), cfg))[0]
    bad = np.asarray(ref.logits(drawn, jnp.asarray(toks), _sizes(
        cfg, control=control, control_chunk=16)))
    assert np.abs(got - bad).max() > 100 * TOL


def _route_grouped_by_loops(s, bias, k, n_group, topk_group, scale):
    """The grouped router written with loops over one token's scores: a
    tie goes to the lower index in both rankings (a stable sort of the
    negated values)."""
    E = len(s)
    size = E // n_group
    c = s + bias
    rank = []
    for g in range(n_group):
        best = sorted(c[g * size:(g + 1) * size], reverse=True)
        rank.append(best[0] + best[1])
    kept = sorted(range(n_group), key=lambda g: (-rank[g], g))[:topk_group]
    allowed = [e for e in range(E) if e // size in kept]
    chosen = sorted(allowed, key=lambda e: (-c[e], e))[:k]
    total = sum(s[e] for e in chosen) + 1e-20
    return chosen, [s[e] / total * scale for e in chosen]


@pytest.mark.parametrize("bias_std", [0.0, 0.3])
def test_route_sigmoid_grouped_matches_loops(bias_std):
    """Scores + bias choose, scores alone weigh; 4 groups of 8, 2 kept,
    top-4 inside them, x 2.5 — with a bias large enough to overrule the
    scores, and with exact ties: two tokens whose router rows are equal
    across experts (every biased score of a group equal: the lower index
    wins) and a group tie (groups 0 and 1 made identical)."""
    N, D, E, G, KG, K = 12, 16, 32, 4, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (N, D))
    w = jax.random.normal(ks[1], (D, E)) / 4.0
    w = w.at[:, 8:16].set(w[:, 0:8])        # group 1 ties with group 0
    h = h.at[0].set(0.0).at[1].set(0.0)     # every score 1/2
    bias = bias_std * jax.random.normal(ks[2], (E,))
    bias = bias.at[8:16].set(bias[0:8])
    wt, idx = route_sigmoid_grouped(h, w, bias, K, n_group=G, topk_group=KG,
                                    scale=2.5)
    s = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h, w, precision=jax.lax.Precision.HIGHEST)),
        np.float64)
    moved = 0
    for n in range(N):
        chosen, weights = _route_grouped_by_loops(
            s[n], np.asarray(bias, np.float64), K, G, KG, 2.5)
        assert list(np.asarray(idx[n])) == chosen, n
        np.testing.assert_allclose(wt[n], weights, rtol=1e-6)
        plain = list(np.argsort(-s[n], kind="stable")[:K])
        moved += plain != chosen
    np.testing.assert_allclose(wt.sum(-1), 2.5, rtol=1e-6)
    assert moved >= 3           # grouping and bias change who is chosen
    if not bias_std:            # all-equal scores: the first group's first
        assert list(np.asarray(idx[0])) == [0, 1, 2, 3]
