"""models/ling3.py against benchmarks/reference/ling3_plain.py on logits,
at toy size in float32 on the CPU.  The reference draws its OWN weights
from the seed by the recipe the configuration states and walks a KDA layer
one token after the other; the program draws its by `init` and the
benchmark's loader and runs the chunked WY form and the step: the first
test holds the two draws leaf for leaf, the others hold the arithmetic.

Tolerances: both sides compute in float32, so they differ by summation
order alone (the chunk's triangular inverse and its exponent differences
included): logits of standard deviation ~0.5 agree to a few 1e-5.  TOL =
2e-4 leaves that room and fails a state arena kept in bfloat16 (8 mantissa
bits a state element at every step: ~1e-3 on a logit), which one test
says."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers.replica_ling3 import shape_weights
from benchmarks.reference import deepseek_v3_plain as dsp
from benchmarks.reference import ling3_plain as ref
from held_leaf import laid
from ray_tpu.models import deepseek_v3 as dm
from ray_tpu.models import ling3 as lm
from ray_tpu.models import served

TOL = 2e-4
SEED = 2147483659            # past 2**31: the loader folds it
WEIGHTS = {"scales": {"w_f": 0.25, "w_qkv": 0.25}, "router_bias_std": 0.005,
           "a_range": [1.0, 16.0], "fresh_log_a": [0.002, 1.0]}
PS = 8


def _sizes(cfg, **kw):
    out = {"eps": cfg.rms_eps, "theta": cfg.rope_theta, "yarn": None,
           "d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "d_head": cfg.d_head, "gate_lower": cfg.gate_lower,
           "layer_group": cfg.layer_group, "kv_rank": cfg.kv_rank,
           "d_nope": cfg.d_nope, "d_rope": cfg.d_rope, "d_v": cfg.d_v,
           "d_ff": cfg.d_ff, "d_expert": cfg.d_expert,
           "d_shared": cfg.d_shared, "n_shared": 1,
           "n_experts": cfg.n_experts, "first": cfg.experts_first,
           "held": cfg.experts_held, "top_k": cfg.top_k,
           "n_group": cfg.n_group, "topk_group": cfg.topk_group,
           "routed_scale": cfg.routed_scale, "n_layers": cfg.n_layers,
           "n_dense": cfg.n_dense, "vocab": cfg.vocab_size,
           "param_dtype": "float32"}
    out.update(kw)
    return out


def _make(**kw):
    # four layers, groups of three: KDA at 0 1 3, MLA at 2; layer 0 dense
    cfg = lm.Ling3Config.nano(dtype=jnp.float32, param_dtype=jnp.float32,
                              **kw)
    params = shape_weights(
        lm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg), WEIGHTS, SEED,
        cfg.gate_lower)
    return cfg, params


@pytest.fixture(scope="module", autouse=True)
def small_pieces():
    """The draw's piece at 4,096 values while this file's tests run (both
    writings of the recipe): toy leaves then span two pieces."""
    mp = pytest.MonkeyPatch()
    mp.setattr(served, "DRAW_PIECE", 4096)
    mp.setattr(dsp, "DRAW_PIECE", 4096)
    yield
    mp.undo()


@pytest.fixture(scope="module")
def model():
    return _make()


@pytest.fixture(scope="module")
def drawn(model):
    return ref.draw(SEED, _sizes(model[0]), WEIGHTS)


@pytest.fixture(scope="module")
def tokens(model):
    return np.random.default_rng(5).integers(0, model[0].vocab_size, 38)


@pytest.fixture(scope="module")
def want(model, drawn, tokens):
    return np.asarray(ref.logits(drawn, jnp.asarray(tokens),
                                 _sizes(model[0])))


def test_the_pattern_is_data(model):
    cfg = model[0]
    assert cfg.mla_layers == [2] and cfg.kda_layers == [0, 1, 3]
    assert ["wkv_a" in l for l in model[1]["layers"]] == [
        False, False, True, False]
    assert ["router" in l for l in model[1]["layers"]] == [False] + [True] * 3
    full = lm.Ling3Config()
    assert len(full.mla_layers) == 7 and len(full.kda_layers) == 35
    assert full.mla_layers[0] == 5 and full.softmax_scale == 192 ** -0.5


def test_the_two_draws_agree_leaf_for_leaf(model, drawn):
    """The program's `init` + the loader's `shape_weights` and the
    reference's own `draw`: the same leaves bit for bit, the gate's A_log
    and dt_bias and the correction bias included."""
    cfg, params = model
    for name in ("embed", "unembed", "final_norm"):
        np.testing.assert_array_equal(np.asarray(params[name]),
                                      np.asarray(drawn[name]), err_msg=name)
    for l, (a, b) in enumerate(zip(params["layers"],
                                   map(laid, drawn["layers"]))):
        assert sorted(a) == sorted(b), l
        for name in a:
            np.testing.assert_array_equal(
                np.asarray(a[name]), np.asarray(b[name]), err_msg=(l, name))
    kda = params["layers"][0]
    a = np.exp(np.asarray(kda["a_log"]))
    assert (a >= 1).all() and (a <= 16).all() and np.ptp(a) > 1
    fresh = cfg.gate_lower / (1 + np.exp(-a[:, None]
                                         * np.asarray(kda["dt_bias"])))
    assert (fresh <= -0.00199).all() and (fresh >= -1.001).all()


def test_apply_is_the_reference(model, tokens, want):
    cfg, params = model
    got = np.asarray(lm.apply(params, jnp.asarray(tokens)[None], cfg))[0]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1


def _serve(cfg, params, tokens, plen, chunk, impl, state_dtype=None,
           keep_tail=True):
    """The prompt in chunks (the last one padded), then a step a token,
    slot 1 of three live, on an entry and pages someone else dirtied.
    Returns the logits rows of positions plen-1 .. len(tokens)-1."""
    cfg = dataclasses.replace(cfg, kda_impl=impl)
    prefill = jax.jit(functools.partial(lm.paged_prefill, cfg=cfg))
    step = jax.jit(functools.partial(lm.paged_decode_step, cfg=cfg))
    view = lm.serve_view(params, cfg)
    cache = lm.init_paged_cache(cfg, {lm.FULL: 16, lm.KDA: 4}, PS)
    cache["state"] = cache["state"] + 3.0        # the last holder's
    cache["tail"] = cache["tail"] - 2.0
    cache["latent"] = [a + 1.0 for a in cache["latent"]]
    if state_dtype is not None:
        cache["state"] = cache["state"].astype(state_dtype)
    R = 8
    tab = np.zeros(R, np.int32)
    tab[:6] = [3, 9, 4, 11, 2, 7]
    rows_of = {lm.FULL: jnp.asarray(tab), lm.KDA: jnp.asarray([2], jnp.int32)}
    rows, start = [], 0
    while start < plen:
        m = min(chunk, plen - start)
        toks = np.zeros(chunk, np.int32)
        toks[:m] = tokens[start:start + m]
        lg, cache, stats = prefill(view, cache, jnp.asarray(toks), rows_of,
                                   jnp.int32(start), jnp.int32(m - 1))
        if not keep_tail:
            cache["tail"] = cache["tail"] * 0
        start += m
    assert float(stats[lm.STEP_STATS.index("kda_live")]) == 1.0
    rows.append(lg)
    B = 3
    ptabs = {lm.FULL: jnp.zeros((B, R), jnp.int32).at[1].set(tab),
             lm.KDA: jnp.asarray([[0], [2], [0]], jnp.int32)}
    null = {k: np.asarray(cache[k][:, 0]) for k in ("state", "tail")}
    for t in range(plen, len(tokens)):
        lg, cache, stats = step(
            view, cache, jnp.asarray([0, int(tokens[t]), 0], jnp.int32),
            ptabs, jnp.asarray([0, t, 0], jnp.int32))
        rows.append(lg[1])
        assert float(stats[lm.STEP_STATS.index("kda_live")]) == (
            3.0 if impl == "xla" else 1.0)
    for k, before in null.items():       # empty slots left the null entry
        np.testing.assert_array_equal(np.asarray(cache[k][:, 0]), before)
    return np.asarray(jnp.stack(rows))


@pytest.mark.parametrize("impl,chunk,plen", [("xla", 16, 29),
                                             ("pallas_interpret", 16, 29),
                                             ("xla", 8, 30)],
                         ids=["xla-16", "kernel-16", "xla-8"])
def test_chunks_and_steps_through_the_mixed_cache_are_the_reference(
        model, tokens, want, impl, chunk, plen):
    """Chunked prefill (a prompt that is no multiple of the chunk, nor of
    4: the conv tail crosses a boundary inside a tap group) + decode
    through latent pages, state entry and tail; the entry's last holder's
    state, tail and pages are not read."""
    cfg, params = model
    got = _serve(cfg, params, tokens, plen, chunk, impl)
    assert np.abs(got - want[plen - 1:]).max() < TOL


def test_a_bfloat16_state_or_a_dropped_tail_fails_the_tolerance(
        model, tokens, want):
    cfg, params = model
    half = _serve(cfg, params, tokens, 29, 16, "xla", jnp.bfloat16)
    assert np.abs(half - want[28:]).max() > 3 * TOL
    lost = _serve(cfg, params, tokens, 29, 16, "xla", keep_tail=False)
    assert np.abs(lost - want[28:]).max() > 30 * TOL


def test_four_chips_shares_add_up_to_the_uncut_layer(model):
    """One expert layer cut four ways as the configuration cuts it (each
    chip a quarter of the experts; router and shared expert replicated):
    the four chips' feed-forwards, with the shared expert counted once,
    are the reference's UNCUT layer (held = all 16)."""
    cfg, _ = model
    E = cfg.n_experts
    sz = _sizes(cfg, first=0, held=E)
    lp = {n: ref.draw_leaf(SEED, sz, WEIGHTS, 1, n) for n in (
        "router", "router_bias", "wg", "wu", "wd", "shared_gate",
        "shared_up", "shared_down")}
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (24, cfg.d_model)), jnp.float32)
    ones = jnp.ones((cfg.d_model,), jnp.float32)
    h = dsp.normed(x, ones, sz)
    whole = np.asarray(ref.moe_layer(x, ones, lp, sz) - x)
    shared = np.asarray(dsp.shared_expert(
        h, lp["shared_gate"], lp["shared_up"], lp["shared_down"], sz))
    total, pairs = -3.0 * shared, 0.0
    for chip in range(4):
        held = E // 4
        part = dataclasses.replace(cfg, experts_first=chip * held,
                                   experts_held=held)
        mine = laid(dict(lp, **{n: lp[n][chip * held:(chip + 1) * held]
                                 for n in ("wg", "wu", "wd")}))
        out, (loads, _) = dm.layer_ffn(h, mine, part)
        total = total + np.asarray(out)
        pairs += float(loads.sum())
    assert pairs == 24 * cfg.top_k            # every pair fell on one chip
    assert np.abs(total - whole).max() < TOL * max(1.0, np.abs(whole).max())


def test_a_nonzero_swiglu_limit_raises():
    with pytest.raises(ValueError, match="SwiGLU limit"):
        lm.Ling3Config.nano(swiglu_limits=(0, 0, 4))
    assert lm.Ling3Config.nano(swiglu_limits=(0,) * 8).swiglu_limits


def test_the_view_and_the_cache_say_what_they_hold(model):
    cfg, params = model
    view = lm.serve_view(params, cfg)
    for l, layer in enumerate(view["layers"]):
        assert ("w_uk" in layer) == cfg.is_mla(l) and "wkv_b" not in layer
    assert lm.serve_view(view, cfg)["layers"][2]["w_uk"] is \
        view["layers"][2]["w_uk"]
    assert lm.cache_kinds(cfg) == {"full": None, "kda": "state"}
    cache = lm.init_paged_cache(cfg, {"full": 5, "kda": 3}, PS)
    H, d = cfg.n_heads, cfg.d_head
    assert [a.shape for a in cache["latent"]] == [(5, 20, PS)]
    assert cache["state"].shape == (3, 3, H, d, d)
    assert cache["tail"].shape == (3, 3, 3, 3 * H, d)
    assert sum(a.nbytes for a in lm.state_leaves(cache)) == (
        3 * 3 * (H * d * d * 4 + 3 * 3 * H * d * 4))
