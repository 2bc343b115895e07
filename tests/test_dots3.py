"""models/dots3.py against benchmarks/reference/dots3_plain.py on logits,
at toy size in float32 on the CPU.  The reference draws its OWN weights
from the seed by the recipe the configuration states; the program draws
its by `init` and the benchmark's loader: the first test holds the two
draws leaf for leaf, the others hold the arithmetic — the selection (a
threshold in the program, `jax.lax.top_k` in the reference), both pools of
latent pages and the indexer's arena, a window's ring.

Tolerances: both sides compute in float32, so they differ by summation
order alone — a few 1e-6 on logits of standard deviation 1; TOL = 1e-4 as
tests/test_deepseek_v3.py.  The toy indexer has 16 heads: with two, a
score is exactly 0 for a quarter of the keys, the threshold form keeps
every key tied at it and `top_k` the lower positions (ISSUE 56, section
3)."""

import dataclasses
import functools
import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.drivers.replica_dots3 import shape_weights
from benchmarks.lib import costs_dsa
from benchmarks.lib.dots3cfg import model_config, reference_shape
from benchmarks.reference import dots3_plain as ref
from held_leaf import apart, laid
from ray_tpu.models import deepseek_v3 as dm
from ray_tpu.models import dots3 as m3
from ray_tpu.models import served
from ray_tpu.ops.select import keep_top

TOL = 1e-4
SEED = 2147483659            # past 2**31: the loader folds it
WEIGHTS = {"scales": {"wq_b": 2}, "router_bias_std": 0.05}
PS = 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = importlib.import_module("ray_tpu.ops.attention")   # not ops.attention()


def _toy_conf():
    with open(os.path.join(ROOT, "benchmarks", "tests",
                           "rehearsal_sparsectx.json")) as f:
        conf = json.load(f)["config"]
    return {**conf, "apply_mla_qkv_lora_rescale": True, "rope_scaling": None,
            "attention_gate_type": "headwise",
            "swa_attention_gate_type": "headwise", "rope_theta": 8e7,
            "swa_rope_theta": 5e4, "routed_scaling_factor": 1,
            "n_shared_experts": 1, "rms_norm_eps": 1e-5,
            "serve": {"max_seq": 128}}


@pytest.fixture(scope="module", autouse=True)
def small_pieces():
    """The draw's piece at 4,096 values while this file's tests run: toy
    leaves then span two pieces, so the joins are crossed."""
    mp = pytest.MonkeyPatch()
    mp.setattr(served, "DRAW_PIECE", 4096)
    mp.setattr(ref, "DRAW_PIECE", 4096)
    yield
    mp.undo()


@pytest.fixture(scope="module")
def model():
    """(cfg, params, the reference's sizes): the rehearsal's toy
    configuration through the benchmark's own two readers — window 17,
    index_topk 24, experts 4-7 of 16 held."""
    conf = _toy_conf()
    cfg = model_config(conf)
    params = shape_weights(
        m3.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg), WEIGHTS, SEED)
    return cfg, params, reference_shape(conf)


@pytest.fixture(scope="module")
def drawn(model):
    return ref.draw(SEED, model[2], WEIGHTS)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def test_the_reference_draws_the_loaders_weights(model, drawn):
    _, params, _ = model
    for name in ("embed", "unembed", "final_norm"):
        np.testing.assert_array_equal(params[name], drawn[name])
    for l, (mine, theirs) in enumerate(zip(params["layers"],
                                           map(laid, drawn["layers"]))):
        assert set(mine) <= set(theirs), (l, set(mine) - set(theirs))
        for name, w in mine.items():
            np.testing.assert_array_equal(w, theirs[name], err_msg=f"{l} "
                                          + name)
    assert float(jnp.abs(params["layers"][1]["router_bias"]).max()) > 0


def test_full_forward_matches_reference(model, drawn):
    """`apply` over 72 tokens: three times `index_topk`, four windows."""
    cfg, params, sz = model
    toks = np.stack([_tokens(72, s) for s in (0, 1)])
    got = np.asarray(m3.apply(params, jnp.asarray(toks), cfg))
    for g, t in zip(got, toks):
        want = np.asarray(ref.logits(drawn, jnp.asarray(t), sz))
        assert want.std() > 0.5
        np.testing.assert_allclose(g, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("heads,rows", [(1, 32), (2, 64), (4, 32)])
def test_reference_in_blocks_changes_nothing(model, drawn, heads, rows):
    """The check on the chip forms a few heads' keys at a time and walks
    the rows in blocks, the selection kept as bits between the two."""
    cfg, _, sz = model
    lp = drawn["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))
    a = ref.kind_sizes(sz, "full")
    c_kv, k_pe = ref.latents(x, lp["attn_norm"], lp["wkv_a"], lp["kv_norm"],
                             a)
    ki = ref.index_keys(x, lp["attn_norm"], lp["wi_k"], lp["wi_knorm"],
                        lp["wi_kbias"], sz)
    sel = (x, ki, lp["attn_norm"], lp["wq_a"], lp["q_norm"], lp["wi_q"],
           lp["wi_w"], sz)
    kept = ref.select(*sel)
    assert int(jnp.sum(ref._unpack(kept)[40])) == cfg.index_topk
    np.testing.assert_array_equal(ref.select(*sel, 4 * heads, rows), kept)
    args = (x, c_kv, k_pe, kept, lp["attn_norm"], lp["wq_a"], lp["q_norm"],
            lp["wq_b"], lp["wkv_b"], lp["w_head_gate"], lp["wo"], a)
    whole = ref.attend(*args)
    np.testing.assert_allclose(ref.attend(*args, heads, rows), whole,
                               atol=1e-5, rtol=0)
    if rows == 32:
        part = ref.attend(*args, heads, rows, 1)
        np.testing.assert_allclose(part[:32], whole[:32], atol=1e-5, rtol=0)
        np.testing.assert_array_equal(part[32:], x[32:])


def _as_on_the_chip(monkeypatch):
    """Both kernels taken as they are on a TPU, interpreted here: the
    decode walk over latent pages and the chunk's block kernel, each with
    the selection's (or the ring's) mask as an operand."""
    monkeypatch.setattr(dm, "latent_decode_uses_kernel",
                        lambda rows, platform=None: rows == 1)
    monkeypatch.setattr(dm, "latent_decode_attention", functools.partial(
        dm.latent_decode_attention, interpret=True))
    monkeypatch.setattr(A, "streamed_attention_uses_kernel",
                        lambda rows, platform=None: rows >= 16)
    monkeypatch.setattr(A, "_KERNEL_ROWS", 16)
    monkeypatch.setattr(A, "_streamed_kernel_loop", functools.partial(
        A._streamed_kernel_loop, interpret=True))


class _Ring:
    """The engine's bookkeeping of one windowed kind's table, by hand."""

    def __init__(self, width, window):
        self.tab = np.zeros(width, np.int32)
        self.free, self.live, self.window = list(range(1, width + 1)), {}, \
            window

    def grow(self, lo, hi):
        for lp in range(lo // PS, (hi - 1) // PS + 1):
            if lp not in self.live:
                self.live[lp] = self.free.pop(0)
                self.tab[lp % len(self.tab)] = self.live[lp]

    def shrink(self, next_pos):
        for lp in [lp for lp in self.live
                   if (lp + 1) * PS - 1 <= next_pos - self.window]:
            page = self.live.pop(lp)
            self.free.append(page)
            if self.tab[lp % len(self.tab)] == page:
                self.tab[lp % len(self.tab)] = 0


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_chunked_prefill_then_decode_through_both_pools(model, drawn,
                                                        kernels,
                                                        monkeypatch):
    """A prompt of 50 in chunks of 16 (contexts below and above the toy
    `index_topk` of 24), then 22 steps, slot 1 of 3 live: every chunk's
    and every step's logits against the reference's full forward pass;
    the sliding kind's ring (7 entries of 4 positions, window 17) wraps
    twice.  With `kernels` the decode walk and the block kernel run
    interpreted, the selection and the ring their mask operand."""
    if kernels:
        _as_on_the_chip(monkeypatch)
    cfg, params, sz = model
    seq = _tokens(72, 5)
    want = np.asarray(ref.logits(drawn, jnp.asarray(seq), sz))
    view = m3.serve_view(params, cfg)
    chunk, plen = 16, 50
    R_full = 72 // PS
    R_sl = -(-(cfg.window + chunk) // PS) + 1
    cache = m3.init_paged_cache(cfg, {"full": 1 + R_full,
                                      "sliding": 1 + R_sl}, PS)
    full = np.arange(1, 1 + R_full, dtype=np.int32)
    ring = _Ring(R_sl, cfg.window)
    tabs = lambda: {"full": full.copy(), "sliding": ring.tab.copy()}
    prefill = jax.jit(lambda c, t, tb, s, li: m3.paged_prefill(
        view, c, t, tb, s, li, cfg))
    step = jax.jit(lambda c, t, tb, p: m3.paged_decode_step(
        view, c, t, tb, p, cfg))
    start = 0
    while start < plen:
        n = min(chunk, plen - start)
        ring.grow(start, start + n)
        toks = np.zeros(chunk, np.int32)
        toks[:n] = seq[start:start + n]
        row, cache, stats = prefill(cache, toks, tabs(), np.int32(start),
                                    np.int32(n - 1))
        start += n
        ring.shrink(start)
        np.testing.assert_allclose(row, want[start - 1], atol=TOL, rtol=0)
    stats = dict(zip(m3.STEP_STATS, np.asarray(stats)))
    # the last chunk: rows 48, 49 see 49 and 50 keys and keep 24 each, in
    # both full layers; every real row met the four blocks fetched
    assert stats["dsa_keys_visible"] == 2 * (49 + 50)
    assert stats["dsa_keys_selected"] == 2 * 2 * cfg.index_topk
    assert stats["dsa_keys_walked"] == 2 * 2 * 4 * 16
    assert stats["dsa_ctx"] == 2 * 50
    assert stats["swa_pairs"] == 3 * 2 * cfg.window
    B = 3
    for i in range(plen, 72):
        ring.grow(i, i + 1)
        tb = {k: np.zeros((B,) + t.shape, np.int32)
              for k, t in tabs().items()}
        for k, t in tabs().items():
            tb[k][1] = t
        pos, tok = np.zeros(B, np.int32), np.zeros(B, np.int32)
        pos[1], tok[1] = i, seq[i]
        lg, cache, stats = step(cache, tok, tb, pos)
        ring.shrink(i + 1)
        np.testing.assert_allclose(lg[1], want[i], atol=TOL, rtol=0)
    stats = dict(zip(m3.STEP_STATS, np.asarray(stats)))
    assert stats["dsa_keys_visible"] == 2 * 72
    assert stats["dsa_keys_selected"] == 2 * cfg.index_topk
    assert stats["swa_pairs"] == stats["swa_keys"] == 3 * cfg.window
    if kernels:         # the walk: the live slot's own pages, whole
        assert stats["dsa_keys_walked"] == 2 * 72


@pytest.mark.parametrize("n,k", [(64, 8), (300, 64), (33, 40)])
def test_the_threshold_keeps_the_set_top_k_gives(n, k):
    """`keep_top` on random float32 scores under a causal mask: row t
    keeps exactly `jax.lax.top_k`'s set over its visible keys (no two
    scores tie), all of them while it sees at most k."""
    scores = jax.random.normal(jax.random.PRNGKey(n), (n, n), jnp.float32)
    visible = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    keep, kept = jax.jit(keep_top, static_argnums=2)(scores, visible, k)
    idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), min(k, n))[1]
    want = jnp.zeros((n, n), bool).at[jnp.arange(n)[:, None], idx].set(
        True) & visible
    np.testing.assert_array_equal(keep, want)
    np.testing.assert_array_equal(kept, np.minimum(np.arange(n) + 1, k))
    # ties AT the threshold are all kept, and `kept` says so
    tied = jnp.zeros((2, 8)).at[:, 0].set(1.0)
    keep, kept = keep_top(tied, jnp.ones((2, 8), bool), 3)
    assert keep.all() and (kept == 8).all()


def test_expert_shares_add_up_to_the_uncut_layer(model):
    """What the four chips of a layer compute of 16 experts, 4 each — the
    shared expert counted once — adds up to the layer with every expert
    held, in the program and in the reference alike (the guide's section
    4; the benchmark's share is 8 chips of 32 of 256)."""
    cfg, _, sz = model
    whole = dataclasses.replace(cfg, experts_first=0, experts_held=16)
    layer = m3.init_layer(jax.random.PRNGKey(5), whole, 1)
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
    want = ref.feed_forward(h, apart(layer), dict(sz, first=0, held=16))
    np.testing.assert_allclose(full, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("control", [
    "no_selection", "topk_half", "no_index_rope", "window_512",
    "swa_theta_full", "no_gate", "no_lora_rescale", "fp8_weights",
    "index_8bit"])
def test_the_comparison_catches(model, drawn, control):
    """Each fault the chip's control runs put into the reference moves
    toy-size logits by far more than TOL."""
    cfg, params, sz = model
    toks = _tokens(72)
    got = np.asarray(m3.apply(params, jnp.asarray(toks[None]), cfg))[0]
    bad = np.asarray(ref.logits(drawn, jnp.asarray(toks),
                                dict(sz, control=control)))
    assert np.abs(got - bad).max() > 100 * TOL


def test_costs_dsa_agrees_with_a_hand_count():
    """One step of two live slots at contexts 3,000 and 9,000 and one
    chunk of 512 rows at a start of 4,096, at the published widths, by
    hand."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev-l5-e32.json")) as f:
        cfg = json.load(f)
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    step = {"active": 2, "dsa_keys_visible": 2 * 12000,
            "dsa_keys_selected": 2 * 4096, "dsa_ctx": 2 * 12000,
            "swa_pairs": 3 * 1026, "swa_keys": 3 * 1026}
    # indexer: 64 heads x (2 x 128 + 2) a pair; 128 values a key row
    assert costs_dsa.index_flops(24000, cfg) == 24000 * 64 * 258
    assert costs_dsa.least_seconds("index_step", step, cfg, peak) == max(
        24000 * 64 * 258 / 1e12,
        (2 * (24000 * 128 + 2 * 2 * 64 * 129) + 4 * 2 * 2) / 1e9)
    # selected attention: 128 heads x (192 + 128) x 2 a pair; a selected
    # key's 576-wide latent row once
    assert costs_dsa.least_seconds("mla_step", step, cfg, peak) == max(
        2 * 8192 * 128 * 320 / 1e12,
        2 * (8192 * 576 + 2 * 2 * 128 * 320) / 1e9)
    # the window: 64 heads x (256 + 128) x 2 a pair; 1,088-wide rows
    assert costs_dsa.least_seconds("swa_step", step, cfg, peak) == max(
        2 * 3078 * 64 * 384 / 1e12,
        2 * (3078 * 1088 + 2 * 3 * 64 * 384) / 1e9)
    pairs = sum(4096 + t + 1 for t in range(512))
    chunk = {"chunk_tokens": 512, "chunk_dsa_keys_visible": 2 * pairs,
             "chunk_dsa_keys_selected": 2 * 512 * 2048,
             "chunk_dsa_ctx": 2 * 4608}
    # a chunk's rows select 2,048 each, but only 4,608 keys are there
    assert costs_dsa.least_seconds("mla_chunk", chunk, cfg, peak) == max(
        2 * 2 * 512 * 2048 * 128 * 320 / 1e12,
        2 * (2 * 4608 * 576 + 512 * 2 * 128 * 320) / 1e9)


# The shared latent pieces learned a key mask and a ring; for a caller that
# passes neither they lower to what stood.  The numbers are the PARENT's
# (commit 312b1da, this file's `_op_counts` run there): deepseek_v3's nano
# step (4 slots) and chunk (16 rows), 8 pages of 8 a slot, on the CPU —
# less, since PR 62, one grouped product in each of the two expert layers
# (gate and up in one leaf: 2 `func.call`s and 2 `dot_general`s fewer and
# what the CPU's lowering of a product puts around them; nothing else
# moved) and, since PR 64, with what `ops/moe.held_expert_ffn` does once a
# layer before its trips in each of the two expert layers: ONE sort of
# three operands written out (`argsort` was one shared call: `sort` 1 ->
# 2), no gather of the sorted weights and no scatter of ones for the loads
# (a gather and a scatter fewer a layer; a compare and a `reduce` in their
# place).  The trips are the parent's; nothing outside the expert layers
# moved.
PARENT_OPS = {'prefill': {'chlo.square': 13,
             'chlo.top_k': 6,
             'func.call': 22,
             'stablehlo.add': 95,
             'stablehlo.and': 21,
             'stablehlo.broadcast_in_dim': 437,
             'stablehlo.compare': 89,
             'stablehlo.concatenate': 21,
             'stablehlo.constant': 291,
             'stablehlo.convert': 38,
             'stablehlo.cosine': 6,
             'stablehlo.divide': 26,
             'stablehlo.dot_general': 40,
             'stablehlo.dynamic_slice': 10,
             'stablehlo.exponential': 11,
             'stablehlo.gather': 19,
             'stablehlo.iota': 16,
             'stablehlo.maximum': 8,
             'stablehlo.minimum': 4,
             'stablehlo.multiply': 89,
             'stablehlo.negate': 5,
             'stablehlo.pad': 3,
             'stablehlo.reduce': 42,
             'stablehlo.reduce_window': 1,
             'stablehlo.remainder': 4,
             'stablehlo.reshape': 67,
             'stablehlo.rsqrt': 13,
             'stablehlo.scatter': 7,
             'stablehlo.select': 51,
             'stablehlo.sign': 6,
             'stablehlo.sine': 6,
             'stablehlo.slice': 41,
             'stablehlo.sort': 2,
             'stablehlo.subtract': 27,
             'stablehlo.transpose': 18,
             'stablehlo.while': 5},
 'step': {'chlo.square': 13,
          'chlo.top_k': 6,
          'func.call': 22,
          'stablehlo.add': 93,
          'stablehlo.and': 21,
          'stablehlo.broadcast_in_dim': 429,
          'stablehlo.compare': 88,
          'stablehlo.concatenate': 21,
          'stablehlo.constant': 289,
          'stablehlo.convert': 37,
          'stablehlo.cosine': 6,
          'stablehlo.divide': 26,
          'stablehlo.dot_general': 40,
          'stablehlo.dynamic_slice': 9,
          'stablehlo.exponential': 11,
          'stablehlo.gather': 19,
          'stablehlo.iota': 15,
          'stablehlo.maximum': 8,
          'stablehlo.minimum': 4,
          'stablehlo.multiply': 89,
          'stablehlo.negate': 5,
          'stablehlo.pad': 3,
          'stablehlo.reduce': 42,
          'stablehlo.reduce_window': 1,
          'stablehlo.remainder': 4,
          'stablehlo.reshape': 62,
          'stablehlo.rsqrt': 13,
          'stablehlo.scatter': 7,
          'stablehlo.select': 49,
          'stablehlo.sign': 6,
          'stablehlo.sine': 6,
          'stablehlo.slice': 41,
          'stablehlo.sort': 2,
          'stablehlo.subtract': 27,
          'stablehlo.transpose': 18,
          'stablehlo.while': 5}}


def _op_counts(lowered_text: str) -> dict:
    ops = {}
    for line in lowered_text.splitlines():
        line = line.strip()
        if " = " in line and ("stablehlo." in line or "func.call" in line
                              or "chlo." in line):
            name = line.split(" = ", 1)[1].split("(")[0].split(" ")[0]
            name = name.strip('"')
            ops[name] = ops.get(name, 0) + 1
    return ops


def _deepseek_programs():
    cfg = dm.DeepSeekV3Config.nano(dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    view = jax.eval_shape(lambda k: dm.serve_view(dm.init(k, cfg), cfg),
                          jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: dm.init_paged_cache(cfg, 33, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = jax.jit(functools.partial(dm.paged_decode_step, cfg=cfg)).lower(
        view, cache, i32(4), i32(4, 8), i32(4))
    chunk = jax.jit(functools.partial(dm.paged_prefill, cfg=cfg)).lower(
        view, cache, i32(16), i32(8), i32(), i32())
    return {"step": _op_counts(step.as_text()),
            "prefill": _op_counts(chunk.as_text())}


def test_deepseek_v3_lowers_to_the_programs_it_had():
    got = _deepseek_programs()
    assert got == PARENT_OPS, got
