"""`ops/moe.held_expert_ffn` against a plain per-pair loop: each pair through
its expert's SwiGLU in float64, weighted, summed per token.  What the cases
hold beside the sum: `loads` are the per-expert counts, and `reads` — an
expert counted once for each trip of grouped products that holds a row of
it — equal the experts touched unless an expert has more rows than a
product takes (M = min(tile, N*k, ROW_BLOCK)).  A trip ends where an expert
ends, so an expert of more than M rows always STARTS a trip and is visited
ceil(rows / M) times wherever its first row falls (trips cut at fixed
offsets would visit an expert of 300 rows 3 or 4 times under a block of
128): reads = sum of ceil(load / M) over the held experts, in every case.
Every case runs in both layouts of the gate and up matrices — apart (three
products a trip) and side by side in one leaf [held, D, 2F] with no up
operand (two) — and the three models that lay the leaf hold the recipe's
`wg` and `wu` in it bit for bit.
Since PR 64 what a layer does ONCE before its trips is one sort that
carries each pair's token and weight and a count by comparison: the lowered
text holds that form (no gather or scatter outside the trips' loop, one of
each inside), and what is not finite in a row of h or in a token's weights
stays with that token."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from held_leaf import apart
from ray_tpu.ops import moe
from ray_tpu.ops.moe import held_expert_ffn, held_load_stats

D, F = 16, 8


def _plain(h, w, idx, wg, wu, wd, first, live):
    h, w, wg, wu, wd = (np.asarray(a, np.float64) for a in (h, w, wg, wu, wd))
    idx = np.asarray(idx)
    count = wg.shape[0]
    out = np.zeros_like(h)
    loads = np.zeros(count, np.int64)
    for n in range(h.shape[0]):
        if live is not None and not live[n]:
            continue
        for j in range(idx.shape[1]):
            e = idx[n, j] - first
            if not 0 <= e < count:
                continue
            g, u = h[n] @ wg[e], h[n] @ wu[e]
            out[n] += w[n, j] * ((g / (1 + np.exp(-g)) * u) @ wd[e])
            loads[e] += 1
    return out, loads


def _drawn(seed, N, k, held):
    """h [N, D], weights [N, k], gate, up [held, D, F], down [held, F, D]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (N, D), jnp.float32),
            jax.random.uniform(ks[1], (N, k), jnp.float32, 0.2, 1.0),
            jax.random.normal(ks[2], (held, D, F), jnp.float32) * D ** -0.5,
            jax.random.normal(ks[3], (held, D, F), jnp.float32) * D ** -0.5,
            jax.random.normal(ks[4], (held, F, D), jnp.float32) * F ** -0.5)


def _routed(N, k, E, seed):
    """Uniform top-k without replacement: [N, k] expert ids."""
    scores = np.random.default_rng(seed).random((N, E))
    return np.argsort(scores, axis=1)[:, :k].astype(np.int32)


def _by_loads(loads, absent_from, N, k):
    """[N, k] ids that give held expert e exactly loads[e] pairs (a token
    names an expert at most once); every other pair names an absent one."""
    idx = np.full((N, k), absent_from, np.int32)
    idx += np.arange(k, dtype=np.int32)[None]      # distinct absent experts
    col = np.zeros(N, np.int64)
    for e, n in enumerate(loads):
        rows = np.argsort(col, kind="stable")[:n]  # the emptiest tokens
        assert n <= N and col[rows].max(initial=0) < k
        idx[rows, col[rows]] = e
        col[rows] += 1
    return idx


B = moe.ROW_BLOCK
# name: (N, k, held, E, first, tile, idx (None: uniform), live rows
#        (None: all), reads pinned by hand (None: the experts touched))
CASES = {
    "pairs_below_the_block": (B // 16, 4, 4, 8, 0, 512, None, None, None),
    "pairs_equal_the_block": (B // 4, 4, 4, 8, 0, 512, None, None, None),
    "pairs_above_the_block": (B, 4, 8, 16, 0, 512, None, None, None),
    # trips: [0,50) e0 | [50,178) e1 | [178,306) e1 | [306,370) e1's last
    # 44 rows and e2's 20: e1 is read 3 times, five visits in all
    "an_expert_of_more_rows_than_a_block": (
        400, 2, 4, 8, 0, 512, _by_loads([50, 300, 20, 0], 4, 400, 2), None,
        5),
    # e0's 300 rows take [0,128) [128,256) [256,300): the third trip ends
    # where e0 ends, so e1's 200 start a trip too: [300,428) [428,500)+e2
    "two_such_experts_in_a_row": (
        400, 2, 4, 8, 0, 512, _by_loads([300, 200, 10, 0], 4, 400, 2), None,
        3 + 2 + 1),
    "every_pair_on_absent_experts": (
        24, 4, 4, 12, 0, 512, 4 + _routed(24, 4, 8, 1), None, 0),
    "live_takes_rows_out": (48, 4, 8, 16, 0, 512, None,
                            np.arange(48) % 3 != 1, None),
    # M = 16: experts of 17+ rows are read twice or more
    "tile_below_the_block_binds": (
        40, 4, 4, 8, 0, 16, _by_loads([40, 17, 16, 3], 4, 40, 4), None,
        3 + 2 + 1 + 1),
    "first_above_zero": (B // 2, 4, 4, 12, 4, 512, None, None, None),
    # M = 16: trips [0,16) [16,32) [32,48): e0's last 8 rows and e1's 8 |
    # [48,64) [64,70): e2's 22 rows start a trip and the last takes 6
    "the_last_trip_is_short": (
        40, 4, 4, 8, 0, 16, _by_loads([40, 8, 22, 0], 4, 40, 4), None,
        3 + 1 + 2),
    # M = 16: e0 ends a trip at 40, so e1's 50 rows START one: [40,56)
    # [56,72) [72,88) and [88,95) with e2's 5 — four visits = ceil(50 / 16)
    # wherever its first row falls; e3's 20 take [95,111) [111,115)
    "an_expert_of_four_trips_starts_where_the_last_ended": (
        64, 4, 4, 8, 0, 16, _by_loads([40, 50, 5, 20], 4, 64, 4), None,
        3 + 4 + 1 + 2),
}


@pytest.mark.parametrize("form", ["apart", "one_leaf"])
@pytest.mark.parametrize("name", list(CASES))
def test_held_products_match_the_per_pair_loop(name, form):
    N, k, held, E, first, tile, idx, live, reads_by_hand = CASES[name]
    M = min(tile, N * k, B)
    h, w, wg, wu, wd = _drawn(len(name), N, k, held)
    if idx is None:
        idx = _routed(N, k, E, seed=N + first)
    out, loads, reads = jax.jit(
        lambda *a: held_expert_ffn(
            *a, first=first, tile=tile,
            live=None if live is None else jnp.asarray(live)))(
                h, w, jnp.asarray(idx),
                *((wg, wu) if form == "apart"
                  else (jnp.concatenate([wg, wu], axis=-1), None)), wd)
    want, want_loads = _plain(h, w, idx, wg, wu, wd, first, live)
    assert out.shape == (N, D) and out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(loads), want_loads)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    touched = int((want_loads > 0).sum())
    if reads_by_hand is None:
        assert want_loads.max() <= M, "the case has an expert past a product"
        reads_by_hand = touched
    assert int(reads) == reads_by_hand == int(np.ceil(want_loads / M).sum())
    if name == "every_pair_on_absent_experts":
        assert not np.asarray(out).any() and touched == 0
    else:
        assert touched > 0
    # what a program reports of one such layer
    stats = [float(s) for s in held_load_stats([(loads, reads)])]
    assert stats == [want_loads.sum(), want_loads.max(), touched,
                     reads_by_hand]


@pytest.mark.parametrize("bad", [np.nan, np.inf])
@pytest.mark.parametrize("where", ["a_row_of_h", "a_tokens_weights"])
def test_what_is_not_finite_stays_in_its_own_row(where, bad):
    """Rows reach the products by a gather and leave by a scatter-add: a
    value that is not finite in one row of h, or in one token's weights,
    is lost with that token, and every other token's sum is what it would
    be without it; the counts are the routing's.  (Not held here: ONE
    expert's matrices.  The chip's grouped product keeps an expert's rows
    to that expert's matrices; the CPU's lowering of `ragged_dot` masks a
    dense product, where 0 * nan reaches the other experts' rows.)"""
    N, k, held, E = 24, 4, 4, 8
    h, w, wg, wu, wd = _drawn(3, N, k, held)
    idx = _routed(N, k, E, seed=5)
    assert (idx[7] < held).any()
    h_bad, w_bad = ((h.at[7, 3].set(bad), w) if where == "a_row_of_h"
                    else (h, w.at[7].set(bad)))
    out, loads, reads = jax.jit(lambda *a: held_expert_ffn(
        *a, first=0, tile=512))(h_bad, w_bad, jnp.asarray(idx), wg, wu, wd)
    want, want_loads = _plain(h, w, idx, wg, wu, wd, 0, None)
    out = np.asarray(out)
    others = np.arange(N) != 7
    assert not np.isfinite(out[7]).all()
    np.testing.assert_allclose(out[others], want[others],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(loads), want_loads)
    assert int(reads) == int((want_loads > 0).sum())


def _loops(text):
    """[(the `stablehlo.while` line, its body's lines)] of a lowered
    module: the printer indents a region's `} do {` and closing `}` as far
    as the loop's own line."""
    lines = text.splitlines()
    found = []
    for i, ln in enumerate(lines):
        if " = stablehlo.while(" not in ln:
            continue
        pad = ln[:len(ln) - len(ln.lstrip())]
        do = lines.index(pad + "} do {", i)
        found.append((i, ln, lines[do + 1:lines.index(pad + "}", do)]))
    return found


@pytest.mark.parametrize("form", ["apart", "one_leaf"])
def test_what_runs_once_a_layer_and_what_runs_a_trip(form):
    """From the text lowered for the chip (where a grouped product stays
    ONE op): before the trips' loop ONE sort of three operands (the key,
    the pair's token, its weight) and no gather or scatter — the sorted
    weights come with the sort, the loads from a comparison; the loop
    carries the [N, D] f32 sum and a trip holds its products, ONE gather
    (h's rows) and ONE scatter (the sum)."""
    N, k, held, D_, F_ = 40, 4, 4, 24, 8
    shapes = [(N, D_), (N, k), (N, k)] + (
        [(held, D_, F_), (held, D_, F_)] if form == "apart"
        else [(held, D_, 2 * F_)]) + [(held, F_, D_)]
    args = [jax.ShapeDtypeStruct(s, jnp.int32 if i == 2 else jnp.float32)
            for i, s in enumerate(shapes)]
    text = jax.jit(lambda h, w, idx, *ws: held_expert_ffn(
        h, w, idx, ws[0], ws[1] if form == "apart" else None, ws[-1],
        first=0, tile=16)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    (at, trips, body), = _loops(text)
    assert f"tensor<{N}x{D_}xf32>" in trips.split(" : ")[-1]
    before = "\n".join(text.splitlines()[:at])
    assert not re.search(r"stablehlo\.\w*(gather|scatter)", before)
    sorts = re.findall(r'"stablehlo\.sort"\(([^)]*)\)', before)
    assert len(sorts) == 1 and sorts[0].count("%") == 3, sorts
    inner = "\n".join(body)
    assert inner.count('"chlo.ragged_dot"(') == (3 if form == "apart" else 2)
    assert len(re.findall(r'"stablehlo\.gather"\(', inner)) == 1
    assert len(re.findall(r'"stablehlo\.scatter"\(', inner)) == 1


@pytest.mark.parametrize("model,config", [("deepseek_v3", "DeepSeekV3Config"),
                                          ("ling3", "Ling3Config"),
                                          ("dots3", "Dots3Config")])
def test_a_models_leaf_holds_the_recipes_gate_and_up(model, config):
    """`init`'s `wgu` [held, D, 2F] is, bit for bit, the `wg` the recipe
    draws at its place beside the `wu` at its own (the benchmark's
    reference draws the two apart, by the same recipe), and the tree
    holds neither apart; the serve view casts the leaf like any other."""
    import importlib
    import math

    mod = importlib.import_module("ray_tpu.models." + model)
    cfg = getattr(mod, config).nano(param_dtype=jnp.float32)
    key = jax.random.PRNGKey(11)
    C, Dm, Fe = cfg.experts_held, cfg.d_model, cfg.d_expert
    params = mod.init(key, cfg)
    layers, view = params["layers"], mod.serve_view(params, cfg)["layers"]
    assert cfg.param_dtype != cfg.dtype
    routed = [l for l, layer in enumerate(layers) if "router" in layer]
    assert routed and "wgu" not in layers[0]
    for l in routed:
        layer = layers[l]
        assert "wg" not in layer and "wu" not in layer
        assert layer["wgu"].shape == (C, Dm, 2 * Fe)
        assert layer["wd"].shape == (C, Fe, Dm)
        for name in ("wg", "wu"):
            want = mod._draw(key, l, mod.LEAVES.index(name), (C, Dm, Fe),
                             1.0 / math.sqrt(Dm), cfg.param_dtype)
            np.testing.assert_array_equal(np.asarray(apart(layer)[name]),
                                          np.asarray(want), err_msg=(l, name))
        assert view[l]["wgu"].dtype == jnp.dtype(cfg.dtype)


def test_load_stats_of_a_program_without_expert_layers():
    assert [float(s) for s in held_load_stats([])] == [0.0] * 4
