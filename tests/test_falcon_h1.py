"""models/falcon_h1.py against benchmarks/reference/falcon_h1_plain.py on
logits, at toy size in float32 on the CPU.  The reference draws its OWN
weights from the seed by the recipe the configuration states, walks the
SSM branch one token after the other, forms attention as explicit masked
scores and applies every multiplier where the equations put it; the
program draws its by `init`, runs the chunked SSD form and the step over
an arena, streams pages, and is served a tree with every multiplier
FOLDED into a matrix.

Tolerances: both sides compute in float32, so they differ by summation
order (and, for the folded tree, by one more float32 rounding a weight):
logits of standard deviation ~0.3 agree to ~3e-6.  TOL = 3e-5 leaves that
room and fails a state arena kept in bfloat16 (8 mantissa bits a state
element at every write), a conv tail dropped between chunks and a first
chunk that reads its entry's last holder, each of which a test says."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3_plain as dsp
from benchmarks.reference import falcon_h1_plain as ref
from ray_tpu.models import falcon_h1 as fm
from ray_tpu.models import served

TOL = 3e-5
SEED = 2147483659            # past 2**31: both draws fold it
PS, CHUNK, PAGES = 8, 16, 8
# what a cell's configuration may ask of the draw: every kind once
WEIGHTS = {"scales": {"wk": 3.0, "lm_head": 2.0, "d_skip": 0.5},
           "in_proj_scales": [2.0, 2.0, 4.0, 3.0, 1.0],
           "dt_range": [0.01, 0.1], "memory_tokens": [8, 64]}
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier", "key_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier")


def _sizes(cfg, **kw):
    out = {"eps": cfg.eps, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "d_head": cfg.d_head,
           "d_ff": cfg.d_ff, "ssm_heads": cfg.ssm_heads,
           "ssm_head_dim": cfg.ssm_head_dim, "d_state": cfg.d_state,
           "n_groups": cfg.n_groups, "theta": cfg.rope_theta,
           "multipliers": {
               "embedding": cfg.embedding_multiplier,
               "lm_head": cfg.lm_head_multiplier, "key": cfg.key_multiplier,
               "attention_in": cfg.attention_in_multiplier,
               "attention_out": cfg.attention_out_multiplier,
               "ssm_in": cfg.ssm_in_multiplier,
               "ssm_out": cfg.ssm_out_multiplier,
               "ssm": cfg.ssm_multipliers, "mlp": cfg.mlp_multipliers},
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


@pytest.fixture(scope="module")
def model():
    from benchmarks.drivers.replica_falcon_h1 import shape_weights

    cfg = fm.FalconH1Config.nano(dtype=jnp.float32, param_dtype=jnp.float32)
    plain = fm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg)
    return cfg, shape_weights(plain, WEIGHTS, SEED), plain


@pytest.fixture(scope="module")
def drawn(model):
    return ref.draw(SEED, _sizes(model[0]), WEIGHTS)


@pytest.fixture(scope="module")
def tokens(model):
    return np.random.default_rng(5).integers(0, model[0].vocab_size, 56)


@pytest.fixture(scope="module")
def want(model, drawn, tokens):
    return np.asarray(ref.logits(drawn, jnp.asarray(tokens),
                                 _sizes(model[0])))


def test_the_published_sizes():
    full = fm.FalconH1Config()
    assert (full.d_ssm, full.d_conv, sum(full.in_segments)) == (4096, 5120,
                                                                 9248)
    assert full.state_bytes == 4194304
    assert full.n_heads // full.n_kv_heads == 5
    assert fm.cache_kinds(full) == {"full": None, "ssm": "state"}
    nano = fm.FalconH1Config.nano()
    assert nano.n_heads // nano.n_kv_heads == 5
    assert ref.segments(_sizes(nano)) == nano.in_segments


@pytest.mark.parametrize("weights", [{}, WEIGHTS], ids=["plain", "shaped"])
def test_the_two_draws_agree_leaf_for_leaf(model, weights):
    """The program's `init` (+ the loader's `shape_weights`) and the
    reference's own `draw`: the same leaves bit for bit."""
    from benchmarks.drivers.replica_falcon_h1 import shape_weights

    cfg, _, plain = model
    params = shape_weights(plain, weights, SEED)
    drawn = ref.draw(SEED, _sizes(cfg), weights)
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(params[name]),
                                      np.asarray(drawn[name]), err_msg=name)
    for l, (a, b) in enumerate(zip(params["layers"], drawn["layers"])):
        assert sorted(a) == sorted(b), l
        for name in a:
            np.testing.assert_array_equal(
                np.asarray(a[name]), np.asarray(b[name]), err_msg=(l, name))
    lp = params["layers"][0]
    dt = np.log1p(np.exp(np.asarray(lp["dt_bias"])))
    memory = 1.0 / (dt * np.exp(np.asarray(lp["a_log"])))
    if weights:
        assert (dt > 0.0099).all() and (dt < 0.1001).all()
        assert (memory > 7.99).all() and (memory < 64.1).all()
    else:
        assert (dt > 0.999e-3).all() and (dt < 1.001e-1).all()


@pytest.mark.parametrize("name", ["embed", "lm_head"])
def test_a_vocabulary_table_drawn_by_slices_is_the_table(model, name):
    """The check draws a table a slice of its rows at a time (whole it is
    2.67e9 B at the published sizes): the slices, each from the pieces of
    the draw that hold its rows — here half a piece each, so every other
    one starts inside a piece —, laid end to end are the table."""
    cfg, params, _ = model
    sz = _sizes(cfg)
    parts = [ref.draw_rows(SEED, sz, WEIGHTS, name, i, 8) for i in range(8)]
    assert parts[0].shape == (cfg.vocab_size // 8, cfg.d_model)
    assert parts[0].size * 2 == dsp.DRAW_PIECE
    np.testing.assert_array_equal(np.concatenate(parts),
                                  np.asarray(params[name]))
    # the program's own slices (the replica's loader makes its tables so)
    key = jax.random.PRNGKey(SEED % (2 ** 31))
    mine = [fm.table_rows(key, cfg, name, i, 8) for i in range(8)]
    np.testing.assert_array_equal(
        np.concatenate(mine) * WEIGHTS["scales"].get(name, 1.0),
        np.asarray(params[name]))


def test_apply_is_the_reference(model, tokens, want):
    cfg, params, _ = model
    got = np.asarray(fm.apply(params, jnp.asarray(tokens)[None], cfg))[0]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1


def test_folded_multipliers_are_the_unfolded_equations(model, tokens, want):
    """`serve_view` folds every multiplier into a matrix; `apply` on the
    view (which multiplies by none) gives the unfolded equations' logits,
    and a view's view is that view."""
    cfg, params, _ = model
    view = fm.serve_view(params, cfg)
    assert "w_qkv" in view["layers"][0] and "wq" not in view["layers"][0]
    assert fm.serve_view(view, cfg) is view
    got = np.asarray(fm.apply(view, jnp.asarray(tokens)[None], cfg))[0]
    assert np.abs(got - want).max() < TOL


def _ones(cfg, name):
    if name in MULTIPLIERS:
        return dataclasses.replace(cfg, **{name: 1.0})
    kind, i = name.split(".")
    vals = list(getattr(cfg, kind))
    vals[int(i)] = 1.0
    return dataclasses.replace(cfg, **{kind: tuple(vals)})


@pytest.mark.parametrize("name", MULTIPLIERS + tuple(
    f"ssm_multipliers.{i}" for i in range(5)) + tuple(
    f"mlp_multipliers.{i}" for i in range(2)))
def test_every_multiplier_reaches_the_logits(model, tokens, want, name):
    """Each of the fourteen numbers (seven scalars, five over in_proj's
    segments, two in the MLP) set to 1: the folded tree's logits move."""
    cfg, params, _ = model
    off = _ones(cfg, name)
    got = np.asarray(fm.apply(fm.serve_view(params, off),
                              jnp.asarray(tokens)[None], off))[0]
    assert np.abs(got - want).max() > 100 * TOL, name


def _dirty_cache(cfg, entries=4):
    cache = fm.init_paged_cache(cfg, {"full": PAGES + 1, "ssm": entries}, PS)
    return jax.tree.map(lambda a: jnp.full_like(a, 1e3), cache)


def _serve(cfg, params, tokens, plen, impl="xla", state_dtype=None,
           keep_tail=True):
    """The prompt in chunks of CHUNK (the last one padded), then a step a
    token with slot 1 of three live, on an entry, pages AND a null page
    someone else dirtied.  Returns (the logits rows of positions plen-1 ..
    len(tokens)-1, the programs' stats, the cache)."""
    cfg = dataclasses.replace(cfg, ssd_impl=impl)
    view = fm.serve_view(params, cfg)
    cache = _dirty_cache(cfg)
    tabs = {"full": jnp.arange(1, PAGES + 1, dtype=jnp.int32),
            "ssm": jnp.asarray([2], jnp.int32)}
    pre = jax.jit(lambda c, *a: fm.paged_prefill(view, c, *a, cfg=cfg))
    step = jax.jit(lambda c, *a: fm.paged_decode_step(view, c, *a, cfg))

    def rounded(c):
        if state_dtype is not None:
            c = dict(c, state=c["state"].astype(state_dtype).astype(
                jnp.float32))
        if not keep_tail:
            c = dict(c, tail=jnp.zeros_like(c["tail"]))
        return c

    stats = []
    for start in range(0, plen, CHUNK):
        n = min(CHUNK, plen - start)
        chunk = np.zeros(CHUNK, np.int32)
        chunk[:n] = tokens[start:start + n]
        lg, cache, st = pre(cache, jnp.asarray(chunk), tabs,
                            jnp.int32(start), jnp.int32(n - 1))
        cache = rounded(cache)
        stats.append(np.asarray(st))
    rows = [np.asarray(lg)]
    for p in range(plen, len(tokens)):
        tb = {k: jnp.stack([jnp.zeros_like(v), v, jnp.zeros_like(v)])
              for k, v in tabs.items()}
        lg, cache, st = step(cache, jnp.asarray([0, int(tokens[p]), 0]), tb,
                             jnp.asarray([0, p, 0], jnp.int32))
        cache = rounded(cache)
        stats.append(np.asarray(st))
        rows.append(np.asarray(lg[1]))
    return np.stack(rows), stats, cache


# one chunk; several with a ragged last; exactly a multiple of the chunk
@pytest.mark.parametrize("plen", [11, 37, 32])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_chunks_and_steps_through_pages_and_entries_are_the_reference(
        model, tokens, want, plen, impl):
    cfg, params, _ = model
    got, stats, cache = _serve(cfg, params, tokens, plen, impl)
    assert np.abs(got - want[plen - 1:]).max() < TOL
    # a chunk moves one state a layer; a step its live slots' (the kernel)
    # or every slot's (gather / scatter); the bytes are those over layers
    n_chunks = -(-plen // CHUNK)
    live = 1 if impl != "xla" else 3
    names = fm.STEP_STATS
    for i, st in enumerate(stats):
        moved = 1 if i < n_chunks else live
        assert st[names.index("ssd_live")] == moved
        assert st[names.index("ssd_state_bytes")] == (
            moved * cfg.n_layers * cfg.state_bytes)
    assert stats[-1][names.index("kv_positions")] == len(tokens)
    # entry 2 and pages 1.. were written; the null entry, the other
    # entries and the null page are as the last holder left them
    state = np.asarray(cache["state"])
    assert (state[:, [0, 1, 3]] == 1e3).all()
    assert not (state[:, 2] == 1e3).any()
    assert (np.asarray(cache["tail"])[:, [0, 1, 3]] == 1e3).all()


def test_what_must_fail_fails(model, tokens, want):
    """The tolerance is tight enough for the faults it is there to catch:
    a state arena rounded to bfloat16 at every write, a conv tail dropped
    between chunks and steps (the next test: a first chunk that reads what
    its entry's last holder left)."""
    cfg, params, _ = model
    err = lambda **kw: np.abs(_serve(cfg, params, tokens, 37, **kw)[0]
                              - want[36:]).max()
    assert err() < TOL
    assert err(state_dtype=jnp.bfloat16) > 10 * TOL
    assert err(keep_tail=False) > 100 * TOL


def test_a_first_chunk_starts_from_zeros(model, tokens):
    """`start == 0` is what empties an entry: the same prompt through an
    entry holding zeros and one holding 1e3 gives the same logits."""
    cfg, params, _ = model
    view = fm.serve_view(params, cfg)
    tabs = {"full": jnp.arange(1, PAGES + 1, dtype=jnp.int32),
            "ssm": jnp.asarray([2], jnp.int32)}
    chunk = jnp.asarray(tokens[:CHUNK])
    run = lambda c, start: fm.paged_prefill(
        view, c, chunk, tabs, jnp.int32(start), jnp.int32(CHUNK - 1), cfg)[0]
    clean = fm.init_paged_cache(cfg, {"full": PAGES + 1, "ssm": 4}, PS)
    a, b = run(clean, 0), run(_dirty_cache(cfg), 0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL)
    # told it is a LATER chunk, the dirt reaches the logits
    assert not np.allclose(np.asarray(run(clean, CHUNK)),
                           np.asarray(run(_dirty_cache(cfg), CHUNK)),
                           atol=100 * TOL)
