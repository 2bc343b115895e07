"""models/phi4flash.py against benchmarks/reference/phi4flash_plain.py on
logits, at toy size in float32 on the CPU.  The reference draws its OWN
weights from the seed by the recipe the configuration states, walks a
Mamba layer one token after the other and forms differential attention as
two explicit softmaxes over masked scores; the program draws its by `init`
and runs the chunked scan, the step, and ONE streamed pass over paired
heads: the first tests hold the plan and the two draws leaf for leaf, the
others the arithmetic.

Tolerances: both sides compute in float32, so they differ by summation
order alone: logits of standard deviation ~0.16 agree to ~2e-6.  TOL =
2e-5 leaves that room and fails a state arena kept in bfloat16 (8
mantissa bits a state element at every write: ~1e-3 on a logit), a conv
tail dropped between chunks, and a second softmax left out, each of which
a test says."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3_plain as dsp
from benchmarks.reference import phi4flash_plain as ref
from ray_tpu.models import phi4flash as pm
from ray_tpu.models import served

TOL = 2e-5
SEED = 2147483659            # past 2**31: both draws fold it
PS, CHUNK = 8, 16
FULL_PAGES, RING = 16, 4     # 128 positions; (window 8 + chunk 16) / 8 + 1


def _sizes(cfg, **kw):
    out = {"eps": cfg.ln_eps, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "d_head": cfg.d_head,
           "d_ff": cfg.d_ff, "window": cfg.sliding_window,
           "mb_per_layer": cfg.mb_per_layer, "d_state": cfg.d_state,
           "d_conv": pm.CONV_TAPS, "expand": cfg.expand,
           "dt_rank": cfg.dt_rank, "n_layers": cfg.n_layers,
           "vocab": cfg.vocab_size, "param_dtype": "float32"}
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
    cfg = pm.Phi4FlashConfig.nano(dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, pm.init(jax.random.PRNGKey(SEED % (2 ** 31)), cfg)


@pytest.fixture(scope="module")
def drawn(model):
    return ref.draw(SEED, _sizes(model[0]))


@pytest.fixture(scope="module")
def tokens(model):
    return np.random.default_rng(5).integers(0, model[0].vocab_size, 56)


@pytest.fixture(scope="module")
def want(model, drawn, tokens):
    return np.asarray(ref.logits(drawn, jnp.asarray(tokens),
                                 _sizes(model[0])))


def test_the_plan_is_data(model):
    cfg = model[0]
    assert cfg.plan == ["mamba", "swa", "mamba", "swa", "mamba", "full",
                        "gmu", "cross"]
    assert [ref.kind(_sizes(cfg), l) for l in range(8)] == cfg.plan
    full = pm.Phi4FlashConfig()
    assert full.layers_of("mamba") == list(range(0, 17, 2))
    assert full.layers_of("swa") == list(range(1, 16, 2))
    assert full.layers_of("full") == [17] and full.memory_layer == 16
    assert full.layers_of("gmu") == list(range(18, 31, 2))
    assert full.layers_of("cross") == list(range(19, 32, 2))
    assert (full.d_inner, full.dt_rank, full.d_state) == (5120, 160, 16)
    assert pm.cache_kinds(full) == {"full": None, "swa": 512,
                                    "mamba": "state"}
    assert ["wq" in l for l in model[1]["layers"]] == [False] * 7 + [True]
    assert abs(full.lam0(17) - float(ref.lam0(17))) < 1e-6


def test_the_two_draws_agree_leaf_for_leaf(model, drawn):
    """The program's `init` and the reference's own `draw`: the same
    leaves bit for bit; A is -(1..N) along the states on both sides (the
    program keeps its log, states along the FIRST axis), the step sizes
    log-uniform in [1e-3, 1e-1], D ones."""
    cfg, params = model
    for name in ("embed", "final_norm", "final_norm_b"):
        np.testing.assert_array_equal(np.asarray(params[name]),
                                      np.asarray(drawn[name]), err_msg=name)
    for l, (a, b) in enumerate(zip(params["layers"], drawn["layers"])):
        assert sorted(set(a) - {"a_log"}) == sorted(set(b) - {"a"}), l
        for name in set(a) - {"a_log"}:
            np.testing.assert_array_equal(
                np.asarray(a[name]), np.asarray(b[name]), err_msg=(l, name))
    mamba = params["layers"][0]
    np.testing.assert_allclose(-np.exp(np.asarray(mamba["a_log"])).T,
                               np.asarray(drawn["layers"][0]["a"]), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(mamba["b_dt"])))
    assert (dt > 0.999e-3).all() and (dt < 1.001e-1).all()
    assert np.ptp(np.log(dt)) > 3
    lam = np.asarray(params["layers"][1]["lam_q1"])
    assert 0.03 < lam.std() < 0.3


def test_apply_is_the_reference(model, tokens, want):
    cfg, params = model
    got = np.asarray(pm.apply(params, jnp.asarray(tokens)[None], cfg))[0]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1


def _ring(lo, hi, window):
    """The engine's ring over pages 1..RING as it stands while positions
    lo..hi-1 are written: logical page lp in entry lp % RING."""
    r = np.zeros(RING, np.int32)
    for lp in range(max(0, lo - window) // PS, (hi - 1) // PS + 1):
        r[lp % RING] = 1 + lp % RING
    return r


def _dirty_cache(cfg, entries=4):
    cache = pm.init_paged_cache(
        cfg, {"full": FULL_PAGES + 1, "swa": RING + 1, "mamba": entries}, PS)
    return jax.tree.map(lambda a: jnp.full_like(a, 1e3), cache)


def _serve(cfg, params, tokens, plen, impl="xla", state_dtype=None,
           keep_tail=True):
    """The prompt in chunks of CHUNK (the last one padded), then a step a
    token with slot 1 of three live, on an entry, pages AND null pages
    someone else dirtied.  Returns (the logits rows of positions plen-1 ..
    len(tokens)-1, the chunks' stats, the cache)."""
    cfg = dataclasses.replace(cfg, mamba_impl=impl)
    view = pm.serve_view(params, cfg)
    cache = _dirty_cache(cfg)
    w = cfg.sliding_window
    full = np.arange(1, FULL_PAGES + 1, dtype=np.int32)
    tabs = lambda lo, hi: {"full": jnp.asarray(full),
                           "swa": jnp.asarray(_ring(lo, hi, w)),
                           "mamba": jnp.asarray([2], jnp.int32)}
    pre = jax.jit(lambda c, *a: pm.paged_prefill(view, c, *a, cfg=cfg))
    step = jax.jit(lambda c, *a: pm.paged_decode_step(view, c, *a, cfg))

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
        lg, cache, st = pre(cache, jnp.asarray(chunk), tabs(start, start + n),
                            jnp.int32(start), jnp.int32(n - 1),
                            jnp.bool_(start + n == plen))
        cache = rounded(cache)
        stats.append(np.asarray(st))
    rows = [np.asarray(lg)]
    for p in range(plen, len(tokens)):
        tb = {k: jnp.stack([jnp.zeros_like(v), v, jnp.zeros_like(v)])
              for k, v in tabs(p, p + 1).items()}
        lg, cache, st = step(cache, jnp.asarray([0, int(tokens[p]), 0]), tb,
                             jnp.asarray([0, p, 0], jnp.int32))
        cache = rounded(cache)
        rows.append(np.asarray(lg[1]))
    return np.stack(rows), stats, cache


# one chunk; several with a ragged last; exactly a multiple of the chunk
@pytest.mark.parametrize("plen", [11, 37, 32])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_chunks_and_steps_through_the_three_kinds_are_the_reference(
        model, tokens, want, plen, impl):
    cfg, params = model
    got, stats, cache = _serve(cfg, params, tokens, plen, impl)
    assert np.abs(got - want[plen - 1:]).max() < TOL
    # the cross-decoder and the head ran on a last chunk's ONE row and on
    # no other chunk; a chunk is one state a Mamba layer; the shared cache
    # holds the prompt's positions so far
    n = -(-plen // CHUNK)
    assert [s[2] for s in stats] == [0.0] * (n - 1) + [1.0]
    assert [s[0] for s in stats] == [1.0] * n
    assert [s[1] for s in stats] == [min(plen, (i + 1) * CHUNK)
                                     for i in range(n)]
    # the null entry and the other entries are as they were left
    for e in (0, 1, 3):
        assert (np.asarray(cache["state"][:, e]) == 1e3).all()


def test_steps_that_walk_their_pages_as_on_the_chip_are_the_reference(
        model, tokens, want, monkeypatch):
    """On a TPU a one-row read of pages (a step's eight of the shared
    cache and one a windowed layer's ring, a last chunk's seven) walks
    each slot's own pages with
    `paged_decode_attention`: here the kernel in interpret mode, the two
    names the model calls patched."""
    import functools

    from ray_tpu.ops.attention import paged_decode_attention

    calls = []
    monkeypatch.setattr(pm, "latent_decode_uses_kernel",
                        lambda rows, platform=None: rows == 1)
    monkeypatch.setattr(
        pm, "paged_decode_attention",
        lambda *a, **kw: calls.append(1) or paged_decode_attention(
            *a, interpret=True, **kw))
    cfg, params = model
    got, _, _ = _serve(cfg, params, tokens, 37)
    assert np.abs(got - want[36:]).max() < TOL
    # traced once a program: the last chunk's cross layer, then the step's
    # two windowed layers (their rings), its full layer and its cross layer
    assert len(calls) == 1 + 4


def test_a_step_counts_what_it_moved(model, tokens):
    cfg, params = model
    view = pm.serve_view(params, cfg)
    cache = pm.init_paged_cache(cfg, {"full": 17, "swa": 5, "mamba": 4}, PS)
    tabs = {"full": jnp.zeros((3, 16), jnp.int32),
            "swa": jnp.zeros((3, RING), jnp.int32),
            "mamba": jnp.asarray([[1], [0], [2]], jnp.int32)}
    pos = jnp.asarray([5, 0, 9], jnp.int32)
    for impl, moved in (("xla", 3.0), ("pallas_interpret", 2.0)):
        c = dataclasses.replace(cfg, mamba_impl=impl)
        _, _, st = pm.paged_decode_step(view, cache, jnp.zeros(3, jnp.int32),
                                        tabs, pos, c)
        assert list(np.asarray(st)) == [moved, 6.0 + 10.0, 2.0]


def test_a_bfloat16_state_fails_the_tolerance(model, tokens, want):
    cfg, params = model
    got, _, _ = _serve(cfg, params, tokens, 37, state_dtype=jnp.bfloat16)
    assert np.abs(got - want[36:]).max() > 5 * TOL


def test_a_dropped_tail_fails_the_tolerance(model, tokens, want):
    cfg, params = model
    got, _, _ = _serve(cfg, params, tokens, 37, keep_tail=False)
    assert np.abs(got - want[36:]).max() > 100 * TOL


def test_the_second_softmax_left_out_fails_the_tolerance(
        model, tokens, want, monkeypatch):
    cfg, params = model
    monkeypatch.setattr(pm, "_lambda", lambda l, layer, cfg: 0.0)
    got = np.asarray(pm.apply(params, jnp.asarray(tokens)[None], cfg))[0]
    assert np.abs(got - want).max() > 100 * TOL


@pytest.mark.parametrize("kind,l", [("swa", 1), ("full", 5)])
def test_differential_attention_is_two_explicit_softmaxes(model, drawn,
                                                          kind, l):
    """ONE layer alone, its input random rows: through `streamed_attention`
    over the sequence's own rows (a chunk), and a row at a time through
    pages (a step: the ring where the layer is windowed, 30 positions
    against a window of 8) = the reference's two softmaxes over masked
    scores, their difference normed; with lam forced to 0 it is not."""
    cfg, params = model
    sz, S = _sizes(cfg), 30
    layer, lp = params["layers"][l], drawn["layers"][l]
    x = jnp.asarray(np.random.default_rng(l).standard_normal(
        (S, cfg.d_model)), jnp.float32)
    k, v = ref.keys_values(x, lp, sz)
    want = np.asarray(ref.attend(x, k, v, lp, sz, l,
                                 cfg.sliding_window if kind == "swa"
                                 else None))
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    h = pm._normed(x[None], layer, "attn_norm", cfg)

    def chunk(q, k, v):
        kv = pm._heads_first(k, cfg), pm._heads_first(v, cfg)
        return pm.streamed_attention(
            q, pos, lambda i: (*kv, pos), 1, window=pm._window(kind, cfg),
            scale=cfg.d_head ** -0.5)

    got = np.asarray(pm._attention(x[None], h, l, layer, chunk, cfg))[0]
    assert np.abs(got - want).max() < TOL
    # a row at a time through pages
    pages = FULL_PAGES if kind == "full" else RING
    arena = {n: jnp.full((pages + 1, PS, cfg.n_kv_heads * cfg.d_head), 1e3)
             for n in ("k", "v")}
    rows = []
    for t in range(S):
        tab = (np.arange(1, pages + 1, dtype=np.int32) if kind == "full"
               else _ring(t, t + 1, cfg.sliding_window))
        at = jnp.full((1, 1), t, jnp.int32)
        io = pm._tables({"full": jnp.asarray(tab)[None],
                         "swa": jnp.asarray(tab)[None]}, at,
                        jnp.ones((1, 1), bool), PS, cfg)[kind]
        attend, box = pm._paged_attend(kind, arena, io, at, cfg)
        rows.append(np.asarray(pm._attention(
            x[None, t:t + 1], h[:, t:t + 1], l, layer, attend, cfg))[0, 0])
        arena = box["arena"]
    assert np.abs(np.stack(rows) - want).max() < TOL
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pm, "_lambda", lambda l, layer, cfg: 0.0)
        off = np.asarray(pm._attention(x[None], h, l, layer, chunk, cfg))[0]
    assert np.abs(off - want).max() > 100 * TOL


def test_a_chunk_that_is_not_the_last_touches_no_cross_layer(model):
    """The chunk program's jaxpr: every leaf of layers past the
    self-decoder, and the final norm, is an operand of ONE equation — the
    `cond` on `is_last` — and of nothing else; its other branch holds no
    product at all.  (The embedding is read by the self-decoder too: it
    is the head's table only inside that branch.)"""
    cfg, params = model
    view = pm.serve_view(params, cfg)
    cache = pm.init_paged_cache(cfg, {"full": 17, "swa": 5, "mamba": 4}, PS)
    tabs = {"full": jnp.zeros(16, jnp.int32), "swa": jnp.zeros(RING, jnp.int32),
            "mamba": jnp.ones(1, jnp.int32)}
    jaxpr = jax.make_jaxpr(
        lambda p, c, last: pm.paged_prefill(
            p, c, jnp.zeros(CHUNK, jnp.int32), tabs, jnp.int32(0),
            jnp.int32(3), last, cfg))(view, cache, jnp.bool_(False)).jaxpr
    flat, _ = jax.tree_util.tree_flatten_with_path(view)
    first_cross = cfg.memory_layer + 2
    late = set()
    for (path, _), var in zip(flat, jaxpr.invars):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        if keys[0] in ("final_norm", "final_norm_b") or (
                keys[0] == "layers" and keys[1] >= first_cross):
            late.add(var)
    assert len(late) > 10
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    for e in jaxpr.eqns:
        if e is not conds[0]:
            assert not late & {v for v in e.invars
                               if not hasattr(v, "val")}, e.primitive
    assert late <= {v for v in conds[0].invars if not hasattr(v, "val")}
    names = [[q.primitive.name for q in b.jaxpr.eqns]
             for b in conds[0].params["branches"]]
    assert "dot_general" not in names[0] and "dot_general" in names[1]
