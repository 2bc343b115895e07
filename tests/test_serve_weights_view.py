"""The serve engine reads a derived view of the parameters
(models/gpt.py serve_view, models/cohere2_moe.py serve_view): the leaves
the serve programs cast with `.astype(cfg.dtype)` at every use, cast
once at set-up.

In-process and on the CPU, `param_dtype` float32 / `dtype` bfloat16 as
the benchmark's `gpt2-large`.  The view's operands are the values the
programs computed for themselves from the float32 tree, so the
comparisons are equality of bits, not tolerances.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import cohere2_moe as cm
from ray_tpu.models import gpt
from ray_tpu.serve._engine import ContinuousEngine

PS, MAXP, NUM_PAGES, SLOTS = 8, 8, 24, 3
S = PS * MAXP                                  # 64 = max_total = max_seq
# page tables scattered through the arena; page 0 is the null page
TABS = np.array([[5, 9, 2, 17, 11, 20, 3, 14],
                 [7, 1, 0, 0, 0, 0, 0, 0],
                 [4, 0, 0, 0, 0, 0, 0, 0]], np.int32)

RECIPES = {
    "learned_ln_gelu_biases_tied": dict(attn_bias=True),
    "learned_ln_gelu_biases_untied": dict(attn_bias=True,
                                          tie_embeddings=False),
    "rope_rms_swiglu_tied": dict(pos="rope", norm="rms", act="swiglu"),
    "rope_rms_swiglu_untied": dict(pos="rope", norm="rms", act="swiglu",
                                   tie_embeddings=False),
}
NORMS = {"attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b",
         "final_norm", "final_norm_b"}


def _model(**kw):
    """f32 parameters served in bf16, no leaf left at init's exact ones
    and zeros (a bias of 0 casts to 0 whatever the cast does)."""
    cfg = gpt.GPTConfig.nano(max_seq=S, **kw)
    assert cfg.param_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [w + 0.05 * jax.random.normal(k, w.shape, w.dtype)
              for w, k in zip(leaves, keys)]
    return cfg, jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(scope="module", params=list(RECIPES))
def model(request):
    return _model(**RECIPES[request.param])


def _named(tree):
    return {jax.tree_util.keystr(p): w for p, w in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _arena(cfg, seed=3):
    """A bf16 arena of stale rows: whatever the masks let through shows."""
    shape = (cfg.n_layers, NUM_PAGES, PS, cfg.n_heads * cfg.d_head)
    rng = np.random.default_rng(seed)
    return {s: jnp.asarray(rng.normal(size=shape), cfg.dtype)
            for s in ("k", "v")}


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]))


def _same_bits(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(_bits(a), _bits(b))


_step = jax.jit(gpt.paged_decode_step, static_argnames="cfg")
_prefill = jax.jit(gpt.paged_prefill, static_argnames="cfg")


def _run_prefill(params, cfg):
    # 5 real tokens padded to a bucket of 8, behind 19 positions of
    # prefix-shared pages: the chunk starts inside page 2 of the table
    toks = np.zeros(8, np.int32)
    toks[:5] = np.random.default_rng(1).integers(1, 250, 5)
    return _prefill(params, _arena(cfg), jnp.asarray(toks),
                    jnp.asarray(TABS[0]), jnp.int32(19), jnp.int32(4),
                    cfg=cfg)


def _run_step(params, cfg):
    # three slots at positions of their own, none on a page boundary,
    # the furthest in the table's last page
    return _step(params, _arena(cfg), jnp.asarray([17, 201, 4], jnp.int32),
                 jnp.asarray(TABS), jnp.asarray([61, 11, 3], jnp.int32),
                 cfg=cfg)


@pytest.mark.parametrize("program", ["padded_prefill", "unaligned_step"])
def test_view_gives_the_f32_trees_bits(model, program):
    cfg, params = model
    run = {"padded_prefill": _run_prefill, "unaligned_step": _run_step}
    view = gpt.serve_view(params, cfg)
    want_logits, want_cache = run[program](params, cfg)
    got_logits, got_cache = run[program](view, cfg)
    assert np.isfinite(np.asarray(want_logits, np.float32)).all()
    _same_bits(got_logits, want_logits)
    _same_bits(got_cache, want_cache)
    # the comparison has teeth: the weights do not survive the cast
    assert not np.array_equal(np.asarray(params["layers"]["wq"]),
                              np.asarray(view["layers"]["wq"], np.float32))


def test_view_casts_what_the_programs_cast_and_keeps_the_norms(model):
    cfg, params = model
    before, after = _named(params), _named(gpt.serve_view(params, cfg))
    assert before.keys() == after.keys()
    for name, w in before.items():
        if name.split("'")[-2] in NORMS:
            assert after[name] is w, name
        else:
            assert after[name].dtype == jnp.bfloat16, name
            _same_bits(after[name], w.astype(jnp.bfloat16))


def _kept_model(name):
    """(module, cfg, params) whose cast leaves are already in cfg.dtype:
    GPT-2 kept whole in bf16, or Command A+ as published (bf16 with an
    f32 router)."""
    if name == "gpt":
        mod, cfg = gpt, gpt.GPTConfig.nano(max_seq=S, attn_bias=True,
                                           param_dtype=jnp.bfloat16)
    else:
        mod, cfg = cm, cm.Cohere2MoEConfig.nano()
    return mod, cfg, mod.init(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("name", ["gpt", "cohere2_moe"])
def test_tree_in_compute_dtype_comes_back_as_the_same_arrays(name):
    mod, cfg, params = _kept_model(name)
    before, after = _named(params), _named(mod.serve_view(params, cfg))
    assert before.keys() == after.keys() and before
    for path, w in before.items():
        assert after[path] is w, path


def test_cohere2_moe_view_casts_all_but_router_and_norms():
    cfg = cm.Cohere2MoEConfig.nano(param_dtype=jnp.float32)
    params = cm.init(jax.random.PRNGKey(0), cfg)
    before, after = _named(params), _named(cm.serve_view(params, cfg))
    for path, w in before.items():
        if path.split("'")[-2] in NORMS | {"router"}:
            assert after[path] is w, path
        else:
            assert after[path].dtype == jnp.bfloat16, path
            _same_bits(after[path], w.astype(jnp.bfloat16))


def _engine(mod, cfg, params):
    return ContinuousEngine(mod, cfg, params, max_slots=SLOTS, page_size=PS,
                            num_pages=NUM_PAGES, max_total=S)


def test_engine_on_f32_parameters_reads_two_bytes_a_parameter():
    cfg, params = _model(attn_bias=True)
    eng = _engine(gpt, cfg, params)
    try:
        norms = sum(int(w.size) for path, w in _named(params).items()
                    if path.split("'")[-2] in NORMS)
        stats = eng.engine_stats()
        assert stats["param_count"] == gpt.num_params(cfg) == sum(
            int(w.size) for w in jax.tree_util.tree_leaves(params))
        # the norms stay f32: 2 bytes a parameter and 2 more a norm entry
        assert stats["param_bytes"] == 2 * stats["param_count"] + 2 * norms
        assert stats["param_bytes"] < 2.1 * stats["param_count"]
        # the caller's tree is untouched, and the programs run on the
        # view (bf16 weights beside f32 norms)
        assert all(w.dtype == jnp.float32
                   for w in jax.tree_util.tree_leaves(params))
        prompt = [int(t) for t in
                  np.random.default_rng(2).integers(1, 250, 11)]
        out = eng.collect(eng.submit(prompt, 6), timeout=120)
        assert len(out["completion"]) == 6
    finally:
        eng.stop()


@pytest.mark.parametrize("name", ["gpt", "cohere2_moe"])
def test_engine_on_compute_dtype_parameters_holds_no_second_copy(name):
    mod, cfg, params = _kept_model(name)
    eng = _engine(mod, cfg, params)
    try:
        for a, b in zip(jax.tree_util.tree_leaves(eng._params),
                        jax.tree_util.tree_leaves(params)):
            assert a is b
        assert eng.engine_stats()["param_bytes"] == sum(
            w.nbytes for w in jax.tree_util.tree_leaves(params))
    finally:
        eng.stop()


def test_llmserver_keeps_the_loaders_f32_tree():
    from ray_tpu.serve.llm import _LLMServerImpl

    srv = _LLMServerImpl(preset="nano", max_seq=S,
                         engine_kwargs=dict(max_slots=SLOTS, page_size=PS,
                                            num_pages=NUM_PAGES,
                                            max_total=S))
    eng = srv._get_engine()
    try:
        assert all(w.dtype == jnp.float32
                   for w in jax.tree_util.tree_leaves(srv._params))
        assert eng._params["layers"]["wq"].dtype == jnp.bfloat16
        assert eng._params["layers"]["attn_norm"] \
            is srv._params["layers"]["attn_norm"]
        assert srv.engine_stats()["param_bytes"] < \
            2.1 * srv.engine_stats()["param_count"]
    finally:
        eng.stop()
