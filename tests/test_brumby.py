"""models/brumby.py (every layer a power-retention layer and a SwiGLU)
against the plain reference (benchmarks/reference/brumby_plain.py: the
quadratic form, float32, written from the layer equations) on LOGITS.
Both sides compute in float32 here: 2e-6 apart on logits of size 1.  The
tolerance is 1e-4; the mutations below move a logit by more than 1e-3."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import brumby_plain as ref
from ray_tpu.models import brumby as bm
from ray_tpu.ops.retention import state_shape

TOL = 1e-4


def shape_of(cfg):
    return {"eps": cfg.rms_eps, "theta": cfg.rope_theta, "ret_eps": 1e-6}


def with_memory(params, bias=4.0):
    """The plain draw's gate is 1/2 (a memory of two tokens): give it one
    of fifty, so that a lost or stale state is seen far down a stream."""
    layers = params["layers"]
    return dict(params, layers=dict(layers, bg=layers["bg"] + bias))


@pytest.fixture(scope="module")
def model():
    cfg = bm.BrumbyConfig.nano(dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, with_memory(bm.init(jax.random.PRNGKey(0), cfg))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


def ref_logits(model, toks):
    cfg, params = model
    return np.asarray(ref.forward(params, jnp.asarray(toks), shape_of(cfg)))


@pytest.mark.parametrize("impl", [None, "pallas_interpret"])
def test_full_forward_matches_reference(model, impl):
    """`apply` maps one sequence's pass over the batch: under that map the
    chunk's kernel gains a grid axis."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, retention_impl=impl)
    toks = np.stack([tokens(40), tokens(40, seed=3)])
    got = np.asarray(bm.apply(params, jnp.asarray(toks), cfg))
    for row, t in zip(got, toks):
        assert np.abs(row - ref_logits(model, t)).max() < TOL


def test_interface_and_sizes():
    cfg = bm.BrumbyConfig()
    assert bm.cache_kinds(cfg) == {"ret": "state"}
    assert bm.STEP_STATS == ("ret_states",)
    cache = jax.eval_shape(lambda: bm.init_paged_cache(cfg, {"ret": 17}, 16))
    assert cache.shape == (40, 17, 8) + state_shape(128) \
        == (40, 17, 8, 136, 8320)
    assert cache.dtype == jnp.float32
    # a layer at published widths: 330.3M parameters
    layer = jax.eval_shape(lambda k: bm.init_layer(k, cfg),
                           jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree.leaves(layer))
    assert 330.2e6 < n < 330.5e6


@pytest.mark.parametrize("impl", [None, "pallas_interpret"])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_prefill_then_decode_matches_reference(model, chunk, impl):
    """The serve programs by hand: a 29-token prompt in chunks (the last
    one ragged, padded to the program's length), then 12 decode steps in
    slot 1 of three beside two empty slots, teacher-forced; every logits
    row against the reference's full forward — through the XLA bodies and
    through both kernels (interpret mode)."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, retention_impl=impl)
    moved = 3.0 if impl is None else 1.0        # the step's kernel skips
    toks = tokens(41, seed=5)
    want = ref_logits(model, toks)
    plen = 29
    cache = bm.init_paged_cache(cfg, {"ret": 4}, 16)
    row = {"ret": jnp.array([2], jnp.int32)}
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        t = np.zeros(chunk, np.int32)
        t[:n] = toks[start:start + n]
        lg, cache, stats = bm.paged_prefill(
            params, cache, jnp.asarray(t), row, jnp.int32(start),
            jnp.int32(n - 1), cfg)
        assert np.abs(np.asarray(lg) - want[start + n - 1]).max() < TOL
        assert float(stats[0]) == 1.0
    ptabs = {"ret": jnp.array([[0], [2], [0]], jnp.int32)}
    for p in range(plen, 41):
        lg, cache, stats = bm.paged_decode_step(
            params, cache, jnp.array([0, toks[p], 0], jnp.int32), ptabs,
            jnp.array([0, p, 0], jnp.int32), cfg)
        assert np.abs(np.asarray(lg)[1] - want[p]).max() < TOL, p
        assert float(stats[0]) == moved
    # nothing but entry 2 was written
    assert float(jnp.abs(cache[:, jnp.array([0, 1, 3])]).max()) == 0.0


def test_the_kernel_path_decodes_what_the_gather_path_decodes(model):
    """`retention_impl="pallas_interpret"`: the step's kernel inside the
    layer scan, arena carried and aliased, against the gather / scatter
    path; it counts the live slot's state alone as moved."""
    cfg, params = model
    kern = dataclasses.replace(cfg, retention_impl="pallas_interpret")
    toks = tokens(12, seed=4)
    row = {"ret": jnp.array([2], jnp.int32)}
    _, cache, _ = bm.paged_prefill(
        params, bm.init_paged_cache(cfg, {"ret": 4}, 16), jnp.asarray(toks),
        row, jnp.int32(0), jnp.int32(11), cfg)
    ptabs = {"ret": jnp.array([[0], [2], [0]], jnp.int32)}
    args = (jnp.array([0, 7, 0], jnp.int32), ptabs,
            jnp.array([0, 12, 0], jnp.int32))
    want, want_cache, moved = bm.paged_decode_step(params, cache, *args, cfg)
    got, got_cache, kmoved = bm.paged_decode_step(params, cache, *args, kern)
    assert (float(moved[0]), float(kmoved[0])) == (3.0, 1.0)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    np.testing.assert_allclose(got_cache, want_cache, atol=1e-5)


def test_first_chunk_empties_a_used_entry(model):
    """An entry that still holds another sequence's state: the first chunk
    (`start == 0`) does not read it; a later chunk does."""
    cfg, params = model
    toks = tokens(16, seed=8)
    want = ref_logits(model, toks)
    dirty = bm.init_paged_cache(cfg, {"ret": 3}, 16) + 3.0
    row = {"ret": jnp.array([1], jnp.int32)}
    lg, cache, _ = bm.paged_prefill(params, dirty, jnp.asarray(toks[:8]),
                                    row, jnp.int32(0), jnp.int32(7), cfg)
    assert np.abs(np.asarray(lg) - want[7]).max() < TOL
    lg, _, _ = bm.paged_prefill(params, cache, jnp.asarray(toks[8:]), row,
                                jnp.int32(8), jnp.int32(7), cfg)
    assert np.abs(np.asarray(lg) - want[15]).max() < TOL
    # the mutation: the second chunk from an emptied entry (a dropped
    # carry) is off by far more than the tolerance
    clean = bm.init_paged_cache(cfg, {"ret": 3}, 16)
    lg, _, _ = bm.paged_prefill(params, clean, jnp.asarray(toks[8:]), row,
                                jnp.int32(8), jnp.int32(7), cfg)
    assert np.abs(np.asarray(lg) - want[15]).max() > 1e-3


def test_serve_view_keeps_the_gate_in_float32():
    cfg = bm.BrumbyConfig.nano(param_dtype=jnp.float32)
    params = bm.init(jax.random.PRNGKey(0), cfg)
    view = bm.serve_view(params, cfg)
    lp = view["layers"]
    assert lp["wq"].dtype == view["unembed"].dtype == jnp.bfloat16
    assert lp["wg"].dtype == lp["bg"].dtype == jnp.float32
    assert lp["attn_norm"] is params["layers"]["attn_norm"]
    assert lp["wq"].shape == (cfg.n_layers, 64, 6, 16)
