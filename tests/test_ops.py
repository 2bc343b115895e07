"""Attention kernel + SP op correctness vs the naive oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (apply_rope, attention, blockwise_attention,
                         flash_attention, flash_attention_with_lse,
                         mha_reference, ring_attention, rms_norm,
                         rope_table, softmax_cross_entropy,
                         ulysses_attention)
from ray_tpu.parallel import make_mesh


def _qkv(b=2, h=4, s=128, d=32, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, h, s, d), dtype)
    k = jax.random.normal(k2, (b, h, s, d), dtype)
    v = jax.random.normal(k3, (b, h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=32)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_nondivisible_block():
    q, k, v = _qkv(s=96)
    ref = mha_reference(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=40)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_block_fitting():
    """Defaults shrink to a divisor for awkward-but-reasonable lengths
    (768 -> 256); lengths with only tiny divisors (520 -> 8) must raise,
    not silently run a degenerate grid."""
    q, k, v = _qkv(b=1, h=1, s=768, d=32)
    out = flash_attention(q, k, v, True, None, 512, 1024, True)
    ref = mha_reference(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    q2, k2, v2 = _qkv(b=1, h=1, s=520, d=32)
    with pytest.raises(ValueError, match="pad"):
        flash_attention(q2, k2, v2, True, None, 512, 1024, True)


def test_flash_causal_rectangular_raises():
    """Without an explicit q_offset the pallas kernels would anchor the
    causal mask at row 0 while mha_reference anchors rectangular inputs
    at sk-sq: causal sq != sk with q_offset=0 must raise instead of
    silently diverging (callers pass q_offset=sk-sq to opt in)."""
    q, _, _ = _qkv(b=1, h=1, s=128, d=32)
    k, v = _qkv(b=1, h=1, s=256, d=32, seed=1)[1:]
    with pytest.raises(ValueError, match="q_offset"):
        flash_attention(q, k, v, True, None, 64, 64, True)
    # non-causal rectangular stays supported
    out = flash_attention(q, k, v, False, None, 64, 64, True)
    ref = mha_reference(q, k, v, causal=False)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_flash_q_offset_decode_alignment():
    """q_offset=sk-sq gives the bottom-right (decode) causal alignment:
    fwd, dq/dk/dv and the lse variant all match the dense oracle on a
    rectangular multi-block grid."""
    q, _, _ = _qkv(b=1, h=2, s=128, d=32)
    k, v = _qkv(b=1, h=2, s=256, d=32, seed=1)[1:]
    ref = mha_reference(q, k, v, causal=True)  # bottom-right for sq<sk
    out = flash_attention(q, k, v, True, None, 64, 64, True, 128)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    out_lse, _ = flash_attention_with_lse(q, k, v, True, None, 64, 64,
                                          True, 128)
    assert np.allclose(np.asarray(out_lse), np.asarray(ref), atol=2e-4)

    def loss_f(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, True, None,
                                       64, 64, True, 128) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=True) ** 2)

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_ref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_attention_causal_rectangular_matches_reference():
    """Causal rectangular through the dispatcher: sq < sk (decode /
    sliding-window shapes) auto-sets q_offset=sk-sq on the pallas paths
    and the xla path applies the same bottom-right mask — every impl
    agrees with the reference."""
    q, _, _ = _qkv(b=1, h=2, s=128, d=32)
    k, v = _qkv(b=1, h=2, s=256, d=32, seed=1)[1:]
    ref = mha_reference(q, k, v, causal=True)
    out = attention(q, k, v, causal=True)  # auto
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    out_xla = attention(q, k, v, causal=True, impl="xla")
    assert np.allclose(np.asarray(out_xla), np.asarray(ref), atol=2e-5)
    out_pl = attention(q, k, v, causal=True, impl="pallas_interpret")
    assert np.allclose(np.asarray(out_pl), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_interpret_matches_reference(causal):
    # interpret mode runs the Pallas kernel on CPU — validates kernel logic
    q, k, v = _qkv(b=1, h=2, s=128, d=32)
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 64, 64, True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_kernels_match_reference(causal):
    """The Pallas dq/dk/dv kernels (interpret mode) against autodiff of
    the dense oracle — multi-block grids so the accumulation loops and
    causal block-skip paths are exercised."""
    q, k, v = _qkv(b=1, h=2, s=256, d=32)

    def loss_f(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal, None,
                                       128, 128, True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=causal) ** 2)

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_ref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# (sq, sk, block_q, block_k, causal, q_offset): where the kernels'
# static extents can be wrong
_FLASH_LAYOUTS = {
    # one program a head, four sub-blocks a side, the diagonal on their
    # corners (the train cells' layout: 1024 x 1024 blocks, sub-blocks 256)
    "s1024-default-blocks": (1024, 1024, 1024, 1024, True, 0),
    # two programs a head, key blocks wider than query blocks
    "s1024-blocks-512x1024": (1024, 1024, 512, 1024, True, 0),
    # sub_q 256 against sub_k 128 and an anchor of 128: the diagonal
    # crosses query sub-blocks in their middle
    "diagonal-mid-sub-block": (512, 640, 512, 1024, True, 128),
    # rectangular, bottom-right anchored: the first rows already see
    # whole key sub-blocks unmasked, and whole key blocks below them
    "q-offset-rectangular": (256, 1024, 256, 512, True, 768),
    "non-causal": (512, 512, 512, 512, False, 0),
    # blocks shrink through _fit_block (512 -> 256, 1024 -> 768)
    "s768-fitted-blocks": (768, 768, 512, 1024, True, 0),
    # a 4 x 2 grid: blocks below, on and above the diagonal, the last
    # fetched never (clamped index maps)
    "grid-4x2": (1024, 1024, 256, 512, True, 0),
    # eight diagonal positions, so eight static bodies a kernel
    "eight-diagonal-positions": (1024, 1024, 128, 1024, True, 0),
    # an anchor short of bottom-right: the last key block is seen by no
    # row, and its dk / dv are zeros that something has to write
    "keys-past-the-last-row": (256, 1024, 256, 512, True, 256),
}


@pytest.mark.parametrize("layout", list(_FLASH_LAYOUTS))
def test_flash_sub_block_extents(layout):
    """Forward and all three gradients against the dense oracle where a
    program holds several sub-blocks, and the count of them computed
    (`causal_work`) against the mask itself."""
    from ray_tpu.ops.attention import causal_work

    sq, sk, bq, bk, causal, off = _FLASH_LAYOUTS[layout]
    # d_head 64, the train cells': its scale (0.125) rides in an operand
    q, _, _ = _qkv(b=1, h=2, s=sq, d=64)
    k, v = _qkv(b=1, h=2, s=sk, d=64, seed=1)[1:]

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal, None, bq, bk, True, off)

    seen = np.ones((sq, sk), bool)
    if causal:      # mha_reference's mask where off == sk - sq
        seen = (np.arange(sq)[:, None] + off) >= np.arange(sk)[None, :]

    def ref(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * q_.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v_)

    assert np.allclose(np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)),
                       atol=2e-4)
    g_f = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g_f, g_ref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    for backward in (False, True):
        w = causal_work(sq, sk, bq, bk, causal, off, backward)
        tiles = seen.reshape(sq // w["sub_q"], w["sub_q"],
                             sk // w["sub_k"], w["sub_k"])
        assert w["total"] == tiles.shape[0] * tiles.shape[2]
        assert w["run"] == int(tiles.any(axis=(1, 3)).sum())
        assert w["masked"] == int((tiles.any(axis=(1, 3))
                                   & ~tiles.all(axis=(1, 3))).sum())


@pytest.mark.parametrize("d_head", [64, 128])
def test_flash_bf16_holds_the_scale_exact(d_head):
    """bf16 operands, f32 accumulation: forward and gradients against
    the f32 oracle on the same (bf16-valued) inputs.  A scale that is no
    power of two (d_head 128) multiplied into a bf16 operand rounds it a
    second time: the output's rms error then reads 2.3e-3 of its rms,
    where scaling the f32 scores reads 2.0e-3 at either d_head, as does
    0.125 in the operand at d_head 64."""
    q, k, v = _qkv(b=1, h=2, s=512, d=d_head, dtype=jnp.bfloat16)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, True, None, 512, 512, True
                               ).astype(jnp.float32)

    def ref(q_, k_, v_):
        return mha_reference(q_, k_, v_, causal=True)

    def rms(x):
        return float(jnp.sqrt(jnp.mean(x.astype(jnp.float32) ** 2)))

    o, o_ref = flash(q, k, v), ref(*f32)
    assert rms(o - o_ref) < 2.15e-3 * rms(o_ref)
    g_f = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(
        *f32)
    for a, b in zip(g_f, g_ref):
        assert rms(a.astype(jnp.float32) - b) < 3.3e-3 * rms(b)
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) \
            < 1e-2 * float(jnp.max(jnp.abs(b)))


def test_flash_causal_work_at_the_train_cells_shape():
    """1024 x 1024 at the default blocks (one program a head): ten of
    sixteen sub-blocks computed, four of them under the mask, forward
    and backward."""
    from ray_tpu.ops.attention import causal_work

    for backward in (False, True):
        assert causal_work(1024, 1024, backward=backward) == {
            "sub_q": 256, "sub_k": 256, "total": 16, "run": 10, "masked": 4}


def test_flash_with_lse_value_and_grads():
    """(out, lse) variant: lse equals dense logsumexp of scaled scores,
    and gradients flow through BOTH outputs (the dlse term folds into
    the same backward kernels)."""
    q, k, v = _qkv(b=1, h=2, s=128, d=32)
    out, lse = flash_attention_with_lse(q, k, v, True, None, 64, 64, True)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    mask = np.tril(np.ones((128, 128), bool))
    s = jnp.where(mask, s, -1e30)
    assert np.allclose(np.asarray(lse),
                       np.asarray(jax.scipy.special.logsumexp(s, -1)),
                       atol=1e-3)
    assert np.allclose(np.asarray(out),
                       np.asarray(mha_reference(q, k, v, causal=True)),
                       atol=2e-4)

    def loss_f(q_, k_, v_):
        o_, l_ = flash_attention_with_lse(q_, k_, v_, True, None,
                                          64, 64, True)
        return jnp.sum(o_ ** 2) + jnp.sum(jnp.sin(l_))

    def loss_ref(q_, k_, v_):
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * (d ** -0.5)
        s_ = jnp.where(mask, s_, -1e30)
        o_ = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s_, -1), v_)
        return jnp.sum(o_ ** 2) + jnp.sum(
            jnp.sin(jax.scipy.special.logsumexp(s_, -1)))

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_ref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_blockwise_grads_match_reference():
    q, k, v = _qkv(b=1, h=2, s=64, d=16)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=True) ** 2)

    def loss_blk(q_, k_, v_):
        return jnp.sum(blockwise_attention(q_, k_, v_, causal=True,
                                           block_k=16) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh(sp=8)
    q, k, v = _qkv(b=1, h=2, s=256, d=16)
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, "sp", causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_pallas_kernels(causal):
    """Ring body built on the flash (o, lse) chunk kernels (interpret
    mode): partial-softmax combination across rotated KV chunks must
    match dense attention, for values AND grads."""
    mesh = make_mesh(sp=4, devices=jax.devices()[:4])
    q, k, v = _qkv(b=1, h=2, s=256, d=16)
    out = ring_attention(q, k, v, mesh, "sp", causal=causal,
                         impl="pallas_interpret")
    ref = mha_reference(q, k, v, causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh, "sp",
                                      causal=causal,
                                      impl="pallas_interpret") ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=causal) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_ring_attention_grads():
    mesh = make_mesh(sp=4, devices=jax.devices()[:4])
    q, k, v = _qkv(b=1, h=2, s=64, d=16)

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh, "sp", causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    mesh = make_mesh(sp=4, devices=jax.devices()[:4])
    q, k, v = _qkv(b=1, h=8, s=128, d=16)  # heads divisible by sp
    ref = mha_reference(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh, "sp", causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_attention_dispatch_cpu():
    q, k, v = _qkv(b=1, h=1, s=64, d=16)
    out = attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
    w = jnp.ones(32) * 2.0
    y = rms_norm(x, w)
    norm = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6)
    assert np.allclose(np.asarray(y), 2.0 * norm, atol=1e-5)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_table(128, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 32))
    y = apply_rope(x, cos, sin)
    assert np.allclose(np.linalg.norm(np.asarray(x), axis=-1),
                       np.linalg.norm(np.asarray(y), axis=-1), atol=1e-4)


def test_rope_positions_offset():
    cos, sin = rope_table(256, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 64, 32))
    full = apply_rope(jnp.tile(x, (1, 1, 2, 1))[:, :, :128], cos, sin)
    part = apply_rope(x, cos, sin, positions=jnp.arange(64, 128))
    assert np.allclose(np.asarray(full[:, :, 64:128]), np.asarray(part),
                       atol=1e-5)


def test_cross_entropy():
    logits = jnp.array([[2.0, 1.0, 0.1]])
    labels = jnp.array([0])
    loss = softmax_cross_entropy(logits, labels)
    p = np.exp(2.0) / (np.exp(2.0) + np.exp(1.0) + np.exp(0.1))
    assert np.allclose(np.asarray(loss), -np.log(p), atol=1e-5)


@pytest.mark.parametrize("z_loss", [0.0, 1e-4])
def test_fused_cross_entropy_matches_dense(z_loss):
    """fused_softmax_cross_entropy (chunked vocab projection inside the
    loss) == dense project-then-CE, for the loss AND the grads wrt both
    hidden states and the unembed table."""
    from ray_tpu.ops import fused_softmax_cross_entropy

    B, S, D, V, chunk = 2, 64, 16, 37, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, (B, S, D))
    w = jax.random.normal(k2, (D, V)) * 0.1
    labels = jax.random.randint(k3, (B, S), 0, V)

    def dense(x, w):
        return jnp.mean(softmax_cross_entropy(
            jnp.einsum("bsd,dv->bsv", x, w), labels, z_loss=z_loss))

    def fused(x, w):
        return jnp.mean(fused_softmax_cross_entropy(
            x, w, labels, z_loss=z_loss, chunk=chunk))

    ld, (gxd, gwd) = jax.value_and_grad(dense, argnums=(0, 1))(x, w)
    lf, (gxf, gwf) = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    assert np.allclose(float(ld), float(lf), atol=1e-6)
    assert np.allclose(np.asarray(gxd), np.asarray(gxf), atol=1e-5)
    assert np.allclose(np.asarray(gwd), np.asarray(gwf), atol=1e-5)


def test_fused_cross_entropy_rejects_indivisible_seq():
    from ray_tpu.ops import fused_softmax_cross_entropy

    with pytest.raises(AssertionError):
        fused_softmax_cross_entropy(jnp.zeros((1, 10, 4)),
                                    jnp.zeros((4, 7)),
                                    jnp.zeros((1, 10), jnp.int32), chunk=16)
