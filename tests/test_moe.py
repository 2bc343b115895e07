"""MoE / expert-parallelism tests (no reference model: EP is absent in-tree
upstream, SURVEY.md §2.3 — behavior is validated against the dense math)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.moe import (expert_capacity, moe_ffn, moe_ffn_sharded,
                             route_topk)


def test_route_topk_shapes_and_capacity():
    T, E, k, C = 32, 4, 2, 16
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    r = route_topk(logits, k, C)
    assert r.dispatch.shape == (T, E, C)
    # each token occupies at most k slots, each with weight exactly 1
    per_token = np.asarray(r.dispatch.sum(axis=(1, 2)))
    assert (per_token <= k + 1e-6).all()
    # each (expert, slot) holds at most one token
    per_slot = np.asarray(r.dispatch.sum(axis=0))
    assert (per_slot <= 1 + 1e-6).all()
    # combine weights are a convex-ish mixture: <= 1 per token
    cw = np.asarray(r.combine.sum(axis=(1, 2)))
    assert (cw <= 1 + 1e-5).all()
    assert np.isfinite(float(r.aux_loss)) and float(r.aux_loss) > 0


def test_route_topk_drops_overflow():
    # all tokens pick expert 0 -> only `capacity` of them may land
    T, E, C = 16, 4, 8
    logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (T, 1))
    r = route_topk(logits, k=1, capacity=C)
    assert float(r.dispatch[:, 0].sum()) == C


def test_moe_ffn_matches_per_token_expert():
    """k=1, generous capacity: output must equal running each token through
    its argmax expert scaled by its (renormalized=1) gate weight."""
    T, D, F, E = 16, 8, 16, 4
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w_in = jax.random.normal(ks[2], (E, D, F)) * 0.1
    w_out = jax.random.normal(ks[3], (E, F, D)) * 0.1
    out, aux, z = moe_ffn(x, router, w_in, w_out, k=1, capacity=T)
    sel = np.asarray(jnp.argmax(x @ router, axis=-1))
    expect = np.stack([
        np.asarray(jax.nn.gelu(x[t] @ w_in[e]) @ w_out[e])
        for t, e in enumerate(sel)])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_moe_sharded_matches_dense(k):
    """Expert-parallel all_to_all path == dense path on an ep mesh."""
    n = 4
    devs = jax.devices()[:n]
    mesh = jax.sharding.Mesh(np.array(devs), ("ep",))
    T, D, F, E = 32, 8, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(keys[0], (T, D))
    router = jax.random.normal(keys[1], (D, E))
    w_in = jax.random.normal(keys[2], (E, D, F)) * 0.1
    w_out = jax.random.normal(keys[3], (E, F, D)) * 0.1
    # capacity per local shard of T/n tokens, same for dense on full T/n:
    cap = expert_capacity(T // n, E, k, 1000.0)  # no drops -> exact match

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sharded = shard_map(
        lambda xt, wr, wi, wo: moe_ffn_sharded(xt, wr, wi, wo, k=k,
                                               capacity=cap),
        mesh=mesh, check_vma=False,
        in_specs=(P("ep"), P(), P("ep"), P("ep")),
        out_specs=(P("ep"), P(), P()))
    out_s, aux_s, z_s = sharded(x, router, w_in, w_out)

    # dense reference: same routing happens per shard-of-T independently
    outs = []
    for i in range(n):
        xi = x[i * (T // n):(i + 1) * (T // n)]
        oi, _, _ = moe_ffn(xi, router, w_in, w_out, k=k, capacity=cap)
        outs.append(np.asarray(oi))
    np.testing.assert_allclose(np.asarray(out_s), np.concatenate(outs),
                               rtol=2e-4, atol=2e-5)


def test_moe_model_forward_and_grad():
    from ray_tpu.models import moe

    cfg = moe.MoEConfig.mixtral_nano()
    params = moe.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                cfg.vocab_size)
    logits, extras = moe.apply(params, tokens[:, :-1], cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert np.isfinite(float(extras["aux"]))

    loss, grads = jax.value_and_grad(moe.loss_fn)(params, {"tokens": tokens},
                                                  cfg)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # router must receive gradient through the combine weights
    g_router = np.asarray(grads["layers"]["router"])
    assert np.abs(g_router).max() > 0


def test_moe_model_on_ep_mesh():
    """Full model under jit on a mesh with a real ep axis."""
    from ray_tpu.models import moe
    from ray_tpu.parallel import make_mesh

    try:
        mesh = make_mesh(ep=4, dp=2)
    except TypeError:
        pytest.skip("mesh has no ep axis yet")
    cfg = moe.MoEConfig.mixtral_nano()
    params = moe.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                cfg.vocab_size)
    from ray_tpu.models.training import _use_mesh

    with _use_mesh(mesh):
        loss_mesh = jax.jit(
            lambda p, b: moe.loss_fn(p, b, cfg, mesh))(params,
                                                       {"tokens": tokens})
    loss_ref = moe.loss_fn(params, {"tokens": tokens}, cfg)
    # ep=4 routes per 2-token shard vs 8-token dense: small capacity/drop
    # differences allowed, but the numbers must be close
    assert abs(float(loss_mesh) - float(loss_ref)) / float(loss_ref) < 0.05
