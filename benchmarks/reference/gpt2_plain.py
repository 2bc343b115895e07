"""GPT-2 as published, in plain jax.numpy and float32 — the benchmark's
yardstick for `correct`.

Follows Radford et al. 2019 / openai/gpt-2 `model.py`: token + learned
position embeddings, pre-LayerNorm blocks (eps 1e-5) of full multi-head
causal attention with q/k/v/o biases and a GELU (tanh form, "gelu_new")
MLP with biases, a final LayerNorm, logits through the tied embedding.
No kernels, no cache, no batching tricks; imports nothing from
`ray_tpu.models` or `ray_tpu.ops`.  Every matmul runs under
`jax.default_matmul_precision("highest")` (on a TPU an f32 matmul is
otherwise done in bf16 passes).

Departures from the published description, each forced by what it is
compared with:
  * the loss adds the program's z-loss term `z * logsumexp(logits)**2`
    (`GPTConfig.z_loss`, 1e-4), because the train step reports the sum;
    `z=0` gives GPT-2's plain cross-entropy;
  * layers are run by `lax.scan` over the stacked weights, and `remat=True`
    wraps each block in `jax.checkpoint` — the same arithmetic, compiled
    once per layer shape and with one layer's activations alive, so that
    48 layers of gradients fit beside the system under test.

Parameters are read from a dict in the layout the program's `gpt.init`
produces (data, not an import): `embed [V,D]`, `pos_embed [P,D]`,
`final_norm`, `final_norm_b [D]`, and under `layers`, stacked over L:
`attn_norm`, `attn_norm_b`, `mlp_norm`, `mlp_norm_b [L,D]`,
`wq`, `wk`, `wv [L,D,H,dh]`, `wq_b`, `wk_b`, `wv_b [L,H,dh]`,
`wo [L,H,dh,D]`, `wo_b [L,D]`, `mlp_in [L,D,F]`, `mlp_in_b [L,F]`,
`mlp_out [L,F,D]`, `mlp_out_b [L,D]`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, lp):
    """One transformer block on x [B,S,D]; lp is one layer's weights."""
    B, S, D = x.shape
    h = layer_norm(x, lp["attn_norm"], lp["attn_norm_b"])
    q = jnp.einsum("bsd,dhk->bhsk", h, lp["wq"]) + lp["wq_b"][None, :, None]
    k = jnp.einsum("bsd,dhk->bhsk", h, lp["wk"]) + lp["wk_b"][None, :, None]
    v = jnp.einsum("bsd,dhk->bhsk", h, lp["wv"]) + lp["wv_b"][None, :, None]
    dh = q.shape[-1]
    s = jnp.einsum("bhqk,bhsk->bhqs", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bhsk->bhqk", p, v)
    x = x + jnp.einsum("bhsk,hkd->bsd", o, lp["wo"]) + lp["wo_b"]
    h = layer_norm(x, lp["mlp_norm"], lp["mlp_norm_b"])
    m = gelu_new(jnp.einsum("bsd,df->bsf", h, lp["mlp_in"]) + lp["mlp_in_b"])
    return x + jnp.einsum("bsf,fd->bsd", m, lp["mlp_out"]) + lp["mlp_out_b"]


def logits(params, tokens, remat: bool = False):
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        S = tokens.shape[1]
        x = p["embed"][tokens] + p["pos_embed"][:S][None]
        f = jax.checkpoint(block) if remat else block
        x, _ = jax.lax.scan(lambda x, lp: (f(x, lp), None), x, p["layers"])
        x = layer_norm(x, p["final_norm"], p["final_norm_b"])
        return jnp.einsum("bsd,vd->bsv", x, p["embed"])


def loss(params, inputs, targets, z: float = 0.0, remat: bool = False):
    """Mean next-token cross-entropy (+ z * logsumexp**2) over [B,S]."""
    lg = logits(params, inputs, remat=remat)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + z * jnp.square(lse))


def loss_and_grad_norm(params, inputs, targets, z: float = 0.0):
    """(loss, global L2 norm of the gradient w.r.t. every parameter)."""
    val, g = jax.value_and_grad(
        lambda p: loss(p, inputs, targets, z=z, remat=True))(params)
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(g))
    return val, jnp.sqrt(sq)
