"""Command A+ (`cohere2_moe`) as published, in plain jax.numpy and float32
— the yardstick for `correct` of the cells that serve it.

Written from the layer equations of the published config
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json),
not from the program; imports nothing from `ray_tpu`.  Per layer, with `h`
the ONE normed input that attention and feed-forward both read
(`use_parallel_block`):

    h   = LayerNorm(x)                 weight only, eps 1e-5
    q   = h Wq (n_heads x dh);  k = h Wk, v = h Wv (n_kv_heads x dh)
    sliding layers: RoPE on q, k over interleaved pairs (`rope_gptj`),
        theta 50000, all dh dims; query i sees key j iff 0 <= i-j < window
    full layers: no position signal; query i sees key j iff j <= i
    a   = softmax(q k^T / sqrt(dh)) v, K/V head g serving query heads
          g*G..g*G+G-1;  attn = a Wo
    s   = sigmoid(h Wr) over ALL experts; T = top-k; w_e = s_e / sum_T s
    routed = sum_{e in T, e held} w_e Wd_e(silu(Wg_e h) * (Wu_e h))
    shared = (1/S) sum_i Wd'_i(silu(Wg'_i h) * (Wu'_i h))
    x'  = x + attn + routed + shared
    logits = logit_scale * LayerNorm(x_L) E^T      (tied embedding)

No cache, no kernels, no batching; every matmul under
`jax.default_matmul_precision("highest")`.

Departures from the published description, each forced by what it is
compared with:
  * only the experts `first..first+held-1` are computed (the chip's share
    of an expert-parallel deployment, model-configs guide section 4): the
    router still scores all `n_experts` and normalises over the chosen k,
    and what absent experts would add is left out.  `held = n_experts`
    is the whole layer;
  * the vocabulary is the slice the parameters hold (`embed` rows);
  * the vision tower is not part of the language model and is left out;
  * it is computed in blocks so that it fits beside the engine and inside
    a run's minute: the layer comes in pieces (`keys_values`, `attend`,
    `route`, `expert`, `shared_expert`, `readout`) that each upcast only their own
    weights; one held expert at a time over the rows routed to it,
    gathered to a static bound `expert_rows` (an expert with more rows
    than that is computed over every row instead; no bound where none is
    given); attention `q_block` query rows at a time, a windowed layer's
    block against the keys it can see.  The arithmetic is the same:
    nothing that enters a sum is left out.

Parameters are read from a dict in the layout the program's
`cohere2_moe.init` produces (data, not an import): `embed [V,D]`,
`final_norm [D]`, and `layers`, a list of dicts: `attn_norm [D]`,
`wq [D,H,dh]`, `wk`, `wv [D,Hkv,dh]`, `wo [H,dh,D]`, `router [D,E]`,
`wg`, `wu [held,D,F]`, `wd [held,F,D]`, and the S shared experts side by
side: `shared_gate`, `shared_up [D,S*F]`, `shared_down [S*F,D]` (expert i
is columns / rows i*F..(i+1)*F).  Sizes come with `shape`, a dict:
layer_types, window, theta, top_k, first, n_shared, logit_scale and,
optionally, expert_rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def layer_norm(x, w):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w


def rope_interleaved(x, theta, i0=0):
    """x [n, H, dh] at positions i0..i0+n-1: pair (2i, 2i+1) turned by the
    angle pos * theta^(-2i/dh)."""
    n, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (i0 + jnp.arange(n)).astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def attention(q, k, v, window, q_block, i0=0):
    """q [n,H,dh] at positions i0..i0+n-1 against keys and values k, v
    [S,Hkv,dh] at positions 0..S-1 -> [n,H,dh]; window None = full.  A
    block of query rows i..i+q_block-1 of a windowed layer is scored
    against the keys it can see at all, i-window+1..i+q_block-1 (a slice
    of static length, clamped at the keys' ends), and masked within; a
    full layer's block against every key."""
    n, H, dh = q.shape
    S, Hkv = k.shape[0], k.shape[1]
    G = H // Hkv
    n_keys = S if window is None else min(S, window + q_block - 1)

    def rows(b0):                       # q_block query rows of every head
        i = i0 + b0 + jnp.arange(q_block)
        j0 = 0 if window is None else jnp.clip(i[0] - window + 1, 0,
                                               S - n_keys)
        j = j0 + jnp.arange(n_keys)
        kb = jax.lax.dynamic_slice_in_dim(k, j0, n_keys, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, j0, n_keys, 0)
        qb = jax.lax.dynamic_slice_in_dim(q, b0, q_block, 0)
        qb = qb.reshape(q_block, Hkv, G, dh)
        s = jnp.einsum("qhgd,shd->hgqs", qb, kb) / math.sqrt(dh)
        see = j[None, :] <= i[:, None]
        if window is not None:
            see = see & (i[:, None] - j[None, :] < window)
        s = jnp.where(see[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqs,shd->qhgd", p, vb).reshape(q_block, H, dh)

    out = jax.lax.map(rows, jnp.arange(0, n, q_block))
    return out.reshape(n, H, dh)


def _highest(fn):
    """Every matmul of a piece runs at the highest precision, whether the
    piece is called eagerly, under `jit`, or alone."""
    import functools

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


# The layer in pieces, each a function of the arrays it needs and each
# upcasting its own weights: `block` and `logits` compose them, and so can a
# caller that has to bound memory (one piece a program: check_cohere2_moe).


@_highest
def keys_values(x, norm_w, wk, wv, kind, shape, i0=0):
    """Keys and values of the rows x [n,D] at positions i0..: each
    [n,Hkv,dh], the keys turned by RoPE on a sliding layer."""
    norm_w, wk, wv = _f32(norm_w, wk, wv)
    h = layer_norm(x, norm_w)
    k = jnp.einsum("sd,dhk->shk", h, wk)
    if kind == "sliding":
        k = rope_interleaved(k, shape["theta"], i0)
    return k, jnp.einsum("sd,dhk->shk", h, wv)


@_highest
def attend(x, k, v, norm_w, wq, wo, kind, shape, q_block, i0=0):
    """The rows x [n,D] at positions i0.. against the sequence's keys and
    values k, v [S,Hkv,dh] (positions 0..) -> (attention's addition to the
    residual [n,D], h [n,D] the normed input the feed-forward reads too)."""
    norm_w, wq, wo = _f32(norm_w, wq, wo)
    h = layer_norm(x, norm_w)
    q = jnp.einsum("sd,dhk->shk", h, wq)
    if kind == "sliding":
        q = rope_interleaved(q, shape["theta"], i0)
    a = attention(q, k, v, shape["window"] if kind == "sliding" else None,
                  q_block, i0)
    return jnp.einsum("shk,hkd->sd", a, wo), h


@_highest
def route(h, router, top_k):
    """Sigmoid scores over ALL experts, top-k, weights normalised over the
    chosen k: (w [S,k], idx [S,k])."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))      # [S, E]
    top, idx = jax.lax.top_k(s, top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


@_highest
def expert(h, w, idx, e, wg, wu, wd, cap: int = 0):
    """Expert e's weighted part of the routed sum, [S,D]: over the rows
    routed to it, gathered (a static `cap` of them), through e, scattered
    back; where more than `cap` rows fall on e — or no cap is given — over
    every row with the others' gates at zero.  The same sum either way."""
    wg, wu, wd = _f32(wg, wu, wd)
    S = h.shape[0]
    gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)    # [S]
    on = jnp.any(idx == e, axis=-1)

    def gathered():
        rows = jnp.nonzero(on, size=cap, fill_value=S)[0]
        y = swiglu(h.at[rows].get(mode="fill", fill_value=0.0), wg, wu, wd)
        y = y * gate.at[rows].get(mode="fill", fill_value=0.0)[:, None]
        return jnp.zeros_like(h).at[rows].add(y, mode="drop")

    def every_row():
        return gate[:, None] * swiglu(h, wg, wu, wd)

    if not cap or cap >= S:
        return every_row()
    return jax.lax.cond(jnp.sum(on) > cap, every_row, gathered)


@_highest
def shared_expert(h, wg, wu, wd):
    return swiglu(h, *_f32(wg, wu, wd))


def feed_forward(h, lp, shape):
    """routed (held experts' part) + mean of the shared experts; h [S,D]."""
    w, idx = route(h, lp["router"], shape["top_k"])
    cap = min(h.shape[0], shape.get("expert_rows") or h.shape[0])
    routed = sum(expert(h, w, idx, shape["first"] + e, lp["wg"][e],
                        lp["wu"][e], lp["wd"][e], cap)
                 for e in range(lp["wg"].shape[0]))
    n = shape["n_shared"]
    F = lp["shared_gate"].shape[1] // n
    shared = sum(shared_expert(h, lp["shared_gate"][:, i * F:(i + 1) * F],
                               lp["shared_up"][:, i * F:(i + 1) * F],
                               lp["shared_down"][i * F:(i + 1) * F])
                 for i in range(n)) / n
    return routed + shared


def block(x, lp, kind, shape, q_block):
    k, v = keys_values(x, lp["attn_norm"], lp["wk"], lp["wv"], kind, shape)
    a, h = attend(x, k, v, lp["attn_norm"], lp["wq"], lp["wo"], kind, shape,
                  q_block)
    return x + a + feed_forward(h, lp, shape)


@_highest
def readout(x, final_norm, embed, logit_scale):
    """x [n,D] -> logits [n,V] through the final LayerNorm and the tied
    embedding's held rows."""
    final_norm, embed = _f32(final_norm, embed)
    return logit_scale * (layer_norm(x, final_norm) @ embed.T)


def logits(params, tokens, shape, q_block: int = 0, rows=None):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence; S a
    multiple of q_block, which defaults to S).  `rows` [n] picks the
    positions whose logits are wanted: [n, V] (the layers still see the
    whole sequence; only the last product is over fewer rows)."""
    q_block = q_block or tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    for lp, kind in zip(params["layers"], shape["layer_types"]):
        x = block(x, lp, kind, shape, q_block)
    if rows is not None:
        x = x[rows]
    return readout(x, params["final_norm"], params["embed"],
                   shape["logit_scale"])
