"""Brumby-14B-Base (`model_type: brumby`) in plain jax.numpy and float32 —
the yardstick for `correct` of the cells that serve it.

Written from the layer equations, not from the program; imports nothing
from `ray_tpu`.  The published config
(https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json)
gives the sizes: d 5120, 40 query heads on 8 K/V heads of 128, SwiGLU of
17,408, `rms_norm_eps` 1e-6, `rope_theta` 1e6, untied head, no biases.
It is silent on the retention layer itself; what is computed here is the
power-retention layer of its makers' description (arXiv:2507.04239) at
degree p = 2 on the base recipe the model was retrained from (per-head
RMSNorm of q and k, rotate-half RoPE), as the configuration file's
`assumed` lists.  Per layer, K/V head h // G serving query head h:

    n   = RMSNorm(x)
    q   = RMSNorm_h(n Wq), k = RMSNorm_h(n Wk)   per head, over dh
    v   = n Wv
    q,k = RoPE(q, k; position, theta)            halves (i, i + dh/2)
    lg  = log sigmoid(n Wg + b)                  per K/V head, float32
    a_ij = exp(sum_{l=j+1..i} lg_l) (q_i . k_j)^2        j <= i
    o_i = sum_j a_ij v_j / (sum_j a_ij + eps)
    h   = x + concat(o) Wo
    y   = h + Wd(silu(Wg' RMSNorm(h)) * (Wu RMSNorm(h)))
    logits = RMSNorm(x_L) Wout

`a` is formed over all pairs: no feature map, no state, no chunks, no
cache.  Every matmul runs under `jax.default_matmul_precision("highest")`.

Departures from the description, each forced by what it is compared with:
  * no fixed scale stands inside the square (one cancels in the quotient);
  * it comes in pieces (`project`, `retain`, `mix`, `readout`) so that
    the check can run it in blocks of rows beside the engine, each piece
    upcasting only its own weights; `retain` scores a block of query rows
    against every key.  Nothing that enters a sum is left out.

Parameters are read from a dict in the layout the program's `brumby.init`
produces (data, not an import): `embed [V,D]`, `unembed [D,V]`,
`final_norm [D]`, and `layers`, a dict whose leaves carry the layer as
their first axis: `attn_norm [L,D]`, `wq [L,D,H,dh]`, `wk`,
`wv [L,D,Hkv,dh]`, `q_norm`, `k_norm [L,dh]`, `wg [L,D,Hkv]`,
`bg [L,Hkv]`, `wo [L,H,dh,D]`, `mlp_norm [L,D]`, `w_gate`,
`w_up [L,D,F]`, `w_down [L,F,D]` (`layer(params, l)` is layer l's).
`shape` is a dict: eps (the norms'), theta, ret_eps (the quotient's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_halves(x, theta, i0=0):
    """x [n, H, dh] at positions i0..i0+n-1: the pair (i, i + dh/2) turned
    by the angle pos * theta^(-2i/dh)."""
    n, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (i0 + jnp.arange(n)).astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def project(x, attn_norm, wq, wk, wv, q_norm, k_norm, wg, bg, shape, i0=0):
    """Rows x [n, D] at positions i0.. -> q [n,H,dh], k, v [n,Hkv,dh],
    lg [n,Hkv] (log of the gate)."""
    with jax.default_matmul_precision("highest"):
        x, attn_norm, wq, wk, wv, q_norm, k_norm, wg, bg = _f32(
            x, attn_norm, wq, wk, wv, q_norm, k_norm, wg, bg)
        n = rms(x, attn_norm, shape["eps"])
        q = rms(jnp.einsum("nd,dhk->nhk", n, wq), q_norm, shape["eps"])
        k = rms(jnp.einsum("nd,dhk->nhk", n, wk), k_norm, shape["eps"])
        v = jnp.einsum("nd,dhk->nhk", n, wv)
        lg = jax.nn.log_sigmoid(n @ wg + bg)
        return (rope_halves(q, shape["theta"], i0),
                rope_halves(k, shape["theta"], i0), v, lg)


def retain(q, k, v, c, shape, i0=0):
    """Query rows q [n,H,dh] at positions i0.. against every key: k, v
    [S,Hkv,dh], c [S,Hkv] the running sum of lg over positions 0..j.
    -> [n,H,dh]."""
    with jax.default_matmul_precision("highest"):
        n, H, dh = q.shape
        S, Hkv = k.shape[0], k.shape[1]
        G = H // Hkv
        i = i0 + jnp.arange(n)
        j = jnp.arange(S)
        ci = jax.lax.dynamic_slice_in_dim(c, i0, n, 0)          # [n, Hkv]
        qg = q.reshape(n, Hkv, G, dh)
        s = jnp.einsum("nhgd,jhd->hgnj", qg, k)
        see = (j[None, :] <= i[:, None])[None]                  # [1, n, S]
        decay = jnp.exp(jnp.where(see, ci.T[:, :, None] - c.T[:, None, :],
                                  -jnp.inf))                    # [Hkv, n, S]
        a = s * s * decay[:, None]
        o = jnp.einsum("hgnj,jhd->nhgd", a, v)
        den = jnp.moveaxis(a.sum(-1), 2, 0)[..., None]          # [n,Hkv,G,1]
        return (o / (den + shape["ret_eps"])).reshape(n, H, dh)


def mix(x, o, wo, mlp_norm, w_gate, w_up, w_down, shape):
    """x [n, D] and the heads' outputs o [n,H,dh] -> the layer's output.
    `w_gate`, `w_up`, `w_down` may be lists of column / row slices of the
    SwiGLU (a slice at a time: the check's memory)."""
    with jax.default_matmul_precision("highest"):
        x, o, wo, mlp_norm = _f32(x, o, wo, mlp_norm)
        h = x + jnp.einsum("nhk,hkd->nd", o, wo)
        m = rms(h, mlp_norm, shape["eps"])
        if not isinstance(w_gate, (list, tuple)):
            w_gate, w_up, w_down = [w_gate], [w_up], [w_down]
        y = h
        for g, u, d in zip(w_gate, w_up, w_down):
            g, u, d = _f32(g, u, d)
            y = y + (jax.nn.silu(m @ g) * (m @ u)) @ d
        return y


def readout(x, final_norm, unembed, shape):
    with jax.default_matmul_precision("highest"):
        x, final_norm, unembed = _f32(x, final_norm, unembed)
        return rms(x, final_norm, shape["eps"]) @ unembed


def layer(params, l):
    return {k: w[l] for k, w in params["layers"].items()}


def hidden(params, tokens, shape):
    """tokens [S] -> final hidden rows [S, D]."""
    x = params["embed"][tokens].astype(jnp.float32)
    for l in range(params["layers"]["wq"].shape[0]):
        lp = layer(params, l)
        q, k, v, lg = project(x, lp["attn_norm"], lp["wq"], lp["wk"],
                              lp["wv"], lp["q_norm"], lp["k_norm"],
                              lp["wg"], lp["bg"], shape)
        o = retain(q, k, v, jnp.cumsum(lg, axis=0), shape)
        x = mix(x, o, lp["wo"], lp["mlp_norm"], lp["w_gate"], lp["w_up"],
                lp["w_down"], shape)
    return x


def forward(params, tokens, shape):
    """tokens [S] -> logits [S, V] float32."""
    return readout(hidden(params, tokens, shape), params["final_norm"],
                   params["unembed"], shape)
