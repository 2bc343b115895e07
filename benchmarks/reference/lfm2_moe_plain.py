"""LFM2-MoE (`lfm2_moe`: gated short convolutions as the mixer three
layers in four, grouped-query attention with normed heads in the fourth,
bias-selected sigmoid experts behind leading dense layers) as its
configuration describes it, in plain jax.numpy and float32 — the yardstick
for `correct` of the cells that serve it.

Written from the layer equations, not from the program; imports nothing
from `ray_tpu`.  The draw's piece generator and the fp8 control are
`deepseek_v3_plain`'s, the leaf drawn from pieces `ling3_plain`'s: all are
this benchmark's own.  Per layer l, RMSNorm(x) = x / sqrt(mean(x^2) + eps)
* g:

    h'  = h  + Mix_l(RMSNorm_op(h))      `layer_types[l]`: conv | attention
    h'' = h' + FF_l(RMSNorm_ffn(h'))     l < n_dense: SwiGLU | the experts

    conv — [a | c | x] = u W_in (no bias); z = a * x;
      y_t = sum_{i=0..2} w_i * z_{t-2+i} (depthwise, causal, tap 2 meets
      the row itself, no bias, NO activation): three shifted sums over the
      whole sequence; out = (c * y) W_out.
    attention — [q | k | v] = u W_qkv; q, k <- RMSNorm over each head's
      d_head (ONE weight [d_head] for the query heads, one for the key
      heads); rotate-half RoPE (theta) on all dims; explicit causal softmax
      over masked scores at d_head^-1/2, query head i on key head
      i // (H / Hkv); W_o.
    experts — s = sigmoid(u W_r) over ALL experts; expert e is chosen where
      fewer than top_k experts j have s_j + b_j > s_e + b_e or the same
      with j < e (the bias CHOOSES, a tie goes to the lower index);
      weight_e = s_e / (sum of the chosen s + 1e-6) x routed_scale (s alone
      WEIGHS); out = sum_e weight_e W2_e (silu(W1_e u) * W3_e u): a loop
      over the held experts with a dense mask — no sort, no grouped
      product.
    logits = RMSNorm_final(h_L) E^T;  h_0 = E[token]    (tied)

The weights are this file's OWN draw from the seed (`draw_leaf`): the
recipe the configuration's `weights.made` states, written a second time.

Departures, each forced by what it is compared with: only the experts
`first..first+held-1` are computed (a chip's share; `held` = n_experts is
the whole layer, which is what the benchmark's configuration holds); it is
computed in pieces (`mixer_layer`, `ffn_layer`, `readout`: a program each,
one layer's weights at a time) so that it fits beside the engine and
inside a run's minute; attention and the feed-forward take rows a block at
a time — a block of query rows against EVERY key.  Nothing that enters a
sum is left out.

`shape["control"]` names a fault put into THIS computation on purpose
(never in a benchmark run): `fp8_weights` (deepseek_v3_plain's).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import deepseek_v3_plain as dsp
from .deepseek_v3_plain import _f32, _highest, rms_norm
from .falcon_h1_plain import _cut, rope
from .ling3_plain import _normal

TAPS = 3
ROUTE_EPS = 1e-6

# name -> place of a layer's leaf in the draw (the program's LEAVES)
LEAVES = ("w_in", "conv_w", "w_out", "w_qkv", "wo", "w_gate_up", "w_down",
          "router", "wg", "wu", "wd")
PLACES = {n: i for i, n in enumerate(LEAVES)}
BIAS_PLACE = len(LEAVES)
MIXER_LEAVES = {"conv": ("w_in", "conv_w", "w_out"),
                "full_attention": ("w_qkv", "wo")}
FFN_LEAVES = {True: ("w_gate_up", "w_down"),
              False: ("router", "router_bias", "wg", "wu", "wd")}


def is_dense(sz: dict, layer: int) -> bool:
    return layer < sz["n_dense"]


def leaf_specs(sz: dict) -> dict:
    """name -> (shape, fan in, scale) of every normally drawn leaf."""
    D, F, Fe, C = sz["d_model"], sz["d_ff"], sz["d_expert"], sz["held"]
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    out = 1.0 / math.sqrt(2 * sz["n_layers"])
    return {
        "w_in": ((D, 3 * D), D, 1.0), "conv_w": ((TAPS, D), TAPS, 1.0),
        "w_out": ((D, D), D, out),
        "w_qkv": ((D, (H + 2 * Hkv) * dh), D, 1.0),
        "wo": ((H * dh, D), H * dh, out),
        "w_gate_up": ((D, 2 * F), D, 1.0), "w_down": ((F, D), F, out),
        "router": ((D, sz["n_experts"]), D, 1.0),
        "wg": ((C, D, Fe), D, 1.0), "wu": ((C, D, Fe), D, 1.0),
        "wd": ((C, Fe, D), Fe, out),
    }


def draw_leaf(seed: int, sz: dict, weights: dict, layer: int, name: str):
    """One leaf as the replica's loader makes it: `layer` -1 holds the
    embedding (the head too: tied).  The plain draw (the program's `init`),
    then the configuration's `weights`: a leaf named in `scales` times its
    factor; `router_bias` the loader's own draw (normal x
    `router_bias_std`, float32, at the place after the last leaf)."""
    seed, pd = seed % (2 ** 31), sz["param_dtype"]
    scales = weights.get("scales", {})
    if layer < 0:
        return draw_rows(seed, sz, weights)
    if name == "router_bias":
        return _normal(seed, layer, BIAS_PLACE, (sz["n_experts"],),
                       float(weights.get("router_bias_std", 0.0)),
                       "float32", 1)
    shape, fan_in, scale = leaf_specs(sz)[name]
    return _normal(seed, layer, PLACES[name], shape,
                   scale / math.sqrt(fan_in),
                   "float32" if name == "router" else pd,
                   scales.get(name, 1))


@functools.partial(jax.jit, static_argnames=("count", "n", "dtype"))
def _piece_run(seed, std, first, count, n, dtype):
    """Pieces first..first+count-1 of the embedding laid end to end."""
    return jax.lax.map(
        lambda i: dsp._piece(seed, -1, 0, first + i, std, n, dtype),
        jnp.arange(count)).reshape(-1)


def draw_rows(seed: int, sz: dict, weights: dict, i: int = 0,
              parts: int = 1):
    """Slice i of `parts` of the embedding's rows ([V, D], place 0 of layer
    -1, std 0.02) as the loader makes it: the pieces of the draw that hold
    those rows and no others."""
    seed, n = seed % (2 ** 31), dsp.DRAW_PIECE
    V, D = sz["vocab"], sz["d_model"]
    rows = V // parts
    lo, hi = i * rows * D, (i + 1) * rows * D
    first = lo // n
    flat = _piece_run(seed, jnp.float32(0.02), first, -(-hi // n) - first, n,
                      sz["param_dtype"])
    w = flat[lo - first * n:hi - first * n].reshape(rows, D)
    factor = weights.get("scales", {}).get("embed", 1)
    return w * factor if factor != 1 else w


def fixed_leaves(sz: dict, weights: dict) -> dict:
    """What the recipe does not draw: every norm weight (ones; the two
    head norms times `scales.q_norm` / `scales.k_norm`)."""
    D, dh, f = sz["d_model"], sz["d_head"], jnp.float32
    scales = weights.get("scales", {})
    return {"norm": jnp.ones(D, f), "ffn_norm": jnp.ones(D, f),
            "q_norm": jnp.ones(dh, f) * scales.get("q_norm", 1),
            "k_norm": jnp.ones(dh, f) * scales.get("k_norm", 1)}


def draw(seed: int, sz: dict, weights: dict) -> dict:
    """The whole tree (small sizes: a test)."""
    layers = []
    for l, kind in enumerate(sz["layer_types"]):
        names = MIXER_LEAVES[kind] + FFN_LEAVES[is_dense(sz, l)]
        layers.append({**{n: draw_leaf(seed, sz, weights, l, n)
                          for n in names}, **fixed_leaves(sz, weights)})
    return {"embed": draw_leaf(seed, sz, weights, -1, "embed"),
            "final_norm": jnp.ones(sz["d_model"], jnp.float32),
            "layers": layers}


# ---------------------------------------------------------------------------


@_highest
def conv_layer(x, lp, sz, stops=None):
    """The sequence's rows x [S, D] (positions 0..S-1) -> x + the gated
    short convolution's addition: three shifted sums over the whole
    sequence.  With `stops` [n] (may be traced) -> (that, [n, 2, D]: for
    each stop the rows stop-2 and stop-1 of z, what a sequence that has
    taken `stop` positions keeps of this layer; zeros stand before
    position 0)."""
    S, D = x.shape
    w_in, conv_w, w_out = _f32(sz, lp["w_in"], lp["conv_w"], lp["w_out"])
    h = rms_norm(x, lp["norm"].astype(jnp.float32), sz["eps"])
    p = h @ w_in
    a, c, xg = p[:, :D], p[:, D:2 * D], p[:, 2 * D:]
    z = jnp.pad(a * xg, ((TAPS - 1, 0), (0, 0)))
    y = sum(conv_w[i] * z[i:i + S] for i in range(TAPS))
    out = x + (c * y) @ w_out
    if stops is None:
        return out
    return out, jax.vmap(lambda at: jax.lax.dynamic_slice_in_dim(
        z, at, TAPS - 1, 0))(stops)


@_highest
def attention_layer(x, lp, sz, rows: int = 0, blocks=None,
                    keys: bool = False):
    """x [S, D] -> x + attention's addition: every key, a block of `rows`
    query rows at a time (0: all at once; S a multiple of it), the blocks
    0..`blocks`-1 (may be traced; None: to the end); the others come back
    as they came.  With `keys` -> (that, the keys as a cache would hold
    them [S, Hkv * d_head]: normed a head and turned)."""
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    S = x.shape[0]
    n = rows or S
    if blocks is None:
        blocks = S // n
    w_qkv, wo = _f32(sz, lp["w_qkv"], lp["wo"])
    f = lambda k: lp[k].astype(jnp.float32)
    h = rms_norm(x, f("norm"), sz["eps"])
    at = jnp.arange(S)
    p = h @ w_qkv
    q = p[:, :H * dh].reshape(S, H, dh)
    k = p[:, H * dh:(H + Hkv) * dh].reshape(S, Hkv, dh)
    v = p[:, (H + Hkv) * dh:].reshape(S, Hkv, dh)
    q = rope(rms_norm(q, f("q_norm"), sz["eps"]), at, sz["theta"])
    k = rope(rms_norm(k, f("k_norm"), sz["eps"]), at, sz["theta"])
    q = q.reshape(S, Hkv, H // Hkv, dh)
    cut = _cut(n)

    def block(b, acc):
        rows_at = (b * n + jnp.arange(n))[:, None]
        see = at[None, :] <= rows_at
        s = jnp.einsum("njgd,sjd->jgns", cut(q, b), k) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("jgns,sjd->njgd", pr, v).reshape(n, H * dh)
        return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + o @ wo,
                                                   b * n, 0)

    out = jax.lax.fori_loop(0, blocks, block, x)
    return (out, k.reshape(S, Hkv * dh)) if keys else out


def route(u, router, bias, sz):
    """u [n, D] normed -> the experts' weights [n, E] (zero where an
    expert is not chosen): sigmoid scores, the `top_k` largest of score +
    bias chosen by COUNTING who stands before whom (a tie to the lower
    index), the scores alone normalised over the chosen."""
    E = sz["n_experts"]
    s = jax.nn.sigmoid(u @ router.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)
    e = jnp.arange(E)
    other, own = c[:, None, :], c[:, :, None]
    before = (other > own) | ((other == own) & (e[None, :] < e[:, None]))
    chosen = jnp.sum(before, -1) < sz["top_k"]          # [n, E]
    w = jnp.where(chosen, s, 0.0)
    return w / (jnp.sum(w, -1, keepdims=True) + ROUTE_EPS) * sz["routed_scale"]


@_highest
def ffn_layer(x, lp, sz, dense: bool, rows: int = 0, blocks=None):
    """x + the feed-forward of RMSNorm_ffn(x), a block of rows at a time:
    a SwiGLU (one fused gate-and-up matrix, gate first), or the held
    experts one after the other, each on EVERY row of the block under its
    dense mask of weights."""
    S = x.shape[0]
    n = rows or S
    if blocks is None:
        blocks = S // n
    cut = _cut(n)
    norm = lp["ffn_norm"].astype(jnp.float32)
    if dense:
        F = sz["d_ff"]
        wgu, wd = _f32(sz, lp["w_gate_up"], lp["w_down"])

        def block(b, acc):
            gu = rms_norm(cut(acc, b), norm, sz["eps"]) @ wgu
            y = (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ wd
            return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + y,
                                                       b * n, 0)

        return jax.lax.fori_loop(0, blocks, block, x)

    first, held = sz["first"], sz["held"]

    def block(b, acc):
        u = rms_norm(cut(acc, b), norm, sz["eps"])
        w = route(u, lp["router"], lp["router_bias"], sz)

        def expert(e, y):
            # one expert's matrices at a time in float32: a layer's are
            # 1.4e9 B so
            wg, wu, wd = _f32(sz, lp["wg"][e], lp["wu"][e], lp["wd"][e])
            we = jax.lax.dynamic_index_in_dim(w, first + e, 1,
                                              keepdims=True)
            return y + we * ((jax.nn.silu(u @ wg) * (u @ wu)) @ wd)

        y = jax.lax.fori_loop(0, held, expert, jnp.zeros_like(u))
        return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + y,
                                                   b * n, 0)

    return jax.lax.fori_loop(0, blocks, block, x)


@_highest
def readout(x, final_norm, embed, sz):
    """x [n, D] -> logits [n, rows of `embed`]: the embedding [V, D] (the
    head is tied to it), or a slice of its rows."""
    table, = _f32(sz, embed)
    return rms_norm(x, final_norm.astype(jnp.float32), sz["eps"]) @ table.T


def mixer_layer(x, lp, sz, kind: str, rows: int = 0, blocks=None):
    if kind == "conv":
        return conv_layer(x, lp, sz)
    return attention_layer(x, lp, sz, rows, blocks)


def logits(params, tokens, sz):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence)."""
    x = params["embed"][tokens].astype(jnp.float32)
    for l, (lp, kind) in enumerate(zip(params["layers"], sz["layer_types"])):
        x = ffn_layer(mixer_layer(x, lp, sz, kind), lp, sz, is_dense(sz, l))
    return readout(x, params["final_norm"], params["embed"], sz)
