"""DeepSeek-V3 (`deepseek_v3`) as published, in plain jax.numpy and float32
— the yardstick for `correct` of the cells that serve it.

Written from the layer equations of the published config
(https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json) and
the makers' description of the router, not from the program; imports
nothing from `ray_tpu`.  Per layer (RMSNorm eps 1e-6):

    h    = RMSNorm(x)
    c_q  = RMSNorm(h Wqa);  q = c_q Wqb -> H heads x (q_nope dn | q_pe dr)
    c_kv | k_pe = h Wkva;   c_kv = RMSNorm(c_kv)
    k_nope_h | v_h = c_kv Wkvb             H heads x (dn | dv)
    q_pe, k_pe <- RoPE over interleaved pairs at YaRN's frequencies; k_pe
            is one key part for all heads
    s_h[i,j] = (q_nope_h[i].k_nope_h[j] + q_pe_h[i].k_pe[j])
               * (dn + dr)^-1/2 * (0.1 ln factor + 1)^2,   j <= i
    x    = x + concat_h(softmax(s_h) v_h) Wo
    h2   = RMSNorm(x)
    dense layer (index < n_dense):  x = x + Wd(silu(Wg h2) * (Wu h2))
    expert layer: s = sigmoid(h2 Wr) over ALL experts;  c = s + b
        a group's rank = the sum of the two largest c in it; the
        `topk_group` best of `n_group` groups are kept; T = the top_k
        largest c inside them;  w_e = s_e / (sum_T s + 1e-20) * routed_scale
        x = x + sum_{e in T, e held} w_e SwiGLU_e(h2) + SwiGLU_shared(h2)
    logits = RMSNorm(x_L) Wout                 (untied head)

This is the PUBLISHED form: keys and values a head wide are formed from
the latent for every position; nothing is absorbed into the query or the
output, nothing is cached, no kernel, no batching; every matmul under
`jax.default_matmul_precision("highest")`.

The weights are this file's OWN draw from the seed (`draw_leaf`, `draw`):
the recipe the configuration's `weights.made` states — a leaf is drawn
`DRAW_PIECE` values at a time, piece i from the key
fold_in(fold_in(fold_in(root, 1 + layer), place), i), root the "rbg" key
of the words (0, seed, 0, seed) (the two vocabulary tables: layer -1), normal draws in float32 times scale /
sqrt(fan in), rounded to the parameters' dtype, the pieces laid end to
end and cut to the leaf's size, then the configuration's `scales`; the
correction bias a draw of its own — written again here, so that the
reference takes no array the program made.

Departures from the published description, each forced by what it is
compared with:
  * only the experts `first..first+held-1` are computed (the chip's share
    of an expert-parallel deployment, model-configs guide section 4): the
    router still scores all `n_experts` in their groups, and what absent
    experts would add is left out.  `held = n_experts` is the whole layer;
  * the vocabulary is the slice held (`vocab` rows);
  * the multi-token-prediction module is a training head and is left out;
  * RoPE turns interleaved pairs (2i, 2i+1), as DeepSeek's own inference
    code does; the `transformers` port first permutes the rope part so
    that its rotate-half form turns the same pairs;
  * a group outside the kept ones is masked with -inf (the makers'
    inference code); the `transformers` port writes 0.0 there, which
    differs only where a biased score is negative;
  * it is computed in pieces so that it fits beside the engine and inside
    a run's minute: the layer comes as `latents`, `attend`, `dense_part`,
    `route`, `expert`, `shared_expert`, `readout`, each upcasting only
    its own weights; attention a few heads at a time (each head's keys
    and values formed from the whole sequence's latents, as published)
    and a block of rows against the key blocks it can see, its softmax's
    maximum and sum carried from block to block (the same softmax: what
    lies behind every row is never scored); one held expert at a time
    over the rows routed to it, gathered to a static bound (an expert
    with more rows than that is computed over every row instead).
    Nothing that enters a sum is left out.

`shape["control"]` names a fault put into THIS computation on purpose (the
control runs that set the check's limits; never in a benchmark run).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS_TOPK = 1e-20
DRAW_PIECE = 1 << 22         # values a piece of a leaf: part of the recipe

# name -> place of a layer's leaf in the draw (the program's LEAVES)
PLACES = {n: i for i, n in enumerate(
    ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
     "router", "wg", "wu", "wd", "shared_gate", "shared_up", "shared_down"))}
BIAS_PLACE = len(PLACES)


def leaf_specs(sz: dict) -> dict:
    """name -> (shape, fan in, scale) of every drawn leaf of a layer."""
    D, H = sz["d_model"], sz["n_heads"]
    rq, rkv, dn, dr, dv = (sz["q_rank"], sz["kv_rank"], sz["d_nope"],
                           sz["d_rope"], sz["d_v"])
    F, Fe, C, S = sz["d_ff"], sz["d_expert"], sz["held"], sz["n_shared"]
    out = 1.0 / math.sqrt(2 * sz["n_layers"])
    return {
        "wq_a": ((D, rq), D, 1.0), "wq_b": ((rq, H, dn + dr), rq, 1.0),
        "wkv_a": ((D, rkv + dr), D, 1.0),
        "wkv_b": ((rkv, H, dn + dv), rkv, 1.0),
        "wo": ((H, dv, D), H * dv, out),
        "w_gate": ((D, F), D, 1.0), "w_up": ((D, F), D, 1.0),
        "w_down": ((F, D), F, out),
        "router": ((D, sz["n_experts"]), D, 1.0),
        "wg": ((C, D, Fe), D, 1.0), "wu": ((C, D, Fe), D, 1.0),
        "wd": ((C, Fe, D), Fe, out),
        "shared_gate": ((D, S * Fe), D, 1.0),
        "shared_up": ((D, S * Fe), D, 1.0),
        "shared_down": ((S * Fe, D), Fe, out),
    }


def _dtype(name):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _piece(seed, layer, place, i, std, n, dtype):
    """Piece i of a leaf: n normal draws x std, rounded to dtype.  One
    program for every leaf of every layer: what differs is an operand."""
    words = jax.random.PRNGKey(seed)            # [0, seed]
    key = jax.random.wrap_key_data(jnp.concatenate([words, words]),
                                   impl="rbg")
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, 1 + layer), place), i)
    return (jax.random.normal(key, (n,), jnp.float32) * std).astype(
        _dtype(dtype))


@functools.partial(jax.jit, static_argnames=("shape", "factor"))
def _join(parts, shape, factor):
    w = jnp.concatenate(parts)[:math.prod(shape)].reshape(shape)
    return w * factor if factor != 1 else w


def _normal(seed, layer, place, shape, std, dtype, factor):
    n = -(-math.prod(shape) // DRAW_PIECE)
    return _join([_piece(seed, layer, place, i, jnp.float32(std), DRAW_PIECE,
                         dtype) for i in range(n)], shape, factor)


def draw_leaf(seed: int, sz: dict, weights: dict, layer: int, name: str):
    """One leaf as the replica's loader makes it: `layer` -1 holds the two
    vocabulary tables; `router_bias` is the loader's own draw."""
    seed, pd = seed % (2 ** 31), sz["param_dtype"]
    scales = weights.get("scales", {})
    if layer < 0:
        V, D = sz["vocab"], sz["d_model"]
        shape, std, place = {"embed": ((V, D), 0.02, 0),
                             "unembed": ((D, V), 1.0 / math.sqrt(D), 1)}[name]
        return _normal(seed, -1, place, shape, std, pd, scales.get(name, 1))
    if name == "router_bias":
        return _normal(seed, layer, BIAS_PLACE, (sz["n_experts"],),
                       float(weights.get("router_bias_std", 0.0)), "float32",
                       1)
    shape, fan_in, scale = leaf_specs(sz)[name]
    return _normal(seed, layer, PLACES[name], shape,
                   scale / math.sqrt(fan_in),
                   "float32" if name == "router" else pd,
                   scales.get(name, 1))


def layer_leaves(sz: dict, layer: int):
    mla = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    if layer < sz["n_dense"]:
        return mla + ("w_gate", "w_up", "w_down")
    return mla + ("router", "router_bias", "wg", "wu", "wd", "shared_gate",
                  "shared_up", "shared_down")


def draw(seed: int, sz: dict, weights: dict) -> dict:
    """The whole tree (small sizes: a test)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for l in range(sz["n_layers"]):
        lp = {n: draw_leaf(seed, sz, weights, l, n)
              for n in layer_leaves(sz, l)}
        lp.update(attn_norm=ones(sz["d_model"]), q_norm=ones(sz["q_rank"]),
                  kv_norm=ones(sz["kv_rank"]), mlp_norm=ones(sz["d_model"]))
        layers.append(lp)
    return {"embed": draw_leaf(seed, sz, weights, -1, "embed"),
            "unembed": draw_leaf(seed, sz, weights, -1, "unembed"),
            "final_norm": ones(sz["d_model"]), "layers": layers}


# ---------------------------------------------------------------------------


def _highest(fn):
    """Every matmul of a piece runs at the highest precision, whether the
    piece is called eagerly, under `jit`, or alone."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _fp8(w):
    """Rounded to fp8-e4m3 in arithmetic (3 mantissa bits, exponents down
    to 2^-6, subnormals below; the v5e has no such type and a pair of
    converts through one is the identity there)."""
    a = jnp.abs(w)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9)))
    step = 2.0 ** (jnp.maximum(e, -6.0) - 3.0)
    return jnp.clip(jnp.round(w / step) * step, -448.0, 448.0)


def _f32(sz, *arrays):
    out = tuple(a.astype(jnp.float32) for a in arrays)
    if sz.get("control") == "fp8_weights":
        out = tuple(_fp8(a) if a.ndim > 1 else a for a in out)
    return out


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def yarn_inv_freq(sz: dict):
    """[dr/2]: pair i turns by pos * this.  Below `low` the plain
    theta^(-2i/dr), above `high` that over `factor`, a linear ramp in i
    between (low, high: where a pair completes beta_fast, beta_slow turns
    in the original context)."""
    dr, theta = sz["d_rope"], sz["theta"]
    plain = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    if not sz.get("yarn"):
        return plain
    factor, fast, slow, orig = sz["yarn"]
    at = lambda turns: (dr * math.log(orig / (turns * 2 * math.pi))
                        / (2 * math.log(theta)))
    low = max(math.floor(at(fast)), 0)
    high = min(math.ceil(at(slow)), dr - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def softmax_scale(sz: dict) -> float:
    m = 0.1 * math.log(sz["yarn"][0]) + 1.0 if sz.get("yarn") else 1.0
    if sz.get("control") == "no_yarn_scale":
        m = 1.0
    return (sz["d_nope"] + sz["d_rope"]) ** -0.5 * m * m


def rope(x, sz):
    """x [n, ..., dr] at positions 0..n-1: pair (2i, 2i+1) turned."""
    n = x.shape[0]
    ang = (jnp.arange(n).astype(jnp.float32).reshape(
        (n,) + (1,) * (x.ndim - 1)) * yarn_inv_freq(sz))
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


# The layer in pieces, each a function of the arrays it needs and each
# upcasting its own weights: `block` and `logits` compose them, and so can a
# caller that has to bound memory (one piece a program: check_deepseek_v3).


@_highest
def latents(x, attn_norm, wkv_a, kv_norm, sz):
    """What the rows x [N, D] (positions 0..) give every later query:
    (c_kv [N, rkv] normed, k_pe [N, dr] turned)."""
    wkv_a, = _f32(sz, wkv_a)
    a = rms_norm(x, attn_norm.astype(jnp.float32), sz["eps"]) @ wkv_a
    rkv = sz["kv_rank"]
    c_kv = rms_norm(a[:, :rkv], kv_norm.astype(jnp.float32), sz["eps"])
    k_pe = rope(a[:, rkv:], sz)
    if sz.get("control") == "latent_8bit":
        c_kv, k_pe = _fp8(c_kv), _fp8(k_pe)
    return c_kv, k_pe


@_highest
def attend(x, c_kv, k_pe, attn_norm, wq_a, q_norm, wq_b, wkv_b, wo, sz,
           heads: int = 0, rows: int = 0, blocks=None, first=0):
    """The sequence's rows x [N, D] (positions 0..N-1) against its latents
    c_kv [N, rkv], k_pe [N, dr] -> x + attention's addition [N, D].

    As published, every head's keys and values are formed from the latents
    (`heads` heads at a time; 0: all at once).  Rows are taken `rows` at a
    time (0: all at once; N a multiple of it) and only the blocks `first`
    .. `blocks` - 1 (may be traced; None: to the end) — the others come
    back as they came: a caller who needs the last layer's output for the
    last rows only forms no other — and a block of rows meets the keys
    block by block, only as far
    as it can see: the softmax is the same one, its maximum and its sum
    carried from key block to key block (exp(s - m) rescaled when m
    grows), so what lies behind every row is never scored."""
    wq_a, wq_b, wkv_b, wo = _f32(sz, wq_a, wq_b, wkv_b, wo)
    N = x.shape[0]
    H, dn, dv = sz["n_heads"], sz["d_nope"], sz["d_v"]
    g, n = heads or H, rows or N
    if blocks is None:
        blocks = N // n
    h = rms_norm(x, attn_norm.astype(jnp.float32), sz["eps"])
    c_q = rms_norm(h @ wq_a, q_norm.astype(jnp.float32), sz["eps"])
    scale = softmax_scale(sz)
    cut = lambda a, j: jax.lax.dynamic_slice_in_dim(a, j * n, n, 0)
    low = jnp.float32(-1e30)

    def some_heads(acc, a):
        wq, w, w_o = a              # [rq,g,dn+dr] [rkv,g,dn+dv] [g,dv,D]
        q = jnp.einsum("nr,rhk->nhk", c_q, wq)
        q_nope, q_pe = q[..., :dn], rope(q[..., dn:], sz)
        k_nope = jnp.einsum("sr,rhk->shk", c_kv, w[..., :dn])
        v = jnp.einsum("sr,rhk->shk", c_kv, w[..., dn:])

        def row_block(b, acc):
            qn, qp, i = cut(q_nope, b), cut(q_pe, b), b * n + jnp.arange(n)

            def key_block(j, carry):
                m, l, o = carry                     # [g,n] [g,n] [g,n,dv]
                s = jnp.einsum("nhk,shk->hns", qn, cut(k_nope, j))
                if sz.get("control") != "no_rope_score":
                    s = s + jnp.einsum("nhk,sk->hns", qp, cut(k_pe, j))
                key_at = j * n + jnp.arange(n)
                see = key_at[None, :] <= i[:, None]
                if sz.get("control") == "chunk_blind":  # earlier chunks lost
                    ch = sz["control_chunk"]
                    see = see & (key_at[None, :] >= (i[:, None] // ch) * ch)
                s = jnp.where(see[None], s * scale, low)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.where(see[None], jnp.exp(s - m_new[..., None]), 0.0)
                fade = jnp.exp(m - m_new)
                return (m_new, l * fade + p.sum(-1),
                        o * fade[..., None]
                        + jnp.einsum("hns,shk->hnk", p, cut(v, j)))

            _, l, o = jax.lax.fori_loop(
                0, b + 1, key_block,
                (jnp.full((g, n), low), jnp.zeros((g, n), jnp.float32),
                 jnp.zeros((g, n, dv), jnp.float32)))
            out = jnp.einsum("hnk,hkd->nd", o / l[..., None], w_o)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, cut(acc, b) + out, b * n, 0)

        return jax.lax.fori_loop(first, blocks, row_block, acc), None

    split = lambda a, ax: jnp.moveaxis(
        a.reshape(a.shape[:ax] + (H // g, g) + a.shape[ax + 1:]), ax, 0)
    out, _ = jax.lax.scan(some_heads, x, (split(wq_b, 1), split(wkv_b, 1),
                                          split(wo, 0)))
    return out


@_highest
def normed(x, w, sz):
    return rms_norm(x, w.astype(jnp.float32), sz["eps"])


def _part(w, i, parts: int, axis: int):
    """Part i of `parts` equal slices of w along `axis` (i may be traced:
    one program whatever the part)."""
    size = w.shape[axis] // parts
    return jax.lax.dynamic_slice_in_dim(w, i * size, size, axis)


@_highest
def dense_part(h, wg, wu, wd, sz, i=0, parts: int = 1):
    """Slice i of `parts` of the dense SwiGLU's width: columns of Wg, Wu
    and the same rows of Wd; the slices' results add up to the layer's."""
    return swiglu(h, *_f32(sz, _part(wg, i, parts, 1), _part(wu, i, parts, 1),
                           _part(wd, i, parts, 0)))


@_highest
def route(h, router, bias, sz):
    """(w [n, k], idx [n, k]): the biased scores choose inside the kept
    groups, the scores alone weigh."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))          # [n, E]
    n, E = s.shape
    G, k = sz["n_group"], sz["top_k"]
    c = s + bias.astype(jnp.float32)
    if sz.get("control") != "no_groups":
        by_group = c.reshape(n, G, E // G)
        rank = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        kept = jax.lax.top_k(rank, sz["topk_group"])[1]         # [n, kept]
        keep = jnp.any(kept[:, :, None] == jnp.arange(G), axis=1)
        c = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(n, E)
    idx = jax.lax.top_k(c, k)[1]
    w = jnp.take_along_axis(s, idx, axis=1)
    return (w / (jnp.sum(w, -1, keepdims=True) + EPS_TOPK)
            * sz["routed_scale"], idx)


@_highest
def expert(h, w, idx, e, wg, wu, wd, sz, cap: int = 0):
    """Expert e's weighted part of the routed sum, [n, D] (wg, wu, wd the
    HELD experts' stacks: e - first picks its matrices): over the rows
    routed to it, gathered (a static `cap` of them), through e, scattered
    back; where more than `cap` rows fall on e — or no cap is given — over
    every row with the others' gates at zero.  The same sum either way."""
    wg, wu, wd = _f32(sz, *(jax.lax.dynamic_index_in_dim(
        a, e - sz["first"], 0, keepdims=False) for a in (wg, wu, wd)))
    n = h.shape[0]
    gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # [n]
    on = jnp.any(idx == e, axis=-1)

    def gathered():
        rows = jnp.nonzero(on, size=cap, fill_value=n)[0]
        y = swiglu(h.at[rows].get(mode="fill", fill_value=0.0), wg, wu, wd)
        y = y * gate.at[rows].get(mode="fill", fill_value=0.0)[:, None]
        return jnp.zeros_like(h).at[rows].add(y, mode="drop")

    def every_row():
        return gate[:, None] * swiglu(h, wg, wu, wd)

    if not cap or cap >= n:
        return every_row()
    return jax.lax.cond(jnp.sum(on) > cap, every_row, gathered)


@_highest
def shared_expert(h, wg, wu, wd, sz, i=0):
    """Shared expert i of the n_shared laid side by side."""
    n = sz["n_shared"]
    return swiglu(h, *_f32(sz, _part(wg, i, n, 1), _part(wu, i, n, 1),
                           _part(wd, i, n, 0)))


@_highest
def readout(x, final_norm, unembed, sz, i=0, parts: int = 1):
    """x [n, D] -> logits [n, V / parts]: slice i of the head's columns."""
    unembed, = _f32(sz, _part(unembed, i, parts, 1))
    return rms_norm(x, final_norm.astype(jnp.float32), sz["eps"]) @ unembed


def feed_forward(h, lp, sz):
    if "router" not in lp:
        return dense_part(h, lp["w_gate"], lp["w_up"], lp["w_down"], sz)
    w, idx = route(h, lp["router"], lp["router_bias"], sz)
    routed = sum(expert(h, w, idx, sz["first"] + e, lp["wg"], lp["wu"],
                        lp["wd"], sz) for e in range(lp["wg"].shape[0]))
    return routed + sum(
        shared_expert(h, lp["shared_gate"], lp["shared_up"],
                      lp["shared_down"], sz, i)
        for i in range(sz["n_shared"]))


def block(x, lp, sz):
    c_kv, k_pe = latents(x, lp["attn_norm"], lp["wkv_a"], lp["kv_norm"], sz)
    x = attend(x, c_kv, k_pe, lp["attn_norm"], lp["wq_a"], lp["q_norm"],
               lp["wq_b"], lp["wkv_b"], lp["wo"], sz)
    return x + feed_forward(normed(x, lp["mlp_norm"], sz), lp, sz)


def logits(params, tokens, sz):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence)."""
    x = params["embed"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = block(x, lp, sz)
    return readout(x, params["final_norm"], params["unembed"], sz)
