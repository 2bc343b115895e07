"""dots3-note-prev's language model (`dots3_note`) in plain jax.numpy and
float32 — the yardstick for `correct` of the cells that serve it.

Written from the layer equations the configuration file states (its keys are
https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json's;
what is an inference is under the file's `assumed`), not from the program;
imports nothing from `ray_tpu`.  RMSNorm with weight, eps 1e-5.  Layer kinds
by `layer_types`.

A full layer (H heads of dn | dr, values dv, ranks rq and rkv, theta):

    h     = RMSNorm(x)
    c_q   = RMSNorm(h Wqa) * sqrt(D / rq);  q = c_q Wqb -> H x (q_nope | q_pe)
    c_kv | k_pe = h Wkva;   c_kv = RMSNorm(c_kv) * sqrt(D / rkv)
    k_nope_h | v_h = c_kv Wkvb;  RoPE over interleaved pairs on q_pe (every
            head) and the one k_pe
    indexer (Hi heads of di; RoPE on the first dr of di):
      qi  = c_q Wiq -> Hi x di;   ki = LayerNorm(h Wik) -> di   (eps 1e-6)
      w   = (h Wiw) * Hi^-1/2 * di^-1/2
      I[t,s] = sum_j w[t,j] relu(qi[t,j] . ki[s]),   s <= t
      S_t = `jax.lax.top_k` of I[t, :t+1], `index_topk` of them (a tie to
            the lower s); every s <= t while t < index_topk
    s_h[t,s] = (q_nope_h[t].k_nope_h[s] + q_pe_h[t].k_pe[s]) (dn + dr)^-1/2
               for s in S_t
    o_h   = softmax_s(s_h) v_h;   g = sigmoid(h Wg) -> H;   o_h <- g_h o_h
    x     = x + concat_h(o_h) Wo

A sliding layer: the same with its own H, dn, dr, dv, rq, rkv and theta, no
indexer, its keys t - window < s <= t.

    h2    = RMSNorm(x)
    dense layer (index < n_dense):  x = x + Wd(silu(Wg h2) * (Wu h2))
    expert layer: s = sigmoid(h2 Wr) over ALL experts;  c = s + b
        T = the top_k largest c (no groups);  w_e = s_e / (sum_T s + 1e-20)
        x = x + sum_{e in T, e held} w_e SwiGLU_e(h2) + SwiGLU_shared(h2)
    logits = RMSNorm(x_L) Wout                 (untied head)

This is the PUBLISHED form: keys and values a head wide formed from the
latent for every position, the selection a top-k over the whole causal row,
nothing absorbed, nothing cached, no kernel, no batching; every matmul under
`jax.default_matmul_precision("highest")`.

The weights are this file's OWN draw from the seed (`draw_leaf`, `draw`):
the recipe the configuration's `weights.made` states, written again here so
that the reference takes no array the program made.

Departures from the published description, each forced by what it is
compared with: only the experts `first..first+held-1` are computed (the
chip's share, model-configs guide section 4; `held = n_experts` is the
whole layer); the vocabulary is the slice held; the vision tower, the audio
encoder and the multi-token-prediction module are left out; it is computed
in pieces (`latents`, `index_keys`, `select`, `attend`, `dense_part`,
`route`, `expert`, `shared_expert`, `readout`, each upcasting only its own
weights; a few heads and a block of rows at a time, the softmax's maximum
and sum carried from key block to key block; a full layer's selection is
formed once, a block of rows against the WHOLE row of keys, and kept as
bits, 32 keys a word) so that it fits beside the engine.  Nothing that
enters a sum is left out.

`shape["control"]` names a fault put into THIS computation on purpose (the
control runs that set the check's limits; never in a benchmark run).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS_TOPK = 1e-20
INDEX_NORM_EPS = 1e-6
DRAW_PIECE = 1 << 22         # values a piece of a leaf: part of the recipe

# name -> place of a layer's leaf in the draw (the program's LEAVES)
PLACES = {n: i for i, n in enumerate(
    ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_head_gate", "wi_q", "wi_k",
     "wi_w", "w_gate", "w_up", "w_down", "router", "wg", "wu", "wd",
     "shared_gate", "shared_up", "shared_down"))}
BIAS_PLACE = len(PLACES)


def kind_sizes(sz: dict, kind: str) -> dict:
    """The attention sizes of one kind of layer: H, rq, rkv, dn, dr, dv,
    theta."""
    return dict(sz[kind], d_model=sz["d_model"], eps=sz["eps"],
                rescale=sz["rescale"], control=sz.get("control"))


def leaf_specs(sz: dict, kind: str) -> dict:
    """name -> (shape, fan in, scale) of every drawn leaf of a layer of
    `kind`."""
    D, a = sz["d_model"], sz[kind]
    H, rq, rkv, dn, dr, dv = (a["n_heads"], a["q_rank"], a["kv_rank"],
                              a["d_nope"], a["d_rope"], a["d_v"])
    Hi, di = sz["index_heads"], sz["index_dim"]
    F, Fe, C, S = sz["d_ff"], sz["d_expert"], sz["held"], sz["n_shared"]
    out = 1.0 / math.sqrt(2 * sz["n_layers"])
    return {
        "wq_a": ((D, rq), D, 1.0), "wq_b": ((rq, H, dn + dr), rq, 1.0),
        "wkv_a": ((D, rkv + dr), D, 1.0),
        "wkv_b": ((rkv, H, dn + dv), rkv, 1.0),
        "wo": ((H, dv, D), H * dv, out),
        "w_head_gate": ((D, H), D, 1.0),
        "wi_q": ((rq, Hi, di), rq, 1.0), "wi_k": ((D, di), D, 1.0),
        "wi_w": ((D, Hi), D, 1.0),
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


@functools.partial(jax.jit, static_argnames=("count", "n", "dtype"))
def _pieces(seed, layer, place, std, count, n, dtype):
    """Pieces 0..count-1 of a leaf end to end: piece i is n normal draws x
    std, rounded to dtype, from fold_in(fold_in(fold_in(root, 1 + layer),
    place), i), root the "rbg" key of the words (0, seed, 0, seed).  One
    program for every leaf of every layer: what differs is an operand."""
    words = jax.random.PRNGKey(seed)            # [0, seed]
    root = jax.random.wrap_key_data(jnp.concatenate([words, words]),
                                    impl="rbg")
    at = jax.random.fold_in(jax.random.fold_in(root, 1 + layer), place)

    def piece(i):
        return (jax.random.normal(jax.random.fold_in(at, i), (n,),
                                  jnp.float32) * std).astype(_dtype(dtype))

    return jax.lax.map(piece, jnp.arange(count)).reshape(-1)


def _normal(seed, layer, place, shape, std, dtype, factor):
    size = math.prod(shape)
    w = _pieces(seed, layer, place, jnp.float32(std),
                -(-size // DRAW_PIECE), DRAW_PIECE, dtype)[:size].reshape(
                    shape)
    return w * factor if factor != 1 else w


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
    shape, fan_in, scale = leaf_specs(sz, sz["layer_types"][layer])[name]
    return _normal(seed, layer, PLACES[name], shape,
                   scale / math.sqrt(fan_in),
                   "float32" if name == "router" else pd,
                   scales.get(name, 1))


def draw_programs(sz: dict) -> set:
    """(pieces, dtype) of every leaf `draw_leaf` makes at these sizes —
    `_pieces`'s static arguments — for a caller that compiles them ahead
    of the first draw."""
    pd = sz["param_dtype"]
    pieces = lambda shape: -(-math.prod(shape) // DRAW_PIECE)
    out = {(pieces((sz["vocab"], sz["d_model"])), pd), (1, "float32")}
    for kind in set(sz["layer_types"]):
        out |= {(pieces(shape), "float32" if name == "router" else pd)
                for name, (shape, _, _) in leaf_specs(sz, kind).items()}
    return out


def layer_leaves(sz: dict, layer: int):
    names = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_head_gate")
    if sz["layer_types"][layer] == "full":
        names += ("wi_q", "wi_k", "wi_w")
    if layer < sz["n_dense"]:
        return names + ("w_gate", "w_up", "w_down")
    return names + ("router", "router_bias", "wg", "wu", "wd", "shared_gate",
                    "shared_up", "shared_down")


def draw(seed: int, sz: dict, weights: dict) -> dict:
    """The whole tree (small sizes: a test)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for l in range(sz["n_layers"]):
        a = sz[sz["layer_types"][l]]
        lp = {n: draw_leaf(seed, sz, weights, l, n)
              for n in layer_leaves(sz, l)}
        lp.update(attn_norm=ones(sz["d_model"]), q_norm=ones(a["q_rank"]),
                  kv_norm=ones(a["kv_rank"]), mlp_norm=ones(sz["d_model"]),
                  wi_knorm=ones(sz["index_dim"]),
                  wi_kbias=jnp.zeros((sz["index_dim"],), jnp.float32))
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


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rescale(a: dict, rank: int) -> float:
    if not a["rescale"] or a.get("control") == "no_lora_rescale":
        return 1.0
    return math.sqrt(a["d_model"] / rank)


def rope(x, theta: float, dims: int, first=0):
    """x [n, ..., d] at positions first..first+n-1: pair (2i, 2i+1) of the
    first `dims` values turned by pos * theta^(-2i/dims), the rest as they
    are."""
    n = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = ((first + jnp.arange(n)).astype(jnp.float32).reshape(
        (n,) + (1,) * (x.ndim - 1)) * inv)
    c, s = jnp.cos(ang), jnp.sin(ang)
    t = x[..., :dims]
    x1, x2 = t[..., 0::2], t[..., 1::2]
    t = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(t.shape)
    return jnp.concatenate([t, x[..., dims:]], -1)


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


# The layer in pieces, each a function of the arrays it needs and each
# upcasting its own weights: `block` and `logits` compose them, and so can a
# caller that has to bound memory (one piece a program: check_dots3).  `a`
# is `kind_sizes(sz, kind)`.


@_highest
def latents(x, attn_norm, wkv_a, kv_norm, a):
    """What the rows x [N, D] (positions 0..) give every later query:
    (c_kv [N, rkv] normed and rescaled, k_pe [N, dr] turned)."""
    wkv_a, = _f32(a, wkv_a)
    y = rms_norm(x, attn_norm.astype(jnp.float32), a["eps"]) @ wkv_a
    rkv = a["kv_rank"]
    c_kv = rms_norm(y[:, :rkv], kv_norm.astype(jnp.float32),
                    a["eps"]) * _rescale(a, rkv)
    theta = a["theta"]
    if a.get("control") == "swa_theta_full" and "other_theta" in a:
        theta = a["other_theta"]
    return c_kv, rope(y[:, rkv:], theta, a["d_rope"])


def _query_latent(x, attn_norm, wq_a, q_norm, a):
    h = rms_norm(x, attn_norm.astype(jnp.float32), a["eps"])
    return h, rms_norm(h @ wq_a, q_norm.astype(jnp.float32),
                       a["eps"]) * _rescale(a, a["q_rank"])


@_highest
def index_keys(x, attn_norm, wi_k, knorm, kbias, sz):
    """The indexer's one key a position, [N, di]: LayerNorm, then its
    first d_rope values turned."""
    wi_k, = _f32(sz, wi_k)
    a = sz["full"]
    h = rms_norm(x, attn_norm.astype(jnp.float32), sz["eps"])
    k = layer_norm(h @ wi_k, knorm.astype(jnp.float32),
                   kbias.astype(jnp.float32), INDEX_NORM_EPS)
    if sz.get("control") == "index_8bit":
        k = _fp8(k)
    if sz.get("control") == "no_index_rope":
        return k
    return rope(k, a["theta"], a["d_rope"])


def _pack(mask):
    """[n, N] bools -> [n, N / 32] uint32, key 32 w + b bit b of word w."""
    n, N = mask.shape
    bits = mask.reshape(n, N // 32, 32).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), -1,
                   dtype=jnp.uint32)


def _unpack(words):
    """`_pack`'s inverse: [n, W] uint32 -> [n, 32 W] bools."""
    n, W = words.shape
    return ((words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
            ).astype(bool).reshape(n, W * 32)


@_highest
def select(x, ki, attn_norm, wq_a, q_norm, wi_q, wi_w, sz, heads: int = 0,
           rows: int = 0, blocks=None):
    """The selection of a full layer over the sequence's rows x [N, D]
    (positions 0..N-1; N a multiple of 32) against the indexer's keys ki
    [N, di]: [N, N / 32] uint32, bit s of row t set iff query t keeps key s
    — the `index_topk` largest I[t, s] over s <= t by `jax.lax.top_k` over
    the whole causal row, every s <= t while t < index_topk.  `rows` rows
    at a time (0: all at once; N a multiple of it), the first `blocks` row
    blocks (may be traced; None: all) — the others come back zero —,
    `heads` index heads at a time."""
    wq_a, wi_q, wi_w = _f32(sz, wq_a, wi_q, wi_w)
    a = kind_sizes(sz, "full")
    N = x.shape[0]
    Hi, K = sz["index_heads"], sz["index_topk"]
    if sz.get("control") == "topk_half":
        K = K // 2
    g, n = heads or Hi, rows or N
    if blocks is None:
        blocks = N // n
    h, c_q = _query_latent(x, attn_norm, wq_a, q_norm, a)
    w = (h @ wi_w) * (Hi * sz["index_dim"]) ** -0.5               # [N, Hi]
    turned = sz.get("control") != "no_index_rope"
    cut = lambda arr, j: jax.lax.dynamic_slice_in_dim(arr, j * n, n, 0)
    split = lambda arr, ax: jnp.moveaxis(
        arr.reshape(arr.shape[:ax] + (Hi // g, g) + arr.shape[ax + 1:]),
        ax, 0)

    def row_block(b, out):
        cq, at = cut(c_q, b), b * n + jnp.arange(n)

        def some_heads(acc, hw):
            wq, wh = hw                              # [rq, g, di], [n, g]
            qi = jnp.einsum("nr,rhk->nhk", cq, wq)
            if turned:
                qi = rope(qi, a["theta"], a["d_rope"], first=b * n)
            s = jax.nn.relu(jnp.einsum("nhk,sk->nhs", qi, ki))
            return acc + jnp.einsum("nhs,nh->ns", s, wh), None

        scores, _ = jax.lax.scan(
            some_heads, jnp.zeros((n, N), jnp.float32),
            (split(wi_q, 1), split(cut(w, b), 1)))
        causal = jnp.arange(N)[None, :] <= at[:, None]
        if sz.get("control") == "no_selection":
            return jax.lax.dynamic_update_slice_in_dim(
                out, _pack(causal), b * n, 0)
        idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                            min(K, N))[1]
        kept = jnp.zeros((n, N), bool).at[
            jnp.arange(n)[:, None], idx].set(True) & causal
        return jax.lax.dynamic_update_slice_in_dim(out, _pack(kept),
                                                   b * n, 0)

    return jax.lax.fori_loop(0, blocks, row_block,
                             jnp.zeros((N, N // 32), jnp.uint32))


@_highest
def attend(x, c_kv, k_pe, kept, attn_norm, wq_a, q_norm, wq_b, wkv_b,
           w_head_gate, wo, a, heads: int = 0, rows: int = 0, blocks=None,
           first=0):
    """The sequence's rows x [N, D] (positions 0..N-1) against its latents
    c_kv [N, rkv], k_pe [N, dr] -> x + attention's addition [N, D].
    `kept`: a full layer's selection (`select`'s bits) or None for a
    sliding layer, whose keys are the `a["window"]` last.

    As published, every head's keys and values are formed from the latents
    (`heads` heads at a time; 0: all at once).  Rows are taken `rows` at a
    time (0: all at once; N a multiple of it) and only the blocks `first`
    .. `blocks` - 1 (may be traced; None: to the end) — the others come
    back as they came — and a block of rows meets the keys block by block,
    only as far as it can see: the softmax is the same one, its maximum
    and its sum carried from key block to key block."""
    wq_a, wq_b, wkv_b, w_head_gate, wo = _f32(a, wq_a, wq_b, wkv_b,
                                              w_head_gate, wo)
    N = x.shape[0]
    H, dn, dv = a["n_heads"], a["d_nope"], a["d_v"]
    g, n = heads or H, rows or N
    if blocks is None:
        blocks = N // n
    h, c_q = _query_latent(x, attn_norm, wq_a, q_norm, a)
    gate = jax.nn.sigmoid(h @ w_head_gate)                          # [N, H]
    if a.get("control") == "no_gate":
        gate = jnp.ones_like(gate)
    scale = (dn + a["d_rope"]) ** -0.5
    window = a.get("window")
    if window and a.get("control") == "window_512":
        window = window - 1
    theta = a["theta"]
    if a.get("control") == "swa_theta_full" and "other_theta" in a:
        theta = a["other_theta"]
    cut = lambda arr, j: jax.lax.dynamic_slice_in_dim(arr, j * n, n, 0)
    low = jnp.float32(-1e30)
    # a window reaches at most this many key blocks back
    back = N // n if not window else -(-(window - 1) // n) + 1

    def some_heads(acc, ws):
        wq, w, w_o, gt = ws      # [rq,g,dn+dr] [rkv,g,dn+dv] [g,dv,D] [N,g]
        q = jnp.einsum("nr,rhk->nhk", c_q, wq)
        q_nope, q_pe = q[..., :dn], rope(q[..., dn:], theta, a["d_rope"])
        k_nope = jnp.einsum("sr,rhk->shk", c_kv, w[..., :dn])
        v = jnp.einsum("sr,rhk->shk", c_kv, w[..., dn:])

        def row_block(b, acc):
            qn, qp, i = cut(q_nope, b), cut(q_pe, b), b * n + jnp.arange(n)
            words = None if kept is None else cut(kept, b)

            def key_block(j, carry):
                m, l, o = carry                     # [g,n] [g,n] [g,n,dv]
                s = (jnp.einsum("nhk,shk->hns", qn, cut(k_nope, j))
                     + jnp.einsum("nhk,sk->hns", qp, cut(k_pe, j)))
                key_at = j * n + jnp.arange(n)
                see = key_at[None, :] <= i[:, None]
                if window:
                    see = see & (i[:, None] - key_at[None, :] < window)
                if words is not None:
                    see = see & _unpack(jax.lax.dynamic_slice_in_dim(
                        words, j * (n // 32), n // 32, 1))
                s = jnp.where(see[None], s * scale, low)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.where(see[None], jnp.exp(s - m_new[..., None]), 0.0)
                fade = jnp.exp(m - m_new)
                return (m_new, l * fade + p.sum(-1),
                        o * fade[..., None]
                        + jnp.einsum("hns,shk->hnk", p, cut(v, j)))

            _, l, o = jax.lax.fori_loop(
                jnp.maximum(b + 1 - back, 0), b + 1, key_block,
                (jnp.full((g, n), low), jnp.zeros((g, n), jnp.float32),
                 jnp.zeros((g, n, dv), jnp.float32)))
            o = o / l[..., None] * jnp.swapaxes(cut(gt, b), 0, 1)[..., None]
            out = jnp.einsum("hnk,hkd->nd", o, w_o)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, cut(acc, b) + out, b * n, 0)

        return jax.lax.fori_loop(first, blocks, row_block, acc), None

    split = lambda arr, ax: jnp.moveaxis(
        arr.reshape(arr.shape[:ax] + (H // g, g) + arr.shape[ax + 1:]),
        ax, 0)
    out, _ = jax.lax.scan(some_heads, x, (split(wq_b, 1), split(wkv_b, 1),
                                          split(wo, 0), split(gate, 1)))
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
    """(w [n, k], idx [n, k]): the biased scores choose, the scores alone
    weigh; no groups."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))          # [n, E]
    idx = jax.lax.top_k(s + bias.astype(jnp.float32), sz["top_k"])[1]
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


def attention(x, lp, sz, kind: str):
    """x [N, D] with one layer's attention added (N a multiple of 32 for a
    full layer: pad behind the real rows)."""
    a = kind_sizes(sz, kind)
    if kind == "sliding":
        a["other_theta"] = sz["full"]["theta"]
    c_kv, k_pe = latents(x, lp["attn_norm"], lp["wkv_a"], lp["kv_norm"], a)
    kept = None
    if kind == "full":
        ki = index_keys(x, lp["attn_norm"], lp["wi_k"], lp["wi_knorm"],
                        lp["wi_kbias"], sz)
        kept = select(x, ki, lp["attn_norm"], lp["wq_a"], lp["q_norm"],
                      lp["wi_q"], lp["wi_w"], sz)
    return attend(x, c_kv, k_pe, kept, lp["attn_norm"], lp["wq_a"],
                  lp["q_norm"], lp["wq_b"], lp["wkv_b"], lp["w_head_gate"],
                  lp["wo"], a)


def block(x, lp, sz, kind: str):
    x = attention(x, lp, sz, kind)
    return x + feed_forward(normed(x, lp["mlp_norm"], sz), lp, sz)


def logits(params, tokens, sz):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence)."""
    S = tokens.shape[0]
    pad = -S % 32       # behind every real row: no real row sees them
    x = params["embed"][jnp.pad(tokens, (0, pad))].astype(jnp.float32)
    for lp, kind in zip(params["layers"], sz["layer_types"]):
        x = block(x, lp, sz, kind)
    return readout(x[:S], params["final_norm"], params["unembed"], sz)
