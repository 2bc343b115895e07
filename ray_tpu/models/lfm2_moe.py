"""LFM2-MoE-class decoder (`model_type: lfm2_moe`): a GATED SHORT
CONVOLUTION as the mixer in three layers of four, grouped-query attention
with normed heads in the fourth, and bias-selected sigmoid experts behind
leading dense layers — the serving engine's ninth model, behind the same
module interface as the eight others.

A layer plan is DATA, and its two halves vary independently: the MIXER's
kind by `cfg.layer_types[l]` (`conv` | `full_attention`), the
FEED-FORWARD's by `l < cfg.n_dense` (a dense SwiGLU | the experts).  With
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g, statistics in f32:

    h'  = h  + Mix_l(RMSNorm_op(h))
    h'' = h' + FF_l(RMSNorm_ffn(h'))

    conv:  [a | c | x] = u W_in              (D -> 3D, no bias)
           z = a * x                         the gate BEFORE the taps
           y_t = sum_{i=0..2} w_i * z_{t-2+i}    depthwise, causal, 3 taps,
                 no bias, NO activation         ops/shortconv.py
           out = (c * y) W_out               the gate AFTER them
    attention: q, k, v = u W_qkv (H query heads on Hkv key heads, no bias);
           q <- RMSNorm_dh(q), k <- RMSNorm_dh(k) a head, ONE weight [dh]
           each; rotate-half RoPE on every dim; causal softmax at
           d_head^-1/2; W_o.
    experts: s = sigmoid(u W_r) over ALL experts in f32; the top_k largest
           of s + b CHOOSE (b [E] the expert bias; a tie to the lower
           index), s alone WEIGHS: s_chosen / (sum s_chosen + route_eps)
           x routed_scale; expert e: W2_e (silu(W1_e u) * W3_e u); no
           shared expert; dropless.                        ops/moe.py
    logits = RMSNorm_final(h_L) E^T          (the embedding itself: tied)

What a sequence keeps: of an ATTENTION layer its keys and values, pages
[pages, page_size, Hkv d_head] a side (the full kind); of a CONV layer the
last two rows of z — one entry of a `"state"` kind, [2, D] float32 a
layer, kept as whole tiles [2, D / 128, 128] (`falcon_h1`'s lesson: two
rows of an 8-row tile are re-laid by arena-wide copies).  At the published
sizes the entry is 16 KB a layer where a page is 256 KB: the first model
whose state entry is negligible and whose pages are a quarter of the
layers' alone.

A chip may hold a *share* of the experts (models/deepseek_v3.py):
`experts_first..+experts_held-1` of `n_experts`; the router keeps its full
width and its experts a token, and what the absent experts would add is
left out.  `n_layers` counts the layers held, `n_dense` of them dense.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import apply_rope_halves, rms_norm
from ray_tpu.ops.moe import (held_expert_ffn, held_experts_leaf,
                             held_load_stats, route_sigmoid_topk)
from ray_tpu.ops.shortconv import conv_chunk, conv_step

from .gpt import cast_leaves, slot_embed
from .served import attend_pages, carried_at, draw, kind_io

__all__ = ["Lfm2MoeConfig", "init", "init_layer", "init_top", "apply",
           "cache_kinds", "init_paged_cache", "paged_decode_step", "paged_prefill",
           "serve_view", "state_leaves", "STEP_STATS"]

# what a serve program returns beside logits and cache, in this order (f32
# scalars, the first four `ops/moe.held_load_stats`' summed over the expert
# layers): token-expert pairs that fell on held experts, the largest load
# of a held expert, held experts touched, held experts' visits by a trip of
# grouped products; and the tails ONE conv layer's update moved (a step's
# live slots; one for a chunk)
STEP_STATS = ("moe_pairs", "moe_load_max", "moe_touched", "moe_reads",
              "conv_live")

FULL, CONV = "full", "conv"
ATTENTION = "full_attention"           # `layer_types`' other word


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    # the plan: a mixer's kind a layer held (`conv` | `full_attention`)
    layer_types: Tuple[str, ...] = (
        ("conv", "conv", ATTENTION) + ("conv", "conv", "conv", ATTENTION) * 4
        + ("conv", "conv", ATTENTION, "conv", "conv"))
    n_dense: int = 2                   # leading layers with a dense SwiGLU
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    d_head: int = 64
    d_ff: int = 7168                   # a dense layer's SwiGLU
    d_expert: int = 1792
    n_experts: int = 32                # the router's width
    experts_first: int = 0             # experts held: first..first+held-1
    experts_held: int = 32
    top_k: int = 4
    routed_scale: float = 1.0
    route_eps: float = 1e-6            # beside the chosen scores' sum
    conv_taps: int = 3                 # `conv_L_cache`
    rope_theta: float = 1e6
    eps: float = 1e-5
    max_seq: int = 128000
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    kv_block: int = 512                # keys scored at once on the serve path
    # at most this many sorted rows a product (see deepseek_v3.moe_tile)
    moe_tile: int = 512
    # what gpt's shared helpers and the engine read off a config
    pos: str = "rope"
    tie_embeddings: bool = True

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        off = set(self.layer_types) - {CONV, ATTENTION}
        if off:
            raise ValueError(f"layer_types knows `conv` and "
                             f"`full_attention`, not {sorted(off)}")
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError("n_dense must lie in 0..n_layers")
        if self.experts_first + self.experts_held > self.n_experts:
            raise ValueError("held experts run past n_experts")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads share key heads evenly")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def is_conv(self, l: int) -> bool:
        return self.layer_types[l] == CONV

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.n_layers) if self.is_conv(l))

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.n_layers) if not self.is_conv(l))

    @property
    def conv_tile(self) -> Tuple[int, int]:
        """A conv layer's channels as whole tiles: (sublanes, lanes)."""
        lanes = 128 if self.d_model % 128 == 0 else self.d_model
        return (self.d_model // lanes, lanes)

    @classmethod
    def nano(cls, **kw):
        """The plan at toy size, for the CPU tests: a dense conv layer,
        then one period (attention, conv, conv, conv) of expert layers —
        8 experts, top-2, all held; 4 query heads on 2 key heads of 8."""
        base = dict(vocab_size=256,
                    layer_types=("conv", ATTENTION, "conv", "conv", "conv"),
                    n_dense=1, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
                    d_ff=64, d_expert=16, n_experts=8, experts_first=0,
                    experts_held=8, top_k=2, rope_theta=1e4, max_seq=128,
                    kv_block=16, moe_tile=16)
        base.update(kw)
        return cls(**base)


# the draw is `served.draw`, a leaf's place its index here.  Norm weights
# are ones and the expert bias zeros (a fresh router's).  `wg` and `wu`
# keep their places in the recipe (the benchmark's reference draws them
# apart) and lie side by side in ONE leaf of the tree, `wgu` [held, D, 2F]
# (`ops.moe.held_experts_leaf`).
LEAVES = ("w_in", "conv_w", "w_out", "w_qkv", "wo", "w_gate_up", "w_down",
          "router", "wg", "wu", "wd")


def init_layer(key, cfg: Lfm2MoeConfig, l: int) -> Dict[str, Any]:
    """Layer l's weights: its mixer's by `cfg.layer_types[l]`, its
    feed-forward's by `l < cfg.n_dense`."""
    D, pd = cfg.d_model, cfg.param_dtype
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    out = 1.0 / math.sqrt(2 * cfg.n_layers)

    def w(name, shape, fan_in, scale=1.0, dtype=pd):
        return draw(key, l, LEAVES.index(name), shape,
                     scale / math.sqrt(fan_in), dtype)

    layer = {"norm": jnp.ones((D,), pd), "ffn_norm": jnp.ones((D,), pd)}
    if cfg.is_conv(l):
        layer.update(
            w_in=w("w_in", (D, 3 * D), D),      # columns: a | c | x
            conv_w=w("conv_w", (cfg.conv_taps, D), cfg.conv_taps),
            w_out=w("w_out", (D, D), D, out))
    else:
        layer.update(
            w_qkv=w("w_qkv", (D, (H + 2 * Hkv) * dh), D),  # q | k | v
            q_norm=jnp.ones((dh,), pd), k_norm=jnp.ones((dh,), pd),
            wo=w("wo", (H * dh, D), H * dh, out))
    if l < cfg.n_dense:
        F = cfg.d_ff
        layer.update(w_gate_up=w("w_gate_up", (D, 2 * F), D),  # gate | up
                     w_down=w("w_down", (F, D), F, out))
        return layer
    F, C = cfg.d_expert, cfg.experts_held
    layer.update(
        # the router and its bias are kept and applied in f32
        router=w("router", (D, cfg.n_experts), D, dtype=jnp.float32),
        router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
        wgu=held_experts_leaf(w("wg", (C, D, F), D), w("wu", (C, D, F), D)),
        wd=w("wd", (C, F, D), F, out))
    return layer


def init_top(key, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """What stands outside the layers: the embedding (the head too: tied)
    and the last norm."""
    return {"embed": draw(key, -1, 0, (cfg.vocab_size, cfg.d_model), 0.02,
                           cfg.param_dtype),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype)}


def init(key, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """The param tree: `layers` is a list (the four kinds of layer hold
    different leaves)."""
    return dict(init_top(key, cfg), layers=[
        init_layer(key, cfg, l) for l in range(cfg.n_layers)])


_SERVE_CAST = frozenset({"embed", "w_in", "w_out", "w_qkv", "wo",
                         "w_gate_up", "w_down", "wgu", "wd"})


def serve_view(params, cfg: Lfm2MoeConfig):
    """The tree the serve programs are handed (see gpt.serve_view): the
    matrices in cfg.dtype; norms, the taps, the router and its bias as
    kept.  A view's view is that view."""
    return cast_leaves(params, cfg, _SERVE_CAST)


# ---------------------------------------------------------------------------
# the block's pieces


def _normed(x, w, cfg: Lfm2MoeConfig):
    return rms_norm(x, w, cfg.eps).astype(cfg.dtype)


def _conv_project(n, layer, cfg: Lfm2MoeConfig):
    """n [.., D] normed -> (z [.., *conv_tile] float32: the gated rows the
    taps meet and the tail keeps, c [.., D] float32: the gate after)."""
    with jax.named_scope("conv_proj"):
        p = jnp.einsum("...d,dc->...c", n, layer["w_in"].astype(cfg.dtype))
        a, c, x = jnp.split(p.astype(jnp.float32), 3, axis=-1)
        z = a * x
        return z.reshape(z.shape[:-1] + cfg.conv_tile), c


def _taps(layer, cfg: Lfm2MoeConfig):
    return layer["conv_w"].reshape((cfg.conv_taps,) + cfg.conv_tile)


def _conv_out(y, c, layer, cfg: Lfm2MoeConfig):
    """The taps' sums y [.., *conv_tile] float32 gated by c [.., D] and
    projected: the mixer's addition to the stream [.., D]."""
    with jax.named_scope("conv_out"):
        g = (c * y.reshape(c.shape)).astype(cfg.dtype)
        return jnp.einsum("...c,cd->...d", g,
                          layer["w_out"].astype(cfg.dtype))


def _conv_sequence(n, layer, tail, cfg: Lfm2MoeConfig):
    """The conv mixer over ONE sequence's normed rows n [T, D] from its
    tail [taps-1, *conv_tile] -> (its addition [T, D], the gated rows z
    [T, *conv_tile] the next tail is cut from)."""
    z, c = _conv_project(n, layer, cfg)
    y = conv_chunk(z, tail, _taps(layer, cfg), None, None, "short_conv")
    return _conv_out(y, c, layer, cfg), z


def _qkv(n, layer, pos, cfg: Lfm2MoeConfig):
    """n [B, T, D] normed at positions pos [B, T] -> (q [B, Hkv, G, T, dh]
    and k [B, Hkv, T, dh] normed a head and rotated, v [B, Hkv, T, dh])."""
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    B, T, _ = n.shape
    with jax.named_scope("attn_proj"):
        q, k, v = jnp.split(
            jnp.einsum("btd,dk->btk", n, layer["w_qkv"].astype(cfg.dtype)),
            [H * dh, (H + Hkv) * dh], axis=-1)
        heads = lambda a, h: jnp.moveaxis(a.reshape(B, T, h, dh), 1, 2)
        q = rms_norm(heads(q, H), layer["q_norm"], cfg.eps)
        k = rms_norm(heads(k, Hkv), layer["k_norm"], cfg.eps)
        q = apply_rope_halves(q, pos, cfg.rope_theta)
        k = apply_rope_halves(k, pos, cfg.rope_theta)
        return q.reshape(B, Hkv, H // Hkv, T, dh), k, heads(v, Hkv)


def _attn_out(o, layer, cfg: Lfm2MoeConfig):
    """o [B, Hkv, G, T, dh] -> the mixer's addition [B, T, D]."""
    B, Hkv, G, T, dh = o.shape
    with jax.named_scope("attn_proj"):
        y = jnp.moveaxis(o.reshape(B, Hkv * G, T, dh), 1, 2).reshape(B, T, -1)
        return jnp.einsum("btk,kd->btd", y.astype(cfg.dtype),
                          layer["wo"].astype(cfg.dtype))


def layer_ffn(h, layer, cfg: Lfm2MoeConfig, live=None):
    """The layer's feed-forward on the normed input h [N, D] -> ([N, D]
    f32, `held_expert_ffn`'s (loads [held], reads) or None for a dense
    layer)."""
    dt = cfg.dtype
    if "router" not in layer:
        with jax.named_scope("mlp"):
            gate, up = jnp.split(jnp.einsum(
                "nd,df->nf", h, layer["w_gate_up"].astype(dt)), 2, axis=-1)
            return jnp.einsum("nf,fd->nd", jax.nn.silu(gate) * up,
                              layer["w_down"].astype(dt),
                              preferred_element_type=jnp.float32), None
    with jax.named_scope("moe_router"):
        w, idx = route_sigmoid_topk(h, layer["router"], cfg.top_k,
                                    bias=layer["router_bias"],
                                    eps=cfg.route_eps)
        if cfg.routed_scale != 1.0:
            w = w * cfg.routed_scale
    with jax.named_scope("moe_experts"):
        routed, loads, reads = held_expert_ffn(
            h, w, idx, layer["wgu"], None, layer["wd"],
            first=cfg.experts_first, tile=cfg.moe_tile, live=live)
    return routed, (loads, reads)


def _ffn(x, layer, cfg: Lfm2MoeConfig, live=None):
    """x [B, T, D] -> (x + FF(RMSNorm_ffn(x)), the layer's (loads, reads)
    or None); `live` [B, T] marks the rows that route."""
    B, T, D = x.shape
    h = _normed(x, layer["ffn_norm"], cfg).reshape(B * T, D)
    y, held = layer_ffn(h, layer, cfg,
                        None if live is None else live.reshape(B * T))
    return x + y.reshape(B, T, D).astype(x.dtype), held


def _head(params, x, cfg: Lfm2MoeConfig):
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,vd->...v",
                          _normed(x, params["final_norm"], cfg),
                          params["embed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _stats(held: list, moved):
    return jnp.stack(held_load_stats([h for h in held if h is not None])
                     + [jnp.asarray(moved, jnp.float32).reshape(())])


def apply(params, tokens, cfg: Lfm2MoeConfig):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32; every sequence one chunk from an empty tail, the keys the
    sequence's own rows, streamed `kv_block` at a time."""
    from ray_tpu.ops.attention import streamed_attention

    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pad = nb * kb - S
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    empty = jnp.zeros((cfg.conv_taps - 1,) + cfg.conv_tile, jnp.float32)
    x = slot_embed(params, tokens, pos, cfg)
    for l, layer in enumerate(params["layers"]):
        n = _normed(x, layer["norm"], cfg)
        if cfg.is_conv(l):
            mix = jax.vmap(lambda n1: _conv_sequence(n1, layer, empty,
                                                     cfg)[0])(n)
        else:
            q, k, v = _qkv(n, layer, pos, cfg)
            rows = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
            k, v = rows(k), rows(v)

            def fetch(i):
                sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(
                    a, i * kb, kb, ax)
                return sl(k, 2), sl(v, 2), sl(kpos, 1)

            with jax.named_scope("attn_chunk"):
                o = streamed_attention(q, pos, fetch, nb,
                                       scale=cfg.d_head ** -0.5)
            mix = _attn_out(o, layer, cfg)
        x, _ = _ffn(x + mix.astype(x.dtype), layer, cfg)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# serving: pages in the attention layers, one tail entry over the conv layers


def cache_kinds(cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """name -> what the engine keeps for it (see gpt.cache_kinds): the
    attention layers' keys and values in a full-kind pool of pages, the
    conv layers' tails one entry of a `"state"` kind a sequence."""
    return {FULL: None, CONV: "state"}


def init_paged_cache(cfg: Lfm2MoeConfig, num_pages, page_size: int):
    """{"k", "v": [an arena an ATTENTION layer, [pages, page_size, Hkv *
    dh]], "tail": [conv layers, entries, taps - 1, *conv_tile] float32}.
    `num_pages` counts pages under `full` and entries under `conv`; page 0
    and entry 0 are the null ones."""
    shape = (int(num_pages[FULL]), page_size, cfg.n_kv_heads * cfg.d_head)
    side = lambda: [jnp.zeros(shape, cfg.dtype) for _ in cfg.attn_layers]
    return {"k": side(), "v": side(),
            "tail": jnp.zeros((len(cfg.conv_layers), int(num_pages[CONV]),
                               cfg.conv_taps - 1) + cfg.conv_tile,
                              jnp.float32)}


def state_leaves(cache) -> List[jax.Array]:
    """The leaves of `cache` that are the state kind's arena (the engine
    counts their bytes apart from the pages')."""
    return [cache["tail"]]


def _paged_pass(params, cache, toks, tab, pos, real, conv_layer, scope: str,
                cfg: Lfm2MoeConfig, ctx=None):
    """Tokens toks [B, T] at CONSECUTIVE positions pos [B, T] through the
    layers; `real` [B, T] marks the rows whose K and V are kept and that
    route.  A conv layer (the j-th of them) is `conv_layer(j, n, layer,
    tail)` -> (its addition [B, T, D], the tail arena after); an attention
    layer writes its rows into its pages and reads them under the named
    scope `scope`.  Returns (x [B, T, D], cache, the expert layers'
    (loads, reads))."""
    ks, vs, tail = list(cache["k"]), list(cache["v"]), cache["tail"]
    ps = ks[0].shape[1]
    io = kind_io("full", tab, pos, real, jnp.max(pos, axis=1),
                 pos.reshape(-1), ps, max(1, cfg.kv_block // ps))
    x = slot_embed(params, toks, pos, cfg)
    held, j, a = [], 0, 0
    for l, layer in enumerate(params["layers"]):
        n = _normed(x, layer["norm"], cfg)
        if cfg.is_conv(l):
            mix, tail = conv_layer(j, n, layer, tail)
            j += 1
        else:
            q, k, v = _qkv(n, layer, pos, cfg)
            with jax.named_scope(scope):
                o, ks[a], vs[a] = attend_pages(q, k, v, ks[a], vs[a], io,
                                               pos, cfg, ctx)
            mix = _attn_out(o, layer, cfg)
            a += 1
        x, ld = _ffn(x + mix.astype(x.dtype), layer, cfg, live=real)
        held.append(ld)
    return x, {"k": ks, "v": vs, "tail": tail}, held


def paged_decode_step(params, cache, tokens, ptabs, pos,
                      cfg: Lfm2MoeConfig):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[FULL] [B, R] the slots' pages, ptabs[CONV] [B, 1] their entries.
    A slot at position 0 is empty (a prompt has at least one token): it
    writes to the null page, routes nowhere and leaves the null entry as
    it is.  Returns (logits [B, V] f32, cache, stats)."""
    idx, live = ptabs[CONV][:, 0], pos > 0

    def conv_layer(j, n, layer, tail):
        z, c = _conv_project(n[:, 0], layer, cfg)
        old = tail[j][idx]
        y, new = conv_step(z, old, _taps(layer, cfg), None, None,
                           "short_conv")
        with jax.named_scope("short_conv"):
            tail = tail.at[j, idx].set(
                jnp.where(live[:, None, None, None], new, old))
        return _conv_out(y, c, layer, cfg)[:, None], tail

    ctx = jnp.where(live, pos + 1, 0)
    x, cache, held = _paged_pass(params, cache, tokens[:, None], ptabs[FULL],
                                 pos[:, None], live[:, None], conv_layer,
                                 "attn_step", cfg, ctx)
    return _head(params, x[:, 0], cfg), cache, _stats(held, live.sum())


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx,
                  cfg: Lfm2MoeConfig):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its pages ptab_rows[FULL] [R] and its
    entry ptab_rows[CONV][0]: in every conv layer the tail is read unless
    this is the sequence's first chunk (`start == 0`) and written back as
    the last two REAL rows of z, and in every attention layer the chunk's K
    and V rows go into the layer's pages.  Returns (logits [V] f32 at row
    last_idx, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    real = t <= last_idx
    idx = ptab_rows[CONV][0]
    first = start == 0

    def conv_layer(j, n, layer, tail):
        t0 = carried_at(first, tail, j, idx)
        y, z = _conv_sequence(n[0], layer, t0, cfg)
        with jax.named_scope("short_conv"):
            t1 = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([t0, z]), last_idx + 1, cfg.conv_taps - 1, 0)
            return y[None], tail.at[j, idx].set(t1)

    x, cache, held = _paged_pass(params, cache, toks[None],
                                 ptab_rows[FULL][None], (start + t)[None],
                                 real[None], conv_layer, "attn_chunk", cfg)
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    return _head(params, x, cfg), cache, _stats(held, 1)
