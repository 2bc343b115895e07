"""Ling-3.0-class hybrid decoder (`model_type: bailing_hybrid`): groups of
`layer_group` layers whose last is latent attention (MLA) and whose others
are Kimi Delta Attention (KDA, ops/kda.py), leading dense SwiGLU layers,
then group-routed experts with a shared one — the serving engine's fifth
model, behind the same module interface as models/gpt.py,
models/cohere2_moe.py, models/brumby.py and models/deepseek_v3.py.

The block (RMSNorm, statistics in f32):

    x = x + mixer_l(RMSNorm(x));   x = x + ffn_l(RMSNorm(x))
    mixer_l: MLA where (l + 1) % layer_group == 0, KDA elsewhere
    ffn_l:   a dense SwiGLU where l < n_dense, experts elsewhere

A KDA layer (H heads, keys and values d_head wide), n the normed input:

    q~ | k~ | v~ = n Wqkv                       3H rows of d_head a token
    u    = SiLU(sum_i c_i u~_{t-3+i} + b)       depthwise, causal, 4 taps
    q    = q / |q| * d_head^-1/2;  k = k / |k|  per head
    log a = lower * sigmoid(exp(A_log_h) (n Wf + dt_bias))   per key channel
    b    = sigmoid(n Wb)                        per head
    o    = kda(q, k, v, log a, b)               ops/kda.py
    y    = (RMSNorm_head(o) * sigmoid(n Wg)) Wo

An MLA layer is models/deepseek_v3.py's with a direct query projection
(no query latent) and a HEAD-WISE output gate: o_h <- o_h sigmoid(n w_h)
before Wo.  Its cached row, its absorbed step and its expanded chunk ARE
deepseek_v3's functions (`latent_rows`, `latent_attend`, `page_io`), as the
feed-forward is (`layer_ffn`): this config answers the attributes they read.

What a sequence keeps, and `cache_kinds` says so with TWO kinds:

  * `full`: one latent row (kv_rank + d_rope values) a position and MLA
    layer, in pages [pages, 576, page_size] as deepseek_v3 lays them;
  * `kda`, a `"state"`: ONE entry for its whole life, holding for every
    KDA layer the heads' state matrices ([H, d_head, d_head] float32, kept
    transposed: ops/kda.py) and the conv tail — its last 3 pre-conv rows
    of q~ | k~ | v~ ([3, 3H, d_head] in cfg.dtype).

The cache is {"latent": [arena a MLA layer], "state": [KDA layers,
entries, H, d_head, d_head], "tail": [KDA layers, entries, 3, 3H,
d_head]}, entry 0 and page 0 the null ones; `state_leaves` names the part
that is the state arena.  A sequence's first chunk (`start == 0`) reads
neither its entry's states nor its tails: that is what empties a re-used
entry.  Nothing of it can be shared: the state after a prefix is in no
page.

A chip may hold a *share* of the model (models/deepseek_v3.py): experts
`experts_first..+experts_held-1` of `n_experts`, `vocab_size` rows of the
vocabulary, `n_layers` layers of which the first `n_dense` are dense.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.kda import kda_chunk, kda_step
from ray_tpu.ops.layers import apply_rope_interleaved, rms_norm
from ray_tpu.ops.shortconv import conv_chunk, conv_step
from ray_tpu.ops.moe import held_experts_leaf, held_load_stats

from . import deepseek_v3 as _dm
from .gpt import cast_leaves, slot_embed
# benchmarks/drivers/replica_ling3.py:28, replica_lfm2_moe.py:39 and
# replica_falcon_h1.py:40 import `_draw` (ROADMAP D17)
from .served import draw as _draw
from .served import carried_at, states_moved

__all__ = ["Ling3Config", "init", "apply", "cache_kinds", "init_paged_cache",
           "paged_decode_step", "paged_prefill", "serve_view", "state_leaves",
           "STEP_STATS"]

# what a serve program returns beside logits and cache, in this order (f32
# scalars): token-expert pairs that fell on held experts, the largest load
# of a held expert, held experts touched, held experts' visits by a trip of
# grouped products (summed over the expert layers, as deepseek_v3), and the
# states ONE KDA layer's update moved — a step's live slots where the
# kernel runs (it moves nothing for an empty slot), every slot on the
# gather / scatter path; one for a chunk
STEP_STATS = ("moe_pairs", "moe_load_max", "moe_touched", "moe_reads",
              "kda_live")

FULL, KDA = "full", "kda"
ABSORB_ROWS = _dm.ABSORB_ROWS
CONV_TAPS = 4


@dataclasses.dataclass(frozen=True)
class Ling3Config:
    vocab_size: int = 157184           # rows of the vocabulary held here
    n_layers: int = 42
    n_dense: int = 2                   # leading layers with a dense SwiGLU
    layer_group: int = 6               # the last layer of a group is MLA
    d_model: int = 2560
    n_heads: int = 32
    d_head: int = 128                  # a KDA head's keys and values
    gate_lower: float = -5.0           # log a lies in (gate_lower, 0)
    kv_rank: int = 512                 # the latent an MLA position is cached as
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    d_ff: int = 6144                   # a dense layer's SwiGLU
    d_expert: int = 768                # one expert's
    d_shared: int = 768                # the shared expert's
    n_experts: int = 512               # the router's width
    experts_first: int = 0             # experts held: first..first+held-1
    experts_held: int = 512
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scale: float = 2.5
    # the published clamp of an expert's / the shared expert's SwiGLU, a
    # layer (`expert_swiglu_limit_list`): only 0, no clamp, is built
    swiglu_limits: tuple = ()
    rms_eps: float = 1e-6
    rope_theta: float = 6e6
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # ops.kda.kda_step's `impl` (None: by backend)
    kda_impl: Optional[str] = None
    kv_block: int = 512                # keys scored at once on the serve path
    # at most this many sorted rows a product; the row block of `ops/moe`
    # (ROW_BLOCK) is the usual bound, so this binds only where it is smaller
    moe_tile: int = 512
    # what gpt's shared helpers and the engine read off a config
    pos: str = "rope"
    tie_embeddings: bool = False

    def __post_init__(self):
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError("n_dense must lie in 0..n_layers")
        if self.experts_first + self.experts_held > self.n_experts:
            raise ValueError("held experts run past n_experts")
        if self.n_experts % self.n_group:
            raise ValueError("n_experts must be a multiple of n_group")
        if any(self.swiglu_limits):
            raise ValueError(
                "a nonzero SwiGLU limit is not built: the configuration "
                "gives the number and not the clamp's form "
                f"(swiglu_limits {self.swiglu_limits})")

    def is_mla(self, l: int) -> bool:
        return (l + 1) % self.layer_group == 0

    @property
    def kda_layers(self) -> List[int]:
        return [l for l in range(self.n_layers) if not self.is_mla(l)]

    @property
    def mla_layers(self) -> List[int]:
        return [l for l in range(self.n_layers) if self.is_mla(l)]

    # what deepseek_v3's attention reads off its config
    @property
    def softmax_scale(self) -> float:
        return (self.d_nope + self.d_rope) ** -0.5

    def rope_freqs(self):
        return None

    @classmethod
    def nano(cls, **kw):
        """The plan at toy size, for the CPU tests: groups of three (KDA,
        KDA, MLA), one dense layer, 16 experts in 4 groups of which 4 are
        held; 4 heads."""
        base = dict(vocab_size=256, n_layers=4, n_dense=1, layer_group=3,
                    d_model=64, n_heads=4, d_head=16, kv_rank=16, d_nope=8,
                    d_rope=4, d_v=8, d_ff=96, d_expert=32, d_shared=32,
                    n_experts=16, experts_first=4, experts_held=4, top_k=4,
                    n_group=4, topk_group=2, max_seq=128, kv_block=16,
                    moe_tile=16)
        base.update(kw)
        return cls(**base)


# the draw is `served.draw`, a leaf's place its index here.  Norms are
# ones; the conv's bias, the correction bias, A_log and dt_bias zeros (a
# benchmark's loader draws what a checkpoint would hold there).  `wg` and
# `wu` keep their places in the recipe and lie in ONE leaf of the tree,
# `wgu` (`ops.moe.held_experts_leaf`).
LEAVES = ("w_qkv", "conv_w", "w_f", "w_b", "w_g", "wo", "wq", "wkv_a",
          "wkv_b", "w_head_gate", "w_gate", "w_up", "w_down", "router", "wg",
          "wu", "wd", "shared_gate", "shared_up", "shared_down")


def init_layer(key, cfg: Ling3Config, l: int) -> Dict[str, Any]:
    """Layer l's weights: its mixer's by `cfg.is_mla(l)`, its
    feed-forward's by `l < cfg.n_dense`."""
    D, H, dh, pd = cfg.d_model, cfg.n_heads, cfg.d_head, cfg.param_dtype
    out = 1.0 / math.sqrt(2 * cfg.n_layers)

    def w(name, shape, fan_in, scale=1.0, dtype=pd):
        return _draw(key, l, LEAVES.index(name), shape,
                     scale / math.sqrt(fan_in), dtype)

    layer = {"attn_norm": jnp.ones((D,), pd), "mlp_norm": jnp.ones((D,), pd)}
    if cfg.is_mla(l):
        rkv, dn, dr, dv = cfg.kv_rank, cfg.d_nope, cfg.d_rope, cfg.d_v
        layer.update(
            kv_norm=jnp.ones((rkv,), pd),
            wq=w("wq", (D, H, dn + dr), D),
            wkv_a=w("wkv_a", (D, rkv + dr), D),
            wkv_b=w("wkv_b", (rkv, H, dn + dv), rkv),
            w_head_gate=w("w_head_gate", (D, H), D),
            wo=w("wo", (H, dv, D), H * dv, out))
    else:
        layer.update(
            w_qkv=w("w_qkv", (D, 3 * H, dh), D),
            conv_w=w("conv_w", (CONV_TAPS, 3 * H, dh), CONV_TAPS),
            conv_b=jnp.zeros((3 * H, dh), pd),
            w_f=w("w_f", (D, H, dh), D), w_b=w("w_b", (D, H), D),
            w_g=w("w_g", (D, H, dh), D),
            # the gate's own parameters are kept and applied in f32
            a_log=jnp.zeros((H,), jnp.float32),
            dt_bias=jnp.zeros((H, dh), jnp.float32),
            o_norm=jnp.ones((dh,), pd),
            wo=w("wo", (H, dh, D), H * dh, out))
    if l < cfg.n_dense:
        F = cfg.d_ff
        layer.update(w_gate=w("w_gate", (D, F), D), w_up=w("w_up", (D, F), D),
                     w_down=w("w_down", (F, D), F, out))
        return layer
    F, C, S = cfg.d_expert, cfg.experts_held, cfg.d_shared
    layer.update(
        router=w("router", (D, cfg.n_experts), D, dtype=jnp.float32),
        router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
        wgu=held_experts_leaf(w("wg", (C, D, F), D), w("wu", (C, D, F), D)),
        wd=w("wd", (C, F, D), F, out),
        shared_gate=w("shared_gate", (D, S), D),
        shared_up=w("shared_up", (D, S), D),
        shared_down=w("shared_down", (S, D), S, out))
    return layer


def init(key, cfg: Ling3Config) -> Dict[str, Any]:
    """The param tree: `layers` is a list (a layer's leaves are its
    mixer's and its feed-forward's kind)."""
    V, D, pd = cfg.vocab_size, cfg.d_model, cfg.param_dtype
    return {
        "embed": _draw(key, -1, 0, (V, D), 0.02, pd),
        "unembed": _draw(key, -1, 1, (D, V), 1.0 / math.sqrt(D), pd),
        "final_norm": jnp.ones((D,), pd),
        "layers": [init_layer(key, cfg, l) for l in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# the two mixers


def _kda_project(h, layer, cfg: Ling3Config):
    """h [B, T, D] normed -> the pre-conv rows [B, T, 3H, dh] in
    cfg.dtype, log a [B, T, H, dh], b [B, T, H] and the output gate
    [B, T, H, dh], float32."""
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("kda_proj"):
        pre = jnp.einsum("btd,dnk->btnk", h, layer["w_qkv"].astype(dt))
        f = jnp.einsum("btd,dhk->bthk", h, layer["w_f"].astype(dt),
                       preferred_element_type=f32)
        log_a = cfg.gate_lower * jax.nn.sigmoid(
            jnp.exp(layer["a_log"])[:, None] * (f + layer["dt_bias"]))
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", h, layer["w_b"].astype(dt),
            preferred_element_type=f32))
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,dhk->bthk", h, layer["w_g"].astype(dt),
            preferred_element_type=f32))
        return pre, log_a, beta, gate


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_qkv(u, cfg: Ling3Config):
    """The conv's output u [.., 3H, dh] float32 -> q, k, v [.., H, dh]: q
    and k of unit length a head, q scaled by dh^-1/2."""
    H = cfg.n_heads
    return (_unit(u[..., :H, :]) * cfg.d_head ** -0.5,
            _unit(u[..., H:2 * H, :]), u[..., 2 * H:, :])


def _kda_out(x, o, gate, layer, cfg: Ling3Config):
    """The heads' outputs o [B, T, H, dh] float32, normed a head wide and
    gated, into the stream."""
    with jax.named_scope("kda_out"):
        y = (rms_norm(o, layer["o_norm"], cfg.rms_eps) * gate).astype(
            cfg.dtype)
        return x + jnp.einsum("bthk,hkd->btd", y,
                              layer["wo"].astype(cfg.dtype)).astype(x.dtype)


def _kda_sequence(x, h, layer, real, state, tail, cfg: Ling3Config):
    """A KDA layer over ONE sequence's rows x [1, T, D] (`real` [T] marks
    those that are not padding) from its carried state [H, dh, dh] and
    tail [3, 3H, dh] -> (x, the state after, the pre-conv rows
    [T, 3H, dh])."""
    pre, log_a, beta, gate = _kda_project(h, layer, cfg)
    q, k, v = _kda_qkv(conv_chunk(pre[0], tail, layer["conv_w"],
                                  layer["conv_b"], jax.nn.silu, "kda_conv"),
                       cfg)
    heads_first = lambda a: jnp.swapaxes(a, 0, 1)
    o, state = kda_chunk(
        heads_first(q), heads_first(jnp.where(real[:, None, None], k, 0.0)),
        heads_first(v),
        heads_first(jnp.where(real[:, None, None], log_a[0], 0.0)),
        heads_first(jnp.where(real[:, None], beta[0], 0.0)), state)
    return _kda_out(x, heads_first(o)[None], gate, layer, cfg), state, pre[0]


def _mla(x, h, layer, pos, write, fetch, n_blocks, absorbed: bool,
         cfg: Ling3Config):
    """An MLA layer on x [B, T, D] at positions pos [B, T]: deepseek_v3's
    cached row and attention, a direct query projection, a head-wise
    sigmoid gate on the heads' outputs."""
    dt = cfg.dtype
    with jax.named_scope("mla_q"):
        q = jnp.einsum("btd,dhk->bhtk", h, layer["wq"].astype(dt))
        q_pe = apply_rope_interleaved(q[..., cfg.d_nope:], pos,
                                      cfg.rope_theta)
    write(_dm.latent_rows(h, layer, pos, cfg))
    o = _dm.latent_attend(q[..., :cfg.d_nope], q_pe, pos, fetch, n_blocks,
                          layer, absorbed, cfg)
    with jax.named_scope("mla_out"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bht", h, layer["w_head_gate"].astype(dt),
            preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
        return x + jnp.einsum("bhtv,hvd->btd", o,
                              layer["wo"].astype(dt)).astype(x.dtype)


def _normed(x, layer, name: str, cfg: Ling3Config):
    return rms_norm(x, layer[name], cfg.rms_eps).astype(cfg.dtype)


def _feed_forward(x, layer, cfg: Ling3Config, live=None):
    """x [B, T, D] with the layer's feed-forward added (deepseek_v3's:
    dense, or routed over the held experts + the shared one)."""
    B, T, D = x.shape
    ffn, held = _dm.layer_ffn(
        _normed(x, layer, "mlp_norm", cfg).reshape(B * T, D), layer, cfg,
        None if live is None else live.reshape(B * T))
    return x + ffn.reshape(B, T, D).astype(x.dtype), held


def apply(params, tokens, cfg: Ling3Config):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32; every sequence one chunk from an empty state and an empty tail,
    latent attention in its published (expanded) form over the sequence's
    own rows."""
    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.pad(pos, ((0, 0), (0, nb * kb - S)), constant_values=-1)
    H, dh = cfg.n_heads, cfg.d_head
    x = slot_embed(params, tokens, pos, cfg)
    for l, layer in enumerate(params["layers"]):
        h = _normed(x, layer, "attn_norm", cfg)
        if cfg.is_mla(l):
            box = {}

            def write(rows):
                box["rows"] = jnp.pad(rows,
                                      ((0, 0), (0, nb * kb - S), (0, 0)))

            def fetch(i):
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * kb, kb, 1)
                return sl(box["rows"]), sl(kpos)

            x = _mla(x, h, layer, pos, write, fetch, nb, False, cfg)
        else:
            x = jax.vmap(lambda x1, h1: _kda_sequence(
                x1[None], h1[None], layer, jnp.ones(S, bool),
                jnp.zeros((H, dh, dh), jnp.float32),
                jnp.zeros((CONV_TAPS - 1, 3 * H, dh), cfg.dtype), cfg)[0][0]
            )(x, h)
        x, _ = _feed_forward(x, layer, cfg)
    return _dm.head_logits(params, x, cfg)


# ---------------------------------------------------------------------------
# serving: latent pages and one state entry a sequence


def cache_kinds(cfg: Ling3Config) -> Dict[str, Any]:
    """name -> what the engine keeps for it (see gpt.cache_kinds): the MLA
    layers' latent rows in a full-kind pool of pages, the KDA layers'
    states and conv tails one entry of a `"state"` kind a sequence."""
    return {FULL: None, KDA: "state"}


def init_paged_cache(cfg: Ling3Config, num_pages, page_size: int):
    """{"latent": one arena an MLA layer (`deepseek_v3.latent_arenas`),
    "state": [KDA layers, entries, H, dh, dh] float32, "tail": [KDA
    layers, entries, 3, 3H, dh] in cfg.dtype — a tail is 3H whole tiles of
    dh lanes, not 3 rows of a 16-row tile}.  `num_pages` counts pages
    under `full` and entries under `kda`; page 0 and entry 0 are the null
    ones."""
    H, dh, n = cfg.n_heads, cfg.d_head, len(cfg.kda_layers)
    entries = int(num_pages[KDA])
    return {
        "latent": _dm.latent_arenas(cfg, num_pages[FULL], page_size,
                                    len(cfg.mla_layers)),
        "state": jnp.zeros((n, entries, H, dh, dh), jnp.float32),
        "tail": jnp.zeros((n, entries, CONV_TAPS - 1, 3 * H, dh), cfg.dtype),
    }


def state_leaves(cache) -> List[jax.Array]:
    """The leaves of `cache` that are the state kind's arena (the engine
    counts their bytes apart from the pages')."""
    return [cache["state"], cache["tail"]]


def _paged_pass(params, cache, toks, ptabs, pos, real, cfg: Ling3Config,
                kda_layer, absorbed=None):
    """Tokens toks [B, T] at CONSECUTIVE positions pos [B, T] through the
    layers; `real` [B, T] marks the rows that are kept and routed.  An MLA
    layer meets its pages through deepseek_v3's `page_io`; a KDA layer is
    `kda_layer(j, x, h, layer, state, tail)` -> (x, state, tail) over the
    two state arenas, j its index among the KDA layers.  Returns
    (x [B, T, D], cache, the expert layers' (loads, reads))."""
    T = toks.shape[1]
    if absorbed is None:
        absorbed = T <= ABSORB_ROWS
    latent = list(cache["latent"])
    state, tail = cache["state"], cache["tail"]
    if latent:
        bind, n_blocks = _dm.page_io(ptabs[FULL], pos, real,
                                     latent[0].shape[2], cfg)
    x = slot_embed(params, toks, pos, cfg)
    held, n_kda, n_mla = [], 0, 0
    for l, layer in enumerate(params["layers"]):
        h = _normed(x, layer, "attn_norm", cfg)
        if cfg.is_mla(l):
            write, fetch, box = bind(latent[n_mla])
            x = _mla(x, h, layer, pos, write, fetch, n_blocks, absorbed, cfg)
            latent[n_mla] = box["arena"]
            n_mla += 1
        else:
            x, state, tail = kda_layer(n_kda, x, h, layer, state, tail)
            n_kda += 1
        x, ld = _feed_forward(x, layer, cfg, live=real)
        if ld is not None:
            held.append(ld)
    return x, {"latent": latent, "state": state, "tail": tail}, held


def paged_decode_step(params, cache, tokens, ptabs, pos, cfg: Ling3Config,
                      absorbed=None):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[FULL] [B, R] the slots' pages, ptabs[KDA] [B, 1] their entries.
    A slot at position 0 is empty (a prompt has at least one token): it
    writes to the null page, routes nowhere and leaves the null entry as
    it is.  Returns (logits [B, V] f32, cache, stats)."""
    idx, live = ptabs[KDA][:, 0], pos > 0

    def kda_layer(j, x, h, layer, state, tail):
        pre, log_a, beta, gate = _kda_project(h, layer, cfg)
        old = tail[j][idx]
        u, new = conv_step(pre[:, 0], old, layer["conv_w"], layer["conv_b"],
                           jax.nn.silu, "kda_conv")
        tail = tail.at[j, idx].set(
            jnp.where(live[:, None, None, None], new, old))
        q, k, v = _kda_qkv(u, cfg)
        o, state = kda_step(q, k, v, log_a[:, 0], beta[:, 0], state, j, idx,
                            live, impl=cfg.kda_impl)
        return _kda_out(x, o[:, None], gate, layer, cfg), state, tail

    x, cache, held = _paged_pass(params, cache, tokens[:, None], ptabs,
                                 pos[:, None], live[:, None], cfg, kda_layer,
                                 absorbed)
    moved = states_moved(live, cfg.kda_impl).astype(jnp.float32)
    return (_dm.head_logits(params, x[:, 0], cfg), cache,
            jnp.stack(held_load_stats(held) + [moved]))


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx,
                  cfg: Ling3Config, absorbed=None):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its pages ptab_rows[FULL] [R] and its
    entry ptab_rows[KDA][0]: states and tails are read unless this is the
    sequence's first chunk (`start == 0`) and written back where they
    stand, the tail as the last three REAL pre-conv rows.  Returns (logits
    [V] f32 at row last_idx, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    real = t <= last_idx
    idx = ptab_rows[KDA][0]
    first = start == 0

    def kda_layer(j, x, h, layer, state, tail):
        s0 = carried_at(first, state, j, idx)
        t0 = carried_at(first, tail, j, idx)
        x, s1, pre = _kda_sequence(x, h, layer, real, s0, t0, cfg)
        t1 = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([t0, pre.astype(tail.dtype)]), last_idx + 1,
            CONV_TAPS - 1, 0)
        return (x, state.at[j, idx].set(s1.astype(state.dtype)),
                tail.at[j, idx].set(t1))

    x, cache, held = _paged_pass(
        params, cache, toks[None], {FULL: ptab_rows[FULL][None]},
        (start + t)[None], real[None], cfg, kda_layer, absorbed)
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    return (_dm.head_logits(params, x, cfg), cache,
            jnp.stack(held_load_stats(held) + [jnp.ones((), jnp.float32)]))


# the leaves the programs cast to cfg.dtype where they use them; the norms,
# the router with its bias and the gate's A_log and dt_bias are used as
# they are kept
_SERVE_CAST = frozenset({
    "embed", "unembed", "w_qkv", "conv_w", "conv_b", "w_f", "w_b", "w_g",
    "wo", "wq", "wkv_a", "w_uk", "w_uv", "w_head_gate", "w_gate", "w_up",
    "w_down", "wgu", "wd", "shared_gate", "shared_up", "shared_down"})


def serve_view(params, cfg: Ling3Config):
    """gpt.cast_leaves over this model's leaves, every MLA layer's `Wkvb`
    re-laid once into `w_uk` / `w_uv` and left out itself
    (`deepseek_v3.with_kv_up`)."""
    return cast_leaves(_dm.with_kv_up(params, cfg), cfg, _SERVE_CAST)
