"""Cohere2-MoE-class decoder (Command A+): the serving engine's second
model, behind the same module interface as models/gpt.py.

The layer, from the published config (`model_type: cohere2_moe`):

    h      = LayerNorm(x)                      weight only, f32 statistics
    q,k,v  = h Wq / h Wk / h Wv                n_heads Q on n_kv_heads K/V
    sliding layers: RoPE on q, k (interleaved pairs), keys 0 <= i-j < W
    full layers:    no position signal, keys j <= i
    attn   = softmax(q k^T / sqrt(dh)) v Wo
    routed = sum over the top-k of sigmoid(h Wr), weights normalised over
             the chosen k, of SwiGLU experts          (ops/moe.py)
    shared = mean of n_shared SwiGLU experts
    x'     = x + attn + routed + shared               (parallel block)
    logits = logit_scale * LayerNorm(x_L) E^T         (tied embedding)

What is its own lives here: the layer *pattern* as data (`layer_types`),
the parallel block, the expert layer's call, and two kinds of paged KV
state (`cache_kinds`): a full layer keeps every position, a sliding layer
only the last `sliding_window`, in a ring of pages the engine refills and
returns as the window passes.  What the family shares is called, not
copied: gpt's `apply_norm`, `qkv_of_normed`, `attn_out`, `slot_embed`,
`unembed_table` and `cast_leaves`; `ops.layers.swiglu` and
`apply_rope_interleaved`; `ops.attention.streamed_attention`;
`ops.moe.route_sigmoid_topk` / `held_expert_ffn` / `held_load_stats`.

A chip may hold a *share* of the model: `experts_held` of `n_experts`
experts from `experts_first` on (the router keeps its full width; what the
absent experts would add is left out, as under expert parallelism before
its exchange), and `vocab_size` rows of the vocabulary.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import streamed_attention
from ray_tpu.ops.layers import apply_rope_interleaved, swiglu
from ray_tpu.ops.moe import (held_expert_ffn, held_load_stats,
                             route_sigmoid_topk)

from .gpt import (apply_norm, attn_out, cast_leaves, qkv_of_normed,
                  slot_embed, unembed_table)
from .served import kind_io, page_blocks

__all__ = ["Cohere2MoEConfig", "init", "apply", "cache_kinds",
           "init_paged_cache", "paged_decode_step", "paged_prefill",
           "serve_view", "STEP_STATS"]

# what a serve program returns beside logits and cache, in this order
# (f32 scalars, summed over the layers): token-expert pairs that fell on
# held experts, the largest load of a held expert, held experts touched,
# held experts' visits by a trip of grouped products (over the touched:
# how often a touched expert's weights were read; `ops.moe.held_load_stats`)
STEP_STATS = ("moe_pairs", "moe_load_max", "moe_touched", "moe_reads")


@dataclasses.dataclass(frozen=True)
class Cohere2MoEConfig:
    vocab_size: int = 32768           # rows of the vocabulary held here
    n_layers: int = 4
    d_model: int = 4096
    n_heads: int = 128
    n_kv_heads: int = 8
    d_head: int = 128
    d_expert: int = 4096              # one expert's width
    n_experts: int = 128              # the router's width
    experts_first: int = 0            # experts held: first..first+held-1
    experts_held: int = 128
    top_k: int = 8
    n_shared: int = 4
    layer_types: Tuple[str, ...] = ("sliding", "sliding", "sliding", "full")
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    max_seq: int = 16384
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    kv_block: int = 512               # keys scored at once on the serve path
    # at most this many sorted rows a product; the row block of `ops/moe`
    # (ROW_BLOCK, 128: what the chip's grouped matmul takes before a visited
    # expert's product outlasts its weights' read) is the usual bound, so
    # this binds only where it is smaller (the tiny configuration's 16).
    # Only the pairs on held experts are computed (an eighth of them when
    # 16 of 128 experts are held), for as many trips as they need
    moe_tile: int = 1024
    # what gpt's shared helpers read off a config
    norm: str = "ln"
    pos: str = "rope"
    attn_bias: bool = False
    tie_embeddings: bool = True

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(f"{len(self.layer_types)} layer types for "
                             f"{self.n_layers} layers")
        if set(self.layer_types) - {"sliding", "full"}:
            raise ValueError(f"unknown layer type in {self.layer_types}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.experts_first + self.experts_held > self.n_experts:
            raise ValueError("held experts run past n_experts")

    @classmethod
    def nano(cls, **kw):
        """The pattern at toy size, for the CPU tests: 4 layers (three
        sliding, one full), 16 experts top-4 of which 4 are held, window
        8, 8 query heads on 2 K/V heads."""
        base = dict(vocab_size=256, n_layers=4, d_model=64, n_heads=8,
                    n_kv_heads=2, d_head=16, d_expert=32, n_experts=16,
                    experts_first=4, experts_held=4, top_k=4, n_shared=2,
                    sliding_window=8, max_seq=64, kv_block=16, moe_tile=16)
        base.update(kw)
        return cls(**base)


def init_layer(key, cfg: Cohere2MoEConfig) -> Dict[str, Any]:
    """One layer's weights (a program of its own: at published widths a
    layer's share is 2.3 GB, and made one at a time only one layer's
    random bits are alive beside the weights)."""
    D, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    F, C, S = cfg.d_expert, cfg.experts_held, cfg.n_shared
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 11))

    def dense(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * (scale / math.sqrt(fan_in))).astype(pd)

    out = 1.0 / math.sqrt(2 * cfg.n_layers)
    return {
        "attn_norm": jnp.ones((D,), pd),
        "wq": dense((D, H, dh), D), "wk": dense((D, Hkv, dh), D),
        "wv": dense((D, Hkv, dh), D),
        "wo": dense((H, dh, D), H * dh, out),
        # the router is kept and applied in f32
        "router": dense((D, cfg.n_experts), D).astype(jnp.float32),
        "wg": dense((C, D, F), D), "wu": dense((C, D, F), D),
        "wd": dense((C, F, D), F, out),
        # the shared experts side by side: one SwiGLU of width S*F whose
        # output is the SUM of theirs (the block divides by S)
        "shared_gate": dense((D, S * F), D), "shared_up": dense((D, S * F), D),
        "shared_down": dense((S * F, D), F, out),
    }


def init(key, cfg: Cohere2MoEConfig) -> Dict[str, Any]:
    """The param tree: `layers` is a list (a layer's weights are buffers
    of their own: the grouped expert product takes them whole)."""
    ke, *kl = jax.random.split(key, cfg.n_layers + 1)
    one = jax.jit(init_layer, static_argnames="cfg")
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(
                                        cfg.param_dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "layers": [one(k, cfg=cfg) for k in kl],
    }


# ---------------------------------------------------------------------------
# the block


def _ffn(h, layer, cfg: Cohere2MoEConfig, live=None):
    """Routed (held experts' part) + mean of the shared experts, on the
    normed input h [N, D].  Returns ([N, D], (loads [held], reads):
    `held_expert_ffn`'s counts, for `held_load_stats`)."""
    with jax.named_scope("moe_router"):
        w, idx = route_sigmoid_topk(h, layer["router"], cfg.top_k)
    with jax.named_scope("moe_experts"):
        # gate and up APART, three products a trip (`deepseek_v3` lays them
        # in one leaf, two products): the benchmark's check reads `wg` and
        # `wu` from the tree `init` returns, and F = 4,096 tiles whole as
        # it is
        routed, loads, reads = held_expert_ffn(
            h, w, idx, layer["wg"], layer["wu"], layer["wd"],
            first=cfg.experts_first, tile=cfg.moe_tile, live=live)
    with jax.named_scope("moe_shared"):
        shared = swiglu(h, layer["shared_gate"].astype(cfg.dtype),
                        layer["shared_up"].astype(cfg.dtype),
                        layer["shared_down"].astype(cfg.dtype))
    routed = routed + shared.astype(jnp.float32) / cfg.n_shared
    return routed, (loads, reads)


def _block(x, layer, kind: str, pos, attend, cfg: Cohere2MoEConfig,
           live=None):
    """One parallel block on x [B, T, D] at positions pos [B, T].
    `attend(q [B,Hkv,G,T,dh], k, v [B,Hkv,T,dh]) -> [B,Hkv,G,T,dh]` owns
    the keys (a cache, or the sequence itself).  `live` [B, T] marks the
    rows that are real (pad rows and empty slots do not route)."""
    B, T, D = x.shape
    G = cfg.n_heads // cfg.n_kv_heads
    h = apply_norm(x, layer["attn_norm"], None, cfg.norm).astype(cfg.dtype)
    q, k, v = qkv_of_normed(h, layer, cfg)
    if kind == "sliding":
        q = apply_rope_interleaved(q, pos, cfg.rope_theta)
        k = apply_rope_interleaved(k, pos, cfg.rope_theta)
    with jax.named_scope("attn_window" if kind == "sliding" else "attn_full"):
        o = attend(q.reshape(B, cfg.n_kv_heads, G, T, cfg.d_head), k, v)
    att = attn_out(o.reshape(B, cfg.n_heads, T, cfg.d_head), layer, cfg)
    ffn, held = _ffn(h.reshape(B * T, D), layer, cfg,
                     None if live is None else live.reshape(B * T))
    x = x + att + ffn.reshape(B, T, D).astype(x.dtype)
    return x, held


def _window(kind: str, cfg: Cohere2MoEConfig) -> Optional[int]:
    return cfg.sliding_window if kind == "sliding" else None


def _logits(params, x, cfg: Cohere2MoEConfig):
    x = apply_norm(x, params["final_norm"], None, cfg.norm)
    lg = jnp.einsum("...d,dv->...v", x.astype(cfg.dtype),
                    unembed_table(params, cfg),
                    preferred_element_type=jnp.float32)
    return lg * cfg.logit_scale


def apply(params, tokens, cfg: Cohere2MoEConfig):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32.  The keys are the sequence itself, streamed `kv_block` at a
    time through the recipe the serve programs use."""
    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = slot_embed(params, tokens, pos, cfg)

    def attend_for(kind):
        def attend(q, k, v):
            pad = nb * kb - S
            kp, vp = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for a in (k, v))
            kpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)

            def fetch(i):
                sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(
                    a, i * kb, kb, ax)
                return sl(kp, 2), sl(vp, 2), sl(kpos, 1)

            return streamed_attention(q, pos, fetch, nb,
                                      window=_window(kind, cfg))
        return attend

    for layer, kind in zip(params["layers"], cfg.layer_types):
        x, _ = _block(x, layer, kind, pos, attend_for(kind), cfg)
    return _logits(params, x, cfg)


# ---------------------------------------------------------------------------
# paged serving: two pools of pages, one page table a sequence and kind


def cache_kinds(cfg: Cohere2MoEConfig) -> Dict[str, Optional[int]]:
    """name -> window of the pools the engine keeps for this model (see
    gpt.cache_kinds).  A windowed kind's table is a RING (`served.kind_io`)
    that the engine sizes to window + its longest prefill chunk."""
    return {k: _window(k, cfg) for k in ("full", "sliding")
            if k in cfg.layer_types}


def init_paged_cache(cfg: Cohere2MoEConfig, num_pages: Dict[str, int],
                     page_size: int) -> List[Dict[str, Any]]:
    """One arena a layer, [pages of its kind, page_size, Hkv, dh] a side
    (a position's K/V heads lie together: that is the order the chip's
    compiler wants for the row scatter and the page gather, and given any
    other it copies the whole arena into this one and back, every
    program); page 0 of every pool is the null page.  The serve programs
    are given the arenas to keep (the engine donates them) and each
    layer's scatter writes its rows where the arena stands."""
    def arena(kind):
        shape = (num_pages[kind], page_size, cfg.n_kv_heads, cfg.d_head)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}
    return [arena(kind) for kind in cfg.layer_types]


def _paged_attend(kind, arena, tab, bases, qpos, write_at, n_blocks,
                  cfg: Cohere2MoEConfig):
    """attend() of _block against one layer's arena: write this call's K
    and V rows at (page, offset) `write_at`, then stream the table's pages
    `kv_block` keys at a time.  Returns (attend, box): box["arena"] is the
    updated arena once attend has run."""
    ps = arena["k"].shape[1]
    npb = max(1, cfg.kv_block // ps)
    B = tab.shape[0]
    box = {}

    def attend(q, k, v):
        pidx, poff = write_at
        rows = lambda a: jnp.moveaxis(a, 2, 1).reshape(
            -1, cfg.n_kv_heads, cfg.d_head)                # [B*T, Hkv, dh]
        kc = arena["k"].at[pidx, poff].set(rows(k).astype(cfg.dtype))
        vc = arena["v"].at[pidx, poff].set(rows(v).astype(cfg.dtype))
        box["arena"] = {"k": kc, "v": vc}
        fetch = page_blocks(tab, bases, kc, vc, npb, lambda c: jnp.moveaxis(
            c.reshape(B, npb * ps, cfg.n_kv_heads, cfg.d_head), 2, 1))
        return streamed_attention(q, qpos, fetch, n_blocks,
                                  window=_window(kind, cfg))

    return attend, box


def _paged_pass(params, cache, toks, ptabs, pos, real, cfg):
    """Tokens toks [B, T] at positions pos [B, T] through the layers
    against the paged cache; `real` [B, T] marks the rows whose K and V are
    kept (the others are written to the null page and do not route).
    ptabs[kind] is [B, R_kind].  Returns (x [B, T, D], cache, stats)."""
    B, T = toks.shape
    ps = cache[0]["k"].shape[1]
    npb = max(1, cfg.kv_block // ps)
    x = slot_embed(params, toks, pos, cfg)
    last = jnp.max(pos, axis=1)                            # [B]
    flat_pos = pos.reshape(B * T)
    per_kind = {kind: kind_io(kind, tab, pos, real, last, flat_pos, ps, npb)
                for kind, tab in ptabs.items()}
    new_cache, held = [], []
    for layer, kind, arena in zip(params["layers"], cfg.layer_types, cache):
        tabp, bases, write_at, n_blocks = per_kind[kind]
        attend, box = _paged_attend(kind, arena, tabp, bases, pos, write_at,
                                    n_blocks, cfg)
        x, ld = _block(x, layer, kind, pos, attend, cfg, live=real)
        new_cache.append(box["arena"])
        held.append(ld)
    return x, new_cache, jnp.stack(held_load_stats(held))


def paged_decode_step(params, cache, tokens, ptabs, pos, cfg):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[kind] [B, R_kind].  A slot at position 0 is empty (a prompt has
    at least one token): it writes to the null page and routes nowhere.
    Returns (logits [B, V] f32, cache, stats)."""
    live = (pos > 0)[:, None]
    x, cache, stats = _paged_pass(params, cache, tokens[:, None], ptabs,
                                  pos[:, None], live, cfg)
    return _logits(params, x[:, 0], cfg), cache, stats


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx, cfg):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its table rows ptab_rows[kind] [R].
    Returns (logits [V] f32 at row last_idx, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    x, cache, stats = _paged_pass(
        params, cache, toks[None], {k: r[None] for k, r in ptab_rows.items()},
        (start + t)[None], (t <= last_idx)[None], cfg)
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    return _logits(params, x, cfg), cache, stats


# the leaves the programs cast to cfg.dtype where they use them (gpt's
# shared helpers, _ffn's shared experts, held_expert_ffn); the router and
# the norms are used as they are kept
_SERVE_CAST = frozenset({"embed", "wq", "wk", "wv", "wo", "wg", "wu", "wd",
                         "shared_gate", "shared_up", "shared_down"})


def serve_view(params, cfg: Cohere2MoEConfig):
    """gpt.cast_leaves over this model's leaves: a tree kept in cfg.dtype
    (the published configuration's) comes back as the same arrays."""
    return cast_leaves(params, cfg, _SERVE_CAST)
