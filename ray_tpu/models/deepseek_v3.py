"""DeepSeek-V3-class decoder: multi-head latent attention over a paged
cache of ONE latent row a position, group-limited sigmoid routing with a
correction bias, dense layers before the expert layers — the serving
engine's fourth model, behind the same module interface as models/gpt.py,
models/cohere2_moe.py and models/brumby.py.

The layer (`model_type: deepseek_v3`; RMSNorm, statistics in f32):

    h     = RMSNorm(x)
    c_q   = RMSNorm(h Wqa);  q = c_q Wqb -> H heads x (q_nope dn | q_pe dr)
    c_kv | k_pe = h Wkva;    c_kv = RMSNorm(c_kv)         <- the cached row
    k_nope_h | v_h = c_kv Wkvb                            H heads x (dn | dv)
    q_pe, k_pe <- RoPE (interleaved pairs, YaRN frequencies); one k_pe
             for all heads
    s_h[i,j] = (q_nope_h[i].k_nope_h[j] + q_pe_h[i].k_pe[j]) * scale, j <= i
    x     = x + concat_h(softmax(s_h) v_h) Wo
    h2    = RMSNorm(x)
    dense layer (index < n_dense):  x = x + SwiGLU(h2)
    expert layer:                   x = x + routed(h2) + SwiGLU_shared(h2)
    logits = RMSNorm(x_L) Wout                            (untied head)

`scale` is (dn + dr)^-1/2 times the square of YaRN's 0.1 ln(factor) + 1.

What a position leaves in the cache is `c_kv | k_pe`: `kv_rank + d_rope`
values (512 + 64) a layer in cfg.dtype — no K side, no V side, no head
axis (`init_paged_cache`: one arena [pages, 576, page_size] a layer, one
full-kind pool; a page's positions lie along the lanes, a position is a
column: 576 is 4.5 lane-widths and would be padded to 640 along them).
Attention reads it in two forms, by the rows a program carries
(`ABSORB_ROWS`):

  * absorbed (a decode step): `Wkvb`'s key half is folded into the query
    (q_lat_h = q_nope_h W_UK_h^T, kv_rank wide) and its value half into
    the output (o_h = (sum_j p_j c_kv[j]) W_UV_h), so the H heads score
    ONE shared key — the latent row as it lies, 576 wide — and nothing a
    head wide is formed per key.  On a TPU a step (one row a slot) walks
    each live slot's own pages with `ops/attention.latent_decode_attention`
    and touches no other; anywhere else, and for a short chunk, blocks of
    every slot's pages go through `streamed_attention`'s XLA body;
  * expanded (a prefill chunk): each block of cached latents goes through
    `Wkvb` once for all the chunk's queries (the published form; cheaper
    from about 170 query rows a program on, PERF.md section 6).

`W_UK` / `W_UV` are `Wkvb` re-laid; `serve_view` makes them once (and
drops `Wkvb`: no byte is held twice), the programs never do.

A chip may hold a *share* of the model (models/cohere2_moe.py): the
experts `experts_first..+experts_held-1` of `n_experts` (the router keeps
its full width, groups and experts a token) and `vocab_size` rows of the
vocabulary; `n_layers` counts the layers held, `n_dense` of them dense.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (latent_decode_attention,
                                   latent_decode_uses_kernel,
                                   latent_walked_keys, streamed_attention)
from ray_tpu.ops.layers import (apply_rope_interleaved, rms_norm, swiglu,
                                yarn_frequencies)
from ray_tpu.ops.moe import (held_expert_ffn, held_experts_leaf,
                             held_load_stats, route_sigmoid_grouped)

from . import served
from .gpt import cast_leaves, slot_embed, unembed_table

__all__ = ["DeepSeekV3Config", "init", "apply", "cache_kinds",
           "init_paged_cache", "paged_decode_step", "paged_prefill",
           "copy_page", "serve_view", "STEP_STATS",
           # what models/ling3.py builds on: its latent-attention layers
           # ARE these (the cached row, both forms of attention over it,
           # how a program's rows meet the pages, the re-laid `Wkvb`), as
           # its feed-forward and its head are
           "latent_rows", "latent_attend", "page_io", "latent_arenas",
           "kv_up", "with_kv_up", "layer_ffn", "head_logits"]

# what a serve program returns beside logits and cache, in this order (f32
# scalars, summed over the layers): token-expert pairs that fell on held
# experts, the largest load of a held expert, held experts touched, held
# experts' visits by a trip of grouped products (as cohere2_moe); (query,
# visible key) pairs of one head, and keys visible to the program — a
# step's live rows see their own contexts, a chunk's rows one context; key
# positions its attention FETCHED (`mla_keys` is what it needed: the block
# loop fetches every row of the batch every block up to the longest
# context, the decode kernel each live slot's own pages)
STEP_STATS = ("moe_pairs", "moe_load_max", "moe_touched", "moe_reads",
              "mla_pairs", "mla_keys", "mla_walked_keys")

KIND = "full"

# A program of at most this many rows a sequence attends absorbed (the
# decode step: one row), a longer one (a prefill chunk) expands the cached
# latents.  By arithmetic the forms break even near 170 rows; on the chip a
# 512-row chunk costs 6.7 ms a thousand keys of context expanded and 10.4
# absorbed (PERF.md section 6, PR 44).
ABSORB_ROWS = 128


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Config:
    vocab_size: int = 129280           # rows of the vocabulary held here
    n_layers: int = 61
    n_dense: int = 3                   # leading layers with a dense SwiGLU
    d_model: int = 7168
    n_heads: int = 128
    q_rank: int = 1536                 # the query's low-rank width
    kv_rank: int = 512                 # the latent a position is cached as
    d_nope: int = 128                  # a head's part without position
    d_rope: int = 64                   # the rope part, one key for all heads
    d_v: int = 128
    d_ff: int = 18432                  # a dense layer's SwiGLU
    d_expert: int = 2048               # one expert's (and the shared one's)
    n_experts: int = 256               # the router's width
    experts_first: int = 0             # experts held: first..first+held-1
    experts_held: int = 256
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scale: float = 2.5
    n_shared: int = 1
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    # YaRN: (factor, beta_fast, beta_slow, original_max); None = plain rope
    yarn: Optional[tuple] = (40.0, 32.0, 1.0, 4096)
    max_seq: int = 163840
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
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

    @property
    def d_latent(self) -> int:
        return self.kv_rank + self.d_rope

    @property
    def softmax_scale(self) -> float:
        m = 1.0 if self.yarn is None else 0.1 * math.log(self.yarn[0]) + 1.0
        return (self.d_nope + self.d_rope) ** -0.5 * m * m

    def rope_freqs(self):
        if self.yarn is None:
            return None
        factor, fast, slow, orig = self.yarn
        return yarn_frequencies(self.d_rope, self.rope_theta, factor, fast,
                                slow, int(orig))

    @classmethod
    def nano(cls, **kw):
        """The plan at toy size, for the CPU tests: one dense layer, then
        two expert layers of 16 experts in 4 groups (2 kept, top-4) of
        which 4 are held; 4 heads of (8 | 4) on a latent of 16 + 4."""
        base = dict(vocab_size=256, n_layers=3, n_dense=1, d_model=64,
                    n_heads=4, q_rank=24, kv_rank=16, d_nope=8, d_rope=4,
                    d_v=8, d_ff=96, d_expert=32, n_experts=16,
                    experts_first=4, experts_held=4, top_k=4, n_group=4,
                    topk_group=2, max_seq=128, kv_block=16, moe_tile=16,
                    yarn=(4.0, 32.0, 1.0, 16))
        base.update(kw)
        return cls(**base)


# the draw is `served.draw`'s recipe (it began here; `_draw` below), a leaf's
# place its index here; norms are ones and the correction bias zeros (a
# fresh router's).  `wg` and `wu` keep their places in the recipe (the
# benchmark's reference draws them there) and lie side by side in ONE leaf
# of the tree, `wgu` [held, D, 2F]: `ops.moe.held_experts_leaf`.
LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
          "router", "wg", "wu", "wd", "shared_gate", "shared_up",
          "shared_down")


def _draw(key, layer: int, place: int, shape, std: float, dtype):
    # `served.draw`'s values, a dispatch a piece (PERF.md section 6, PR 65)
    size, n = math.prod(shape), served.DRAW_PIECE
    parts = [served.piece(key, layer, place, i, jnp.float32(std), n,
                          jnp.dtype(dtype)) for i in range(-(-size // n))]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return flat[:size].reshape(shape)


def init_layer(key, cfg: DeepSeekV3Config, l: int) -> Dict[str, Any]:
    """Layer l's weights (dense where l < cfg.n_dense)."""
    D, H, pd = cfg.d_model, cfg.n_heads, cfg.param_dtype
    rq, rkv, dn, dr, dv = (cfg.q_rank, cfg.kv_rank, cfg.d_nope, cfg.d_rope,
                           cfg.d_v)
    out = 1.0 / math.sqrt(2 * cfg.n_layers)

    def w(name, shape, fan_in, scale=1.0, dtype=pd):
        return _draw(key, l, LEAVES.index(name), shape,
                     scale / math.sqrt(fan_in), dtype)

    layer = {
        "attn_norm": jnp.ones((D,), pd), "q_norm": jnp.ones((rq,), pd),
        "kv_norm": jnp.ones((rkv,), pd), "mlp_norm": jnp.ones((D,), pd),
        "wq_a": w("wq_a", (D, rq), D),
        "wq_b": w("wq_b", (rq, H, dn + dr), rq),
        "wkv_a": w("wkv_a", (D, rkv + dr), D),
        "wkv_b": w("wkv_b", (rkv, H, dn + dv), rkv),
        "wo": w("wo", (H, dv, D), H * dv, out),
    }
    if l < cfg.n_dense:
        F = cfg.d_ff
        layer.update(w_gate=w("w_gate", (D, F), D), w_up=w("w_up", (D, F), D),
                     w_down=w("w_down", (F, D), F, out))
        return layer
    F, C, S = cfg.d_expert, cfg.experts_held, cfg.n_shared
    layer.update(
        # the router and its bias are kept and applied in f32
        router=w("router", (D, cfg.n_experts), D, dtype=jnp.float32),
        router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
        wgu=held_experts_leaf(w("wg", (C, D, F), D), w("wu", (C, D, F), D)),
        wd=w("wd", (C, F, D), F, out),
        # the shared experts side by side: one SwiGLU of width S*F whose
        # output is the sum of theirs
        shared_gate=w("shared_gate", (D, S * F), D),
        shared_up=w("shared_up", (D, S * F), D),
        shared_down=w("shared_down", (S * F, D), F, out))
    return layer


def init(key, cfg: DeepSeekV3Config) -> Dict[str, Any]:
    """The param tree: `layers` is a list (dense and expert layers hold
    different leaves; a layer's weights are buffers of their own)."""
    V, D, pd = cfg.vocab_size, cfg.d_model, cfg.param_dtype
    return {
        "embed": _draw(key, -1, 0, (V, D), 0.02, pd),
        "unembed": _draw(key, -1, 1, (D, V), 1.0 / math.sqrt(D), pd),
        "final_norm": jnp.ones((D,), pd),
        "layers": [init_layer(key, cfg, l) for l in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# the block


def kv_up(layer, cfg: DeepSeekV3Config):
    """(W_UK [H, dn, rkv], W_UV [H, rkv, dv]) in cfg.dtype: the serve
    view's own leaves, or `Wkvb` re-laid where a program is handed the
    plain tree (a test, `apply`)."""
    if "w_uk" in layer:
        return layer["w_uk"], layer["w_uv"]
    wkv_b = layer["wkv_b"].astype(cfg.dtype)
    return (jnp.transpose(wkv_b[:, :, :cfg.d_nope], (1, 2, 0)),
            jnp.transpose(wkv_b[:, :, cfg.d_nope:], (1, 0, 2)))


def _queries(h, layer, pos, cfg: DeepSeekV3Config):
    """h [B, T, D] normed -> (q_nope [B, H, T, dn], q_pe [B, H, T, dr])."""
    dt = cfg.dtype
    with jax.named_scope("mla_q"):
        c_q = rms_norm(jnp.einsum("btd,dr->btr", h, layer["wq_a"].astype(dt)),
                       layer["q_norm"], cfg.rms_eps)
        q = jnp.einsum("btr,rhk->bhtk", c_q, layer["wq_b"].astype(dt))
        q_pe = apply_rope_interleaved(q[..., cfg.d_nope:], pos,
                                      cfg.rope_theta, cfg.rope_freqs())
        return q[..., :cfg.d_nope], q_pe


def latent_rows(h, layer, pos, cfg: DeepSeekV3Config):
    """h [B, T, D] normed -> the rows the cache keeps, [B, T, rkv + dr] in
    cfg.dtype: the normed latent beside the turned rope key."""
    dt = cfg.dtype
    with jax.named_scope("mla_kv"):
        a = jnp.einsum("btd,dr->btr", h, layer["wkv_a"].astype(dt))
        c_kv = rms_norm(a[..., :cfg.kv_rank], layer["kv_norm"], cfg.rms_eps)
        k_pe = apply_rope_interleaved(a[:, None, :, cfg.kv_rank:], pos,
                                      cfg.rope_theta, cfg.rope_freqs())[:, 0]
        return jnp.concatenate([c_kv, k_pe], axis=-1).astype(dt)


def latent_attend(q_nope, q_pe, qpos, fetch, n_blocks, layer,
                  absorbed: bool, cfg: DeepSeekV3Config, window=None,
                  scope: str = "mla_attend"):
    """Heads' outputs [B, H, T, dv] of queries at positions qpos [B, T]
    against cached latents: `fetch(i)` -> (block i's rows [B, S, rkv + dr],
    their positions [B, S], negative where there is none) and, where the
    caller SELECTS keys, a third item, the keys of the block each query
    keeps [B, T, S] (models/dots3.py: a learned indexer's choice).  Query
    t sees key s iff 0 <= qpos - kpos (< `window`, where one is given) and
    it keeps it.  Where the latents lie in pages, `fetch.pages()` ->
    (arena, table [B, R], the positions of each slot's row to walk [B][,
    which of them it keeps [B, R * ps]]), and an absorbed step on a TPU
    (one row a slot: `latent_decode_uses_kernel`) walks them with the
    decode kernel and fetches no block.  `scope` names the two forms'
    `jax.named_scope`s (`<scope>_step`, `<scope>_chunk`)."""
    B, H, T, _ = q_nope.shape
    rkv = cfg.kv_rank
    w_uk, w_uv = kv_up(layer, cfg)
    if absorbed:
        with jax.named_scope(scope + "_step"):
            q_lat = jnp.einsum("bhtn,hnr->bhtr", q_nope, w_uk)
            q = jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)
            if hasattr(fetch, "pages") and latent_decode_uses_kernel(T):
                q1 = q[:, :, 0]
                arena, tab, ctx, *keep = fetch.pages()
                o = latent_decode_attention(
                    q1, arena, tab, ctx, scale=cfg.softmax_scale,
                    v_dim=rkv, **({"keep": keep[0]} if keep else {})
                    )[:, :, None]
                return jnp.einsum("bhtr,hrv->bhtv", o, w_uv)

            def one_key(i):                 # the latent as it lies
                rows, kpos, *keep = fetch(i)
                return rows[:, None], rows[:, None, :, :rkv], kpos, *keep

            o = streamed_attention(q[:, None], qpos, one_key, n_blocks,
                                   window=window, scale=cfg.softmax_scale,
                                   v_dim=rkv)
            return jnp.einsum("bhtr,hrv->bhtv", o[:, 0], w_uv)
    with jax.named_scope(scope + "_chunk"):
        q = jnp.concatenate([q_nope, q_pe], axis=-1)

        def per_head(i):                    # the published form
            rows, kpos, *keep = fetch(i)
            c_kv, k_pe = rows[..., :rkv], rows[..., rkv:]
            k_nope = jnp.einsum("bsr,hnr->bhsn", c_kv, w_uk)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe[:, None],
                                          (B, H) + k_pe.shape[1:])], axis=-1)
            return (k, jnp.einsum("bsr,hrv->bhsv", c_kv, w_uv), kpos,
                    *keep)

        o = streamed_attention(q[:, :, None], qpos, per_head, n_blocks,
                               window=window, scale=cfg.softmax_scale,
                               v_dim=cfg.d_v)
        return o[:, :, 0]


def layer_ffn(h, layer, cfg: DeepSeekV3Config, live=None):
    """The layer's feed-forward on the normed input h [N, D] -> ([N, D]
    f32, `held_expert_ffn`'s (loads [held], reads) or None for a dense
    layer)."""
    dt = cfg.dtype
    if "router" not in layer:
        with jax.named_scope("mlp"):
            return swiglu(h, layer["w_gate"].astype(dt),
                          layer["w_up"].astype(dt),
                          layer["w_down"].astype(dt)).astype(jnp.float32), None
    with jax.named_scope("moe_router"):
        w, idx = route_sigmoid_grouped(
            h, layer["router"], layer["router_bias"], cfg.top_k,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            scale=cfg.routed_scale)
    with jax.named_scope("moe_experts"):
        routed, loads, reads = held_expert_ffn(
            h, w, idx, layer["wgu"], None, layer["wd"],
            first=cfg.experts_first, tile=cfg.moe_tile, live=live)
    with jax.named_scope("moe_shared"):
        shared = swiglu(h, layer["shared_gate"].astype(dt),
                        layer["shared_up"].astype(dt),
                        layer["shared_down"].astype(dt))
    return routed + shared.astype(jnp.float32), (loads, reads)


def _block(x, layer, pos, write, fetch, n_blocks, absorbed: bool,
           cfg: DeepSeekV3Config, live=None):
    """One sequential pre-norm block on x [B, T, D] at positions pos
    [B, T].  `write(rows [B, T, rkv + dr])` keeps this call's latent rows
    (before any is read: a query sees its own key), `fetch` reads blocks
    of the kept ones back.  `live` [B, T] marks the rows that are real
    (pad rows and empty slots do not route)."""
    B, T, D = x.shape
    dt = cfg.dtype
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(dt)
    q_nope, q_pe = _queries(h, layer, pos, cfg)
    write(latent_rows(h, layer, pos, cfg))
    o = latent_attend(q_nope, q_pe, pos, fetch, n_blocks, layer, absorbed,
                      cfg)
    with jax.named_scope("mla_out"):
        x = x + jnp.einsum("bhtv,hvd->btd", o.astype(dt),
                           layer["wo"].astype(dt)).astype(x.dtype)
    h2 = rms_norm(x, layer["mlp_norm"], cfg.rms_eps).astype(dt)
    ffn, held = layer_ffn(h2.reshape(B * T, D), layer, cfg,
                          None if live is None else live.reshape(B * T))
    return x + ffn.reshape(B, T, D).astype(x.dtype), held


def head_logits(params, x, cfg: DeepSeekV3Config):
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return jnp.einsum("...d,dv->...v", x.astype(cfg.dtype),
                          unembed_table(params, cfg),
                          preferred_element_type=jnp.float32)


def _stats(held: list, pos, real, walked, cfg: DeepSeekV3Config):
    """The STEP_STATS vector of one program: its expert layers' (loads,
    reads), its rows' positions and which of them are real, the key
    positions its layers fetched."""
    seen = jnp.where(real, pos + 1, 0).astype(jnp.float32)     # [B, T]
    mla = [seen.sum() * cfg.n_layers, seen.max(axis=1).sum() * cfg.n_layers,
           jnp.asarray(walked, jnp.float32)]
    return jnp.stack(held_load_stats(held) + mla)


def apply(params, tokens, cfg: DeepSeekV3Config):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32, in the published (expanded) form; the keys are the sequence's own
    latent rows, streamed `kv_block` at a time."""
    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.pad(pos, ((0, 0), (0, nb * kb - S)), constant_values=-1)
    x = slot_embed(params, tokens, pos, cfg)
    for layer in params["layers"]:
        box = {}

        def write(rows):
            box["rows"] = jnp.pad(rows, ((0, 0), (0, nb * kb - S), (0, 0)))

        def fetch(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * kb, kb, 1)
            return sl(box["rows"]), sl(kpos)

        x, _ = _block(x, layer, pos, write, fetch, nb, False, cfg)
    return head_logits(params, x, cfg)


# ---------------------------------------------------------------------------
# paged serving: one pool of latent pages, one page table a sequence


def cache_kinds(cfg: DeepSeekV3Config) -> Dict[str, Optional[int]]:
    """One full-kind pool (see gpt.cache_kinds): every layer keeps every
    position, as one latent row."""
    return {KIND: None}


def _only(x):
    return x[KIND] if isinstance(x, dict) else x


def latent_arenas(cfg, pages: int, page_size: int, layers: int
                  ) -> List[jax.Array]:
    """One arena a latent-attention layer, [pages, kv_rank + d_rope,
    page_size] in cfg.dtype: a position is ONE latent row (`c_kv | k_pe`),
    with no K side, no V side and no head axis, laid as a COLUMN of its
    page — the page's 128 positions fill the lanes and the 576 values 36
    sublane groups, so a position costs its 1,152 B and nothing more (576
    along the lanes is padded to 640, and the chip's compiler, asked for
    that, re-laid the whole arena into this form and back in every
    program).  Page 0 is the null page.  `page_io` and the decode kernel
    read and write this layout."""
    shape = (int(pages), cfg.kv_rank + cfg.d_rope, page_size)
    return [jnp.zeros(shape, cfg.dtype) for _ in range(layers)]


def init_paged_cache(cfg: DeepSeekV3Config, num_pages, page_size: int
                     ) -> List[jax.Array]:
    """`latent_arenas`, one a layer.  The serve programs are given them to
    keep (the engine donates them) and write whole pages where the arena
    stands (`_paged_pass`)."""
    return latent_arenas(cfg, _only(num_pages), page_size, cfg.n_layers)


def page_io(ptab, pos, real, ps: int, cfg, window: Optional[int] = None):
    """How the rows of a program at CONSECUTIVE positions pos [B, T] (a
    row's are pos[b, 0] + t; `real` [B, T] marks the rows whose latent is
    kept) meet the pages of ptab [B, R], `ps` positions a page along the
    lanes: `bind(arena)` -> (`_block`'s write and fetch over that arena —
    any arena of the table's kind, whatever its rows' width: a layer may
    keep more than one leaf under one table —, the box whose "arena" the
    write leaves and whose "walked" says how many key positions the
    layer's attention fetched, through `fetch` or through `fetch.pages`),
    beside the number of key blocks the live contexts reach.

    `window` None: the table is in sequence order (entry i holds positions
    i * ps onward).  A window: the table is the engine's RING for a kind
    that keeps `window` positions (logical page lp in entry lp % R:
    `cohere2_moe.cache_kinds`): every block is fetched, an entry's
    positions are its base's (negative where it holds nothing a query at
    or before the row's last position may see), and `fetch.pages` hands
    the decode kernel a fourth item — which positions of the walk the
    slot's query sees.

    A layer's rows are written a whole page at a time: the pages the rows
    fall in are read, the rows laid over them, the pages written back.  A
    scatter of single rows — a sixteenth of a tile each — makes the chip's
    compiler re-lay the whole arena around it, in and out, every program
    (3.2 GB a step); whole pages are whole tiles."""
    B, T = pos.shape
    npb = max(1, cfg.kv_block // ps)
    R = ptab.shape[1]
    width = -(-R // npb) * npb
    tabp = jnp.pad(ptab, ((0, 0), (0, width - R)))
    # the pages a row of the batch writes in, and which of their positions
    n_pg = 1 if T == 1 else -(-T // ps) + 1
    first = pos[:, 0]
    entry = first[:, None] // ps + jnp.arange(n_pg, dtype=jnp.int32)
    if window is not None:
        entry = entry % R
    pages = jnp.where(entry < R, jnp.take_along_axis(
        tabp, jnp.minimum(entry, width - 1), axis=1), 0)       # [B, n_pg]
    t_of = (jnp.arange(n_pg * ps, dtype=jnp.int32)[None]
            - (first % ps)[:, None])                           # [B, n_pg*ps]
    row_of = jnp.clip(t_of, 0, T - 1)
    lay = ((t_of >= 0) & (t_of < T)
           & jnp.take_along_axis(real, row_of, axis=1))[..., None]
    last = jnp.max(jnp.where(real, pos, 0))
    if window is None:
        n_blocks = jnp.minimum(last // (npb * ps) + 1, width // npb)
        block_pos = jnp.arange(npb * ps, dtype=jnp.int32)
        bases = None
    else:
        n_blocks = width // npb
        e = jnp.arange(width, dtype=jnp.int32)[None]
        hi = (pos[:, -1] // ps)[:, None]
        lp = hi - (hi - e) % R
        bases = jnp.where((e < R) & (lp >= 0), lp * ps, -1)    # [B, width]
    # the keys a slot's one row sees (0: an empty slot); the key positions
    # the block loop fetches (formed here: `fetch` is traced inside it)
    ctx = jnp.where(real[:, 0], first + 1, 0) if T == 1 else None
    by_block = B * npb * ps * n_blocks

    def bind(arena):
        box = {}
        d = arena.shape[1]

        def write(rows):
            old = jnp.swapaxes(arena[pages], 2, 3).reshape(B, n_pg * ps, d)
            new = jnp.where(lay, jnp.take_along_axis(
                rows, row_of[..., None], axis=1), old)
            box["arena"] = arena.at[pages.reshape(B * n_pg)].set(
                jnp.swapaxes(new.reshape(B * n_pg, ps, d), 1, 2))

        def fetch(i):
            box["walked"] = by_block
            t = jax.lax.dynamic_slice_in_dim(tabp, i * npb, npb, 1)
            rows = jnp.swapaxes(box["arena"][t], 2, 3).reshape(
                B, npb * ps, d)
            if bases is None:
                return rows, jnp.broadcast_to(i * npb * ps + block_pos,
                                              (B, npb * ps))
            b = jax.lax.dynamic_slice_in_dim(bases, i * npb, npb, 1)
            kpos = jnp.where(b[:, :, None] >= 0, b[:, :, None]
                             + jnp.arange(ps, dtype=jnp.int32), -1)
            return rows, kpos.reshape(B, npb * ps)

        def in_place():             # a step's keys, where they lie
            box["walked"] = latent_walked_keys(ctx, ps)
            return box["arena"], ptab, ctx

        def ring_in_place():        # every entry of a live slot's ring
            live = real[:, 0]
            box["walked"] = jnp.sum(live) * R * ps
            at = (bases[:, :R, None]
                  + jnp.arange(ps, dtype=jnp.int32)).reshape(B, R * ps)
            d_pos = first[:, None] - at
            see = ((jnp.repeat(bases[:, :R], ps, axis=1) >= 0)
                   & (d_pos >= 0) & (d_pos < window))
            return (box["arena"], ptab, jnp.where(live, R * ps, 0), see)

        if T == 1:
            fetch.pages = in_place if window is None else ring_in_place
        return write, fetch, box

    return bind, n_blocks


def _paged_pass(params, cache, toks, ptab, pos, real, cfg, absorbed=None):
    """Tokens toks [B, T] at CONSECUTIVE positions pos [B, T] (a row's are
    pos[b, 0] + t) through the layers against the paged latents; `real`
    [B, T] marks the rows whose latent is kept (the others leave their
    page as it was and do not route); ptab [B, R] in sequence order.
    Attention is absorbed where T <= ABSORB_ROWS; `absorbed` (a test, the
    timing study) names the form instead.  Returns (x [B, T, D], cache,
    stats)."""
    T = toks.shape[1]
    if absorbed is None:
        absorbed = T <= ABSORB_ROWS
    ps = cache[0].shape[2]
    bind, n_blocks = page_io(ptab, pos, real, ps, cfg)
    x = slot_embed(params, toks, pos, cfg)
    new_cache, held, walked = [], [], 0
    for layer, arena in zip(params["layers"], cache):
        write, fetch, box = bind(arena)
        x, ld = _block(x, layer, pos, write, fetch, n_blocks, absorbed, cfg,
                       live=real)
        new_cache.append(box["arena"])
        walked += box["walked"]
        if ld is not None:
            held.append(ld)
    return x, new_cache, _stats(held, pos, real, walked, cfg)


def paged_decode_step(params, cache, tokens, ptabs, pos, cfg, absorbed=None):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B]; ptabs
    [B, R] (or {KIND: that}).  A slot at position 0 is empty (a prompt has
    at least one token): it writes to the null page and routes nowhere.
    Returns (logits [B, V] f32, cache, stats)."""
    live = (pos > 0)[:, None]
    x, cache, stats = _paged_pass(params, cache, tokens[:, None],
                                  _only(ptabs), pos[:, None], live, cfg,
                                  absorbed)
    return head_logits(params, x[:, 0], cfg), cache, stats


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx, cfg,
                  absorbed=None):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its table row ptab_rows [R] (or
    {KIND: that}); it sees the latents earlier chunks left in its pages.
    Returns (logits [V] f32 at row last_idx, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    x, cache, stats = _paged_pass(
        params, cache, toks[None], _only(ptab_rows)[None], (start + t)[None],
        (t <= last_idx)[None], cfg, absorbed)
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    return head_logits(params, x, cfg), cache, stats


def copy_page(cache, dst, src):
    """Copy-on-write: latent page `src` into `dst` in every layer."""
    return [arena.at[dst].set(arena[src]) for arena in cache]


# the leaves the programs cast to cfg.dtype where they use them; the norms,
# the router and its bias are used as they are kept
_SERVE_CAST = frozenset({"embed", "unembed", "wq_a", "wq_b", "wkv_a", "w_uk",
                         "w_uv", "wo", "w_gate", "w_up", "w_down", "wgu", "wd",
                         "shared_gate", "shared_up", "shared_down"})


@functools.partial(jax.jit, static_argnames="cfg")
def _relaid(wkv_b, cfg):
    return kv_up({"wkv_b": wkv_b}, cfg)


def with_kv_up(params, cfg):
    """`params` with every layer's `Wkvb` re-laid ONCE into the two
    matrices attention multiplies by (`w_uk` [H, dn, rkv], `w_uv`
    [H, rkv, dv]: a program then derives nothing) and left out itself, so
    the view counts what the tree counts.  A layer without `Wkvb` (a view's,
    or one that is no latent attention) comes back as it is."""
    layers = []
    for layer in params["layers"]:
        if "wkv_b" in layer:
            w_uk, w_uv = _relaid(layer["wkv_b"], cfg=cfg)
            layer = {**{k: v for k, v in layer.items() if k != "wkv_b"},
                     "w_uk": w_uk, "w_uv": w_uv}
        layers.append(layer)
    return dict(params, layers=layers)


def serve_view(params, cfg: DeepSeekV3Config):
    """gpt.cast_leaves over this model's leaves, `Wkvb` re-laid
    (`with_kv_up`).  A view comes back as it is."""
    return cast_leaves(with_kv_up(params, cfg), cfg, _SERVE_CAST)
