"""dots3-note-class decoder (`model_type: dots3_note`, the language model):
latent attention of TWO geometries by layer kind — full layers whose every
query keeps the `index_topk` keys a learned indexer scores highest, sliding
layers that are latent attention of another rank and head count under a
window — a sigmoid gate a head on both, a dense SwiGLU layer and then
sigmoid-routed experts with a shared one: the serving engine's seventh
model, behind the same module interface as the other six.

A full layer (H heads of dn | dr, values dv, ranks rq and rkv; RMSNorm
statistics in f32; n the normed input):

    c_q   = RMSNorm(n Wqa) * sqrt(D / rq);  q = c_q Wqb -> H x (q_nope | q_pe)
    c_kv | k_pe = n Wkva;  c_kv = RMSNorm(c_kv) * sqrt(D / rkv)  <- cached row
    k_nope_h | v_h = c_kv Wkvb;  RoPE (interleaved pairs) on q_pe and k_pe
    the indexer (Hi heads of di, rope on the first dr of di):
      qi = c_q Wiq -> Hi x di;   ki = LayerNorm(n Wik) -> di     <- cached row
      w  = (n Wiw) * Hi^-1/2 * di^-1/2
      I[t,s] = sum_j w[t,j] relu(qi[t,j] . ki[s]),  s <= t        (float32)
      S_t = the `index_topk` keys s <= t of largest I[t,s]; all while
            t < index_topk
    s_h[t,s] = (q_nope_h[t].k_nope_h[s] + q_pe_h[t].k_pe[s]) (dn + dr)^-1/2
               for s in S_t
    o_h   = softmax_s(s_h) v_h * sigmoid(n Wg)_h;   x = x + concat_h(o_h) Wo

A sliding layer is the same with its own H, dn, dr, dv, ranks and theta,
no indexer, and the keys t - window < s <= t.

The latent row, both forms of attention over it, how a program's rows meet
the pages and the re-laid `Wkvb` ARE models/deepseek_v3.py's (`latent_rows`,
`latent_attend`, `page_io`, `latent_arenas`, `with_kv_up`), as the
feed-forward and the head are (`layer_ffn`: its grouped router with ONE
group is this router): each kind hands them its own view of the config
(`Dots3Config.view`).  What is this module's own: the query's latent
(shared with the indexer), the two rescales, the indexer and the selection,
the gate.

The selection is a THRESHOLD, not a gather: a row's index scores against
every key it may see (`ops/attention.indexer_scores`, block by block over
the indexer's own pages), the row's min(index_topk, visible)-th largest of
them (`ops/select.keep_top`: no sort), and the attention that stands —
the absorbed walk of a step, the expanded block loop of a chunk — with the
mask `I[t,s] >= threshold_t` beside the causal one.  Every latent page a
context reaches is still read; `dsa_keys_walked` over `dsa_keys_selected`
says what a gathering form would save.

What a sequence keeps, and `cache_kinds` says so with TWO paged kinds:

  * `full`: per full layer one latent row (rkv + dr = 576 values) AND one
    indexer row (di = 128 values) a position, two arenas under the kind's
    ONE page table, [pages, 576, ps] and [pages, 128, ps];
  * `sliding`: per sliding layer one latent row of the sliding geometry
    (1,088 values), [pages, 1088, ps], in the engine's ring of window +
    chunk positions.

The cache is {"full": [{"latent", "index"} a full layer], "sliding":
[arena a sliding layer]}; page 0 of either pool is the null page.

A chip may hold a *share* of the model (models/deepseek_v3.py): experts
`experts_first..+experts_held-1` of `n_experts`, `vocab_size` rows of the
vocabulary, the layers `layer_types` names.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import indexer_scores
from ray_tpu.ops.layers import apply_rope_interleaved, layer_norm, rms_norm
from ray_tpu.ops.moe import held_experts_leaf, held_load_stats
from ray_tpu.ops.select import keep_top

from . import deepseek_v3 as _dm
from .gpt import cast_leaves, slot_embed
# benchmarks/drivers/replica_dots3.py:23 imports `_draw` (ROADMAP D17)
from .served import draw as _draw

__all__ = ["Dots3Config", "init", "apply", "cache_kinds", "init_paged_cache",
           "paged_decode_step", "paged_prefill", "serve_view",
           "STEP_STATS"]

# what a serve program returns beside logits and cache, in this order (f32
# scalars, summed over the layers they speak of): the experts' four, as
# deepseek_v3; then of the FULL layers, as (query row, key) pairs of one
# head: those a row may see (causal), those its selection keeps, those whose
# latent row its attention fetched and scored — and `dsa_ctx`, the keys
# visible to the program (a step: its live rows' contexts, a chunk: its one
# context); then of the SLIDING layers the pairs under the window and the
# keys the program sees under it
STEP_STATS = ("moe_pairs", "moe_load_max", "moe_touched", "moe_reads",
              "dsa_keys_visible", "dsa_keys_selected", "dsa_keys_walked",
              "dsa_ctx", "swa_pairs", "swa_keys")

FULL, SLIDING = "full", "sliding"
ABSORB_ROWS = _dm.ABSORB_ROWS
INDEX_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LatentView:
    """What deepseek_v3's latent pieces read off a config, for ONE kind of
    layer."""
    n_heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    rope_theta: float
    rms_eps: float
    dtype: Any
    kv_block: int

    @property
    def softmax_scale(self) -> float:
        return (self.d_nope + self.d_rope) ** -0.5

    def rope_freqs(self):
        return None


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064           # rows of the vocabulary held here
    # the layers held, each "full" or "sliding"; the first `n_dense` have a
    # dense SwiGLU (published: 46 layers, 0 and 1 full, then 3 sliding : 1
    # full)
    layer_types: tuple = (FULL, FULL) + (SLIDING, SLIDING, SLIDING, FULL) * 11
    n_dense: int = 1
    d_model: int = 5120
    # full layers
    n_heads: int = 128
    q_rank: int = 1024
    kv_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    rope_theta: float = 8e7
    # their indexer
    index_heads: int = 64
    index_dim: int = 128               # d_rope of them turned
    index_topk: int = 2048
    # sliding layers
    swa_heads: int = 64
    swa_q_rank: int = 1024
    swa_kv_rank: int = 1024
    swa_d_nope: int = 192
    swa_d_rope: int = 64
    swa_d_v: int = 128
    swa_rope_theta: float = 5e4
    window: int = 513                  # counts the query's own position
    lora_rescale: bool = True          # normed latents x sqrt(D / rank)
    d_ff: int = 13824                  # a dense layer's SwiGLU
    d_expert: int = 1536               # one expert's (and the shared one's)
    n_experts: int = 256               # the router's width
    experts_first: int = 0             # experts held: first..first+held-1
    experts_held: int = 256
    top_k: int = 8
    routed_scale: float = 1.0
    n_shared: int = 1
    rms_eps: float = 1e-5
    max_seq: int = 524288
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    kv_block: int = 512                # keys scored at once on the serve path
    moe_tile: int = 512
    # what gpt's shared helpers, deepseek_v3's feed-forward and the engine
    # read off a config
    pos: str = "rope"
    tie_embeddings: bool = False
    n_group: int = 1                   # no groups: one, kept
    topk_group: int = 1

    def __post_init__(self):
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {self.layer_types}")
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError("n_dense must lie in 0..n_layers")
        if self.experts_first + self.experts_held > self.n_experts:
            raise ValueError("held experts run past n_experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def layers_of(self, kind: str) -> List[int]:
        return [l for l, k in enumerate(self.layer_types) if k == kind]

    def view(self, kind: str) -> LatentView:
        sizes = ((self.n_heads, self.q_rank, self.kv_rank, self.d_nope,
                  self.d_rope, self.d_v, self.rope_theta) if kind == FULL
                 else (self.swa_heads, self.swa_q_rank, self.swa_kv_rank,
                       self.swa_d_nope, self.swa_d_rope, self.swa_d_v,
                       self.swa_rope_theta))
        return LatentView(*sizes, self.rms_eps, self.dtype, self.kv_block)

    @classmethod
    def nano(cls, **kw):
        """The plan at toy size, for the CPU tests: a dense full layer,
        then full, sliding, sliding, full with 16 experts of which 4 are
        held; the selection keeps 12 keys, the window 9."""
        base = dict(vocab_size=256,
                    layer_types=(FULL, FULL, SLIDING, SLIDING, FULL),
                    n_dense=1, d_model=64, n_heads=4, q_rank=24, kv_rank=16,
                    d_nope=8, d_rope=4, d_v=8, index_heads=16, index_dim=8,
                    index_topk=12, swa_heads=2, swa_q_rank=24, swa_kv_rank=32,
                    swa_d_nope=12, swa_d_rope=4, swa_d_v=8, window=9,
                    d_ff=96, d_expert=32, n_experts=16, experts_first=4,
                    experts_held=4, top_k=4, max_seq=128, kv_block=16,
                    moe_tile=16)
        base.update(kw)
        return cls(**base)


# the draw is `served.draw`, a leaf's place its index here.  Norms are
# ones, the indexer's LayerNorm bias and the correction bias zeros (a
# benchmark's loader draws what a checkpoint would hold there).  `wg` and
# `wu` keep their places in the recipe and lie in ONE leaf of the tree,
# `wgu` (`ops.moe.held_experts_leaf`).
LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_head_gate", "wi_q",
          "wi_k", "wi_w", "w_gate", "w_up", "w_down", "router", "wg", "wu",
          "wd", "shared_gate", "shared_up", "shared_down")


def init_layer(key, cfg: Dots3Config, l: int) -> Dict[str, Any]:
    """Layer l's weights: its attention's by `cfg.layer_types[l]`, its
    feed-forward's by `l < cfg.n_dense`."""
    kind, D, pd = cfg.layer_types[l], cfg.d_model, cfg.param_dtype
    v = cfg.view(kind)
    H, rq, rkv = v.n_heads, v.q_rank, v.kv_rank
    out = 1.0 / math.sqrt(2 * cfg.n_layers)

    def w(name, shape, fan_in, scale=1.0, dtype=pd):
        return _draw(key, l, LEAVES.index(name), shape,
                     scale / math.sqrt(fan_in), dtype)

    layer = {
        "attn_norm": jnp.ones((D,), pd), "q_norm": jnp.ones((rq,), pd),
        "kv_norm": jnp.ones((rkv,), pd), "mlp_norm": jnp.ones((D,), pd),
        "wq_a": w("wq_a", (D, rq), D),
        "wq_b": w("wq_b", (rq, H, v.d_nope + v.d_rope), rq),
        "wkv_a": w("wkv_a", (D, rkv + v.d_rope), D),
        "wkv_b": w("wkv_b", (rkv, H, v.d_nope + v.d_v), rkv),
        "wo": w("wo", (H, v.d_v, D), H * v.d_v, out),
        "w_head_gate": w("w_head_gate", (D, H), D),
    }
    if kind == FULL:
        Hi, di = cfg.index_heads, cfg.index_dim
        layer.update(wi_q=w("wi_q", (rq, Hi, di), rq),
                     wi_k=w("wi_k", (D, di), D), wi_w=w("wi_w", (D, Hi), D),
                     wi_knorm=jnp.ones((di,), pd),
                     wi_kbias=jnp.zeros((di,), pd))
    if l < cfg.n_dense:
        F = cfg.d_ff
        layer.update(w_gate=w("w_gate", (D, F), D), w_up=w("w_up", (D, F), D),
                     w_down=w("w_down", (F, D), F, out))
        return layer
    F, C, S = cfg.d_expert, cfg.experts_held, cfg.n_shared
    layer.update(
        router=w("router", (D, cfg.n_experts), D, dtype=jnp.float32),
        router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
        wgu=held_experts_leaf(w("wg", (C, D, F), D), w("wu", (C, D, F), D)),
        wd=w("wd", (C, F, D), F, out),
        shared_gate=w("shared_gate", (D, S * F), D),
        shared_up=w("shared_up", (D, S * F), D),
        shared_down=w("shared_down", (S * F, D), F, out))
    return layer


def init(key, cfg: Dots3Config) -> Dict[str, Any]:
    """The param tree: `layers` is a list (a layer's leaves are its
    kind's)."""
    V, D, pd = cfg.vocab_size, cfg.d_model, cfg.param_dtype
    return {
        "embed": _draw(key, -1, 0, (V, D), 0.02, pd),
        "unembed": _draw(key, -1, 1, (D, V), 1.0 / math.sqrt(D), pd),
        "final_norm": jnp.ones((D,), pd),
        "layers": [init_layer(key, cfg, l) for l in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# the block


def _rescaled(norm, rank: int, cfg: Dots3Config):
    """A latent's norm weight with the rescale folded in, float32 (the norm
    multiplies in float32, so nothing is rounded twice)."""
    w = norm.astype(jnp.float32)
    return w * math.sqrt(cfg.d_model / rank) if cfg.lora_rescale else w


def _queries(h, layer, pos, v: LatentView, cfg: Dots3Config):
    """h [B, T, D] normed -> (the query's latent c_q [B, T, rq], q_nope
    [B, H, T, dn], q_pe [B, H, T, dr] turned)."""
    dt = cfg.dtype
    with jax.named_scope("mla_q"):
        c_q = rms_norm(jnp.einsum("btd,dr->btr", h, layer["wq_a"].astype(dt)),
                       _rescaled(layer["q_norm"], v.q_rank, cfg), cfg.rms_eps)
        q = jnp.einsum("btr,rhk->bhtk", c_q, layer["wq_b"].astype(dt))
        q_pe = apply_rope_interleaved(q[..., v.d_nope:], pos, v.rope_theta)
        return c_q, q[..., :v.d_nope], q_pe


def _turn_first(x, pos, cfg: Dots3Config):
    """The indexer's rope: the first d_rope of x's last axis turned (x
    [B, heads, T, di])."""
    dr = cfg.d_rope
    return jnp.concatenate(
        [apply_rope_interleaved(x[..., :dr], pos, cfg.rope_theta),
         x[..., dr:]], axis=-1)


def _index_rows(h, layer, pos, cfg: Dots3Config):
    """h [B, T, D] normed -> the indexer's key rows [B, T, di] in
    cfg.dtype: the one key all its heads score, normed and turned."""
    dt = cfg.dtype
    k = layer_norm(jnp.einsum("btd,dk->btk", h, layer["wi_k"].astype(dt)),
                   layer["wi_knorm"], layer["wi_kbias"], INDEX_NORM_EPS)
    return _turn_first(k[:, None], pos, cfg)[:, 0].astype(dt)


def _index_queries(h, c_q, layer, pos, cfg: Dots3Config):
    """(qi [B, T, Hi, di] in cfg.dtype, w [B, T, Hi] float32)."""
    dt = cfg.dtype
    qi = jnp.einsum("btr,rhk->bhtk", c_q, layer["wi_q"].astype(dt))
    qi = jnp.swapaxes(_turn_first(qi, pos, cfg), 1, 2)
    w = jnp.einsum("btd,dh->bth", h, layer["wi_w"].astype(dt),
                   preferred_element_type=jnp.float32)
    return qi, w * (cfg.index_heads * cfg.index_dim) ** -0.5


def _select(scores, pos, reach, block: int, k: int):
    """`ops/select.keep_top` of index scores [N, W] for queries at
    positions pos [N] (a query may see the keys at or before its own), over
    the table's first positions only: `reach` (traced) positions were
    scored, the rest stand at -inf, and the threshold's thirty-two counts
    run over W / 8, W / 4, W / 2 or W positions (whole blocks), whichever
    holds `reach` first — a chunk at a context of 4,000 does not count
    over the 33,792 positions its table could hold."""
    N, W = scores.shape
    widths = sorted({min(W, -(-(W >> s) // block) * block)
                     for s in (3, 2, 1, 0)})

    def upto(w):
        def run():
            visible = jnp.arange(w, dtype=jnp.int32) <= pos[:, None]
            keep, kept = keep_top(scores[:, :w], visible, k)
            return jnp.pad(keep, ((0, 0), (0, W - w))), kept
        return run

    which = sum((jnp.asarray(reach) > w).astype(jnp.int32)
                for w in widths[:-1])
    return jax.lax.switch(which, [upto(w) for w in widths])


def _selecting(fetch, keep):
    """`fetch` with the selection keep [B, T, W] (W: the table's positions)
    as its blocks' third item and its pages' fourth."""
    def selected(i):
        rows, kpos = fetch(i)
        S = rows.shape[1]
        return rows, kpos, jax.lax.dynamic_slice_in_dim(keep, i * S, S, 2)

    if hasattr(fetch, "pages"):
        selected.pages = lambda: (*fetch.pages(), keep[:, 0])
    return selected


def _attention(x, h, layer, kind: str, pos, real, io, n_blocks,
               absorbed: bool, cfg: Dots3Config):
    """The attention of one layer on x [B, T, D] (h its normed input) at
    positions pos [B, T].  `io`: {"latent": (write, fetch)} and, for a
    full layer, {"index": (write, fetch)} over the indexer's rows with the
    positions of a fetched `block` and of the whole table (`width`).
    Returns (x, [visible, selected] pairs of the layer's selection or
    None)."""
    dt = cfg.dtype
    v = cfg.view(kind)
    c_q, q_nope, q_pe = _queries(h, layer, pos, v, cfg)
    write, fetch = io["latent"]
    write(_dm.latent_rows(
        h, {**layer, "kv_norm": _rescaled(layer["kv_norm"], v.kv_rank, cfg)},
        pos, v))
    counts = None
    if kind == FULL:
        iwrite, ifetch = io["index"]
        with jax.named_scope("dsa_index_step" if absorbed
                             else "dsa_index_chunk"):
            iwrite(_index_rows(h, layer, pos, cfg))
            qi, w = _index_queries(h, c_q, layer, pos, cfg)
            scores = indexer_scores(qi, w, ifetch, n_blocks, io["block"],
                                    io["width"])
        with jax.named_scope("dsa_select"):
            B, T, W = scores.shape
            keep, kept = _select(scores.reshape(B * T, W),
                                 pos.reshape(B * T), n_blocks * io["block"],
                                 io["block"], cfg.index_topk)
            fetch = _selecting(fetch, keep.reshape(B, T, W))
            live = real.reshape(B * T)
            counts = [jnp.sum(jnp.where(real, pos + 1, 0)),
                      jnp.sum(jnp.where(live, kept, 0))]
    o = _dm.latent_attend(
        q_nope, q_pe, pos, fetch, n_blocks, layer, absorbed, v,
        window=None if kind == FULL else cfg.window,
        scope="mla_attend" if kind == FULL else "swa_latent_attend")
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bht", h, layer["w_head_gate"].astype(dt),
            preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
    with jax.named_scope("mla_out"):
        return x + jnp.einsum("bhtv,hvd->btd", o,
                              layer["wo"].astype(dt)).astype(x.dtype), counts


def _block(x, layer, kind: str, pos, real, io, n_blocks, absorbed: bool,
           cfg: Dots3Config):
    """One sequential pre-norm block on x [B, T, D]; `real` [B, T] marks
    the rows that are kept and routed.  Returns (x, the selection's
    counts or None, the expert layer's (loads, reads) or None)."""
    B, T, D = x.shape
    dt = cfg.dtype
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(dt)
    x, counts = _attention(x, h, layer, kind, pos, real, io, n_blocks,
                           absorbed, cfg)
    h2 = rms_norm(x, layer["mlp_norm"], cfg.rms_eps).astype(dt)
    ffn, held = _dm.layer_ffn(h2.reshape(B * T, D), layer, cfg,
                              real.reshape(B * T))
    return x + ffn.reshape(B, T, D).astype(x.dtype), counts, held


def apply(params, tokens, cfg: Dots3Config):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32, attention in its published (expanded) form; the keys are the
    sequence's own rows, streamed `kv_block` at a time."""
    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.pad(pos, ((0, 0), (0, nb * kb - S)), constant_values=-1)
    real = jnp.ones((B, S), bool)

    def kept_rows():                 # (write, fetch) over the rows handed
        box = {}

        def write(rows):
            box["rows"] = jnp.pad(rows, ((0, 0), (0, nb * kb - S), (0, 0)))

        def fetch(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * kb, kb, 1)
            return sl(box["rows"]), sl(kpos)

        return write, fetch

    x = slot_embed(params, tokens, pos, cfg)
    for layer, kind in zip(params["layers"], cfg.layer_types):
        io = {"latent": kept_rows(), "index": kept_rows(), "block": kb,
              "width": nb * kb}
        x, _, _ = _block(x, layer, kind, pos, real, io, nb, False, cfg)
    return _dm.head_logits(params, x, cfg)


# ---------------------------------------------------------------------------
# paged serving: two pools of latent pages, one table a sequence and kind


def cache_kinds(cfg: Dots3Config) -> Dict[str, Optional[int]]:
    """name -> window of the pools the engine keeps (see gpt.cache_kinds):
    the full layers keep every position (a latent row and an indexer row
    under one table), the sliding layers `window` positions in a ring."""
    return {k: None if k == FULL else cfg.window for k in (FULL, SLIDING)
            if k in cfg.layer_types}


def init_paged_cache(cfg: Dots3Config, num_pages: Dict[str, int],
                     page_size: int):
    """{"full": [{"latent": [pages, rkv + dr, ps], "index": [pages, di,
    ps]} a full layer], "sliding": [[pages, swa rkv + dr, ps] a sliding
    layer]} in cfg.dtype, as `deepseek_v3.latent_arenas` lays a latent (a
    position a column of its page): a full layer keeps TWO leaves under the
    kind's one table.  `num_pages` counts pages by kind; page 0 of either
    pool is the null page."""
    cache = {}
    if FULL in num_pages:
        n, pages = len(cfg.layers_of(FULL)), int(num_pages[FULL])
        cache[FULL] = [
            {"latent": a, "index": jnp.zeros(
                (pages, cfg.index_dim, page_size), cfg.dtype)}
            for a in _dm.latent_arenas(cfg.view(FULL), pages, page_size, n)]
    if SLIDING in num_pages:
        cache[SLIDING] = _dm.latent_arenas(
            cfg.view(SLIDING), int(num_pages[SLIDING]), page_size,
            len(cfg.layers_of(SLIDING)))
    return cache


def _paged_pass(params, cache, toks, ptabs, pos, real, cfg: Dots3Config,
                absorbed=None):
    """Tokens toks [B, T] at CONSECUTIVE positions pos [B, T] through the
    layers against the paged latents; `real` [B, T] marks the rows that are
    kept and routed; ptabs[kind] [B, R_kind], the full kind's in sequence
    order, the sliding kind's a ring.  Attention is absorbed where T <=
    ABSORB_ROWS; `absorbed` (a test) names the form instead.  Returns
    (x [B, T, D], cache, stats)."""
    T = toks.shape[1]
    if absorbed is None:
        absorbed = T <= ABSORB_ROWS
    ps = jax.tree_util.tree_leaves(cache)[0].shape[2]
    binds = {k: _dm.page_io(ptabs[k], pos, real, ps, cfg.view(k),
                            window=None if k == FULL else cfg.window)
             for k in ptabs}
    npb = max(1, cfg.kv_block // ps)
    new = {k: list(v) for k, v in cache.items()}
    at = {FULL: 0, SLIDING: 0}
    x = slot_embed(params, toks, pos, cfg)
    held, dsa, walked = [], [], 0
    for layer, kind in zip(params["layers"], cfg.layer_types):
        bind, n_blocks = binds[kind]
        arenas = new[kind][at[kind]]
        if kind == FULL:
            write, fetch, box = bind(arenas["latent"])
            iwrite, ifetch, ibox = bind(arenas["index"])
            width = -(-ptabs[FULL].shape[1] // npb) * npb * ps
            io = {"latent": (write, fetch), "index": (iwrite, ifetch),
                  "block": npb * ps, "width": width}
        else:
            write, fetch, box = bind(arenas)
            io = {"latent": (write, fetch)}
        x, counts, ld = _block(x, layer, kind, pos, real, io, n_blocks,
                               absorbed, cfg)
        if kind == FULL:
            new[kind][at[kind]] = {"latent": box["arena"],
                                   "index": ibox["arena"]}
            dsa.append(counts)
            # a step's walk is a slot's own pages, one row each; a chunk's
            # rows each meet every block fetched
            walked += (box["walked"] if T == 1 else
                       jnp.sum(real) * n_blocks * npb * ps)
        else:
            new[kind][at[kind]] = box["arena"]
        at[kind] += 1
        if ld is not None:
            held.append(ld)
    return x, new, _stats(held, dsa, walked, pos, real, cfg)


def _stats(held, dsa, walked, pos, real, cfg: Dots3Config):
    """The STEP_STATS vector of one program."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    seen = jnp.where(real, pos + 1, 0)                          # [B, T]
    n_full, n_swa = len(cfg.layers_of(FULL)), len(cfg.layers_of(SLIDING))
    zero = jnp.zeros((), jnp.float32)
    return jnp.stack(held_load_stats(held) + [
        sum((f32(c[0]) for c in dsa), zero),
        sum((f32(c[1]) for c in dsa), zero), f32(walked),
        f32(seen.max(axis=1).sum()) * n_full,
        f32(jnp.minimum(seen, cfg.window).sum()) * n_swa,
        f32(jnp.minimum(seen.max(axis=1), cfg.window + pos.shape[1] - 1
                        ).sum()) * n_swa])


def paged_decode_step(params, cache, tokens, ptabs, pos, cfg: Dots3Config,
                      absorbed=None):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[kind] [B, R_kind].  A slot at position 0 is empty (a prompt has
    at least one token): it writes to the null pages and routes nowhere.
    Returns (logits [B, V] f32, cache, stats)."""
    live = (pos > 0)[:, None]
    x, cache, stats = _paged_pass(params, cache, tokens[:, None], ptabs,
                                  pos[:, None], live, cfg, absorbed)
    return _dm.head_logits(params, x[:, 0], cfg), cache, stats


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx,
                  cfg: Dots3Config, absorbed=None):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its table rows ptab_rows[kind] [R];
    it sees the rows earlier chunks left in its pages.  Returns (logits
    [V] f32 at row last_idx, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    x, cache, stats = _paged_pass(
        params, cache, toks[None], {k: r[None] for k, r in ptab_rows.items()},
        (start + t)[None], (t <= last_idx)[None], cfg, absorbed)
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    return _dm.head_logits(params, x, cfg), cache, stats


# the leaves the programs cast to cfg.dtype where they use them; the norms,
# the router and its bias are used as they are kept
_SERVE_CAST = frozenset({
    "embed", "unembed", "wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo",
    "w_head_gate", "wi_q", "wi_k", "wi_w", "w_gate", "w_up", "w_down", "wgu",
    "wd", "shared_gate", "shared_up", "shared_down"})


def serve_view(params, cfg: Dots3Config):
    """gpt.cast_leaves over this model's leaves, every layer's `Wkvb`
    re-laid once by its own kind's sizes into `w_uk` / `w_uv` and left out
    itself (`deepseek_v3.with_kv_up`, a layer at a time).  A view comes
    back as it is."""
    layers = [_dm.with_kv_up({"layers": [layer]}, cfg.view(kind))["layers"][0]
              for layer, kind in zip(params["layers"], cfg.layer_types)]
    return cast_leaves(dict(params, layers=layers), cfg, _SERVE_CAST)
