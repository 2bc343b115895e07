"""Falcon-H1-class hybrid decoder (`model_type: falcon_h1`): every block
runs attention heads AND Mamba-2 (SSD, arXiv 2405.21060) heads side by
side on ONE normed input — the serving engine's eighth model, behind the
same module interface as the seven others.

The block, with n = RMSNorm(h) and the configuration's µP multipliers m_*:

    h' = h + m_ssm_out SSM(m_ssm_in n) + m_attn_out Attn(m_attn_in n)
    h'' = h' + MLP(RMSNorm(h'))
    MLP(x) = m_mlp[1] W_down(W_up x * SiLU(m_mlp[0] W_gate x))

    Attn: q, k, v = u W_q, u W_k, u W_v (no bias; H query heads on Hkv key
      heads, H / Hkv — five at the published sizes — to a key head);
      k <- m_key k; rotate-half RoPE on every dim of q and k; causal
      softmax at d_head^-1/2; W_o.
    SSM: [z | x B C | dt] = (u W_in) * mup, mup the five `ssm_multipliers`
      spread over the segments z, x (d_ssm each), B, C (G N each), dt (Hs);
      x B C <- SiLU(conv4(x B C) + b)                    ops/shortconv.py
      dt = softplus(dt + dt_bias);  a = -exp(A_log), a head
      S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
                                                          ops/ssd.py
      y <- RMSNorm_grouped(y * SiLU(z)) * w  (a group's d_ssm / G channels
      a norm: `mamba_norm_before_gate` false);  W_out.
    logits = m_lm_head (RMSNorm(h_L) W_head);  embedding rows x m_emb.

Every multiplier is a product by a constant, and `serve_view` folds each
into the matrix beside it ONCE (m_emb into the table, m_attn_in into W_q,
W_k, W_v and m_key into W_k — laid side by side as one `w_qkv` —,
m_ssm_in * mup into W_in's columns, m_mlp[0] into the gate's half, the
out multipliers into W_o, W_out, W_down, m_lm_head into the head), so the
serve programs multiply by none.  The tree `init` gives is the unfolded
one (`wq`, `wk`, `wv` apart), and every function here takes either: a
layer that holds `w_qkv` is a folded one.  `apply` on the unfolded tree is
the equations as written.

What a sequence keeps, in EVERY layer: its keys and values, pages
[pages, page_size, Hkv d_head] a side (the full kind), AND one entry of a
`"state"` kind — the layer's S ([Hs, P, N] float32: 4 MB at the published
sizes) and conv tail ([3, d_ssm + 2 G N] float32, kept [3, 40, 128]: whole
tiles — three rows of an 8-row tile were re-laid by ten arena-wide copies
a step).  Below ~2,000 positions the entry is the larger part of a slot's
cache.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import streamed_attention
from ray_tpu.ops.layers import apply_rope_halves, rms_norm
from ray_tpu.ops.shortconv import conv_chunk, conv_step
from ray_tpu.ops.ssd import ssd_chunk, ssd_step

from . import served
from .gpt import cast_leaves
from .served import attend_pages, carried_at, draw, kind_io, states_moved

__all__ = ["FalconH1Config", "init", "init_layer", "init_top", "table_rows",
           "fold_layer", "fold_table", "fold_top", "apply", "cache_kinds",
           "init_paged_cache", "paged_decode_step", "paged_prefill",
           "serve_view", "state_leaves", "STEP_STATS"]

# what a serve program returns beside logits and cache, in this order (f32
# scalars): the states ONE layer's update moved (a step's live slots where
# the kernel runs, every slot on the gather / scatter path; one for a
# chunk), the bytes of state those are over all layers, and the key
# positions the live rows' queries see (a step: the sum of the live slots'
# contexts; a chunk: its last real row's)
STEP_STATS = ("ssd_live", "ssd_state_bytes", "kv_positions")

FULL, SSM = "full", "ssm"
CONV_TAPS = 4


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    n_layers: int = 72
    d_model: int = 5120
    n_heads: int = 20
    n_kv_heads: int = 4
    d_head: int = 128
    d_ff: int = 21504
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    d_state: int = 256
    n_groups: int = 2
    ssm_chunk: int = 128               # rows of a sub-chunk of the SSD chunk
    rope_theta: float = 1e11
    eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over in_proj's segments z, x, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25,
                                          0.1767766952966369, 0.5,
                                          0.3535533905932738)
    # on the gate's product, on the down projection
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # ops.ssd's `impl` (None: by backend)
    ssd_impl: Optional[str] = None
    kv_block: int = 512                # keys scored at once on the serve path
    # what the engine reads off a config
    pos: str = "rope"
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.n_groups:
            raise ValueError("query heads share key heads and SSM heads "
                             "share groups evenly")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("five ssm_multipliers (z, x, B, C, dt) and two "
                             "mlp_multipliers (gate, down)")

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_conv(self) -> int:
        """Channels behind the conv: x, B and C."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def conv_tile(self) -> Tuple[int, int]:
        """The conv's channels as whole tiles: (sublanes, lanes)."""
        lanes = 128 if self.d_conv % 128 == 0 else self.d_conv
        return (self.d_conv // lanes, lanes)

    @property
    def in_segments(self) -> Tuple[int, ...]:
        """in_proj's output, segment by segment: z, x, B, C, dt."""
        gn = self.n_groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)

    @property
    def state_bytes(self) -> int:
        """One sequence's float32 state in one layer."""
        return 4 * self.ssm_heads * self.ssm_head_dim * self.d_state

    @classmethod
    def nano(cls, **kw):
        """The block at toy size, for the CPU tests: 3 layers, 10 query
        heads on 2 key heads (five a key head, as published), 4 SSM heads
        in 2 groups; every multiplier away from 1."""
        base = dict(vocab_size=256, n_layers=3, d_model=64, n_heads=10,
                    n_kv_heads=2, d_head=16, d_ff=96, ssm_heads=4,
                    ssm_head_dim=16, d_state=32, n_groups=2, ssm_chunk=8,
                    rope_theta=1e4, max_seq=128, kv_block=16,
                    embedding_multiplier=2.0, lm_head_multiplier=0.5,
                    key_multiplier=0.7, attention_in_multiplier=1.25,
                    attention_out_multiplier=0.6, ssm_in_multiplier=0.8,
                    ssm_out_multiplier=0.5,
                    ssm_multipliers=(0.9, 1.1, 1.5, 1.3, 0.6),
                    mlp_multipliers=(0.7, 0.4))
        base.update(kw)
        return cls(**base)


# the draw is `served.draw`, a leaf's place its index here.  Norm weights
# are ones, the conv's bias zeros; A, D and the step-size bias take
# Mamba-2's own initialisation (float32): A uniform in [1, 16] a head,
# D = 1, softplus(dt_bias) log-uniform in [DT_MIN, DT_MAX] (a uniform is
# the normal draw through its own distribution function).
LEAVES = ("w_in", "conv_w", "a_log", "dt_bias", "w_out", "wq", "wk", "wv",
          "wo", "w_gate_up", "w_down")
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def inv_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


def init_layer(key, cfg: FalconH1Config, l: int) -> Dict[str, Any]:
    D, F, Hs, pd = cfg.d_model, cfg.d_ff, cfg.ssm_heads, cfg.param_dtype
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    out = 1.0 / math.sqrt(2 * cfg.n_layers)
    f32 = jnp.float32

    def w(name, shape, fan_in, scale=1.0, dtype=pd):
        return draw(key, l, LEAVES.index(name), shape,
                     scale / math.sqrt(fan_in), dtype)

    uniform = lambda name: jax.scipy.special.ndtr(
        w(name, (Hs,), 1, dtype=f32))
    dt = jnp.exp(uniform("dt_bias") * (math.log(DT_MAX) - math.log(DT_MIN))
                 + math.log(DT_MIN))
    return {
        "norm": jnp.ones((D,), pd), "mlp_norm": jnp.ones((D,), pd),
        "w_in": w("w_in", (D, sum(cfg.in_segments)), D),
        "conv_w": w("conv_w", (CONV_TAPS, cfg.d_conv), CONV_TAPS),
        "conv_b": jnp.zeros((cfg.d_conv,), pd),
        "a_log": jnp.log(A_MIN + (A_MAX - A_MIN) * uniform("a_log")),
        "dt_bias": inv_softplus(dt),
        "d_skip": jnp.ones((Hs,), f32),
        "ssm_norm": jnp.ones((cfg.d_ssm,), pd),
        "w_out": w("w_out", (cfg.d_ssm, D), cfg.d_ssm, out),
        "wq": w("wq", (D, H * dh), D), "wk": w("wk", (D, Hkv * dh), D),
        "wv": w("wv", (D, Hkv * dh), D),
        "wo": w("wo", (H * dh, D), H * dh, out),
        "w_gate_up": w("w_gate_up", (D, 2 * F), D),
        "w_down": w("w_down", (F, D), F, out),
    }


@functools.partial(jax.jit, static_argnames=("count", "n", "dtype"))
def _piece_run(key, place, std, first, count, n, dtype):
    """Pieces first..first+count-1 of a table (`served.pieces`, offset)."""
    return jax.lax.map(
        lambda i: served.piece(key, -1, place, first + i, std, n, dtype),
        jnp.arange(count)).reshape(-1)


TABLES = {"embed": 0, "lm_head": 1}     # a vocabulary table's place in the draw


def table_rows(key, cfg: FalconH1Config, name: str, i: int = 0,
               parts: int = 1):
    """Slice i of `parts` of the rows of a vocabulary table, both [V, D]
    (a slice of the vocabulary is whole rows): `embed` at std 0.02,
    `lm_head` at 1/sqrt(D) — from the pieces of the draw that hold those
    rows and no others (whole, a table is 2.67e9 B at the published
    sizes: a caller short of memory makes it a slice at a time)."""
    V, D, n = cfg.vocab_size, cfg.d_model, served.DRAW_PIECE
    std = 0.02 if name == "embed" else 1.0 / math.sqrt(D)
    rows = V // parts
    lo, hi = i * rows * D, (i + 1) * rows * D
    first = lo // n
    flat = _piece_run(key, TABLES[name], jnp.float32(std), first,
                      -(-hi // n) - first, n, jnp.dtype(cfg.param_dtype))
    return flat[lo - first * n:hi - first * n].reshape(rows, D)


def init_top(key, cfg: FalconH1Config) -> Dict[str, Any]:
    """What stands outside the layers: the two vocabulary tables and the
    last norm."""
    return {"embed": table_rows(key, cfg, "embed"),
            "lm_head": table_rows(key, cfg, "lm_head"),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype)}


def init(key, cfg: FalconH1Config) -> Dict[str, Any]:
    """The UNFOLDED param tree: `layers` a list, the head its own table."""
    return dict(init_top(key, cfg), layers=[
        init_layer(key, cfg, l) for l in range(cfg.n_layers)])


# ---------------------------------------------------------------------------
# the multipliers: written out on an unfolded tree, folded by `serve_view`


def _folded(layer) -> bool:
    return "w_qkv" in layer


def _times(x, m: float):
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def _mup(cfg: FalconH1Config):
    """[9,248]: `ssm_multipliers` over in_proj's columns."""
    return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in
                            zip(cfg.in_segments, cfg.ssm_multipliers)])


@functools.partial(jax.jit, static_argnames="dtype")
def _fold_to(w, m, dtype):
    # one program: eagerly, a table's float32 copy (5.3e9 B at the
    # published sizes) would stand in memory beside the table
    return (w.astype(jnp.float32) * m).astype(dtype)


def _fold(w, m, cfg: FalconH1Config):
    return _fold_to(w, jnp.asarray(m, jnp.float32), jnp.dtype(cfg.dtype))


def fold_layer(lp, cfg: FalconH1Config):
    """One layer of `serve_view`: every multiplier folded into the matrix
    beside it (in float32, rounded to cfg.dtype ONCE), W_q, W_k and W_v
    side by side as `w_qkv`."""
    F, ain = cfg.d_ff, cfg.attention_in_multiplier
    gate = jnp.concatenate([jnp.full((F,), cfg.mlp_multipliers[0]),
                            jnp.ones((F,))]).astype(jnp.float32)
    out = {k: v for k, v in lp.items() if k not in ("wq", "wk", "wv")}
    out.update(
        w_qkv=jnp.concatenate(
            [_fold(lp["wq"], ain, cfg),
             _fold(lp["wk"], ain * cfg.key_multiplier, cfg),
             _fold(lp["wv"], ain, cfg)], axis=1),
        w_in=_fold(lp["w_in"], cfg.ssm_in_multiplier * _mup(cfg), cfg),
        w_out=_fold(lp["w_out"], cfg.ssm_out_multiplier, cfg),
        wo=_fold(lp["wo"], cfg.attention_out_multiplier, cfg),
        w_gate_up=_fold(lp["w_gate_up"], gate, cfg),
        w_down=_fold(lp["w_down"], cfg.mlp_multipliers[1], cfg))
    return cast_leaves(out, cfg, frozenset({"conv_w", "conv_b"}))


def fold_table(w, cfg: FalconH1Config, name: str):
    """A vocabulary table of `serve_view` (or a slice of its rows): the
    embedding's or the head's multiplier folded in."""
    return _fold(w, cfg.embedding_multiplier if name == "embed"
                 else cfg.lm_head_multiplier, cfg)


def fold_top(top, cfg: FalconH1Config):
    return {**{name: fold_table(top[name], cfg, name) for name in TABLES},
            "final_norm": top["final_norm"]}


def serve_view(params, cfg: FalconH1Config):
    """The tree the serve programs are handed: `fold_top` and `fold_layer`
    over the tree; A_log, D, dt_bias stay float32 and the norms as kept.
    A view's view is that view.  Every folded matrix is a NEW array: a
    caller short of memory folds a layer at a time as it makes them and
    hands the engine the view (benchmarks/drivers/replica_falcon_h1)."""
    if _folded(params["layers"][0]):
        return params
    return dict(fold_top(params, cfg),
                layers=[fold_layer(lp, cfg) for lp in params["layers"]])


# ---------------------------------------------------------------------------
# the mixers


def _normed(x, w, cfg: FalconH1Config):
    return rms_norm(x, w, cfg.eps).astype(cfg.dtype)


def _embed(params, tokens, cfg: FalconH1Config):
    x = params["embed"][tokens].astype(cfg.dtype)
    return x if _folded(params["layers"][0]) else _times(
        x, cfg.embedding_multiplier)


def _ssd_project(n, layer, cfg: FalconH1Config):
    """n [.., D] normed -> (z [.., d_ssm], the pre-conv rows [.., d_conv],
    dt's own columns [.., Hs]) in cfg.dtype."""
    with jax.named_scope("ssd_proj"):
        w = layer["w_in"].astype(cfg.dtype)
        if _folded(layer):
            p = jnp.einsum("...d,dc->...c", n, w)
        else:
            p = jnp.einsum("...d,dc->...c",
                           _times(n, cfg.ssm_in_multiplier), w)
            p = p * _mup(cfg).astype(p.dtype)
        S = cfg.d_ssm
        return p[..., :S], p[..., S:S + cfg.d_conv], p[..., S + cfg.d_conv:]


def _conv(pre, tail, layer, cfg: FalconH1Config, step: bool):
    """The short conv over pre-conv rows [.., d_conv] against a tail
    [.., 3, *conv_tile] -> (u [.., d_conv] float32, the tail after — of a
    step — or the rows as the tail keeps them — of a chunk)."""
    tile = cfg.conv_tile
    with jax.named_scope("ssd_conv"):
        rows = pre.reshape(pre.shape[:-1] + tile)
        w = layer["conv_w"].reshape((CONV_TAPS,) + tile)
        b = layer["conv_b"].reshape(tile)
        if step:
            u, kept = conv_step(rows, tail, w, b, jax.nn.silu, "kda_conv")
        else:
            u = conv_chunk(rows, tail, w, b, jax.nn.silu, "kda_conv")
            kept = rows
        return u.reshape(pre.shape), kept


def _ssd_operands(u, dt, layer, cfg: FalconH1Config):
    """The conv's output u [.., d_conv] float32 and dt's columns -> (x
    [.., Hs, P], dt [.., Hs] after the softplus, B, C [.., G, N]),
    float32."""
    Hs, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.n_groups, cfg.d_state
    x, b, c = jnp.split(u, [cfg.d_ssm, cfg.d_ssm + G * N], axis=-1)
    lead = u.shape[:-1]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
    return (x.reshape(lead + (Hs, P)), dt, b.reshape(lead + (G, N)),
            c.reshape(lead + (G, N)))


def _ssd_out(y, z, layer, cfg: FalconH1Config):
    """The scan's read-out y [.., Hs, P] float32 (the skip in it) gated by
    z [.., d_ssm], normed a group at a time and projected: the branch's
    addition to the stream [.., D]."""
    with jax.named_scope("ssd_out"):
        G = cfg.n_groups
        lead = z.shape[:-1]
        g = y.reshape(lead + (cfg.d_ssm,)) * jax.nn.silu(
            z.astype(jnp.float32))
        g = g.reshape(lead + (G, cfg.d_ssm // G))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + cfg.eps)
        g = (g.reshape(lead + (cfg.d_ssm,))
             * layer["ssm_norm"].astype(jnp.float32)).astype(cfg.dtype)
        o = jnp.einsum("...c,cd->...d", g, layer["w_out"].astype(cfg.dtype))
        return o if _folded(layer) else _times(o, cfg.ssm_out_multiplier)


def _ssd_sequence(n, layer, real, state, tail, cfg: FalconH1Config):
    """The SSM branch over ONE sequence's normed rows n [T, D] (`real` [T]
    marks those that are not padding) from its carried state [Hs, P, N]
    and tail [3, *conv_tile] -> (the branch's addition [T, D], the state
    after, the pre-conv rows [T, *conv_tile])."""
    z, pre, dt = _ssd_project(n, layer, cfg)
    u, pre = _conv(pre, tail, layer, cfg, step=False)
    x, dt, b, c = _ssd_operands(u, dt, layer, cfg)
    y, state = ssd_chunk(x, jnp.where(real[:, None], dt, 0.0),
                         -jnp.exp(layer["a_log"]), b, c, layer["d_skip"],
                         state, impl=cfg.ssd_impl, dtype=cfg.dtype,
                         block=cfg.ssm_chunk)
    return _ssd_out(y, z, layer, cfg), state, pre


def _qkv(n, layer, pos, cfg: FalconH1Config):
    """n [B, T, D] normed at positions pos [B, T] -> (q [B, Hkv, G, T, dh]
    and k [B, Hkv, T, dh] rotated, v [B, Hkv, T, dh])."""
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    B, T, _ = n.shape
    with jax.named_scope("attn_proj"):
        if _folded(layer):
            q, k, v = jnp.split(
                jnp.einsum("btd,dk->btk", n, layer["w_qkv"].astype(cfg.dtype)),
                [H * dh, (H + Hkv) * dh], axis=-1)
        else:
            u = _times(n, cfg.attention_in_multiplier)
            q, k, v = (jnp.einsum("btd,dk->btk", u,
                                  layer[w].astype(cfg.dtype))
                       for w in ("wq", "wk", "wv"))
            k = _times(k, cfg.key_multiplier)
        heads = lambda a, h: jnp.moveaxis(a.reshape(B, T, h, dh), 1, 2)
        q = apply_rope_halves(heads(q, H), pos, cfg.rope_theta)
        k = apply_rope_halves(heads(k, Hkv), pos, cfg.rope_theta)
        return q.reshape(B, Hkv, H // Hkv, T, dh), k, heads(v, Hkv)


def _attn_out(o, layer, cfg: FalconH1Config):
    """o [B, Hkv, G, T, dh] -> the branch's addition [B, T, D]."""
    B, Hkv, G, T, dh = o.shape
    with jax.named_scope("attn_proj"):
        y = jnp.moveaxis(o.reshape(B, Hkv * G, T, dh), 1, 2).reshape(B, T, -1)
        y = jnp.einsum("btk,kd->btd", y.astype(cfg.dtype),
                       layer["wo"].astype(cfg.dtype))
        return y if _folded(layer) else _times(
            y, cfg.attention_out_multiplier)


_MLP_LEAVES = ("mlp_norm", "w_gate_up", "w_down")


@functools.partial(jax.jit, static_argnames=("cfg", "folded"))
def _swiglu(x, w, cfg: FalconH1Config, folded: bool):
    """Jitted here, so that a program that calls it a layer traces and
    lowers it once (as `ops.kda.kda_chunk`: ROADMAP S11)."""
    with jax.named_scope("mlp"):
        m = (1.0, 1.0) if folded else cfg.mlp_multipliers
        n = _normed(x, w["mlp_norm"], cfg)
        gate, up = jnp.split(jnp.einsum(
            "...d,df->...f", n, w["w_gate_up"].astype(cfg.dtype)), 2, axis=-1)
        y = jnp.einsum("...f,fd->...d", up * jax.nn.silu(_times(gate, m[0])),
                       w["w_down"].astype(cfg.dtype))
        return x + _times(y, m[1]).astype(x.dtype)


def _mlp(x, layer, cfg: FalconH1Config):
    return _swiglu(x, {k: layer[k] for k in _MLP_LEAVES}, cfg, _folded(layer))


def _head(params, x, cfg: FalconH1Config):
    with jax.named_scope("lm_head"):
        n = _normed(x, params["final_norm"], cfg)
        logits = jnp.einsum("...d,vd->...v", n,
                            params["lm_head"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        return logits if _folded(params["layers"][0]) else _times(
            logits, cfg.lm_head_multiplier)


def apply(params, tokens, cfg: FalconH1Config):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32; every sequence one chunk from an empty state and an empty tail,
    the keys the sequence's own rows, streamed `kv_block` at a time."""
    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pad = nb * kb - S
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    Hs, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state
    x = _embed(params, tokens, cfg)
    for layer in params["layers"]:
        n = _normed(x, layer["norm"], cfg)
        ssm = jax.vmap(lambda n1: _ssd_sequence(
            n1, layer, jnp.ones(S, bool), jnp.zeros((Hs, P, N), jnp.float32),
            jnp.zeros((CONV_TAPS - 1,) + cfg.conv_tile, jnp.float32),
            cfg)[0])(n)
        q, k, v = _qkv(n, layer, pos, cfg)
        rows = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k, v = rows(k), rows(v)

        def fetch(i):
            sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(a, i * kb, kb, ax)
            return sl(k, 2), sl(v, 2), sl(kpos, 1)

        with jax.named_scope("attn_chunk"):
            o = streamed_attention(q, pos, fetch, nb,
                                   scale=cfg.d_head ** -0.5)
        x = x + (ssm + _attn_out(o, layer, cfg)).astype(x.dtype)
        x = _mlp(x, layer, cfg)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# serving: pages AND one state entry, in every layer


def cache_kinds(cfg: FalconH1Config) -> Dict[str, Any]:
    """name -> what the engine keeps for it (see gpt.cache_kinds): every
    layer's keys and values in a full-kind pool of pages, every layer's
    SSM state and conv tail one entry of a `"state"` kind a sequence."""
    return {FULL: None, SSM: "state"}


def init_paged_cache(cfg: FalconH1Config, num_pages, page_size: int):
    """{"k", "v": [an arena a layer, [pages, page_size, Hkv * dh] (a
    position's heads lie together, whole lanes)], "state": [layers,
    entries, Hs, P, N] float32, "tail": [layers, entries, 3, *conv_tile]
    float32}.  `num_pages` counts pages under `full` and entries under
    `ssm`; page 0 and entry 0 are the null ones."""
    L, entries = cfg.n_layers, int(num_pages[SSM])
    shape = (int(num_pages[FULL]), page_size, cfg.n_kv_heads * cfg.d_head)
    side = lambda: [jnp.zeros(shape, cfg.dtype) for _ in range(L)]
    return {"k": side(), "v": side(),
            "state": jnp.zeros((L, entries, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.d_state), jnp.float32),
            "tail": jnp.zeros((L, entries, CONV_TAPS - 1) + cfg.conv_tile,
                              jnp.float32)}


def state_leaves(cache) -> List[jax.Array]:
    """The leaves of `cache` that are the state kind's arena (the engine
    counts their bytes apart from the pages')."""
    return [cache["state"], cache["tail"]]


def _paged_pass(params, cache, toks, tab, pos, real, ssd_layer, scope: str,
                cfg: FalconH1Config, ctx=None):
    """Tokens toks [B, T] at CONSECUTIVE positions pos [B, T] through the
    layers; `real` [B, T] marks the rows whose K and V are kept.  Both
    branches of a layer read the SAME n: the SSM branch is
    `ssd_layer(l, n, layer, state, tail)` -> (its addition [B, T, D],
    state, tail) over the two state arenas, the attention branch writes
    its rows into the layer's pages and reads them under the named scope
    `scope`.  Returns (x [B, T, D], cache)."""
    ks, vs = list(cache["k"]), list(cache["v"])
    state, tail = cache["state"], cache["tail"]
    ps = ks[0].shape[1]
    io = kind_io("full", tab, pos, real, jnp.max(pos, axis=1),
                 pos.reshape(-1), ps, max(1, cfg.kv_block // ps))
    x = _embed(params, toks, cfg)
    for l, layer in enumerate(params["layers"]):
        n = _normed(x, layer["norm"], cfg)
        ssm, state, tail = ssd_layer(l, n, layer, state, tail)
        q, k, v = _qkv(n, layer, pos, cfg)
        with jax.named_scope(scope):
            o, ks[l], vs[l] = attend_pages(q, k, v, ks[l], vs[l], io, pos,
                                           cfg, ctx)
        x = x + (ssm + _attn_out(o, layer, cfg)).astype(x.dtype)
        x = _mlp(x, layer, cfg)
    return x, {"k": ks, "v": vs, "state": state, "tail": tail}


def _stats(moved, seen, cfg: FalconH1Config):
    moved = jnp.asarray(moved, jnp.float32).reshape(())
    return jnp.stack([moved, moved * (cfg.n_layers * cfg.state_bytes),
                      jnp.asarray(seen, jnp.float32).reshape(())])


def paged_decode_step(params, cache, tokens, ptabs, pos,
                      cfg: FalconH1Config):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[FULL] [B, R] the slots' pages, ptabs[SSM] [B, 1] their entries.
    A slot at position 0 is empty (a prompt has at least one token): it
    writes to the null page and leaves the null entry as it is.  Returns
    (logits [B, V] f32, cache, stats)."""
    idx, live = ptabs[SSM][:, 0], pos > 0

    def ssd_layer(l, n, layer, state, tail):
        z, pre, dt = _ssd_project(n[:, 0], layer, cfg)
        old = tail[l][idx]
        u, new = _conv(pre, old, layer, cfg, step=True)
        with jax.named_scope("ssd_conv"):
            tail = tail.at[l, idx].set(
                jnp.where(live[:, None, None, None], new, old))
        x, dt, b, c = _ssd_operands(u, dt, layer, cfg)
        y, state = ssd_step(x, dt, -jnp.exp(layer["a_log"]), b, c,
                            layer["d_skip"], state, l, idx, live,
                            impl=cfg.ssd_impl)
        return _ssd_out(y, z, layer, cfg)[:, None], state, tail

    ctx = jnp.where(live, pos + 1, 0)
    x, cache = _paged_pass(params, cache, tokens[:, None], ptabs[FULL],
                           pos[:, None], live[:, None], ssd_layer,
                           "attn_step", cfg, ctx)
    moved = states_moved(live, cfg.ssd_impl)
    return _head(params, x[:, 0], cfg), cache, _stats(moved, ctx.sum(), cfg)


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx,
                  cfg: FalconH1Config):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its pages ptab_rows[FULL] [R] and its
    entry ptab_rows[SSM][0]: in every layer the state and the tail are
    read unless this is the sequence's first chunk (`start == 0`) and
    written back where they stand (the tail as the last three REAL
    pre-conv rows), and the chunk's K and V rows go into the layer's
    pages.  Returns (logits [V] f32 at row last_idx, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    real = t <= last_idx
    idx = ptab_rows[SSM][0]
    first = start == 0

    def ssd_layer(l, n, layer, state, tail):
        s0 = carried_at(first, state, l, idx)
        t0 = carried_at(first, tail, l, idx)
        y, s1, pre = _ssd_sequence(n[0], layer, real, s0, t0, cfg)
        t1 = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([t0, pre.astype(tail.dtype)]), last_idx + 1,
            CONV_TAPS - 1, 0)
        return y[None], state.at[l, idx].set(s1), tail.at[l, idx].set(t1)

    x, cache = _paged_pass(params, cache, toks[None], ptab_rows[FULL][None],
                           (start + t)[None], real[None], ssd_layer,
                           "attn_chunk", cfg)
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    return (_head(params, x, cfg), cache,
            _stats(1, start + last_idx + 1, cfg))
