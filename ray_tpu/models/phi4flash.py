"""Phi-4-mini-flash-class decoder-hybrid-decoder (`model_type: phi4flash`;
SambaY, arXiv 2507.06607, with Differential Attention, arXiv 2410.05258,
and Mamba-1, arXiv 2312.00752) — the serving engine's sixth model, behind
the same module interface as models/gpt.py, models/cohere2_moe.py,
models/brumby.py, models/deepseek_v3.py and models/ling3.py.

The block (LayerNorm with weight and bias, statistics in f32, no position
signal anywhere):

    x = x + mixer_l(LN(x));   x = x + W_down(W_up x' * SiLU(W_gate x'))

and the mixer's kind is the layer's place (`Phi4FlashConfig.plan`), with
h = n_layers / 2 and every `mb_per_layer`-th layer (the even ones) a
recurrent one:

    self-decoder, l <= h + 1      cross-decoder, l > h + 1
      even: `mamba`                 even: `gmu`    gates layer h's scan
      odd, l < h: `swa`  (window)   odd:  `cross`  reads layer h + 1's K, V
      l = h + 1: `full`

A Mamba layer (C = expand * d_model channels, N states, R = dt_rank):

    u~ | z = n W_in;   u = SiLU(conv4(u~) + b_c)        ops/shortconv.py
    r | B | C = u W_x;   d = softplus(r W_dt + b_dt)
    h_t = exp(d_t (x) A) h_{t-1} + (d_t u_t) (x) B_t     ops/mamba.py
    y = h_t C_t + D u;   out = (y * SiLU(z)) W_out

Layer h's y — before the gate — is the memory m every `gmu` layer reads at
the same position: out = ((m * SiLU(n W_1)) W_2.  It crosses layers inside
a program and is kept nowhere.

Differential attention (`swa`, `full`, `cross`).  Heads pair up: query
heads (2p, 2p+1) are (q1_p, q2_p), key heads (2j, 2j+1) are (k1_j, k2_j),
value heads (2j, 2j+1) side by side are ONE value V_j, 2 d_head wide, and
query pair p reads key pair j = p // (H / Hkv):

    a1 = softmax(s q1 K1^T) V,  a2 = softmax(s q2 K2^T) V,  s = d_head^-1/2
    lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0(l),  lam0 = 0.8 - 0.6 e^(-0.3 l)
    o_p = (1 - lam0) RMSNorm_2d(a1 - lam a2);   y = [o_p] W_o + b_o

Both score sets are ONE pass of `streamed_attention` over Hkv/2 heads of
width 2 d_head: a cached position's keys [k1_j | k2_j] and values lie as
they are read, and a query head is laid into the half its key fills, zeros
in the other (q1 -> [q1 | 0], q2 -> [0 | q2]): four score rows a pair of
key heads, the keys and values of a page read once.  A `cross` layer has
a query and an output projection only; its K, V are layer h + 1's, as
cached.

What a sequence keeps, and `cache_kinds` says so with THREE kinds:

  * `full`: layer h + 1's keys and values, pages [pages, ps, Hkv d_head] a
    side — ONE layer of pages, read by that layer and every `cross` one;
  * `swa`: the windowed layers', a ring of pages (`served.kind_io`'s);
  * `mamba`, a `"state"`: ONE entry holding every Mamba layer's h ([N, C]
    float32: ops/mamba.py says why this way round) and conv tail ([3, C]).

A prefill chunk runs the self-decoder on its rows and — `PREFILL_KNOWS_LAST`
— the cross-decoder and the head on its ONE row `last_idx` only where the
engine says the chunk is its prompt's last: the cross-decoder writes no
cache, so no other chunk needs it (the architecture's linear prefill).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (latent_decode_uses_kernel,
                                   paged_decode_attention, streamed_attention)
from ray_tpu.ops.shortconv import conv_chunk, conv_step
from ray_tpu.ops.layers import layer_norm, rms_norm
from ray_tpu.ops.mamba import selective_scan_chunk, selective_step

from .gpt import cast_leaves, slot_embed, unembed_table
from .served import carried_at, draw, kind_io, page_blocks, states_moved

__all__ = ["Phi4FlashConfig", "init", "apply", "cache_kinds",
           "init_paged_cache", "paged_decode_step", "paged_prefill",
           "serve_view", "state_leaves", "STEP_STATS", "PREFILL_KNOWS_LAST"]

# what a serve program returns beside logits and cache, in this order (f32
# scalars): the states ONE Mamba layer's update moved (a step's live slots
# where the kernel runs, every slot on the gather / scatter path; one for
# a chunk), the positions of the one shared cache its live rows' queries
# may see (a step: sum of the live slots' contexts; a chunk: its last real
# row's), and the rows that went through the cross-decoder and the head (a
# step's live slots; a chunk: 1 where it is its prompt's last, else 0)
STEP_STATS = ("mamba_live", "shared_kv_positions", "cross_rows")

# `paged_prefill` takes `is_last` behind `last_idx` (serve/_engine.py, "The
# model interface")
PREFILL_KNOWS_LAST = True

FULL, SWA, MAMBA = "full", "swa", "mamba"
CONV_TAPS = 4


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    n_layers: int = 32
    d_model: int = 2560
    n_heads: int = 40
    n_kv_heads: int = 20
    d_head: int = 64
    d_ff: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2              # every such layer is a recurrent one
    d_state: int = 16
    expand: int = 2
    dt_rank: int = 160
    ln_eps: float = 1e-5
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # ops.mamba's `impl` (None: by backend)
    mamba_impl: Optional[str] = None
    kv_block: int = 512                # keys scored at once on the serve path
    # what gpt's shared helpers and the engine read off a config
    pos: str = "none"
    tie_embeddings: bool = True

    def __post_init__(self):
        if self.n_layers % 2 or self.n_layers // 2 % self.mb_per_layer:
            raise ValueError("n_layers / 2 must be a multiple of "
                             "mb_per_layer: layer n_layers / 2 is the "
                             "memory's source, a recurrent layer")
        if self.mb_per_layer != 2:
            raise ValueError("what is built alternates: mb_per_layer 2")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("heads pair up: n_kv_heads even, n_heads a "
                             "multiple of it")

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def memory_layer(self) -> int:
        return self.n_layers // 2

    @property
    def plan(self) -> List[str]:
        """The mixer's kind a layer."""
        h = self.memory_layer
        kinds = []
        for l in range(self.n_layers):
            recurrent = l % self.mb_per_layer == 0
            if l <= h + 1:
                kinds.append(MAMBA if recurrent else
                             FULL if l == h + 1 else SWA)
            else:
                kinds.append("gmu" if recurrent else "cross")
        return kinds

    def layers_of(self, kind: str) -> List[int]:
        return [l for l, k in enumerate(self.plan) if k == kind]

    def lam0(self, l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)

    @classmethod
    def nano(cls, **kw):
        """The plan at toy size, for the CPU tests: 8 layers (mamba, swa,
        mamba, swa, mamba, full | gmu, cross), 8 query and 4 key heads, a
        window of 8."""
        base = dict(vocab_size=256, n_layers=8, d_model=64, n_heads=8,
                    n_kv_heads=4, d_head=16, d_ff=96, sliding_window=8,
                    d_state=16, dt_rank=8, max_seq=128, kv_block=16)
        base.update(kw)
        return cls(**base)


# the draw is `served.draw`, a leaf's place its index here.  Norm weights
# are ones and biases zeros; a Mamba layer's A, D and step-size bias take
# Mamba's own initialisation — they decide what a state remembers:
# A = -(1..N) along the states, D = 1, softplus(b_dt) log-uniform in
# [DT_MIN, DT_MAX] (the uniform is the normal draw through its own
# distribution function).
LEAVES = ("w_in", "conv_w", "w_x", "w_dt", "b_dt", "w_out", "w_qkv", "wq",
          "wo", "lam_q1", "lam_k1", "lam_q2", "lam_k2", "w1", "w2",
          "w_gate_up", "w_down")
DT_MIN, DT_MAX = 1e-3, 1e-1
LAMBDA_STD = 0.1


def init_layer(key, cfg: Phi4FlashConfig, l: int) -> Dict[str, Any]:
    """Layer l's weights: its mixer's by `cfg.plan[l]`, and its SwiGLU's."""
    D, C, N, R, F = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_ff
    H, Hkv, dh, pd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.param_dtype
    out = 1.0 / math.sqrt(2 * cfg.n_layers)
    f32 = jnp.float32

    def w(name, shape, fan_in, scale=1.0, dtype=pd):
        return draw(key, l, LEAVES.index(name), shape,
                     scale / math.sqrt(fan_in), dtype)

    layer = {"attn_norm": jnp.ones((D,), pd), "attn_norm_b": jnp.zeros((D,), pd),
             "mlp_norm": jnp.ones((D,), pd), "mlp_norm_b": jnp.zeros((D,), pd),
             "w_gate_up": w("w_gate_up", (D, 2 * F), D),
             "w_down": w("w_down", (F, D), F, out)}
    kind = cfg.plan[l]
    if kind == MAMBA:
        uniform = jax.scipy.special.ndtr(w("b_dt", (C,), 1, dtype=f32))
        dt = jnp.exp(uniform * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        layer.update(
            w_in=w("w_in", (D, 2 * C), D),
            conv_w=w("conv_w", (CONV_TAPS, C), CONV_TAPS),
            conv_b=jnp.zeros((C,), pd),
            w_x=w("w_x", (C, R + 2 * N), C),
            w_dt=w("w_dt", (R, C), R),
            # kept and applied in f32: the inverse of softplus at dt
            b_dt=dt + jnp.log(-jnp.expm1(-dt)),
            a_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=f32))[:, None], (N, C)),
            d_skip=jnp.ones((C,), f32),
            w_out=w("w_out", (C, D), C, out))
    elif kind == "gmu":
        layer.update(w1=w("w1", (D, C), D), w2=w("w2", (C, D), C, out))
    else:
        if kind == "cross":
            layer.update(wq=w("wq", (D, H * dh), D),
                         wq_b=jnp.zeros((H * dh,), pd))
        else:
            layer.update(w_qkv=w("w_qkv", (D, (H + 2 * Hkv) * dh), D),
                         w_qkv_b=jnp.zeros(((H + 2 * Hkv) * dh,), pd))
        layer.update(
            wo=w("wo", (H * dh, D), H * dh, out), wo_b=jnp.zeros((D,), pd),
            sub_norm=jnp.ones((2 * dh,), pd),
            **{n: w(n, (dh,), 1, LAMBDA_STD, f32)
               for n in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")})
    return layer


def init(key, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """The param tree: `layers` is a list (a layer's leaves are its
    mixer's kind); the embedding is the head's table too."""
    D, pd = cfg.d_model, cfg.param_dtype
    return {
        "embed": draw(key, -1, 0, (cfg.vocab_size, D), 0.02, pd),
        "final_norm": jnp.ones((D,), pd), "final_norm_b": jnp.zeros((D,), pd),
        "layers": [init_layer(key, cfg, l) for l in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# the mixers


def _normed(x, layer, name: str, cfg: Phi4FlashConfig):
    return layer_norm(x, layer[name], layer[name + "_b"], cfg.ln_eps).astype(
        cfg.dtype)


_MLP_LEAVES = ("mlp_norm", "mlp_norm_b", "w_gate_up", "w_down")


@functools.partial(jax.jit, static_argnames="cfg")
def _swiglu(x, w, cfg: Phi4FlashConfig):
    """Jitted here, so that a program that calls it a layer — thirty-two
    times — traces and lowers it once (as `ops.kda.kda_chunk`: ROADMAP
    S11)."""
    with jax.named_scope("mlp"):
        h = _normed(x, w, "mlp_norm", cfg)
        gu = jnp.einsum("btd,df->btf", h, w["w_gate_up"].astype(cfg.dtype))
        gate, up = jnp.split(gu, 2, axis=-1)
        return x + jnp.einsum("btf,fd->btd", up * jax.nn.silu(gate),
                              w["w_down"].astype(cfg.dtype)).astype(x.dtype)


def _mlp(x, layer, cfg: Phi4FlashConfig):
    return _swiglu(x, {k: layer[k] for k in _MLP_LEAVES}, cfg)


def _mamba_project(h, layer, cfg: Phi4FlashConfig):
    """h [.., D] normed -> the pre-conv rows u~ and the gate's z [.., C]
    in cfg.dtype."""
    with jax.named_scope("mamba_proj"):
        return jnp.split(jnp.einsum("...d,dc->...c", h,
                                    layer["w_in"].astype(cfg.dtype)), 2, -1)


def _mamba_gates(u, layer, cfg: Phi4FlashConfig):
    """The conv's output u [.., C] float32 -> the step sizes d [.., C] and
    the token's B, C [.., N], float32."""
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("mamba_proj"):
        x = jnp.einsum("...c,cr->...r", u.astype(dt),
                       layer["w_x"].astype(dt), preferred_element_type=f32)
        r, bm, cm = jnp.split(x, [cfg.dt_rank, cfg.dt_rank + cfg.d_state], -1)
        d = jax.nn.softplus(jnp.einsum(
            "...r,rc->...c", r.astype(dt), layer["w_dt"].astype(dt),
            preferred_element_type=f32) + layer["b_dt"])
        return d, bm, cm


def _mamba_out(x, y, u, z, layer, cfg: Phi4FlashConfig):
    """The scan's read-out y [B, T, C] float32 with the layer's skip, gated
    and projected into the stream -> (x, the memory m = y + D u)."""
    with jax.named_scope("mamba_out"):
        m = y + layer["d_skip"] * u
        g = (m * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
        return x + jnp.einsum("btc,cd->btd", g,
                              layer["w_out"].astype(cfg.dtype)).astype(x.dtype), m


def _mamba_sequence(x, h, layer, real, state, tail, cfg: Phi4FlashConfig):
    """A Mamba layer over ONE sequence's rows x [1, T, D] (`real` [T]
    marks those that are not padding) from its carried state [N, C] and
    tail [3, C] -> (x, m [1, T, C], the state after, the pre-conv rows
    [T, C])."""
    pre, z = _mamba_project(h, layer, cfg)
    u = conv_chunk(pre[0], tail, layer["conv_w"], layer["conv_b"],
                   jax.nn.silu, "kda_conv")
    d, bm, cm = _mamba_gates(u, layer, cfg)
    y, state = selective_scan_chunk(
        u, jnp.where(real[:, None], d, 0.0), -jnp.exp(layer["a_log"]), bm, cm,
        state, impl=cfg.mamba_impl)
    x, m = _mamba_out(x, y[None], u[None], z, layer, cfg)
    return x, m, state, pre[0]


def _gmu(x, h, m, layer, cfg: Phi4FlashConfig):
    with jax.named_scope("gmu"):
        g = jnp.einsum("btd,dc->btc", h, layer["w1"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)
        g = (m * jax.nn.silu(g)).astype(cfg.dtype)
        return x + jnp.einsum("btc,cd->btd", g,
                              layer["w2"].astype(cfg.dtype)).astype(x.dtype)


def _pair_queries(q, cfg: Phi4FlashConfig):
    """q [B, T, H * dh] -> [B, Hkv/2, 2G, T, 2dh]: key pair j's 2G query
    heads, each in the half of the pair's width its own key fills (q1 ->
    [q1 | 0], q2 -> [0 | q2]), so that a product with [k1_j | k2_j]
    scores q1 against k1 and q2 against k2."""
    B, T, _ = q.shape
    J, dh = cfg.n_kv_heads // 2, cfg.d_head
    G = cfg.n_heads // cfg.n_kv_heads
    q = q.reshape(B, T, J, G, 2, dh)
    zero = jnp.zeros_like(q[..., 0, :])
    q = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                   jnp.concatenate([zero, q[..., 1, :]], -1)], axis=4)
    return jnp.moveaxis(q.reshape(B, T, J, 2 * G, 2 * dh), 1, 3)


def _lambda(l: int, layer, cfg: Phi4FlashConfig):
    dot = lambda a, b: jnp.sum(layer[a].astype(jnp.float32)
                               * layer[b].astype(jnp.float32))
    return (jnp.exp(dot("lam_q1", "lam_k1")) - jnp.exp(dot("lam_q2", "lam_k2"))
            + cfg.lam0(l))


def _diff_out(x, o, l: int, layer, cfg: Phi4FlashConfig):
    """The two softmaxes' results o [B, Hkv/2, 2G, T, 2dh] -> their
    difference, normed a pair wide, into the stream."""
    B, J, _, T, dv = o.shape
    with jax.named_scope("attn_out"):
        o = o.astype(jnp.float32).reshape(B, J, -1, 2, T, dv)
        diff = o[:, :, :, 0] - _lambda(l, layer, cfg) * o[:, :, :, 1]
        diff = (1.0 - cfg.lam0(l)) * rms_norm(diff, layer["sub_norm"],
                                              cfg.ln_eps)
        y = jnp.moveaxis(diff, 3, 1).reshape(B, T, -1).astype(cfg.dtype)
        return x + (jnp.einsum("btk,kd->btd", y, layer["wo"].astype(cfg.dtype))
                    + layer["wo_b"].astype(cfg.dtype)).astype(x.dtype)


def _project(h, layer, name: str, cfg: Phi4FlashConfig):
    with jax.named_scope("attn_proj"):
        return (jnp.einsum("btd,dk->btk", h, layer[name].astype(cfg.dtype))
                + layer[name + "_b"].astype(cfg.dtype))


def _attention(x, h, l: int, layer, attend, cfg: Phi4FlashConfig,
               scope: str = "attend"):
    """A differential-attention layer on x [B, T, D]: `attend(q [B, Hkv/2,
    2G, T, 2dh], k, v [B, T, Hkv * dh] or None) -> [B, Hkv/2, 2G, T, 2dh]`
    owns the keys (a cache, or the sequence itself) and runs under the
    named scope `scope`; a `cross` layer brings none."""
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if "wq" in layer:
        q, k, v = _project(h, layer, "wq", cfg), None, None
    else:
        q, k, v = jnp.split(_project(h, layer, "w_qkv", cfg),
                            [H * dh, (H + Hkv) * dh], axis=-1)
    with jax.named_scope(scope):
        o = attend(_pair_queries(q, cfg), k, v)
    return _diff_out(x, o, l, layer, cfg)


def _head(params, x, cfg: Phi4FlashConfig):
    with jax.named_scope("lm_head"):
        x = layer_norm(x, params["final_norm"], params["final_norm_b"],
                       cfg.ln_eps)
        return jnp.einsum("...d,dv->...v", x.astype(cfg.dtype),
                          unembed_table(params, cfg),
                          preferred_element_type=jnp.float32)


def _window(kind: str, cfg: Phi4FlashConfig) -> Optional[int]:
    return cfg.sliding_window if kind == SWA else None


def _heads_first(a, cfg: Phi4FlashConfig):
    """Cached rows [B, S, Hkv * dh] -> [B, Hkv/2, S, 2dh]: a pair of key
    (or value) heads side by side is one head twice as wide."""
    B, S, _ = a.shape
    return jnp.moveaxis(a.reshape(B, S, cfg.n_kv_heads // 2, 2 * cfg.d_head),
                        2, 1)


def apply(params, tokens, cfg: Phi4FlashConfig):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32; every sequence one chunk from an empty state and an empty tail,
    the keys the sequence's own rows, streamed `kv_block` at a time, every
    layer on every position."""
    B, S = tokens.shape
    kb = min(cfg.kv_block, S)
    nb = -(-S // kb)
    pad = nb * kb - S
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    C, N = cfg.d_inner, cfg.d_state
    x = slot_embed(params, tokens, pos, cfg)
    shared, m = {}, None

    def attend_for(kind):
        def attend(q, k, v):
            if k is not None:
                rows = lambda a: jnp.pad(_heads_first(a, cfg),
                                         ((0, 0), (0, 0), (0, pad), (0, 0)))
                kv = rows(k), rows(v)
                if kind == FULL:
                    shared["kv"] = kv
            else:
                kv = shared["kv"]

            def fetch(i):
                sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(
                    a, i * kb, kb, ax)
                return sl(kv[0], 2), sl(kv[1], 2), sl(kpos, 1)

            return streamed_attention(q, pos, fetch, nb,
                                      window=_window(kind, cfg),
                                      scale=cfg.d_head ** -0.5)
        return attend

    for l, (layer, kind) in enumerate(zip(params["layers"], cfg.plan)):
        h = _normed(x, layer, "attn_norm", cfg)
        if kind == MAMBA:
            x, m_l = jax.vmap(lambda x1, h1: _mamba_sequence(
                x1[None], h1[None], layer, jnp.ones(S, bool),
                jnp.zeros((N, C), jnp.float32),
                jnp.zeros((CONV_TAPS - 1, C), cfg.dtype), cfg)[:2])(x, h)
            x, m_l = x[:, 0], m_l[:, 0]
            if l == cfg.memory_layer:
                m = m_l
        elif kind == "gmu":
            x = _gmu(x, h, m, layer, cfg)
        else:
            x = _attention(x, h, l, layer, attend_for(kind), cfg)
        x = _mlp(x, layer, cfg)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# serving: one layer of full pages, a ring of window pages, one state entry


def cache_kinds(cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """name -> what the engine keeps for it (see gpt.cache_kinds): the one
    full-attention layer's keys and values in a full-kind pool, the
    windowed layers' in a ring, the Mamba layers' states and conv tails
    one entry of a `"state"` kind a sequence."""
    return {FULL: None, SWA: cfg.sliding_window, MAMBA: "state"}


def init_paged_cache(cfg: Phi4FlashConfig, num_pages, page_size: int):
    """{"full": the one arena every cross layer reads, "swa": [an arena a
    windowed layer], each {"k", "v"} of [pages of its kind, page_size,
    Hkv * dh] (a position's heads lie together, whole lanes), "state":
    [Mamba layers, entries, N, C] float32, "tail": [Mamba layers, entries,
    3, C] in cfg.dtype}.  `num_pages` counts pages under `full` and `swa`
    and entries under `mamba`; page 0 and entry 0 are the null ones."""
    C, N, n = cfg.d_inner, cfg.d_state, len(cfg.layers_of(MAMBA))
    entries = int(num_pages[MAMBA])

    def arena(kind):
        shape = (int(num_pages[kind]), page_size, cfg.n_kv_heads * cfg.d_head)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    return {"full": arena(FULL),
            "swa": [arena(SWA) for _ in cfg.layers_of(SWA)],
            "state": jnp.zeros((n, entries, N, C), jnp.float32),
            "tail": jnp.zeros((n, entries, CONV_TAPS - 1, C), cfg.dtype)}


def state_leaves(cache) -> List[jax.Array]:
    """The leaves of `cache` that are the state kind's arena (the engine
    counts their bytes apart from the pages')."""
    return [cache["state"], cache["tail"]]


def _paged_attend(kind: str, arena, io, qpos, cfg: Phi4FlashConfig, ctx=None):
    """attend() of `_attention` against one arena: write this call's K and
    V rows (where the layer brings any) at the (page, offset) of `io`,
    then stream the table's pages `kv_block` keys at a time — or, a row a
    slot on a TPU (`ctx` [B]: the keys each slot's row sees in a full
    kind, 0 for an empty slot), walk each slot's own pages where they lie
    (`ops.attention.paged_decode_attention`: a full kind's as far as its
    context, a ring's those its window reaches).  Returns (attend, box):
    box["arena"] is the arena after attend has run."""
    tab, bases, (pidx, poff), n_blocks = io
    ps = arena["k"].shape[1]
    npb = max(1, cfg.kv_block // ps)
    B = tab.shape[0]
    box = {"arena": arena}

    def attend(q, k, v):
        if k is not None:
            rows = lambda a: a.reshape(-1, a.shape[-1]).astype(cfg.dtype)
            box["arena"] = {"k": arena["k"].at[pidx, poff].set(rows(k)),
                            "v": arena["v"].at[pidx, poff].set(rows(v))}
        kc, vc = box["arena"]["k"], box["arena"]["v"]
        if ctx is not None and latent_decode_uses_kernel(q.shape[3]):
            walk = (-(-ctx // ps) if kind == FULL
                    else jnp.where(ctx > 0, tab.shape[1], 0))
            return paged_decode_attention(
                q[:, :, :, 0], kc, vc, tab, bases, qpos[:, 0], walk,
                scale=cfg.d_head ** -0.5,
                window=_window(kind, cfg))[:, :, :, None]

        fetch = page_blocks(tab, bases, kc, vc, npb, lambda c: _heads_first(
            c.reshape(B, npb * ps, c.shape[-1]), cfg))
        return streamed_attention(q, qpos, fetch, n_blocks,
                                  window=_window(kind, cfg),
                                  scale=cfg.d_head ** -0.5)

    return attend, box


def _tables(ptabs, pos, real, ps: int, cfg: Phi4FlashConfig):
    """How rows at positions pos [B, T] (`real` marks those whose K and V
    are kept) meet the two paged kinds' tables (`served.kind_io`)."""
    npb = max(1, cfg.kv_block // ps)
    last, flat_pos = jnp.max(pos, axis=1), pos.reshape(-1)
    return {k: kind_io("full" if k == FULL else "sliding", ptabs[k], pos,
                       real, last, flat_pos, ps, npb) for k in (FULL, SWA)}


def _self_decoder(params, cache, x, pos, real, io, mamba_layer, scopes,
                  cfg: Phi4FlashConfig, ctx=None):
    """Layers 0 .. h + 1 on x [B, T, D]: a Mamba layer is
    `mamba_layer(j, x, h, layer, state, tail)` -> (x, m, state, tail) over
    the two state arenas, j its index among them; an attention layer
    writes its rows into its arena and reads it.  Returns (x, the memory
    m [B, T, C], cache)."""
    swa, state, tail = list(cache["swa"]), cache["state"], cache["tail"]
    full, m = cache["full"], None
    n_mamba = n_swa = 0
    for l in range(cfg.memory_layer + 2):
        layer, kind = params["layers"][l], cfg.plan[l]
        h = _normed(x, layer, "attn_norm", cfg)
        if kind == MAMBA:
            x, m, state, tail = mamba_layer(n_mamba, x, h, layer, state, tail)
            n_mamba += 1
        else:
            arena = full if kind == FULL else swa[n_swa]
            attend, box = _paged_attend(kind, arena, io[kind], pos, cfg, ctx)
            x = _attention(x, h, l, layer, attend, cfg, scopes[kind])
            if kind == FULL:
                full = box["arena"]
            else:
                swa[n_swa] = box["arena"]
                n_swa += 1
        x = _mlp(x, layer, cfg)
    return x, m, {"full": full, "swa": swa, "state": state, "tail": tail}


def _cross_decoder(params, full, x, m, pos, io, ctx, scope: str,
                   cfg: Phi4FlashConfig):
    """Layers h + 2 .. on x [B, 1, D] at positions pos [B, 1] with the
    memory m [B, 1, C] of the same positions: every `cross` layer reads
    the shared arena `full` as it stands (this program's rows in it).
    Returns logits [B, V] f32."""
    for l in range(cfg.memory_layer + 2, cfg.n_layers):
        layer, kind = params["layers"][l], cfg.plan[l]
        h = _normed(x, layer, "attn_norm", cfg)
        if kind == "gmu":
            x = _gmu(x, h, m, layer, cfg)
        else:
            attend, _ = _paged_attend(FULL, full, io, pos, cfg, ctx)
            x = _attention(x, h, l, layer, attend, cfg, scope)
        x = _mlp(x, layer, cfg)
    return _head(params, x[:, 0], cfg)


def _stats(moved, seen, rows):
    return jnp.stack([jnp.asarray(a, jnp.float32).reshape(())
                      for a in (moved, seen, rows)])


def paged_decode_step(params, cache, tokens, ptabs, pos,
                      cfg: Phi4FlashConfig):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[FULL] [B, R] the slots' pages, ptabs[SWA] [B, ring] their window
    rings, ptabs[MAMBA] [B, 1] their entries.  A slot at position 0 is
    empty (a prompt has at least one token): it writes to the null pages
    and leaves the null entry as it is.  Returns (logits [B, V] f32,
    cache, stats)."""
    idx, live = ptabs[MAMBA][:, 0], pos > 0

    def mamba_layer(j, x, h, layer, state, tail):
        pre, z = _mamba_project(h, layer, cfg)
        old = tail[j][idx]
        u, new = conv_step(pre[:, 0], old, layer["conv_w"], layer["conv_b"],
                           jax.nn.silu, "kda_conv")
        tail = tail.at[j, idx].set(jnp.where(live[:, None, None], new, old))
        d, bm, cm = _mamba_gates(u, layer, cfg)
        y, state = selective_step(u, d, -jnp.exp(layer["a_log"]), bm, cm,
                                  state, j, idx, live, impl=cfg.mamba_impl)
        x, m = _mamba_out(x, y[:, None], u[:, None], z, layer, cfg)
        return x, m, state, tail

    pos2, live2 = pos[:, None], live[:, None]
    io = _tables(ptabs, pos2, live2, cache["full"]["k"].shape[1], cfg)
    x = slot_embed(params, tokens[:, None], pos2, cfg)
    scopes = {FULL: "shared_kv_attend_step", SWA: "swa_attend_step"}
    ctx = jnp.where(live, pos + 1, 0)
    x, m, cache = _self_decoder(params, cache, x, pos2, live2, io,
                                mamba_layer, scopes, cfg, ctx)
    logits = _cross_decoder(params, cache["full"], x, m, pos2, io[FULL], ctx,
                            scopes[FULL], cfg)
    return logits, cache, _stats(states_moved(live, cfg.mamba_impl),
                                 ctx.sum(), live.sum())


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx, is_last,
                  cfg: Phi4FlashConfig):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, against its pages ptab_rows[FULL] [R], its
    ring ptab_rows[SWA] and its entry ptab_rows[MAMBA][0]: the
    self-decoder on every row (states and tails read unless this is the
    sequence's first chunk, `start == 0`, and written back where they
    stand, the tail as the last three REAL pre-conv rows) and, where
    `is_last` says the chunk is its prompt's last, the cross-decoder and
    the head on row last_idx alone.  Returns (logits [V] f32 at row
    last_idx — zeros from a chunk that is not the last —, cache, stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    real = t <= last_idx
    idx = ptab_rows[MAMBA][0]
    first = start == 0

    def mamba_layer(j, x, h, layer, state, tail):
        s0 = carried_at(first, state, j, idx)
        t0 = carried_at(first, tail, j, idx)
        x, m, s1, pre = _mamba_sequence(x, h, layer, real, s0, t0, cfg)
        t1 = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([t0, pre.astype(tail.dtype)]), last_idx + 1,
            CONV_TAPS - 1, 0)
        return (x, m, state.at[j, idx].set(s1.astype(state.dtype)),
                tail.at[j, idx].set(t1))

    pos = (start + t)[None]
    ps = cache["full"]["k"].shape[1]
    tabs = {k: ptab_rows[k][None] for k in (FULL, SWA)}
    io = _tables(tabs, pos, real[None], ps, cfg)
    x = slot_embed(params, toks[None], pos, cfg)
    scopes = {FULL: "full_attend_chunk", SWA: "swa_attend_chunk"}
    x, m, cache = _self_decoder(params, cache, x, pos, real[None], io,
                                mamba_layer, scopes, cfg)
    row = lambda a: jax.lax.dynamic_slice_in_dim(a, last_idx, 1, 1)
    at = row(pos)

    def cross(full, x1, m1):
        one = _tables(tabs, at, jnp.ones((1, 1), bool), ps, cfg)[FULL]
        return _cross_decoder(params, full, x1, m1, at, one, at[0] + 1,
                              "shared_kv_attend_row", cfg)[0]

    logits = jax.lax.cond(
        is_last, cross,
        lambda *_: jnp.zeros((cfg.vocab_size,), jnp.float32),
        cache["full"], row(x), row(m))
    return logits, cache, _stats(1, start + last_idx + 1, is_last)


# the leaves the programs cast to cfg.dtype where they use them; the norms
# and the differential attention's and the scan's own parameters (lam_*,
# b_dt, a_log, d_skip) are used as they are kept
_SERVE_CAST = frozenset({
    "embed", "w_gate_up", "w_down", "w_in", "conv_w", "conv_b", "w_x", "w_dt",
    "w_out", "w1", "w2", "w_qkv", "w_qkv_b", "wq", "wq_b", "wo", "wo_b"})


def serve_view(params, cfg: Phi4FlashConfig):
    """gpt.cast_leaves over this model's leaves: a tree kept in cfg.dtype
    (the published configuration's) comes back as the same arrays."""
    return cast_leaves(params, cfg, _SERVE_CAST)
