"""Brumby-class decoder: every layer a power-retention layer (degree 2)
and a SwiGLU, no softmax attention anywhere — the serving engine's third
model, behind the same module interface as models/gpt.py and
models/cohere2_moe.py.

The layer (`model_type: brumby`; G = n_heads / n_kv_heads query heads read
one K/V head's state):

    n     = RMSNorm(x)
    q, k  = RMSNorm_h(n Wq), RMSNorm_h(n Wk)      per head, then RoPE
    v     = n Wv
    log g = log sigmoid(n Wg + b)                 per K/V head, float32
    o     = retention(q, k, v, log g)             ops/retention.py
    h     = x + o Wo;   y = h + SwiGLU(RMSNorm(h))
    logits = RMSNorm(x_L) Wout                    (untied head)

What a sequence keeps between tokens is not a list of keys and values but
one **state of fixed size** a layer, [n_kv_heads, R, F] float32 whatever
its length (`ops.retention.state_shape`).  `cache_kinds` says so: the one
kind, `ret`, is a `"state"` — one entry of the arena a sequence for its
whole life, no page table, no window.  The arena is one array
[layers, entries, n_kv_heads, R, F], entry 0 the null state empty slots
ride on; the programs are given it to keep (the engine donates it) and
write a state where it stands.  A sequence's first chunk (`start == 0`)
does not read its entry: that is what empties a re-used one.

The layers are alike, so their weights are stacked ([layers, ..] a leaf)
and a program walks them in a `lax.scan`: one layer's text, and one
instance of the step's kernel, whatever the depth (unrolled, the eight
kernels alone took the chip's compiler 26 of a step's 34 seconds).

A chip may hold a run of the model's layers (a pipeline stage, with the
embedding and the head): `n_layers` is then the run's length.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import apply_rope_halves, rms_norm, swiglu
from ray_tpu.ops.retention import (retention_chunk, retention_step,
                                   state_shape)

from .gpt import cast_leaves
from .served import states_moved

__all__ = ["BrumbyConfig", "init", "apply", "cache_kinds",
           "init_paged_cache", "paged_decode_step", "paged_prefill",
           "serve_view", "state_leaves", "STEP_STATS"]

# what a serve program returns beside logits and cache (an f32 vector):
# the states its retention read and wrote in a layer — for a step the live
# slots' where the kernel runs (it moves nothing for an empty slot) and
# every slot's on the gather / scatter path, one for a chunk
STEP_STATS = ("ret_states",)

KIND = "ret"


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    n_layers: int = 40
    d_model: int = 5120
    n_heads: int = 40
    n_kv_heads: int = 8
    d_head: int = 128
    d_ff: int = 17408
    rms_eps: float = 1e-6
    rope_theta: float = 1e6
    max_seq: int = 32768
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    # ops.retention's `impl`, of the step and the chunk (None: by backend)
    retention_impl: Optional[str] = None
    pos: str = "rope"                 # what the engine reads off a config

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @classmethod
    def nano(cls, **kw):
        """The layer at toy size, for the CPU tests."""
        base = dict(vocab_size=256, n_layers=2, d_model=64, n_heads=6,
                    n_kv_heads=2, d_head=16, d_ff=96, max_seq=128)
        base.update(kw)
        return cls(**base)


def init_layer(key, cfg: BrumbyConfig) -> Dict[str, Any]:
    """One layer's weights.  The gate's weights and bias are kept and
    applied in f32; the bias is 0, so g = 1/2 on average."""
    D, H, Hkv, dh, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                        cfg.d_head, cfg.d_ff)
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 8))

    def dense(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * (scale / math.sqrt(fan_in))).astype(pd)

    out = 1.0 / math.sqrt(2 * cfg.n_layers)
    return {
        "attn_norm": jnp.ones((D,), pd), "mlp_norm": jnp.ones((D,), pd),
        "q_norm": jnp.ones((dh,), pd), "k_norm": jnp.ones((dh,), pd),
        "wq": dense((D, H, dh), D), "wk": dense((D, Hkv, dh), D),
        "wv": dense((D, Hkv, dh), D),
        "wg": dense((D, Hkv), D).astype(jnp.float32),
        "bg": jnp.zeros((Hkv,), jnp.float32),
        "wo": dense((H, dh, D), H * dh, out),
        "w_gate": dense((D, F), D), "w_up": dense((D, F), D),
        "w_down": dense((F, D), F, out),
    }


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _table(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames="cfg")
def _init_layers(keys, cfg):
    return jax.vmap(lambda k: init_layer(k, cfg))(keys)


def init(key, cfg: BrumbyConfig) -> Dict[str, Any]:
    """The param tree; `layers` is `init_layer`'s dict with every leaf
    stacked over the layers.  Three programs: the two vocabulary tables,
    then the layers (the random bits are made where they are used: no
    program holds more than its output and 0.1 GB beside it)."""
    ke, ku, kl = jax.random.split(key, 3)
    V, D, pd = cfg.vocab_size, cfg.d_model, cfg.param_dtype
    return {
        "embed": _table(ke, (V, D), 0.02, pd),
        "unembed": _table(ku, (D, V), 1.0 / math.sqrt(D), pd),
        "final_norm": jnp.ones((D,), pd),
        "layers": _init_layers(jax.random.split(kl, cfg.n_layers), cfg=cfg),
    }


# ---------------------------------------------------------------------------
# the block


def _project(x, layer, pos, cfg: BrumbyConfig):
    """x [B, T, D] at positions pos [B, T] -> q [B, H, T, dh], k, v
    [B, Hkv, T, dh] in cfg.dtype, log g [B, Hkv, T] float32."""
    dt = cfg.dtype
    n = rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(dt)
    q = jnp.einsum("btd,dhk->bhtk", n, layer["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bhtk", n, layer["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bhtk", n, layer["wv"].astype(dt))
    q = apply_rope_halves(rms_norm(q, layer["q_norm"], cfg.rms_eps), pos,
                          cfg.rope_theta)
    k = apply_rope_halves(rms_norm(k, layer["k_norm"], cfg.rms_eps), pos,
                          cfg.rope_theta)
    log_g = jax.nn.log_sigmoid(
        jnp.einsum("btd,dh->bht", n.astype(jnp.float32), layer["wg"])
        + layer["bg"][:, None])
    return q, k, v, log_g


def _mix(x, o, layer, cfg: BrumbyConfig):
    """The heads' outputs o [B, H, T, dh] into the stream, then the MLP."""
    dt = cfg.dtype
    h = x + jnp.einsum("bhtk,hkd->btd", o.astype(dt),
                       layer["wo"].astype(dt)).astype(x.dtype)
    with jax.named_scope("mlp"):
        m = rms_norm(h, layer["mlp_norm"], cfg.rms_eps).astype(dt)
        return h + swiglu(m, layer["w_gate"].astype(dt),
                          layer["w_up"].astype(dt),
                          layer["w_down"].astype(dt)).astype(x.dtype)


def _embed(params, tokens, cfg: BrumbyConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def _logits(params, x, cfg: BrumbyConfig):
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return jnp.einsum("...d,dv->...v", x.astype(cfg.dtype),
                          params["unembed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _grouped(q, cfg: BrumbyConfig):
    """q [H, ..] -> [Hkv, G, ..]: query head h reads K/V head h // G."""
    return q.reshape((cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads)
                     + q.shape[1:])


def _chunk_pass(params, toks, pos, real, states, cfg: BrumbyConfig):
    """One sequence's rows toks [T] at positions pos [T] (`real` marks the
    rows that are not padding) through the layers, from the states
    `states` [L, Hkv, R, F].  Returns (x [T, D], the states after)."""
    def layer_of(x, xs):
        layer, state = xs
        q, k, v, log_g = _project(x, layer, pos[None], cfg)
        k = jnp.where(real[None, :, None], k[0], 0)
        log_g = jnp.where(real[None], log_g[0], 0.0)
        o, state = retention_chunk(_grouped(q[0], cfg), k, v[0], log_g,
                                   state.astype(jnp.float32),
                                   impl=cfg.retention_impl, dtype=cfg.dtype)
        x = _mix(x, o.reshape((1, cfg.n_heads) + o.shape[2:]), layer, cfg)
        return x, state

    x, states = jax.lax.scan(layer_of, _embed(params, toks, cfg)[None],
                             (params["layers"], states))
    return x[0], states


def apply(params, tokens, cfg: BrumbyConfig):
    """Full forward without a cache: tokens [B, S] -> logits [B, S, V]
    f32; every sequence one chunk from an empty state."""
    S = tokens.shape[1]
    zero = jnp.zeros((cfg.n_layers, cfg.n_kv_heads)
                     + state_shape(cfg.d_head), jnp.float32)

    def one(toks):
        x, _ = _chunk_pass(params, toks, jnp.arange(S, dtype=jnp.int32),
                           jnp.ones(S, bool), zero, cfg)
        return x

    return _logits(params, jax.vmap(one)(tokens), cfg)


# ---------------------------------------------------------------------------
# serving: one state a sequence, no pages


def cache_kinds(cfg: BrumbyConfig) -> Dict[str, Any]:
    """name -> what the engine keeps for it (see gpt.cache_kinds): here
    one kind and it is a `"state"` — an entry of fixed size a sequence,
    taken at admission and returned at eviction; its table row is that
    one entry's index."""
    return {KIND: "state"}


def init_paged_cache(cfg: BrumbyConfig, num_pages, page_size: int):
    """The state arena [L, entries, Hkv, R, F], entry 0 of every layer the
    null state.  `num_pages[KIND]` counts the entries; there are no pages
    and `page_size` is not read."""
    n = num_pages[KIND] if isinstance(num_pages, dict) else num_pages
    return jnp.zeros((cfg.n_layers, int(n), cfg.n_kv_heads)
                     + state_shape(cfg.d_head), cfg.state_dtype)


def paged_decode_step(params, cache, tokens, ptabs, pos, cfg: BrumbyConfig):
    """Slot-batch decode: tokens [B] at per-slot positions pos [B];
    ptabs[KIND] [B, 1] the slots' entries.  A slot at position 0 is empty
    (a prompt has at least one token): its entry, the null one, is left
    as it is.  Returns (logits [B, V] f32, cache, stats)."""
    B = tokens.shape[0]
    idx, live = ptabs[KIND][:, 0], pos > 0

    def layer_of(carry, xs):
        x, arena = carry
        layer, l = xs
        q, k, v, log_g = _project(x, layer, pos[:, None], cfg)
        o, arena = retention_step(
            q.reshape(B, cfg.n_kv_heads, -1, cfg.d_head), k[:, :, 0],
            v[:, :, 0], log_g[:, :, 0], arena, l, idx, live,
            impl=cfg.retention_impl, dtype=cfg.dtype)
        x = _mix(x, o.reshape(B, cfg.n_heads, 1, cfg.d_head), layer, cfg)
        return (x, arena), None

    (x, cache), _ = jax.lax.scan(
        layer_of, (_embed(params, tokens, cfg)[:, None], cache),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    moved = states_moved(live, cfg.retention_impl)
    return (_logits(params, x[:, 0], cfg), cache,
            moved.astype(jnp.float32).reshape(1))


def paged_prefill(params, cache, toks, ptab_rows, start, last_idx,
                  cfg: BrumbyConfig):
    """One chunk of one sequence: toks [T] at positions start..start+T-1,
    real up to row last_idx, carried by entry ptab_rows[KIND][0]: read
    unless this is the sequence's first chunk (`start == 0`), written
    back where it stands.  Returns (logits [V] f32 at row last_idx, cache,
    stats)."""
    T = toks.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    idx = ptab_rows[KIND][0]
    states = jnp.where(start == 0, 0, jax.lax.dynamic_index_in_dim(
        cache, idx, 1, keepdims=False))
    x, states = _chunk_pass(params, toks, start + t, t <= last_idx, states,
                            cfg)
    cache = jax.lax.dynamic_update_index_in_dim(
        cache, states.astype(cache.dtype), idx, 1)
    x = jax.lax.dynamic_index_in_dim(x, last_idx, 0, keepdims=False)
    return _logits(params, x, cfg), cache, jnp.ones((1,), jnp.float32)


def state_leaves(cache):
    """The leaves of `cache` that are the state kind's arena: all of it."""
    return [cache]


# the leaves the programs cast to cfg.dtype where they use them; the norms
# and the gate (f32) are used as they are kept
_SERVE_CAST = frozenset({"embed", "unembed", "wq", "wk", "wv", "wo",
                         "w_gate", "w_up", "w_down"})


def serve_view(params, cfg: BrumbyConfig):
    """gpt.cast_leaves over this model's leaves: a tree kept in cfg.dtype
    (the published configuration's) comes back as the same arrays."""
    return cast_leaves(params, cfg, _SERVE_CAST)
