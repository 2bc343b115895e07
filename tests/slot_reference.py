"""The contiguous slot cache: the PLAIN recipe the paged serve programs of
`ray_tpu/models/gpt.py` are held to (`tests/test_serve_prefill.py`,
`tests/test_serve_live_blocks.py`).

One row [H, S, dh] a slot and side, every position of it scored at once
under the causal mask, one softmax over all of them.  No program serves
this layout; it is the reference, and it shares with the served programs
what is not under test: the embedding, the QKV projection with its
position signal, the output projection and MLP, and `_prefill_chunk`'s
pass of a padded chunk through the layers, which takes a layout's `write`
and `attend`."""
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.gpt import (GPTConfig, _attn_out_and_mlp, _numbered,
                                _prefill_chunk, _slot_qkv, apply_norm,
                                slot_embed, unembed_table)
from ray_tpu.ops import rope_table


def _slot_attention(q, kc, vc, pos, cfg: GPTConfig):
    """q [B,H,T,dh] at positions pos [B,T] against a per-slot cache view
    kc/vc [B,H,S,dh], masked causally by position (key <= pos[b, t]):
    every position of the view scored at once under the mask, one softmax
    over all of them (a decode step is its T = 1 case, a prefill its B = 1
    case).  The paged programs apply the same mask and take the same sums
    block by block over the live part of the page table
    (`gpt._paged_attention`): the tests measure paged == contiguous on
    logits, within 1e-4 in f32."""
    S = kc.shape[2]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
            <= pos[:, None, :, None])
    s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * (cfg.d_head ** -0.5)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    vcd = vc if vc.dtype == cfg.dtype else vc.astype(cfg.dtype)
    return jnp.einsum("bhqs,bhsk->bhqk", p.astype(cfg.dtype), vcd)


def init_slot_cache(cfg: GPTConfig, slots: int, max_total: int
                    ) -> Dict[str, Any]:
    """Contiguous slot cache: [L, slots, H, max_total, d_head] per side.
    Positions live with the engine (per-slot, host-driven), not in the
    cache — unlike init_cache's scalar lockstep `pos`."""
    shape = (cfg.n_layers, slots, cfg.n_heads, max_total, cfg.d_head)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _slot_decode_hidden(params, kcache, vcache, tokens, pos, cfg: GPTConfig,
                        rope=None):
    """One decode position for every slot: tokens [B] at per-slot
    positions pos [B] -> (hidden [B, D], kcache, vcache).  kcache/vcache
    [L, B, H, S, dh]."""
    B = tokens.shape[0]
    S = kcache.shape[3]
    if cfg.pos == "learned":
        rope = None
    elif rope is None:
        rope = rope_table(S, cfg.d_head, dtype=jnp.float32)
    qpos = pos[:, None]                        # one query row a slot
    x = slot_embed(params, tokens[:, None], qpos, cfg)     # [B, 1, D]
    bidx = jnp.arange(B)

    def block(carry, inp):
        x, kc, vc = carry                      # kc/vc [L, B, H, S, dh]
        layer, l = inp
        q, k, v = _slot_qkv(x, layer, cfg, rope, qpos)
        kc = kc.at[l, bidx, :, pos, :].set(k[:, :, 0, :].astype(kc.dtype))
        vc = vc.at[l, bidx, :, pos, :].set(v[:, :, 0, :].astype(vc.dtype))
        o = _slot_attention(q, kc[l], vc[l], qpos, cfg)
        return (_attn_out_and_mlp(x, o, layer, cfg), kc, vc), None

    (x, k_new, v_new), _ = jax.lax.scan(
        block, (x, kcache, vcache), _numbered(params["layers"], cfg),
        unroll=cfg.n_layers)
    x = apply_norm(x, params["final_norm"], params.get("final_norm_b"),
                   cfg.norm)
    return x[:, 0], k_new, v_new


def slot_decode_step(params, cache, tokens, pos, cfg: GPTConfig, rope=None):
    """Slot-batch decode on the contiguous cache: tokens [B] at per-slot
    positions pos [B] -> (logits [B, V], cache)."""
    x, k_new, v_new = _slot_decode_hidden(params, cache["k"], cache["v"],
                                          tokens, pos, cfg, rope)
    logits = jnp.einsum("bd,dv->bv", x.astype(cfg.dtype),
                        unembed_table(params, cfg))
    return logits, {"k": k_new, "v": v_new}


def slot_prefill(params, cache, toks, start, last_idx, slot,
                 cfg: GPTConfig, rope=None):
    """Prefill ONE slot while the rest of the batch is frozen: the
    padded chunk toks [T], starting at position `start`, goes through
    the layers in one pass (_prefill_chunk); logits are taken at chunk
    row `last_idx`.  Returns (logits [V], cache)."""
    S = cache["k"].shape[3]

    def write(c, l, rows, wpos):               # c [L, B, H, S, dh]
        return c.at[l, slot, :, wpos, :].set(rows, mode="drop")

    def attend(q, kc, vc, l, pos):             # the slot's own row, whole
        return _slot_attention(q, kc[l, slot][None], vc[l, slot][None],
                               pos, cfg)

    logits, kc, vc = _prefill_chunk(
        params, cache["k"], cache["v"], toks, start, last_idx, S, write,
        attend, cfg, rope)
    return logits, {"k": kc, "v": vc}
