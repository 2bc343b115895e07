"""Mixture-of-experts GPT: the EP (expert-parallel) flagship model.

The reference has no in-tree MoE/EP (SURVEY.md §2.3) — this is the native
build: a decoder-only transformer whose MLP is a top-k routed expert bank
(ops/moe.py), expert-sharded over the mesh's `ep` axis with all_to_all
token dispatch inside a partial-manual shard_map region.  Attention, norms,
rope, scan-over-layers and the sharding-constraint idiom are shared with
models/gpt.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import rms_norm, rope_table, apply_rope, \
    softmax_cross_entropy
from ray_tpu.ops.moe import expert_capacity, moe_ffn, moe_ffn_sharded
from ray_tpu.parallel.sharding import Logical

from .gpt import GPTConfig, apply_norm, attention_op, constrain


@dataclasses.dataclass(frozen=True)
class MoEConfig(GPTConfig):
    """GPT config + expert bank. d_ff is the per-expert hidden size."""

    n_experts: int = 8
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_z_weight: float = 0.001

    @classmethod
    def mixtral_nano(cls, **kw):
        kw.setdefault("norm", "rms")
        kw.setdefault("act", "gelu")
        kw.setdefault("pos", "rope")
        return cls(n_layers=2, d_model=64, n_heads=4, d_head=16, d_ff=128,
                   vocab_size=256, max_seq=128, n_experts=4,
                   tie_embeddings=True, **kw)

    @classmethod
    def small(cls, **kw):
        kw.setdefault("norm", "rms")
        kw.setdefault("pos", "rope")
        return cls(n_layers=12, d_model=768, n_heads=12, d_head=64,
                   d_ff=2048, n_experts=8, **kw)


def logical_axes(cfg: MoEConfig) -> Dict[str, Any]:
    lp = {
        "attn_norm": Logical("layers", None),
        "wq": Logical("layers", "embed", "heads", "head_dim"),
        "wk": Logical("layers", "embed", "heads", "head_dim"),
        "wv": Logical("layers", "embed", "heads", "head_dim"),
        "wo": Logical("layers", "heads", "head_dim", "embed"),
        "mlp_norm": Logical("layers", None),
        # router replicated over experts (every token scores every expert)
        "router": Logical("layers", "embed", None),
        "w_in": Logical("layers", "experts", "embed", "mlp"),
        "w_out": Logical("layers", "experts", "mlp", "embed"),
    }
    if cfg.norm == "ln":
        lp["attn_norm_b"] = Logical("layers", None)
        lp["mlp_norm_b"] = Logical("layers", None)
    out = {
        "embed": Logical("vocab", "embed"),
        "layers": lp,
        "final_norm": Logical(None),
    }
    if cfg.norm == "ln":
        out["final_norm_b"] = Logical(None)
    if cfg.pos == "learned":
        out["pos_embed"] = Logical(None, "embed")
    if not cfg.tie_embeddings:
        out["unembed"] = Logical("embed", "vocab")
    return out


def init(key, cfg: MoEConfig) -> Dict[str, Any]:
    L, D, H, dh, F, V, E = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                            cfg.d_head, cfg.d_ff, cfg.vocab_size,
                            cfg.n_experts)
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 16))

    def dense(rng, shape, fan_in):
        return jax.random.normal(rng, shape, pd) * (1.0 / math.sqrt(fan_in))

    lp = {
        "attn_norm": jnp.ones((L, D), pd),
        "wq": dense(next(k), (L, D, H, dh), D),
        "wk": dense(next(k), (L, D, H, dh), D),
        "wv": dense(next(k), (L, D, H, dh), D),
        "wo": dense(next(k), (L, H, dh, D), H * dh) / math.sqrt(2 * L),
        "mlp_norm": jnp.ones((L, D), pd),
        "router": dense(next(k), (L, D, E), D),
        "w_in": dense(next(k), (L, E, D, F), D),
        "w_out": dense(next(k), (L, E, F, D), F) / math.sqrt(2 * L),
    }
    if cfg.norm == "ln":
        lp["attn_norm_b"] = jnp.zeros((L, D), pd)
        lp["mlp_norm_b"] = jnp.zeros((L, D), pd)
    params = {
        "embed": jax.random.normal(next(k), (V, D), pd) * 0.02,
        "layers": lp,
        "final_norm": jnp.ones((D,), pd),
    }
    if cfg.norm == "ln":
        params["final_norm_b"] = jnp.zeros((D,), pd)
    if cfg.pos == "learned":
        params["pos_embed"] = jax.random.normal(next(k), (cfg.max_seq, D),
                                                pd) * 0.01
    if not cfg.tie_embeddings:
        params["unembed"] = dense(next(k), (D, V), D)
    return params


def _moe_op(h, router_w, w_in, w_out, cfg: MoEConfig, mesh,
            allow_manual: bool = True):
    """Routed MLP on [B, S, D] activations; returns (out, aux, z).

    With an ep axis on the mesh the expert computation runs in a
    partial-manual shard_map over {'ep'}: tokens stay sharded over the data
    axes automatically, experts are split manually, and dispatch is one
    lax.all_to_all each way over ICI.  Inside the pp pipeline's manual
    region (allow_manual=False) shardy cannot open another manual region,
    so expert parallelism falls back to GSPMD auto-partitioning of the
    dense routed-FFN einsums over the expert-sharded weights.
    """
    B, S, D = h.shape
    x2 = h.reshape(B * S, D)
    if allow_manual and mesh is not None and mesh.shape.get("ep", 1) > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        n_ep = mesh.shape["ep"]
        # partial-manual over {'ep'} divides the token dim by ep only (the
        # dp/fsdp shards stay inside the body's GSPMD-auto dimension), so
        # the routing group holds B*S/ep tokens
        cap = expert_capacity(x2.shape[0] // n_ep, cfg.n_experts,
                              cfg.expert_top_k, cfg.capacity_factor)
        fn = lambda xt, wr, wi, wo: moe_ffn_sharded(
            xt, wr, wi, wo, axis_name="ep", k=cfg.expert_top_k,
            capacity=cap)
        out, aux, z = shard_map(
            fn, check_vma=False, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=(P("ep"), P(), P()),
            axis_names=frozenset({"ep"}))(x2, router_w, w_in, w_out)
    else:
        out, aux, z = moe_ffn(x2, router_w, w_in, w_out,
                              k=cfg.expert_top_k,
                              capacity_factor=cfg.capacity_factor)
    return out.reshape(B, S, D), aux, z


def apply(params, tokens, cfg: MoEConfig, mesh=None
          ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Forward: tokens [B, S] -> (logits [B, S, V], {"aux","z"} losses)."""
    B, S = tokens.shape
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:S][None].astype(cfg.dtype)
        rope = None
    else:
        rope = rope_table(S, cfg.d_head, dtype=jnp.float32)
    x = constrain(x, "batch", "seq", "embed")

    def block(x, layer):
        h = apply_norm(x, layer["attn_norm"], layer.get("attn_norm_b"),
                       cfg.norm)
        h = h.astype(cfg.dtype)
        q = jnp.einsum("bsd,dhk->bhsk", h, layer["wq"].astype(cfg.dtype))
        k = jnp.einsum("bsd,dhk->bhsk", h, layer["wk"].astype(cfg.dtype))
        v = jnp.einsum("bsd,dhk->bhsk", h, layer["wv"].astype(cfg.dtype))
        if rope is not None:
            q = apply_rope(q, *rope)
            k = apply_rope(k, *rope)
        q = constrain(q, "batch", "heads", "seq", "head_dim")
        k = constrain(k, "batch", "heads", "seq", "head_dim")
        v = constrain(v, "batch", "heads", "seq", "head_dim")
        o = attention_op(q, k, v, cfg, mesh, allow_manual=(pp == 1))
        att = jnp.einsum("bhsk,hkd->bsd", o, layer["wo"].astype(cfg.dtype))
        x = x + att
        h2 = apply_norm(x, layer["mlp_norm"], layer.get("mlp_norm_b"),
                        cfg.norm)
        m, aux, z = _moe_op(h2.astype(cfg.dtype),
                            layer["router"].astype(cfg.dtype),
                            layer["w_in"].astype(cfg.dtype),
                            layer["w_out"].astype(cfg.dtype), cfg, mesh,
                            allow_manual=(pp == 1))
        x = x + m
        return constrain(x, "batch", "seq", "embed"), aux, z

    def scan_body(carry, layer):
        x, aux_sum, z_sum = carry
        if cfg.remat:
            x, aux, z = jax.checkpoint(block)(x, layer)
        else:
            x, aux, z = block(x, layer)
        return (x, aux_sum + aux, z_sum + z), None

    if pp > 1:
        # MoE through the pp pipeline: the (x, aux, z) triple rides the
        # rotation as a pytree carry (parallel/pipeline.py), so router
        # losses from every stage reach the output
        from ray_tpu.parallel.pipeline import (merge_microbatches,
                                               pipeline_apply,
                                               split_microbatches)

        if cfg.n_layers % pp:
            raise ValueError(f"n_layers {cfg.n_layers} not divisible by "
                             f"pp {pp}")
        M = cfg.num_microbatches or pp

        def stage_fn(stage_layers, carry):
            (x, aux, z), _ = jax.lax.scan(scan_body, carry, stage_layers)
            return (x, aux, z)

        stacked = jax.tree.map(
            lambda p: p.reshape(pp, cfg.n_layers // pp, *p.shape[1:]),
            params["layers"])
        zeros_mb = jnp.zeros((M,), jnp.float32)
        x_out, aux_mb, z_mb = pipeline_apply(
            stage_fn, stacked,
            (split_microbatches(x, M), zeros_mb, zeros_mb), mesh)
        x = merge_microbatches(x_out)
        aux_sum = jnp.mean(aux_mb)
        z_sum = jnp.mean(z_mb)
    else:
        zero = jnp.zeros((), jnp.float32)
        (x, aux_sum, z_sum), _ = jax.lax.scan(
            scan_body, (x, zero, zero), params["layers"])
    x = apply_norm(x, params["final_norm"], params.get("final_norm_b"),
                   cfg.norm)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"]).astype(cfg.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cfg.dtype), unembed)
    losses = {"aux": aux_sum / cfg.n_layers, "z": z_sum / cfg.n_layers}
    return constrain(logits, "batch", "seq", "vocab"), losses


def loss_fn(params, batch, cfg: MoEConfig, mesh=None):
    """LM loss + weighted router aux losses."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        toks = batch["tokens"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
    logits, extras = apply(params, inputs, cfg, mesh)
    loss = softmax_cross_entropy(logits, targets, z_loss=cfg.z_loss)
    if "mask" in batch:
        mask = batch["mask"].astype(jnp.float32)
        lm = jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        lm = jnp.mean(loss)
    return (lm + cfg.aux_loss_weight * extras["aux"]
            + cfg.router_z_weight * extras["z"])


def num_params(cfg: MoEConfig) -> int:
    L, D, H, dh, F, V, E = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                            cfg.d_head, cfg.d_ff, cfg.vocab_size,
                            cfg.n_experts)
    per_layer = (2 * D + 3 * D * H * dh + H * dh * D + D * E
                 + 2 * E * D * F)
    total = V * D + L * per_layer + D
    if not cfg.tie_embeddings:
        total += D * V
    if cfg.pos == "learned":
        total += cfg.max_seq * D
    return total
