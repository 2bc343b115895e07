"""GPT-class transformer LM — the flagship model, TPU-first.

Pure-function + pytree design (no module framework): params are nested dicts
of jax arrays with a parallel tree of Logical axis annotations, so any mesh
shape (dp/fsdp/tp/sp/pp) shards the same code.  Layers are *stacked* on a
leading axis and scanned (`lax.scan` + `jax.checkpoint`), which keeps compile
time O(1) in depth and gives PP a natural stage axis.

Capability target: the reference runs GPT-2 via Train integrations
(reference: release/air_tests/air_benchmarks, train/examples/deepspeed/
deepspeed_torch_trainer.py fine-tunes GPT-2-class models); here the model is
in-tree and sharding-native.  BASELINE.md north star: GPT-2-medium
throughput on pods.

Supports both the GPT-2 recipe (learned positions, LayerNorm, GELU) and the
modern recipe (RoPE, RMSNorm, SwiGLU) via config flags.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import (apply_rope, attention, blockwise_attention,
                         fused_softmax_cross_entropy, gelu_mlp, layer_norm,
                         rms_norm, rope_table, softmax_cross_entropy, swiglu)
from ray_tpu.ops.attention import resolve_impl
from ray_tpu.ops.ring_attention import ring_attention_sharded
from ray_tpu.parallel.sharding import (ACTIVATION_RULES, Logical,
                                       spec_from_logical)

__all__ = [
    "GPTConfig", "logical_axes", "init", "apply", "apply_hidden", "loss_fn",
    "num_params", "generate", "sample_logits", "init_cache", "decode_step",
    "partition_stage_params", "merge_stage_trees", "stage_hidden",
    "stage_loss",
    # the served GPT-2 (the engine's model interface, serve/_engine.py)
    "cache_kinds", "init_paged_cache", "paged_decode_step", "paged_prefill",
    "copy_page", "serve_view", "step_kv_read", "kv_block_pages",
    # what the other model modules build on
    "apply_norm", "constrain", "attention_op", "qkv_of_normed", "attn_out",
    "slot_embed", "unembed_table", "cast_leaves",
]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_head: int = 64
    d_ff: int = 3072
    max_seq: int = 1024
    norm: str = "ln"          # "ln" | "rms"
    act: str = "gelu"         # "gelu" | "swiglu"
    pos: str = "learned"      # "learned" | "rope"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full" recomputes the whole block in the bwd pass; "dots" saves
    # matmul outputs and recomputes only cheap elementwise ops
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) — most
    # of no-remat's speed at a fraction of its activation memory
    remat_policy: str = "full"
    # CE over sequence chunks of this size, fusing the vocab projection
    # into the loss so [B, S, V] logits are never materialized — an
    # opt-in memory saver (peak [B, chunk, V] instead of [B, S, V]): on
    # v5e GPT-2-small@512 it measured ~1% slower than the dense path
    # (XLA already fuses the CE epilogue well), so dense is the default.
    # Ignored (dense fallback) when S isn't divisible or under sp.
    loss_chunk: Optional[int] = None
    attention_impl: str = "auto"
    # q/k/v/o projection biases (real GPT-2 checkpoints have them; our
    # from-scratch recipes don't need them)
    attn_bias: bool = False
    sp_mode: str = "ring"     # how to handle a >1 sp axis: "ring" | "none"
    z_loss: float = 1e-4
    tie_embeddings: bool = True
    num_microbatches: Optional[int] = None  # pp microbatches; default = pp
    # key positions one loop turn of the paged serve programs' attention
    # gathers and scores (whole pages of the KV arena).  One value is in
    # use, chosen from a sweep on the chip (PERF.md section 5); no
    # engine argument, flag or environment variable sets another.
    kv_block: int = 128

    @classmethod
    def gpt2_small(cls, **kw):
        return cls(n_layers=12, d_model=768, n_heads=12, d_head=64,
                   d_ff=3072, **kw)

    @classmethod
    def gpt2_medium(cls, **kw):
        return cls(n_layers=24, d_model=1024, n_heads=16, d_head=64,
                   d_ff=4096, **kw)

    @classmethod
    def gpt2_large(cls, **kw):
        return cls(n_layers=36, d_model=1280, n_heads=20, d_head=64,
                   d_ff=5120, **kw)

    @classmethod
    def gpt2_xl(cls, **kw):
        return cls(n_layers=48, d_model=1600, n_heads=25, d_head=64,
                   d_ff=6400, **kw)

    @classmethod
    def nano(cls, **kw):
        """Tiny config for tests: runs on an 8-device CPU mesh."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq", 128)
        return cls(n_layers=4, d_model=64, n_heads=4, d_head=16, d_ff=128,
                   **kw)


def logical_axes(cfg: GPTConfig) -> Dict[str, Any]:
    """Logical sharding annotations mirroring init()'s param tree."""
    lp = {
        "attn_norm": Logical("layers", None),
        "wq": Logical("layers", "embed", "heads", "head_dim"),
        "wk": Logical("layers", "embed", "heads", "head_dim"),
        "wv": Logical("layers", "embed", "heads", "head_dim"),
        "wo": Logical("layers", "heads", "head_dim", "embed"),
        "mlp_norm": Logical("layers", None),
        "mlp_out": Logical("layers", "mlp", "embed"),
    }
    if cfg.act == "swiglu":
        lp["mlp_gate"] = Logical("layers", "embed", "mlp")
        lp["mlp_up"] = Logical("layers", "embed", "mlp")
    else:
        lp["mlp_in"] = Logical("layers", "embed", "mlp")
        lp["mlp_in_b"] = Logical("layers", "mlp")
        lp["mlp_out_b"] = Logical("layers", None)
    if cfg.norm == "ln":
        lp["attn_norm_b"] = Logical("layers", None)
        lp["mlp_norm_b"] = Logical("layers", None)
    if cfg.attn_bias:
        lp["wq_b"] = Logical("layers", "heads", "head_dim")
        lp["wk_b"] = Logical("layers", "heads", "head_dim")
        lp["wv_b"] = Logical("layers", "heads", "head_dim")
        lp["wo_b"] = Logical("layers", None)
    out = {
        # vocab-only sharding: the table's lookup is a gather, and an
        # fsdp-sharded embed dim makes the partitioner emit embed-sharded
        # activations + a full reshard ("involuntary full
        # rematerialization"); vocab(tp) already gives the table a
        # sharded-storage story
        "embed": Logical("vocab", None),
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


def init(key, cfg: GPTConfig) -> Dict[str, Any]:
    """Initialize the (host or sharded — see training.init_sharded) params."""
    L, D, H, dh, F, V = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_head,
                         cfg.d_ff, cfg.vocab_size)
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 16))

    def norm_init(shape):
        return jnp.ones(shape, pd)

    def dense(rng, shape, fan_in):
        return (jax.random.normal(rng, shape, pd)
                * (1.0 / math.sqrt(fan_in)))

    lp = {
        "attn_norm": norm_init((L, D)),
        "wq": dense(next(k), (L, D, H, dh), D),
        "wk": dense(next(k), (L, D, H, dh), D),
        "wv": dense(next(k), (L, D, H, dh), D),
        # residual-branch scaling a la GPT-2 (1/sqrt(2L))
        "wo": dense(next(k), (L, H, dh, D), H * dh) / math.sqrt(2 * L),
        "mlp_norm": norm_init((L, D)),
        "mlp_out": dense(next(k), (L, F, D), F) / math.sqrt(2 * L),
    }
    if cfg.act == "swiglu":
        lp["mlp_gate"] = dense(next(k), (L, D, F), D)
        lp["mlp_up"] = dense(next(k), (L, D, F), D)
    else:
        lp["mlp_in"] = dense(next(k), (L, D, F), D)
        lp["mlp_in_b"] = jnp.zeros((L, F), pd)
        lp["mlp_out_b"] = jnp.zeros((L, D), pd)
    if cfg.norm == "ln":
        lp["attn_norm_b"] = jnp.zeros((L, D), pd)
        lp["mlp_norm_b"] = jnp.zeros((L, D), pd)
    if cfg.attn_bias:
        lp["wq_b"] = jnp.zeros((L, H, dh), pd)
        lp["wk_b"] = jnp.zeros((L, H, dh), pd)
        lp["wv_b"] = jnp.zeros((L, H, dh), pd)
        lp["wo_b"] = jnp.zeros((L, D), pd)
    params = {
        "embed": jax.random.normal(next(k), (V, D), pd) * 0.02,
        "layers": lp,
        "final_norm": norm_init((D,)),
    }
    if cfg.norm == "ln":
        params["final_norm_b"] = jnp.zeros((D,), pd)
    if cfg.pos == "learned":
        params["pos_embed"] = jax.random.normal(next(k), (cfg.max_seq, D),
                                                pd) * 0.01
    if not cfg.tie_embeddings:
        params["unembed"] = dense(next(k), (D, V), D)
    return params


def apply_norm(x, w, b, kind):
    if kind == "rms":
        return rms_norm(x, w)
    return layer_norm(x, w, b)


def constrain(x, *axes):
    """Activation sharding constraint (ACTIVATION_RULES: fsdp stays on
    the batch dim — params' embed-dim fsdp sharding is gathered on use,
    never propagated onto activations)."""
    try:
        return jax.lax.with_sharding_constraint(
            x, spec_from_logical(axes, ACTIVATION_RULES))
    except Exception:
        return x  # outside jit / no mesh context


def attention_op(q, k, v, cfg: GPTConfig, mesh, allow_manual: bool = True):
    """Pick the attention path: ring over sp when the mesh has an sp axis,
    otherwise flash/blockwise on the whole (possibly sharded) arrays.

    The sp region is *partial-manual* shard_map (axis_names={'sp'}): dp/tp
    stay automatic.  The Pallas kernel is a Mosaic custom call that GSPMD
    cannot partition, so on a mesh of more than one device it runs inside
    a (fully manual) shard_map over the batch axes and the head axis —
    each device runs the kernel on its own [B/n, H/tp, S, dh] shard.
    Inside the pp pipeline region (allow_manual=False) shardy cannot nest
    another manual region, so attention falls back to the GSPMD-
    partitionable XLA path there (exact, all-gathers KV over sp)."""
    from jax import shard_map

    if (allow_manual and mesh is not None and mesh.shape.get("sp", 1) > 1
            and cfg.sp_mode == "ring"):
        from jax.sharding import PartitionSpec as P

        spec = P(None, None, "sp", None)
        fn = lambda q_, k_, v_: ring_attention_sharded(
            q_, k_, v_, "sp", causal=True)
        # mesh=None -> ambient context mesh, so this nests inside the pp
        # pipeline's manual region (whose context mesh has pp already Manual)
        return shard_map(fn, check_vma=False,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=frozenset({"sp"}))(q, k, v)
    impl = resolve_impl(cfg.attention_impl, q.shape[-2], k.shape[-2], True)
    if impl.startswith("pallas") and mesh is not None and mesh.size > 1:
        spec = spec_from_logical(("batch", "heads", None, None),
                                 ACTIVATION_RULES, mesh)
        if allow_manual and all(d % _ways(mesh, entry) == 0
                                for d, entry in zip(q.shape, spec)):
            fn = lambda q_, k_, v_: attention(q_, k_, v_, causal=True,
                                              impl=impl)
            return shard_map(fn, check_vma=False, mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
        impl = "xla"
    return attention(q, k, v, causal=True, impl=impl)


def _ways(mesh, entry) -> int:
    """How many ways one PartitionSpec entry splits its dimension."""
    names = () if entry is None else \
        (entry,) if isinstance(entry, str) else entry
    return math.prod(mesh.shape[a] for a in names)


def qkv_of_normed(h, layer, cfg):
    """Q, K and V of an already normed input h [B, S, D]: [B, H, S, dh]
    each (K and V with as many heads as `wk` / `wv` hold: fewer than Q
    under grouped-query attention), biases where the config has them, no
    position signal.  Shared by every block recipe of the family."""
    q = jnp.einsum("bsd,dhk->bhsk", h, layer["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", h, layer["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", h, layer["wv"].astype(cfg.dtype))
    if cfg.attn_bias:
        q = q + layer["wq_b"].astype(cfg.dtype)[None, :, None]
        k = k + layer["wk_b"].astype(cfg.dtype)[None, :, None]
        v = v + layer["wv_b"].astype(cfg.dtype)[None, :, None]
    return q, k, v


def _qkv_proj(x, layer, cfg: GPTConfig, rope, positions=None):
    """Pre-norm + QKV projection + rope — the one source of truth shared
    by the training forward and the KV-cache decode path (a recipe tweak
    made in only one of them would silently break decode==forward
    parity, which test_gpt_decode_matches_full_forward enforces)."""
    h = apply_norm(x, layer["attn_norm"], layer.get("attn_norm_b"),
                   cfg.norm)
    q, k, v = qkv_of_normed(h.astype(cfg.dtype), layer, cfg)
    if rope is not None:
        q = apply_rope(q, *rope, positions=positions)
        k = apply_rope(k, *rope, positions=positions)
    return q, k, v


def attn_out(o, layer, cfg):
    """Attention's output projection: o [B, H, S, dh] -> [B, S, D]."""
    att = jnp.einsum("bhsk,hkd->bsd", o, layer["wo"].astype(cfg.dtype))
    if cfg.attn_bias:
        att = att + layer["wo_b"].astype(cfg.dtype)
    return att


def _attn_out_and_mlp(x, o, layer, cfg: GPTConfig):
    """Output projection + residual + MLP sublayer (shared, see
    _qkv_proj)."""
    x = x + attn_out(o, layer, cfg)
    h2 = apply_norm(x, layer["mlp_norm"], layer.get("mlp_norm_b"),
                    cfg.norm)
    h2 = h2.astype(cfg.dtype)
    if cfg.act == "swiglu":
        m = swiglu(h2, layer["mlp_gate"].astype(cfg.dtype),
                   layer["mlp_up"].astype(cfg.dtype),
                   layer["mlp_out"].astype(cfg.dtype))
    else:
        m = gelu_mlp(h2, layer["mlp_in"].astype(cfg.dtype),
                     layer["mlp_in_b"].astype(cfg.dtype),
                     layer["mlp_out"].astype(cfg.dtype),
                     layer["mlp_out_b"].astype(cfg.dtype))
    return x + m


def _scan_blocks(x, layers, cfg: GPTConfig, rope, mesh=None,
                 allow_manual: bool = True):
    """Scan a (stacked) layer slice over x — the one block recipe shared
    by the full SPMD forward, the SPMD pp stage_fn, and the MPMD
    per-stage programs (parallel/mpmd.py), so every pipelining story
    computes bit-for-bit the same math as the reference stack."""

    def block(x, layer):
        q, k, v = _qkv_proj(x, layer, cfg, rope)
        q = constrain(q, "batch", "heads", "seq", "head_dim")
        k = constrain(k, "batch", "heads", "seq", "head_dim")
        v = constrain(v, "batch", "heads", "seq", "head_dim")
        o = attention_op(q, k, v, cfg, mesh, allow_manual=allow_manual)
        x = _attn_out_and_mlp(x, o, layer, cfg)
        return constrain(x, "batch", "seq", "embed")

    def scan_body(x, layer):
        if cfg.remat:
            policy = (jax.checkpoint_policies
                      .dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else None)
            x = jax.checkpoint(block, policy=policy)(x, layer)
        else:
            x = block(x, layer)
        return x, None

    x, _ = jax.lax.scan(scan_body, x, layers)
    return x


def apply_hidden(params, tokens, cfg: GPTConfig, mesh=None):
    """Transformer stack up to (and including) the final norm: tokens
    [B, S] int32 -> hidden [B, S, D].  The vocab projection is split out
    so loss_fn can fuse it into a chunked CE that never materializes the
    [B, S, V] logits (see ops/layers.py fused_softmax_cross_entropy)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:S][None].astype(cfg.dtype)
        rope = None
    else:
        rope = rope_table(S, cfg.d_head, dtype=jnp.float32)
    x = constrain(x, "batch", "seq", "embed")
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1

    if pp > 1:
        from ray_tpu.parallel.pipeline import (merge_microbatches,
                                               pipeline_apply,
                                               split_microbatches)

        if cfg.n_layers % pp:
            raise ValueError(f"n_layers {cfg.n_layers} not divisible by "
                             f"pp {pp}")
        M = cfg.num_microbatches or pp

        def stage_fn(stage_layers, xm):
            return _scan_blocks(xm, stage_layers, cfg, rope, mesh,
                                allow_manual=False)

        stacked = jax.tree.map(
            lambda p: p.reshape(pp, cfg.n_layers // pp, *p.shape[1:]),
            params["layers"])
        x = merge_microbatches(
            pipeline_apply(stage_fn, stacked, split_microbatches(x, M), mesh))
    else:
        x = _scan_blocks(x, params["layers"], cfg, rope, mesh,
                         allow_manual=True)
    x = apply_norm(x, params["final_norm"], params.get("final_norm_b"),
                   cfg.norm)
    return x


# ---------------------------------------------------------------------------
# MPMD pipeline partitioning (parallel/mpmd.py).  Unlike the SPMD pp path
# above — ONE compiled program where every rank holds every stage's
# schedule — these helpers slice the model into per-stage param trees and
# per-stage forward programs, each compiled alone on its own worker gang,
# so model depth is no longer capped by what a single program can hold.


def partition_stage_params(params, cfg: GPTConfig, stages: int):
    """Slice init()'s tree into `stages` contiguous per-stage trees.

    Stage 0 owns embed (+ learned positions); the last stage owns the
    final norm and the vocab projection.  With tied embeddings BOTH end
    stages hold the table (stage 0 for lookup, the last for unembed) —
    parallel/mpmd.py keeps the two copies identical by exchanging embed
    grads between them every step."""
    if cfg.n_layers % stages:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by "
                         f"stages {stages}")
    per = cfg.n_layers // stages
    out = []
    for s in range(stages):
        st = {"layers": jax.tree.map(lambda p: p[s * per:(s + 1) * per],
                                     params["layers"])}
        if s == 0:
            st["embed"] = params["embed"]
            if cfg.pos == "learned":
                st["pos_embed"] = params["pos_embed"]
        if s == stages - 1:
            st["final_norm"] = params["final_norm"]
            if cfg.norm == "ln":
                st["final_norm_b"] = params["final_norm_b"]
            if cfg.tie_embeddings:
                st.setdefault("embed", params["embed"])
            else:
                st["unembed"] = params["unembed"]
        out.append(st)
    return out


def merge_stage_trees(stage_trees, cfg: GPTConfig, grads: bool = False,
                      tie_summed: bool = False):
    """Inverse of partition_stage_params: reassemble the full tree.

    For params (grads=False) the tied embed copies are identical and
    stage 0's is taken; for grads (grads=True) the two ends' partials
    are SUMMED — the chain-rule contributions of the lookup and the
    unembed projection to the one shared table.  When the pipeline has
    already run its tied-embed exchange both copies hold the total
    (tie_summed=True): take one instead of double-counting."""
    stages = len(stage_trees)
    first, last = stage_trees[0], stage_trees[-1]
    out = {"layers": jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                  *[t["layers"] for t in stage_trees])}
    out["embed"] = first["embed"]
    if grads and cfg.tie_embeddings and stages > 1 and not tie_summed:
        out["embed"] = out["embed"] + last["embed"]
    if cfg.pos == "learned":
        out["pos_embed"] = first["pos_embed"]
    out["final_norm"] = last["final_norm"]
    if cfg.norm == "ln":
        out["final_norm_b"] = last["final_norm_b"]
    if not cfg.tie_embeddings:
        out["unembed"] = last["unembed"]
    return out


def stage_hidden(stage_params, x, cfg: GPTConfig, stage: int, stages: int):
    """One MPMD stage's forward: tokens [B, S] (stage 0) or hidden
    [B, S, D] -> hidden [B, S, D] (final-normed on the last stage)."""
    if stage == 0:
        S = x.shape[1]
        h = stage_params["embed"][x].astype(cfg.dtype)
        if cfg.pos == "learned":
            h = h + stage_params["pos_embed"][:S][None].astype(cfg.dtype)
    else:
        S = x.shape[1]
        h = x.astype(cfg.dtype)
    rope = (None if cfg.pos == "learned"
            else rope_table(S, cfg.d_head, dtype=jnp.float32))
    h = _scan_blocks(h, stage_params["layers"], cfg, rope, mesh=None)
    if stage == stages - 1:
        h = apply_norm(h, stage_params["final_norm"],
                       stage_params.get("final_norm_b"), cfg.norm)
    return h


def stage_loss(stage_params, x, targets, cfg: GPTConfig, stage: int,
               stages: int):
    """Last-stage forward + next-token CE (mean over this microbatch;
    with equal microbatch sizes the mean-of-means equals loss_fn's
    global mean, which the MPMD<->SPMD parity tests pin down)."""
    h = stage_hidden(stage_params, x, cfg, stage, stages)
    table = (stage_params["embed"].T if cfg.tie_embeddings
             else stage_params["unembed"]).astype(cfg.dtype)
    logits = jnp.einsum("bsd,dv->bsv", h.astype(cfg.dtype), table)
    return jnp.mean(softmax_cross_entropy(logits, targets,
                                          z_loss=cfg.z_loss))


def unembed_table(params, cfg: GPTConfig):
    return (params["embed"].T if cfg.tie_embeddings
            else params["unembed"]).astype(cfg.dtype)


def apply(params, tokens, cfg: GPTConfig, mesh=None):
    """Forward pass: tokens [B, S] int32 -> logits [B, S, V]."""
    x = apply_hidden(params, tokens, cfg, mesh)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cfg.dtype),
                        unembed_table(params, cfg))
    return constrain(logits, "batch", "seq", "vocab")


def loss_fn(params, batch, cfg: GPTConfig, mesh=None):
    """Next-token LM loss.  batch: {"tokens": [B, S+1]} or
    {"inputs","targets"}."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        toks = batch["tokens"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
    chunk = cfg.loss_chunk
    sp = 1 if mesh is None else mesh.shape.get("sp", 1)
    if chunk and targets.shape[1] % chunk == 0 and sp == 1:
        # fused path: chunk over the (locally whole) sequence axis —
        # with an sp axis the sequence is device-sharded, so slicing it
        # host-side would gather; fall back to dense there
        x = apply_hidden(params, inputs, cfg, mesh)
        loss = fused_softmax_cross_entropy(
            x.astype(cfg.dtype), unembed_table(params, cfg), targets,
            z_loss=cfg.z_loss, chunk=chunk)
    else:
        logits = apply(params, inputs, cfg, mesh)
        loss = softmax_cross_entropy(logits, targets, z_loss=cfg.z_loss)
    if "mask" in batch:
        mask = batch["mask"].astype(jnp.float32)
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)


# ---------------------------------------------------------------------------
# KV-cache decoding (inference).  The reference delegates generation to
# torch/vLLM; here decode is a first-class jit program: per-layer KV
# buffers carried through a lax.scan over the stacked layer params, one
# dynamic_update_slice per step — static shapes throughout, so the whole
# generate loop compiles once for a given (batch, max_seq).


def init_cache(cfg: GPTConfig, batch: int, max_seq: Optional[int] = None
               ) -> Dict[str, Any]:
    """Empty KV cache: [L, B, H, max_seq, d_head] per side + a scalar
    write position."""
    S = max_seq or cfg.max_seq
    shape = (cfg.n_layers, batch, cfg.n_heads, S, cfg.d_head)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _decode_hidden(params, cache, tokens, cfg: GPTConfig, rope=None):
    """One decode position through the stack: tokens [B] at position
    cache['pos'] -> (final-norm hidden [B, D], updated cache).  The
    layer recipe is the shared _qkv_proj/_attn_out_and_mlp (identical to
    the training forward); only the attention inner product runs against
    the cache with a position mask.  `rope` may be precomputed by the
    caller (generate hoists it out of its scans)."""
    S = cache["k"].shape[3]
    pos = cache["pos"]
    x = params["embed"][tokens].astype(cfg.dtype)          # [B, D]
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], pos, axis=0)[None].astype(
            cfg.dtype)
        rope = None
    elif rope is None:
        rope = rope_table(S, cfg.d_head, dtype=jnp.float32)
    x = x[:, None]                                         # [B, 1, D]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
            <= pos)                                        # causal @ pos

    def block(x, inp):
        layer, kc, vc = inp                                # kc/vc [B,H,S,Dh]
        q, k, v = _qkv_proj(x, layer, cfg, rope, positions=pos[None])
        kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, pos, 0))
        s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                       kc.astype(jnp.float32)) * (cfg.d_head ** -0.5)
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bhsk->bhqk", p.astype(cfg.dtype), vc)
        return _attn_out_and_mlp(x, o, layer, cfg), (kc, vc)

    # full unroll: a rolled scan at decode shapes ([B, D] operands) is
    # dominated by per-op fixed cost and blocks cross-layer fusion —
    # unrolling the 12-layer stack measured +55% decode steps/s on v5e
    # (786 -> 1219 at B=8, gpt2-small)
    x, (k_new, v_new) = jax.lax.scan(
        block, x, (params["layers"], cache["k"], cache["v"]),
        unroll=cfg.n_layers)
    x = apply_norm(x, params["final_norm"], params.get("final_norm_b"),
                   cfg.norm)
    return x[:, 0], {"k": k_new, "v": v_new, "pos": pos + 1}


def decode_step(params, cache, tokens, cfg: GPTConfig, rope=None):
    """One decode position: tokens [B] int32 at position cache['pos'] ->
    (logits [B, V], updated cache)."""
    x, cache = _decode_hidden(params, cache, tokens, cfg, rope)
    logits = jnp.einsum("bd,dv->bv", x.astype(cfg.dtype),
                        unembed_table(params, cfg))
    return logits, cache


def _decode_fast_eligible(cfg: GPTConfig) -> bool:
    # the fast path hand-writes the GPT-2-family recipe; other variants
    # (rope/rms/swiglu) take the generic shared-recipe path
    return cfg.norm == "ln" and cfg.act == "gelu" and cfg.pos == "learned"


def _decode_view(params, cfg: GPTConfig):
    """Decode-optimized view of the param tree: compute-dtype weights
    (decode re-reads every weight every step, so storing f32 and
    casting per use would double the HBM traffic that bounds the loop)
    and the q/k/v projections fused into one [D, 3*H*dh] matmul per
    layer.  Built INSIDE the jitted generate call — one pass over the
    weights, amortized across all decode steps."""
    L, D, H, dh = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_head
    lp = params["layers"]
    dt = cfg.dtype

    def f(w):
        return w.astype(dt)

    view = {
        "embed": f(params["embed"]),
        "pos_embed": f(params["pos_embed"]),
        "wqkv": jnp.concatenate([f(lp["wq"]).reshape(L, D, H * dh),
                                 f(lp["wk"]).reshape(L, D, H * dh),
                                 f(lp["wv"]).reshape(L, D, H * dh)], -1),
        "wo": f(lp["wo"]).reshape(L, H * dh, D),
        "attn_norm": lp["attn_norm"], "attn_norm_b": lp["attn_norm_b"],
        "mlp_norm": lp["mlp_norm"], "mlp_norm_b": lp["mlp_norm_b"],
        "mlp_in": f(lp["mlp_in"]), "mlp_in_b": f(lp["mlp_in_b"]),
        "mlp_out": f(lp["mlp_out"]), "mlp_out_b": f(lp["mlp_out_b"]),
        "final_norm": params["final_norm"],
        "final_norm_b": params.get("final_norm_b"),
    }
    if cfg.attn_bias:
        view["bqkv"] = jnp.concatenate(
            [f(lp["wq_b"]).reshape(L, H * dh),
             f(lp["wk_b"]).reshape(L, H * dh),
             f(lp["wv_b"]).reshape(L, H * dh)], -1)
        view["wo_b"] = f(lp["wo_b"])
    view["unembed"] = (view["embed"].T if cfg.tie_embeddings
                       else f(params["unembed"]))
    return view


def _decode_hidden_fast(view, cfg: GPTConfig, kcache, vcache, pos, toks):
    """One decode position on the view: toks [B] -> (final-norm hidden
    [B, D], kcache, vcache).  Python-unrolled layer loop (decode-shape
    ops are fixed-cost-dominated; a rolled scan also blocks cross-layer
    fusion), cache layout [L, B, H, S, dh] (a seq-major layout measured
    ~40% SLOWER on v5e: strided attention reads cost more than the
    scattered single-position writes)."""
    B = toks.shape[0]
    L, H, dh = cfg.n_layers, cfg.n_heads, cfg.d_head
    S = kcache.shape[3]
    x = view["embed"][toks] + view["pos_embed"][pos][None]      # [B, D]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, S), 2) <= pos)
    for l in range(L):
        h = layer_norm(x, view["attn_norm"][l],
                       view["attn_norm_b"][l]).astype(cfg.dtype)
        qkv = h @ view["wqkv"][l]                               # [B, 3Hd]
        if cfg.attn_bias:
            qkv = qkv + view["bqkv"][l]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        knew = k.reshape(B, H, dh)[:, :, None].astype(kcache.dtype)
        vnew = v.reshape(B, H, dh)[:, :, None].astype(vcache.dtype)
        kcache = jax.lax.dynamic_update_slice(kcache, knew[None],
                                              (l, 0, 0, pos, 0))
        vcache = jax.lax.dynamic_update_slice(vcache, vnew[None],
                                              (l, 0, 0, pos, 0))
        q = q.reshape(B, H, dh)
        s = jnp.einsum("bhk,bhsk->bhs", q.astype(jnp.float32),
                       kcache[l].astype(jnp.float32)) * (dh ** -0.5)
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        vc = vcache[l]
        if vc.dtype != cfg.dtype:
            vc = vc.astype(cfg.dtype)
        o = jnp.einsum("bhs,bhsk->bhk", p.astype(cfg.dtype), vc)
        att = o.reshape(B, H * dh) @ view["wo"][l]
        if cfg.attn_bias:
            att = att + view["wo_b"][l]
        x = x + att
        h2 = layer_norm(x, view["mlp_norm"][l],
                        view["mlp_norm_b"][l]).astype(cfg.dtype)
        m = jax.nn.gelu(h2 @ view["mlp_in"][l] + view["mlp_in_b"][l])
        x = x + (m @ view["mlp_out"][l] + view["mlp_out_b"][l])
    x = layer_norm(x, view["final_norm"], view["final_norm_b"])
    return x.astype(cfg.dtype), kcache, vcache


# ---------------------------------------------------------------------------
# Slot-batch decoding (continuous batching).  The serving engine keeps a
# fixed-shape batch of B "slots"; sequences join at prefill and leave at
# EOS/max-tokens, so every slot sits at its OWN position.  One cache
# layout is served: a device arena of fixed-size pages [L, P, ps, H * dh]
# plus per-slot page tables, read inside the programs block by block as
# far as the contexts are live (_paged_attention).  Page 0 is reserved as
# the null page: inactive slots write there and their outputs are
# discarded host-side, so the compiled step program never changes shape
# as sequences come and go.  The plain recipe the paged programs are held
# to — a contiguous row a slot, every position scored at once — is
# tests/slot_reference.py.
#
# A sequence joins through a prefill program (paged_prefill): its padded
# prompt chunk in ONE pass through the layers (_prefill_chunk), T query
# rows through the attention that a decode step feeds one row a slot.
#
# Every program takes the cache of ALL layers and hands it back: it is
# the layer loop's carry, a layer scatters its new rows at [l, ...] and
# reads its own slab at [l], and nothing else of it is touched.  The
# serve engine donates the cache to each of these programs, so the rows
# are written into the buffer it came in.  (Not a scan's xs -> ys: that
# slices each layer's slab out and stacks it back, and the whole cache
# moves to write a few rows.)


def _slot_rope(x, cos, sin, positions):
    """Per-slot rotary embedding: x [B, H, T, dh], positions [B, T] (each
    batch row at its own positions, unlike ops.apply_rope whose
    positions are shared across the batch)."""
    c = cos[positions][:, None]                 # [B, 1, T, dh/2]
    sn = sin[positions][:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn],
                           axis=-1).astype(x.dtype)


def slot_embed(params, tokens, pos, cfg: GPTConfig):
    """tokens [B, T] at positions pos [B, T] -> x [B, T, D]."""
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][pos].astype(cfg.dtype)  # per-slot rows
    return x


def _numbered(layers, cfg: GPTConfig):
    """The stacked layers as a scan's xs, each with its index into the
    stacked cache."""
    return layers, jnp.arange(cfg.n_layers, dtype=jnp.int32)


def _slot_qkv(x, layer, cfg: GPTConfig, rope, pos):
    q, k, v = _qkv_proj(x, layer, cfg, rope=None)
    if rope is not None:
        q = _slot_rope(q, *rope, positions=pos)
        k = _slot_rope(k, *rope, positions=pos)
    return q, k, v


def _prefill_chunk(params, kcache, vcache, toks, start, last_idx, S,
                   write, attend, cfg: GPTConfig, rope=None):
    """One pass of a padded prompt chunk through the stack, the cache's
    layout its caller's (paged_prefill here, the contiguous reference of
    tests/slot_reference.py): toks [T] sit at positions start..start+T-1 of
    ONE sequence whose cache holds S positions; logits are taken at row
    `last_idx` (the last REAL prompt token).  Per layer l the chunk's T
    rows of K and V are written with one `write(c, l, rows [T,H,dh],
    wpos)` a side, then the chunk attends to what the cache held before
    `start` and to itself: `attend(q [1,H,T,dh], kc, vc, l, pos [1,T])`
    -> [1,H,T,dh], the layout's own reading of layer l under the causal
    mask by position.  kcache/vcache are stacked per layer on axis 0 and
    are the layer loop's carry: `write` scatters into them.

    Pad rows sit behind every real token, so no real query sees them;
    their K/V land where decode overwrites before it attends, and a row
    at or past S (a chunk padded over the end of the cache) must be
    written nowhere a real row lives: `write` sees the unclamped wpos.
    Returns (logits [V], kcache, vcache)."""
    T = toks.shape[0]
    wpos = start + jnp.arange(T, dtype=jnp.int32)
    pos = jnp.minimum(wpos, S - 1)[None]                   # [1, T]
    if cfg.pos == "learned":
        rope = None
    elif rope is None:
        rope = rope_table(S, cfg.d_head, dtype=jnp.float32)
    x = slot_embed(params, toks[None], pos, cfg)           # [1, T, D]

    def block(carry, inp):
        x, kc, vc = carry
        layer, l = inp
        q, k, v = _slot_qkv(x, layer, cfg, rope, pos)      # [1, H, T, dh]
        kc = write(kc, l, jnp.swapaxes(k[0], 0, 1).astype(kc.dtype), wpos)
        vc = write(vc, l, jnp.swapaxes(v[0], 0, 1).astype(vc.dtype), wpos)
        o = attend(q, kc, vc, l, pos)
        return (_attn_out_and_mlp(x, o, layer, cfg), kc, vc), None

    # a rolled scan: at T rows a layer the matmuls dwarf the per-op
    # fixed cost that makes the decode step unroll, and the program
    # stays one layer long to compile and to load
    (x, k_new, v_new), _ = jax.lax.scan(
        block, (x, kcache, vcache), _numbered(params["layers"], cfg))
    x = jax.lax.dynamic_index_in_dim(x[0], last_idx, 0, keepdims=False)
    x = apply_norm(x, params["final_norm"], params.get("final_norm_b"),
                   cfg.norm)
    logits = jnp.einsum("d,dv->v", x.astype(cfg.dtype),
                        unembed_table(params, cfg))
    return logits, k_new, v_new


# -- paged variant ----------------------------------------------------------


def cache_kinds(cfg: GPTConfig) -> Dict[str, Optional[int]]:
    """The kinds of KV state this model's layers keep, as the serving
    engine's page pools: name -> window (None: every position is kept).
    Every layer here attends to the whole context, so there is one pool
    and one page table a sequence.  The engine hands a model its page
    counts and page tables keyed by these names; a one-kind model also
    takes them bare (`_only`)."""
    return {"full": None}


def _only(x):
    return x["full"] if isinstance(x, dict) else x


def init_paged_cache(cfg: GPTConfig, num_pages, page_size: int
                     ) -> Dict[str, Any]:
    """Paged KV arena: [L, num_pages, page_size, H * d_head] per side (a
    position's heads lie together, as one row: the shape the chip keeps
    as it is written, unpadded, and the order its compiler wants for the
    row scatter and the page gather; given [.., H, page_size, d_head] it
    stored the PAGE axis innermost, at twice the bytes, and every
    program converted the whole arena on the way in and on the way out).
    Page 0 is the reserved null page (inactive-slot writes land there;
    the allocator never hands it out).  The programs below scatter their
    rows into it and read a slot's live pages out of it where it stands,
    a block of pages a loop turn (_paged_attention)."""
    num_pages = _only(num_pages)
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_heads * cfg.d_head)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def kv_block_pages(cfg: GPTConfig, page_size: int, max_pages: int) -> int:
    """Pages of a slot's table that one loop turn of _paged_attention
    reads: cfg.kv_block positions' worth, whole pages, at most the
    table."""
    return max(1, min(cfg.kv_block // page_size, max_pages))


def _live_blocks(last_pos, block: int):
    """Key blocks of `block` positions from 0 to the one that holds
    position `last_pos` (a number on the host, a traced scalar in a
    program: the two count alike)."""
    return last_pos // block + 1


def step_kv_read(cfg: GPTConfig, pos, page_size: int, max_pages: int):
    """The host's side of a decode step's trip count, for the engine's
    iteration record: (key positions a layer's attention reads in a step
    at per-slot positions `pos` [B] — slots x live blocks x block — and
    the span of the page tables, slots x max_pages x page_size, which
    is what a gather of every slot's whole table read)."""
    block = kv_block_pages(cfg, page_size, max_pages) * page_size
    span = max_pages * page_size
    n = _live_blocks(min(int(pos.max()), span - 1), block)
    return len(pos) * n * block, len(pos) * span


def _live_table(ptab, last_pos, ps: int, cfg: GPTConfig):
    """What _paged_attention's loop walks: page tables ptab [B, maxp]
    padded with the null page to whole blocks [B, n * npb], the pages a
    block npb, and the blocks that are live up to position `last_pos`
    (a traced scalar)."""
    maxp = ptab.shape[1]
    npb = kv_block_pages(cfg, ps, maxp)
    return (jnp.pad(ptab, ((0, 0), (0, -maxp % npb))), npb,
            _live_blocks(last_pos, npb * ps))


def _paged_attention(q, kc, vc, l, live, pos, cfg: GPTConfig):
    """q [B,H,T,dh] at positions pos [B,T] against layer l of the page
    arena kc/vc [L,P,ps,H*dh] through `live` (_live_table: the block
    table, npb, n_blocks), causal by position (key <= pos[b, t]) ->
    [B,H,T,dh].  The ONE attention of both paged programs: a decode
    step is its T = 1 case, a prefill its B = 1 case.

    The keys are read where they stand and only as far as they are
    live: a loop of n_blocks turns (traced: up to the block that holds
    the furthest query position) gathers npb pages a slot a turn,
    scores them and folds them into an online softmax — the mask and
    the sums of the plain recipe (tests/slot_reference.py), taken block
    by block (statistics f32, the values' weights cfg.dtype).  A block
    is left out only if every key in it lies past every query.

    A gathered block stays [B, npb*ps, H*dh], the arena's own row: dh
    = 64 as a minor dimension pads to the chip's 128 lanes, so nothing
    of a block's size is split by heads.  The heads are contracted on
    the unsplit row instead: the query is laid out block-diagonally
    ([B, T*H, H*dh], head h's dh values in its own columns, zeros
    elsewhere), so one product with the block gives every head's scores;
    the weighted values come out [B, T*H, H*dh] and each head keeps its
    own columns.  K goes in as stored (bf16 x bf16 products are exact
    in the f32 they accumulate in), never as an f32 copy."""
    B, H, T, dh = q.shape
    tab, npb, n_blocks = live
    HD, ps = H * dh, kc.shape[2]
    Sb = npb * ps
    own = (jnp.arange(HD, dtype=jnp.int32)[None] // dh
           == jnp.arange(H, dtype=jnp.int32)[:, None])     # [H, HD]
    qrow = jnp.swapaxes(q, 1, 2).reshape(B, T, 1, HD)
    qbd = jnp.where(own, qrow, 0).reshape(B, T * H, HD)
    scale = dh ** -0.5

    def body(i, carry):
        m, den, acc = carry                    # [B,T,H] x2, [B,T,HD], f32
        t = jax.lax.dynamic_slice_in_dim(tab, i * npb, npb, 1)
        kb = kc[l, t].reshape(B, Sb, HD)
        vb = vc[l, t].reshape(B, Sb, HD).astype(cfg.dtype)
        s = jnp.einsum("bqk,bsk->bqs", qbd, kb,
                       preferred_element_type=jnp.float32)
        s = s.reshape(B, T, H, Sb) * scale
        kpos = i * Sb + jnp.arange(Sb, dtype=jnp.int32)
        ok = (kpos <= pos[:, :, None])[:, :, None]         # [B,T,1,Sb]
        m_new = jnp.maximum(m, jnp.max(jnp.where(ok, s, -1e30), axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        den = den * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqs,bsk->bqk",
                        p.reshape(B, T * H, Sb).astype(cfg.dtype), vb,
                        preferred_element_type=jnp.float32)
        # acc * corr + pv, head h in its own columns of the row
        acc = jnp.sum(jnp.where(own, acc[:, :, None] * corr[..., None]
                                + pv.reshape(B, T, H, HD), 0.0), axis=2)
        return m_new, den, acc

    init = (jnp.full((B, T, H), -1e30, jnp.float32),
            jnp.zeros((B, T, H), jnp.float32),
            jnp.zeros((B, T, HD), jnp.float32))
    _, den, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    # every query sees key 0, so den > 0
    o = jnp.sum(jnp.where(own, acc[:, :, None] / den[..., None], 0.0),
                axis=2).astype(cfg.dtype)
    return jnp.swapaxes(o.reshape(B, T, H, dh), 1, 2)


def _paged_decode_hidden(params, kpages, vpages, tokens, ptab, pos,
                         cfg: GPTConfig, rope=None):
    """One decode position for every slot against the page arena:
    tokens [B], ptab [B, max_pages] (page ids in sequence order; unused
    entries 0), pos [B] -> (hidden [B, D], kpages, vpages).  Writes
    scatter into each slot's current page; attention reads the pages
    where they stand, block by block up to the block that holds the
    furthest slot's position (_paged_attention): the same mask and the
    same sums as the contiguous reference (tests/slot_reference.py), to
    which the tests hold it."""
    B = tokens.shape[0]
    ps = kpages.shape[2]
    S = ptab.shape[1] * ps
    pos = jnp.minimum(pos, S - 1)
    if cfg.pos == "learned":
        rope = None
    elif rope is None:
        rope = rope_table(S, cfg.d_head, dtype=jnp.float32)
    qpos = pos[:, None]                        # one query row a slot
    x = slot_embed(params, tokens[:, None], qpos, cfg)      # [B, 1, D]
    pidx = jnp.take_along_axis(ptab, (pos // ps)[:, None], axis=1)[:, 0]
    poff = pos % ps
    live = _live_table(ptab, jnp.max(pos), ps, cfg)

    def block(carry, inp):
        x, kc, vc = carry                      # kc/vc [L, P, ps, H * dh]
        layer, l = inp
        q, k, v = _slot_qkv(x, layer, cfg, rope, qpos)      # [B, H, 1, dh]
        kc = kc.at[l, pidx, poff].set(k.reshape(B, -1).astype(kc.dtype))
        vc = vc.at[l, pidx, poff].set(v.reshape(B, -1).astype(vc.dtype))
        o = _paged_attention(q, kc, vc, l, live, qpos, cfg)
        return (_attn_out_and_mlp(x, o, layer, cfg), kc, vc), None

    (x, k_new, v_new), _ = jax.lax.scan(
        block, (x, kpages, vpages), _numbered(params["layers"], cfg),
        unroll=cfg.n_layers)
    x = apply_norm(x, params["final_norm"], params.get("final_norm_b"),
                   cfg.norm)
    return x[:, 0], k_new, v_new


def paged_decode_step(params, cache, tokens, ptab, pos, cfg: GPTConfig,
                      rope=None):
    """Slot-batch decode on the paged cache: -> (logits [B, V], cache)."""
    x, k_new, v_new = _paged_decode_hidden(params, cache["k"], cache["v"],
                                           tokens, _only(ptab), pos, cfg,
                                           rope)
    logits = jnp.einsum("bd,dv->bv", x.astype(cfg.dtype),
                        unembed_table(params, cfg))
    return logits, {"k": k_new, "v": v_new}


def paged_prefill(params, cache, toks, ptab_row, start, last_idx,
                  cfg: GPTConfig, rope=None):
    """Prefill one sequence's pages: the padded chunk toks [T], starting
    at position `start` (positions before `start` are prefix-shared
    pages already holding valid K/V), goes through the layers in one
    pass (_prefill_chunk); logits at chunk row `last_idx`.  A row's K/V
    scatter into page ptab_row[p // ps] at offset p % ps: pad rows reach
    this sequence's own later pages or, past its allocation and past
    the end of the table, the null page 0 — never another sequence's.
    The chunk attends through _paged_attention, block by block up to
    the block that holds its last row.  Returns (logits [V], cache)."""
    ptab_row = _only(ptab_row)
    ps = cache["k"].shape[2]
    S = ptab_row.shape[0] * ps

    def write(c, l, rows, wpos):               # c [L, P, ps, H * dh]
        pidx = ptab_row.at[wpos // ps].get(mode="fill", fill_value=0)
        return c.at[l, pidx, wpos % ps].set(rows.reshape(rows.shape[0], -1))

    live = _live_table(ptab_row[None],
                       jnp.minimum(start + toks.shape[0], S) - 1, ps, cfg)

    def attend(q, kc, vc, l, pos):             # up to the chunk's last row
        return _paged_attention(q, kc, vc, l, live, pos, cfg)

    logits, kc, vc = _prefill_chunk(
        params, cache["k"], cache["v"], toks, start, last_idx, S, write,
        attend, cfg, rope)
    return logits, {"k": kc, "v": vc}


def copy_page(cache, dst, src):
    """Copy-on-write: duplicate page `src` into `dst` across all layers
    (both K and V sides) — used when a new sequence diverges inside a
    prefix-shared page."""
    return {"k": cache["k"].at[:, dst].set(cache["k"][:, src]),
            "v": cache["v"].at[:, dst].set(cache["v"][:, src])}


# the leaves the serve programs cast with `.astype(cfg.dtype)` where they
# use them (slot_embed, qkv_of_normed, attn_out, _attn_out_and_mlp,
# unembed_table); the norms' scales and biases are used as they are kept
_SERVE_CAST = frozenset({
    "embed", "pos_embed", "unembed", "wq", "wk", "wv", "wo", "wq_b", "wk_b",
    "wv_b", "wo_b", "mlp_in", "mlp_in_b", "mlp_out", "mlp_out_b", "mlp_gate",
    "mlp_up"})


def cast_leaves(params, cfg, names):
    """`params` with every leaf named in `names` through the
    `astype(cfg.dtype)` the serve programs apply at each use (a model's
    `serve_view` is this over its own leaves).  The operands of every
    product are bit for bit what the programs computed for themselves.
    Every other leaf, and a leaf already in cfg.dtype, is the same array:
    nothing is copied, so a tree kept in cfg.dtype costs no memory."""
    dt = jnp.dtype(cfg.dtype)

    def leaf(path, w):
        name = next((k.key for k in reversed(path)
                     if isinstance(k, jax.tree_util.DictKey)), None)
        return w.astype(dt) if name in names and w.dtype != dt else w

    return jax.tree_util.tree_map_with_path(leaf, params)


def serve_view(params, cfg):
    """The tree a serving engine hands to paged_decode_step /
    paged_prefill in place of `params`, made once at its set-up: inside
    the programs every cast is then the identity and a decode step reads
    its weights in the dtype it multiplies in (a float32 tree served in
    bf16 was read whole, at twice the bytes, and cast again in every step
    and every prefill)."""
    return cast_leaves(params, cfg, _SERVE_CAST)


def sample_logits(logits, key, temperature: float = 0.0,
                  top_k: Optional[int] = None, dtype=jnp.int32):
    """The ONE sampling recipe (greedy argmax at temperature 0, else
    temperature-scaled, optionally top-k-truncated categorical) — shared
    by generate() and the serving stream step so seed parity between
    routes can't drift."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(dtype)
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(dtype)


def generate(params, cfg: GPTConfig, prompt, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: Optional[int] = None,
             rng=None, max_seq: Optional[int] = None):
    """Autoregressive generation: prompt [B, S] int32 -> [B, S + new].

    temperature == 0 is greedy argmax; otherwise categorical sampling
    over logits/temperature (optionally top_k-truncated).  The prefill
    and decode loops are both lax.scans of decode_step, so the entire
    call jits to one program with static shapes.  GPT-2-family configs
    take a decode-view fast path (fused QKV, compute-dtype weights,
    unrolled layers) measured ~2x the generic path on v5e; both paths
    share sample_logits and the key schedule (token-exact in f32; at
    bf16, fusion-order rounding can flip near-tie logits).
    """
    B, S = prompt.shape
    total = S + max_new_tokens
    if max_seq is None:
        max_seq = total
    if total > max_seq:
        raise ValueError(f"prompt ({S}) + max_new_tokens "
                         f"({max_new_tokens}) > max_seq ({max_seq})")
    if cfg.pos == "learned" and total > cfg.max_seq:
        raise ValueError(f"learned positions stop at {cfg.max_seq}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        return sample_logits(logits, key, temperature, top_k,
                             dtype=prompt.dtype)

    keys = jax.random.split(rng, max_new_tokens)

    if _decode_fast_eligible(cfg):
        view = _decode_view(params, cfg)
        shape = (cfg.n_layers, B, cfg.n_heads, max_seq, cfg.d_head)
        kc0 = jnp.zeros(shape, cfg.dtype)
        vc0 = jnp.zeros(shape, cfg.dtype)

        def prefill_f(carry, tok):
            kc, vc, pos = carry
            # hidden only — projecting [B, V] logits per prompt
            # position would throw away all but the last
            x, kc, vc = _decode_hidden_fast(view, cfg, kc, vc, pos, tok)
            return (kc, vc, pos + 1), x

        (kc, vc, pos), hidden_all = jax.lax.scan(
            prefill_f, (kc0, vc0, jnp.zeros((), jnp.int32)), prompt.T)
        last_logits = hidden_all[-1] @ view["unembed"]

        def step_f(carry, key):
            kc, vc, pos, logits = carry
            tok = sample(logits, key)
            x, kc, vc = _decode_hidden_fast(view, cfg, kc, vc, pos, tok)
            return (kc, vc, pos + 1, x @ view["unembed"]), tok

        (_, _, _, _), new_tokens = jax.lax.scan(
            step_f, (kc, vc, pos, last_logits), keys)
        return jnp.concatenate([prompt, new_tokens.T], axis=1)

    cache = init_cache(cfg, B, max_seq)
    # hoisted out of both scan bodies: the table is position-invariant
    rope = (rope_table(max_seq, cfg.d_head, dtype=jnp.float32)
            if cfg.pos != "learned" else None)

    def prefill(cache, tok):
        # hidden only (see prefill_f above)
        x, cache = _decode_hidden(params, cache, tok, cfg, rope)
        return cache, x

    cache, hidden_all = jax.lax.scan(prefill, cache, prompt.T)
    last_logits = jnp.einsum("bd,dv->bv",
                             hidden_all[-1].astype(cfg.dtype),
                             unembed_table(params, cfg))

    def step(carry, key):
        cache, logits = carry
        tok = sample(logits, key)
        new_logits, cache = decode_step(params, cache, tok, cfg, rope)
        return (cache, new_logits), tok

    (_, _), new_tokens = jax.lax.scan(step, (cache, last_logits), keys)
    return jnp.concatenate([prompt, new_tokens.T], axis=1)


def num_params(cfg: GPTConfig) -> int:
    p = init(jax.random.PRNGKey(0), dataclasses.replace(cfg, n_layers=1))
    base = sum(x.size for x in jax.tree.leaves(p))
    per_layer = sum(x.size for x in jax.tree.leaves(p["layers"]))
    return base + per_layer * (cfg.n_layers - 1)
