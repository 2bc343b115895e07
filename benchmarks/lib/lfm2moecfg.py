"""A configuration file with the published `lfm2_moe` key names ->
ray_tpu's Lfm2MoeConfig, and -> the `shape` dict of the plain reference
(benchmarks/reference/lfm2_moe_plain.py)."""

from __future__ import annotations


def _checked(cfg: dict) -> dict:
    fixed = {"conv_bias": False, "use_expert_bias": True,
             "norm_topk_prob": True}
    off = {k: cfg.get(k) for k, v in fixed.items() if cfg.get(k) != v}
    if off:
        raise ValueError(f"what is built has {fixed}; the file says {off}")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names every layer held")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads")
    return cfg


def _held(cfg: dict):
    share = cfg.get("deployment_share", {})
    return (int(share.get("experts_first", 0)),
            int(share.get("experts_held", cfg["num_experts"])))


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import lfm2_moe as lm

    cfg = _checked(cfg)
    first, held = _held(cfg)
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              layer_types=tuple(cfg["layer_types"]),
              n_dense=cfg["num_dense_layers"], d_model=cfg["hidden_size"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"],
              d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
              d_ff=cfg["intermediate_size"],
              d_expert=cfg["moe_intermediate_size"],
              n_experts=cfg["num_experts"], experts_first=first,
              experts_held=held, top_k=cfg["num_experts_per_tok"],
              routed_scale=float(cfg["routed_scaling_factor"]),
              conv_taps=cfg["conv_L_cache"],
              rope_theta=float(cfg["rope_theta"]),
              eps=float(cfg["norm_eps"]), max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(cfg.get("program", {}))    # kv_block, moe_tile
    kw.update(overrides)
    return lm.Lfm2MoeConfig(**kw)


def reference_shape(cfg: dict) -> dict:
    cfg = _checked(cfg)
    first, held = _held(cfg)
    if cfg["conv_L_cache"] != 3:
        raise ValueError("the reference is written for three taps")
    return {"eps": float(cfg["norm_eps"]), "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_head": cfg["hidden_size"] // cfg["num_attention_heads"],
            "d_ff": cfg["intermediate_size"],
            "d_expert": cfg["moe_intermediate_size"],
            "n_experts": cfg["num_experts"], "first": first, "held": held,
            "top_k": cfg["num_experts_per_tok"],
            "routed_scale": float(cfg["routed_scaling_factor"]),
            "theta": float(cfg["rope_theta"]),
            "layer_types": tuple(cfg["layer_types"]),
            "n_dense": cfg["num_dense_layers"],
            "n_layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "param_dtype": cfg["param_dtype"]}
