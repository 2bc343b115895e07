"""A configuration file with the published `cohere2_moe` key names ->
ray_tpu's Cohere2MoEConfig, and -> the `shape` dict of the plain reference
(benchmarks/reference/cohere2_moe_plain.py)."""

from __future__ import annotations


def layer_types(cfg: dict) -> tuple:
    """The first `num_hidden_layers` entries of the published pattern."""
    names = {"sliding_attention": "sliding", "full_attention": "full"}
    return tuple(names[t] for t in
                 cfg["layer_types"][:cfg["num_hidden_layers"]])


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import cohere2_moe as cm

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              n_layers=cfg["num_hidden_layers"],
              d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
              d_expert=cfg["intermediate_size"],
              n_experts=cfg["published"]["num_experts"],
              experts_first=cfg["deployment_share"]["experts_first"],
              experts_held=cfg["num_experts"],
              top_k=cfg["num_experts_per_tok"],
              n_shared=cfg["num_shared_experts"],
              layer_types=layer_types(cfg),
              sliding_window=cfg["sliding_window"],
              rope_theta=float(cfg["rope_theta"]),
              logit_scale=float(cfg["logit_scale"]),
              max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(overrides)
    return cm.Cohere2MoEConfig(**kw)


def reference_shape(cfg: dict) -> dict:
    return {"layer_types": layer_types(cfg), "window": cfg["sliding_window"],
            "theta": float(cfg["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"],
            "first": cfg["deployment_share"]["experts_first"],
            "n_shared": cfg["num_shared_experts"],
            "logit_scale": float(cfg["logit_scale"])}
