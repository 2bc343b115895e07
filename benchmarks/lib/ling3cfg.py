"""A configuration file with the published `bailing_hybrid` key names ->
ray_tpu's Ling3Config, and -> the `shape` dict of the plain reference
(benchmarks/reference/ling3_plain.py)."""

from __future__ import annotations


def _limits(cfg: dict) -> tuple:
    """The SwiGLU clamps of the layers held (an expert's, then the shared
    expert's): the program builds none but 0 and says so itself."""
    n = cfg["num_hidden_layers"]
    return (tuple(cfg["expert_swiglu_limit_list"][:n])
            + tuple(cfg["share_expert_swiglu_limit_list"][:n]))


def _checked(cfg: dict) -> dict:
    fixed = {"short_conv_kernel_size": 4, "q_lora_rank": None,
             "rope_scaling": None, "group_norm_size": 1,
             "num_shared_experts": 1, "head_dim": cfg["v_head_dim"]}
    off = {k: cfg.get(k) for k, v in fixed.items() if cfg.get(k) != v}
    if off:
        raise ValueError(f"what is built has {fixed}; the file says {off}")
    return cfg


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import ling3 as lm

    cfg = _checked(cfg)
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              n_layers=cfg["num_hidden_layers"],
              n_dense=cfg["first_k_dense_replace"],
              layer_group=cfg["layer_group_size"],
              d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
              d_head=cfg["head_dim"],
              gate_lower=float(cfg["kda_lower_bound"]),
              kv_rank=cfg["kv_lora_rank"], d_nope=cfg["qk_nope_head_dim"],
              d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
              d_ff=cfg["intermediate_size"],
              d_expert=cfg["moe_intermediate_size"],
              d_shared=cfg["moe_shared_expert_intermediate_size"],
              n_experts=cfg["published"]["num_experts"],
              experts_first=cfg["deployment_share"]["experts_first"],
              experts_held=cfg["num_experts"],
              top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
              topk_group=cfg["topk_group"],
              routed_scale=float(cfg["routed_scaling_factor"]),
              swiglu_limits=_limits(cfg),
              rms_eps=float(cfg["rms_norm_eps"]),
              rope_theta=float(cfg["rope_theta"]),
              max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(cfg.get("program", {}))    # kv_block, moe_tile
    kw.update(overrides)
    return lm.Ling3Config(**kw)


def reference_shape(cfg: dict) -> dict:
    cfg = _checked(cfg)
    if any(_limits(cfg)):
        raise ValueError("the reference has no SwiGLU clamp either")
    return {"eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]), "yarn": None,
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"], "d_head": cfg["head_dim"],
            "gate_lower": float(cfg["kda_lower_bound"]),
            "layer_group": cfg["layer_group_size"],
            "kv_rank": cfg["kv_lora_rank"],
            "d_nope": cfg["qk_nope_head_dim"],
            "d_rope": cfg["qk_rope_head_dim"], "d_v": cfg["v_head_dim"],
            "d_ff": cfg["intermediate_size"],
            "d_expert": cfg["moe_intermediate_size"],
            "d_shared": cfg["moe_shared_expert_intermediate_size"],
            "n_shared": cfg["num_shared_experts"],
            "n_experts": cfg["published"]["num_experts"],
            "first": cfg["deployment_share"]["experts_first"],
            "held": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"], "n_group": cfg["n_group"],
            "topk_group": cfg["topk_group"],
            "routed_scale": float(cfg["routed_scaling_factor"]),
            "n_layers": cfg["num_hidden_layers"],
            "n_dense": cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"], "param_dtype": cfg["param_dtype"]}
