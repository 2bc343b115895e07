"""A configuration file with the published `deepseek_v3` key names ->
ray_tpu's DeepSeekV3Config, and -> the `shape` dict of the plain reference
(benchmarks/reference/deepseek_v3_plain.py)."""

from __future__ import annotations


def _yarn(cfg: dict):
    rs = cfg.get("rope_scaling")
    if not rs:
        return None
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("only YaRN with mscale == mscale_all_dim (cos and "
                         "sin unscaled) is built")
    return (float(rs["factor"]), float(rs["beta_fast"]),
            float(rs["beta_slow"]),
            int(rs["original_max_position_embeddings"]))


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as dm

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              n_layers=cfg["num_hidden_layers"],
              n_dense=cfg["first_k_dense_replace"],
              d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
              q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
              d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
              d_v=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
              d_expert=cfg["moe_intermediate_size"],
              n_experts=cfg["published"]["n_routed_experts"],
              experts_first=cfg["deployment_share"]["experts_first"],
              experts_held=cfg["n_routed_experts"],
              top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
              topk_group=cfg["topk_group"],
              routed_scale=float(cfg["routed_scaling_factor"]),
              n_shared=cfg["n_shared_experts"],
              rms_eps=float(cfg["rms_norm_eps"]),
              rope_theta=float(cfg["rope_theta"]), yarn=_yarn(cfg),
              max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(cfg.get("program", {}))    # kv_block, moe_tile
    kw.update(overrides)
    return dm.DeepSeekV3Config(**kw)


def reference_shape(cfg: dict) -> dict:
    y = _yarn(cfg)
    return {"eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "yarn": list(y) if y else None,
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "d_nope": cfg["qk_nope_head_dim"],
            "d_rope": cfg["qk_rope_head_dim"], "d_v": cfg["v_head_dim"],
            "d_ff": cfg["intermediate_size"],
            "d_expert": cfg["moe_intermediate_size"],
            "n_experts": cfg["published"]["n_routed_experts"],
            "first": cfg["deployment_share"]["experts_first"],
            "held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"], "n_group": cfg["n_group"],
            "topk_group": cfg["topk_group"],
            "routed_scale": float(cfg["routed_scaling_factor"]),
            "n_shared": cfg["n_shared_experts"],
            "n_layers": cfg["num_hidden_layers"],
            "n_dense": cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"], "param_dtype": cfg["param_dtype"]}
