"""A configuration file with the published `dots3_note` key names ->
ray_tpu's Dots3Config, and -> the `shape` dict of the plain reference
(benchmarks/reference/dots3_plain.py)."""

from __future__ import annotations

_KIND = {"full_attention": "full", "sliding_attention": "sliding"}


def _checked(cfg: dict) -> list:
    if cfg.get("rope_scaling"):
        raise ValueError("only plain rope (rope_scaling null) is built")
    if (cfg["attention_gate_type"], cfg["swa_attention_gate_type"]) != (
            "headwise", "headwise"):
        raise ValueError("only the headwise attention gate is built")
    if cfg["first_k_dense_replace"] > cfg["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace runs past the layers held")
    kinds = [_KIND[k] for k in cfg["layer_types"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names another number of layers than "
                         "num_hidden_layers")
    return kinds


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import dots3 as m

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"], layer_types=tuple(_checked(cfg)),
              n_dense=cfg["first_k_dense_replace"],
              d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
              q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
              d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
              d_v=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
              index_heads=cfg["index_n_heads"],
              index_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
              swa_heads=cfg["swa_num_attention_heads"],
              swa_q_rank=cfg["swa_q_lora_rank"],
              swa_kv_rank=cfg["swa_kv_lora_rank"],
              swa_d_nope=cfg["swa_qk_nope_head_dim"],
              swa_d_rope=cfg["swa_qk_rope_head_dim"],
              swa_d_v=cfg["swa_v_head_dim"],
              swa_rope_theta=float(cfg["swa_rope_theta"]),
              window=cfg["sliding_window_size"],
              lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
              d_ff=cfg["intermediate_size"],
              d_expert=cfg["moe_intermediate_size"],
              n_experts=cfg["published"]["n_routed_experts"],
              experts_first=cfg["deployment_share"]["experts_first"],
              experts_held=cfg["n_routed_experts"],
              top_k=cfg["num_experts_per_tok"],
              routed_scale=float(cfg["routed_scaling_factor"]),
              n_shared=cfg["n_shared_experts"],
              rms_eps=float(cfg["rms_norm_eps"]),
              max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(cfg.get("program", {}))    # kv_block, moe_tile
    kw.update(overrides)
    return m.Dots3Config(**kw)


def reference_shape(cfg: dict) -> dict:
    kinds = _checked(cfg)
    attn = lambda p, theta: {
        "n_heads": cfg[p + "num_attention_heads"],
        "q_rank": cfg[p + "q_lora_rank"], "kv_rank": cfg[p + "kv_lora_rank"],
        "d_nope": cfg[p + "qk_nope_head_dim"],
        "d_rope": cfg[p + "qk_rope_head_dim"], "d_v": cfg[p + "v_head_dim"],
        "theta": float(cfg[theta])}
    return {"eps": float(cfg["rms_norm_eps"]), "d_model": cfg["hidden_size"],
            "rescale": bool(cfg["apply_mla_qkv_lora_rescale"]),
            "full": attn("", "rope_theta"),
            "sliding": dict(attn("swa_", "swa_rope_theta"),
                            window=cfg["sliding_window_size"]),
            "index_heads": cfg["index_n_heads"],
            "index_dim": cfg["index_head_dim"],
            "index_topk": cfg["index_topk"],
            "layer_types": kinds, "n_layers": len(kinds),
            "n_dense": cfg["first_k_dense_replace"],
            "d_ff": cfg["intermediate_size"],
            "d_expert": cfg["moe_intermediate_size"],
            "n_experts": cfg["published"]["n_routed_experts"],
            "first": cfg["deployment_share"]["experts_first"],
            "held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "routed_scale": float(cfg["routed_scaling_factor"]),
            "n_shared": cfg["n_shared_experts"],
            "vocab": cfg["vocab_size"], "param_dtype": cfg["param_dtype"]}
