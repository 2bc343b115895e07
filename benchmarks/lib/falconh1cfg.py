"""A configuration file with the published `falcon_h1` key names ->
ray_tpu's FalconH1Config, and -> the `shape` dict of the plain reference
(benchmarks/reference/falcon_h1_plain.py)."""

from __future__ import annotations


def _checked(cfg: dict) -> dict:
    fixed = {"attention_bias": False, "mamba_proj_bias": False,
             "projectors_bias": False, "mlp_bias": False,
             "mamba_conv_bias": True, "mamba_rms_norm": True,
             "mamba_norm_before_gate": False, "mamba_d_conv": 4,
             "hidden_act": "silu", "tie_word_embeddings": False,
             "rope_scaling": None, "attn_layer_indices": None}
    off = {k: cfg.get(k) for k, v in fixed.items() if cfg.get(k) != v}
    if off:
        raise ValueError(f"what is built has {fixed}; the file says {off}")
    if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
    return cfg


def _multipliers(cfg: dict) -> dict:
    return {"embedding": cfg["embedding_multiplier"],
            "lm_head": cfg["lm_head_multiplier"],
            "key": cfg["key_multiplier"],
            "attention_in": cfg["attention_in_multiplier"],
            "attention_out": cfg["attention_out_multiplier"],
            "ssm_in": cfg["ssm_in_multiplier"],
            "ssm_out": cfg["ssm_out_multiplier"],
            "ssm": tuple(cfg["ssm_multipliers"]),
            "mlp": tuple(cfg["mlp_multipliers"])}


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import falcon_h1 as fm

    cfg = _checked(cfg)
    m = _multipliers(cfg)
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
              d_ff=cfg["intermediate_size"],
              ssm_heads=cfg["mamba_n_heads"],
              ssm_head_dim=cfg["mamba_d_head"],
              d_state=cfg["mamba_d_state"], n_groups=cfg["mamba_n_groups"],
              ssm_chunk=cfg["mamba_chunk_size"],
              rope_theta=float(cfg["rope_theta"]),
              eps=float(cfg["rms_norm_eps"]),
              embedding_multiplier=m["embedding"],
              lm_head_multiplier=m["lm_head"], key_multiplier=m["key"],
              attention_in_multiplier=float(m["attention_in"]),
              attention_out_multiplier=m["attention_out"],
              ssm_in_multiplier=m["ssm_in"],
              ssm_out_multiplier=m["ssm_out"], ssm_multipliers=m["ssm"],
              mlp_multipliers=m["mlp"], max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(cfg.get("program", {}))    # kv_block
    kw.update(overrides)
    return fm.FalconH1Config(**kw)


def reference_shape(cfg: dict) -> dict:
    cfg = _checked(cfg)
    return {"eps": float(cfg["rms_norm_eps"]), "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_head": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
            "ssm_heads": cfg["mamba_n_heads"],
            "ssm_head_dim": cfg["mamba_d_head"],
            "d_state": cfg["mamba_d_state"],
            "n_groups": cfg["mamba_n_groups"],
            "theta": float(cfg["rope_theta"]),
            "multipliers": _multipliers(cfg),
            "n_layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "param_dtype": cfg["param_dtype"]}
