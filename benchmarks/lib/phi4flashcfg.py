"""A configuration file with the published `phi4flash` key names ->
ray_tpu's Phi4FlashConfig, and -> the `shape` dict of the plain reference
(benchmarks/reference/phi4flash_plain.py).  The keys config.json does not
give (the Mamba layer's sizes) are read from the file's `assumed_sizes`."""

from __future__ import annotations


def _checked(cfg: dict) -> dict:
    fixed = {"mb_per_layer": 2, "tie_word_embeddings": True,
             "mlp_bias": False, "lm_head_bias": False, "hidden_act": "silu"}
    off = {k: cfg.get(k) for k, v in fixed.items() if cfg.get(k) != v}
    if off:
        raise ValueError(f"what is built has {fixed}; the file says {off}")
    return cfg


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash as pm

    cfg = _checked(cfg)
    a = cfg["assumed_sizes"]
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"], d_head=_head_dim(cfg),
              d_ff=cfg["intermediate_size"],
              sliding_window=cfg["sliding_window"],
              mb_per_layer=cfg["mb_per_layer"], d_state=a["mamba_d_state"],
              expand=a["mamba_expand"], dt_rank=a["mamba_dt_rank"],
              ln_eps=float(cfg["layer_norm_eps"]),
              max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    if a["mamba_d_conv"] != pm.CONV_TAPS:
        raise ValueError(f"the conv that is built has {pm.CONV_TAPS} taps")
    kw.update(cfg.get("program", {}))    # kv_block
    kw.update(overrides)
    return pm.Phi4FlashConfig(**kw)


def reference_shape(cfg: dict) -> dict:
    cfg = _checked(cfg)
    a = cfg["assumed_sizes"]
    return {"eps": float(cfg["layer_norm_eps"]),
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_head": _head_dim(cfg), "d_ff": cfg["intermediate_size"],
            "window": cfg["sliding_window"],
            "mb_per_layer": cfg["mb_per_layer"],
            "d_state": a["mamba_d_state"], "d_conv": a["mamba_d_conv"],
            "expand": a["mamba_expand"], "dt_rank": a["mamba_dt_rank"],
            "n_layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "param_dtype": cfg["param_dtype"]}
