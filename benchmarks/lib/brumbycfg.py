"""A configuration file with the published `brumby` key names -> ray_tpu's
BrumbyConfig, and -> the `shape` dict of the plain reference
(benchmarks/reference/brumby_plain.py)."""

from __future__ import annotations


def model_config(cfg: dict, **overrides):
    """The program's config at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import brumby as bm

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"],
              n_layers=cfg["num_hidden_layers"],
              d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
              d_ff=cfg["intermediate_size"],
              rms_eps=float(cfg["rms_norm_eps"]),
              rope_theta=float(cfg["rope_theta"]),
              max_seq=cfg["serve"]["max_seq"],
              dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]],
              state_dtype=dt[cfg["state_dtype"]])
    kw.update(overrides)
    return bm.BrumbyConfig(**kw)


def reference_shape(cfg: dict) -> dict:
    return {"eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "ret_eps": float(cfg["assumed_values"]["retention_eps"])}
