"""A configuration file (HF GPT-2 key names) -> ray_tpu's GPTConfig."""

from __future__ import annotations


def gpt_config(cfg: dict, **overrides):
    """The program's GPTConfig at the file's sizes.  Imports jax."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"],
              d_model=cfg["n_embd"], n_heads=cfg["n_head"],
              d_head=cfg["head_dim"], d_ff=cfg["n_inner"],
              max_seq=cfg["n_positions"], norm="ln", act="gelu",
              pos="learned", attn_bias=bool(cfg.get("attn_bias", True)),
              tie_embeddings=True, dtype=dt[cfg["compute_dtype"]],
              param_dtype=dt[cfg["param_dtype"]])
    kw.update(overrides)
    return gpt.GPTConfig(**kw)
