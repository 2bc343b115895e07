"""Ulysses-style sequence parallelism: all-to-all head<->sequence reshard.

Absent from the reference (SURVEY.md §2.3); built natively: with activations
sharded on sequence over `sp`, attention wants full sequence per head — so
all-to-all swaps the sharded axis from seq to heads before attention and back
after (DeepSpeed-Ulysses; maps to one `lax.all_to_all` each way over ICI).
Requires heads % sp == 0.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _seq_to_heads(x, axis_name: str):
    # local [B, H, S/n, D] -> exchange -> local [B, H/n, S, D]
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _heads_to_seq(x, axis_name: str):
    # local [B, H/n, S, D] -> local [B, H, S/n, D]
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                      causal: bool = False, scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None):
    """Attention with Ulysses resharding.

    Inputs [B, H, S, D] sequence-sharded over `axis_name`; internally
    resharded to head-parallel (full sequence per device), attention runs
    with any inner implementation (defaults to the blockwise XLA path /
    Pallas kernel on TPU via ops.attention), then reshard back.
    """
    from .attention import attention as default_attn

    if axis_name not in mesh.shape:
        raise ValueError(f"ulysses_attention: axis {axis_name!r} is not in "
                         f"the mesh (axes: {tuple(mesh.axis_names)})")
    heads, sp = q.shape[1], mesh.shape[axis_name]
    if heads % sp:
        raise ValueError(
            f"ulysses_attention: the all_to_all reshard splits the head dim "
            f"across the {axis_name!r} axis, so heads ({heads}) must be "
            f"divisible by the axis size ({sp}); pad/regroup heads or "
            f"shrink {axis_name!r}")

    inner = attn_fn or (lambda a, b, c: default_attn(a, b, c, causal=causal,
                                                     scale=scale))
    spec = P(None, None, axis_name, None)

    def local(q_, k_, v_):
        qh = _seq_to_heads(q_, axis_name)
        kh = _seq_to_heads(k_, axis_name)
        vh = _seq_to_heads(v_, axis_name)
        oh = inner(qh, kh, vh)
        return _heads_to_seq(oh, axis_name)

    return shard_map(local, check_vma=False, mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


def ulysses_attention_sharded(q, k, v, axis_name: str = "sp",
                              causal: bool = False,
                              scale: Optional[float] = None,
                              attn_fn: Optional[Callable] = None):
    """Per-device body for use inside an existing shard_map program.
    The inner attention goes through ops.attention's dispatch, so TPU
    runs the Pallas flash kernels (same as the outer wrapper)."""
    from .attention import attention as default_attn

    inner = attn_fn or (lambda a, b, c: default_attn(
        a, b, c, causal=causal, scale=scale))
    qh = _seq_to_heads(q, axis_name)
    kh = _seq_to_heads(k, axis_name)
    vh = _seq_to_heads(v, axis_name)
    return _heads_to_seq(inner(qh, kh, vh), axis_name)
