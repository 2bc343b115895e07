"""The short causal convolution: depthwise, `W` taps, a sum over W shifted
copies of a sequence's rows — in its two serving forms, one chunk of one
sequence (prefill) against the W-1 rows before it, and one token of every
slot (decode) against each slot's kept tail.

    y_t = act(b + sum_{i=0..W-1} w_i * x_{t-(W-1)+i})       (tap W-1 meets
                                                             the row itself)

What stands around it is the caller's.  Three models run it as a SiLU'd
pre-filter INSIDE a recurrent mixer (`act` SiLU, a bias, four taps: ling3's
KDA, phi4flash's Mamba, falcon_h1's SSD — `ops/kda.conv_chunk` /
`conv_step` are those arguments bound); in `models/lfm2_moe.py` it IS the
mixer: three taps over a gated input, no bias, no activation (`act` and `b`
None), gated again after.  A sequence keeps its last W-1 input rows (the
tail) in a state arena `[layers, entries, W-1, ..]` float32, entry 0 the
null one; a first chunk starts from zeros, a step hands the tail back with
the oldest row out and the new one in, and the caller leaves an empty
slot's entry as it was.  The sums are float32 whatever the rows' dtype.
`scope` is the `jax.named_scope` the trace reads the op under.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["conv_chunk", "conv_step"]


def conv_chunk(rows, tail, w, b, act: Optional[Callable], scope: str):
    """rows [T, ..] one sequence's pre-conv rows in order, tail [W-1, ..]
    the W-1 rows before them (zeros before a sequence's start), w [W, ..]
    (tap W-1 meets the row itself), b [..] or None -> act(conv) [T, ..]
    float32 (`act` None: the sum as it is): a sum over W shifted copies."""
    with jax.named_scope(scope):
        T, W = rows.shape[0], w.shape[0]
        ext = jnp.concatenate([tail.astype(rows.dtype), rows], axis=0)
        bias = None if b is None else b.astype(jnp.float32)
        acc = sum(
            w[i].astype(jnp.float32)
            * jax.lax.slice_in_dim(ext, i, i + T, axis=0).astype(jnp.float32)
            for i in range(W))
        if bias is not None:
            acc = bias + acc
        return acc if act is None else act(acc)


def conv_step(row, tail, w, b, act: Optional[Callable], scope: str):
    """One token of every slot: row [B, ..] the new pre-conv rows, tail
    [B, W-1, ..] each slot's last W-1 -> (act(conv) [B, ..] float32, the
    tails after: the oldest row out, the new one in)."""
    with jax.named_scope(scope):
        ext = jnp.concatenate([tail, row[:, None].astype(tail.dtype)], axis=1)
        bias = None if b is None else b.astype(jnp.float32)
        acc = jnp.einsum("bw...,w...->b...", ext.astype(jnp.float32),
                         w.astype(jnp.float32))
        if bias is not None:
            acc = bias + acc
        return (acc if act is None else act(acc)), ext[:, 1:]
