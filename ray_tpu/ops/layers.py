"""Core layer math: rmsnorm, rope, activations — XLA-fusable building blocks.

XLA fuses these elementwise chains into surrounding matmuls (the HBM-
bandwidth recipe); they are written shape-polymorphic so the same code runs
under any sharding.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm in f32 accumulation, cast back to input dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm in f32; `bias` None is the weight-only form."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rope_table(seq_len: int, head_dim: int, base: float = 10000.0,
               dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precomputed cos/sin tables [seq, head_dim/2]."""
    half = head_dim // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(pos, freqs)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(x, cos, sin, positions: Optional[jnp.ndarray] = None):
    """Rotary embedding for [B, H, S, D] with tables [S_max, D/2].

    positions: optional [S] global positions (sequence-parallel chunks pass
    their offsets); defaults to arange(S).
    """
    b, h, s, d = x.shape
    if positions is None:
        c = cos[:s][None, None]
        sn = sin[:s][None, None]
    else:
        c = cos[positions][None, None]
        sn = sin[positions][None, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    y1 = x1 * c - x2 * sn
    y2 = x2 * c + x1 * sn
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def yarn_frequencies(dim: int, theta: float = 10000.0, factor: float = 40.0,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     original_max: int = 4096):
    """YaRN's angular frequencies (arXiv:2309.00071) for a rope part of
    `dim` values, [dim/2] float32 (numpy: a constant of the program).
    Pair i of the plain recipe turns at theta^(-2i/dim) a position.  A
    pair that completes more than `beta_fast` turns over the
    `original_max` positions the model was trained on keeps that
    frequency; one that completes fewer than `beta_slow` is slowed by
    `factor` (positions interpolated); between the two pair indices — the
    floor and the ceiling of dim ln(original_max / (2 pi turns)) /
    (2 ln theta) — the blend is linear in the index."""
    half = dim // 2
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / (max(high - low, 0.001)), 0, 1)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def apply_rope_interleaved(x, positions, theta: float = 10000.0, freqs=None):
    """Rotary embedding over interleaved pairs (x[2i], x[2i+1]) — the
    GPT-J convention — for x [B, H, T, D] at positions [B, T], every row
    of the batch at its own positions.  Angles are computed in f32 from
    the positions (no table: contexts run to hundreds of thousands).
    `freqs` [D/2] takes the place of the plain theta^(-2i/D) (scaled
    rotary embeddings: `yarn_frequencies`)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, :, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    y = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return y.reshape(x.shape).astype(x.dtype)


def apply_rope_halves(x, positions, theta: float = 10000.0):
    """Rotary embedding over the pairs (x[i], x[i + D/2]) — the
    rotate-half convention of `apply_rope` — for x [B, H, T, D] at
    positions [B, T], every row of the batch at its own positions; angles
    in f32 from the positions (no table), as `apply_rope_interleaved`."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, :, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: silu(x@Wg) * (x@Wu) @ Wd, bf16-friendly."""
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = jnp.einsum("...d,df->...f", x, w_in) + b_in
    return jnp.einsum("...f,fd->...d", jax.nn.gelu(h), w_out) + b_out


def fused_softmax_cross_entropy(x, unembed, labels, z_loss: float = 0.0,
                                chunk: int = 128):
    """Vocab-projected CE WITHOUT materializing [B, S, V] logits: scan
    over sequence chunks; each chunk's logits exist only inside its
    (checkpointed) scan step, so peak memory is [B, chunk, V] and the
    bwd pass recomputes chunk logits instead of reading a stored f32
    logits tensor — on HBM-bandwidth-bound steps the recompute is
    cheaper than the traffic.  Numerically identical to the dense path:
    both einsum in x.dtype and upcast to f32 for the logsumexp.

    x [B, S, D] (compute dtype), unembed [D, V], labels [B, S] int.
    Returns per-token loss [B, S] (f32).
    """
    B, S, D = x.shape
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    xs = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)     # [n, B, c, D]
    ls = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)   # [n, B, c]

    @jax.checkpoint
    def chunk_loss(xc, lc):
        logits = jnp.einsum("bcd,dv->bcv", xc, unembed).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        out = lse - jnp.take_along_axis(logits, lc[..., None],
                                        axis=-1)[..., 0]
        if z_loss:
            out = out + z_loss * jnp.square(lse)
        return out

    _, losses = jax.lax.scan(lambda _, t: (None, chunk_loss(*t)),
                             None, (xs, ls))               # [n, B, c]
    return jnp.moveaxis(losses, 0, 1).reshape(B, S)


def softmax_cross_entropy(logits, labels, z_loss: float = 0.0):
    """Token-level CE in f32 with optional z-loss (stabilizes large-vocab
    training); logits [..., V], labels [...] int."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss
