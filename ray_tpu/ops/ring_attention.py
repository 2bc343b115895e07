"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

Absent from the reference (SURVEY.md §2.3/§5: "no ring_attention/ulysses/
context_parallel anywhere in-tree") — the TPU build implements it natively:
Q stays resident per device; K/V blocks rotate around the `sp` ring via
`lax.ppermute` while each device accumulates flash-style online-softmax
partial results.  ICI neighbor links make the rotation bandwidth-optimal,
and XLA overlaps the ppermute with the local attention compute (the
latency-hiding recipe of Liu et al., Ring Attention, and the scaling-book
collective chapter).

Causal masking works on *global* positions: device r owns query rows
[r*S_local, (r+1)*S_local); at rotation step t it sees KV chunk from device
(r - t) mod n, i.e. kv_offset = ((r - t) mod n) * S_local.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from .attention import (DEFAULT_MASK_VALUE, _block_stats_update,
                        blockwise_attention, flash_attention_with_lse)


def _ring_attention_local_pallas(q, k, v, axis_name: str, causal: bool,
                                 scale: Optional[float],
                                 block_k: int = 512,
                                 interpret: bool = False):
    """Pallas-kernel ring body.  Because KV rotates in whole-device
    chunks, every step is one of three STATIC shapes — full attention
    (KV strictly before Q), diagonal causal (own chunk), or fully
    masked (KV strictly after Q) — so the offset-free flash kernels
    compose: each chunk call returns a per-chunk-normalized (o, lse)
    and steps combine in log space.  No offset-aware kernel needed."""
    n = axis_size(axis_name)
    r = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    scale_ = (d ** -0.5) if scale is None else scale
    perm = [(i, (i + 1) % n) for i in range(n)]

    def chunk(k_cur, v_cur, diag: bool):
        o, lse = flash_attention_with_lse(
            q, k_cur, v_cur, diag, scale_, 512, block_k, interpret)
        return o.astype(jnp.float32), lse

    def masked(k_cur, v_cur):
        return (jnp.zeros((b, h, s_loc, d), jnp.float32),
                jnp.full((b, h, s_loc), DEFAULT_MASK_VALUE, jnp.float32))

    def step(t, carry):
        o_acc, lse_acc, k_cur, v_cur = carry
        src = (r - t) % n                  # whose KV chunk we hold
        if causal:
            o_c, lse_c = jax.lax.cond(
                src == r,
                lambda kc, vc: chunk(kc, vc, True),
                lambda kc, vc: jax.lax.cond(
                    src < r,
                    lambda kc_, vc_: chunk(kc_, vc_, False),
                    masked, kc, vc),
                k_cur, v_cur)
        else:
            o_c, lse_c = chunk(k_cur, v_cur, False)
        m = jnp.maximum(lse_acc, lse_c)
        w1 = jnp.exp(lse_acc - m)
        w2 = jnp.exp(lse_c - m)
        o_acc = (o_acc * w1[..., None] + o_c * w2[..., None]) \
            / jnp.maximum(w1 + w2, 1e-30)[..., None]
        lse_acc = m + jnp.log(jnp.maximum(w1 + w2, 1e-30))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o_acc, lse_acc, k_nxt, v_nxt

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), DEFAULT_MASK_VALUE, jnp.float32)
    o, _, _, _ = jax.lax.fori_loop(0, n, step, (o0, lse0, k, v))
    return o.astype(q.dtype)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          scale: Optional[float], block_k: int):
    """Runs inside shard_map: q,k,v are the local [B,H,S_loc,D] chunks."""
    n = axis_size(axis_name)
    r = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    scale_ = (d ** -0.5) if scale is None else scale
    q_offset = r * s_loc

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(t, carry):
        acc, m, l, k_cur, v_cur = carry
        src = (r - t) % n                  # whose KV chunk we hold at step t
        kv_offset = src * s_loc
        s_blk_fn = functools.partial(
            _partial_scores, q=q, scale=scale_, causal=causal,
            q_offset=q_offset, kv_offset=kv_offset, block_k=block_k)
        acc, m, l = _accumulate_chunk(acc, m, l, s_blk_fn, k_cur, v_cur)
        # rotate KV to the next device; XLA overlaps this with compute
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc, 1), DEFAULT_MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc, m, l, _, _ = jax.lax.fori_loop(
        0, n, step, (acc0, m0, l0, k, v))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _partial_scores(k_blk, col_start, *, q, scale, causal, q_offset,
                    kv_offset, block_k):
    q32 = q.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk.astype(jnp.float32)) * scale
    sq = q.shape[-2]
    bk = k_blk.shape[-2]
    rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, 1), 0)
    cols = kv_offset + col_start + jax.lax.broadcasted_iota(
        jnp.int32, (1, bk), 1)
    if causal:
        mask = rows >= cols
        s = jnp.where(mask[None, None], s, DEFAULT_MASK_VALUE)
    return s


def _accumulate_chunk(acc, m, l, s_blk_fn, k_chunk, v_chunk):
    """Fold one KV chunk into the running flash stats, blockwise."""
    s_loc = k_chunk.shape[-2]
    s = s_blk_fn(k_chunk, 0)
    return _block_stats_update((acc, m, l), s, v_chunk)


def _ring_local_dispatch(q, k, v, axis_name: str, causal: bool,
                         scale: Optional[float], block_k: int, impl: str):
    if impl == "auto":
        # same rule as attention(): the flash kernels win on TPU for any
        # kernel-shaped chunk; the XLA scan is the portable path
        s_loc, sk_loc = q.shape[-2], k.shape[-2]
        impl = ("pallas" if (jax.default_backend() == "tpu"
                             and s_loc % 128 == 0 and sk_loc % 128 == 0)
                else "xla")
    if impl == "pallas":
        return _ring_attention_local_pallas(q, k, v, axis_name, causal,
                                            scale, block_k)
    if impl == "pallas_interpret":
        return _ring_attention_local_pallas(q, k, v, axis_name, causal,
                                            scale, block_k, interpret=True)
    if impl == "xla":
        return _ring_attention_local(q, k, v, axis_name, causal, scale,
                                     block_k)
    raise ValueError(f"unknown ring attention impl {impl!r}")


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   block_k: int = 512, in_specs: Optional[P] = None,
                   impl: str = "auto"):
    """Sequence-parallel attention over `axis_name`.

    q,k,v are global arrays [B, H, S, D] sharded on S over the mesh axis
    (other axes may carry dp/tp sharding; this op only touches `sp`).
    Returns the globally-correct attention output with the same sharding.
    """
    spec = in_specs if in_specs is not None else P(None, None, axis_name, None)
    local = functools.partial(_ring_local_dispatch, axis_name=axis_name,
                              causal=causal, scale=scale, block_k=block_k,
                              impl=impl)
    return shard_map(local, check_vma=False, mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


def ring_attention_sharded(q, k, v, axis_name: str = "sp",
                           causal: bool = False,
                           scale: Optional[float] = None,
                           block_k: int = 512, impl: str = "auto"):
    """For use *inside* an existing shard_map/pjit program: the per-device
    body alone (q,k,v already local chunks)."""
    return _ring_local_dispatch(q, k, v, axis_name, causal, scale,
                                block_k, impl)
