"""Attention ops: Pallas TPU flash attention + blockwise XLA fallback.

The reference has no attention kernels at all (it delegates compute to
torch); for a TPU-native framework the attention kernel IS the hot op, so it
lives here as a first-class component (SURVEY.md §2.3: ring attention must be
built natively).

Layouts: all functions take [batch, heads, seq, head_dim] (BHSD).

Three tiers:
  * mha_reference     — O(S^2) naive, the correctness oracle.
  * blockwise_attention — flash-style streaming softmax as a lax.scan; runs
    anywhere XLA runs, differentiable, memory O(S·block).
  * flash_attention   — Pallas TPU kernels, forward AND backward (MXU-tiled,
    VMEM-resident blocks, causal block skipping; FlashAttention-2-style
    dq/dk/dv backward, so no XLA recompute anywhere).
  * flash_attention_with_lse — (out, logsumexp) variant whose partial
    results compose across KV chunks (the ring-attention building block).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def mha_reference(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Naive O(S^2) attention; the oracle for kernel tests."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention in pure XLA — runs on CPU/TPU, grads OK
# ---------------------------------------------------------------------------


def _block_stats_update(carry, s_blk, v_blk):
    """One online-softmax accumulation step (the flash recurrence)."""
    acc, m_prev, l_prev = carry
    m_cur = jnp.max(s_blk, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s_blk - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_blk.dtype),
                                       v_blk).astype(acc.dtype)
    return acc_new, m_new, l_new


def blockwise_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None, block_k: int = 512,
                        kv_offset: int = 0, q_offset: int = 0):
    """Streaming-softmax attention scanning KV blocks.

    kv_offset/q_offset give the *global* positions of the local q/k chunks —
    that's what lets ring attention reuse this with rotated KV blocks.

    Deliberately a FLAT scan over KV blocks with all queries in each
    matmul: a q-chunked variant that skips upper-triangle blocks via
    lax.cond was measured 2.5x SLOWER end-to-end on v5e (GPT-2 @4096:
    7.8k vs 19.8k tokens/s) — the skip trades one wide MXU-saturating
    matmul per KV block for a serialized chain of narrow ones.  On TPU,
    keep matmuls big; masked FLOPs are cheaper than small grids.
    """
    b, h, sq, d = q.shape
    sk = k.shape[-2]
    scale = (d ** -0.5) if scale is None else scale
    block_k = min(block_k, sk)
    nblocks = (sk + block_k - 1) // block_k
    pad = nblocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)

    q32 = q.astype(jnp.float32)
    row_ids = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, 1), 0)

    def step(carry, inputs):
        idx, k_blk, v_blk = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_blk.astype(jnp.float32)) * scale
        col_start = kv_offset + idx * block_k
        col_ids = col_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = col_ids < (kv_offset + sk)  # padding mask
        if causal:
            mask = mask & (row_ids >= col_ids)
        s = jnp.where(mask[None, None], s, DEFAULT_MASK_VALUE)
        return _block_stats_update(carry, s, v_blk), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), DEFAULT_MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (jnp.arange(nblocks), kb, vb))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash attention
# ---------------------------------------------------------------------------


def streamed_attention(q, qpos, fetch, n_blocks, *, window=None):
    """Causal attention over keys that arrive block by block, with an
    online softmax: the serve path's recipe for caches too long to score
    at once, grouped-query heads and a sliding window included.

    q [B, Hkv, G, T, dh] (G query heads a K/V head) at positions qpos
    [B, T]; `fetch(i)` gives block i's keys and values [B, Hkv, S, dh]
    and their positions kpos [B, S] (negative: no key there).  Query t
    sees key s iff 0 <= qpos - kpos (< window, where a window is given).
    `n_blocks` may be traced: only blocks 0..n_blocks-1 are fetched.
    A row that sees no key at all comes out zero.  Returns [B, Hkv, G, T,
    dh] in q's dtype; scores and statistics are f32."""
    B, Hkv, G, T, dh = q.shape
    scale = dh ** -0.5

    def body(i, carry):
        m, l, acc = carry
        k, v, kpos = fetch(i)
        s = jnp.einsum("bhgtd,bhsd->bhgts", q, k,
                       preferred_element_type=jnp.float32) * scale
        d = qpos[:, :, None] - kpos[:, None, :]             # [B, T, S]
        ok = (d >= 0) & (kpos[:, None, :] >= 0)
        if window is not None:
            ok = ok & (d < window)
        ok = ok[:, None, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(ok, s, DEFAULT_MASK_VALUE), axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgts,bhsd->bhgtd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((B, Hkv, G, T), DEFAULT_MASK_VALUE, jnp.float32),
            jnp.zeros((B, Hkv, G, T), jnp.float32),
            jnp.zeros((B, Hkv, G, T, dh), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _fit_block(block, seq):
    # shrink to a divisor so seq lengths like 768 (divisible by 256
    # but not the 512/1024 defaults) keep working — but never below
    # 128 lanes: a seq like 520 would "fit" at block 8, turning the
    # grid into thousands of tiny sequential programs (an orders-of-
    # magnitude perf cliff, and sub-sublane blocks may not even
    # lower); such lengths must pad instead, loudly
    floor = min(128, seq)
    block = min(block, seq)
    while block > floor and seq % block:
        block //= 2
    if seq % block:
        raise ValueError(
            f"seq length {seq} has no block divisor >= {floor}; pad "
            f"the sequence to a multiple of 128 for the pallas path")
    return block


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                  causal: bool, block_q: int, block_k: int,
                  q_offset: int, with_lse: bool):
    from jax.experimental import pallas as pl

    if with_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest

    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # whole block above the diagonal contributes nothing; q_offset
        # shifts local q rows to their global positions (decode-style
        # rectangular causal: q_offset = sk - sq anchors bottom-right)
        run = (j * block_k) <= (q_offset + i * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _compute():
        # inputs stay bf16 — the MXU runs bf16 x bf16 at full rate with
        # f32 accumulation via preferred_element_type; casting to f32
        # first would halve matmul throughput for zero extra precision
        q = q_ref[0, 0]                               # [bq, d]
        k = k_ref[0, 0]                               # [bk, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)
        if with_lse:
            # logsumexp per query row, broadcast across the 128 lanes
            # (sublane->lane transposes don't lower, so LSE lives as a
            # lane-replicated [.., 128] plane end to end)
            lse_ref[0, 0] = m_ref[:] + jnp.log(
                jnp.maximum(l_ref[:], 1e-30))


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool, with_lse: bool = False,
                   q_offset: int = 0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[-2]
    if q_offset < 0:
        raise ValueError(f"q_offset must be >= 0, got {q_offset}")
    if causal and sq != sk and q_offset == 0:
        # with no offset the kernels anchor the causal mask at row 0
        # (rows >= cols) while mha_reference anchors rectangular inputs
        # bottom-right (tril with k=sk-sq, decode semantics: the last
        # query row is position sk-1) — letting this through would
        # silently diverge from the other impls
        raise ValueError(
            f"causal pallas flash attention with sq ({sq}) != sk ({sk}) "
            f"needs an explicit query anchor: pass q_offset=sk-sq "
            f"({sk - sq}) for bottom-right (decode) alignment, use "
            f"impl='xla' (blockwise_attention handles the offset), or "
            f"pad q to sk.")
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    grid = (b, h, sq // block_q, sk // block_k)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               q_offset=q_offset, with_lse=with_lse)
    out_specs = [pl.BlockSpec((1, 1, block_q, d),
                              lambda b_, h_, i, j: (b_, h_, i, 0))]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, block_q, 128),
                                      lambda b_, h_, i, j: (b_, h_, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, 128), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return (res[0], res[1]) if with_lse else res[0]


# ---------------------------------------------------------------------------
# Pallas flash attention backward (FlashAttention-2 style dq / dk / dv)
# ---------------------------------------------------------------------------
#
# Residuals are (q, k, v, o, lse): the big P matrix is never stored.  The
# backward recomputes p = exp(s - lse) blockwise inside two kernels:
#   dkv: grid (b, h, Nk, Nq) — for a fixed KV block, accumulate over q
#        blocks   dv_j += p^T do,   dk_j += scale * ds^T q
#   dq:  grid (b, h, Nq, Nk) — for a fixed Q block, accumulate over k
#        blocks   dq_i += scale * ds k
# with ds = p * (dp - di), dp = do v^T, di = rowsum(do * o) - dlse (the
# dlse term folds the cotangent of the lse output into the same kernel:
# d lse_i / d s_ik = p_ik).  di and lse ride as lane-replicated
# [B, H, S, 128] planes (see _flash_kernel._finalize).


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          causal: bool, block_q: int, block_k: int,
                          q_offset: int):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)   # k block (outer)
    i = pl.program_id(3)   # q block (inner, sequential accumulation)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (j * block_k) <= (q_offset + i * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0, 0]                                 # [bq, d]
        k = k_ref[0, 0]                                 # [bk, d]
        v = v_ref[0, 0]
        do = do_ref[0, 0]                               # [bq, d]
        lse = lse_ref[0, 0][:, :1]                      # [bq, 1] f32
        di = di_ref[0, 0][:, :1]                        # [bq, 1] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        p = jnp.exp(s - lse)
        if causal:
            rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        # dv += p^T do  (contract the q axis of both)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = (p * (dp - di) * scale).astype(q.dtype)
        # dk += ds^T q
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                         dq_ref, dq_acc, *, scale: float, causal: bool,
                         block_q: int, block_k: int, q_offset: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)   # q block (outer)
    j = pl.program_id(3)   # k block (inner, sequential accumulation)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (j * block_k) <= (q_offset + i * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        di = di_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal:
            rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - di) * scale).astype(q.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse128, do, dlse, causal: bool,
                    scale: float, block_q: int, block_k: int,
                    interpret: bool, q_offset: int = 0):
    """dq, dk, dv from residuals.  lse128: [B,H,Sq,128] lane-replicated
    logsumexp; dlse: [B,H,Sq] cotangent of the lse output or None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[-2]
    # bwd blocks are capped at 512x512: four [bq, bk] f32 intermediates
    # live at once (s, p, dp, ds), twice the fwd's VMEM appetite
    block_q = _fit_block(min(block_q, 512), sq)
    block_k = _fit_block(min(block_k, 512), sk)

    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        di = di - dlse
    di128 = jnp.broadcast_to(di[..., None], (b, h, sq, 128))

    def qspec(rev):
        # rev: grid is (b, h, kblock, qblock); else (b, h, qblock, kblock)
        if rev:
            return pl.BlockSpec((1, 1, block_q, d),
                                lambda b_, h_, j, i: (b_, h_, i, 0))
        return pl.BlockSpec((1, 1, block_q, d),
                            lambda b_, h_, i, j: (b_, h_, i, 0))

    def kspec(rev):
        if rev:
            return pl.BlockSpec((1, 1, block_k, d),
                                lambda b_, h_, j, i: (b_, h_, j, 0))
        return pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, h_, i, j: (b_, h_, j, 0))

    def lanespec(rev):
        if rev:
            return pl.BlockSpec((1, 1, block_q, 128),
                                lambda b_, h_, j, i: (b_, h_, i, 0))
        return pl.BlockSpec((1, 1, block_q, 128),
                            lambda b_, h_, i, j: (b_, h_, i, 0))

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, q_offset=q_offset)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, sk // block_k, sq // block_q),
        in_specs=[qspec(True), kspec(True), kspec(True), qspec(True),
                  lanespec(True), lanespec(True)],
        out_specs=[kspec(True), kspec(True)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse128, di128)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, q_offset=q_offset)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, sq // block_q, sk // block_k),
        in_specs=[qspec(False), kspec(False), kspec(False), qspec(False),
                  lanespec(False), lanespec(False)],
        out_specs=qspec(False),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse128, di128)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 1024, interpret: bool = False,
                    q_offset: int = 0):
    """Pallas TPU flash attention, forward AND backward kernels (the
    backward is the FlashAttention-2 dq/dk/dv pair above — no XLA
    recompute fallback).

    q_offset (static): global position of q's row 0 in the causal mask.
    Pass sk - sq for bottom-right (decode) alignment of causal
    rectangular inputs, matching mha_reference's tril(k=sk-sq)."""
    scale = (q.shape[-1] ** -0.5) if scale is None else scale
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret, q_offset=q_offset)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               q_offset):
    scale_ = (q.shape[-1] ** -0.5) if scale is None else scale
    out, lse128 = _flash_forward(q, k, v, causal, scale_, block_q, block_k,
                                 interpret, with_lse=True,
                                 q_offset=q_offset)
    return out, (q, k, v, out, lse128)


def _flash_bwd(causal, scale, block_q, block_k, interpret, q_offset,
               res, g):
    q, k, v, o, lse128 = res
    scale_ = (q.shape[-1] ** -0.5) if scale is None else scale
    return _flash_backward(q, k, v, o, lse128, g, None, causal, scale_,
                           block_q, block_k, interpret, q_offset=q_offset)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: int = 512, block_k: int = 1024,
                             interpret: bool = False, q_offset: int = 0):
    """(out, lse) variant for partial-softmax composition (ring
    attention): lse is [B, H, Sq] f32 logsumexp of the scaled scores.
    Differentiable in both outputs — the lse cotangent folds into the
    same backward kernels (di -= dlse)."""
    scale_ = (q.shape[-1] ** -0.5) if scale is None else scale
    out, lse128 = _flash_forward(q, k, v, causal, scale_, block_q, block_k,
                                 interpret, with_lse=True,
                                 q_offset=q_offset)
    return out, lse128[..., 0]


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   q_offset):
    scale_ = (q.shape[-1] ** -0.5) if scale is None else scale
    out, lse128 = _flash_forward(q, k, v, causal, scale_, block_q, block_k,
                                 interpret, with_lse=True,
                                 q_offset=q_offset)
    return (out, lse128[..., 0]), (q, k, v, out, lse128)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, q_offset,
                   res, g):
    q, k, v, o, lse128 = res
    do, dlse = g
    scale_ = (q.shape[-1] ** -0.5) if scale is None else scale
    return _flash_backward(q, k, v, o, lse128, do, dlse, causal, scale_,
                           block_q, block_k, interpret, q_offset=q_offset)


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def resolve_impl(impl: str, sq: int, sk: int, causal: bool) -> str:
    """The concrete path `attention(impl=...)` takes for these shapes on
    this backend ("auto" resolved; anything else returned as given)."""
    if impl != "auto":
        return impl
    # v5e measurements (GPT-2-small training, tokens/s), with the
    # native FlashAttention-2 dq/dk/dv bwd kernels: pallas beats XLA
    # blockwise at EVERY seq — 512 B=16: 99.5k vs 75.7k (+31%, MFU
    # .40 vs .31); 4096: 59.5k vs 19.8k (3.0x, MFU .37); 8192: 37.0k
    # vs 11.3k (3.3x, MFU .32).  (Before the bwd kernels existed the
    # custom_vjp fell back to a full blockwise recompute and lost
    # everywhere — that's why this dispatch was XLA-only through
    # round 4.)  XLA remains the portable path: CPU meshes, seqs not
    # a multiple of 128, and anything interpret-mode.
    # causal rectangular with sq > sk still routes to XLA (a
    # negative q_offset has no causal interpretation here); sk >= sq
    # runs in pallas with the bottom-right anchor via q_offset
    if (jax.default_backend() == "tpu"
            and sq % 128 == 0 and sk % 128 == 0
            and not (causal and sq > sk)):
        return "pallas"
    return "xla"


def attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
              impl: str = "auto", block_q: Optional[int] = None,
              block_k: Optional[int] = None):
    """Dispatching attention: blockwise XLA by default, Pallas on request.

    q,k,v: [batch, heads, seq, head_dim]

    Block defaults are per-path (v5e-measured optima differ 4x): the
    XLA scan wants small KV blocks (256 — deeper fusion per step), the
    pallas grid wants fat ones (512x1024 — fewer sequential programs).

    The Pallas kernel is a Mosaic custom call, which GSPMD cannot
    partition: on sharded arrays call it from inside a shard_map
    (models/gpt.py `_attention_op` does).
    """
    sq, sk = q.shape[-2], k.shape[-2]
    impl = resolve_impl(impl, sq, sk, causal)
    # bottom-right-aligned causal mask for rectangular inputs, matching
    # mha_reference's tril(k=sk-sq) decode semantics
    qoff = (sk - sq) if (causal and sk > sq) else 0
    if impl == "pallas":
        return flash_attention(q, k, v, causal, scale, block_q or 512,
                               block_k or 1024, False, qoff)
    if impl == "pallas_interpret":
        return flash_attention(q, k, v, causal, scale, block_q or 512,
                               block_k or 1024, True, qoff)
    if impl == "xla":
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   block_k=block_k or 256,
                                   q_offset=(sk - sq) if causal else 0)
    if impl == "reference":
        return mha_reference(q, k, v, causal=causal, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")
