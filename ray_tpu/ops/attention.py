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
    VMEM-resident blocks, only the causal part of a block computed;
    FlashAttention-2-style dq/dk/dv backward, so no XLA recompute
    anywhere).
  * flash_attention_with_lse — (out, logsumexp) variant whose partial
    results compose across KV chunks (the ring-attention building block).
"""

from __future__ import annotations

import functools
import math
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


def _streamed_xla(q, qpos, fetch, n_blocks, window, scale, v_dim):
    """`streamed_attention` (at the end of this file: it says what the
    operands are, and chooses) with a block's scores formed by XLA: what
    a decode step's call takes (one row a head, slots in B) and every
    call off the TPU.  A block's f32 scores [B, Hkv, G, T, S] go through
    memory between the two products; for the rows of a prefill chunk
    that is most of the call's time, and the block kernel at the end of
    this file keeps them on the chip.

    This body stands where `streamed_attention` stood before PR 46, in
    as many lines, and everything PR 46 added is at the file's end: a
    Pallas kernel's serialised module carries its source lines, so a
    line added above the flash-attention kernels below changes the text,
    and the compile-cache key, of every train program that holds them.

    Query t sees key s iff 0 <= qpos - kpos (< window, where one is
    given) and kpos >= 0; a row that sees no key comes out zero (p is
    zeroed where the mask hides a score, so an all-hidden row sums to
    l = 0 and its accumulator stays 0).  `n_blocks` may be traced.
    Scores, statistics (m, l) and the accumulator are f32; p is cast to
    the values' dtype for the second product.
    """
    B, Hkv, G, T, dh = q.shape

    # one key block: the flash recurrence on XLA's own products
    def body(i, carry):
        m, l, acc = carry
        k, v, kpos, *keep = fetch(i)
        s = jnp.einsum("bhgtd,bhsd->bhgts", q, k,
                       preferred_element_type=jnp.float32) * scale
        d = qpos[:, :, None] - kpos[:, None, :]             # [B, T, S]
        ok = (d >= 0) & (kpos[:, None, :] >= 0)
        if window is not None:
            ok = ok & (d < window)
        ok = (ok & keep[0] if keep else ok)[:, None, None]
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
            jnp.zeros((B, Hkv, G, T, v_dim), jnp.float32))
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


# A program stays fat (one [block_q, block_k] block: few grid steps, few
# DMAs) and computes only the causal part of it.  Where the diagonal lies
# in a block depends on the block's position alone — `delta`, the global
# position of its first query row minus that of its first key — and a
# call's grid meets few values of it (one at 1024 x 1024 blocks).  So a
# kernel carries one straight-line body per value, picked by
# `pl.when(delta == d)`.  In a body every extent is static: each group of
# sub_q query rows (sub_k keys in dkv, which walks the other way) meets
# exactly the sub-blocks it sees, those wholly below the diagonal in one
# matmul with no iota / compare / select, those the diagonal crosses in
# another with the mask, and nothing above the diagonal is computed.
# Blocks wholly below the diagonal share one unmasked body; blocks above
# it run none, and their index_map names the block already resident, so
# skipped steps fetch nothing.  (A loop over sub-blocks with a traced
# trip count does the same arithmetic and was measured slower than no
# skipping at all on v5e: PERF.md section 6, PR 41.)
_SUB = 256          # rows of a sub-block, queries' and keys' alike
# the backward's programs are capped at 1024 x 1024 (a group's four f32
# intermediates s, p, dp, ds span the block's other side, and lse and di
# ride as [block_q, 128] f32 planes)
_BWD_BLOCK = 1024


def _sub_blocks(block_q: int, block_k: int):
    """Rows of queries and of keys in the sub-blocks a program divides
    its [block_q, block_k] block into; from the block sizes alone."""
    return (_fit_block(min(_SUB, block_q), block_q),
            _fit_block(min(_SUB, block_k), block_k))


def _split_scale(scale: float):
    """`scale` as (the factor of a group's matmul operand, [rows, d_head]
    once a group; the factor of its f32 scores, [rows, keys]), one of
    them None.  A power of two (d_head 64: 0.125) goes into the operand,
    which no dtype rounds; any other would round a bf16 operand a second
    time, and stays on the scores."""
    return (scale, None) if math.frexp(scale)[0] == 0.5 else (None, scale)


def _times(x, factor):
    return x if factor is None else x * factor


def _add(acc, term):
    return term if acc is None else acc + term


def _visible_keys(row0: int, rows: int, sub_k: int, n_sub: int):
    """Which of n_sub key sub-blocks [c*sub_k, (c+1)*sub_k) the query
    rows [row0, row0 + rows) see under the causal mask: [0, n_full)
    whole, no mask needed; [n_full, n_end) in part, masked; the rest not
    at all.  Positions relative to the first key; row0 may be negative."""
    return (max(0, min((row0 + 1) // sub_k, n_sub)),
            max(0, min((row0 + rows - 1) // sub_k + 1, n_sub)))


def _visible_queries(col0: int, cols: int, row0: int, sub_q: int,
                     n_sub: int):
    """The other way round, for the kernel that walks queries under the
    keys [col0, col0 + cols): of n_sub query sub-blocks (rows row0 +
    [r*sub_q, (r+1)*sub_q)) those below r_start see none of them,
    [r_start, r_full) part (masked), r_full and up all of them."""
    t = col0 - row0
    return (max(0, min(t // sub_q, n_sub)),
            max(0, min((t + cols + sub_q - 2) // sub_q, n_sub)))


def _diagonals(sq: int, sk: int, block_q: int, block_k: int, q_offset: int):
    """The deltas (first row's position minus first key's) of the blocks
    of a call's grid that the diagonal crosses, and whether any block
    lies wholly below it."""
    deltas = {q_offset + i * block_q - j * block_k
              for i in range(sq // block_q) for j in range(sk // block_k)}
    return (sorted(d for d in deltas if -block_q < d < block_k - 1),
            any(d >= block_k - 1 for d in deltas))


def causal_work(sq: int, sk: int, block_q: int = 1024, block_k: int = 1024,
                causal: bool = True, q_offset: int = 0,
                backward: bool = False) -> dict:
    """How much of the [sq, sk] score square one head of a
    flash_attention call computes: `total` sub-blocks in the square,
    `run` of them computed, `masked` of those built with the causal
    mask.  A pure function of the shapes, from the arithmetic the
    kernels lay their bodies out with: what they do, not an estimate."""
    if backward:
        block_q, block_k = min(block_q, _BWD_BLOCK), min(block_k, _BWD_BLOCK)
    block_q, block_k = _fit_block(block_q, sq), _fit_block(block_k, sk)
    sub_q, sub_k = _sub_blocks(block_q, block_k)
    nq, nk = sq // sub_q, sk // sub_k
    run, masked = nq * nk, 0
    if causal:
        seen = [_visible_keys(q_offset + r * sub_q, sub_q, sub_k, nk)
                for r in range(nq)]
        run = sum(n_end for _, n_end in seen)
        masked = sum(n_end - n_full for n_full, n_end in seen)
    return {"sub_q": sub_q, "sub_k": sub_k, "total": nq * nk, "run": run,
            "masked": masked}


def _key_parts(d: int, masked: bool, g: int, sub_q: int, sub_k: int,
               block_k: int):
    """The keys of a block at delta d that its g-th group of sub_q query
    rows multiplies against, as (lo, hi, needs the mask) extents."""
    if not masked:
        return [(0, block_k, False)]
    n_full, n_end = _visible_keys(d + g * sub_q, sub_q, sub_k,
                                  block_k // sub_k)
    parts = [(0, n_full * sub_k, False), (n_full * sub_k, n_end * sub_k, True)]
    return [p for p in parts if p[0] < p[1]]


def _query_parts(d: int, masked: bool, g: int, sub_q: int, sub_k: int,
                 block_q: int):
    """The query rows of a block at delta d that see its g-th group of
    sub_k keys, as (lo, hi, needs the mask) extents."""
    if not masked:
        return [(0, block_q, False)]
    r_start, r_full = _visible_queries(g * sub_k, sub_k, d, sub_q,
                                       block_q // sub_q)
    parts = [(r_start * sub_q, r_full * sub_q, True),
             (r_full * sub_q, block_q, False)]
    return [p for p in parts if p[0] < p[1]]


def _dispatch(body, delta, causal: bool, block_k: int, diagonals):
    """Run body(d, masked) for this program's delta: one body per
    diagonal position the call's grid meets (d static), one unmasked for
    blocks below the diagonal, none above it."""
    from jax.experimental import pallas as pl

    if not causal:
        return body(0, False)
    crossing, below = diagonals
    if below:
        pl.when(delta >= block_k - 1)(lambda: body(0, False))
    for d in crossing:
        pl.when(delta == d)(functools.partial(body, d, True))


def _causal_mask(row0, col0, shape):
    # rows >= cols, at positions row0 + iota and col0 + iota
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1)) >= col0 - row0


def _mask_cache():
    """_causal_mask, built once a body for each (offset, shape): on an
    aligned diagonal every group's mask is the same triangle."""
    built = {}

    def tril(row0, col0, shape):
        key = (col0 - row0, shape)
        if key not in built:
            built[key] = _causal_mask(row0, col0, shape)
        return built[key]

    return tril


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    # operands stay in their own dtype (bf16 on the chip: the MXU runs
    # bf16 x bf16 at full rate, casting to f32 first would halve it);
    # accumulation is f32
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                  causal: bool, block_q: int, block_k: int, q_offset: int,
                  diagonals, single: bool, with_lse: bool):
    from jax.experimental import pallas as pl

    lse_ref = rest[0] if with_lse else None
    q_scale, s_scale = _split_scale(scale)
    # single: the block holds every key, so a group of rows is finished
    # where it is computed and no statistics are carried in scratch
    acc_ref, m_ref, l_ref = (None,) * 3 if single else rest[-3:]

    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block
    nk = pl.num_programs(3)

    def finish(rows, acc, m, l):
        o_ref[0, 0, rows, :] = (acc / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        if with_lse:
            # logsumexp per query row, broadcast across the 128 lanes
            # (sublane->lane transposes don't lower, so LSE lives as a
            # lane-replicated [.., 128] plane end to end)
            lse_ref[0, 0, rows, :] = jnp.broadcast_to(
                m + jnp.log(jnp.maximum(l, 1e-30)), (acc.shape[0], 128))

    def body(d, masked):
        sub_q, sub_k = _sub_blocks(block_q, block_k)
        tril = _mask_cache()
        for g in range(block_q // sub_q):
            parts = _key_parts(d, masked, g, sub_q, sub_k, block_k)
            if not parts:
                continue
            rows = slice(g * sub_q, (g + 1) * sub_q)
            q = _times(q_ref[0, 0, rows, :], q_scale)        # [sub_q, d]
            scores = []
            for lo, hi, mask in parts:
                s = _times(_dot(q, k_ref[0, 0, lo:hi, :], _NT), s_scale)
                if mask:
                    s = jnp.where(tril(d + g * sub_q, lo, s.shape), s,
                                  DEFAULT_MASK_VALUE)
                scores.append(s)
            m_new = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=-1, keepdims=True) for s in scores])
            l_new = acc = None
            if not single:
                m_prev = m_ref[rows, :1]
                m_new = jnp.maximum(m_prev, m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_ref[rows, :1]
                acc = acc_ref[rows, :] * alpha
            for (lo, hi, _), s in zip(parts, scores):
                p = jnp.exp(s - m_new)
                v = v_ref[0, 0, lo:hi, :]
                l_new = _add(l_new, jnp.sum(p, axis=-1, keepdims=True))
                acc = _add(acc, _dot(p.astype(v.dtype), v, _NN))
            if single:
                finish(rows, acc, m_new, l_new)
            else:
                acc_ref[rows, :] = acc
                m_ref[rows, :] = jnp.broadcast_to(m_new, (sub_q, 128))
                l_ref[rows, :] = jnp.broadcast_to(l_new, (sub_q, 128))

    if not single:
        @pl.when(j == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    # q_offset shifts local q rows to their global positions (decode-
    # style rectangular causal: q_offset = sk - sq anchors bottom-right)
    _dispatch(body, q_offset + i * block_q - j * block_k, causal, block_k,
              diagonals)

    if not single:
        @pl.when(j == nk - 1)
        def _finalize():
            finish(slice(None), acc_ref[:], m_ref[:, :1], l_ref[:, :1])


def _index_maps(causal: bool, block_q: int, block_k: int, sq: int,
                q_offset: int):
    """Block index of the q-side and k-side operands at grid step (i, j)
    (i the q block, j the k block, whichever is outer).  Under the causal
    mask the inner axis is clamped to the blocks the outer block sees: a
    step that computes nothing names the block already resident and
    fetches nothing."""
    def q_of(i, j, q_inner):
        if causal and q_inner:
            first = jnp.maximum(j * block_k - q_offset, 0) // block_q
            i = jnp.maximum(i, jnp.minimum(first, sq // block_q - 1))
        return i

    def k_of(i, j, q_inner):
        if causal and not q_inner:
            j = jnp.minimum(j, (q_offset + (i + 1) * block_q - 1) // block_k)
        return j

    return q_of, k_of


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
    single = sk == block_k      # a program holds every key
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset,
        diagonals=_diagonals(sq, sk, block_q, block_k, q_offset),
        single=single, with_lse=with_lse)
    q_of, k_of = _index_maps(causal, block_q, block_k, sq, q_offset)

    def q_map(b_, h_, i, j):
        return (b_, h_, q_of(i, j, False), 0)

    def k_map(b_, h_, i, j):
        return (b_, h_, k_of(i, j, False), 0)

    out_specs = [pl.BlockSpec((1, 1, block_q, d), q_map)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, block_q, 128), q_map))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, 128), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), k_map),
            pl.BlockSpec((1, 1, block_k, d), k_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[] if single else [
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
# [B, H, S, 128] planes (see _flash_kernel._finalize).  Both lay their
# block out as the forward does: dkv's groups of keys meet the query
# rows from the diagonal down, dq's groups of queries the keys up to it.
# `scale` is folded into the group's operand (k in dkv, q in dq) where
# that is exact (_split_scale), and into the accumulator once at the end.


def _bwd_tile(q, k, v, do, lse, di, mask, s_scale):
    """p and ds of one tile, as matmul operands; q or k carries the
    scale where the scores (s_scale) do not."""
    p = jnp.exp(_times(_dot(q, k, _NT), s_scale) - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    ds = p * (_dot(do, v, _NT) - di)
    return p.astype(do.dtype), ds.astype(q.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, *acc, scale: float, causal: bool,
                          block_q: int, block_k: int, q_offset: int,
                          diagonals, single: bool):
    from jax.experimental import pallas as pl

    # single (the block holds every query): a group of keys is finished
    # where it is computed, nothing accumulates in scratch
    dk_acc, dv_acc = (None, None) if single else acc
    k_scale, s_scale = _split_scale(scale)

    j = pl.program_id(2)   # k block (outer)
    i = pl.program_id(3)   # q block (inner, sequential accumulation)
    nq = pl.num_programs(3)

    def finish(cols, dk, dv):
        dk_ref[0, 0, cols, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, cols, :] = dv.astype(dv_ref.dtype)

    def body(d, masked):
        sub_q, sub_k = _sub_blocks(block_q, block_k)
        tril = _mask_cache()
        for g in range(block_k // sub_k):
            parts = _query_parts(d, masked, g, sub_q, sub_k, block_q)
            cols = slice(g * sub_k, (g + 1) * sub_k)
            if not parts:       # never where single: the last row sees
                continue        # every key
            k = _times(k_ref[0, 0, cols, :], k_scale)        # [sub_k, d]
            v = v_ref[0, 0, cols, :]
            dk, dv = (None, None) if single else (dk_acc[cols, :],
                                                  dv_acc[cols, :])
            for lo, hi, mask in parts:
                q = q_ref[0, 0, lo:hi, :]
                do = do_ref[0, 0, lo:hi, :]
                p, ds = _bwd_tile(
                    q, k, v, do, lse_ref[0, 0, lo:hi, :1],
                    di_ref[0, 0, lo:hi, :1],
                    tril(d + lo, g * sub_k, (hi - lo, sub_k))
                    if mask else None, s_scale)
                # dv += p^T do, dk += ds^T q (contract the q axis); both
                # products before either sum: emitted product, sum,
                # product, sum the kernel reads 0.5404 ms for 0.5225 at
                # the train cells' shape (PERF.md section 6, PR 41)
                dv_p, dk_p = _dot(p, do, _TN), _dot(ds, q, _TN)
                dv, dk = _add(dv, dv_p), _add(dk, dk_p)
            if single:
                finish(cols, dk, dv)
            else:
                dk_acc[cols, :], dv_acc[cols, :] = dk, dv

    if not single:
        @pl.when(i == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    _dispatch(body, q_offset + i * block_q - j * block_k, causal, block_k,
              diagonals)

    if not single:
        @pl.when(i == nq - 1)
        def _finalize():
            finish(slice(None), dk_acc[:], dv_acc[:])


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                         dq_ref, *acc, scale: float, causal: bool,
                         block_q: int, block_k: int, q_offset: int,
                         diagonals, single: bool):
    from jax.experimental import pallas as pl

    dq_acc = None if single else acc[0]
    q_scale, s_scale = _split_scale(scale)

    i = pl.program_id(2)   # q block (outer)
    j = pl.program_id(3)   # k block (inner, sequential accumulation)
    nk = pl.num_programs(3)

    def body(d, masked):
        sub_q, sub_k = _sub_blocks(block_q, block_k)
        tril = _mask_cache()
        for g in range(block_q // sub_q):
            parts = _key_parts(d, masked, g, sub_q, sub_k, block_k)
            if not parts:
                continue
            rows = slice(g * sub_q, (g + 1) * sub_q)
            q = _times(q_ref[0, 0, rows, :], q_scale)
            do = do_ref[0, 0, rows, :]
            lse = lse_ref[0, 0, rows, :1]                    # [sub_q, 1] f32
            di = di_ref[0, 0, rows, :1]
            dq = None if single else dq_acc[rows, :]
            for lo, hi, mask in parts:
                k = k_ref[0, 0, lo:hi, :]
                _, ds = _bwd_tile(
                    q, k, v_ref[0, 0, lo:hi, :], do, lse, di,
                    tril(d + g * sub_q, lo, (sub_q, hi - lo))
                    if mask else None, s_scale)
                dq = _add(dq, _dot(ds, k, _NN))
            if single:
                dq_ref[0, 0, rows, :] = (dq * scale).astype(dq_ref.dtype)
            else:
                dq_acc[rows, :] = dq

    if not single:
        @pl.when(j == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    _dispatch(body, q_offset + i * block_q - j * block_k, causal, block_k,
              diagonals)

    if not single:
        @pl.when(j == nk - 1)
        def _finalize():
            dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse128, do, dlse, causal: bool,
                    scale: float, block_q: int, block_k: int,
                    interpret: bool, q_offset: int = 0):
    """dq, dk, dv from residuals.  lse128: [B,H,Sq,128] lane-replicated
    logsumexp; dlse: [B,H,Sq] cotangent of the lse output or None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[-2]
    block_q = _fit_block(min(block_q, _BWD_BLOCK), sq)
    block_k = _fit_block(min(block_k, _BWD_BLOCK), sk)
    # a program of dq holds every key / of dkv every query (and no key
    # lies past the last row: every group of keys meets a part that
    # writes it)
    dq_single = sk == block_k
    dkv_single = sq == block_q and (not causal or q_offset + sq >= sk)

    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        di = di - dlse
    di128 = jnp.broadcast_to(di[..., None], (b, h, sq, 128))

    q_of, k_of = _index_maps(causal, block_q, block_k, sq, q_offset)

    def spec(rows, width, of, rev):
        # rev: grid is (b, h, kblock, qblock); else (b, h, qblock, kblock)
        if rev:
            return pl.BlockSpec(
                (1, 1, rows, width),
                lambda b_, h_, j, i: (b_, h_, of(i, j, True), 0))
        return pl.BlockSpec(
            (1, 1, rows, width),
            lambda b_, h_, i, j: (b_, h_, of(i, j, False), 0))

    def qspec(rev):
        return spec(block_q, d, q_of, rev)

    def kspec(rev):
        return spec(block_k, d, k_of, rev)

    def lanespec(rev):
        return spec(block_q, 128, q_of, rev)

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              q_offset=q_offset,
              diagonals=_diagonals(sq, sk, block_q, block_k, q_offset))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, single=dkv_single, **kw),
        grid=(b, h, sk // block_k, sq // block_q),
        in_specs=[qspec(True), kspec(True), kspec(True), qspec(True),
                  lanespec(True), lanespec(True)],
        out_specs=[kspec(True), kspec(True)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[] if dkv_single else [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse128, di128)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, single=dq_single, **kw),
        grid=(b, h, sq // block_q, sk // block_k),
        in_specs=[qspec(False), kspec(False), kspec(False), qspec(False),
                  lanespec(False), lanespec(False)],
        out_specs=qspec(False),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[] if dq_single else [
            pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse128, di128)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 1024,
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
                             block_q: int = 1024, block_k: int = 1024,
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
    # On a TPU the Pallas kernels are the path wherever they apply: the
    # ledger's train cells run them (`train-medium-1024`,
    # `train-xl-fsdp4`: forward, dk/dv and dq kernels, no XLA recompute
    # anywhere; 18.8% of medium's step at 24.8% of their roofline after
    # PR 41 taught them to skip what the causal mask hides, 31.2% at
    # 12.6% before).  XLA remains the portable path: CPU meshes, seqs
    # not a multiple of 128, and anything interpret-mode.
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

    Block defaults are per-path: the XLA scan wants small KV blocks
    (256 — deeper fusion per step); the pallas grid wants fat ones
    (1024 x 1024: at the train cells' S = 1024 one program holds a
    head's whole square, every grid step costs its fixed overhead and
    its DMA set-up, and a program that holds every key finishes its
    rows where it computes them, with no statistics carried in
    scratch).  The causal skipping happens inside the program, in
    sub-blocks whose extents are static (see `causal_work`): PERF.md
    section 6, PR 41, has the chip's readings at 512- and 1024-row
    blocks and 128- to 512-row sub-blocks.

    The Pallas kernel is a Mosaic custom call, which GSPMD cannot
    partition: on sharded arrays call it from inside a shard_map
    (models/gpt.py `attention_op` does).
    """
    sq, sk = q.shape[-2], k.shape[-2]
    impl = resolve_impl(impl, sq, sk, causal)
    # bottom-right-aligned causal mask for rectangular inputs, matching
    # mha_reference's tril(k=sk-sq) decode semantics
    qoff = (sk - sq) if (causal and sk > sq) else 0
    if impl == "pallas":
        return flash_attention(q, k, v, causal, scale, block_q or 1024,
                               block_k or 1024, False, qoff)
    if impl == "pallas_interpret":
        return flash_attention(q, k, v, causal, scale, block_q or 1024,
                               block_k or 1024, True, qoff)
    if impl == "xla":
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   block_k=block_k or 256,
                                   q_offset=(sk - sq) if causal else 0)
    if impl == "reference":
        return mha_reference(q, k, v, causal=causal, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------------------
# streamed attention: the serve path's attention over keys that arrive
# block by block (its XLA body is `_streamed_xla`, above)
# ---------------------------------------------------------------------------

# A query head must bring this many rows (and a multiple of them) for
# the block kernel: a prefill chunk does (512), a decode step (1) does not
_KERNEL_ROWS = 128
# query rows a program of the block kernel scores against a key block
_BLOCK_ROWS = 1024
# "no window", as the kernel's int32 operand: a window no context reaches
_NO_WINDOW = np.iinfo(np.int32).max


def streamed_attention_uses_kernel(rows: int, platform: Optional[str] = None
                                   ) -> bool:
    """Whether `streamed_attention` scores a call whose query heads bring
    `rows` rows each (T) with the Pallas block kernel: on a TPU, for 128
    rows or more (a prefill chunk).  Rows and platform decide, nothing
    else; `platform` None means the backend this process computes on."""
    if platform is None:
        platform = jax.default_backend()
    return (platform == "tpu" and rows >= _KERNEL_ROWS
            and rows % _KERNEL_ROWS == 0)


def _streamed_block_kernel(scale_ref, window_ref, q_ref, k_ref, v_ref,
                           qpos_ref, kpos_ref, *rest):
    """One key block's online-softmax update for one K/V head's block of
    query rows.  Keys lie along the sublanes and queries along the lanes
    (scores [S, R]), so a row's statistics are [1, R] rows: they reduce
    over sublanes, broadcast over sublanes, and travel as they lie in
    [.., T] arrays, with no transpose and no lane-replicated plane.  The
    accumulator is kept the same way round, [v_dim, R].  `rest`: m, l,
    acc in and out, behind `keep` [1, S, R] (above 0: a key its query
    keeps) where the call selects keys."""
    keep_ref = rest[0] if len(rest) == 7 else None
    m_ref, l_ref, acc_ref, m_out, l_out, acc_out = rest[-6:]
    q = q_ref[0, 0]                                       # [R, dh]
    k = k_ref[0, 0]                                       # [S, dh]
    v = v_ref[0, 0]                                       # [S, dv]
    s = _dot(k, q, _NT) * scale_ref[0]                    # [S, R] f32
    kpos = kpos_ref[0, :, :1]                             # [S, 1]
    # a hole among the keys (kpos < 0) lies past every query; and
    # 0 <= d < window is ONE unsigned comparison
    d = qpos_ref[0] - jnp.where(kpos >= 0, kpos, _NO_WINDOW)   # [S, R]
    ok = (jax.lax.bitcast_convert_type(d, jnp.uint32)
          < window_ref[0].astype(jnp.uint32))
    if keep_ref is not None:
        ok = ok & (keep_ref[0] > 0)
    s = jnp.where(ok, s, DEFAULT_MASK_VALUE)
    m = m_ref[0, 0]                                       # [1, R]
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    # a hidden score is DEFAULT_MASK_VALUE, so its p is exp(-huge) = 0
    # wherever the row has seen a key (m_new is a real score); a row
    # that has seen none (m_new still DEFAULT_MASK_VALUE: p = 1
    # throughout) is taken out a row at a time, not a score at a time
    seen = (m_new > DEFAULT_MASK_VALUE).astype(jnp.float32)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    m_out[0, 0] = m_new
    l_out[0, 0] = l_ref[0, 0] * corr + seen * jnp.sum(p, axis=0,
                                                      keepdims=True)
    acc_out[0, 0] = acc_ref[0, 0] * corr + seen * _dot(
        v, p.astype(v.dtype), _TN)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _streamed_block(scale, window, q, k, v, qpos, kpos, m, l, acc,
                    keep=None, interpret=False):
    """(m, l, acc) after key block (k, v, kpos): q [B, H, R, dh] at qpos
    [B, R], k [B, H, S, dh], v [B, H, S, dv], kpos [B, S]; m, l [B, H, R]
    and acc [B, H, dv, R] float32; `keep` [B, S, R] float32 (above 0: the
    key a query keeps, beside what position and window allow) where the
    call selects keys, one operand more of the same kernel.  ONE jitted
    function, with the scale
    and the window as operands: every call site of a program whose
    shapes agree (a chunk program's layers, windowed or not) shares one
    traced jaxpr and one lowered function — a Pallas call is traced and
    lowered to Mosaic in Python at every call site of every process,
    before the compile cache is asked, so a copy a layer is paid in warm
    set-up (PERF.md section 6, PR 46)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R, dh = q.shape
    S, dv = v.shape[-2:]
    rows = _fit_block(_BLOCK_ROWS, R)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def per_head(width, last):      # a K/V head's whole block
        return pl.BlockSpec((1, 1, width, last),
                            lambda b, h, r: (b, h, 0, 0))

    def per_rows(height):           # a program's rows, along the lanes
        return pl.BlockSpec((1, 1, height, rows),
                            lambda b, h, r: (b, h, 0, r))

    stat, acc_spec = per_rows(1), per_rows(dv)
    m, l = m[:, :, None], l[:, :, None]
    # the selection's operand: a block [S, rows] the heads of a row block
    # share (the grid's head axis turns inside it: fetched once)
    kept = [] if keep is None else [keep]
    first = 7 + len(kept)
    m, l, acc = pl.pallas_call(
        _streamed_block_kernel,
        grid=(B, H, R // rows),
        in_specs=[
            smem, smem,
            pl.BlockSpec((1, 1, rows, dh), lambda b, h, r: (b, h, r, 0)),
            per_head(S, dh), per_head(S, dv),
            pl.BlockSpec((1, 1, rows), lambda b, h, r: (b, 0, r)),
            pl.BlockSpec((1, S, 128), lambda b, h, r: (b, 0, 0)),
            *[pl.BlockSpec((1, S, rows), lambda b, h, r: (b, 0, r))
              for _ in kept],
            stat, stat, acc_spec],
        out_specs=[stat, stat, acc_spec],
        out_shape=[jax.ShapeDtypeStruct(m.shape, m.dtype),
                   jax.ShapeDtypeStruct(l.shape, l.dtype),
                   jax.ShapeDtypeStruct(acc.shape, acc.dtype)],
        input_output_aliases={first: 0, first + 1: 1, first + 2: 2},
        interpret=interpret,
        name="streamed_attention_block",
    )(scale, window, q, k, v, qpos[:, None],
      jnp.broadcast_to(kpos[:, :, None], kpos.shape + (128,)), *kept,
      m, l, acc)
    return m[:, :, 0], l[:, :, 0], acc


def _streamed_kernel_loop(q, qpos, fetch, n_blocks, window, scale, v_dim,
                          interpret=False):
    """streamed_attention's loop with the block kernel as its body.  A
    K/V head's G query heads are rows of one array (R = G * T rows at
    positions qpos tiled G times), so a program's products are as tall
    as the head count allows whatever the grouping."""
    B, Hkv, G, T, dh = q.shape
    R = G * T
    q = q.reshape(B, Hkv, R, dh)
    qpos = jnp.tile(qpos.astype(jnp.int32), (1, G))
    scale = jnp.full((1,), scale, jnp.float32)
    window = jnp.full((1,), _NO_WINDOW if window is None else window,
                      jnp.int32)

    def body(carry):
        i, *stats = carry
        k, v, kpos, *keep = fetch(i)
        # a selection [B, T, S] the way round the kernel scores: keys
        # down, the G heads' rows side by side
        kept = ([jnp.tile(jnp.swapaxes(keep[0], 1, 2).astype(jnp.float32),
                          (1, 1, G))] if keep else [])
        return (i + 1, *_streamed_block(scale, window, q, k, v, qpos,
                                        kpos.astype(jnp.int32), *stats,
                                        *kept, interpret=interpret))

    init = (jnp.int32(0),
            jnp.full((B, Hkv, R), DEFAULT_MASK_VALUE, jnp.float32),
            jnp.zeros((B, Hkv, R), jnp.float32),
            jnp.zeros((B, Hkv, v_dim, R), jnp.float32))
    # a while loop whatever the trip count: `fori_loop` makes a scan of a
    # static one, whose body jax rewrites (a new jaxpr object for the
    # same block function), and the program then holds a second lowered
    # copy of the kernel beside the traced loops'
    _, _, l, acc = jax.lax.while_loop(lambda c: c[0] < n_blocks, body, init)
    out = acc / jnp.maximum(l, 1e-30)[:, :, None]
    return jnp.swapaxes(out, 2, 3).reshape(B, Hkv, G, T, v_dim).astype(
        q.dtype)


def streamed_attention(q, qpos, fetch, n_blocks, *, window=None, scale=None,
                       v_dim=None):
    """Causal attention over keys that arrive block by block, with an
    online softmax: the serve path's recipe for caches too long to score
    at once, grouped-query heads and a sliding window included.

    q [B, Hkv, G, T, dh] (G query heads a K/V head) at positions qpos
    [B, T]; `fetch(i)` gives block i's keys and values [B, Hkv, S, dh]
    and their positions kpos [B, S] (negative: no key there).  Query t
    sees key s iff 0 <= qpos - kpos (< window, where a window is given).
    A fetch may hand a fourth item, `keep` [B, T, S] bools: the keys of
    the block each query KEEPS beside that (a learned selection:
    ops/select.keep_top; the heads of a call share it).
    `n_blocks` may be traced: only blocks 0..n_blocks-1 are fetched.
    A row that sees no key at all comes out zero.  Returns [B, Hkv, G, T,
    dh] in q's dtype; scores and statistics are f32.

    `scale` multiplies the scores (dh ** -0.5 where none is given) and
    `v_dim` is the width of the values where it is not the keys' (latent
    attention: keys [.., 576] — latent and rope part — against values that
    are the latent alone; or 192-wide keys with 128-wide values): the
    result is then [B, Hkv, G, T, v_dim].

    One algorithm, two bodies for a block, chosen by what the call can
    see (`streamed_attention_uses_kernel`: the rows a head brings and the
    platform).  A prefill chunk on a TPU (T >= 128) takes a Pallas
    kernel, `streamed_attention_block`: a block's scores, its masks and p
    live in VMEM only, where the XLA body (`_streamed_xla`) sends f32
    scores [heads, T, S] through memory between its two products (134 MB
    a block of a 512-row chunk of 128 heads: 40% of the chip's time in
    `serve-commandaplus-mixedctx` (retired at PR 55) and 55% in
    `serve-deepseekv3-longctx` before, PERF.md section 6, PR 46).  A
    decode step that comes here (T = 1, slots in B) and every other
    platform take the XLA body: a row a head gives THIS kernel nothing to
    keep on the chip.  Not every decode step comes here: DeepSeek's
    absorbed step over latent pages walks them on a TPU with
    `latent_decode_attention` (below) and fetches no block at all.
    The mathematics and the precision are the same: products on operands
    as they come, f32 scores, statistics and accumulator, p cast to the
    values' dtype."""
    T, dh = q.shape[-2:]
    if scale is None:
        scale = dh ** -0.5
    if v_dim is None:
        v_dim = dh
    if streamed_attention_uses_kernel(T):
        return _streamed_kernel_loop(q, qpos, fetch, n_blocks, window, scale,
                                     v_dim)
    return _streamed_xla(q, qpos, fetch, n_blocks, window, scale, v_dim)


# ---------------------------------------------------------------------------
# latent decode attention: the absorbed decode step's attention over latent
# pages, a walk of each live slot's own pages and of nothing else
# ---------------------------------------------------------------------------

# pages of one compute block of the walk: one online-softmax update, and
# one double-buffered fetch, for every so many pages (4 pages of 128
# positions are the XLA body's block of 512 keys)
_WALK_PAGES = 4


def latent_decode_uses_kernel(rows: int, platform: Optional[str] = None
                              ) -> bool:
    """Whether an absorbed call over latent pages whose slots bring `rows`
    query rows each walks the pages with `latent_decode_attention`: on a
    TPU, for one row a slot (the decode step).  Rows and platform decide,
    nothing else; `platform` None means the backend this process computes
    on."""
    if platform is None:
        platform = jax.default_backend()
    return platform == "tpu" and rows == 1


def latent_walked_keys(ctx, page_size: int):
    """Key positions `latent_decode_attention` fetches for contexts ctx
    [B]: every page a context reaches, whole."""
    return jnp.sum(-(-ctx // page_size)) * page_size


def _latent_decode_kernel(tab_ref, ctx_ref, scale_ref, q_ref, arena_ref,
                          *rest, v_dim: int, width: int):
    """One slot's walk.  A page [d, ps] IS its keys transposed: a block
    of pages lies side by side along the lanes of `buf` [2, d, S], scores
    are q [H, d] . block [d, S] with no transposing copy, and the weighted
    sum contracts p [H, S] with the block's first `v_dim` rows over S.
    `width`: entries of a slot's row of the (flattened) table.  `rest`:
    the output and the scratch, behind `keep` [1, blocks, S] (above 0: a
    walked position the query keeps) where the call selects keys."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    keep_ref = rest[0] if len(rest) == 5 else None
    o_ref, buf, sem, acc_ref = rest[-4:]
    b = pl.program_id(0)
    _, d, S = buf.shape
    ps = arena_ref.shape[-1]
    npb = S // ps
    ctx = ctx_ref[b]
    n_pages = (ctx + ps - 1) // ps
    n_blocks = (n_pages + npb - 1) // npb

    # what a DMA has not filled must still be finite: a key past the
    # context is hidden from the scores, and its p = 0 meets its value
    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def pages(blk, half, act):
        """`act` (start, or wait for) the copy of each page of block
        `blk` that the context reaches into half `half` of the buffer."""
        for j in range(npb):
            at = blk * npb + j

            @pl.when(at < n_pages)
            def _():
                act(pltpu.make_async_copy(
                    arena_ref.at[tab_ref[b * width + at]],
                    buf.at[half, :, pl.ds(j * ps, ps)], sem.at[half]))

    @pl.when(ctx == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(ctx > 0)
    def _():
        q = q_ref[0]                                          # [H, d]
        H = q.shape[0]
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        pages(0, 0, lambda cp: cp.start())

        def block(blk, carry):
            m, l = carry                                      # [H, 1]
            half = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                pages(blk + 1, 1 - half, lambda cp: cp.start())

            pages(blk, half, lambda cp: cp.wait())
            kv = buf[half]                                    # [d, S]
            s = _dot(q, kv, _NN) * scale_ref[0]               # [H, S] f32
            kpos = blk * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
            if keep_ref is not None:
                return selected(s, kpos, kv, keep_ref[0, pl.ds(blk, 1), :],
                                m, l)
            s = jnp.where(kpos < ctx, s, DEFAULT_MASK_VALUE)
            # a block's first key is inside the context, so m_new is a
            # real score and a hidden key's p is exp(-huge) = 0
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            acc_ref[...] = acc_ref[...] * corr + _dot(
                p.astype(kv.dtype), kv[:v_dim], _NT)
            return m_new, l * corr + jnp.sum(p, axis=1, keepdims=True)

        def selected(s, kpos, kv, keep, m, l):
            """The block's update where the query keeps some of its keys
            and a whole block may hold none: a row that has seen no key
            yet (m_new still the mask's value: p = 1 throughout) is taken
            out, as `_streamed_block_kernel` takes it."""
            s = jnp.where((kpos < ctx) & (keep > 0), s, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            seen = (m_new > DEFAULT_MASK_VALUE).astype(jnp.float32)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            acc_ref[...] = acc_ref[...] * corr + seen * _dot(
                p.astype(kv.dtype), kv[:v_dim], _NT)
            return m_new, l * corr + seen * jnp.sum(p, axis=1,
                                                    keepdims=True)

        _, l = jax.lax.fori_loop(
            0, n_blocks, block,
            (jnp.full((H, 1), DEFAULT_MASK_VALUE, jnp.float32),
             jnp.zeros((H, 1), jnp.float32)))
        if keep_ref is not None:
            l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("v_dim", "interpret"))
def _latent_decode(scale, q, arena, ptab, ctx, v_dim, keep=None,
                   interpret=False):
    """The kernel's one lowered function, the scale an operand: every
    latent layer of a step program that reads arenas of one shape calls
    this one (see `_streamed_block` for what a lowering a layer costs in
    warm set-up).  `keep` [B, blocks, S]: one operand more (a block of
    `_WALK_PAGES` pages a row, a slot's whole plane a grid turn)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    ps = arena.shape[-1]
    at_slot = lambda b, *_: (b, 0, 0)
    kept = [] if keep is None else [keep]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, H, d), at_slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  *[pl.BlockSpec((1,) + keep.shape[1:], at_slot)
                    for _ in kept]],
        out_specs=pl.BlockSpec((1, H, v_dim), at_slot),
        scratch_shapes=[pltpu.VMEM((2, d, _WALK_PAGES * ps), arena.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((H, v_dim), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, v_dim=v_dim,
                          width=ptab.shape[1]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="latent_decode_attention",
    )(ptab.reshape(-1), ctx, scale, q, arena, *kept)


def latent_decode_attention(q, arena, ptab, ctx, *, scale: float,
                            v_dim: int, keep=None, interpret: bool = False):
    """The absorbed decode step's attention, as work in proportion to the
    live slots' own contexts: slot b's query heads q[b] [H, d] against
    the first ctx[b] positions of its own pages — page ptab[b, i] of
    `arena` [pages, d, page_size] holds positions i * page_size onward,
    one position a column (`deepseek_v3.init_paged_cache`), keys d wide
    whose first `v_dim` values are the values too.  Returns [B, H, v_dim]
    in q's dtype.

    One Pallas kernel, a grid turn a slot.  A slot with ctx 0 (an empty
    one) fetches nothing and comes out zero.  A live slot walks pages
    0 .. (ctx - 1) // page_size of its own table row and stops: the
    kernel's own double-buffered DMA copies them, `_WALK_PAGES` a compute
    block, from where they lie in HBM (the arena is never gathered, and
    what lies past a context's last page — the table's other entries,
    the null page — is not read); an online softmax across the blocks
    keeps (m, l, acc) on the chip for the whole walk, the last block
    masked by the context.  The mathematics and the precision are
    `_streamed_xla`'s: operands as they come, f32 scores, statistics and
    accumulator, p cast to the values' dtype for the second product.

    `keep` [B, R * page_size] bools, where given, says which of the walked
    positions — position j of the walk lies in entry j // page_size of the
    slot's row — the query KEEPS: a learned selection over a full table
    (ops/select.keep_top), or what a window leaves of a RING, whose
    entries are in no order the kernel knows (`ctx` is then the positions
    to walk, as many whole entries as hold something, and the caller's
    mask says what each holds: `deepseek_v3.page_io`).  Every page a
    context reaches is still read: the selection saves scores' weight in
    the softmax and not a byte (what a gathering form would save is
    `dsa_keys_walked` over `dsa_keys_selected`, models/dots3.py).  A slot
    that keeps nothing comes out zero.

    What it replaces: the XLA body gives every slot of the batch, empty
    or not, every block up to the LONGEST live context, each block
    gathered and transposed first (26% of the chip's time in
    `serve-deepseekv3-longctx`, PERF.md section 6, PR 48)."""
    if keep is not None:        # [B, blocks, S]: a block of the walk a row
        S = _WALK_PAGES * arena.shape[-1]
        B, n = keep.shape
        keep = jnp.pad(keep.astype(jnp.float32),
                       ((0, 0), (0, -n % S))).reshape(B, -1, S)
    return _latent_decode(jnp.full((1,), scale, jnp.float32), q, arena,
                          ptab.astype(jnp.int32), ctx.astype(jnp.int32),
                          v_dim=v_dim, keep=keep, interpret=interpret)


# ---------------------------------------------------------------------------
# paged decode attention: a decode step's attention over pages of keys and
# values a head wide, a walk of each live slot's own pages and of nothing
# else (`latent_decode_attention`'s scheme for a cache with a K and a V side)
# ---------------------------------------------------------------------------

_ROWS = 8           # query rows a K/V head brings, padded to whole sublanes


def _paged_decode_kernel(tab_ref, base_ref, qpos_ref, walk_ref, window_ref,
                         scale_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                         sem, acc_ref, *, heads: int, width: int):
    """One slot's walk.  A page [ps, heads * d] holds a position a row, a
    head's keys d lanes of it: a block of pages lies one under the other
    in `kbuf` / `vbuf` [2, S, heads * d], a head's scores are q [8, d] .
    block [S, d] contracted over d, and its weighted sum p [8, S] . block
    [S, d].  `width`: entries of a slot's row of the (flattened) table and
    of its bases."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    _, S, hd = kbuf.shape
    d = hd // heads
    ps = k_hbm.shape[1]
    npb = S // ps
    qpos, window = qpos_ref[b], window_ref[0]
    n_blocks = (walk_ref[b] + npb - 1) // npb

    # what a DMA has not filled must still be finite: a key the query does
    # not see is hidden from the scores, and its p = 0 meets its value
    @pl.when(b == 0)
    def _():
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    def base_of(at):
        """The first position entry `at` of the slot's row holds; below 0
        where it holds nothing the query sees (no page, every position
        past the query or a window or more behind it)."""
        base = base_ref[b * width + jnp.minimum(at, width - 1)]
        seen = ((at < width) & (base >= 0) & (base <= qpos)
                & (qpos - (base + ps - 1) < window))
        return jnp.where(seen, base, -1)

    def pages(blk, half, act):
        """`act` (start, or wait for) the copies of each page of block
        `blk` that the query sees into half `half` of the buffers."""
        for j in range(npb):
            at = blk * npb + j

            @pl.when(base_of(at) >= 0)
            def _():
                page = tab_ref[b * width + at]
                rows = pl.ds(j * ps, ps)
                act(pltpu.make_async_copy(
                    k_hbm.at[page], kbuf.at[half, rows], sem.at[0, half]))
                act(pltpu.make_async_copy(
                    v_hbm.at[page], vbuf.at[half, rows], sem.at[1, half]))

    @pl.when(n_blocks == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n_blocks > 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        pages(0, 0, lambda cp: cp.start())
        within = jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)

        def block(blk, carry):
            m, l = carry                                  # [heads * 8, 1]
            half = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                pages(blk + 1, 1 - half, lambda cp: cp.start())

            pages(blk, half, lambda cp: cp.wait())
            # the block's key positions, page by page; a page that was not
            # fetched lies past every query
            kpos = jnp.concatenate([
                jnp.where(base_of(blk * npb + j) >= 0,
                          base_of(blk * npb + j) + within, _NO_WINDOW)
                for j in range(npb)], axis=1)             # [1, S]
            # 0 <= qpos - kpos < window is ONE unsigned comparison
            seen = (jax.lax.bitcast_convert_type(qpos - kpos, jnp.uint32)
                    < window.astype(jnp.uint32))
            ms, ls = [], []
            for h in range(heads):
                rows, lanes = slice(h * _ROWS, (h + 1) * _ROWS), slice(
                    h * d, (h + 1) * d)
                k, v = kbuf[half, :, lanes], vbuf[half, :, lanes]
                s = _dot(q_ref[0, rows], k, _NT) * scale_ref[0]   # [8, S]
                s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
                m_new = jnp.maximum(m[rows], jnp.max(s, axis=1,
                                                     keepdims=True))
                # a row that has seen no key yet (m_new still the mask's
                # value: p = 1 throughout) is taken out a row at a time
                any_seen = (m_new > DEFAULT_MASK_VALUE).astype(jnp.float32)
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m[rows] - m_new)
                acc_ref[rows] = acc_ref[rows] * corr + any_seen * _dot(
                    p.astype(v.dtype), v, _NN)
                ms.append(m_new)
                ls.append(l[rows] * corr
                          + any_seen * jnp.sum(p, axis=1, keepdims=True))
            return jnp.concatenate(ms, 0), jnp.concatenate(ls, 0)

        _, l = jax.lax.fori_loop(
            0, n_blocks, block,
            (jnp.full((heads * _ROWS, 1), DEFAULT_MASK_VALUE, jnp.float32),
             jnp.zeros((heads * _ROWS, 1), jnp.float32)))
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode(scale, window, q, k_arena, v_arena, ptab, bases, qpos, walk,
                  interpret=False):
    """The kernel's one lowered function, the scale and the window
    operands: every layer of a step program that reads pages of one shape
    calls this one (see `_streamed_block` for what a lowering a layer costs
    in warm set-up)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R, d = q.shape
    ps, hd = k_arena.shape[1:]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, _ROWS - R), (0, 0))).reshape(
        B, H * _ROWS, d)
    at_slot = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, H * _ROWS, d), at_slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H * _ROWS, d), at_slot),
        scratch_shapes=[pltpu.VMEM((2, _WALK_PAGES * ps, hd), k_arena.dtype),
                        pltpu.VMEM((2, _WALK_PAGES * ps, hd), v_arena.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((H * _ROWS, d), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, heads=H, width=ptab.shape[1]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H * _ROWS, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_decode_attention",
    )(ptab.reshape(-1), bases.reshape(-1), qpos, walk, window, scale, q,
      k_arena, v_arena)
    return out.reshape(B, H, _ROWS, d)[:, :, :R]


def paged_decode_attention(q, k_arena, v_arena, ptab, bases, qpos, walk, *,
                           scale: float, window: Optional[int] = None,
                           interpret: bool = False):
    """A decode step's attention over pages of keys and values, as work in
    proportion to what the live slots' queries see: slot b's query rows
    q[b] [H, R, d] (R rows a K/V head, at most 8: its grouped query heads)
    at position qpos[b] against its own pages — entry i of its table row
    ptab[b] is a page of `k_arena` / `v_arena` [pages, page_size, H * d]
    that holds positions bases[b, i] onward (negative: nothing), a
    position a row, head h's keys (values) lanes h * d onward; a full
    kind's bases run i * page_size, a windowed kind's are its ring's.
    The query sees key s iff 0 <= qpos - s (< window, where one is given).
    Entries 0 .. walk[b] - 1 are walked (0: an empty slot, which fetches
    nothing and comes out zero).  Returns [B, H, R, d] in q's dtype.

    `latent_decode_attention`'s walk for a cache with two sides: one
    Pallas kernel, a grid turn a slot; of the entries walked only the
    pages that hold a position the query sees are fetched, the kernel's
    own double-buffered DMA copying `_WALK_PAGES` entries of each side a
    compute block from where they lie in HBM; an online softmax across
    the blocks keeps (m, l, acc) on the chip.  The mathematics and the
    precision are `_streamed_xla`'s.

    What it replaces: the XLA body gives every slot of the batch, empty or
    not, every block up to the LONGEST live context (or the whole ring),
    each block gathered first — for phi4flash's one shared cache eight
    times a step (PERF.md section 6, PR 51)."""
    i32 = lambda a: a.astype(jnp.int32)
    return _paged_decode(
        jnp.full((1,), scale, jnp.float32),
        jnp.full((1,), _NO_WINDOW if window is None else window, jnp.int32),
        q, k_arena, v_arena, i32(ptab), i32(bases), i32(qpos), i32(walk),
        interpret=interpret)


# ---------------------------------------------------------------------------
# a learned indexer's scoring pass: which keys a query is to keep, before
# any key a head wide (or a latent wide) is scored
# ---------------------------------------------------------------------------


def indexer_scores(q, w, fetch, n_blocks, block: int, width: int):
    """The index scores of queries against an indexer's own cached keys,
    block by block: q [B, T, Hi, di] (Hi small heads), w [B, T, Hi]
    float32 (a head's weight, a query's own), `fetch(i)` -> (block i's
    index keys [B, block, di], their positions) as `streamed_attention`'s
    fetch hands latents.  Returns I [B, T, width] float32, position s of
    the table's order:

        I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])

    for s in blocks 0..n_blocks-1 (`n_blocks` may be traced) and -inf past
    them.  Nothing is masked here: what a query may see is the caller's to
    say when it selects (ops/select.keep_top).  The products are formed on
    the operands as they come, the scores and their sum in float32."""
    B, T = q.shape[:2]

    def body(i, out):
        k, _ = fetch(i)
        s = jnp.einsum("bthd,bsd->bths", q, k,
                       preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.einsum("bths,bth->bts", jax.nn.relu(s), w),
            i * block, 2)

    return jax.lax.fori_loop(
        0, n_blocks, body, jnp.full((B, T, width), -jnp.inf, jnp.float32))
