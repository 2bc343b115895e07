"""Block-wise int8 quantization: the compressed-collective building block.

EQuARX ("Efficient Quantized AllReduce in XLA", PAPERS.md) recovers ~2x
allreduce speedups by moving gradients as int8 blocks with per-block
scales instead of f32.  This module provides the quantize/dequantize
primitives that `collective/compression.py` and the quantized
`collective/xla_group.py` collectives compose:

  * Pallas TPU kernels — per-block absmax reduction, scale, round (round
    half-to-even, or stochastic via the on-core PRNG) fused in VMEM, so
    the quantize never round-trips HBM per block.
  * An XLA-lowered fallback with IDENTICAL numerics (same rounding mode,
    same scale formula), so CPU meshes and tier-1 tests exercise the
    real arithmetic, not a mock.

Layout contract (shared with the collectives): an array is flattened,
zero-padded to a multiple of `block_size`, and viewed as
[nblocks, block_size]; block b covers flat elements
[b*block_size, (b+1)*block_size).  scales[b] = absmax(block b)/127 (1.0
for an all-zero block), values are the clipped rounded ratios in int8.
Zero padding quantizes to exact zeros, so the trailing remainder of a
non-multiple array survives a round trip untouched.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

INT8_MAX = 127.0


def padded_len(n: int, block_size: int) -> int:
    """Smallest multiple of block_size >= n."""
    return n + (-n) % block_size


def num_blocks(n: int, block_size: int) -> int:
    return padded_len(n, block_size) // block_size


def _as_blocks(x, block_size: int):
    """Flatten + zero-pad to [nblocks, block_size] f32."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = padded_len(n, block_size) - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block_size)


def _block_scales(blocks):
    absmax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    return jnp.where(absmax > 0, absmax / INT8_MAX, 1.0)


# ---------------------------------------------------------------------------
# XLA fallback (CPU/TPU, in-jit traceable — the tier-1 numerics path)
# ---------------------------------------------------------------------------


def _quantize_xla(blocks, stochastic: bool, key):
    scales = _block_scales(blocks)
    # reciprocal-multiply, in lockstep with compression.compress_array:
    # 1/scale rounds identically under IEEE on numpy and XLA, so the host
    # codec stays bit-exact with this path
    y = blocks * (1.0 / scales)
    if stochastic:
        # unbiased: floor(y + u), u ~ U[0,1) — E[q] = y exactly
        u = jax.random.uniform(key, y.shape, jnp.float32)
        q = jnp.floor(y + u)
    else:
        q = jnp.round(y)  # round half-to-even, same as the kernel
    q = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scales[:, 0]


def _dequantize_xla(q_blocks, scales):
    return q_blocks.astype(jnp.float32) * scales[:, None]


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------

# Rows of [block_size] blocks handled per grid step; int8 tiles are
# (32, 128) so stay a multiple of 32 sublanes.
_KERNEL_ROWS = 32


def _quantize_kernel(seed_ref, x_ref, q_ref, s_ref, *, stochastic: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = x_ref[:]                                        # [rows, block] f32
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / INT8_MAX, 1.0)
    y = x * (1.0 / scale)   # lockstep with _quantize_xla / host codec
    if stochastic:
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(y.shape), jnp.int32)
        # top 24 bits -> u in [0, 1); floor(y + u) is unbiased.  Mosaic
        # has no uint32 -> f32 cast, so the shift is a signed (sign-
        # extending) one and the mask puts the value back in [0, 2^24)
        u = ((bits >> 8) & 0xFFFFFF).astype(jnp.float32) * (1.0 / (1 << 24))
        y = jnp.floor(y + u)
    else:
        y = jnp.round(y)
    q_ref[:] = jnp.clip(y, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    # scales ride as a lane-replicated [rows, 128] plane (sublane->lane
    # transposes don't lower; same layout trick as attention's LSE)
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:, :1]


def _dequant_accum_kernel(q_ref, s_ref, o_ref):
    # q [world, rows, block] int8, s [world, rows, 128] lane-replicated
    # scales -> o [rows, block] f32: dequantize every peer's rows and
    # accumulate in VMEM, so the [world, n] f32 expansion of the separate
    # dequantize-then-sum path never exists in HBM.
    q = q_ref[:].astype(jnp.float32)
    o_ref[:] = jnp.sum(q * s_ref[:, :, :1], axis=0)


def _pad_rows(blocks, rows_mult: int):
    nblocks = blocks.shape[0]
    pad = (-nblocks) % rows_mult
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
    return blocks, nblocks


def _quantize_pallas(blocks, stochastic: bool, seed, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_size = blocks.shape[1]
    blocks, nblocks = _pad_rows(blocks, _KERNEL_ROWS)
    rows = blocks.shape[0]
    kernel = functools.partial(_quantize_kernel, stochastic=stochastic)
    seed_arr = jnp.asarray([seed], jnp.int32)
    if interpret and stochastic:
        # the plain interpreter has no rule for the on-core PRNG; the
        # TPU interpreter emulates it
        interpret = pltpu.InterpretParams()
    q, s = pl.pallas_call(
        kernel,
        grid=(rows // _KERNEL_ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_KERNEL_ROWS, block_size), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_KERNEL_ROWS, block_size), lambda i: (i, 0)),
            pl.BlockSpec((_KERNEL_ROWS, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, block_size), jnp.int8),
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(seed_arr, blocks)
    return q[:nblocks], s[:nblocks, 0]


def _dequantize_pallas(q_blocks, scales, interpret: bool):
    from jax.experimental import pallas as pl

    block_size = q_blocks.shape[1]
    q_blocks, nblocks = _pad_rows(q_blocks, _KERNEL_ROWS)
    rows = q_blocks.shape[0]
    s128 = jnp.broadcast_to(scales[:, None], (nblocks, 128))
    if rows != nblocks:
        s128 = jnp.pad(s128, ((0, rows - nblocks), (0, 0)))
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(rows // _KERNEL_ROWS,),
        in_specs=[
            pl.BlockSpec((_KERNEL_ROWS, block_size), lambda i: (i, 0)),
            pl.BlockSpec((_KERNEL_ROWS, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_KERNEL_ROWS, block_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block_size), jnp.float32),
        interpret=interpret,
    )(q_blocks, s128)
    return out[:nblocks]


def _dequant_accum_pallas(q, scales, world: int, block_size: int,
                          interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nblk = scales.shape[0] // world
    q3 = q.reshape(world, nblk, block_size)
    s3 = jnp.broadcast_to(scales.reshape(world, nblk, 1), (world, nblk, 128))
    rows = nblk
    pad = (-rows) % _KERNEL_ROWS
    if pad:
        q3 = jnp.pad(q3, ((0, 0), (0, pad), (0, 0)))
        s3 = jnp.pad(s3, ((0, 0), (0, pad), (0, 0)))
        rows += pad
    out = pl.pallas_call(
        _dequant_accum_kernel,
        grid=(rows // _KERNEL_ROWS,),
        in_specs=[
            pl.BlockSpec((world, _KERNEL_ROWS, block_size),
                         lambda i: (0, i, 0)),
            pl.BlockSpec((world, _KERNEL_ROWS, 128), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((_KERNEL_ROWS, block_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block_size), jnp.float32),
        interpret=interpret,
    )(q3, s3)
    return out[:nblk].reshape(-1)


def _pick_impl(impl: str, block_size: int) -> str:
    if impl != "auto":
        return impl
    # pallas wants a lane-aligned block; anything else takes the XLA path
    if jax.default_backend() == "tpu" and block_size % 128 == 0:
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def quantize_blockwise(x, block_size: int = 256, *, stochastic: bool = False,
                       key=None, seed: int = 0,
                       impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Quantize any-shape float array to (values int8 [npad], scales f32
    [nblocks]) under the module's block layout.  Traceable (fixed shapes
    given static block_size), so it composes into shard_map collectives.

    stochastic: unbiased stochastic rounding — `key` (jax PRNG key) on
    the XLA path, `seed` (int32) on the pallas path.
    """
    if block_size <= 0:
        raise ValueError(f"block_size must be positive, got {block_size}")
    blocks = _as_blocks(x, block_size)
    impl = _pick_impl(impl, block_size)
    if impl in ("pallas", "pallas_interpret"):
        q, s = _quantize_pallas(blocks, stochastic, seed,
                                interpret=(impl == "pallas_interpret"))
    elif impl == "xla":
        if stochastic and key is None:
            key = jax.random.PRNGKey(seed)
        q, s = _quantize_xla(blocks, stochastic, key)
    else:
        raise ValueError(f"unknown quantize impl {impl!r}")
    return q.reshape(-1), s


def dequantize_blockwise(q, scales, shape, dtype, block_size: int = 256,
                         impl: str = "auto") -> jax.Array:
    """Inverse of quantize_blockwise: back to `shape`/`dtype`, dropping
    the zero padding."""
    q_blocks = q.reshape(-1, block_size)
    impl = _pick_impl(impl, block_size)
    if impl in ("pallas", "pallas_interpret"):
        out = _dequantize_pallas(q_blocks, scales,
                                 interpret=(impl == "pallas_interpret"))
    elif impl == "xla":
        out = _dequantize_xla(q_blocks, scales)
    else:
        raise ValueError(f"unknown quantize impl {impl!r}")
    n = 1
    for d in shape:
        n *= d
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


def dequantize_accumulate(q, scales, world: int, block_size: int = 256,
                          impl: str = "auto") -> jax.Array:
    """Fused dequantize-and-reduce of `world` peers' quantized blocks.

    q is int8 [world * n] (n a block multiple), scales f32
    [world * n/block_size]; returns f32 [n] = sum over peers of their
    dequantized contribution — the accumulate half of the quantized
    reduce-scatter.  On the pallas path the int8 load, scale multiply
    and the sum over peers happen in one VMEM pass; the XLA fallback
    lowers the identical expression (same accumulation structure and f32
    dtype), so CPU tier-1 exercises the same numerics."""
    impl = _pick_impl(impl, block_size)
    if impl in ("pallas", "pallas_interpret"):
        return _dequant_accum_pallas(q, scales, world, block_size,
                                     interpret=(impl == "pallas_interpret"))
    if impl == "xla":
        q3 = q.reshape(world, -1, block_size).astype(jnp.float32)
        return (q3 * scales.reshape(world, -1)[:, :, None]).sum(
            axis=0).reshape(-1)
    raise ValueError(f"unknown quantize impl {impl!r}")


# ---------------------------------------------------------------------------
# Fused quantize -> shard-exchange -> accumulate (single TPU kernel)
# ---------------------------------------------------------------------------

# One VMEM-resident kernel per device does the whole reduce-scatter hop:
# quantize all per-peer sub-chunks, push each peer its int8 chunk + scales
# over the interconnect with async remote DMA, and dequantize-accumulate
# arrivals — no HBM round trip between the stages, which is the EQuARX
# fusion argument.  Deterministic rounding only (the staged path serves
# stochastic).  The exchange at offset o is the cyclic shift my->my+o+1,
# so every device sends and receives on the same semaphore slot and one
# descriptor's wait() covers both directions (the ring-collective pattern
# from the TPU guide, generalized to all-to-all).

_FUSED_COLLECTIVE_ID = 13


def _fused_rs_kernel(x_ref, o_ref, qs, ss, qr, sr, send_sem, recv_sem,
                     *, axis: str, world: int, nblk: int, block: int,
                     use_barrier: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    my = jax.lax.axis_index(axis)
    b = x_ref[:].reshape(world * nblk, block)
    absmax = jnp.max(jnp.abs(b), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / INT8_MAX, 1.0)
    q = jnp.clip(jnp.round(b * (1.0 / scale)), -INT8_MAX,
                 INT8_MAX).astype(jnp.int8)
    qs[:] = q.reshape(world, nblk, block)
    ss[:] = jnp.broadcast_to(scale.reshape(world, nblk, 1),
                             (world, nblk, 128))
    # every peer must have its recv buffers live before anyone writes;
    # interpret mode has no barrier primitive (its DMA emulation is
    # already globally ordered), so the barrier only runs compiled
    if use_barrier:
        bar = pltpu.get_barrier_semaphore()
        for off in range(world - 1):
            pltpu.semaphore_signal(
                bar, inc=1, device_id=jax.lax.rem(my + off + 1, world),
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bar, world - 1)
    copies = []
    for off in range(world - 1):
        dst = jax.lax.rem(my + off + 1, world)
        # remote row index = sender id, so arrivals never collide
        cp_q = pltpu.make_async_remote_copy(
            src_ref=qs.at[dst], dst_ref=qr.at[my],
            send_sem=send_sem.at[0, off], recv_sem=recv_sem.at[0, off],
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        cp_s = pltpu.make_async_remote_copy(
            src_ref=ss.at[dst], dst_ref=sr.at[my],
            send_sem=send_sem.at[1, off], recv_sem=recv_sem.at[1, off],
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        cp_q.start()
        cp_s.start()
        copies.append((cp_q, cp_s))
    # own contribution stays local: VMEM copy overlaps the in-flight DMAs
    own = pl.ds(my, 1)
    qr[own] = qs[own]
    sr[own] = ss[own]
    for cp_q, cp_s in copies:
        cp_q.wait()
        cp_s.wait()
    o_ref[:] = jnp.sum(qr[:].astype(jnp.float32) * sr[:, :, :1], axis=0)


def fused_reduce_scatter(x2d, axis: str, block_size: int = 256,
                         interpret: bool = False) -> jax.Array:
    """One-kernel quantized reduce-scatter hop, called inside a shard_map
    body.  x2d is this device's [world, sub] f32 contributions (sub a
    multiple of block_size); returns f32 [sub]: the sum over all peers of
    their (once-quantized) contribution to this device's chunk.

    TPU-only (remote DMA); numerics match
    quantize_blockwise -> all_to_all -> dequantize_accumulate, which is
    the XLA-lowered fallback the CPU tier-1 suite exercises."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    world, sub = x2d.shape
    if sub % block_size:
        raise ValueError(f"fused_reduce_scatter needs sub ({sub}) to be a "
                         f"multiple of block_size ({block_size})")
    nblk = sub // block_size
    kernel = functools.partial(_fused_rs_kernel, axis=axis, world=world,
                               nblk=nblk, block=block_size,
                               use_barrier=not interpret)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nblk, block_size), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((world, nblk, block_size), jnp.int8),
            pltpu.VMEM((world, nblk, 128), jnp.float32),
            pltpu.VMEM((world, nblk, block_size), jnp.int8),
            pltpu.VMEM((world, nblk, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, world - 1)),
            pltpu.SemaphoreType.DMA((2, world - 1)),
        ],
        # no DCE risk (o_ref is a consumed output), so only the
        # collective id for the cross-device barrier semaphore is needed
        compiler_params=pltpu.CompilerParams(
            collective_id=_FUSED_COLLECTIVE_ID),
        interpret=interpret,
    )(x2d)
    return out.reshape(-1)


def fused_rs_vmem_bytes(world: int, sub: int) -> int:
    """VMEM footprint estimate for fused_reduce_scatter (input + output +
    scratch); callers chunk until this fits comfortably on-core."""
    nblk_bytes = (sub // 256 + 1) * 128 * 4
    return world * (sub * 4 + 2 * sub + 2 * nblk_bytes) + sub * 4


def quantization_error(x, block_size: int = 256, impl: str = "xla"):
    """x - deq(quant(x)): the per-call compression error (what error
    feedback accumulates).  Deterministic rounding only — the stochastic
    path's error depends on the drawn bits."""
    q, s = quantize_blockwise(x, block_size, impl=impl)
    return x - dequantize_blockwise(q, s, x.shape, x.dtype, block_size,
                                    impl=impl)
