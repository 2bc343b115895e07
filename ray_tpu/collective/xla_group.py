"""Compiled (XLA) collectives over a device mesh — the NCCL-group analog.

The reference's NCCL collective group issues runtime library calls per
operation (reference: nccl_collective_group.py:830 LoC of stream/comm
management).  On TPU the idiomatic equivalent is *compiled* collectives:
`shard_map` over a `jax.sharding.Mesh` lowers `lax.psum`/`all_gather`/
`psum_scatter`/`ppermute`/`all_to_all` to ICI/DCN programs fused into the
surrounding computation.  These helpers give that capability the shape of a
collective API for code that isn't already inside a pjit program; inside
one, use `jax.lax` primitives directly.

All helpers are single-controller: they operate on (possibly sharded) global
arrays over the local mesh.  The multi-process story is the Train backend
(jax.distributed + the same compiled collectives across hosts).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ray_tpu.collective.compression import (CompressionConfig,
                                            auto_pipeline_chunks,
                                            chunk_layout, parse_compression,
                                            result_block_size, wire_ratio)
from ray_tpu.ops.quantize import (dequantize_accumulate, dequantize_blockwise,
                                  fused_reduce_scatter, fused_rs_vmem_bytes,
                                  padded_len, quantize_blockwise)
from ray_tpu.util import tracing

import os
import time


def _record_mesh_op(op: str, t0: float, x, cc: Optional[CompressionConfig],
                    breakdown: Optional[dict] = None) -> None:
    """Report dispatch time + byte counters to the flight recorder.
    Dispatch-side only — no forced fence here: blocking the hot path to
    measure it would serialize the very overlap XLA buys us.  Device
    time lands in the step's fenced total instead.  `breakdown` carries
    measured quantize/transfer/dequantize sub-phase seconds when the
    caller ran the staged (fenced) profiling path."""
    try:
        from ray_tpu.telemetry import recorder as _rec

        nbytes = float(getattr(x, "nbytes", 0) or 0)
        wire = None
        if nbytes and cc is not None:
            itemsize = getattr(getattr(x, "dtype", None), "itemsize", 4)
            wire = nbytes * wire_ratio(x.size, cc,
                                       baseline_itemsize=itemsize)
        _rec.record_collective(op, time.perf_counter() - t0, nbytes, wire,
                               breakdown=breakdown)
    except Exception:
        pass


def _axis(mesh: Mesh, axis_name: Optional[str]) -> str:
    if axis_name is None:
        if len(mesh.axis_names) != 1:
            raise ValueError(f"specify axis_name for multi-axis mesh "
                             f"{mesh.axis_names}")
        return mesh.axis_names[0]
    return axis_name


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op"))
def _allreduce_impl(x, mesh: Mesh, axis: str, op: str):
    spec = P(axis)

    def f(shard):
        if op == "sum":
            return jax.lax.psum(shard, axis)
        if op == "max":
            return jax.lax.pmax(shard, axis)
        if op == "min":
            return jax.lax.pmin(shard, axis)
        if op == "mean":
            return jax.lax.pmean(shard, axis)
        raise ValueError(f"unknown reduce op {op}")

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=spec, out_specs=spec)(x)


def _resolve_chunks(cc: CompressionConfig, n_elements: int,
                    itemsize: int) -> int:
    if cc.pipeline_chunks:
        return cc.pipeline_chunks
    return auto_pipeline_chunks(n_elements, itemsize, jax.default_backend())


# Largest per-chunk VMEM footprint the fused single-kernel reduce-scatter
# will accept before falling back to the staged kernels (quantize kernel
# -> all_to_all -> dequant-accumulate kernel).
_FUSED_RS_VMEM_CAP = 8 << 20


def _resolve_rs_impl(impl: str, world: int, block: int, stochastic: bool,
                     max_chunk_elems: int) -> str:
    """Pick how the reduce-scatter phase runs.  "fused" = the one-kernel
    quantize->remote-DMA-exchange->accumulate path (TPU only,
    deterministic rounding only, chunk must fit VMEM);
    "fused_interpret" forces the same kernel through the pallas
    interpreter (CPU tests); anything else takes the XLA-lowered
    fallback with identical numerics."""
    if impl != "auto":
        return impl
    if os.environ.get("RAY_TPU_FUSED_RS", "1") in ("0", "false", "off"):
        return "xla"
    if (jax.default_backend() == "tpu" and not stochastic
            and block % 128 == 0 and world > 1
            and fused_rs_vmem_bytes(world, max_chunk_elems)
            <= _FUSED_RS_VMEM_CAP):
        return "fused"
    return "xla"


def mesh_allreduce(x, mesh: Mesh, axis_name: Optional[str] = None,
                   op: str = "sum",
                   compression: Union[None, str, CompressionConfig] = None,
                   seed: int = 0, impl: str = "auto",
                   profile: bool = False):
    """Allreduce a leading-axis-sharded array across a mesh axis.

    x has a per-device leading chunk layout [n_dev * k, ...]; each device's
    chunk is reduced with its peers' — the allreduce of the NCCL API, but
    compiled (reference API: collective.py:258 allreduce).

    compression: a CompressionConfig / spec string ("int8", "int8:block=512")
    switches to the EQuARX-style two-phase quantized path: blockwise int8
    quantize → all_to_all (the reduce-scatter phase) → fused
    dequantize+accumulate → requantize → all_gather → dequantize.  Wire
    traffic drops ~4x; result carries quantization error (sum/mean only).
    `seed` feeds stochastic rounding when the config asks for it.

    The quantized path is chunked and pipelined per
    `CompressionConfig.pipeline_chunks` (0 = auto): the tensor is split
    into block-aligned chunks emitted so quantization of chunk k+1
    overlaps the exchange of chunk k and the accumulate of chunk k-1
    (XLA's latency-hiding scheduler does the overlap; chunk results are
    bit-identical to the monolithic path for deterministic rounding).
    On TPU, each chunk's reduce-scatter hop runs as ONE pallas kernel
    (quantize -> remote DMA exchange -> accumulate, never leaving VMEM);
    `impl` overrides the choice ("fused", "fused_interpret", "xla").

    profile=True runs the same numerics as separate fenced stage
    programs and reports measured quantize/transfer/dequantize sub-phase
    seconds to the flight recorder — attribution mode for bench/debug;
    the fused path stays the production default because the fences
    serialize the very overlap the pipeline buys."""
    axis = _axis(mesh, axis_name)
    cc = parse_compression(compression)
    t0 = time.perf_counter()
    breakdown = None
    with tracing.span("collective.mesh_allreduce", axis=axis, op=op,
                      compressed=cc is not None):
        if cc is None:
            out = _allreduce_impl(x, mesh, axis, op)
        else:
            if op not in ("sum", "mean"):
                raise ValueError(f"compressed allreduce supports op in "
                                 f"('sum', 'mean'), got {op!r}")
            if profile:
                out, breakdown = _q_allreduce_profiled(
                    x, jnp.int32(seed), mesh, axis, op, cc, impl)
            else:
                chunks = _resolve_chunks(cc, x.size, x.dtype.itemsize)
                out = _q_allreduce_impl(x, jnp.int32(seed), mesh, axis, op,
                                        cc.block_size, cc.stochastic,
                                        chunks, impl)
    _record_mesh_op("mesh_allreduce", t0, x, cc, breakdown)
    return out


# ---------------------------------------------------------------------------
# Quantized (EQuARX-style) variants.  Same shard_map in/out contracts as the
# full-precision impls above; inside the body the payload moves between
# devices as int8 blocks + f32 per-block scales (ops/quantize.py layout).
# ---------------------------------------------------------------------------


def _fold_key(seed, axis: str, stochastic: bool):
    if not stochastic:
        return None
    return jax.random.fold_in(jax.random.PRNGKey(seed),
                              jax.lax.axis_index(axis))


def _dequant_rows(q, s, world: int, block: int):
    # q [world, nblk*block] int8, s [world, nblk] -> f32 [world, nblk, block]
    return q.reshape(world, -1, block).astype(jnp.float32) * s[:, :, None]


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op", "block",
                                             "stochastic", "chunks", "impl"))
def _q_allreduce_impl(x, seed, mesh: Mesh, axis: str, op: str, block: int,
                      stochastic: bool, chunks: int = 1, impl: str = "auto"):
    """Chunked, software-pipelined two-phase quantized allreduce.

    The flat payload is padded to a world*block multiple, viewed as
    [world, sub], and split column-wise into `chunks` block-aligned
    pieces (compression.chunk_layout).  Per chunk the EQuARX structure
    runs: quantize -> all_to_all (the reduce-scatter hop, still int8) ->
    fused dequantize-accumulate -> requantize at the finer result block
    -> all_gather -> dequantize.  Emission order is software-pipelined —
    chunk k+1's quantize is emitted before chunk k's exchange is
    consumed — so XLA's latency-hiding scheduler overlaps codec compute
    with transfer; there is no barrier between chunks.

    Because chunk boundaries land on (result-)block boundaries, every
    per-block scale sees exactly the elements it would monolithically,
    and the f32 accumulation order over the world axis is unchanged:
    chunked and monolithic results are BIT-IDENTICAL for deterministic
    rounding (stochastic draws differ per chunk layout and are exempt).

    On TPU (impl="fused"/auto) each chunk's whole reduce-scatter hop is
    ONE pallas kernel doing quantize -> remote-DMA exchange ->
    accumulate in VMEM (ops/quantize.fused_reduce_scatter);
    "fused_interpret" drives the same kernel through the pallas
    interpreter on CPU meshes, and the default CPU path is the
    XLA-lowered stage sequence with identical numerics."""
    world = mesh.shape[axis]
    spec = P(axis)

    def f(shard, seed_):
        shape, dtype = shard.shape, shard.dtype
        flat = shard.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        total = padded_len(n, world * block)
        if total != n:
            flat = jnp.pad(flat, (0, total - n))
        sub = total // world
        nblk = sub // block
        layout = chunk_layout(nblk, chunks)
        csizes = [nb * block for nb in layout]
        offs = [0]
        for csz in csizes[:-1]:
            offs.append(offs[-1] + csz)
        C = len(csizes)
        x2d = flat.reshape(world, sub)
        idx = jax.lax.axis_index(axis)
        key = _fold_key(seed_, axis, stochastic)
        rs_impl = _resolve_rs_impl(impl, world, block, stochastic,
                                   max(csizes))
        rblock = result_block_size(block)
        # phase 2 pipelines per chunk only when chunk boundaries are also
        # result-block boundaries (true whenever rblock divides block);
        # otherwise the reduced chunks are restitched and phase 2 runs
        # monolithically — either way bit-identical to chunks=1
        p2_chunked = C > 1 and block % rblock == 0

        def quantize_chunk(c):
            xc = x2d[:, offs[c]:offs[c] + csizes[c]]
            # C == 1 keeps the exact pre-chunking key/seed derivation so
            # stochastic draws reproduce across versions
            kc = None
            if stochastic:
                kc = key if C == 1 else jax.random.fold_in(key, c)
            return quantize_blockwise(xc, block, stochastic=stochastic,
                                      key=kc, seed=seed_ * world + idx + c)

        def requant_chunk(c, red_c):
            kc = (jax.random.fold_in(key, world + c)
                  if stochastic else None)
            return quantize_blockwise(red_c, rblock, stochastic=stochastic,
                                      key=kc,
                                      seed=seed_ * world + idx + c + 1)

        reds = [None] * C
        if rs_impl in ("fused", "fused_interpret"):
            for c in range(C):
                xc = x2d[:, offs[c]:offs[c] + csizes[c]]
                reds[c] = fused_reduce_scatter(
                    xc, axis, block,
                    interpret=(rs_impl == "fused_interpret"))
        else:
            qs = [None] * C
            ss = [None] * C
            qs[0], ss[0] = quantize_chunk(0)
            for c in range(C):
                # exchange chunk c ...
                qx = jax.lax.all_to_all(qs[c].reshape(world, csizes[c]),
                                        axis, split_axis=0, concat_axis=0,
                                        tiled=True)
                sx = jax.lax.all_to_all(ss[c].reshape(world, layout[c]),
                                        axis, split_axis=0, concat_axis=0,
                                        tiled=True)
                # ... while quantizing chunk c+1 (emitted before the
                # exchange is consumed: the scheduler may overlap them)
                if c + 1 < C:
                    qs[c + 1], ss[c + 1] = quantize_chunk(c + 1)
                reds[c] = dequantize_accumulate(qx.reshape(-1),
                                                sx.reshape(-1), world,
                                                block)
        if op == "mean":
            reds = [r / world for r in reds]

        def gather_chunk(q2, s2, csz):
            qg = jax.lax.all_gather(q2, axis, tiled=True)
            sg = jax.lax.all_gather(s2, axis, tiled=True)
            # per-device pieces may carry rblock padding; dequantize
            # row-wise and strip before restitching
            out = _dequant_rows(qg.reshape(world, -1),
                                sg.reshape(world, -1), world, rblock)
            return out.reshape(world, -1)[:, :csz]

        if p2_chunked:
            q2s = [None] * C
            s2s = [None] * C
            q2s[0], s2s[0] = requant_chunk(0, reds[0])
            pieces = [None] * C
            for c in range(C):
                if c + 1 < C:
                    q2s[c + 1], s2s[c + 1] = requant_chunk(c + 1,
                                                           reds[c + 1])
                pieces[c] = gather_chunk(q2s[c], s2s[c], csizes[c])
            out2d = jnp.concatenate(pieces, axis=1)
        else:
            red = reds[0] if C == 1 else jnp.concatenate(reds)
            q2, s2 = requant_chunk(0, red)
            out2d = gather_chunk(q2, s2, sub)
        return out2d.reshape(-1)[:n].reshape(shape).astype(dtype)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=(spec, P()),
                     out_specs=spec)(x, seed)


# --- staged profiling path -------------------------------------------------
# Same numerics as _q_allreduce_impl with chunks=1, but split into six
# separately-jitted, fenced stage programs so wall time is attributable to
# quantize / transfer / dequantize sub-phases.  The fences serialize the
# overlap the pipelined path exists to create, so this is a measurement
# mode (bench --emit-telemetry, debugging), never the production default.


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "block",
                                             "stochastic"))
def _qprof_quantize(x, seed, mesh: Mesh, axis: str, block: int,
                    stochastic: bool):
    world = mesh.shape[axis]

    def f(shard, seed_):
        flat = shard.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        total = padded_len(n, world * block)
        if total != n:
            flat = jnp.pad(flat, (0, total - n))
        sub = total // world
        idx = jax.lax.axis_index(axis)
        key = _fold_key(seed_, axis, stochastic)
        q, s = quantize_blockwise(flat.reshape(world, sub), block,
                                  stochastic=stochastic, key=key,
                                  seed=seed_ * world + idx)
        return q.reshape(world, sub), s.reshape(world, sub // block)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=(P(axis), P(axis)))(x, seed)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _qprof_exchange(q, s, mesh: Mesh, axis: str):
    def f(qs, ss):
        qx = jax.lax.all_to_all(qs, axis, split_axis=0, concat_axis=0,
                                tiled=True)
        sx = jax.lax.all_to_all(ss, axis, split_axis=0, concat_axis=0,
                                tiled=True)
        return qx, sx

    return shard_map(f, check_vma=False, mesh=mesh,
                     in_specs=(P(axis), P(axis)),
                     out_specs=(P(axis), P(axis)))(q, s)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op", "block"))
def _qprof_accumulate(qx, sx, mesh: Mesh, axis: str, op: str, block: int):
    world = mesh.shape[axis]

    def f(q, s):
        red = dequantize_accumulate(q.reshape(-1), s.reshape(-1), world,
                                    block)
        if op == "mean":
            red = red / world
        return red

    return shard_map(f, check_vma=False, mesh=mesh,
                     in_specs=(P(axis), P(axis)), out_specs=P(axis))(qx, sx)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "block",
                                             "stochastic"))
def _qprof_requant(red, seed, mesh: Mesh, axis: str, block: int,
                   stochastic: bool):
    world = mesh.shape[axis]
    rblock = result_block_size(block)

    def f(r, seed_):
        idx = jax.lax.axis_index(axis)
        key = _fold_key(seed_, axis, stochastic)
        key2 = jax.random.fold_in(key, world) if stochastic else None
        return quantize_blockwise(r.reshape(-1), rblock,
                                  stochastic=stochastic, key=key2,
                                  seed=seed_ * world + idx + 1)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=(P(axis), P(axis)))(red, seed)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _qprof_gather(q2, s2, mesh: Mesh, axis: str):
    def f(qv, sv):
        qg = jax.lax.all_gather(qv, axis, tiled=True)
        sg = jax.lax.all_gather(sv, axis, tiled=True)
        return qg, sg

    return shard_map(f, check_vma=False, mesh=mesh,
                     in_specs=(P(axis), P(axis)),
                     out_specs=(P(), P()))(q2, s2)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "rblock", "sub",
                                             "n", "shape", "dtype"))
def _qprof_stitch(qg, sg, mesh: Mesh, axis: str, rblock: int, sub: int,
                  n: int, shape: tuple, dtype: str):
    world = mesh.shape[axis]

    def f(qg_, sg_):
        out = _dequant_rows(qg_.reshape(world, -1), sg_.reshape(world, -1),
                            world, rblock)
        out = out.reshape(world, -1)[:, :sub]
        return out.reshape(-1)[:n].reshape(shape).astype(jnp.dtype(dtype))

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=(P(), P()),
                     out_specs=P(axis))(qg, sg)


def _q_allreduce_profiled(x, seed, mesh: Mesh, axis: str, op: str,
                          cc: CompressionConfig, impl: str):
    """Run the quantized allreduce as six fenced stage programs and
    return (result, {"quantize","transfer","dequantize"} seconds).
    Bit-identical to _q_allreduce_impl(chunks=1) for deterministic
    rounding; `impl` is ignored — attribution always uses the XLA stage
    sequence (a fused kernel cannot be split for timing)."""
    del impl
    block, stochastic = cc.block_size, cc.stochastic
    world = mesh.shape[axis]
    rblock = result_block_size(block)
    pershard = (x.shape[0] // world,) + tuple(x.shape[1:])
    n = 1
    for d in pershard:
        n *= d
    sub = padded_len(n, world * block) // world
    times = {"quantize": 0.0, "transfer": 0.0, "dequantize": 0.0}

    def run(bucket, fn, *a):
        t = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out)
        times[bucket] += time.perf_counter() - t
        return out

    x = jax.block_until_ready(x)
    q, s = run("quantize", _qprof_quantize, x, seed, mesh, axis, block,
               stochastic)
    qx, sx = run("transfer", _qprof_exchange, q, s, mesh, axis)
    red = run("dequantize", _qprof_accumulate, qx, sx, mesh, axis, op, block)
    q2, s2 = run("quantize", _qprof_requant, red, seed, mesh, axis, block,
                 stochastic)
    qg, sg = run("transfer", _qprof_gather, q2, s2, mesh, axis)
    out = run("dequantize", _qprof_stitch, qg, sg, mesh, axis, rblock, sub,
              n, pershard, jnp.dtype(x.dtype).name)
    return out, times


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "block",
                                             "stochastic"))
def _q_reducescatter_impl(x, seed, mesh: Mesh, axis: str, block: int,
                          stochastic: bool):
    world = mesh.shape[axis]

    def f(shard, seed_):
        row = shard[0].astype(jnp.float32)      # this device's [N] contribution
        sub = row.shape[0] // world
        sub_pad = padded_len(sub, block)
        chunks = row.reshape(world, sub)
        if sub_pad != sub:
            chunks = jnp.pad(chunks, ((0, 0), (0, sub_pad - sub)))
        idx = jax.lax.axis_index(axis)
        key = _fold_key(seed_, axis, stochastic)
        q, s = quantize_blockwise(chunks, block, stochastic=stochastic,
                                  key=key, seed=seed_ * world + idx)
        qx = jax.lax.all_to_all(q.reshape(world, sub_pad), axis, split_axis=0,
                                concat_axis=0, tiled=True)
        sx = jax.lax.all_to_all(s.reshape(world, sub_pad // block), axis,
                                split_axis=0, concat_axis=0, tiled=True)
        red = dequantize_accumulate(qx.reshape(-1), sx.reshape(-1), world,
                                    block)
        return red[:sub][None].astype(shard.dtype)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=P(axis))(x, seed)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "block",
                                             "stochastic"))
def _q_allgather_impl(x, seed, mesh: Mesh, axis: str, block: int,
                      stochastic: bool):
    world = mesh.shape[axis]

    def f(shard, seed_):
        flat = shard.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        npad = padded_len(n, block)
        idx = jax.lax.axis_index(axis)
        key = _fold_key(seed_, axis, stochastic)
        q, s = quantize_blockwise(flat, block, stochastic=stochastic,
                                  key=key, seed=seed_ * world + idx)
        qg = jax.lax.all_gather(q, axis, tiled=True).reshape(world, npad)
        sg = jax.lax.all_gather(s, axis, tiled=True).reshape(world, -1)
        out = _dequant_rows(qg, sg, world, block).reshape(world, npad)[:, :n]
        return out.reshape((world * shard.shape[0],)
                           + shard.shape[1:]).astype(shard.dtype)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=P())(x, seed)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "tiled"))
def _allgather_impl(x, mesh: Mesh, axis: str, tiled: bool):
    def f(shard):
        return jax.lax.all_gather(shard, axis, tiled=tiled)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=P(axis), out_specs=P())(x)


def mesh_allgather(x, mesh: Mesh, axis_name: Optional[str] = None,
                   compression: Union[None, str, CompressionConfig] = None,
                   seed: int = 0):
    """Each device contributes its shard; all get the concatenation
    (reference API: collective.py:423 allgather).  With `compression`,
    shards travel as int8 blocks + scales and are dequantized on arrival
    (lossy; see compression.py)."""
    axis = _axis(mesh, axis_name)
    cc = parse_compression(compression)
    t0 = time.perf_counter()
    with tracing.span("collective.mesh_allgather", axis=axis,
                      compressed=cc is not None):
        if cc is None:
            out = _allgather_impl(x, mesh, axis, True)
        else:
            out = _q_allgather_impl(x, jnp.int32(seed), mesh, axis,
                                    cc.block_size, cc.stochastic)
    _record_mesh_op("mesh_allgather", t0, x, cc)
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _reducescatter_impl(x, mesh: Mesh, axis: str):
    def f(shard):
        # shard is [1, N] (this device's contribution row); NCCL semantics:
        # reduce all rows, each device keeps its N/world chunk
        y = jax.lax.psum_scatter(shard[0], axis, scatter_dimension=0,
                                 tiled=True)
        return y[None]

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis))(x)


def mesh_reducescatter(x, mesh: Mesh, axis_name: Optional[str] = None,
                       compression: Union[None, str, CompressionConfig] = None,
                       seed: int = 0):
    """Reduce across the axis, leave each device its scattered chunk
    (reference API: collective.py:472 reducescatter).  Input is the stacked
    per-device contributions [world, N]; output [world, N/world] where row r
    is the reduced chunk owned by device r.  With `compression`,
    contributions travel as int8 blocks + scales (sum semantics, lossy)."""
    axis = _axis(mesh, axis_name)
    cc = parse_compression(compression)
    t0 = time.perf_counter()
    with tracing.span("collective.mesh_reducescatter", axis=axis,
                      compressed=cc is not None):
        if cc is None:
            out = _reducescatter_impl(x, mesh, axis)
        else:
            world = mesh.shape[axis]
            if x.shape[-1] % world:
                raise ValueError(
                    f"compressed reducescatter needs the payload dim "
                    f"({x.shape[-1]}) divisible by the axis size "
                    f"({world})")
            out = _q_reducescatter_impl(x, jnp.int32(seed), mesh, axis,
                                        cc.block_size, cc.stochastic)
    _record_mesh_op("mesh_reducescatter", t0, x, cc)
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "root"))
def _broadcast_impl(x, mesh: Mesh, axis: str, root: int):
    def f(shard):
        # rotate root's shard to everyone: gather then index is simplest
        # and XLA turns the gather+slice into a broadcast from root
        full = jax.lax.all_gather(shard, axis)
        return full[root]

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis))(x)


def mesh_broadcast(x, mesh: Mesh, axis_name: Optional[str] = None,
                   root: int = 0):
    """Every device receives root's shard (reference API: collective.py:373)."""
    # NOTE: this (and ppermute/all_to_all below) used to jit a closure
    # built per call — a fresh wrapper per invocation discards the trace
    # cache, so EVERY broadcast recompiled.  The compilation ledger made
    # the storm visible and the jit-per-call lint now flags the pattern;
    # the impls are module-level with hashable statics, like
    # _allreduce_impl always was.
    axis = _axis(mesh, axis_name)
    return _broadcast_impl(x, mesh, axis, int(root))


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "perm"))
def _ppermute_impl(x, mesh: Mesh, axis: str, perm):
    def f(shard):
        return jax.lax.ppermute(shard, axis, perm)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis))(x)


def mesh_ppermute(x, mesh: Mesh, perm: Sequence[tuple],
                  axis_name: Optional[str] = None):
    """Point-to-point shard rotation — the send/recv of the compiled world
    (reference API: collective.py:531/:594 send/recv); the building block of
    ring attention and pipeline microbatching."""
    axis = _axis(mesh, axis_name)
    perm = tuple((int(a), int(b)) for a, b in perm)
    return _ppermute_impl(x, mesh, axis, perm)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "split_axis",
                                             "concat_axis"))
def _all_to_all_impl(x, mesh: Mesh, axis: str, split_axis: int,
                     concat_axis: int):
    def f(shard):
        return jax.lax.all_to_all(shard, axis, split_axis, concat_axis,
                                  tiled=True)

    return shard_map(f, check_vma=False, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis))(x)


def mesh_all_to_all(x, mesh: Mesh, axis_name: Optional[str] = None,
                    split_axis: int = 1, concat_axis: int = 0):
    """All-to-all reshard — the Ulysses/MoE-dispatch primitive.

    With the array sharded on dim 0 over the mesh axis, each device splits
    its shard along `split_axis` and exchanges pieces, concatenating along
    `concat_axis` (maps to lax.all_to_all; EP token dispatch and
    sequence<->head resharding are this one op)."""
    axis = _axis(mesh, axis_name)
    return _all_to_all_impl(x, mesh, axis, int(split_axis),
                            int(concat_axis))


# -- compilation-ledger hookup (telemetry/device.py) ------------------------
# The decorated defs above stay plain jax.jit so the static analyzer's
# decorator-based traced-function discovery is undisturbed; the module
# then routes the compiled entry points through the process ledger, so a
# mesh collective recompiling in steady state shows up as a recompile
# (with a cause diff) instead of silent step-time jitter.

from ray_tpu.telemetry import device as _devtel  # noqa: E402

_allreduce_impl = _devtel.instrument(
    _allreduce_impl, name="collective.allreduce")
_q_allreduce_impl = _devtel.instrument(
    _q_allreduce_impl, name="collective.q_allreduce")
_q_reducescatter_impl = _devtel.instrument(
    _q_reducescatter_impl, name="collective.q_reducescatter")
_q_allgather_impl = _devtel.instrument(
    _q_allgather_impl, name="collective.q_allgather")
_allgather_impl = _devtel.instrument(
    _allgather_impl, name="collective.allgather")
_reducescatter_impl = _devtel.instrument(
    _reducescatter_impl, name="collective.reducescatter")
_broadcast_impl = _devtel.instrument(
    _broadcast_impl, name="collective.broadcast")
_ppermute_impl = _devtel.instrument(
    _ppermute_impl, name="collective.ppermute")
_all_to_all_impl = _devtel.instrument(
    _all_to_all_impl, name="collective.all_to_all")
