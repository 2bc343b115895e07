"""Power retention of degree 2: linear attention whose weight is the
SQUARE of the query-key product, with a per-head forget gate, in its two
recurrent forms — one chunk of one sequence (prefill) and one token of
every slot (decode) against a state of fixed size.

    a_ij = exp(sum_{l=j+1..i} log g_l) * (q_i . k_j)^2           (j <= i)
    o_i  = sum_j a_ij v_j / (sum_j a_ij + eps)

The square is a plain inner product of features: phi(u) holds the
dh(dh+1)/2 products u_a u_b (a <= b), sqrt(2) on a < b, so that
phi(q).phi(k) = (q.k)^2, and

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

How the state lies.  `phi` is built from dh/2 + 1 circular shifts: piece
t holds u_a u_{(a+t) mod dh}, every unordered pair once (the last piece
is half empty), so F = (dh/2 + 1) dh features of which dh(dh+1)/2 are
used — 8,320 for 8,256 at dh 128, whole lanes.  A state is ONE array
[.., R, F] float32, features along the lanes: rows 0..dh-1 are S^T (one
row a value dimension), row dh is z (the value 1 appended to v), and R is
dh + 1 rounded up to whole sublanes (136 at dh 128; a [.., 8256, 129]
array would pad to twice it).

Both forms are a Pallas kernel on the chip and XLA elsewhere
(`resolve_impl`).  The state and the normaliser are float32; products take
bf16 operands and accumulate in float32.  The chunk's kernel keeps the XLA
body's roundings (features float32, then cast; the state cast for the
read-out alone): only the order of float32 sums differs.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["phi", "state_shape", "retention_chunk", "retention_step",
           "resolve_impl", "visit", "EPS"]

EPS = 1e-6          # the normaliser's floor (sum_j a_ij + EPS)
_GP = 8             # query heads of a group, padded to whole sublanes


def state_shape(d_head: int) -> Tuple[int, int]:
    """(R, F) of one head's state: see the module docstring."""
    if d_head % 2:
        raise ValueError("d_head must be even")
    return -(-(d_head + 1) // 8) * 8, (d_head // 2 + 1) * d_head


def phi(u):
    """[.., dh] -> [.., F] float32: phi(a).phi(b) = (a.b)^2.  Piece t is
    u times u shifted round by t (a slice of u laid twice end to end);
    the weights are 1 on piece 0, sqrt(2) on the others, 0 on the second
    half of the last."""
    u = u.astype(jnp.float32)
    dh = u.shape[-1]
    half = dh // 2
    twice = jnp.concatenate([u, u], axis=-1)
    shifted = jnp.concatenate(
        [jax.lax.slice_in_dim(twice, t, t + dh, axis=-1)
         for t in range(half + 1)], axis=-1)
    w = np.full((half + 1, dh), math.sqrt(2.0), np.float32)
    w[0] = 1.0
    w[half, half:] = 0.0
    return shifted * jnp.tile(u, half + 1) * w.reshape(-1)


def _with_one(v, rows: int):
    """[.., dh] -> [.., R] float32: v, then 1 (the normaliser's row), then
    zeros up to R."""
    v = v.astype(jnp.float32)
    dh = v.shape[-1]
    one = jnp.ones(v.shape[:-1] + (1,), jnp.float32)
    pad = jnp.zeros(v.shape[:-1] + (rows - dh - 1,), jnp.float32)
    return jnp.concatenate([v, one, pad], axis=-1)


def _quotient(o, dh: int):
    return o[..., :dh] / (o[..., dh:dh + 1] + EPS)


def _chunk_xla(q, k, v, log_g, state, dtype):
    """`retention_chunk` in XLA: the CPU's path, and what the kernel
    (`_chunk_kernel`, below the step's) is held to, rounding for
    rounding.  One K/V head a loop turn: the features of a head's
    queries ([G, C, F] float32, then again in `dtype`) are the largest
    thing alive, not those of all heads; they pass through memory, which
    is what the kernel is there to spare (a chunk's 64 turns at the
    published widths: 85 MB + 43 MB a turn)."""
    G, C, dh = q.shape[1:]
    R = state.shape[-2]
    i = jnp.arange(C)
    see = i[:, None] >= i[None, :]

    def head(args):
        q, k, v, log_g, state = args
        b = jnp.cumsum(log_g.astype(jnp.float32))               # [C]
        # inside the chunk: the quadratic form, causal, decayed
        decay = jnp.exp(jnp.where(see, b[:, None] - b[None, :], -jnp.inf))
        s = jnp.einsum("gid,jd->gij", q.astype(dtype), k.astype(dtype),
                       preferred_element_type=jnp.float32)
        a = s * s * decay
        v1 = _with_one(v, R).astype(dtype)                      # [C, R]
        intra = jnp.einsum("gij,jr->gir", a.astype(dtype), v1,
                           preferred_element_type=jnp.float32)
        # the normaliser's column from the float32 weights themselves
        intra = intra.at[..., dh].set(a.sum(-1))
        # before the chunk: phi(q) against the carried state, decayed
        inter = jnp.einsum("gif,rf->gir", phi(q).astype(dtype),
                           state.astype(dtype),
                           preferred_element_type=jnp.float32)
        o = _quotient(intra + jnp.exp(b)[None, :, None] * inter, dh)
        # the state after it: decayed, plus every key's column x row
        fk = (phi(k) * jnp.exp(b[-1] - b)[:, None]).astype(dtype)  # [C, F]
        new = (jnp.exp(b[-1]) * state
               + jnp.einsum("jr,jf->rf", v1, fk,
                            preferred_element_type=jnp.float32))
        return o, new

    with jax.named_scope("retention_chunk"):
        return jax.lax.map(head, (q, k, v, log_g, state))


def _step_kernel(layer_ref, ent_ref, head_ref, live_ref, fq_ref, fk_ref, v_ref,
                 g_ref, s_ref, o_ref, s_out_ref, *, dtype):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)

    @pl.when(live_ref[b] > 0)
    def _():
        s = g_ref[...] * s_ref[...] + v_ref[...] * fk_ref[...]
        s_out_ref[...] = s
        o_ref[...] = jax.lax.dot_general(
            fq_ref[...].astype(dtype), s.astype(dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(live_ref[b] <= 0)
    def _():
        # an empty slot's turn points at a live neighbour's block (_visit)
        # and must leave it alone; with no live slot at all it points at
        # the null entry, which goes back as it came
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(live_ref[live_ref.shape[0] - 1] < 0)
        def _():
            s_out_ref[...] = s_ref[...]


def _visit(idx, live, n_heads: int):
    """Which block of a layer's arena each grid turn (slot b, head h)
    points at: a live slot's at (idx[b], h); an empty slot's at the block
    the turn before it pointed at (the last live slot's last head; before
    the first live slot, that slot's first head), so that the pipeline,
    which moves a block only when the index changes, moves nothing for an
    empty slot.  Returns (entry [B], head [B]: -1 = the turn's own h, and
    `live` with its last element negated where no slot is live: the null
    entry's one block is then the one thing moved)."""
    B = idx.shape[0]
    at = jnp.arange(B, dtype=jnp.int32)
    alive = live != 0
    before = jax.lax.cummax(jnp.where(alive, at, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(alive).astype(jnp.int32))
    entry = jnp.where(alive.any(), idx[src], 0)
    head = jnp.where(alive, -1, jnp.where(before >= 0, n_heads - 1, 0))
    flag = jnp.where(alive.any(), live, live.at[B - 1].set(-1))
    return entry, head.astype(jnp.int32), flag


def _step_pallas(fq, fk, v1, g, state, layer, idx, live, dtype,
                 interpret: bool):
    """The step as one kernel: a grid turn is one slot's one K/V head; its
    state block is read where the slot's entry stands in the layer's part
    of the arena (prefetched scalars say where), updated, read out and
    written back to the same place (the arena is aliased to the output:
    no second copy of it exists).  Only live slots' blocks are moved."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, Gp, F = fq.shape
    R = state.shape[-2]
    entry, head, flag = _visit(idx, live, Hkv)
    at_slot = lambda b, h, *_: (b, h, 0, 0)
    at_entry = lambda b, h, layer, entry, head, live: (
        layer[0], entry[b], jnp.where(head[b] < 0, h, head[b]), 0, 0)
    blk = lambda *shape: (None, None) + shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, Hkv),
        in_specs=[pl.BlockSpec(blk(Gp, F), at_slot),
                  pl.BlockSpec(blk(1, F), at_slot),
                  pl.BlockSpec(blk(R, 1), at_slot),
                  pl.BlockSpec(blk(R, 1), at_slot),
                  pl.BlockSpec((None,) + blk(R, F), at_entry)],
        out_specs=[pl.BlockSpec(blk(Gp, R), at_slot),
                   pl.BlockSpec((None,) + blk(R, F), at_entry)])
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, dtype=dtype), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Gp, R), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="retention_step",
    )(layer.reshape(1), entry, head, flag, fq, fk[:, :, None, :],
      v1[..., None], jnp.broadcast_to(g[:, :, None, None], v1.shape + (1,)),
      state)
    return o, state


def _step_xla(fq, fk, v1, g, state, layer, idx, live, dtype):
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)[idx]
    new = g[:, :, None, None] * s + v1[..., :, None] * fk[..., None, :]
    new = jnp.where(live[:, None, None, None] != 0, new, s).astype(
        state.dtype)
    o = jnp.einsum("bhgf,bhrf->bhgr", fq.astype(dtype), new.astype(dtype),
                   preferred_element_type=jnp.float32)
    o = jnp.where(live[:, None, None, None] != 0, o, 0.0)
    return o, state.at[layer, idx].set(new)


def resolve_impl(impl: Optional[str]) -> str:
    """The path `retention_step(impl=...)` and `retention_chunk(impl=...)`
    take: None picks by backend, the kernels ("pallas") on a TPU and the
    XLA bodies ("xla") elsewhere."""
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def retention_step(q, k, v, log_g, state, layer, idx, live,
                   impl: Optional[str] = None, dtype=jnp.bfloat16):
    """One token of every slot in one layer.  q [B, Hkv, G, dh], k, v
    [B, Hkv, dh], log_g [B, Hkv] float32; `state` is the arena
    [L, N, Hkv, R, F] float32, `layer` (a scalar, traced or not) the part
    of it this call reads and writes, and slot b's state its entry idx[b]
    there; a slot
    with live[b] == 0 leaves its entry as it is (empty slots ride on the
    null entry).  The read-out takes `dtype` operands.  Returns
    (o [B, Hkv, G, dh] float32, the arena).

    `impl`: "pallas" (the chip's path: in place, one read and one write
    of each LIVE slot's state and none of an empty slot's),
    "pallas_interpret", or "xla" (gather, update, scatter of every slot's:
    the CPU's path); None picks by backend."""
    impl = resolve_impl(impl)
    with jax.named_scope("retention_step"):
        B, Hkv, G, dh = q.shape
        R = state.shape[-2]
        fq = phi(q)
        fk, v1 = phi(k), _with_one(v, R)
        g = jnp.exp(log_g.astype(jnp.float32))
        idx, live = idx.astype(jnp.int32), live.astype(jnp.int32)
        layer = jnp.asarray(layer, jnp.int32)
        if impl == "xla":
            o, state = _step_xla(fq, fk, v1, g, state, layer, idx, live, dtype)
        elif impl in ("pallas", "pallas_interpret"):
            if state.dtype != jnp.float32:
                raise ValueError("the kernel keeps its state in float32")
            fq = jnp.pad(fq, ((0, 0), (0, 0), (0, -G % _GP), (0, 0)))
            o, state = _step_pallas(fq, fk, v1, g, state, layer, idx, live,
                                    dtype, impl == "pallas_interpret")
            o = o[:, :, :G]
        else:
            raise ValueError(f"unknown retention impl {impl!r}")
        return _quotient(o, dh), state


# -- the chunk as one kernel ---------------------------------------------------

_TILE_PIECES = 13   # at most so many of phi's pieces a grid turn
_ROW_BLOCK = 128    # rows whose features are formed at once


def _chunk_kernel(q_ref, k_ref, v1_ref, v1t_ref, b_ref, bt_ref, w_ref, s_ref,
                  o_ref, s_out_ref, acc_ref, f_ref, sd_ref, *, dtype):
    """A grid turn is one K/V head's tile of `phi`'s pieces (whole pieces,
    dh lanes each).  The head's G x C query rows and C keys stay in VMEM
    for all its turns.  A turn forms the tile's features of C rows at a
    time (piece t = u x u rolled by t x its weight, float32, cast to
    `dtype`), multiplies the queries' against the same lanes of the
    carried state into a float32 accumulator, and the keys', decayed,
    into the tile of the new state.  The accumulator lies transposed,
    [R, G C]: the state's 136 rows stream through the MXU against
    features held still, where [G C, R] would give the 8 columns past 128
    a pass of their own (a 512-row program 6.7 -> 5.4 ms, PERF.md section
    6, PR 57).  The head's last turn adds the quadratic form inside the
    chunk and divides."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, last = pl.program_id(1), pl.num_programs(1) - 1
    C, dh = k_ref.shape
    G, W = q_ref.shape[0] // C, s_ref.shape[1]
    block = _ROW_BLOCK if C % _ROW_BLOCK == 0 else C
    f32 = jnp.float32
    lanes = (((1,), (1,)), ((), ()))        # a . b^T

    def features(src, r0, scale=None):
        """The tile's features of rows r0.. of `src` into f_ref."""
        for i in range(C // block):
            u = src[pl.ds(r0 + i * block, block), :].astype(f32)
            for p in range(W // dh):
                t = j * (W // dh) + p
                f = (pltpu.roll(u, jax.lax.rem(dh - t, dh), 1) * u
                     * w_ref[:, p * dh:(p + 1) * dh])
                if scale is not None:
                    f = f * scale[i * block:(i + 1) * block]
                f_ref[i * block:(i + 1) * block,
                      p * dh:(p + 1) * dh] = f.astype(dtype)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # before the chunk: the queries' features against the carried state
    sd_ref[...] = s_ref[...].astype(dtype)

    def against_state(g, _):
        rows = pl.ds(pl.multiple_of(g * C, C), C)
        features(q_ref, pl.multiple_of(g * C, block))
        acc_ref[:, rows] += jax.lax.dot_general(
            sd_ref[...], f_ref[...], lanes, preferred_element_type=f32)

    jax.lax.fori_loop(0, G, against_state, None)

    # the state after it: decayed, plus every key's column x row
    b, b_last = b_ref[...], b_ref[C - 1:C, :]              # [C, 1], [1, 1]
    features(k_ref, 0, jnp.exp(b_last - b))
    # (a [1, 1] is not broadcast both ways at once: down the rows first)
    forget = jnp.exp(jnp.broadcast_to(b_last, (s_ref.shape[0], 1)))
    s_out_ref[...] = forget * s_ref[...] + jnp.dot(
        v1t_ref[...], f_ref[...], preferred_element_type=f32)

    @pl.when(j == last)
    def _():
        # inside the chunk: the quadratic form, causal, decayed
        i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        see = i >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        decay = jnp.exp(jnp.where(see, b - bt_ref[...], -jnp.inf))
        grow = jnp.exp(b)
        kd = k_ref[...].astype(dtype)

        def inside(g, _):
            rows = pl.ds(pl.multiple_of(g * C, C), C)
            s = jax.lax.dot_general(q_ref[rows, :].astype(dtype), kd, lanes,
                                    preferred_element_type=f32)
            a = s * s * decay
            intra = jnp.dot(a.astype(dtype), v1_ref[...],
                            preferred_element_type=f32)
            inter = acc_ref[:, rows].T
            # the normaliser from the float32 weights themselves
            z = a.sum(-1, keepdims=True) + grow * inter[:, dh:dh + 1]
            o_ref[rows, :] = (intra[:, :dh] + grow * inter[:, :dh]) / (z + EPS)

        jax.lax.fori_loop(0, G, inside, None)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _chunk_pallas(q, k, v, log_g, state, dtype, interpret: bool):
    """The chunk as one kernel.  What is F wide — a head's features, the
    [2560, 8320] float32 of the XLA body — exists a tile of pieces at a
    time in VMEM; the state is read once and written once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hkv, G, C, dh = q.shape
    R, F = state.shape[-2:]
    n = F // dh
    tile = dh * max(p for p in range(1, _TILE_PIECES + 1) if n % p == 0)
    b = jnp.cumsum(log_g.astype(jnp.float32), axis=-1)          # [Hkv, C]
    v1 = _with_one(v, R).astype(dtype)                          # [Hkv, C, R]
    # (`phi`'s weights [1, F], the kernel's seventh operand, are phi(1))
    # a head's own block, kept for all its turns; a turn's tile of lanes
    head = lambda *shape: pl.BlockSpec((None,) + shape,
                                       lambda h, j: (h, 0, 0))
    tile_of_state = pl.BlockSpec((None, R, tile), lambda h, j: (h, 0, j))
    tile_of_weights = pl.BlockSpec((1, tile), lambda h, j: (0, j))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, dtype=dtype),
        grid=(Hkv, F // tile),
        in_specs=[head(G * C, dh), head(C, dh), head(C, R), head(R, C),
                  head(C, 1), head(1, C), tile_of_weights, tile_of_state],
        out_specs=[head(G * C, dh), tile_of_state],
        out_shape=[jax.ShapeDtypeStruct((Hkv, G * C, dh), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((R, G * C), jnp.float32),
                        pltpu.VMEM((C, tile), dtype),
                        pltpu.VMEM((R, tile), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="retention_chunk",
    )(q.reshape(Hkv, G * C, dh), k, v1, jnp.swapaxes(v1, 1, 2),
      b[..., None], b[:, None, :], phi(jnp.ones((1, dh))), state)
    return o.reshape(Hkv, G, C, dh), state


def retention_chunk(q, k, v, log_g, state, impl: Optional[str] = None,
                    dtype=jnp.bfloat16):
    """One chunk of one sequence.  q [Hkv, G, C, dh], k, v [Hkv, C, dh],
    log_g [Hkv, C] float32, state [Hkv, R, F] float32 (zeros for a
    sequence's first chunk).  A pad row carries k = 0 and log_g = 0: it
    adds nothing and forgets nothing.  Returns (o [Hkv, G, C, dh] float32,
    the state after the chunk).  Products take `dtype` operands.

    `impl`: "pallas" (the chip's path: `_chunk_kernel`),
    "pallas_interpret", or "xla" (`_chunk_xla`); None picks by backend."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _chunk_xla(q, k, v, log_g, state, dtype)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown retention impl {impl!r}")
    if state.dtype != jnp.float32:
        raise ValueError("the kernel keeps its state in float32")
    with jax.named_scope("retention_chunk"):
        return _chunk_pallas(q, k, v, log_g, state, dtype=dtype,
                             interpret=impl == "pallas_interpret")


# the public name of `_visit`, which stands above the step's kernel (whose
# lines may not move): ops/kda.py, ops/mamba.py and ops/ssd.py lay their
# steps' grids over an arena by it, and take `resolve_impl` from here too
visit = _visit
