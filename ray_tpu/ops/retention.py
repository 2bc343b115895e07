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
dh + 1 rounded up to whole sublanes (136 at dh 128; the chip would pad a
[.., 129, 8256] array to exactly this, and a [.., 8256, 129] one to twice
it).  An update is then a column (v, 1) times a row phi(k), a read-out a
product that contracts the lanes of both operands.

The state and the normaliser are float32; products take bf16 operands and
accumulate in float32 (the chunk's products and the step's read-out; the
step's update is elementwise, in float32).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["phi", "state_shape", "retention_chunk", "retention_step",
           "resolve_impl", "EPS"]

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


def retention_chunk(q, k, v, log_g, state, dtype=jnp.bfloat16):
    """One chunk of one sequence.  q [Hkv, G, C, dh], k, v [Hkv, C, dh],
    log_g [Hkv, C] float32, state [Hkv, R, F] float32 (zeros for a
    sequence's first chunk).  A pad row carries k = 0 and log_g = 0: it
    adds nothing and forgets nothing.  Returns (o [Hkv, G, C, dh] float32,
    the state after the chunk).  Products take `dtype` operands.  One K/V
    head a loop turn: the features of a head's queries ([G, C, F]) are
    the largest thing alive, not those of all heads."""
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
    """The path `retention_step(impl=...)` takes: None picks by backend."""
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
