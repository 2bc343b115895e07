"""Mamba-1's selective scan (arXiv 2312.00752) in its two recurrent forms
— one chunk of one sequence (prefill) and one token of every slot
(decode) — against a state of fixed size.

For a layer of C channels and N states a channel, per token t:

    h_t = exp(d_t (x) A) * h_{t-1} + (d_t * u_t) (x) B_t        [N, C]
    y_t = sum_n h_t[n, :] * C_t[n]                              [C]

with u_t the channel's input (behind the layer's short conv), d_t > 0 its
step size, A < 0 [N, C] the layer's own, B_t and C_t [N] the token's.
Unlike ops/retention.py and ops/kda.py nothing here is a matrix product:
the decay differs per channel AND per state, so the update is elementwise
work on N x C values a token, and its cost is the bytes it moves.  The
layer's skip (D * u_t) and its gate are the model's.

How the state lies.  A layer's state is kept [N, C]: the CHANNELS run
along the lanes and the N states along the sublanes (16 = two float32
tiles).  Laid [C, N] as the equations are written, a 16-wide last axis
fills an eighth of every 128-lane tile — the chip pads it to eight times
its bytes, in memory and in every move.  This way round d_t, u_t and y_t
are rows, A is the state's own shape, B_t and C_t are columns broadcast
over the lanes, and y_t a sum over sublanes.  An arena is [layers,
entries, N, C] float32, entry 0 the null one.

A chunk never forms h for all its rows ([T, N, C]: 168 MB a layer at 512
rows of the published widths): the kernel keeps ONE block of channels'
h in VMEM and walks the rows; the XLA form (the CPU's) is a `lax.scan`
over the rows with the same carry.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .retention import resolve_impl, visit

__all__ = ["selective_scan_chunk", "selective_step", "resolve_impl"]

_LANES = 128
_CHANNELS = 512     # channels of one program of the chunk kernel
_ROWS = 128         # rows of one grid turn of it


def _fit(block: int, size: int, unit: int) -> int:
    """The largest multiple of `unit` that is at most `block` and divides
    `size` (`size` itself where it is no multiple of `unit`)."""
    if size % unit:
        return size
    b = min(block, size) // unit * unit
    while size % b:
        b -= unit
    return b


# ---------------------------------------------------------------------------
# one chunk of one sequence


def _chunk_xla(u, dt, a, bm, cm, h0):
    def row(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[None, :] * a) * h + (d_t * u_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    h, y = jax.lax.scan(row, h0, (u, dt, bm, cm))
    return y, h


def _chunk_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    a = a_ref[...]                                        # [N, Cb]
    rows, width = u_ref.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)

    def tile(i, h):
        r0 = pl.multiple_of(i * 8, 8)
        u8 = u_ref[pl.ds(r0, 8), :]
        d8 = dt_ref[pl.ds(r0, 8), :]
        y8 = jnp.zeros((8, width), jnp.float32)
        for j in range(8):
            d = d8[j:j + 1, :]                            # [1, Cb]
            b = b_ref[r0 + j][:, :1]                      # [N, 1]
            c = c_ref[r0 + j][:, :1]
            h = jnp.exp(d * a) * h + (d * u8[j:j + 1, :]) * b
            y8 = jnp.where(at == j, jnp.sum(h * c, axis=0, keepdims=True),
                           y8)
        y_ref[pl.ds(r0, 8), :] = y8
        return h

    h_ref[...] = jax.lax.fori_loop(0, rows // 8, tile, h_ref[...])


@functools.partial(jax.jit, static_argnames="interpret")
def _chunk_pallas(u, dt, a, bm, cm, h0, interpret: bool):
    """The chunk as one kernel: a program is one block of channels, its h
    ([N, block] float32, 32 KB) resident in VMEM while the grid's second
    axis walks the rows `_ROWS` at a time; B and C reach it as columns
    (a row's N values down the sublanes, copied across a tile's lanes)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, C = u.shape
    N = a.shape[0]
    cb, tb = _fit(_CHANNELS, C, _LANES), _fit(_ROWS, T, 8)
    columns = lambda x: jnp.broadcast_to(x[:, :, None], (T, N, _LANES))
    by_rows = pl.BlockSpec((tb, cb), lambda c, t: (t, c))
    by_chan = pl.BlockSpec((N, cb), lambda c, t: (0, c))
    cols = pl.BlockSpec((tb, N, _LANES), lambda c, t: (t, 0, 0))
    return pl.pallas_call(
        _chunk_kernel, grid=(C // cb, T // tb),
        in_specs=[by_rows, by_rows, by_chan, cols, cols, by_chan],
        out_specs=[by_rows, by_chan],
        out_shape=[jax.ShapeDtypeStruct((T, C), jnp.float32),
                   jax.ShapeDtypeStruct((N, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="mamba_chunk",
    )(u, dt, a, columns(bm), columns(cm), h0)


def selective_scan_chunk(u, dt, a, bm, cm, h0, impl: Optional[str] = None):
    """One chunk of one sequence.  u, dt [T, C], a [N, C] (negative), bm,
    cm [T, N], h0 [N, C] (zeros for a sequence's first chunk), all taken
    as float32.  A pad row carries dt = 0: it forgets nothing and adds
    nothing (its y is not to be read).  Returns (y [T, C] float32 — h_t
    read out along C_t, the layer's skip not in it — and h after the
    chunk).

    `impl`: "pallas" (the chip's path), "pallas_interpret", or "xla" (a
    scan over the rows: the CPU's path); None picks by backend."""
    impl = resolve_impl(impl)
    with jax.named_scope("mamba_chunk"):
        u, dt, a, bm, cm, h0 = (x.astype(jnp.float32)
                                for x in (u, dt, a, bm, cm, h0))
        if impl == "xla":
            return _chunk_xla(u, dt, a, bm, cm, h0)
        if impl not in ("pallas", "pallas_interpret"):
            raise ValueError(f"unknown mamba impl {impl!r}")
        T = u.shape[0]
        pad = -T % 8
        if pad:
            rows = lambda x: jnp.pad(x, ((0, pad), (0, 0)))
            u, dt, bm, cm = map(rows, (u, dt, bm, cm))
        y, h = _chunk_pallas(u, dt, a, bm, cm, h0,
                             impl == "pallas_interpret")
        return y[:T], h


# ---------------------------------------------------------------------------
# one token of every slot


def _step_kernel(layer_ref, ent_ref, flag_ref, r_ref, bc_ref, a_ref, h_ref,
                 y_ref, h_out_ref):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)

    @pl.when(flag_ref[s] > 0)
    def _():
        d, du = r_ref[0:1, :], r_ref[1:2, :]              # [1, C]
        b, c = bc_ref[0][:, :1], bc_ref[1][:, :1]         # [N, 1]
        h = jnp.exp(d * a_ref[...]) * h_ref[...] + du * b
        h_out_ref[...] = h
        y_ref[...] = jnp.sum(h * c, axis=0, keepdims=True)

    @pl.when(flag_ref[s] <= 0)
    def _():
        # an empty slot's turn points at a live neighbour's block
        # (retention.visit) and must leave it alone; with no live slot at
        # all it points at the null entry, which goes back as it came
        y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(flag_ref[flag_ref.shape[0] - 1] < 0)
        def _():
            h_out_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames="interpret")
def _step_pallas(rows, cols, a, state, layer, idx, live, interpret: bool):
    """The step as one kernel: a grid turn is one slot; its state — [N, C]
    float32, 328 KB at the published widths — is read where its entry
    stands in the layer's part of the arena, updated, read out and written
    back to the same place (the arena is aliased to the output).  Only
    live slots' blocks are moved."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, C = rows.shape
    N = a.shape[0]
    entry, _, flag = visit(idx, live, 1)
    at_entry = lambda s, layer, entry, flag: (layer[0], entry[s], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((None, 2, C), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec((None, 2, N, _LANES),
                               lambda s, *_: (s, 0, 0, 0)),
                  pl.BlockSpec((N, C), lambda s, *_: (0, 0)),
                  pl.BlockSpec((None, None, N, C), at_entry)],
        out_specs=[pl.BlockSpec((None, 1, C), lambda s, *_: (s, 0, 0)),
                   pl.BlockSpec((None, None, N, C), at_entry)])
    y, state = pl.pallas_call(
        _step_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="mamba_step",
    )(layer.reshape(1), entry, flag, rows, cols, a, state)
    return y[:, 0], state


def _step_xla(rows, bm, cm, a, state, layer, idx, live):
    d, du = rows[:, 0], rows[:, 1]                        # [B, C]
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)[idx]
    h = (jnp.exp(d[:, None, :] * a) * old.astype(jnp.float32)
         + du[:, None, :] * bm[:, :, None])
    alive = (live != 0)[:, None]
    y = jnp.where(alive, jnp.sum(h * cm[:, :, None], axis=1), 0.0)
    new = jnp.where(alive[..., None], h.astype(state.dtype), old)
    return y, state.at[layer, idx].set(new)


def selective_step(u, dt, a, bm, cm, state, layer, idx, live,
                   impl: Optional[str] = None):
    """One token of every slot in one layer.  u, dt [B, C], a [N, C], bm,
    cm [B, N]; `state` is the arena [L, entries, N, C], `layer` (a scalar,
    traced or not) the part of it this call reads and writes, and slot b's
    state its entry idx[b] there; a slot with live[b] == 0 leaves its
    entry as it is (empty slots ride on the null entry) and reads y = 0.
    Returns (y [B, C] float32, the arena).

    `impl`: "pallas" (the chip's path: in place, one read and one write of
    each LIVE slot's state and none of an empty slot's),
    "pallas_interpret", or "xla" (gather, update, scatter of every
    slot's: the CPU's path); None picks by backend."""
    impl = resolve_impl(impl)
    with jax.named_scope("mamba_step"):
        f32 = lambda x: x.astype(jnp.float32)
        u, dt, a, bm, cm = map(f32, (u, dt, a, bm, cm))
        rows = jnp.stack([dt, dt * u], axis=1)            # [B, 2, C]
        idx, live = idx.astype(jnp.int32), live.astype(jnp.int32)
        layer = jnp.asarray(layer, jnp.int32)
        if impl == "xla":
            return _step_xla(rows, bm, cm, a, state, layer, idx, live)
        if impl not in ("pallas", "pallas_interpret"):
            raise ValueError(f"unknown mamba impl {impl!r}")
        if state.dtype != jnp.float32:
            raise ValueError("the kernel keeps its state in float32")
        cols = jnp.broadcast_to(jnp.stack([bm, cm], axis=1)[..., None],
                                bm.shape[:1] + (2,) + bm.shape[1:]
                                + (_LANES,))
        return _step_pallas(rows, cols, a, state, layer, idx, live,
                            impl == "pallas_interpret")
