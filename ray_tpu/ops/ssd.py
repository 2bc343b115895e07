"""Mamba-2's state-space duality (SSD, arXiv 2405.21060) in its two
recurrent forms — one chunk of one sequence (prefill) and one token of
every slot (decode) — against a state of fixed size.

For a layer of H heads of P channels, N states and G groups (head i reads
group i // (H / G)), per head and token t:

    S_t = exp(dt_t a) S_{t-1} + (dt_t x_t) (x) B_t               [P, N]
    y_t = S_t C_t + d x_t                                         [P]

with x_t [P] the head's input (behind the layer's short conv), dt_t > 0
its step size, a < 0 and d the head's own SCALARS, B_t and C_t [N] the
token's, shared by a group's heads.  Unlike ops/mamba.py (a decay per
channel AND per state: elementwise work) the decay here is one number a
head and token, so a chunk is a chain of matrix products.  For Q rows,
l_t = dt_t a <= 0 and s_t = sum_{i<=t} l_i:

    Y   = ((C B^T) * L)(dt * X) + exp(s) * (C S_0^T)
    L[t, r] = exp(s_t - s_r) for r <= t, else 0
    S_Q = exp(s_Q) S_0 + sum_r exp(s_Q - s_r) (dt_r x_r) (x) B_r

C B^T is formed ONCE A GROUP and masked by each of the group's heads' own
L.  Every exponent is <= 0: nothing is divided by a decay and nothing
overflows.  The state, the decays and their sums are float32; the chunk's
products take `dtype` operands and accumulate in float32 (the state cast
for the read-out alone, as ops/retention.py); the step is float32
throughout.

How the state lies.  [H, P, N] float32 a layer and sequence: the N states
(256) along the lanes and the P channels (128) along the sublanes — whole
tiles, no padding; B_t and C_t are rows, x_t a column.  An arena is
[layers, entries, H, P, N], entry 0 the null one.

Both forms are a Pallas kernel on the chip and XLA elsewhere
(`retention.resolve_impl`), each behind ONE module-level `jax.jit`, so
that a program with a call a layer traces and lowers it once (ROADMAP
S11).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .retention import resolve_impl, visit

__all__ = ["ssd_chunk", "ssd_step", "resolve_impl", "BLOCK"]

BLOCK = 128         # rows of one sub-chunk (the published mamba_chunk_size)
_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# ---------------------------------------------------------------------------
# one chunk of one sequence


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def _chunk_xla(x, dt, a, b, c, state, block: int, dtype):
    """The chunk as a `lax.scan` over its sub-chunks, every head at once:
    the CPU's path, and what the kernel is held to."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    R, Q = H // G, block
    es = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                           precision=_HI if dtype == jnp.float32 else None)
    cut = lambda z: z.reshape((T // Q, Q) + z.shape[1:])
    at = jnp.arange(Q)
    see = at[:, None] >= at[None, :]

    def sub(S, xs):
        dx, la, bq, cq = xs                     # [Q, H, P], [Q, H], [Q, G, N]
        s = jnp.cumsum(la, axis=0).T            # [H, Q]
        cb = es("tgn,rgn->gtr", cq.astype(dtype), bq.astype(dtype))
        L = jnp.exp(jnp.where(see, s[:, :, None] - s[:, None, :], -jnp.inf))
        m = (cb[:, None] * L.reshape(G, R, Q, Q)).astype(dtype)
        dxg = dx.reshape(Q, G, R, P)
        y = es("gjtr,rgjp->tgjp", m, dxg.astype(dtype))
        Sg = S.reshape(G, R, P, N)
        y = y + jnp.exp(s).T.reshape(Q, G, R, 1) * es(
            "tgn,gjpn->tgjp", cq.astype(dtype), Sg.astype(dtype))
        last = s[:, -1]                         # [H]
        w = jnp.exp(last[:, None] - s).T.reshape(Q, G, R, 1)
        new = jnp.exp(last).reshape(G, R, 1, 1) * Sg + es(
            "rgjp,rgn->gjpn", (w * dxg).astype(dtype), bq.astype(dtype))
        return new.reshape(H, P, N), y.reshape(Q, H, P)

    state, y = jax.lax.scan(
        sub, state, (cut(dt[..., None] * x), cut(dt * a), cut(b), cut(c)))
    return y.reshape(T, H, P), state


def _chunk_kernel(cb_ref, dx_ref, b_ref, c_ref, s_ref, end_ref, h0_ref, y_ref,
                  h_ref, *, dtype):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    Q, N = dx_ref.shape[0], b_ref.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    s_row = s_ref[...]                                       # [1, Q]
    # a row turned to lie down the sublanes, by the unit matrix
    down = lambda r: jnp.sum(jnp.where(row == col, r, 0.0), axis=1,
                             keepdims=True)
    s_col = down(s_row)
    L = jnp.where(row >= col, jnp.exp(jnp.minimum(s_col - s_row, 0.0)), 0.0)
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    dx, S = dx_ref[...], h_ref[...]
    y = dot((cb_ref[...] * L).astype(dtype), dx.astype(dtype),
            (((1,), (0,)), ((), ())))
    y_ref[...] = y + jnp.exp(s_col) * dot(c_ref[...].astype(dtype),
                                          S.astype(dtype), _NT)
    # the sub-chunk's whole sum s_Q, the same in every lane (a [1, 1]
    # value cannot be spread over sublanes and lanes at once)
    end = end_ref[...]
    h_ref[...] = jnp.exp(end[:, :N]) * S + dot(
        (down(jnp.exp(end[:, :Q] - s_row)) * dx).astype(dtype),
        b_ref[...].astype(dtype), _TN)


@functools.partial(jax.jit, static_argnames=("block", "dtype", "interpret"))
def _chunk_pallas(x, dt, a, b, c, state, block: int, dtype, interpret: bool):
    """The chunk as one kernel: a program is one head, its state ([P, N]
    float32, 128 KB) resident in VMEM while the grid's second axis walks
    the sub-chunks; C B^T [sub-chunks, G, Q, Q] is formed before it, once
    a group, and every head of the group reads its block of it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, P = x.shape
    G, N = b.shape[1:]
    R, Q, nb = H // G, block, T // block
    cut = lambda z: z.reshape((nb, Q) + z.shape[1:])
    cb = jnp.einsum("jtgn,jrgn->jgtr", cut(c).astype(dtype),
                    cut(b).astype(dtype), preferred_element_type=jnp.float32,
                    precision=_HI if dtype == jnp.float32 else None)
    s = jnp.cumsum(cut(dt * a), axis=1)                      # [nb, Q, H]
    end = jnp.broadcast_to(jnp.moveaxis(s[:, -1], 1, 0)[..., None, None],
                           (H, nb, 1, max(N, Q)))
    s = jnp.moveaxis(s, 2, 0).reshape(H, 1, T)
    rows = lambda width, group: pl.BlockSpec(
        (Q, width), (lambda h, j: (j, h // R)) if group else
        (lambda h, j: (j, h)))
    held = pl.BlockSpec((None, P, N), lambda h, j: (h, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, dtype=dtype), grid=(H, nb),
        in_specs=[pl.BlockSpec((None, None, Q, Q),
                               lambda h, j: (j, h // R, 0, 0)),
                  rows(P, False), rows(N, True), rows(N, True),
                  pl.BlockSpec((None, 1, Q), lambda h, j: (h, 0, j)),
                  pl.BlockSpec((None, None, 1, max(N, Q)),
                               lambda h, j: (h, j, 0, 0)), held],
        out_specs=[rows(P, False), held],
        out_shape=[jax.ShapeDtypeStruct((T, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((H, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="ssd_chunk",
    )(cb, (dt[..., None] * x).reshape(T, H * P), b.reshape(T, G * N),
      c.reshape(T, G * N), s, end, state)
    return y.reshape(T, H, P), state


def ssd_chunk(x, dt, a, b, c, d, state, impl: Optional[str] = None,
              dtype=jnp.bfloat16, block: int = BLOCK):
    """One chunk of one sequence.  x [T, H, P], dt [T, H], a, d [H] (a
    negative), b, c [T, G, N], state [H, P, N] float32 (zeros for a
    sequence's first chunk).  A pad row carries dt = 0: it forgets nothing
    and adds nothing (its y is not to be read).  The rows are taken
    `block` at a time, the state carried from sub-chunk to sub-chunk.
    Returns (y [T, H, P] float32 — S_t read out along C_t plus the head's
    skip d x_t —, the state after the chunk).  Products take `dtype`
    operands.

    `impl`: "pallas" (the chip's path), "pallas_interpret", or "xla" (a
    scan over the sub-chunks: the CPU's path); None picks by backend."""
    impl = resolve_impl(impl)
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown ssd impl {impl!r}")
    if state.dtype != jnp.float32:
        raise ValueError("the state is kept in float32")
    with jax.named_scope("ssd_chunk"):
        f32 = lambda z: z.astype(jnp.float32)
        x, dt, a, b, c, d = map(f32, (x, dt, a, b, c, d))
        T = x.shape[0]
        pad = -T % block
        if pad:
            rows = lambda z: jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1))
            xp, dtp, b, c = map(rows, (x, dt, b, c))
        else:
            xp, dtp = x, dt
        dtype = jnp.dtype(dtype)
        if impl == "xla":
            y, state = _chunk_xla(xp, dtp, a, b, c, state, block, dtype)
        else:
            y, state = _chunk_pallas(xp, dtp, a, b, c, state, block, dtype,
                                     impl == "pallas_interpret")
        return y[:T] + d[:, None] * x, state


# ---------------------------------------------------------------------------
# one token of every slot


def _step_kernel(layer_ref, ent_ref, flag_ref, dx_ref, dec_ref, bc_ref, s_ref,
                 y_ref, s_out_ref, *, per_group: int):
    from jax.experimental import pallas as pl

    slot = pl.program_id(0)
    H, P, _ = s_ref.shape

    @pl.when(flag_ref[slot] > 0)
    def _():
        i = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
        eye = i == j
        for h in range(H):
            g = h // per_group
            dx = dx_ref[h:h + 1, :]                           # [1, P]
            decay = dec_ref[h:h + 1, :]                       # [1, N]
            b, c = bc_ref[0, g:g + 1, :], bc_ref[1, g:g + 1, :]   # [1, N]
            dx_col = jnp.sum(jnp.where(eye, dx, 0.0), axis=1, keepdims=True)
            s = decay * s_ref[h] + dx_col * b                 # [P, N]
            s_out_ref[h] = s
            y_col = jnp.sum(s * c, axis=1, keepdims=True)     # [P, 1]
            y_ref[h:h + 1, :] = jnp.sum(jnp.where(eye, y_col, 0.0), axis=0,
                                        keepdims=True)

    @pl.when(flag_ref[slot] <= 0)
    def _():
        # an empty slot's turn points at a live neighbour's block
        # (retention.visit) and must leave it alone; with no live slot at
        # all it points at the null entry, which goes back as it came
        y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(flag_ref[flag_ref.shape[0] - 1] < 0)
        def _():
            s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames="interpret")
def _step_pallas(dx, decay, bc, state, layer, idx, live, interpret: bool):
    """The step as one kernel: a grid turn is one slot; the slot's block —
    its H heads' states, 4 MB at the published widths — is read where its
    entry stands in the layer's part of the arena, updated, read out and
    written back to the same place (the arena is aliased to the output).
    Only live slots' blocks are moved."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, P = dx.shape
    G, N = bc.shape[2:]
    entry, _, flag = visit(idx, live, 1)
    at_slot = lambda s, *_: (s, 0, 0)
    at_entry = lambda s, layer, entry, flag: (layer[0], entry[s], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((None, H, P), at_slot),
                  pl.BlockSpec((None, H, N), at_slot),
                  pl.BlockSpec((None, 2, G, N), lambda s, *_: (s, 0, 0, 0)),
                  pl.BlockSpec((None, None, H, P, N), at_entry)],
        out_specs=[pl.BlockSpec((None, H, P), at_slot),
                   pl.BlockSpec((None, None, H, P, N), at_entry)])
    return pl.pallas_call(
        functools.partial(_step_kernel, per_group=H // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="ssd_step",
    )(layer.reshape(1), entry, flag, dx, decay, bc, state)


@jax.jit
def _step_xla(dx, decay, bc, state, layer, idx, live):
    H, G = dx.shape[1], bc.shape[2]
    b, c = (jnp.repeat(bc[:, n], H // G, axis=1) for n in range(2))
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)[idx]
    s = (decay[:, :, None, :] * old.astype(jnp.float32)
         + dx[..., None] * b[:, :, None, :])
    alive = (live != 0)[:, None, None]
    y = jnp.where(alive, jnp.einsum("bhpn,bhn->bhp", s, c, precision=_HI), 0.0)
    new = jnp.where(alive[..., None], s.astype(state.dtype), old)
    return y, state.at[layer, idx].set(new)


def ssd_step(x, dt, a, b, c, d, state, layer, idx, live,
             impl: Optional[str] = None):
    """One token of every slot in one layer.  x [B, H, P], dt [B, H], a, d
    [H], b, c [B, G, N]; `state` is the arena [L, entries, H, P, N]
    float32, `layer` (a scalar, traced or not) the part of it this call
    reads and writes, and slot s's state its entry idx[s] there; a slot
    with live[s] == 0 leaves its entry as it is (empty slots ride on the
    null entry) and reads y = 0.  Returns (y [B, H, P] float32 with the
    skip d x, the arena).

    `impl`: "pallas" (the chip's path: in place, one read and one write of
    each LIVE slot's state and none of an empty slot's),
    "pallas_interpret", or "xla" (gather, update, scatter of every
    slot's: the CPU's path); None picks by backend."""
    impl = resolve_impl(impl)
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown ssd impl {impl!r}")
    with jax.named_scope("ssd_step"):
        f32 = lambda z: z.astype(jnp.float32)
        x, dt, a, b, c, d = map(f32, (x, dt, a, b, c, d))
        dx = dt[..., None] * x                                # [B, H, P]
        # a head's one decay, the same in every lane of a row N wide
        decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None],
                                 dt.shape + b.shape[-1:])
        bc = jnp.stack([b, c], axis=1)                        # [B, 2, G, N]
        idx, live = idx.astype(jnp.int32), live.astype(jnp.int32)
        layer = jnp.asarray(layer, jnp.int32)
        if impl == "xla":
            y, state = _step_xla(dx, decay, bc, state, layer, idx, live)
        else:
            if state.dtype != jnp.float32:
                raise ValueError("the kernel keeps its state in float32")
            y, state = _step_pallas(dx, decay, bc, state, layer, idx, live,
                                    impl == "pallas_interpret")
        skip = jnp.where((live != 0)[:, None, None], d[:, None] * x, 0.0)
        return y + skip, state
