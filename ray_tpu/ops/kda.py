"""Kimi Delta Attention (KDA; the Kimi Linear report, arXiv 2510.26692): a
gated delta rule whose decay is a vector, one factor a KEY CHANNEL — in
its two recurrent forms, one chunk of one sequence (prefill) and one
token of every slot (decode), against a state of fixed size — and the
short causal convolution that stands before it.

For a head with keys and values dk and dv wide, per token t:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T     [dk, dv]
    o_t = S_t^T q_t

with a_t in (0, 1)^dk (handed over as log a_t) and b_t in (0, 1).  Unlike
ops/retention.py's accumulation the update SUBTRACTS what the decayed
state already holds along k_t; written with S' = Diag(a_t) S_{t-1}:

    u_t = b_t (v_t - S'^T k_t)        S_t = S' + k_t u_t^T

How the state lies.  A head's state is kept TRANSPOSED, [dv, dk]: the key
channels run along the lanes, so the decay, the read along k and the
read-out along q are a row broadcast over the sublanes and a sum over the
lanes, and the update a column times a row — the step's kernel takes q, k,
v, a as rows and needs no operand a column wide.  An arena is
[layers, entries, H, dv, dk] float32, entry 0 the null one.

A chunk (the WY / UT form).  With G_t the running sum of log a inside a
block of C rows and E_tj = exp(G_t - G_j) (j <= t, per channel):

    A_tj = b_t sum_c k_t[c] k_j[c] E_tj[c]   (j <  t)
    B_tj =     sum_c q_t[c] k_j[c] E_tj[c]   (j <= t)
    U    = W - Kbar S_0,  (W, Kbar) = (I + A)^-1 b (V, K exp G)
    O    = (Q exp G) S_0 + B U
    S_C  = Diag(exp G_C) S_0 + (K exp(G_C - G))^T U

exp(G_t) and exp(-G_j) formed apart leave float32 (log a = -5 over 64
rows reaches -320), so a block is cut in SUB-BLOCKS of 16 rows.  Inside
one, E is the exponential of the DIFFERENCE (15 rows reach -75).  For t in
sub-block i and j in an earlier one, with R_i = G at the last row before
sub-block i, E_tj = exp(G_t - R_i) exp(R_i - G_j): G only falls, so both
exponents are <= 0 (the first reaches 16 x -5 = -80, short of float32's
-87): both factors are <= 1 and neither is smaller than E_tj — a factor
leaves float32 only where E_tj does — and A, B below the diagonal
sub-blocks are MATRIX PRODUCTS of rows scaled by the one and keys scaled
by the other.  (A block that sub-blocks do not tile, a short chunk's, is
one diagonal piece: the difference holds at any length.)  (I + A)^-1 is
the product (I - A)(I + A^2)(I + A^4).. — A is strictly lower triangular,
so the series ends at A^C.  A, B, the inverse, W and Kbar read no state:
they are formed for all of a chunk's blocks at once (`_state_free`), and
the scan over the blocks keeps the three products that do.  Everything is
float32 and the products take float32 operands at the highest precision:
bf16 operands would round the carried state at every block's read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .retention import resolve_impl, visit

__all__ = ["kda_chunk", "kda_step", "conv_chunk", "conv_step", "BLOCK",
           "resolve_impl"]

BLOCK = 64          # rows of one WY block of a chunk
SUB_BLOCK = 16      # rows of a sub-block of it (its bound: the docstring)
_HI = jax.lax.Precision.HIGHEST


def _inverse_unit_lower(a):
    """(I + a)^-1 for strictly lower triangular a [.., C, C]."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=_HI)
    inv, power, n = eye - a, a, 2
    while n < C:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        n *= 2
    return inv


def _block(state, xs):
    """One WY block's turn of the scan — the three products that read the
    state, their operands scaled on the way in: state [H, dv, dk];
    `_state_free`'s wk [H, C, dv + dk] (W beside Kbar), b [H, C, C], and
    the block's q, k, g [H, C, dk] -> (state, o [H, C, dv])."""
    wk, b, q, k, g = xs
    es = functools.partial(jnp.einsum, precision=_HI)
    dv, last = state.shape[1], g[:, -1:]
    u = wk[..., :dv] - es("htc,hvc->htv", wk[..., dv:], state)
    o = es("htc,hvc->htv", q * jnp.exp(g), state) + es("htj,hjv->htv", b, u)
    return state * jnp.exp(last) + es(
        "htv,htc->hvc", u, k * jnp.exp(last - g)), o


@functools.partial(jax.jit, static_argnames="block")
def kda_chunk(q, k, v, log_a, beta, state, block: int = BLOCK):
    """One chunk of one sequence.  q, k [H, T, dk] (normalised as the
    layer says), v [H, T, dv], log_a [H, T, dk] (<= 0), beta [H, T], all
    float32; state [H, dv, dk] float32 (zeros for a sequence's first
    chunk).  A pad row carries k = 0, beta = 0 and log_a = 0: it adds
    nothing and forgets nothing.  Returns (o [H, T, dv] float32, the
    state after the chunk).  Jitted here, so that a program that calls it
    a layer traces and lowers it once (as `_step_pallas`: ROADMAP S11)."""
    H, T, _ = q.shape
    C = min(block, T)
    pad = -T % C
    f32 = lambda x: x.astype(jnp.float32)

    def blocks(x):      # [H, T, ..] -> [T/C, H, C, ..], pad rows zero
        x = jnp.pad(f32(x), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((H, (T + pad) // C, C) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    with jax.named_scope("kda_chunk"):
        state, o = jax.lax.scan(_block, f32(state), _state_free(
            *map(blocks, (q, k, v, log_a, beta))))
        o = jnp.moveaxis(o, 0, 1).reshape(H, T + pad, -1)
        return o[:, :T], state


def _step_kernel(layer_ref, ent_ref, flag_ref, r_ref, s_ref, o_ref,
                 s_out_ref):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    H, dv, _ = s_ref.shape

    @pl.when(flag_ref[b] > 0)
    def _():
        i = jax.lax.broadcasted_iota(jnp.int32, (dv, dv), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (dv, dv), 1)
        eye = (i == j).astype(jnp.float32)
        for h in range(H):
            q, k, kb, v, a = (r_ref[n, h:h + 1, :] for n in range(5))
            s = s_ref[h] * a                                  # Diag(a) S
            held = jnp.sum(s * k, axis=1, keepdims=True)      # S'^T k, a column
            v_col = jnp.sum(eye * v, axis=1, keepdims=True)
            s = s + (v_col - held) * kb
            s_out_ref[h] = s
            o_col = jnp.sum(s * q, axis=1, keepdims=True)
            o_ref[h:h + 1, :] = jnp.sum(eye * o_col, axis=0, keepdims=True)

    @pl.when(flag_ref[b] <= 0)
    def _():
        # an empty slot's turn points at a live neighbour's block
        # (retention.visit) and must leave it alone; with no live slot at
        # all it points at the null entry, which goes back as it came
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(flag_ref[flag_ref.shape[0] - 1] < 0)
        def _():
            s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames="interpret")
def _step_pallas(rows, state, layer, idx, live, interpret: bool):
    """The step as one kernel: a grid turn is one slot; the slot's block —
    its H heads' states, 2 MB at the published widths — is read where its
    entry stands in the layer's part of the arena, updated, read out and
    written back to the same place (the arena is aliased to the output).
    Only live slots' blocks are moved."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, H, dk = rows.shape
    dv = state.shape[-2]
    entry, _, flag = visit(idx, live, 1)
    at_slot = lambda b, *_: (b, 0, 0, 0)
    at_entry = lambda b, layer, entry, flag: (layer[0], entry[b], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((None, 5, H, dk), at_slot),
                  pl.BlockSpec((None, None, H, dv, dk), at_entry)],
        out_specs=[pl.BlockSpec((None, H, dv), lambda b, *_: (b, 0, 0)),
                   pl.BlockSpec((None, None, H, dv, dk), at_entry)])
    return pl.pallas_call(
        _step_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="kda_step",
    )(layer.reshape(1), entry, flag, rows, state)


def _step_xla(rows, state, layer, idx, live):
    q, k, kb, v, a = (rows[:, n] for n in range(5))           # [B, H, d]
    es = functools.partial(jnp.einsum, precision=_HI)
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)[idx]
    s = old.astype(jnp.float32) * a[:, :, None, :]
    s = s + (v - es("bhvc,bhc->bhv", s, k))[..., None] * kb[:, :, None, :]
    alive = (live != 0)[:, None, None]
    o = jnp.where(alive, es("bhvc,bhc->bhv", s, q), 0.0)
    new = jnp.where(alive[..., None], s.astype(state.dtype), old)
    return o, state.at[layer, idx].set(new)


def kda_step(q, k, v, log_a, beta, state, layer, idx, live,
             impl: Optional[str] = None):
    """One token of every slot in one layer.  q, k [B, H, dk], v
    [B, H, dv], log_a [B, H, dk], beta [B, H]; `state` is the arena
    [L, N, H, dv, dk], `layer` (a scalar, traced or not) the part of it
    this call reads and writes, and slot b's state its entry idx[b] there;
    a slot with live[b] == 0 leaves its entry as it is (empty slots ride
    on the null entry).  Returns (o [B, H, dv] float32, the arena).

    `impl`: "pallas" (the chip's path: in place, one read and one write of
    each LIVE slot's state and none of an empty slot's),
    "pallas_interpret", or "xla" (gather, update, scatter of every slot's:
    the CPU's path); None picks by backend."""
    impl = resolve_impl(impl)
    with jax.named_scope("kda_step"):
        f32 = lambda x: x.astype(jnp.float32)
        k = f32(k)
        rows = jnp.stack([f32(q), k, k * f32(beta)[..., None], f32(v),
                          jnp.exp(f32(log_a))], axis=1)       # [B, 5, H, d]
        idx, live = idx.astype(jnp.int32), live.astype(jnp.int32)
        layer = jnp.asarray(layer, jnp.int32)
        if impl == "xla":
            return _step_xla(rows, state, layer, idx, live)
        if impl not in ("pallas", "pallas_interpret"):
            raise ValueError(f"unknown kda impl {impl!r}")
        if state.dtype != jnp.float32:
            raise ValueError("the kernel keeps its state in float32")
        if q.shape[-1] != v.shape[-1]:
            raise ValueError("the kernel stacks q, k, v as rows of one "
                             "width: dk must equal dv")
        return _step_pallas(rows, state, layer, idx, live,
                            impl == "pallas_interpret")


def _state_free(q, k, v, log_a, beta):
    """The half of a chunk that reads no state, for all of its WY blocks at
    once (the module docstring's A, B, W, Kbar): q, k, log_a [.., C, dk],
    v [.., C, dv], beta [.., C] -> `_block`'s (wk, b, q, k, g).  It stands
    below the lines `kda_step`'s kernel is serialised with (ROADMAP S11)."""
    C = q.shape[-2]
    s = SUB_BLOCK if C % SUB_BLOCK == 0 else C
    mm = functools.partial(jnp.matmul, precision=_HI)
    g = jnp.cumsum(log_a, axis=-2)
    bk = beta[..., None] * k
    # the diagonal sub-blocks, by the difference: [.., i, t, j, dk] summed
    # over dk for A's rows and for B's (two sums: the compiler forms the
    # exponentials in each and lays them nowhere)
    subs = lambda a: a.reshape(a.shape[:-2] + (C // s, s, a.shape[-1]))
    gs, t = subs(g), jnp.arange(s)
    ke = subs(k)[..., None, :, :] * jnp.exp(jnp.where(
        (t[:, None] >= t[None, :])[..., None],
        gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf))
    diag = jnp.stack([(subs(y)[..., :, None, :] * ke).sum(-1)
                      for y in (bk, q)], axis=-4)          # [.., 2, i, t, j]
    # below them, sub-block i's rows (A's over B's) times the keys before
    # it, each side scaled to R_i
    x, rows = jnp.stack([bk, q], axis=-3), []
    for i in range(C // s):
        lo, piece = i * s, diag[..., i, :, :]
        if i:
            r = g[..., lo - 1:lo, :]
            later = x[..., lo:lo + s, :] * jnp.exp(
                g[..., lo:lo + s, :] - r)[..., None, :, :]
            earlier = k[..., :lo, :] * jnp.exp(r - g[..., :lo, :])
            piece = jnp.concatenate([mm(later, jnp.swapaxes(
                earlier, -1, -2)[..., None, :, :]), piece], axis=-1)
        rows.append(jnp.pad(
            piece, ((0, 0),) * (x.ndim - 1) + ((0, C - lo - s),)))
    ab = jnp.concatenate(rows, axis=-2)                    # [.., 2, C, C]
    wk = mm(_inverse_unit_lower(jnp.tril(ab[..., 0, :, :], -1)), beta[
        ..., None] * jnp.concatenate([v, k * jnp.exp(g)], axis=-1))
    return wk, ab[..., 1, :, :], q, k, g


# ---------------------------------------------------------------------------
# the short convolution before it: depthwise, causal, `W` taps, then SiLU.
# The form lives in `ops/shortconv.py` (the activation, the bias and the
# scope its arguments: `models/lfm2_moe.py` runs it bare, as a mixer); these
# are its two functions bound to what stands before a recurrent mixer —
# SiLU, under the scope `kda_conv` — for the callers that take them from
# here.  (Imported HERE and not at the top: nothing above `_step_kernel` may
# gain or lose a line.)

from . import shortconv as _sc  # noqa: E402


def conv_chunk(rows, tail, w, b):
    """`shortconv.conv_chunk`: rows [T, ..], tail [W-1, ..], w [W, ..],
    b [..] -> SiLU(conv) [T, ..] float32."""
    return _sc.conv_chunk(rows, tail, w, b, jax.nn.silu, "kda_conv")


def conv_step(row, tail, w, b):
    """`shortconv.conv_step`: row [B, ..], tail [B, W-1, ..] -> (SiLU(conv)
    [B, ..] float32, the tails after)."""
    return _sc.conv_step(row, tail, w, b, jax.nn.silu, "kda_conv")
