"""What the served models decide ONCE: how K/V rows meet a page table
(`kind_io`, `page_blocks`, `attend_pages`), what a state entry hands a
sequence's first chunk (`carried_at`, `states_moved`) and the weight draw
(`DRAW_PIECE`, `piece`, `pieces`, `draw`).  A model module takes these from
here (GPT-2's own pieces from `gpt`, a family's from its base), never from
a sibling; this module imports `jax` and `ray_tpu.ops` only.  `cohere2_moe`
([pages, page_size, Hkv, dh] arenas, windows) and `phi4flash` (layers that
bring no rows, head pairs) keep their own write-then-attend.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (latent_decode_uses_kernel,
                                   paged_decode_attention, streamed_attention)
from ray_tpu.ops.retention import resolve_impl

__all__ = ["kind_io", "page_blocks", "attend_pages", "carried_at",
           "states_moved", "DRAW_PIECE", "piece", "pieces", "draw"]


# K/V pages: one page table a sequence and kind


def _entry_bases(kind: str, tab, last_pos, ps: int, width: int):
    """First position held by each entry of a page table tab [B, R],
    padded to `width` entries: [B, width], negative where the entry holds
    nothing a query at or before last_pos [B] may see.  A full table is
    in sequence order; a windowed one is a RING: logical page lp
    (positions lp*ps ..) sits in entry lp % R."""
    B, R = tab.shape
    e = jnp.arange(width, dtype=jnp.int32)[None]
    if kind == "full":
        return jnp.broadcast_to(e * ps, (B, width))
    hi = (last_pos // ps)[:, None]
    lp = hi - (hi - e) % R
    return jnp.where((e < R) & (lp >= 0), lp * ps, -1)


def kind_io(kind: str, tab, pos, real, last, flat_pos, ps: int, npb: int):
    """How rows at positions pos [B, T] (`real` marks those whose K and V
    are kept; `last` [B] their greatest, `flat_pos` [B * T] themselves)
    meet the page table tab [B, R] of one kind ("full", or a ring): (the
    table padded to whole blocks of `npb` pages, its entries' bases, the
    (page, offset) each row is written at — the null page for a row that
    is not kept —, the key blocks to stream)."""
    R = tab.shape[1]
    width = -(-R // npb) * npb
    tabp = jnp.pad(tab, ((0, 0), (0, width - R)))
    lp = pos // ps
    entry = lp if kind == "full" else lp % R
    page = jnp.take_along_axis(
        tabp, jnp.minimum(entry, width - 1), axis=1)
    page = jnp.where(real & (entry < R), page, 0).reshape(flat_pos.shape)
    n_blocks = (width // npb if kind != "full" else
                jnp.minimum(jnp.max(last) // (npb * ps) + 1,
                            width // npb))
    return (tabp, _entry_bases(kind, tab, last, ps, width),
            (page, flat_pos % ps), n_blocks)


def page_blocks(tab, bases, kc, vc, npb: int, heads):
    """The `fetch` of `ops.attention.streamed_attention` over `kind_io`'s
    table tab [B, width] and `bases`: block i is `npb` entries -> (`heads`
    of those pages of the arena kc [pages, ps, ..], of vc, their keys'
    positions [B, npb * ps], negative where an entry holds nothing);
    `heads` lays a block's pages [B, npb, ps, ..] as the caller reads keys."""
    B, ps = tab.shape[0], kc.shape[1]

    def fetch(i):
        t = jax.lax.dynamic_slice_in_dim(tab, i * npb, npb, 1)
        b = jax.lax.dynamic_slice_in_dim(bases, i * npb, npb, 1)
        kpos = jnp.where(b[:, :, None] >= 0,
                         b[:, :, None] + jnp.arange(ps, dtype=jnp.int32), -1)
        return heads(kc[t]), heads(vc[t]), kpos.reshape(B, npb * ps)

    return fetch


def attend_pages(q, k, v, kc, vc, io, qpos, cfg, ctx=None):
    """Write this call's K and V rows ([B, Hkv, T, dh]) at the (page,
    offset) of `io` (`kind_io`, a full kind) into arenas [pages, page_size,
    Hkv * dh], then attend: a row a slot on a TPU (`ctx` [B]: the keys each
    slot's row sees, 0 for an empty one) walks each slot's own pages
    (`ops.attention.paged_decode_attention`); a chunk, and the CPU, stream
    the table `cfg.kv_block` keys at a time.  -> (o [B, Hkv, G, T, dh], ..)."""
    tab, bases, (pidx, poff), n_blocks = io
    ps = kc.shape[1]
    npb = max(1, cfg.kv_block // ps)
    B, Hkv, T, dh = k.shape
    rows = lambda a: jnp.moveaxis(a, 1, 2).reshape(B * T, Hkv * dh).astype(
        cfg.dtype)
    kc = kc.at[pidx, poff].set(rows(k))
    vc = vc.at[pidx, poff].set(rows(v))
    scale = cfg.d_head ** -0.5
    if ctx is not None and latent_decode_uses_kernel(T):
        o = paged_decode_attention(q[:, :, :, 0], kc, vc, tab, bases,
                                   qpos[:, 0], -(-ctx // ps), scale=scale)
        return o[:, :, :, None], kc, vc
    fetch = page_blocks(tab, bases, kc, vc, npb, lambda c: jnp.moveaxis(
        c.reshape(B, npb * ps, Hkv, dh), 2, 1))
    return (streamed_attention(q, qpos, fetch, n_blocks, scale=scale),
            kc, vc)


# state entries: one entry of a `"state"` kind a sequence


def carried_at(first, arena, j, idx):
    """What entry `idx` of layer j's part of an arena [layers, entries,
    ..] hands a chunk: zeros to a sequence's FIRST chunk, whatever the
    entry's last holder left — read where it stands (`arena[j]` first is
    a copy of the layer's whole part: 136 MB of Ling's states, PR 60)."""
    held = jax.lax.dynamic_slice(
        arena, (j, idx) + (0,) * (arena.ndim - 2),
        (1, 1) + arena.shape[2:])[0, 0]
    return jnp.where(first, jnp.zeros_like(held), held)


def states_moved(live, impl):
    """The states ONE layer's update moves in a step over slots `live` [B]:
    the live ones where the op's kernel runs (`impl`), else every slot's."""
    return (live.sum() if resolve_impl(impl) != "xla"
            else jnp.asarray(live.shape[0]))


# the draw, leaf by leaf.  A leaf's values are `DRAW_PIECE` standard normals
# at a time — piece i of the leaf at `place` of layer l from the key
# fold_in(fold_in(fold_in(root, 1 + l), place), i) (root: the caller's two
# key words as an "rbg" key), times the leaf's std in f32, rounded to its
# dtype — laid end to end and cut to the leaf's size, by small programs (one
# a leaf took the chip's compiler minutes on a cold start).  A model names
# its leaves' places (`LEAVES`); the vocabulary tables are places 0 and 1
# of "layer" -1.  The piece is part of the recipe (a leaf's VALUES depend
# on it): a constant, no setting, read at call time.
DRAW_PIECE = 1 << 22


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def piece(key, layer, place, i, std, n, dtype):
    # the chip's own bit generator ("rbg": the key's two words twice over):
    # threefry's arithmetic over 4.6e9 values is 1.5 s of a replica's start
    k = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        k, 1 + layer), place), i)
    return (jax.random.normal(k, (n,), jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("count", "n", "dtype"))
def pieces(key, layer, place, std, count, n, dtype):
    """Pieces 0..count-1 of a leaf, end to end, inside ONE program (Ling's
    tree is 3,600 pieces: a dispatch each was 3 s of a start).  A loop and
    not a `vmap`: the generator's batched draws are other draws."""
    return jax.lax.map(
        lambda i: piece(key, layer, place, i, std, n, dtype),
        jnp.arange(count)).reshape(-1)


def draw(key, layer: int, place: int, shape, std: float, dtype):
    size, n = math.prod(shape), DRAW_PIECE
    flat = pieces(key, layer, place, jnp.float32(std), -(-size // n), n,
                  jnp.dtype(dtype))
    return flat[:size].reshape(shape)
