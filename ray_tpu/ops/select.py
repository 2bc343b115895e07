"""Selection without a sort: the k-th largest value of each row, found over
the ordered bits of the values, and what is built on it — the threshold of a
sampler's top-k filter (ops/sampling.py) and the set of keys a learned
indexer keeps for a query (`keep_top`: models/dots3.py's selected latent
attention).  One compare-and-count over the row a bit, so no cap on k and no
sorted copy of a row of tens of thousands of scores.
"""

import jax.numpy as jnp
from jax import lax

_UINT = {16: jnp.uint16, 32: jnp.uint32}


def _float_bits(dtype) -> int:
    return jnp.finfo(dtype).bits


def ordered_bits(x):
    """`x`'s bits as unsigned integers (32 wide, whatever `x`'s width)
    ordered as the floats are: all bits of a negative flipped, the sign
    bit of the others set.  -0.0 comes out one below +0.0."""
    nbits = _float_bits(x.dtype)
    b = lax.bitcast_convert_type(x, _UINT[nbits]).astype(jnp.uint32)
    top = jnp.uint32(1 << (nbits - 1))
    return jnp.where(b >= top, b ^ jnp.uint32((1 << nbits) - 1), b | top)


def from_ordered_bits(u, dtype):
    """`ordered_bits`' inverse: the float of `dtype` whose key `u` is."""
    nbits = _float_bits(dtype)
    top = jnp.uint32(1 << (nbits - 1))
    b = jnp.where(u >= top, u ^ top, u ^ jnp.uint32((1 << nbits) - 1))
    return lax.bitcast_convert_type(b.astype(_UINT[nbits]), dtype)


def kth_largest(x, k):
    """The k-th largest value of each row: `x` [N, V] floats, `k` [N]
    ints in [1, V] -> [N], each the value `jnp.sort(row)[V - k]` holds
    (ties counted as a sort counts them; a zero's sign is the one thing
    that may differ, and no comparison sees it).

    The threshold's bits are fixed from the highest down: a bit stays
    set where at least k of the row's keys reach the candidate.  As many
    turns as the dtype has bits, each one compare-and-count over the
    row; no sort, and no cap on k."""
    nbits = _float_bits(x.dtype)
    keys = ordered_bits(x)
    k = k.astype(jnp.int32)

    def turn(i, t):
        cand = t | lax.shift_right_logical(jnp.uint32(1 << (nbits - 1)),
                                           i.astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, t)

    t = lax.fori_loop(0, nbits, turn, jnp.zeros(x.shape[0], jnp.uint32))
    return from_ordered_bits(t, x.dtype)


def keep_top(scores, visible, k: int):
    """The keys each row keeps: `scores` [N, S] floats, `visible` [N, S]
    bools (the keys a row may see at all) -> (keep [N, S] bools, how many
    each row kept [N] int32).  A row keeps its visible keys whose score
    reaches the row's min(k, visible)-th largest visible score: all of
    them while it sees at most k, and the set `jax.lax.top_k` gives
    otherwise — but for scores that TIE at the threshold, every one of
    which is kept (top_k keeps the lower positions), so a row may keep
    more than k, and `kept` says so.  A row that sees nothing keeps
    nothing."""
    low = jnp.asarray(-jnp.inf, scores.dtype)
    seen = jnp.where(visible, scores, low)
    n = jnp.sum(visible, axis=-1, dtype=jnp.int32)
    thr = kth_largest(seen, jnp.clip(n, 1, k))
    keep = visible & (seen >= thr[:, None])
    return keep, jnp.sum(keep, axis=-1, dtype=jnp.int32)
