"""The short causal convolution (ray_tpu/ops/shortconv.py): one chunk of a
sequence and one token of every slot against the token-by-token form, the
tail carried across chunk cuts and into steps, the activation an argument
(None: the bare sum `models/lfm2_moe.py` runs as its mixer; SiLU: what
stands before three models' recurrent mixers), an empty slot, and
`ops/kda`'s two names — those arguments bound — bit for bit what they were
before the form had a home of its own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda
from ray_tpu.ops.shortconv import conv_chunk, conv_step

ACTS = {"none": None, "silu": jax.nn.silu}


def _operands(T, W, tile, seed=0, bias=True):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    return f(T, *tile), f(W, *tile), (f(*tile) if bias else None)


def _by_token(rows, w, b, act):
    """y_t = act(b + sum_i w_i x_{t-(W-1)+i}) a token at a time, zeros
    before the sequence's start (float64)."""
    rows, w = np.asarray(rows, np.float64), np.asarray(w, np.float64)
    W = w.shape[0]
    out = []
    for t in range(rows.shape[0]):
        acc = np.zeros(rows.shape[1:]) if b is None else np.asarray(
            b, np.float64).copy()
        for i in range(W):
            if t - (W - 1) + i >= 0:
                acc += w[i] * rows[t - (W - 1) + i]
        out.append(acc / (1.0 + np.exp(-acc)) if act else acc)
    return np.stack(out)


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("W,tile,bias", [(3, (32,), False), (4, (5,), True),
                                         (3, (2, 8), False)],
                         ids=["3taps", "4taps-bias", "3taps-tiles"])
def test_a_chunk_is_the_token_by_token_form(act, W, tile, bias):
    rows, w, b = _operands(17, W, tile, bias=bias)
    zero = jnp.zeros((W - 1,) + tile, jnp.float32)
    got = conv_chunk(rows, zero, w, b, ACTS[act], "short_conv")
    assert got.dtype == jnp.float32 and got.shape == rows.shape
    np.testing.assert_allclose(got, _by_token(rows, w, b, act == "silu"),
                               rtol=2e-6,
                               atol=2e-6)


@pytest.mark.parametrize("act", sorted(ACTS))
def test_a_chunk_then_steps_are_one_long_chunk(act):
    """12 rows as a chunk, then 5 steps of a batch whose slot 1 carries the
    sequence: the same rows as one chunk of 17, and the tail the steps
    leave is the sequence's last W-1 rows."""
    T, cut, W, tile = 17, 12, 3, (2, 8)
    rows, w, _ = _operands(T, W, tile, seed=1, bias=False)
    zero = jnp.zeros((W - 1,) + tile, jnp.float32)
    want = conv_chunk(rows, zero, w, None, ACTS[act], "short_conv")
    got = [conv_chunk(rows[:cut], zero, w, None, ACTS[act], "short_conv")]
    tails = jnp.zeros((3, W - 1) + tile, jnp.float32).at[1].set(
        rows[cut - (W - 1):cut])
    for t in range(cut, T):
        row = jnp.zeros((3,) + tile, jnp.float32).at[1].set(rows[t])
        y, tails = conv_step(row, tails, w, None, ACTS[act], "short_conv")
        got.append(y[1][None])
    np.testing.assert_allclose(jnp.concatenate(got), want, rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_array_equal(tails[1], rows[-(W - 1):])


@pytest.mark.parametrize("cuts", [(5, 9), (1, 2), (2, 16)],
                         ids=["5+4+8", "1+1+15", "2+14+1"])
def test_tails_carried_over_three_chunks(cuts):
    """17 rows in three chunks cut where a chunk is shorter than the tail,
    the last W-1 rows carried between them: one chunk of the whole."""
    T, W, tile = 17, 3, (16,)
    rows, w, _ = _operands(T, W, tile, seed=2, bias=False)
    tail = jnp.zeros((W - 1,) + tile, jnp.float32)
    want = conv_chunk(rows, tail, w, None, None, "short_conv")
    got, lo = [], 0
    for hi in cuts + (T,):
        got.append(conv_chunk(rows[lo:hi], tail, w, None, None,
                              "short_conv"))
        tail = jnp.concatenate([tail, rows[lo:hi]])[-(W - 1):]
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(got), want, rtol=1e-6,
                               atol=1e-6)


def test_no_activation_is_not_silu():
    """`act` None gives the sum itself; SiLU of that sum is the other
    callers' form, and the two differ."""
    rows, w, _ = _operands(9, 3, (8,), seed=3, bias=False)
    zero = jnp.zeros((2, 8), jnp.float32)
    bare = conv_chunk(rows, zero, w, None, None, "short_conv")
    silu = conv_chunk(rows, zero, w, None, jax.nn.silu, "short_conv")
    np.testing.assert_allclose(silu, jax.nn.silu(bare), rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(silu - bare).max()) > 0.1


def test_an_empty_slot_moves_nothing():
    """A step's caller keeps an empty slot's entry (the null one) as it
    was: `where(live, new, old)` over what `conv_step` hands back, the
    models' one line — zeros stay zeros, and a live slot's tail is not
    touched by its neighbour's row."""
    W, tile, B = 3, (8,), 4
    rows, w, _ = _operands(B, W, tile, seed=4, bias=False)
    arena = jnp.zeros((3, W - 1) + tile, jnp.float32).at[2].set(7.0)
    idx = jnp.asarray([0, 2, 0, 0])           # slot 1 holds entry 2
    live = jnp.asarray([False, True, False, False])
    old = arena[idx]
    _, new = conv_step(rows, old, w, None, None, "short_conv")
    arena = arena.at[idx].set(jnp.where(live[:, None, None], new, old))
    np.testing.assert_array_equal(arena[0], 0.0)
    np.testing.assert_array_equal(arena[1], 0.0)
    np.testing.assert_array_equal(arena[2, 0], 7.0)
    np.testing.assert_array_equal(arena[2, 1], rows[1])


def _parent_chunk(rows, tail, w, b):
    """`ops/kda.conv_chunk` as it stood before PR 63, written out."""
    T, W = rows.shape[0], w.shape[0]
    ext = jnp.concatenate([tail.astype(rows.dtype), rows], axis=0)
    acc = b.astype(jnp.float32) + sum(
        w[i].astype(jnp.float32)
        * jax.lax.slice_in_dim(ext, i, i + T, axis=0).astype(jnp.float32)
        for i in range(W))
    return jax.nn.silu(acc)


def _parent_step(row, tail, w, b):
    """`ops/kda.conv_step` as it stood before PR 63, written out."""
    ext = jnp.concatenate([tail, row[:, None].astype(tail.dtype)], axis=1)
    acc = b.astype(jnp.float32) + jnp.einsum(
        "bw...,w...->b...", ext.astype(jnp.float32), w.astype(jnp.float32))
    return jax.nn.silu(acc), ext[:, 1:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kdas_two_names_give_what_they_gave(dtype):
    """`ops/kda.conv_chunk` / `conv_step` — `shortconv`'s two with SiLU and
    the scope `kda_conv` bound — bit for bit the parent's on seeded rows,
    eagerly and under `jit` (the three models' programs hold them)."""
    W, tile = 4, (3, 16)
    rows, w, b = _operands(24, W, tile, seed=5)
    rows = rows.astype(dtype)
    tail = _operands(W - 1, W, tile, seed=6)[0]
    for wrap in (lambda f: f, jax.jit):
        np.testing.assert_array_equal(
            wrap(kda.conv_chunk)(rows, tail, w, b),
            wrap(_parent_chunk)(rows, tail, w, b))
        tails = jnp.stack([tail, tail * 2.0])
        got = wrap(kda.conv_step)(rows[:2], tails, w, b)
        want = wrap(_parent_step)(rows[:2], tails, w, b)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
