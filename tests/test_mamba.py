"""ops/mamba.py: the selective scan's chunk (the kernel in interpret mode
and the XLA scan) and its step (both bodies) against the plain recurrence
written out token by token in numpy, float64.

Tolerances: float32 elementwise work against float64 — a state element
carries ~1e-7 of relative error a token, so 1e-5 on values of order one
leaves two orders of room; a bfloat16 state (8 mantissa bits) stands at
~4e-3 and one test says so."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import mamba as M
from ray_tpu.ops.kda import conv_chunk, conv_step

TOL = 1e-5
C, N = 256, 16


def _inputs(T, seed=0, dt_range=(1e-3, 1e-1)):
    rng = np.random.default_rng(seed)
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    dt = np.exp(rng.uniform(lo, hi, (T, C)))
    # step sizes at BOTH ends of their range: a state that keeps nearly
    # everything (exp(-1e-3)) beside one that forgets in a token
    # (exp(-0.1 * 16)); the first and last channel pinned there
    dt[:, 0], dt[:, -1] = dt_range[0], dt_range[1]
    a = -np.broadcast_to(np.arange(1, N + 1, dtype=np.float64)[:, None],
                         (N, C))
    return (rng.standard_normal((T, C)), dt, a, rng.standard_normal((T, N)),
            rng.standard_normal((T, N)), rng.standard_normal((N, C)))


def _recurrence(u, dt, a, bm, cm, h):
    """The equations as written, a token at a time, float64."""
    ys = []
    for t in range(u.shape[0]):
        h = np.exp(dt[t][None, :] * a) * h + (dt[t] * u[t])[None, :] \
            * bm[t][:, None]
        ys.append((h * cm[t][:, None]).sum(0))
    return np.stack(ys), h


def _f32(*xs):
    return tuple(jnp.asarray(x, jnp.float32) for x in xs)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_chunks_are_the_recurrence(impl):
    """Several chunks of uneven length (21 rows: no multiple of 8) carried
    through their state = the recurrence over the whole sequence."""
    u, dt, a, bm, cm, h0 = _inputs(61)
    want_y, want_h = _recurrence(u, dt, a, bm, cm, h0)
    h, ys = jnp.asarray(h0, jnp.float32), []
    for lo, hi in ((0, 16), (16, 37), (37, 61)):
        y, h = M.selective_scan_chunk(*_f32(u[lo:hi], dt[lo:hi], a,
                                            bm[lo:hi], cm[lo:hi]), h,
                                      impl=impl)
        ys.append(np.asarray(y))
    assert np.abs(np.concatenate(ys) - want_y).max() < TOL * 10
    assert np.abs(np.asarray(h) - want_h).max() < TOL
    assert np.abs(want_y).max() > 1


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_pad_row_forgets_nothing_and_adds_nothing(impl):
    u, dt, a, bm, cm, h0 = _inputs(16)
    dt[11:] = 0.0
    _, h = M.selective_scan_chunk(*_f32(u, dt, a, bm, cm, h0), impl=impl)
    _, want = _recurrence(u[:11], dt[:11], a, bm[:11], cm[:11], h0)
    assert np.abs(np.asarray(h) - want).max() < TOL


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_steps_are_the_recurrence_and_an_empty_slot_moves_nothing(impl):
    """Token by token through the arena's layer 1, slots on entries
    (3, -, 1, 4) with slot 1 EMPTY: the live slots' states and outputs are
    the recurrence's, the empty slot reads 0, and neither its entry nor
    the arena's other layer changes by a bit."""
    T, B = 9, 4
    seqs = [_inputs(T, seed=s) for s in range(B)]
    rng = np.random.default_rng(9)
    arena = rng.standard_normal((2, 6, N, C)).astype(np.float32)
    idx, live = np.array([3, 0, 1, 4]), np.array([1, 0, 1, 1])
    for b in range(B):
        if live[b]:
            arena[1, idx[b]] = seqs[b][5]
    state, a = jnp.asarray(arena), seqs[0][2]
    ys = []
    for t in range(T):
        row = lambda k: np.stack([s[k][t] for s in seqs])
        y, state = M.selective_step(*_f32(row(0), row(1), a, row(3), row(4)),
                                    state, 1, jnp.asarray(idx),
                                    jnp.asarray(live), impl=impl)
        ys.append(np.asarray(y))
    ys, state = np.stack(ys, 1), np.asarray(state)
    for b in range(B):
        if not live[b]:
            assert (ys[b] == 0).all()
            continue
        want_y, want_h = _recurrence(*seqs[b])
        assert np.abs(ys[b] - want_y).max() < TOL * 10, b
        assert np.abs(state[1, idx[b]] - want_h).max() < TOL, b
    assert (state[0] == arena[0]).all()
    for e in (0, 2, 5):
        assert (state[1, e] == arena[1, e]).all(), e


def test_no_live_slot_leaves_the_arena_as_it_is():
    u, dt, a, bm, cm, _ = _inputs(2)
    arena = np.random.default_rng(1).standard_normal(
        (1, 3, N, C)).astype(np.float32)
    y, state = M.selective_step(*_f32(u, dt, a, bm, cm), jnp.asarray(arena),
                                0, jnp.zeros(2, jnp.int32),
                                jnp.zeros(2, jnp.int32),
                                impl="pallas_interpret")
    assert (np.asarray(state) == arena).all() and (np.asarray(y) == 0).all()


def test_a_bfloat16_state_fails_the_tolerance():
    u, dt, a, bm, cm, h0 = _inputs(48)
    _, want = _recurrence(u, dt, a, bm, cm, h0)
    h = jnp.asarray(h0, jnp.float32)
    for t in range(0, 48, 16):
        _, h = M.selective_scan_chunk(*_f32(u[t:t + 16], dt[t:t + 16], a,
                                            bm[t:t + 16], cm[t:t + 16]), h,
                                      impl="xla")
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
    assert np.abs(np.asarray(h) - want).max() > 100 * TOL


@pytest.mark.parametrize("cut", [5, 7, 13])
def test_the_conv_tail_crosses_a_cut_that_is_no_multiple_of_four(cut):
    """The width-4 conv (ops/kda.conv_chunk / conv_step, on this layer's
    [T, C] rows) over a sequence cut at `cut`, the tail the last three
    rows before the cut, then token by token = the conv over the whole."""
    rng = np.random.default_rng(cut)
    rows = jnp.asarray(rng.standard_normal((20, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, C)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(C), jnp.float32)
    zero = jnp.zeros((3, C), jnp.float32)
    want = np.asarray(conv_chunk(rows, zero, w, b))
    first = conv_chunk(rows[:cut], zero, w, b)
    tail = jnp.concatenate([zero, rows[:cut]])[-3:]
    second = conv_chunk(rows[cut:16], tail, w, b)
    tail = jnp.concatenate([tail, rows[cut:16]])[-3:][None]
    steps = []
    for t in range(16, 20):
        y, tail = conv_step(rows[t][None], tail, w, b)
        steps.append(y)
    got = np.concatenate([first, second] + steps)
    assert np.abs(got - want).max() < 1e-6
