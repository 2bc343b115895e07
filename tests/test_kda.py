"""ops/kda.py against the recurrence written token by token: the chunked WY
form over several chunks (blocks of four sub-blocks, of one, and padded),
the step in both of its bodies (with an empty slot in the batch), every
gate at its lower bound for a whole chunk, and the short convolution's
tail across chunk boundaries."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

H, DK, DV = 3, 16, 16
LOWER = -5.0


def _inputs(T, seed=0, gate=None, heads=H, d=DK):
    """`gate`: None (uniform down to the lower bound), a number, or a row
    of `d` numbers, a channel each."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.standard_normal((heads, T, d))) * d ** -0.5
    k = unit(r.standard_normal((heads, T, d)))
    v = r.standard_normal((heads, T, d))
    log_a = (LOWER * r.uniform(0.0, 1.0, (heads, T, d)) if gate is None
             else np.broadcast_to(gate, (heads, T, d)))
    beta = r.uniform(0.05, 0.95, (heads, T))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, log_a, beta))


def _recurrence(q, k, v, log_a, beta, state):
    """The module docstring's two lines in float64, a token at a time;
    state [H, dv, dk] as the ops keep it."""
    q, k, v, log_a, beta = (np.asarray(x, np.float64)
                            for x in (q, k, v, log_a, beta))
    s = np.swapaxes(np.asarray(state, np.float64), 1, 2)       # [H, dk, dv]
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        for h in range(q.shape[0]):
            sp = np.exp(log_a[h, t])[:, None] * s[h]
            u = beta[h, t] * (v[h, t] - sp.T @ k[h, t])
            s[h] = sp + np.outer(k[h, t], u)
            out[h, t] = s[h].T @ q[h, t]
    return out, np.swapaxes(s, 1, 2)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("T,chunk,block,heads,d", [
    (48, 16, 8, H, DK), (40, 40, 16, H, DK), (96, 32, 64, H, DK),
    (128, 64, 64, 2, 32), (200, 100, 64, 2, 32), (40, 40, 64, H, DK),
    (36, 12, 12, H, DK)],
    ids=["3x16by8", "1x40by16", "3x32whole", "2x64in4subs", "2x100padded",
         "1x40untiled", "3x12by12"])
def test_chunks_carry_the_state_like_the_recurrence(T, chunk, block, heads,
                                                    d):
    """Several chunks, each in WY blocks, against the token-by-token
    recurrence: float32 throughout, so 1e-5 of the largest value.  Blocks
    of 64 rows in four sub-blocks (the products below the diagonal
    sub-blocks exist from the second on), a chunk whose last block is
    padded (two cases; one's pad fills sub-blocks whole), blocks shorter
    than a sub-block, and a block of 40 rows, which sub-blocks do not
    tile."""
    assert kda.SUB_BLOCK == 16 and kda.BLOCK == 64
    xs = _inputs(T, heads=heads, d=d)
    state = jnp.zeros((heads, d, d), jnp.float32)
    outs = []
    for c in range(0, T, chunk):
        o, state = kda.kda_chunk(*(x[:, c:c + chunk] for x in xs), state,
                                 block=block)
        outs.append(o)
    want, s_want = _recurrence(*xs, np.zeros((heads, d, d)))
    _close(jnp.concatenate(outs, 1), want, 1e-5)
    _close(state, s_want, 1e-5)


@pytest.mark.parametrize("gate", [
    LOWER, np.where(np.arange(DK) % 2, LOWER, -0.001)], ids=["every", "mixed"])
def test_every_gate_at_the_lower_bound_for_a_whole_chunk(gate):
    """log a = -5 in every channel of every row of a 64-row block: the
    running sum reaches -320, whose exponential and its inverse both
    leave float32; the differences inside a sub-block and the two factors
    between sub-blocks do not, or only where their product does.  Mixed:
    every other channel at -0.001, so that a product of keys holds
    channels whose factor has gone to zero beside channels that have
    hardly decayed."""
    xs = _inputs(128, seed=1, gate=gate)
    start = jnp.asarray(np.random.default_rng(2).standard_normal(
        (H, DV, DK)), jnp.float32)
    o, state = kda.kda_chunk(*xs, start)
    assert np.isfinite(np.asarray(o)).all()
    want, s_want = _recurrence(*xs, start)
    _close(o, want, 1e-5)
    _close(state, s_want, 1e-5)
    assert float(jnp.exp(-jnp.cumsum(xs[3], 1)).max()) == np.inf


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_step_is_the_recurrence_and_leaves_an_empty_slot_alone(impl):
    """Four slots walked six tokens through layer 1 of a two-layer arena,
    slot 2 empty (on the null entry): the live slots follow the
    recurrence, every other entry of the arena stays as it was."""
    B, T, N = 4, 6, 6
    r = np.random.default_rng(3)
    arena = jnp.asarray(r.standard_normal((2, N, H, DV, DK)), jnp.float32)
    before = np.asarray(arena)
    idx = jnp.asarray([3, 1, 0, 5], jnp.int32)
    live = jnp.asarray([1, 1, 0, 1], jnp.int32)
    per_slot = [_inputs(T, seed=10 + b) for b in range(B)]
    outs = []
    for t in range(T):
        q, k, v, log_a, beta = (jnp.stack([s[n][:, t] for s in per_slot])
                                for n in range(5))
        o, arena = kda.kda_step(q, k, v, log_a, beta, arena, jnp.int32(1),
                                idx, live, impl=impl)
        outs.append(np.asarray(o))
    outs = np.stack(outs, 2)                                   # [B, H, T, dv]
    after = np.asarray(arena)
    for b in (0, 1, 3):
        want, s_want = _recurrence(*per_slot[b], before[1, int(idx[b])])
        _close(outs[b], want, 1e-5)
        _close(after[1, int(idx[b])], s_want, 1e-5)
    assert (outs[2] == 0).all()
    untouched = np.ones(before.shape[:2], bool)
    untouched[1, [3, 1, 5]] = False
    assert (after[untouched] == before[untouched]).all()


def test_step_kernel_with_no_live_slot_hands_the_arena_back():
    arena = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 3, H, DV, DK)), jnp.float32)
    before = np.asarray(arena)
    z = lambda *s: jnp.zeros(s, jnp.float32)
    o, arena = kda.kda_step(z(2, H, DK), z(2, H, DK), z(2, H, DV),
                            z(2, H, DK), z(2, H), arena, 0,
                            jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
                            impl="pallas_interpret")
    assert (np.asarray(o) == 0).all() and (np.asarray(arena) == before).all()


def test_chunk_then_steps_is_one_recurrence():
    """A prompt in two chunks, then steps from the state the chunks left:
    what the engine does to one sequence."""
    xs = _inputs(40, seed=5)
    state = jnp.zeros((H, DV, DK), jnp.float32)
    outs = []
    for lo, hi in ((0, 16), (16, 32)):
        o, state = kda.kda_chunk(*(x[:, lo:hi] for x in xs), state, block=8)
        outs.append(o)
    arena = jnp.zeros((1, 2, H, DV, DK), jnp.float32).at[0, 1].set(state)
    for t in range(32, 40):
        o, arena = kda.kda_step(*(x[:, t][None] for x in xs), arena, 0,
                                jnp.ones(1, jnp.int32), jnp.ones(1, jnp.int32),
                                impl="xla")
        outs.append(o[0][:, None])
    want, s_want = _recurrence(*xs, np.zeros((H, DV, DK)))
    _close(jnp.concatenate(outs, 1), want, 1e-5)
    _close(arena[0, 1], s_want, 1e-5)


@pytest.mark.parametrize("cuts", [(7, 13), (1, 2, 3, 14), (20,)],
                         ids=["7+6+7", "1+1+1+11+6", "whole"])
def test_conv_tail_across_chunk_boundaries(cuts):
    """The convolution of 20 rows in chunks cut where no multiple of 4
    falls, the tail (the last 3 pre-conv rows) carried between them, then
    two steps: the same as the sum over four shifted rows of the whole."""
    T, C, W = 22, 5, 4
    r = np.random.default_rng(6)
    rows = jnp.asarray(r.standard_normal((T, C)), jnp.float32)
    w = jnp.asarray(r.standard_normal((W, C)), jnp.float32)
    b = jnp.asarray(r.standard_normal(C), jnp.float32)
    padded = np.concatenate([np.zeros((W - 1, C)), np.asarray(rows)])
    acc = np.asarray(b) + sum(np.asarray(w)[i] * padded[i:i + T]
                              for i in range(W))
    want = acc / (1.0 + np.exp(-acc))
    tail = jnp.zeros((W - 1, C), jnp.float32)
    got, lo = [], 0
    for hi in cuts + (20,):
        if hi == lo:
            continue
        got.append(kda.conv_chunk(rows[lo:hi], tail, w, b))
        tail = jnp.concatenate([tail, rows[lo:hi]])[-(W - 1):]
        lo = hi
    for t in (20, 21):
        y, tails = kda.conv_step(rows[t][None], tail[None], w, b)
        got.append(y)
        tail = tails[0]
    np.testing.assert_allclose(np.concatenate(got), want, rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(rows[-3:]))


def test_unknown_impl_and_half_state_are_refused():
    z = lambda *s: jnp.zeros(s, jnp.float32)
    args = (z(1, H, DK), z(1, H, DK), z(1, H, DV), z(1, H, DK), z(1, H))
    one = jnp.ones(1, jnp.int32)
    with pytest.raises(ValueError, match="unknown kda impl"):
        kda.kda_step(*args, z(1, 2, H, DV, DK), 0, one, one, impl="cuda")
    with pytest.raises(ValueError, match="float32"):
        kda.kda_step(*args, jnp.zeros((1, 2, H, DV, DK), jnp.bfloat16), 0,
                     one, one, impl="pallas_interpret")
