"""ops/ssd.py against the recurrence written a token at a time in float64
(numpy): the chunked form, chunks then steps, a state carried over
several chunks, the groups, the kernels in interpret mode, empty slots."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import retention
from ray_tpu.ops.ssd import ssd_chunk, ssd_step

F32 = jnp.float32


def _draw(seed, T, H=4, G=2, P=8, N=16):
    r = np.random.default_rng(seed)
    return dict(
        x=r.normal(size=(T, H, P)), dt=np.exp(r.uniform(-5, -0.5, (T, H))),
        a=-np.exp(r.uniform(-1, 1.5, H)), b=r.normal(size=(T, G, N)),
        c=r.normal(size=(T, G, N)), d=r.normal(size=H))


def _recurrence(w, state=None):
    """The equations, a token and a head at a time -> (y [T, H, P], S)."""
    T, H, P = w["x"].shape
    G, N = w["b"].shape[1:]
    S = np.zeros((H, P, N)) if state is None else np.array(state, np.float64)
    y = np.zeros((T, H, P))
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            S[h] = (np.exp(w["dt"][t, h] * w["a"][h]) * S[h]
                    + w["dt"][t, h] * np.outer(w["x"][t, h], w["b"][t, g]))
            y[t, h] = S[h] @ w["c"][t, g] + w["d"][h] * w["x"][t, h]
    return y, S


def _chunk(w, state, rows=slice(None), **kw):
    f = lambda k: jnp.asarray(w[k][rows] if w[k].ndim > 1 else w[k], F32)
    return ssd_chunk(f("x"), f("dt"), f("a"), f("b"), f("c"), f("d"),
                     jnp.asarray(state, F32), dtype=F32, **kw)


def _close(got, want, tol=2e-5):
    # float32 sums of up to 64 products against float64: a few 1e-6 of the
    # largest value
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float64) / scale,
                               want / scale, atol=tol)


@pytest.mark.parametrize("T,block", [(64, 16), (48, 16), (40, 16)],
                         ids=["whole", "three", "padded"])
def test_chunk_is_the_recurrence(T, block):
    w = _draw(T, T)
    s0 = np.random.default_rng(1).normal(size=(4, 8, 16))
    y, S = _chunk(w, s0, impl="xla", block=block)
    want_y, want_S = _recurrence(w, s0)
    _close(y, want_y)
    _close(S, want_S)


def test_state_carried_over_three_chunks_and_then_steps():
    """Three chunks of one sequence, then eight steps through an arena,
    against ONE long pass of the recurrence."""
    T, n = 48, 8
    w = _draw(7, T + n)
    want_y, want_S = _recurrence(w)
    S = np.zeros((4, 8, 16))
    ys = []
    for k in range(3):
        y, S = _chunk(w, S, slice(16 * k, 16 * k + 16), impl="xla", block=16)
        ys.append(y)
    _close(jnp.concatenate(ys), want_y[:T])
    # the one-chunk pass gives the same state
    _close(_chunk(w, np.zeros((4, 8, 16)), slice(0, T), impl="xla",
                  block=16)[1], S)
    arena = jnp.zeros((2, 3, 4, 8, 16), F32).at[1, 2].set(S)
    f = lambda k, t: jnp.asarray(w[k][t:t + 1], F32)
    for t in range(T, T + n):
        y, arena = ssd_step(f("x", t), f("dt", t), jnp.asarray(w["a"], F32),
                            f("b", t), f("c", t), jnp.asarray(w["d"], F32),
                            arena, 1, jnp.array([2]), jnp.array([1]),
                            impl="xla")
        _close(y[0], want_y[t])
    _close(arena[1, 2], want_S)
    assert not np.asarray(arena[0]).any() and not np.asarray(arena[1, :2]).any()


def test_head_sixteen_reads_group_one():
    """32 heads in 2 groups: heads 0..15 read B, C of group 0 and heads
    16..31 of group 1 — with group 1's B, C changed only the latter move."""
    w = _draw(3, 32, H=32, G=2, P=4, N=8)
    zero = np.zeros((32, 4, 8))
    y, S = _chunk(w, zero, impl="xla", block=16)
    want_y, want_S = _recurrence(w)
    _close(y, want_y)
    _close(S, want_S)
    w2 = dict(w, b=w["b"].copy(), c=w["c"].copy())
    w2["b"][:, 1] += 1.0
    w2["c"][:, 1] -= 1.0
    y2, _ = _chunk(w2, zero, impl="xla", block=16)
    moved = np.abs(np.asarray(y2 - y)).max(axis=(0, 2))
    assert (moved[:16] == 0).all() and (moved[16:] > 1e-3).all()


def test_chunk_kernel_interpreted_is_the_xla_form():
    w = _draw(11, 64, H=4, G=2, P=16, N=128)
    s0 = np.random.default_rng(2).normal(size=(4, 16, 128))
    y, S = _chunk(w, s0, impl="xla", block=32)
    yk, Sk = _chunk(w, s0, impl="pallas_interpret", block=32)
    np.testing.assert_allclose(yk, y, rtol=0, atol=2e-5 * np.abs(y).max())
    np.testing.assert_allclose(Sk, S, rtol=0, atol=2e-5 * np.abs(S).max())


def test_chunk_products_in_bfloat16_stay_near():
    """bf16 operands, float32 sums and state: 2^-8 a product."""
    w = _draw(13, 64)
    want_y, want_S = _recurrence(w)
    f = lambda k: jnp.asarray(w[k], F32)
    for impl in ("xla", "pallas_interpret"):
        y, S = ssd_chunk(f("x"), f("dt"), f("a"), f("b"), f("c"), f("d"),
                         jnp.zeros((4, 8, 16), F32), impl=impl, block=16)
        assert S.dtype == F32 and y.dtype == F32
        _close(y, want_y, 2e-2)
        _close(S, want_S, 2e-2)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0), (0, 0, 1, 0, 0),
                                  (0, 0, 0, 0, 0)],
                         ids=["some", "one", "none"])
def test_step_moves_live_slots_only(impl, live):
    """Five slots on entries of a dirtied arena: a live slot's entry is the
    recurrence's next state and its y the read-out; an empty slot reads 0
    and every other entry, the null one and the other layer's stay bit for
    bit."""
    H, G, P, N = 4, 2, 8, 128
    r = np.random.default_rng(5)
    arena = jnp.asarray(r.normal(size=(2, 7, H, P, N)), F32)
    idx = np.array([3, 0, 6, 1, 0])
    live = np.array(live)
    w = _draw(9, 5, H, G, P, N)
    f = lambda k: jnp.asarray(w[k], F32)
    y, out = ssd_step(f("x"), f("dt"), f("a"), f("b"), f("c"), f("d"), arena,
                      jnp.asarray(1), jnp.asarray(idx), jnp.asarray(live),
                      impl=impl)
    want = np.asarray(arena).copy()
    for s in range(5):
        one = {k: (v[s:s + 1] if v.ndim > 1 else v) for k, v in w.items()}
        if live[s]:
            ys, S = _recurrence(one, np.asarray(arena)[1, idx[s]])
            _close(y[s], ys[0])
            _close(out[1, idx[s]], S)
            want[1, idx[s]] = np.asarray(out[1, idx[s]])
        else:
            assert not np.asarray(y[s]).any()
    np.testing.assert_array_equal(np.asarray(out), want)


def test_visit_has_one_public_home():
    from ray_tpu.ops import kda, mamba, ssd

    assert retention.visit is retention._visit
    assert kda.visit is mamba.visit is ssd.visit is retention.visit
    assert (kda.resolve_impl is mamba.resolve_impl is ssd.resolve_impl
            is retention.resolve_impl)
