"""ops/retention.py: the chunked form = the recurrent form = the plain
reference's quadratic form (benchmarks/reference/brumby_plain.retain: a_ij
over all pairs, no feature map, no state), on seeded inputs, in float32.

What separates the sides is the order of sums: 2e-6 relative on the CPU.
The tolerance is 1e-4; a state that forgets one key, or a gate applied one
position late, moves an output by more than 1e-2."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import brumby_plain as ref
from ray_tpu.ops import retention as R

TOL = 1e-4
HKV, G, DH, S = 2, 3, 16, 37


@pytest.fixture(scope="module")
def data():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (S, HKV * G, DH))
    k = jax.random.normal(ks[1], (S, HKV, DH))
    v = jax.random.normal(ks[2], (S, HKV, DH))
    lg = jax.nn.log_sigmoid(3 + jax.random.normal(ks[3], (S, HKV)))
    want = ref.retain(q, k, v, jnp.cumsum(lg, 0), {"ret_eps": R.EPS})
    return q, k, v, lg, np.asarray(want)


def _rel(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_phi_is_the_square_of_the_inner_product():
    a, b = jax.random.normal(jax.random.PRNGKey(1), (2, 7, DH))
    assert R.phi(a).shape == (7, R.state_shape(DH)[1])
    np.testing.assert_allclose((R.phi(a) * R.phi(b)).sum(-1),
                               (a * b).sum(-1) ** 2, rtol=1e-5, atol=1e-4)
    # dh (dh + 1) / 2 features are used; the padding to whole lanes is 0
    assert int((np.asarray(R.phi(a)) != 0).sum(-1).max()) \
        == DH * (DH + 1) // 2
    assert R.state_shape(128) == (136, 8320)


def _chunks(data, C):
    """The sequence C rows at a time, the last chunk ragged and padded."""
    q, k, v, lg, _ = data
    state = jnp.zeros((HKV,) + R.state_shape(DH))
    outs = []
    for c0 in range(0, S, C):
        n = min(C, S - c0)
        pad = lambda x: jnp.pad(x[c0:c0 + n],
                                ((0, C - n),) + ((0, 0),) * (x.ndim - 1))
        o, state = R.retention_chunk(
            jnp.moveaxis(pad(q).reshape(C, HKV, G, DH), 0, 2),
            jnp.moveaxis(pad(k), 0, 1), jnp.moveaxis(pad(v), 0, 1),
            pad(lg).T, state, dtype=jnp.float32)
        outs.append(jnp.moveaxis(o, 2, 0).reshape(C, HKV * G, DH)[:n])
    return jnp.concatenate(outs), state


@pytest.mark.parametrize("C", [4, 8, 16, 37, 64])
def test_chunked_form_is_the_quadratic_form(data, C):
    got, _ = _chunks(data, C)
    assert _rel(got, data[-1]) < TOL


def _steps(data, impl, t0=0, arena=None, dtype=jnp.float32):
    """Token by token in slots 0 and 2 of three (entries 2 and 3 of
    layer 1 of an arena of two layers and four entries); slot 1 is empty
    and rides on the null entry."""
    q, k, v, lg, _ = data
    if arena is None:
        arena = jnp.zeros((2, 4, HKV) + R.state_shape(DH))
    idx, live = jnp.array([2, 0, 3]), jnp.array([1, 0, 1])
    outs = []
    for t in range(t0, S):
        three = lambda a: jnp.stack([a] * 3)
        o, arena = R.retention_step(
            three(q[t].reshape(HKV, G, DH)), three(k[t]), three(v[t]),
            three(lg[t]), arena, 1, idx, live, impl=impl, dtype=dtype)
        outs.append(o)
    return jnp.stack(outs), arena


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_recurrent_form_is_the_quadratic_form(data, impl):
    o, arena = _steps(data, impl)
    for slot in (0, 2):
        assert _rel(o[:, slot].reshape(S, HKV * G, DH), data[-1]) < TOL
    # the empty slot puts out nothing and the null entry, like the entry
    # nobody holds, stays empty
    assert float(jnp.abs(o[:, 1]).max()) == 0.0
    assert float(jnp.abs(arena[1, :2]).max()) == 0.0
    assert float(jnp.abs(arena[0]).max()) == 0.0        # the other layer
    np.testing.assert_array_equal(arena[1, 2], arena[1, 3])


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_chunks_then_steps_carry_one_state(data, impl):
    """A prompt in chunks, then decoding from the state the chunks left:
    the two forms keep the same state in the same layout."""
    q, k, v, lg, want = data
    head = tuple(a[:24] for a in (q, k, v, lg))
    state = jnp.zeros((HKV,) + R.state_shape(DH))
    for c0 in (0, 8, 16):
        sl = lambda x: x[c0:c0 + 8]
        _, state = R.retention_chunk(
            jnp.moveaxis(sl(head[0]).reshape(8, HKV, G, DH), 0, 2),
            jnp.moveaxis(sl(head[1]), 0, 1), jnp.moveaxis(sl(head[2]), 0, 1),
            sl(head[3]).T, state, dtype=jnp.float32)
    arena = jnp.zeros((2, 4, HKV) + R.state_shape(DH)).at[1, 2].set(state)
    arena = arena.at[1, 3].set(state)
    o, _ = _steps(data, impl, t0=24, arena=arena)
    assert _rel(o[:, 0].reshape(S - 24, HKV * G, DH), want[24:]) < TOL


def test_a_forgotten_key_or_a_late_gate_is_seen(data):
    """What the tolerance must see: the state without its first key, and
    the gate of position t applied at t + 1."""
    q, k, v, lg, want = data
    got, _ = _chunks((q, k.at[0].set(0), v, lg, want), 8)
    assert _rel(got[1:], want[1:]) > 1e-2
    got, _ = _chunks((q, k, v, jnp.roll(lg, 1, 0), want), 8)
    assert _rel(got, want) > 1e-2


def test_bf16_operands_stay_near_the_float32_result(data):
    """The serve path multiplies in bf16 and accumulates in float32, state
    in float32: within a few per cent of the float32 result at this size
    (a weighted mean of 37 values of size 1)."""
    q, k, v, lg, want = data
    o, _ = _steps(data, "xla", dtype=jnp.bfloat16)
    assert _rel(o[:, 0].reshape(S, HKV * G, DH), want) < 0.1


@pytest.mark.parametrize("live", [(0, 0, 0), (0, 1, 0), (1, 0, 0), (0, 0, 1),
                                  (1, 1, 1), (0, 1, 1)])
def test_kernel_moves_only_live_slots_blocks(live):
    """An empty slot's grid turns point at a live neighbour's block and
    leave it alone (`_visit`): whatever the pattern of live slots, the
    kernel gives what the gather / update / scatter form gives, the null
    entry and the entries nobody holds stay as they were."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (3, HKV, G, DH))
    k, v = jax.random.normal(ks[1], (2, 3, HKV, DH))
    lg = jax.nn.log_sigmoid(jax.random.normal(ks[2], (3, HKV)) + 2)
    arena = jax.random.normal(ks[3], (2, 5, HKV) + R.state_shape(DH))
    live = jnp.array(live)
    idx = jnp.where(live != 0, jnp.array([3, 1, 4]), 0)
    want_o, want = R.retention_step(q, k, v, lg, arena, 1, idx, live,
                                    impl="xla", dtype=jnp.float32)
    got_o, got = R.retention_step(q, k, v, lg, arena, 1, idx, live,
                                  impl="pallas_interpret",
                                  dtype=jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[0], arena[0])
    np.testing.assert_array_equal(got[1, 0], arena[1, 0])
    entry, head, flag = R._visit(idx, live, HKV)
    n = int((live != 0).sum())
    # the blocks the pipeline moves: one a change of (entry, head)
    turns = [(int(entry[b]), h if int(head[b]) < 0 else int(head[b]))
             for b in range(3) for h in range(HKV)]
    moved = 1 + sum(a != b for a, b in zip(turns, turns[1:]))
    assert moved == max(n * HKV, 1)


@jax.jit
def _recur(q, k, v, lg, state):
    """Token by token from `state` [Hkv, R, F]: q [T, Hkv, G, dh], k, v
    [T, Hkv, dh], lg [T, Hkv] -> (o [T, Hkv, G, dh], the state after)."""
    arena = jnp.zeros((1, 2) + state.shape).at[0, 1].set(state)
    idx, live = jnp.array([1]), jnp.array([1])

    def step(arena, x):
        o, arena = R.retention_step(*(a[None] for a in x), arena, 0, idx,
                                    live, impl="xla", dtype=jnp.float32)
        return arena, o[0]

    arena, o = jax.lax.scan(step, arena, (q, k, v, lg))
    return o, arena[0, 1]


def _as_chunk(q, k, v, lg, C):
    """[T, ..] rows -> `retention_chunk`'s operands, padded to C rows (a
    pad row: k = 0, log g = 0; its q and v are whatever came)."""
    n = q.shape[0]
    real = (jnp.arange(C) < n)
    pad = lambda x: jnp.pad(x, ((0, C - n),) + ((0, 0),) * (x.ndim - 1),
                            constant_values=0.5)
    k = jnp.where(real[:, None, None], pad(k), 0)
    lg = jnp.where(real[:, None], pad(lg), 0.0)
    return (jnp.moveaxis(pad(q), 0, 2), jnp.moveaxis(k, 0, 1),
            jnp.moveaxis(pad(v), 0, 1), lg.T)


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("hkv,g,dh", [(2, 3, 16), (1, 5, 128)],
                         ids=["dh16", "g5dh128"])
@pytest.mark.parametrize("C", [128, 256, 384, 512])
def test_chunk_kernel_is_the_xla_chunk_and_the_recurrence(C, hkv, g, dh,
                                                          carried):
    """The chunk's kernel (interpret mode) at the engine's four program
    lengths, the last 5 rows padding: its outputs and its float32 state
    against the XLA body's and against the token-by-token form, from an
    empty and from a carried state; then 4 steps from the state it left
    against one longer chunk (the XLA body: any length)."""
    n, more = C - 5, 4
    ks = jax.random.split(jax.random.PRNGKey(C + dh), 5)
    q = jax.random.normal(ks[0], (n + more, hkv, g, dh))
    k = jax.random.normal(ks[1], (n + more, hkv, dh))
    v = jax.random.normal(ks[2], (n + more, hkv, dh))
    lg = jax.nn.log_sigmoid(3 + jax.random.normal(ks[3], (n + more, hkv)))
    state = jnp.zeros((hkv,) + R.state_shape(dh))
    if carried:
        # what some earlier keys left: a state is not any array
        _, state = _recur(*(a[:8] for a in (q, k[::-1], v[::-1], lg)), state)
    ops = _as_chunk(q[:n], k[:n], v[:n], lg[:n], C)
    got_o, got_s = R.retention_chunk(*ops, state, impl="pallas_interpret",
                                     dtype=jnp.float32)
    xla_o, xla_s = R.retention_chunk(*ops, state, impl="xla",
                                     dtype=jnp.float32)
    want_o, want_s = _recur(q[:n], k[:n], v[:n], lg[:n], state)
    rows = lambda o: np.asarray(jnp.moveaxis(o, 2, 0))[:n]
    assert _rel(rows(got_o), rows(xla_o)) < TOL
    # (a first row's weight is ONE square, which may lie near EPS: the sum
    # over features resolves it to 1e-5 and the quotient shows it)
    first = 0 if carried else 1
    assert _rel(rows(got_o)[first:], np.asarray(want_o)[first:]) < TOL
    assert _rel(got_s, np.asarray(xla_s)) < TOL
    assert _rel(got_s, np.asarray(want_s)) < TOL
    # a chunk followed by steps = one longer chunk
    then_o, then_s = _recur(q[n:], k[n:], v[n:], lg[n:], got_s)
    long_o, long_s = R.retention_chunk(
        *_as_chunk(q, k, v, lg, n + more), state, impl="xla",
        dtype=jnp.float32)
    assert _rel(then_o, np.asarray(jnp.moveaxis(long_o, 2, 0))[n:]) < TOL
    assert _rel(then_s, np.asarray(long_s)) < TOL


def test_chunk_kernel_rounds_where_the_xla_chunk_rounds():
    """bf16 operands: the kernel casts what the XLA body casts (features
    after they are formed in float32, the carried state for the read-out
    alone), so the two differ by the order of float32 sums — far inside
    what one bf16 rounding moves (2^-9)."""
    hkv, g, C, dh = 2, 5, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    bf = lambda key, *shape: jax.random.normal(key, shape, jnp.bfloat16)
    q, k, v = bf(ks[0], hkv, g, C, dh), bf(ks[1], hkv, C, dh), bf(
        ks[2], hkv, C, dh)
    lg = jax.nn.log_sigmoid(3 + jax.random.normal(ks[3], (hkv, C)))
    state = jax.random.normal(ks[4], (hkv,) + R.state_shape(dh))
    got = R.retention_chunk(q, k, v, lg, state, impl="pallas_interpret")
    want = R.retention_chunk(q, k, v, lg, state, impl="xla")
    assert got[1].dtype == jnp.float32
    for a, b in zip(got, want):
        assert _rel(a, np.asarray(b)) < 1e-5


def test_chunk_rejects_an_unknown_impl_and_a_bf16_state():
    z = jnp.zeros
    args = (z((1, 1, 8, 16)), z((1, 8, 16)), z((1, 8, 16)), z((1, 8)))
    with pytest.raises(ValueError, match="unknown retention impl"):
        R.retention_chunk(*args, z((1,) + R.state_shape(16)), impl="mosaic")
    with pytest.raises(ValueError, match="float32"):
        R.retention_chunk(*args, z((1,) + R.state_shape(16), jnp.bfloat16),
                          impl="pallas_interpret")
