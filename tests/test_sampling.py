"""The serve step's sampler (`ray_tpu/ops/sampling.py`) on the CPU: the
top-k threshold selected over the bits against the sorted row's, and
`sample` a slot against `gpt.sample_logits` a row under the same keys.
What the chip's compiler makes of it is `tests/test_chip_compile.py`'s."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.ops.sampling import sample
from ray_tpu.ops.select import from_ordered_bits, kth_largest, ordered_bits

_ROWS = ("random", "ties", "zeros", "one_value", "filtered", "wide")


def _rows(V, dtype):
    """[6, V]: a plain row; one of many ties (rounded values); one
    holding +0.0 and -0.0 among small values; one repeated value; one
    half at -1e30, as a filter leaves it; one spread over the dtype's
    range, both signs."""
    k = jax.random.split(jax.random.PRNGKey(V), 4)
    x = jax.random.normal(k[0], (len(_ROWS), V), jnp.float32) * 3
    x = x.at[1].set(jnp.round(x[1]))
    x = x.at[2].set(jnp.where(jax.random.bernoulli(k[1], 0.5, (V,)),
                              x[2] * 1e-3, 0.0))
    x = x.at[2, ::7].set(-0.0)
    x = x.at[3].set(2.5)
    x = x.at[4, ::2].set(-1e30)
    x = x.at[5].set(x[5] * jnp.exp(20 * jax.random.normal(k[2], (V,))))
    return x.astype(dtype)


def _bits(a):
    """An array's bytes, a zero's sign dropped: -0.0 and +0.0 sort as
    equals (which of them stands at an index is the order they came in)
    and no comparison tells them apart."""
    return np.asarray(a + 0.0).view(np.uint8)


@pytest.mark.parametrize("k", ["1", "2", "16", "V-1", "V", "a_row"])
@pytest.mark.parametrize("V", [50, 1000, 39296])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kth_largest_is_the_sorted_rows(dtype, V, k):
    x = _rows(V, dtype)
    ks = {"1": [1] * 6, "2": [2] * 6, "16": [16] * 6, "V-1": [V - 1] * 6,
          "V": [V] * 6, "a_row": [1, V // 2, 3, V, V - 1, 17]}[k]
    ks = jnp.asarray(ks, jnp.int32)
    want = jnp.take_along_axis(jnp.sort(x, axis=-1), (V - ks)[:, None],
                               axis=-1)[:, 0]
    got = jax.jit(kth_largest)(x, ks)
    assert got.dtype == x.dtype
    assert np.array_equal(_bits(got), _bits(want)), (got, want)
    if k == "V":            # top-k off: the row's minimum, filters nothing
        assert np.array_equal(_bits(got), _bits(x.min(axis=-1)))
        assert not bool((x < got[:, None]).any())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_ordered_bits_order_as_the_floats_and_map_back(dtype):
    x = jnp.sort(_rows(1000, dtype).reshape(-1))
    u = ordered_bits(x)
    assert u.dtype == jnp.uint32
    above, same = x[1:] > x[:-1], (x[1:] == x[:-1]) & (x[1:] != 0)
    assert bool((u[1:] > u[:-1])[above].all())
    assert bool((u[1:] == u[:-1])[same].all())
    assert int(ordered_bits(jnp.zeros((), dtype))) \
        == int(ordered_bits(-jnp.zeros((), dtype))) + 1
    back = from_ordered_bits(u, dtype)
    assert np.array_equal(np.asarray(back).view(np.uint8),
                          np.asarray(x).view(np.uint8))


_SLOTS = 6
# temperatures and top-ks of six slots (0 = greedy / top-k off)
_BATCHES = {
    "all_greedy": ([0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]),
    # a top_k only ever reaches a row that draws
    "all_greedy_with_top_k": ([0, 0, 0, 0, 0, 0], [5, 0, 1, 0, 40, 0]),
    "all_drawn": ([0.8, 1.3, 0.5, 1.0, 2.0, 0.7], [0, 0, 0, 0, 0, 0]),
    "all_drawn_top_k": ([0.8, 1.3, 0.5, 1.0, 2.0, 0.7],
                        [20, 1, 5, 1000, 999, 2]),
    "mixed": ([0, 0.8, 1.3, 0, 0.5, 1.0], [0, 0, 20, 5, 1, 1000]),
    "one_draws": ([0, 0, 0, 0, 0.9, 0], [0, 0, 0, 0, 40, 0]),
}


@pytest.mark.parametrize("batch", sorted(_BATCHES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_sample_is_sample_logits_a_row(dtype, batch):
    """Every slot's token is `gpt.sample_logits`' of that row alone under
    the same key: a drawn row's the same draw, a greedy row's the argmax,
    whatever the other rows of the batch ask for."""
    V = 1000
    # logits as a model computes them (in `dtype`) and the engine carries
    # them (f32); two near the top a hair apart, so a greedy row's argmax
    # has a near-tie to resolve in `dtype`
    logits = (jax.random.normal(jax.random.PRNGKey(1), (_SLOTS, V)) * 4)
    logits = logits.at[:, 7].set(logits.max(-1) + 1.0)
    logits = logits.at[:, 600].set(logits[:, 7] * (1 + 2e-3))
    logits = logits.astype(dtype).astype(jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), _SLOTS)
    temps, topks = _BATCHES[batch]
    got = jax.jit(lambda *a: sample(*a, dtype))(
        logits, keys, jnp.asarray(temps, jnp.float32),
        jnp.asarray(topks, jnp.int32))
    assert got.dtype == jnp.int32 and got.shape == (_SLOTS,)
    want = [int(gpt.sample_logits(logits[i:i + 1].astype(dtype), keys[i],
                                  float(temps[i]), topks[i] or None)[0])
            for i in range(_SLOTS)]
    assert got.tolist() == want
    greedy = jnp.argmax(logits.astype(dtype), axis=-1).tolist()
    assert all(g == w for g, w, t in zip(greedy, want, temps) if t == 0)


def test_a_greedy_batch_runs_the_argmax_and_nothing_else():
    """One `cond` on `any(temps > 0)`; the branch a greedy batch takes
    reads the logits alone — no key, no `topks`, no noise, no loop —
    and neither branch sorts."""
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda *a: sample(*a, jnp.bfloat16))(
        s((4, 512), jnp.float32), s((4, 2), jnp.uint32),
        s((4,), jnp.float32), s((4,), jnp.int32))
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert "sort" not in str(jaxpr)
    greedy, drawn = conds[0].params["branches"]     # index 0: predicate False
    names = lambda j: {e.primitive.name for e in j.eqns}
    assert "argmax" in names(greedy)
    assert not names(greedy) & {"while", "scan", "random_bits",
                                "threefry2x32", "random_wrap", "div", "sort"}
    used = {v for e in greedy.eqns for v in e.invars
            if isinstance(v, jax.extend.core.Var)}
    read = [v.aval.shape for v in greedy.jaxpr.invars if v in used]
    assert read == [(4, 512)]
    assert names(drawn) & {"while", "scan"}      # the selection's turns
