"""A model whose layers keep a STATE of fixed size a sequence and no page
(models/brumby.py) through the serving engine: `cache_kinds` says
`"state"`, the engine keeps a pool of entries, admits by free entries and
slots, and bounds positions by `max_total` alone.

Held against the plain reference (benchmarks/reference/brumby_plain.py) on
LOGITS, float32 on both sides: 2e-6 apart.  The tolerance is 1e-4.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import brumby as bm
from ray_tpu.models import cohere2_moe as cm
from ray_tpu.models import gpt
from ray_tpu.serve._engine import AdmissionRejected, ContinuousEngine

from test_brumby import ref_logits, tokens, with_memory

TOL = 1e-4
CHUNK, BUCKET = 8, 4


@pytest.fixture(scope="module")
def model():
    cfg = bm.BrumbyConfig.nano(dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, with_memory(bm.init(jax.random.PRNGKey(0), cfg))


def _engine(model, **kw):
    cfg, params = model
    defaults = dict(max_slots=3, max_total=96, prefill_bucket=BUCKET,
                    prefill_chunk=CHUNK)
    defaults.update(kw)
    return ContinuousEngine(bm, cfg, params, **defaults)


def _by_hand(eng):
    """Drive the engine's iterations from the test: a thread that has
    already ended stands where the loop's would be started."""
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    eng._thread = t
    return eng


def _run(eng, seqs, rows=None, limit=400):
    for _ in range(limit):
        eng._iteration()
        for i, s in enumerate(seqs):
            # after an iteration a decoding slot's row is the one the NEXT
            # step launched will draw from: as many steps in as were
            # launched for it (their tokens come an iteration later)
            if (rows is not None and s.t_ready
                    and eng._slots[s.slot] is s):
                rows[i][s.launched] = np.asarray(
                    eng._logits)[s.slot].copy()
        if all(s.result.done() for s in seqs):
            return
    raise AssertionError("the engine did not drain")


def _idle(eng):
    a = eng._allocs["ret"]
    assert a.free_pages == a.num_pages - 1 and a.reserved == 0
    assert eng._prefilling is None
    assert eng.engine_stats()["states_live"] == 0


def test_the_engine_reads_the_kind_off_the_model(model):
    eng = _engine(model)
    try:
        assert eng._kinds == {"ret": "state"} and eng._state_kinds == ["ret"]
        assert eng._widths == {"ret": 1}
        assert eng._pool_pages == {"ret": 1 + 3}      # a state a slot + null
        assert not eng._share and not eng._windowed
        assert eng.max_total == 96                    # positions, not pages
        st = eng.engine_stats()
        assert (st["states_live"], st["states_free"]) == (0, 3)
        assert st["free_pages"] == 3
    finally:
        eng.stop()


def test_chunked_prefill_then_decode_through_the_state_arena(model):
    """Two sequences side by side, a 29-token prompt in four chunks and a
    6-token one in one program, each decoded 22 tokens: after every
    program the slot's logits row is the reference's for that position."""
    eng = _by_hand(_engine(model))
    plens, new = (29, 6), 22
    seqs = [eng.submit(tokens(p, seed=p).tolist(), new) for p in plens]
    rows = [{}, {}]
    _run(eng, seqs, rows)
    assert seqs[0].chunks == 4 and seqs[1].chunks == 1
    for s, p, got in zip(seqs, plens, rows):
        out = s.result.result()["completion"]
        assert len(out) == new and sorted(got) == list(range(1, new))
        want = ref_logits(model, np.array(list(s.tokens) + out, np.int32))
        assert int(np.argmax(want[p - 1])) == out[0]
        for j, row in got.items():          # row j predicts generated[j]
            assert np.abs(row - want[p - 1 + j]).max() < TOL, (p, j)
    ring = eng.phase_ring()
    steps = [r for r in ring if r["active"]]
    assert all(r["ret_states"] == 3.0 for r in steps)     # every slot's
    assert {r["states_live"] for r in ring} <= {0, 1, 2}
    assert sum(r["chunk_ret_states"] for r in ring) == 5.0
    st = eng.engine_stats()
    R, F = 24, 144                                        # dh 16
    assert st["state_arena_bytes"] == 2 * 4 * 2 * R * F * 4    # L, N, Hkv
    _idle(eng)
    eng.stop()


def test_a_reused_entry_starts_empty(model):
    """Two requests one after the other through ONE slot and one entry:
    each gets the logits it gets alone in a fresh engine."""
    a, b = tokens(13, seed=1).tolist(), tokens(9, seed=2).tolist()

    def alone(prompt):
        eng = _by_hand(_engine(model, max_slots=1))
        s = eng.submit(prompt, 6)
        rows = [{}]
        _run(eng, [s], rows)
        eng.stop()
        return s.result.result()["completion"], rows[0]

    eng = _by_hand(_engine(model, max_slots=1))
    assert eng._pool_pages == {"ret": 2}
    got = []
    for prompt in (a, b):
        s = eng.submit(prompt, 6)
        rows = [{}]
        _run(eng, [s], rows)
        assert s.states == {} and eng._allocs["ret"].free_pages == 1
        got.append((s.result.result()["completion"], rows[0]))
    eng.stop()
    for (out, rows), prompt in zip(got, (a, b)):
        want_out, want_rows = alone(prompt)
        assert out == want_out
        for j in rows:
            assert np.abs(rows[j] - want_rows[j]).max() < 1e-6


def test_admission_counts_states_sheds_and_drains(model):
    """Five requests on two slots: two are admitted (each holds one entry
    while it lives), the others wait for an eviction, the queue sheds at
    its cap, and everything drains with every entry returned."""
    eng = _by_hand(_engine(model, max_slots=2, queue_cap=3))
    submit = lambda i: eng.submit(tokens(5 + i, seed=i).tolist(), 3 + i)
    seqs = [submit(0), submit(1)]
    eng._iteration()
    assert eng.engine_stats()["states_live"] == 2
    seqs += [submit(i) for i in (2, 3, 4)]
    with pytest.raises(AdmissionRejected):
        eng.submit(tokens(4).tolist(), 2)
    eng._iteration()
    assert eng.engine_stats()["states_live"] == 2
    assert sorted(s.states.get("ret", 0) for s in seqs)[-2:] == [1, 2]
    assert eng.engine_stats()["queue_depth"] == 3
    _run(eng, seqs)
    assert [len(s.result.result()["completion"]) for s in seqs] \
        == [3, 4, 5, 6, 7]
    assert eng.check_health()
    _idle(eng)
    # positions are bounded by max_total, nothing else
    with pytest.raises(ValueError, match="exceeds engine capacity"):
        eng.submit(tokens(90).tolist(), 7)
    eng.submit(tokens(90).tolist(), 6)
    eng.stop()


def test_stop_returns_the_states_of_sequences_in_flight(model):
    eng = _by_hand(_engine(model))
    s = eng.submit(tokens(20).tolist(), 30)
    eng._iteration()
    assert s.states == {"ret": 1} and s.prefilling
    eng.stop()
    assert s.states == {} and eng._allocs["ret"].free_pages == 3
    with pytest.raises(RuntimeError):
        s.result.result(timeout=1)


@pytest.mark.parametrize("mod,cfg", [
    (gpt, gpt.GPTConfig.nano()),
    (cm, cm.Cohere2MoEConfig.nano())], ids=["gpt2", "cohere2_moe"])
def test_paged_models_keep_their_engine(mod, cfg):
    """A model without a state kind: no state pool, no state counters, its
    sharing rule and its main pool as they were."""
    eng = ContinuousEngine(mod, cfg, mod.init(jax.random.PRNGKey(0), cfg),
                           max_slots=2, page_size=4, max_total=32)
    try:
        assert eng._state_kinds == [] and eng._main == "full"
        assert eng._share == (mod is gpt)
        st = eng.engine_stats()
        assert not {"states_live", "states_free", "state_arena_bytes"} & set(st)
        seq = eng.submit([1, 2, 3], 2)
        assert len(eng.collect(seq, timeout=120)["completion"]) == 2
        assert all("states_live" not in r for r in eng.phase_ring())
    finally:
        eng.stop()
