"""A model that MIXES a state kind with a paged kind (models/ling3.py: KDA
layers' states and conv tails in one entry a sequence, the latent-attention
layer's rows in pages) through the serving engine: an admission takes a
slot, an entry AND pages and waits while any is missing, an eviction
returns all three, the counters tell entries and their bytes from pages and
theirs, and an entry's next holder starts from zero.

Held against the plain reference (benchmarks/reference/ling3_plain.py) on
LOGITS, float32 on both sides; tolerance as tests/test_ling3.py's.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.reference import ling3_plain as ref
from ray_tpu.models import ling3 as lm
from ray_tpu.serve._engine import ContinuousEngine

from test_ling3 import SEED, TOL, WEIGHTS, _make, _sizes
from test_serve_state_kind import _by_hand, _run

PS, CHUNK, BUCKET = 8, 16, 8


@pytest.fixture(scope="module")
def model():
    return _make()


def _engine(model, **kw):
    cfg, params = model
    defaults = dict(max_slots=3, page_size=PS, max_total=64,
                    prefill_bucket=BUCKET, prefill_chunk=CHUNK)
    defaults.update(kw)
    return ContinuousEngine(lm, cfg, params, **defaults)


def _toks(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _idle(eng):
    for a in eng._allocs.values():
        assert a.free_pages == a.num_pages - 1 and a.reserved == 0
    st = eng.engine_stats()
    assert st["states_live"] == 0 and st["state_bytes"] == 0
    assert st["cache_bytes"] == 0 and eng._prefilling is None


def test_the_engine_reads_both_kinds_off_the_model(model):
    eng = _engine(model)
    try:
        assert eng._kinds == {"full": None, "kda": "state"}
        assert eng._state_kinds == ["kda"] and eng._main == "full"
        assert eng._widths == {"full": 8, "kda": 1}
        assert eng._pool_pages == {"full": 1 + 3 * 8, "kda": 1 + 3}
        # the state after a shared prefix is in no page: nothing is shared,
        # though the latent kind alone would allow it
        assert not eng._share and not eng._windowed
        st = eng.engine_stats()
        assert (st["states_live"], st["states_free"]) == (0, 3)
        assert st["free_pages"] == 24
    finally:
        eng.stop()


def test_served_rows_are_the_reference_and_the_counters_tell_the_pools_apart(
        model):
    """Two sequences side by side (a 37-token prompt in three chunks, a
    6-token one in one program), each decoded: every logits row is the
    reference's; the ring and `engine_stats` count entries, their bytes
    and the pages' apart."""
    cfg, _ = model
    eng = _by_hand(_engine(model))
    plens, new = (37, 6), 10
    seqs = [eng.submit(_toks(p, p), new) for p in plens]
    rows = [{}, {}]
    _run(eng, seqs, rows)
    assert seqs[0].chunks == 3 and seqs[1].chunks == 1
    drawn = ref.draw(SEED, _sizes(cfg), WEIGHTS)
    for s, p, got in zip(seqs, plens, rows):
        out = s.result.result()["completion"]
        want = np.asarray(ref.logits(
            drawn, jnp.asarray(list(s.tokens) + out), _sizes(cfg)))
        assert int(np.argmax(want[p - 1])) == out[0]
        for j, row in got.items():          # row j predicts generated[j]
            assert np.abs(row - want[p - 1 + j]).max() < TOL, (p, j)
    H, d, L = cfg.n_heads, cfg.d_head, len(cfg.kda_layers)
    entry = L * (H * d * d * 4 + 3 * 3 * H * d * 4)
    page = (cfg.kv_rank + cfg.d_rope) * PS * 4 * len(cfg.mla_layers)
    st = eng.engine_stats()
    assert st["state_arena_bytes"] == 4 * entry
    assert (eng._entry_bytes, eng._page_bytes) == (entry, {"full": page})
    ring = eng.phase_ring()
    assert {r["states_live"] for r in ring} <= {0, 1, 2}
    both = [r for r in ring if r["states_live"] == 2]
    assert both and all(r["state_bytes"] == 2 * entry for r in both)
    # 37 + 10 positions hold 6 pages, 6 + 10 hold 2
    assert max(r["pages_full"] for r in both) == 8
    assert all(r["cache_bytes"] == r["state_bytes"] + r["pages_full"] * page
               for r in ring)
    steps = [r for r in ring if r["active"]]
    assert all(r["kda_live"] == 3.0 for r in steps)       # the XLA body
    assert sum(r["chunk_kda_live"] for r in ring) == 4.0
    census = eng._census_report()
    assert census["state_arena_bytes"] == 4 * entry
    assert set(census["pools"]) == {"full", "kda"}
    _idle(eng)
    eng.stop()


@pytest.mark.parametrize("pools,why", [
    ({"full": 1 + 3 * 8, "kda": 1 + 2}, "entries"),
    ({"full": 1 + 2 * 3, "kda": 1 + 3}, "pages")])
def test_an_admission_waits_for_whichever_pool_is_empty(model, pools, why):
    """Three requests, three slots, but only two entries — or pages for
    two: the third waits though a slot is free, is admitted when an
    eviction returns slot, entry and pages together, and everything
    drains with every pool whole."""
    eng = _by_hand(_engine(model, num_pages=pools))
    seqs = [eng.submit(_toks(12, i), 8 + 2 * i) for i in range(3)]
    for _ in range(4):
        eng._iteration()
    held = [s for s in seqs if s.states]
    assert held == seqs[:2] and not seqs[2].pages, why
    assert not any(s.result.done() for s in seqs)
    assert all(s.states["kda"] > 0 and len(s.pages) == 3 for s in held)
    assert eng.engine_stats()["states_live"] == 2
    assert eng._allocs["full"].used_pages == 6
    first = seqs[0]
    while not first.result.done():
        eng._iteration()
    assert first.states == {} and first.pages == []
    _run(eng, seqs)
    assert [len(s.result.result()["completion"]) for s in seqs] == [8, 10, 12]
    _idle(eng)
    eng.stop()


def test_a_second_holder_of_an_entry_starts_from_zero(model):
    """Two requests one after the other through ONE slot, one entry and
    the same pages: each gets the logits it gets alone in a fresh
    engine."""
    a, b = _toks(21, 1), _toks(9, 2)

    def alone(prompt):
        eng = _by_hand(_engine(model, max_slots=1))
        s = eng.submit(prompt, 6)
        rows = [{}]
        _run(eng, [s], rows)
        eng.stop()
        return s.result.result()["completion"], rows[0]

    eng = _by_hand(_engine(model, max_slots=1))
    assert eng._pool_pages == {"full": 9, "kda": 2}
    got = []
    for prompt in (a, b):
        s = eng.submit(prompt, 6)
        rows = [{}]
        _run(eng, [s], rows)
        assert s.states == {} and eng._allocs["kda"].free_pages == 1
        got.append((s.result.result()["completion"], rows[0]))
    eng.stop()
    for (out, rows), prompt in zip(got, (a, b)):
        want_out, want_rows = alone(prompt)
        assert out == want_out
        for j in rows:
            assert np.abs(rows[j] - want_rows[j]).max() < 1e-6


def test_stop_returns_entry_and_pages_of_a_sequence_in_flight(model):
    eng = _by_hand(_engine(model))
    s = eng.submit(_toks(20, 3), 30)
    eng._iteration()
    assert s.states == {"kda": 1} and len(s.pages) == 7 and s.prefilling
    eng.stop()
    assert s.states == {} and s.pages == []
    assert eng._allocs["kda"].free_pages == 3
    assert eng._allocs["full"].free_pages == 24


def test_the_benchmark_cut_ends_streams_by_the_engine_own_way_out(model):
    """`Ling3Server.bench_cut` (the reasoning cell's window closes on
    streams that have thousands of tokens to go): a queued sequence ends
    with nothing, a streaming one with its next token, one in mid-prefill
    after its last chunk and one token — and slot, entry and pages of all
    come back."""
    import types

    from benchmarks.drivers.replica_ling3 import Ling3Server

    eng = _by_hand(_engine(model, max_slots=2))
    streaming = eng.submit(_toks(6, 1), 40)
    chunked = eng.submit(_toks(40, 2), 20)          # three chunks
    queued = eng.submit(_toks(6, 3), 40)
    for _ in range(2):
        eng._iteration()
    assert len(streaming.generated) >= 1 and chunked.prefilling
    assert chunked.chunks < 3
    assert not queued.pages and not queued.result.done()
    had = len(streaming.generated)
    replica = types.SimpleNamespace(_engine=eng)
    assert Ling3Server.bench_cut(replica) == 3
    assert queued.result.result()["completion"] == []
    _run(eng, [streaming, chunked], limit=8)
    assert len(streaming.result.result()["completion"]) == had + 1
    assert len(chunked.result.result()["completion"]) == 1
    assert chunked.chunks == 3
    assert Ling3Server.bench_cut(replica) == 0
    _idle(eng)
    eng.stop()
