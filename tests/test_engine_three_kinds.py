"""A model with THREE kinds of sequence state (models/phi4flash.py: one
full-attention layer's pages, the windowed layers' ring of pages, the
Mamba layers' states and conv tails in one entry) through the serving
engine: an admission takes a slot, an entry, its full pages AND a window
reservation together and waits while any is missing; window pages go back
as the window passes while the full kind's stay; an eviction returns all
of them; the counters tell state bytes, full pages and window pages apart;
an entry's next holder starts from zero; and the engine tells this model —
and no other — which prefill chunk is its prompt's last.

Held against the plain reference (benchmarks/reference/phi4flash_plain.py)
on LOGITS, float32 on both sides; tolerance as tests/test_phi4flash.py's.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.reference import phi4flash_plain as ref
from ray_tpu.models import phi4flash as pm
from ray_tpu.serve._engine import ContinuousEngine

from test_phi4flash import SEED, TOL, _sizes, model, small_pieces  # noqa: F401
from test_serve_model_interface import MODELS
from test_serve_state_kind import _by_hand, _run

PS, CHUNK, BUCKET = 8, 16, 8


def _engine(model, **kw):
    cfg, params = model
    defaults = dict(max_slots=3, page_size=PS, max_total=64,
                    prefill_bucket=BUCKET, prefill_chunk=CHUNK)
    defaults.update(kw)
    return ContinuousEngine(pm, cfg, params, **defaults)


def _toks(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _idle(eng):
    for a in eng._allocs.values():
        assert a.free_pages == a.num_pages - 1 and a.reserved == 0
    st = eng.engine_stats()
    assert st["states_live"] == 0 and st["state_bytes"] == 0
    assert st["cache_bytes"] == 0 and eng._prefilling is None


def test_the_engine_reads_three_kinds_off_the_model(model):
    eng = _engine(model)
    try:
        assert eng._kinds == {"full": None, "swa": 8, "mamba": "state"}
        assert eng._state_kinds == ["mamba"] and eng._main == "full"
        assert eng._windowed == ["swa"] and not eng._share
        # the ring: (window 8 + chunk 16) / 8 + 1
        assert eng._widths == {"full": 8, "swa": 4, "mamba": 1}
        assert eng._pool_pages == {"full": 25, "swa": 13, "mamba": 4}
        assert eng._tell_last
    finally:
        eng.stop()


def test_served_rows_are_the_reference_and_the_counters_tell_the_pools_apart(
        model):
    """Two sequences side by side (a 37-token prompt in three chunks, a
    6-token one in one program), each decoded: every logits row is the
    reference's; the ring and `engine_stats` count entries and their
    bytes, full pages and window pages apart; window pages are returned
    while the sequences run and the full kind's are not."""
    cfg, _ = model
    eng = _by_hand(_engine(model))
    plens, new = (37, 6), 10
    seqs = [eng.submit(_toks(p, p), new) for p in plens]
    rows = [{}, {}]
    _run(eng, seqs, rows)
    assert seqs[0].chunks == 3 and seqs[1].chunks == 1
    drawn = ref.draw(SEED, _sizes(cfg))
    for s, p, got in zip(seqs, plens, rows):
        out = s.result.result()["completion"]
        want = np.asarray(ref.logits(
            drawn, jnp.asarray(list(s.tokens) + out), _sizes(cfg)))
        assert int(np.argmax(want[p - 1])) == out[0]
        for j, row in got.items():          # row j predicts generated[j]
            assert np.abs(row - want[p - 1 + j]).max() < TOL, (p, j)
    C, N, L = cfg.d_inner, cfg.d_state, len(cfg.layers_of("mamba"))
    entry = L * (N * C * 4 + 3 * C * 4)
    side = PS * cfg.n_kv_heads * cfg.d_head * 4
    full_page, swa_page = 2 * side, 2 * side * len(cfg.layers_of("swa"))
    st = eng.engine_stats()
    assert st["state_arena_bytes"] == 4 * entry
    assert (eng._entry_bytes, eng._page_bytes) == (
        entry, {"full": full_page, "swa": swa_page})
    ring = eng.phase_ring()
    both = [r for r in ring if r["states_live"] == 2]
    assert both and all(r["state_bytes"] == 2 * entry for r in both)
    # 37 + 10 positions hold 6 full pages, 6 + 10 hold 2: taken whole at
    # admission and kept; a window of 8 never holds more than 4 pages
    assert max(r["pages_full"] for r in both) == 8
    assert max(r["pages_swa"] for r in ring) <= 2 * 4
    assert all(r["cache_bytes"] == r["state_bytes"]
               + r["pages_full"] * full_page + r["pages_swa"] * swa_page
               for r in ring)
    assert st["window_pages_returned"] == sum(
        r["pages_returned"] for r in ring) > 0
    steps = [r for r in ring if r["active"]]
    assert all(r["mamba_live"] == 3.0 for r in steps)     # the XLA body
    assert all(r["cross_rows"] == r["active"] for r in steps)
    # four chunks, two of them their prompts' last
    assert sum(r["chunk_mamba_live"] for r in ring) == 4.0
    assert sum(r["chunk_cross_rows"] for r in ring) == 2.0
    assert st["chunk_cross_rows"] == 2.0 and st["chunks"] == 4
    held = [r["shared_kv_positions"] for r in steps]
    assert held == sorted(held) or len(set(r["active"] for r in steps)) > 1
    census = eng._census_report()
    assert census["state_arena_bytes"] == 4 * entry
    assert set(census["pools"]) == {"full", "swa", "mamba"}
    _idle(eng)
    eng.stop()


@pytest.mark.parametrize("pools,why", [
    ({"full": 25, "swa": 13, "mamba": 1 + 2}, "entries"),
    ({"full": 1 + 2 * 3, "swa": 13, "mamba": 4}, "full pages"),
    ({"full": 25, "swa": 1 + 2 * 3, "mamba": 4}, "the window reservation")])
def test_an_admission_waits_for_whichever_pool_is_empty(model, pools, why):
    """Three requests, three slots, but only two entries — or full pages
    for two, or window pages for two reservations: the third waits though
    a slot is free, is admitted when an eviction returns slot, entry,
    pages and reservation together, and everything drains with every pool
    whole."""
    eng = _by_hand(_engine(model, num_pages=pools))
    seqs = [eng.submit(_toks(12, i), 8 + 2 * i) for i in range(3)]
    for _ in range(4):
        eng._iteration()
    held = [s for s in seqs if s.states]
    assert held == seqs[:2] and not seqs[2].pages, why
    assert not any(s.result.done() for s in seqs)
    for s in held:
        assert s.states["mamba"] > 0 and len(s.pages) == 3
        assert len(s.win["swa"]) + s.reserved["swa"] == 3
    assert eng.engine_stats()["states_live"] == 2
    assert eng._allocs["full"].used_pages == 6
    a = eng._allocs["swa"]
    assert a.used_pages + a.reserved == 6
    first = seqs[0]
    while not first.result.done():
        eng._iteration()
    assert first.states == {} and first.pages == [] and not first.win["swa"]
    _run(eng, seqs)
    assert [len(s.result.result()["completion"]) for s in seqs] == [8, 10, 12]
    _idle(eng)
    eng.stop()


def test_window_pages_go_back_as_the_window_passes_and_full_pages_stay(model):
    eng = _by_hand(_engine(model, max_slots=1))
    s = eng.submit(_toks(40, 4), 20)
    seen = []
    while not s.result.done():
        eng._iteration()
        if s.pages:
            seen.append((len(s.pages), len(s.win["swa"])))
    assert {n for n, _ in seen} == {8}              # 60 positions, whole
    assert max(w for _, w in seen) <= 4 and min(w for _, w in seen) >= 1
    assert eng.engine_stats()["window_pages_returned"] >= 5
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
    assert eng._pool_pages == {"full": 9, "swa": 5, "mamba": 2}
    got = []
    for prompt in (a, b):
        s = eng.submit(prompt, 6)
        rows = [{}]
        _run(eng, [s], rows)
        assert s.states == {} and eng._allocs["mamba"].free_pages == 1
        got.append((s.result.result()["completion"], rows[0]))
    eng.stop()
    for (out, rows), prompt in zip(got, (a, b)):
        want_out, want_rows = alone(prompt)
        assert out == want_out
        for j in rows:
            assert np.abs(rows[j] - want_rows[j]).max() < 1e-6


def test_stop_returns_everything_of_a_sequence_in_flight(model):
    eng = _by_hand(_engine(model))
    s = eng.submit(_toks(20, 3), 30)
    eng._iteration()
    assert s.states == {"mamba": 1} and len(s.pages) == 7 and s.prefilling
    assert s.win["swa"] and s.reserved["swa"] >= 1
    eng.stop()
    assert s.states == {} and s.pages == [] and not s.win["swa"]
    _idle(eng)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_only_a_model_that_asks_is_told_which_chunk_is_the_last(name):
    """The prefill program's operands as the engine hands them over: the
    five models without `PREFILL_KNOWS_LAST` get (params, cache, chunk,
    tables, start, last_idx) as before the interface's addition, this one
    a seventh — False on every chunk but its prompt's last."""
    import jax

    mod, cfg = MODELS[name]
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              param_dtype=jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), cfg)
    eng = _by_hand(ContinuousEngine(
        mod, cfg, params, max_slots=2, page_size=PS, max_total=64,
        prefill_bucket=BUCKET, prefill_chunk=CHUNK))
    calls = []
    real = eng._fn(("prefill", CHUNK))
    eng._fns[("prefill", CHUNK)] = lambda *a: (calls.append(a[2:])
                                               or real(*a))
    s = eng.submit(_toks(30, 1), 2)
    _run(eng, [s])
    eng.stop()
    assert len(calls) == 2
    asks = name == "phi-4-flash"
    assert asks == bool(getattr(mod, "PREFILL_KNOWS_LAST", False))
    for i, ops in enumerate(calls):
        assert len(ops) == (5 if asks else 4), name
        chunk, tabs, start, last_idx = ops[:4]
        assert (int(start), int(last_idx)) == ((0, 15), (16, 13))[i]
        assert set(tabs) == set(eng._kinds)
        if asks:
            assert bool(ops[4]) == (i == 1) and ops[4].dtype == np.bool_
