"""One step stays in flight (serve/_engine.py, "The order of an
iteration"): the engine launches step n+1 before it fetches step n's
tokens.  What that must not change — the tokens, who holds what, how a
sequence ends, what an idle engine looks like, how a failure is told — and
what the ring says of it, over the three ways a model keeps its state:
pages of every position (models/gpt.py), a windowed ring beside them
(models/cohere2_moe.py) and one entry of a state arena
(models/brumby.py), each at the tiny size its own engine tests use,
float32, on the CPU.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import brumby as bm
from ray_tpu.models import cohere2_moe as cm
from ray_tpu.models import gpt
from ray_tpu.serve._engine import ContinuousEngine

from test_brumby import with_memory
from test_serve_state_kind import _by_hand

KINDS = ["paged", "windowed", "state"]
ENGINE_KW = {
    "paged": dict(page_size=8, max_total=64, prefill_bucket=8),
    "windowed": dict(page_size=4, max_total=64, prefill_bucket=4),
    "state": dict(max_total=96, prefill_bucket=4)}
# the keys a ring record had before a step stayed in flight: the
# benchmark's readers and the operator's shares read them
OLD_KEYS = ("iter_s", "decode_s", "swap_s", "prefill_s", "dispatch_s",
            "ready_wait_s", "step_dispatch_s", "step_wait_s",
            "device_wait_s", "host_s", "active", "admitted",
            "blocked_slots", "chunks", "chunk_tokens", "requests",
            "launches", "waits", "iter", "gc_s", "ts", "t0",
            "pages_returned", "sampled_steps")


@pytest.fixture(scope="module")
def models():
    f32 = dict(dtype=jnp.float32)
    gcfg = gpt.GPTConfig.nano(max_seq=64, **f32)
    ccfg = cm.Cohere2MoEConfig.nano(param_dtype=jnp.float32, **f32)
    bcfg = bm.BrumbyConfig.nano(param_dtype=jnp.float32, **f32)
    key = jax.random.PRNGKey(0)
    return {"paged": (gpt, gcfg, gpt.init(key, gcfg)),
            "windowed": (cm, ccfg, cm.init(key, ccfg)),
            "state": (bm, bcfg, with_memory(bm.init(key, bcfg)))}


_PROGRAMS = {}      # kind -> an engine's `_fns`: a model's programs close
# over its module and config alone, so every engine of a kind runs the
# first one's (a compile a shape, not a compile a test)


def _engine(models, kind, by_hand=True, **kw):
    mod, cfg, params = models[kind]
    eng = ContinuousEngine(mod, cfg, params, **{
        "max_slots": 3, "prefill_chunk": 8, **ENGINE_KW[kind], **kw})
    eng._fns = _PROGRAMS.setdefault(kind, eng._fns)
    return _by_hand(eng) if by_hand else eng    # the test's iterations


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 250, n).tolist()


def _expected(models, kind, prompt, got, temperature=0.0, seed=0,
              top_k=None):
    """What the model alone answers: gpt's `generate`, greedy or sampled;
    another module's `apply`, teacher-forced over the greedy tokens the
    engine gave (each the argmax of the position before it)."""
    mod, cfg, params = models[kind]
    if mod is gpt:
        out = gpt.generate(params, cfg, jnp.asarray([prompt]), len(got),
                           temperature=temperature, top_k=top_k,
                           rng=jax.random.PRNGKey(seed), max_seq=64)
        return np.asarray(out)[0, len(prompt):].tolist()
    logits = np.asarray(mod.apply(
        params, jnp.asarray(prompt + got)[None], cfg))[0]
    return np.argmax(logits[len(prompt) - 1:-1], axis=-1).tolist()


def _drive(eng, until, limit=300):
    for _ in range(limit):
        if until():
            return
        eng._iteration()
    raise AssertionError("the engine did not get there")


def _drain(eng, seqs=()):
    _drive(eng, lambda: not eng._busy())
    assert all(s.result.done() for s in seqs)


def _holds_nothing(eng):
    """No step unfetched, every page, reservation and entry back."""
    assert not eng._in_flight and not eng._pending
    assert eng._slots == [None] * eng.max_slots and eng._prefilling is None
    for k, a in eng._allocs.items():
        occ = a.occupancy()
        assert (occ["used"], occ["free"], a.reserved) == (
            0, a.num_pages - 1, 0), k
    assert not eng._pos.any() and not eng._temps.any()
    assert not any(tab.any() for tab in eng._ptabs.values())
    if eng._state_kinds:
        assert eng.engine_stats()["states_live"] == 0


# -- the tokens ----------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_tokens_are_the_models_with_admissions_landing_mid_stream(models,
                                                                  kind):
    """Greedy and sampled requests side by side, the second and the third
    admitted while the first streams (the third's prompt in chunks), the
    fourth once a slot is free: each one's tokens are what the model alone
    answers, and every step but the first went out ahead of a fetch."""
    eng = _engine(models, kind)
    sampled = dict(temperature=0.8, seed=31, top_k=12) \
        if kind == "paged" else {}      # `generate` is gpt's
    asks = [(_prompt(6, 1), 18, {}), (_prompt(5, 2), 4, sampled),
            (_prompt(19, 3), 9, {}),
            (_prompt(7, 4), 6, dict(sampled, seed=7) if sampled else {})]
    try:
        seqs = [eng.submit(asks[0][0], asks[0][1], **asks[0][2])]
        _drive(eng, lambda: len(seqs[0].generated) >= 3)
        seqs.append(eng.submit(asks[1][0], asks[1][1], **asks[1][2]))
        _drive(eng, lambda: len(seqs[1].generated) >= 2)
        seqs += [eng.submit(p, n, **kw) for p, n, kw in asks[2:]]
        _drain(eng, seqs)
        _holds_nothing(eng)
        st = eng.engine_stats()
    finally:
        eng.stop()
    for s, (p, n, kw) in zip(seqs, asks):
        got = s.result.result()["completion"]
        assert len(got) == n and s.launched == n
        assert got == _expected(models, kind, p, got, **kw), (p, kw)
    assert seqs[2].chunks == 3
    assert st["tokens"] == sum(n for _, n, _ in asks)
    # only the idle engine's first step had nothing to fetch
    assert st["steps_ahead"] == st["steps"] - 1


# -- how a sequence ends -------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_eos_ends_on_the_eos_though_one_step_ran_late(models, kind):
    """A request with `eos_id` ends ON its EOS: the step launched before
    the EOS was seen computed one more token for it, which nobody gets;
    slot, pages, window pages and entry are all back, and the sequence
    decoding beside it is not disturbed."""
    eng = _engine(models, kind)
    prompt, other = _prompt(11, 5), _prompt(6, 6)
    try:
        ref = eng.submit(prompt, 12)
        _drain(eng, [ref])
        ref = ref.result.result()["completion"]
        eos = ref[5]
        cut = ref.index(eos) + 1
        assert cut < 12
        beside = eng.submit(other, 14)
        seq = eng.submit(prompt, 12, eos_id=eos)
        _drive(eng, seq.result.done)
        # found one step late: a step more was launched than tokens came
        assert seq.launched == cut + 1 and len(seq.generated) == cut
        assert eng._slots[seq.slot] is None and not seq.pages
        assert not any(seq.win.values()) and not seq.states
        late = len(beside.generated)
        eng._iteration()            # the late step's tokens: its is dropped
        assert len(seq.generated) == cut and len(beside.generated) == late + 1
        _drain(eng, [beside])
        _holds_nothing(eng)
    finally:
        eng.stop()
    out = seq.result.result()
    assert out["completion"] == ref[:cut] and out["completion"][-1] == eos
    assert list(seq.out_q.queue) == ref[:cut] + [eng._END]
    got = beside.result.result()["completion"]
    assert got == _expected(models, kind, other, got)


@pytest.mark.parametrize("kind", KINDS)
def test_ending_by_count_frees_the_slot_for_the_very_next_admission(models,
                                                                    kind):
    """One slot, two requests: the iteration that launches the first's
    last step by count gives the slot, the pages and the entry back, so
    the NEXT iteration admits the second — the one that emits the first's
    last token, which its caller hears of only then."""
    eng = _engine(models, kind, max_slots=1)
    try:
        a, b = eng.submit(_prompt(6, 7), 3), eng.submit(_prompt(5, 8), 3)
        _drive(eng, lambda: a.launched == 3)
        assert eng._slots == [None] and not a.pages and not a.states
        assert len(a.generated) == 2 and not a.result.done()
        assert b.slot == -1
        eng._iteration()
        assert a.result.done() and eng._slots == [b]
        rec = eng.phase_ring()[-1]
        assert rec["admitted"] == 1 and rec["active"] == 1
        # b's first step went out behind a's last, ahead of its fetch
        assert (rec["stepped"], rec["ahead"]) == (1, 1) and b.launched == 1
        _drain(eng, [a, b])
        _holds_nothing(eng)
    finally:
        eng.stop()
    for s, p in ((a, _prompt(6, 7)), (b, _prompt(5, 8))):
        got = s.result.result()["completion"]
        assert len(got) == 3 and got == _expected(models, kind, p, got)


@pytest.mark.parametrize("kind", KINDS)
def test_max_new_cut_from_outside_ends_at_the_next_emit(models, kind):
    """`max_new` set to 0 while a step is in flight (a driver closing its
    window, `bench_cut`): a streaming sequence ends with the token in
    flight, one in mid-prefill with its first token, both by the engine's
    own way out, and nothing stays held."""
    eng = _engine(models, kind)
    p_stream, p_chunked = _prompt(6, 9), _prompt(21, 10)
    try:
        streaming = eng.submit(p_stream, 30)
        _drive(eng, lambda: len(streaming.generated) >= 4)
        chunked = eng.submit(p_chunked, 30)
        _drive(eng, lambda: chunked.chunks == 1)
        assert chunked.prefilling and streaming.launched == len(
            streaming.generated) + 1
        had = len(streaming.generated)
        with eng._lock:
            for s in (streaming, chunked):
                s.max_new = 0
        eng._iteration()
        assert streaming.result.done()
        assert len(streaming.generated) == had + 1 == streaming.launched
        _drive(eng, chunked.result.done)
        assert chunked.chunks == 3 and chunked.launched == 1
        _drain(eng)
        _holds_nothing(eng)
    finally:
        eng.stop()
    got = streaming.result.result()["completion"]
    assert got == _expected(models, kind, p_stream, got)
    got = chunked.result.result()["completion"]
    assert len(got) == 1 and got == _expected(models, kind, p_chunked, got)


# -- an idle engine ------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_an_idle_engine_holds_no_step_and_runs_its_programs_by_hand(models,
                                                                    kind):
    """The loop's own thread: once the last request has answered, nothing
    is launched and unfetched, `drain` and the health check say so, the
    step program can be driven by hand on the engine's `_cache` /
    `_logits` under their own names (the reference checks do) — and the
    next request is served from what that left."""
    eng = _engine(models, kind, by_hand=False)
    prompt = _prompt(9, 11)
    try:
        first = eng.collect(eng.submit(prompt, 5), timeout=120)["completion"]
        assert eng.drain(timeout_s=30) and not eng._busy()
        assert not eng._in_flight and eng.check_health()
        with eng._lock:
            eng._draining = False
        toks, eng._logits, eng._cache, _ = eng._fn("step")(
            eng._params, eng._cache, eng._logits, eng._toks_keys,
            eng._temps, eng._topks, eng._ptabs, eng._pos)
        assert np.asarray(toks).shape == (eng.max_slots,)
        again = eng.collect(eng.submit(prompt, 5), timeout=120)["completion"]
        assert eng.drain(timeout_s=30)
        _holds_nothing(eng)
    finally:
        eng.stop()
    assert first == again == _expected(models, kind, prompt, first)


@pytest.mark.parametrize("kind", KINDS)
def test_a_steps_host_operands_are_its_own(models, kind):
    """The engine's positions, tables, keys, temperatures and top-ks change
    right behind a launch (they are counted by steps launched), while the
    program may still read what it was handed: every one is a copy, and
    holds after the iteration what it held at the call."""
    eng = _engine(models, kind)
    real, calls = eng._fn("step"), []

    def watched(params, cache, logits, *operands):
        flat = jax.tree.leaves(operands)
        calls.append((flat, [np.array(a) for a in flat]))
        return real(params, cache, logits, *operands)

    try:
        eng._fns = dict(eng._fns, step=watched)
        seq = eng.submit(_prompt(13, 12), 8, temperature=0.7, seed=3)
        _drain(eng, [seq])
    finally:
        eng.stop()
    own = jax.tree.leaves((eng._toks_keys, eng._temps, eng._topks,
                           eng._ptabs, eng._pos))
    assert len(calls) == 8
    for n, (handed, at_call) in enumerate(calls):
        for a, snap, mine in zip(handed, at_call, own):
            assert isinstance(a, np.ndarray) and a.shape == mine.shape
            assert not np.shares_memory(a, mine)
            assert np.array_equal(a, snap)
        # the position a step was launched with is the count of the
        # steps before it, whatever the host has fetched by then
        assert int(handed[-1][seq.slot]) == 13 + n
    poses = [int(h[-1][seq.slot]) for h, _ in calls]
    assert poses == list(range(13, 21))


# -- a failure -----------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_a_program_that_raises_fails_every_request_in_flight_once(models,
                                                                  kind):
    """The third step consumes its state and raises, with the second
    unfetched: the sequence whose last token by count was in that second
    step — out of its slot already — fails like the one still decoding
    and the one still queued, each hears of it once, nothing stays held,
    the state is made anew and the next request is served."""
    eng = _engine(models, kind, max_slots=2)
    real, calls = eng._fn("step"), []

    def third_fails(*args):
        calls.append(1)
        out = real(*args)           # the state given is gone after this
        if len(calls) == 3:
            raise RuntimeError("device fault")
        return out

    prompt = _prompt(6, 13)
    try:
        eng._fns = dict(eng._fns, step=third_fails)
        leaving = eng.submit(prompt, 2)
        staying = eng.submit(_prompt(7, 14), 9)
        queued = eng.submit(_prompt(5, 15), 4)
        eng._thread = threading.Thread(target=eng._loop, daemon=True)
        eng._thread.start()
        for s in (leaving, staying, queued):
            with pytest.raises(RuntimeError, match="device fault"):
                eng.collect(s, timeout=120)
        assert len(leaving.generated) == 1 and leaving.launched == 2
        for s in (leaving, staying, queued):
            assert list(s.out_q.queue).count(eng._END) == 1
        got = eng.collect(eng.submit(prompt, 6), timeout=120)["completion"]
        assert eng.drain(timeout_s=30)
        _holds_nothing(eng)
        assert eng.check_health()
    finally:
        eng.stop()
    assert got == _expected(models, kind, prompt, got)


# -- the ring ------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_ring_describes_the_step_it_emitted_and_the_one_it_launched(models,
                                                                    kind):
    """Every key the ring had is there and holds a number; `stepped` /
    `ahead` count the steps launched and those launched over an unfetched
    one, and sum to the totals; the records tile the thread's time; a
    record's `active` and `requests` belong to the step whose tokens it
    emitted."""
    eng = _engine(models, kind)
    try:
        a = eng.submit(_prompt(6, 16), 10)
        _drive(eng, lambda: len(a.generated) >= 3)
        b = eng.submit(_prompt(18, 17), 5)      # three chunks
        _drain(eng, [a, b])
        ring, st = eng.phase_ring(), eng.engine_stats()
        time.sleep(0.01)
        c = eng.submit(_prompt(5, 18), 2)       # after an idle stretch
        _drain(eng, [c])
        ring2 = eng.phase_ring()[len(ring):]
    finally:
        eng.stop()
    for r in ring + ring2:
        for k in OLD_KEYS + tuple("pages_" + p for p in eng._allocs):
            assert r[k] is not None, k
        assert r["ahead"] <= r["stepped"] <= 1
        assert r["host_s"] + r["device_wait_s"] == pytest.approx(
            r["iter_s"], abs=1e-9)
        assert 0 <= r["swap_s"] + r["decode_s"] <= r["iter_s"]
        assert r["t0"] < r["ts"] <= r["t0"] + r["iter_s"]
        assert (r["decode_s"] > 0) == (r["active"] > 0) == (
            r["step_wait_s"] > 0)
        assert (r["step_dispatch_s"] > 0) == bool(r["stepped"])
    # a step goes out ahead wherever the iteration before launched one
    for stretch in (ring, ring2):
        assert [r["ahead"] for r in stretch] == [0] + [
            r["stepped"] and p["stepped"]
            for p, r in zip(stretch, stretch[1:])]
        # the records tile the busy stretch: none starts before the one
        # before it closed (here the test's own turns lie between them)
        for p, r in zip(stretch, stretch[1:]):
            assert p["t0"] + p["iter_s"] <= r["t0"]
    # a's ten steps from the first iteration on, b's five from the seventh
    # (its third chunk's): every iteration but the last launches one
    assert st["steps"] == sum(r["stepped"] for r in ring) == len(ring) - 1 \
        == 11
    assert st["steps_ahead"] == sum(r["ahead"] for r in ring) \
        == st["steps"] - 1
    assert [(r["stepped"], r["ahead"], r["active"]) for r in ring2] == [
        (1, 0, 0), (1, 1, 1), (0, 0, 1)]
    # what an iteration emitted: the slots the fetched step decoded, and
    # the first tokens it gave, each in the record that spans its clock
    assert st["tokens"] == sum(r["active"] for r in ring) == 15
    seqs = {s.rid: s for s in (a, b, c)}
    seen = [q["rid"] for r in ring + ring2 for q in r["requests"]]
    assert seen == [a.rid, b.rid, c.rid]
    for r in ring + ring2:
        for q in r["requests"]:
            s = seqs[q["rid"]]
            assert r["active"] and r["t0"] < s.t_first <= r["ts"]
            assert q["ttft_s"] == s.t_first - s.t_submit
    # the admitting iteration is the one before: its step carries the
    # first token, the next one's emit hands it out
    for s in (a, b, c):
        (i,) = [i for i, r in enumerate(ring + ring2)
                if any(q["rid"] == s.rid for q in r["requests"])]
        before = (ring + ring2)[i - 1]
        assert before["chunks"] and before["stepped"]
        assert before["t0"] < s.t_ready <= before["ts"]
