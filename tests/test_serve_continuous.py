"""Continuous-batching engine (serve/_engine.py) + paged KV cache
(models/gpt.py paged_*): scheduler correctness, parity with
gpt.generate, prefix sharing / copy-on-write, admission control, the
one served mode of serve/llm.py, and the serve.batch / router
regression fixes that rode along.

Everything here is in-process (no cluster): the engine is a plain
object plus a daemon thread, and the jit programs run on CPU.
"""

import asyncio
import gc
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt
from ray_tpu.serve import _engine
from ray_tpu.serve._engine import (AdmissionRejected, ContinuousEngine,
                                   PageAllocator)

MAX_SEQ = 64
PROMPT = [3, 14, 15, 92, 6, 5]


@pytest.fixture(scope="module")
def model():
    # f32: the engine's token-exact parity with gpt.generate is a claim
    # about the schedule and the cache, made in f32 (serve/llm.py); in
    # bf16 a different batch shape may round a near-tie the other way
    cfg = gpt.GPTConfig.nano(max_seq=MAX_SEQ, dtype=jnp.float32)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _make_engine(model, **kw):
    cfg, params = model
    defaults = dict(max_slots=4, page_size=8, prefill_bucket=8)
    defaults.update(kw)
    return ContinuousEngine(gpt, cfg, params, **defaults)


def _expected(model, prompt, max_new, temperature=0.0, seed=0,
              top_k=None):
    cfg, params = model
    out = gpt.generate(params, cfg, jnp.asarray([prompt]), max_new,
                       temperature=temperature, top_k=top_k,
                       rng=jax.random.PRNGKey(seed), max_seq=MAX_SEQ)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# parity


def test_paged_matches_contiguous_and_generate_greedy(model):
    """Three prompts side by side in the slot batch decode what
    gpt.generate decodes for each alone over its contiguous cache."""
    prompts = [PROMPT, [7, 9, 2], list(range(1, 18))]
    eng = _make_engine(model)
    try:
        seqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        outs = [eng.collect(s, timeout=120)["completion"] for s in seqs]
    finally:
        eng.stop()
    for p, got in zip(prompts, outs):
        assert got == _expected(model, p, 6)


def test_sampled_decode_matches_generate(model):
    # same per-request key schedule as gpt.generate => parity holds for
    # sampled decodes too, not just greedy
    eng = _make_engine(model)
    try:
        s = eng.submit(PROMPT, max_new_tokens=8, temperature=0.8,
                       seed=123, top_k=16)
        got = eng.collect(s, timeout=120)["completion"]
    finally:
        eng.stop()
    assert got == _expected(model, PROMPT, 8, temperature=0.8,
                            seed=123, top_k=16)


# ---------------------------------------------------------------------------
# scheduling


def test_join_and_evict_mid_step(model):
    """A short request submitted after a long one is already decoding
    joins the running batch and finishes first — no batch-boundary
    stall — and every completion still matches the reference decode."""
    eng = _make_engine(model, max_slots=2)
    try:
        long = eng.submit(PROMPT, max_new_tokens=20)
        # wait until the long sequence is actually in a slot
        deadline = time.time() + 60
        while eng.engine_stats()["active"] == 0:
            assert time.time() < deadline
            time.sleep(0.005)
        short = eng.submit([7, 9, 2], max_new_tokens=3)
        r_short = eng.collect(short, timeout=120)
        r_long = eng.collect(long, timeout=120)
        assert r_short["completion"] == _expected(model, [7, 9, 2], 3)
        assert r_long["completion"] == _expected(model, PROMPT, 20)
        # the short one co-resided with the long one
        assert r_short["batch_size"] >= 2
        st = eng.engine_stats()
        assert st["active"] == 0
        assert st["free_pages"] == eng.num_pages - 1
    finally:
        eng.stop()


def test_eos_evicts_early(model):
    eng = _make_engine(model)
    try:
        ref = _expected(model, PROMPT, 8)
        eos = ref[2]
        s = eng.submit(PROMPT, max_new_tokens=8, eos_id=eos)
        got = eng.collect(s, timeout=120)["completion"]
    finally:
        eng.stop()
    # stops AT the first eos occurrence, inclusive
    assert got == ref[:ref.index(eos) + 1]


def test_streaming_interleaved_order(model):
    """Two streams driven concurrently: each consumer sees its own
    tokens, in order, matching the non-streaming result."""
    eng = _make_engine(model, max_slots=4)
    try:
        prompts = [PROMPT, [11, 4, 8, 2]]
        seqs = [eng.submit(p, max_new_tokens=10, stream=True)
                for p in prompts]
        got = [[] for _ in prompts]

        def drain(i):
            for tok in eng.stream(seqs[i]):
                got[i].append(tok)

        threads = [threading.Thread(target=drain, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        for p, g in zip(prompts, got):
            assert g == _expected(model, p, 10)
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# paged allocator: prefix sharing + copy-on-write


def test_page_allocator_share_and_release():
    a = PageAllocator(num_pages=8, page_size=4)
    toks = list(range(100, 110))   # 10 tokens: 2 full pages + tail
    plan = a.plan(toks, 3)
    assert plan["shared_len"] == 0 and not plan["copies"]
    assert len(plan["pages"]) == 3
    for i in range(2):             # register the two full pages
        a.register_prefix(tuple(toks[:(i + 1) * 4]), plan["pages"][i])

    # a second sequence with the same first 8 tokens shares both pages
    plan2 = a.plan(toks[:8] + [7, 7], 3)
    assert plan2["shared_len"] == 8 and plan2["n_shared"] == 2
    assert plan2["pages"][:2] == plan["pages"][:2]
    assert not plan2["copies"]
    assert a.refcount(plan["pages"][0]) == 2

    # release the second: shared pages survive (first still holds them)
    a.release(plan2["pages"])
    assert a.refcount(plan["pages"][0]) == 1
    # release the first: registry purged, pages return to the free list
    a.release(plan["pages"])
    assert a.free_pages == 7
    assert a.lookup_prefix(tuple(toks[:4])) is None


def test_page_allocator_cow_on_exact_match():
    """A prompt fully covered by registered pages must still recompute
    its LAST position (it produces the first logits), so the final
    shared page is copy-on-write'd into a private one."""
    a = PageAllocator(num_pages=8, page_size=4)
    toks = list(range(50, 58))     # exactly 2 pages
    plan = a.plan(toks, 3)
    for i in range(2):
        a.register_prefix(tuple(toks[:(i + 1) * 4]), plan["pages"][i])
    plan2 = a.plan(toks, 3)        # identical prompt
    assert plan2["shared_len"] == 7          # clamped to plen - 1
    assert len(plan2["copies"]) == 1
    src, dst = plan2["copies"][0]
    assert src == plan["pages"][1] and dst == plan2["pages"][1]
    assert plan2["pages"][1] != plan["pages"][1]   # private copy
    assert a.refcount(src) == 1    # COW did not ref the source


def test_page_allocator_starved_plan_takes_no_refs():
    a = PageAllocator(num_pages=4, page_size=4)
    p1 = a.plan([1] * 8, 3)        # takes all 3 usable pages
    assert p1 is not None and a.free_pages == 0
    assert a.plan([2] * 8, 2) is None
    a.release(p1["pages"])
    assert a.free_pages == 3


def test_prefix_sharing_cow_end_to_end(model):
    """Two identical page-aligned prompts CO-RESIDENT in the engine
    (sharing is live-sequence only): pages shared, one COW copy,
    identical leading completions, full reclamation afterwards — and a
    third distinct prompt is unaffected."""
    prompt = list(range(40, 56))           # 16 tokens = 2 pages of 8
    eng = _make_engine(model, max_slots=4)
    try:
        a = eng.submit(prompt, max_new_tokens=24)
        # b must join while a is still live so a's registered prompt
        # pages are shareable
        deadline = time.time() + 60
        while eng.engine_stats()["prefills"] < 1:
            assert time.time() < deadline
            time.sleep(0.005)
        b = eng.submit(prompt, max_new_tokens=5)
        c = eng.submit([9, 9, 1], max_new_tokens=5)
        rb = eng.collect(b, timeout=120)
        rc = eng.collect(c, timeout=120)
        ra = eng.collect(a, timeout=120)
        st = eng.engine_stats()
    finally:
        eng.stop()
    assert ra["completion"] == _expected(model, prompt, 24)
    assert rb["completion"] == ra["completion"][:5]
    assert rc["completion"] == _expected(model, [9, 9, 1], 5)
    assert st["shared_pages"] >= 1
    assert st["cow_copies"] >= 1
    assert st["free_pages"] == eng.num_pages - 1


# ---------------------------------------------------------------------------
# admission control


def test_oversized_request_rejected_up_front(model):
    eng = _make_engine(model, num_pages=3)    # 2 usable pages = 16 toks
    try:
        with pytest.raises(ValueError, match="pages"):
            eng.submit(list(range(20)), max_new_tokens=8)
        # a fitting request still goes through
        s = eng.submit(PROMPT, max_new_tokens=4)
        assert len(eng.collect(s, timeout=120)["completion"]) == 4
    finally:
        eng.stop()


def test_queue_cap_sheds_with_retry_after(model):
    cfg, params = model
    eng = ContinuousEngine(gpt, cfg, params, max_slots=1, page_size=8,
                           prefill_bucket=8, queue_cap=2,
                           shed_queue_depth=1, retry_after_s=2.5)
    try:
        first = eng.submit(PROMPT, max_new_tokens=40)
        deadline = time.time() + 60         # wait until it holds the slot
        while eng.engine_stats()["active"] < 1:
            assert time.time() < deadline
            time.sleep(0.005)
        q1 = eng.submit(PROMPT, max_new_tokens=4)
        q2 = eng.submit(PROMPT, max_new_tokens=4)   # queue at cap
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(PROMPT, max_new_tokens=4)
        assert ei.value.retry_after_s == 2.5
        st = eng.engine_stats()
        assert st["rejected"] >= 1
        assert st["accepting"] is False          # past shed watermark
        for s in (first, q1, q2):
            eng.collect(s, timeout=300)
    finally:
        eng.stop()


def test_page_starved_request_waits_not_fails(model):
    """A request that fits the arena but not RIGHT NOW parks at the
    queue head and admits once pages free up."""
    eng = _make_engine(model, max_slots=2, num_pages=5)  # 4 usable
    try:
        a = eng.submit(list(range(10)), max_new_tokens=10)  # 3 pages
        b = eng.submit(list(range(20, 28)), max_new_tokens=10)  # needs 3
        rb = eng.collect(b, timeout=300)
        ra = eng.collect(a, timeout=300)
    finally:
        eng.stop()
    assert ra["completion"] == _expected(model, list(range(10)), 10)
    assert rb["completion"] == _expected(model, list(range(20, 28)), 10)


def test_engine_stats_shape(model):
    eng = _make_engine(model)
    try:
        s = eng.submit(PROMPT, max_new_tokens=4)
        eng.collect(s, timeout=120)
        st = eng.engine_stats()
    finally:
        eng.stop()
    # every key has a reader outside the tests: router (accepting,
    # retry_after_s), controller/autoscaler (queue_depth, active,
    # free_pages, ttft_p99_s, tokens_per_s), health probe and smoke
    # (steps, prefills), operators (the running totals)
    for key in ("active", "queue_depth", "free_pages", "accepting",
                "retry_after_s", "ttft_p99_s", "tokens_per_s", "requests",
                "tokens", "steps", "prefills", "queue_wait_s", "prefill_s",
                "decode_s", "host_s", "device_wait_s", "blocked_slot_s",
                "prefill_tokens", "prefill_scanned_tokens", "dispatch_s",
                "ready_wait_s", "launches", "gc_s", "gc_collections",
                "gc_max_s"):
        assert key in st, key
    assert st["active"] == 0 and st["accepting"]
    assert eng._census_report()["cache"] == "paged"
    assert st["requests"] == 1 and st["tokens"] == 4
    assert st["ttft_p99_s"] > 0
    assert eng.phase_ring()                      # phases were recorded


def test_stop_fails_waiting_requests(model):
    cfg, params = model
    eng = ContinuousEngine(gpt, cfg, params, max_slots=1, page_size=8,
                           prefill_bucket=8)
    running = eng.submit(PROMPT, max_new_tokens=8)
    waiting = eng.submit(PROMPT, max_new_tokens=8)
    eng.stop()
    with pytest.raises(RuntimeError):
        eng.collect(waiting, timeout=10)
    with pytest.raises(RuntimeError):
        eng.submit(PROMPT)
    del running


# ---------------------------------------------------------------------------
# the engine measures itself: ring records, annotations, request spans

# an iteration that admits one unchunked greedy prompt into an idle
# engine, each annotation under the one it lies in: every launch is a
# `dispatch` inside the annotation that was there, nothing between two
# launches is unannotated, the thread first blocks (`wait`: the prefill's
# ready stamp) once the step is launched, and there is nothing to fetch:
# the step's tokens are the NEXT iteration's, behind that one's launch
_D, _W = "serve.engine.dispatch", "serve.engine.wait"
_STEP = [("serve.engine.step", None), (_D, "serve.engine.step")]
_EMIT = [("serve.engine.fetch", None), ("serve.engine.emit", None)]
_TAIL = _EMIT + [("serve.engine.account", None)]
_PREFILL = [("serve.engine.prefill", "serve.engine.admit"),
            (_D, "serve.engine.prefill"),
            ("serve.engine.setrow", "serve.engine.prefill"),
            (_D, "serve.engine.setrow")]
_ANNOTATIONS = ([("serve.engine.admit", None)] + _PREFILL + _STEP
                + [(_W, None), ("serve.engine.account", None)])
# the launches of that iteration as the engine counts them (`launches`)
_PROGRAMS = ["serve.prefill:8", "serve.setrow", "serve.step"]
# a request with a temperature draws its keys: launched ahead of the
# prefill (one launch, of three small programs of jax's own), fetched
# behind the row — the one wait before the step's launch, for keys that
# were done before the prefill began
_KEYS = ("serve.engine.keys", "serve.engine.admit")
_SAMPLED_ANNOTATIONS = (
    [("serve.engine.admit", None), _KEYS, (_D, "serve.engine.keys")]
    + _PREFILL + [_KEYS, (_W, "serve.engine.keys")] + _STEP
    + [(_W, None), ("serve.engine.account", None)])
_SAMPLED_PROGRAMS = ["serve.keys"] + _PROGRAMS


class _Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: what was entered,
    with its stats and the annotation it was entered under."""

    seen: list = []
    stack: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        self.seen.append((self.name, self.kwargs,
                          self.stack[-1] if self.stack else None))
        self.stack.append(self.name)

    def __exit__(self, *exc):
        self.stack.pop()
        return False


@pytest.fixture
def recorder(monkeypatch):
    monkeypatch.setattr(_Recorder, "seen", [])
    monkeypatch.setattr(_Recorder, "stack", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    return _Recorder


def _second_request_while_first_decodes(model):
    """-> (engine stats, ring, the second request's rid); the second
    request joins while the first is streaming."""
    eng = _make_engine(model, max_slots=2)
    try:
        first = eng.submit(PROMPT, max_new_tokens=24)
        next(eng.stream(first))             # the first has streamed a token
        # same max_new as the first: its key-splitting program is compiled
        second = eng.submit(list(range(20, 31)), max_new_tokens=24,
                            request_id="req-2")
        eng.collect(first, timeout=120)
        eng.collect(second, timeout=120)
    finally:
        eng.stop()
    # read once the engine thread has ended: the last record is in
    return eng.engine_stats(), eng.phase_ring(), second.rid


def test_ring_decomposes_ttft_and_iteration_time(model):
    # the clock identities hold to 1 ms on a quiet machine; the suite
    # runs six workers wide, so a preempted attempt gets another try
    for attempt in range(5):
        st, ring, rid = _second_request_while_first_decodes(model)
        (rec, req), = [(r, q) for r in ring for q in r["requests"]
                       if q["rid"] == rid]
        # what does not depend on the clock holds in every attempt
        assert req["request_id"] == "req-2"
        assert req["prompt_tokens"] == 11 and req["shared_tokens"] == 0
        assert req["scanned_tokens"] == 16          # two buckets of 8
        # a request's first token is the step's that its admitting
        # iteration launched: the next iteration emits it
        assert rec["admitted"] == 0 and rec["active"] == 2
        adm = ring[ring.index(rec) - 1]
        assert adm["admitted"] == 1 and adm["blocked_slots"] == 1
        assert [r["blocked_slots"] for r in ring
                if not r["admitted"]] == [0] * (len(ring) - 2)
        assert sum(len(r["requests"]) for r in ring) == 2
        for key in ("prefill_s", "decode_s", "host_s", "device_wait_s",
                    "dispatch_s", "ready_wait_s", "launches"):
            assert st[key] == pytest.approx(sum(r[key] for r in ring))
        assert st["blocked_slot_s"] == pytest.approx(
            sum(r["swap_s"] * r["blocked_slots"] for r in ring))
        reqs = [q for r in ring for q in r["requests"]]
        assert st["queue_wait_s"] == pytest.approx(
            sum(q["queue_wait_s"] for q in reqs))
        assert st["prefill_s"] == pytest.approx(
            sum(q["prefill_s"] for q in reqs))
        assert st["prefill_tokens"] == len(PROMPT) + 11
        assert st["prefill_scanned_tokens"] == 8 + 16
        parts = (req["queue_wait_s"] + req["prefill_s"]
                 + req["first_step_wait_s"])
        errs = [abs(parts - req["ttft_s"])] + [
            abs(r["host_s"] + r["device_wait_s"] - (r["ts"] - r["t0"]))
            for r in ring]
        if max(errs) < 1e-3:
            break
    assert max(errs) < 1e-3, errs
    assert 0 < req["prefill_s"] < req["ttft_s"]
    assert all(r["host_s"] > 0 and r["device_wait_s"] > 0 for r in ring)


def test_ring_splits_device_wait_into_dispatch_and_ready_wait(model):
    """Every launch is stamped entered -> returned, every wait from where
    the thread was last let go: the two sums ARE `device_wait_s` (same
    stamps), a plain iteration's launch and fetch lie inside `decode_s`,
    and a record carries its iteration's ordinal, the launches it made,
    the times it blocked, whether it launched a step and whether that step
    went out with the one before it unfetched."""
    st, ring, rid = _second_request_while_first_decodes(model)
    assert [r["iter"] for r in ring] == list(range(1, len(ring) + 1))
    # every iteration but the last launches a step, every one but the
    # first (the idle engine's: nothing in flight) ahead of a fetch; the
    # last fetches the last step and launches nothing
    assert [r["stepped"] for r in ring] == [1] * (len(ring) - 1) + [0]
    assert [r["ahead"] for r in ring] == [0] + [1] * (len(ring) - 2) + [0]
    assert (st["steps"], st["steps_ahead"]) == (len(ring) - 1, len(ring) - 2)
    assert [bool(r["active"]) for r in ring] == [False] + [True] * (
        len(ring) - 1)
    # a plain iteration launches the step and waits for the tokens of the
    # step before; one that admits an unchunked greedy prompt launches its
    # prefill, its row and the step, and only then waits: the tokens (if
    # a step was in flight), the stamp
    assert [r["launches"] for r in ring] == [
        (len(_PROGRAMS) if r["admitted"] else 1) * r["stepped"]
        for r in ring]
    assert [r["waits"] for r in ring] == [
        bool(r["admitted"]) + bool(r["active"]) for r in ring]
    assert sum(r["admitted"] for r in ring) == 2
    for r in ring:
        assert r["dispatch_s"] + r["ready_wait_s"] == pytest.approx(
            r["device_wait_s"], abs=1e-9)
        assert r["host_s"] + r["device_wait_s"] == pytest.approx(
            r["iter_s"], abs=1e-9)
        assert (0 < r["step_dispatch_s"]) == bool(r["stepped"])
        assert (0 < r["step_wait_s"]) == bool(r["active"])
        assert (0 < r["decode_s"]) == bool(r["active"])
        assert r["step_dispatch_s"] + r["step_wait_s"] <= r["decode_s"] \
            or not r["active"]
        if r["admitted"]:       # the admission's programs are apart, and
            # the step is launched before the admission's work is done;
            # the admission's share and the step's tile the iteration
            assert r["step_dispatch_s"] < r["dispatch_s"] < r["swap_s"]
            assert r["swap_s"] + r["decode_s"] == pytest.approx(
                r["ts"] - r["t0"], abs=1e-9)
            assert 0 < r["prefill_s"] <= r["swap_s"] + r["decode_s"]
            if r["active"]:     # the tokens are fetched before the stamp
                assert r["step_wait_s"] < r["ready_wait_s"]
        else:
            assert r["swap_s"] == 0
            assert r["step_dispatch_s"] == r["dispatch_s"]
            assert r["step_wait_s"] == r["ready_wait_s"]
    assert st["launches"] == 2 * len(_PROGRAMS) + len(ring) - 3


def test_chunk_iterations_launch_one_program_and_no_step(model):
    """A prompt of three chunks alone in the engine: two iterations run
    one prefill program each and no step (`step_dispatch_s` 0), the third
    the last chunk, the row and the first step; each waits for its chunk
    once.  The fourth launches the second step and fetches the first, the
    fifth has nothing to launch and fetches the second."""
    eng = _make_engine(model, prefill_chunk=8)
    try:
        eng.collect(eng.submit(list(range(1, 21)), max_new_tokens=2),
                    timeout=120)
    finally:
        eng.stop()
    ring = eng.phase_ring()         # whole: the engine thread has ended
    assert [r["launches"] for r in ring] == [1, 1, len(_PROGRAMS), 1, 0]
    assert [r["chunks"] for r in ring] == [1, 1, 1, 0, 0]
    assert [r["waits"] for r in ring] == [1, 1, 1, 1, 1]
    assert [r["stepped"] for r in ring] == [0, 0, 1, 1, 0]
    assert [r["ahead"] for r in ring] == [0, 0, 0, 1, 0]
    assert [r["active"] for r in ring] == [0, 0, 0, 1, 1]
    for r in ring[:2]:
        assert r["active"] == 0 and r["decode_s"] == 0
        assert r["step_dispatch_s"] == 0 and r["step_wait_s"] == 0
        assert 0 < r["dispatch_s"] < r["device_wait_s"] <= r["swap_s"]


@pytest.mark.parametrize("temperature, annotations, programs", [
    (0.0, _ANNOTATIONS, _PROGRAMS),
    (0.8, _SAMPLED_ANNOTATIONS, _SAMPLED_PROGRAMS)],
    ids=["greedy", "sampled"])
def test_iteration_nests_annotations_and_names_every_launch(
        model, recorder, temperature, annotations, programs):
    eng = _make_engine(model)
    try:
        eng.collect(eng.submit(PROMPT, max_new_tokens=3, seed=5,
                               temperature=temperature,
                               request_id="req-7"), timeout=120)
    finally:
        eng.stop()
    seen, ring = recorder.seen, eng.phase_ring()
    nest = [(n, parent) for n, _, parent in seen]
    first = nest.index(("serve.engine.account", None)) + 1
    assert nest[:first] == annotations           # the admitting iteration
    plain = [("serve.engine.admit", None)] + _STEP + _TAIL
    assert nest[first:first + len(plain)] == plain
    # a launch names its program as the compilation ledger does
    assert [kw["program"] for n, kw, _ in seen[:first]
            if n == _D] == programs
    assert (ring[0]["launches"], ring[0]["waits"]) == (
        len(programs), 1 + (temperature > 0))
    # nothing waits for the prefill between its launch and the step's:
    # its ready stamp is the first thing after the step's `dispatch`
    step = nest.index((_D, "serve.engine.step"))
    assert nest[step + 1] == (_W, None)
    assert [parent for n, parent in nest[:step] if n == _W] == (
        ["serve.engine.keys"] if temperature > 0 else [])
    (prefill,) = [kw for n, kw, _ in seen if n == "serve.engine.prefill"]
    assert prefill == {"request_id": "req-7", "tokens": len(PROMPT),
                       "bucket": 8}
    # `admit` and `step` carry the ordinal of their iteration's record
    # (the last iteration fetches the last step and launches none)
    for name, key in (("serve.engine.admit", "iter"),
                      ("serve.engine.step", "stepped")):
        assert [kw for n, kw, _ in seen if n == name] == [
            {"iter": r["iter"]} for r in ring if r[key]]
    assert not any(kw for n, kw, _ in seen if n in (
        _W, "serve.engine.setrow", "serve.engine.keys",
        "serve.engine.fetch", "serve.engine.emit", "serve.engine.account"))


@pytest.mark.parametrize("joined", [False, True],
                         ids=["alone", "joined_mid_stream"])
@pytest.mark.parametrize("temperature", [0.0, 0.7],
                         ids=["greedy", "sampled"])
def test_tokens_are_generates_with_and_without_a_newcomer(model, temperature,
                                                          joined):
    """A request's tokens are `gpt.generate`'s on the same seed whether it
    decodes alone or a second request (one that samples, so that keys are
    drawn beside its rows) is admitted while it streams — and so are the
    newcomer's.  A greedy request draws no keys at all."""
    eng = _make_engine(model, max_slots=2)
    try:
        a = eng.submit(PROMPT, max_new_tokens=16, temperature=temperature,
                       seed=11, top_k=12, stream=joined)
        if joined:
            it = eng.stream(a)
            head = [next(it), next(it)]
            b = eng.submit([7, 9, 2, 30], max_new_tokens=8, temperature=0.9,
                           seed=29)
            got_a = head + list(it)
            got_b = eng.collect(b, timeout=120)["completion"]
        else:
            got_a = eng.collect(a, timeout=120)["completion"]
    finally:
        eng.stop()
    assert got_a == _expected(model, PROMPT, 16, temperature=temperature,
                              seed=11, top_k=12)
    assert (a.keys is None) == (temperature == 0)
    if joined:
        assert got_b == _expected(model, [7, 9, 2, 30], 8, temperature=0.9,
                                  seed=29)
        ring = eng.phase_ring()
        assert [r["launches"] for r in ring if r["admitted"]] == [
            len(_PROGRAMS) + (temperature > 0), len(_SAMPLED_PROGRAMS)]
        # a slot a sampling request has left reads no stale key
        assert not eng._toks_keys.any()


@pytest.mark.parametrize("temps, sampled_steps", [
    ((0.0,), 0), ((0.8,), 6), ((0.0, 0.8), 6), ((0.0, 0.8, 0.0), 6)],
    ids=["greedy", "sampled", "sampled_beside_greedy", "sampled_between"])
def test_sampled_steps_counts_the_steps_that_draw(model, temps,
                                                  sampled_steps):
    """`sampled_steps`: the steps launched with a temperature in some slot
    — the ones whose sampler takes its drawing branch — in the ring's
    records and `engine_stats()`, read off the host's operands.  A greedy
    request answers 24 tokens, one that samples 6: it is live for the six
    steps that emit them, from the hand-over of its prompt to its
    eviction, and the steps before and after it count nothing."""
    eng = _make_engine(model)
    try:
        seqs = [eng.submit([3 + i, 14, 15, 9], temperature=t, seed=5 + i,
                           top_k=12 if t else None,
                           max_new_tokens=6 if t else 24)
                for i, t in enumerate(temps)]
        outs = [eng.collect(s, timeout=120)["completion"] for s in seqs]
    finally:
        eng.stop()
    # read once the loop has closed the iteration that emitted the last
    # token (stop joins it): its record and sums come after the emit
    stats, ring = eng.engine_stats(), eng.phase_ring()
    assert [len(o) for o in outs] == [6 if t else 24 for t in temps]
    assert stats["sampled_steps"] == sampled_steps
    assert stats["steps"] >= (24 if 0.0 in temps else 6)
    assert (stats["sampled_steps"] == stats["steps"]) == (0.0 not in temps)
    assert sum(r["sampled_steps"] for r in ring) == sampled_steps
    assert {r["sampled_steps"] for r in ring} <= {0.0, 1.0}
    # a record that counts a step that draws has a sampling slot decoding
    assert all(r["active"] for r in ring if r["sampled_steps"])


def test_a_failing_prefill_is_reported_at_the_wait_and_holds_nothing(
        model, monkeypatch):
    """A program that fails says so where its result is waited for, after
    the row and the step were launched behind it: every request the
    iteration touched fails with that error, no slot or page stays held,
    the device state is dropped — and the next request is served."""
    launched = []
    real = jax.block_until_ready

    def fail_once(x):
        if not launched:
            launched.append(dict(eng._launched))
            raise RuntimeError("prefill failed on the chip")
        return real(x)

    eng = _make_engine(model, max_slots=2)
    monkeypatch.setattr(jax, "block_until_ready", fail_once)
    try:
        a = eng.submit(PROMPT, max_new_tokens=6)
        with pytest.raises(RuntimeError, match="failed on the chip"):
            eng.collect(a, timeout=120)
        # the wait came after the iteration's three launches, first of all
        assert (launched[0]["launches"], launched[0]["waits"]) == (3, 0)
        st = eng.engine_stats()
        assert st["active"] == 0 and st["queue_depth"] == 0
        assert st["free_pages"] == eng.num_pages - 1
        assert eng._cache is None and eng._logits is None
        assert eng._prefilling is None and eng.check_health()
        b = eng.submit(PROMPT, max_new_tokens=6)
        assert eng.collect(b, timeout=120)["completion"] == _expected(
            model, PROMPT, 6)
    finally:
        eng.stop()


def test_two_admissions_in_one_iteration_count_no_stretch_twice(model):
    """Two requests admitted by ONE iteration of an idle engine: five
    launches, then two waits (a stamp each; no step was in flight); the
    second's prefill counts from the first's ready stamp, so the prefill
    seconds stay inside `swap_s`, and its wait behind the first is
    `chunk_wait_s`.  The next iteration launches their last step by count
    — both slots are free from there — and emits their first tokens."""
    eng = _make_engine(model, max_slots=2)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    eng._thread = t                 # the test drives the iterations
    try:
        seqs = [eng.submit(p, max_new_tokens=2)
                for p in (PROMPT, list(range(20, 31)))]
        eng._iteration()
        (rec,) = eng.phase_ring()
        assert rec["admitted"] == 2 and rec["active"] == 0
        assert (rec["stepped"], rec["ahead"]) == (1, 0)
        assert (rec["launches"], rec["waits"]) == (5, 2)
        assert rec["requests"] == [] and rec["decode_s"] == 0
        assert seqs[0].prefill_s + seqs[1].prefill_s == pytest.approx(
            rec["prefill_s"])
        assert 0 < rec["prefill_s"] <= rec["swap_s"]
        eng._iteration()
        rec = eng.phase_ring()[1]
        assert rec["admitted"] == 0 and rec["active"] == 2
        assert (rec["stepped"], rec["ahead"]) == (1, 1)
        assert (rec["launches"], rec["waits"]) == (1, 1)
        first, second = rec["requests"]
        assert [q["rid"] for q in rec["requests"]] == [s.rid for s in seqs]
        assert [q["prefill_s"] for q in rec["requests"]] == [
            s.prefill_s for s in seqs]
        assert first["chunk_wait_s"] == 0 < second["chunk_wait_s"]
        assert seqs[0].t_prefill < seqs[1].t_prefill < seqs[0].t_ready \
            <= seqs[1].t_ready < seqs[1].t_first
        for q, s in zip(rec["requests"], seqs):
            parts = (q["queue_wait_s"] + (s.t_prefill - s.t_admit)
                     + q["prefill_s"] + q["chunk_wait_s"]
                     + q["first_step_wait_s"])
            assert parts == pytest.approx(q["ttft_s"], abs=1e-9)
        # their last token by count is in flight: the slots are free, the
        # callers have not heard
        assert eng._slots == [None, None] and len(eng._in_flight) == 1
        assert not any(s.result.done() for s in seqs)
        eng._iteration()
        assert all(s.result.done() for s in seqs) and not eng._in_flight
    finally:
        eng.stop()
    for s, p in zip(seqs, (PROMPT, list(range(20, 31)))):
        assert s.result.result()["completion"] == _expected(model, p, 2)


def test_copy_on_write_is_a_launch_inside_admit(model, recorder):
    """The page copy of an exact-duplicate prompt goes through the same
    helper: a `dispatch` named `serve.copy_page` under `admit`, counted
    in that iteration's `launches` and `dispatch_s`."""
    prompt = list(range(40, 56))               # two full pages of 8
    eng = _make_engine(model)
    try:
        a = eng.submit(prompt, max_new_tokens=24)
        next(eng.stream(a))                     # a's pages are registered
        eng.collect(eng.submit(prompt, max_new_tokens=2), timeout=120)
        eng.collect(a, timeout=120)
    finally:
        eng.stop()
    st, ring = eng.engine_stats(), eng.phase_ring()
    assert st["cow_copies"] == 1
    assert [(kw["program"], parent) for n, kw, parent in recorder.seen
            if n == _D and kw["program"] == "serve.copy_page"] == [
        ("serve.copy_page", "serve.engine.admit")]
    assert [r["launches"] for r in ring if r["admitted"]] == [
        len(_PROGRAMS), len(_PROGRAMS) + 1]


def test_gc_hook_times_collections_and_leaves_with_the_thread(model):
    """A collection made by another thread while the engine iterates is
    in some iteration's `gc_s` and in the totals; the hook is there while
    the engine thread lives and gone once it has stopped."""
    eng = _make_engine(model)
    assert eng._on_gc not in gc.callbacks       # no thread yet, no hook
    try:
        seq = eng.submit(PROMPT, max_new_tokens=40, stream=True)
        collected = 0
        for _ in eng.stream(seq):               # this thread, not its own
            assert eng._on_gc in gc.callbacks
            gc.collect()
            collected += 1
    finally:
        eng.stop()
    st, ring = eng.engine_stats(), eng.phase_ring()
    assert eng._on_gc not in gc.callbacks
    assert collected == 40 and st["gc_collections"] >= collected
    assert any(r["gc_s"] > 0 for r in ring)
    assert all(r["gc_s"] >= 0 for r in ring)
    # what the records hold is part of what the process spent
    assert 0 < sum(r["gc_s"] for r in ring) <= st["gc_s"] * (1 + 1e-9)
    assert 0 < st["gc_max_s"] <= st["gc_s"]


def test_phase_histogram_observes_dispatch_once_an_iteration(model,
                                                             monkeypatch):
    seen = []

    class Histogram:
        @staticmethod
        def observe(value, tags=None):
            seen.append((tags["phase"], value))

    monkeypatch.setattr(_engine, "_m_phase", Histogram)
    eng = _make_engine(model)
    try:
        eng.collect(eng.submit(PROMPT, max_new_tokens=3), timeout=120)
    finally:
        eng.stop()
    ring = eng.phase_ring()
    assert [v for ph, v in seen if ph == "dispatch"] == [
        r["dispatch_s"] for r in ring if r["launches"]]
    assert [v for ph, v in seen if ph == "decode"] == [
        r["decode_s"] for r in ring if r["active"]]
    assert {ph for ph, _ in seen} == {"swap", "prefill", "decode",
                                      "dispatch"}


def test_finished_request_emits_engine_spans(model, monkeypatch):
    from ray_tpu.util import tracing

    spans = []
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_enabled", False)
    monkeypatch.setattr(tracing, "_sample_ratio", 0.0)   # record all
    eng = _make_engine(model)
    try:
        eng.collect(eng.submit(PROMPT, max_new_tokens=4), timeout=120)
        assert spans == []                          # tracing is off
        tracing.configure(spans.append)
        with tracing.span("client.call") as parent:
            seq = eng.submit(PROMPT, max_new_tokens=4, request_id="req-9")
        eng.collect(seq, timeout=120)           # spans precede the result
        eng.collect(eng.submit(PROMPT, max_new_tokens=4), timeout=120)
    finally:
        eng.stop()
    got = {s["name"]: s for s in spans if s["name"].startswith("engine.")}
    assert list(got) == ["engine.queue_wait", "engine.prefill",
                         "engine.first_step", "engine.decode"]
    assert len(spans) == 5                # + client.call; no parent, no span
    for s in got.values():
        assert s["trace_id"] == f"{parent['trace_id']:032x}"
        assert s["parent_id"] == f"{parent['span_id']:016x}"
        assert s["attributes"]["request_id"] == "req-9"
    q, p, f, d = got.values()
    assert (q["start_ns"] <= q["end_ns"] <= p["start_ns"] < p["end_ns"]
            == f["start_ns"] < f["end_ns"] == d["start_ns"] < d["end_ns"])
    assert d["attributes"]["generated_tokens"] == 4


# ---------------------------------------------------------------------------
# serve.batch flusher regressions


def test_batch_flusher_propagates_fn_error_and_recovers():
    from ray_tpu.serve.batching import batch

    calls = {"n": 0}

    @batch(max_batch_size=4, batch_wait_timeout_s=0.01)
    async def f(items):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return [x * 2 for x in items]

    async def main():
        with pytest.raises(RuntimeError, match="boom"):
            await f(1)
        # the flusher survived the fn error: the next batch works
        assert await f(3) == 6

    asyncio.run(main())


def test_batch_flusher_rearms_across_event_loops():
    """A new event loop (fresh asyncio.run) must get a fresh flusher
    bound to IT — the old one died with its loop."""
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=4, batch_wait_timeout_s=0.01)
    async def g(items):
        return [x + 1 for x in items]

    assert asyncio.run(g(1)) == 2
    assert asyncio.run(g(10)) == 11      # second loop: re-armed


# ---------------------------------------------------------------------------
# router regressions


def _fake_router(table):
    from ray_tpu.serve import _router

    r = _router.Router("app", "dep", controller=object())
    r._refresh = lambda force=False: None
    r._replicas = {row["replica_id"]: row for row in table}
    return r


def test_router_decrements_inflight_when_submit_raises():
    class BadHandle:
        class handle_request:
            @staticmethod
            def remote(*a, **k):
                raise RuntimeError("actor died")

    r = _fake_router([{"replica_id": "r1", "handle": BadHandle}])
    with pytest.raises(RuntimeError):
        r.assign(None, (), {}, {})
    assert r._inflight.get("r1", 0) == 0


def test_router_sheds_when_every_engine_stops_accepting():
    from ray_tpu.serve._common import NoCapacityError

    table = [{"replica_id": f"r{i}", "handle": None,
              "engine": {"accepting": False, "retry_after_s": 3.0}}
             for i in range(2)]
    r = _fake_router(table)
    with pytest.raises(NoCapacityError) as ei:
        r._pick()
    assert ei.value.retry_after_s == 3.0


def test_router_skips_shedding_replica():
    ok = {"replica_id": "ok", "handle": None,
          "engine": {"accepting": True}}
    shed = {"replica_id": "shed", "handle": None,
            "engine": {"accepting": False, "retry_after_s": 1.0}}
    r = _fake_router([ok, shed])
    for _ in range(8):
        assert r._pick()["replica_id"] == "ok"


# ---------------------------------------------------------------------------
# config knobs


def test_serve_knobs_resolve_from_env(monkeypatch):
    from ray_tpu._private.config import Config

    monkeypatch.setenv("RAY_TPU_SERVE_MAX_SLOTS", "3")
    monkeypatch.setenv("RAY_TPU_SERVE_PAGE_SIZE", "4")
    monkeypatch.setenv("RAY_TPU_SERVE_PREFILL_BUCKET", "16")
    monkeypatch.delenv("RAY_TPU_SYSTEM_CONFIG", raising=False)
    c = Config()
    assert c.serve_max_slots == 3
    assert c.serve_page_size == 4
    assert c.serve_prefill_bucket == 16
    assert c.is_set("serve_max_slots")
    assert not c.is_set("serve_queue_cap")       # default untouched


def _impl(monkeypatch, **kw):
    """A directly built deployment body on the `model` fixture's weights
    (nano, f32, PRNGKey(0)), with no system config pinned."""
    from ray_tpu._private import config as _c
    from ray_tpu.serve.llm import _LLMServerImpl

    monkeypatch.delenv("RAY_TPU_SYSTEM_CONFIG", raising=False)
    monkeypatch.setattr(_c, "_current", None)    # un-pin any system cfg
    kw.setdefault("engine_kwargs", dict(max_slots=4, page_size=8,
                                        prefill_bucket=8))
    return _LLMServerImpl(preset="nano", max_seq=MAX_SEQ,
                          cfg_kwargs={"dtype": jnp.float32}, **kw)


def _stop(srv):
    if srv._engine is not None:
        srv._engine.stop()


def test_llm_impl_reads_serve_knobs(monkeypatch, model):
    monkeypatch.setenv("RAY_TPU_SERVE_MAX_SLOTS", "3")
    monkeypatch.setenv("RAY_TPU_SERVE_PAGE_SIZE", "4")
    srv = _impl(monkeypatch, engine_kwargs={})
    try:
        eng = srv._get_engine()
        assert (eng.max_slots, eng.page_size) == (3, 4)
    finally:
        _stop(srv)
    # bind-time engine_kwargs beat the env knobs
    srv2 = _impl(monkeypatch, engine="paged",
                 engine_kwargs={"max_slots": 2})
    try:
        eng = srv2._get_engine()
        assert (eng.max_slots, eng.page_size) == (2, 4)
    finally:
        _stop(srv2)
    with pytest.raises(ValueError):
        _impl(monkeypatch, engine="bogus")


# ---------------------------------------------------------------------------
# one served mode


@pytest.mark.parametrize("mode", ["static", "contiguous"])
def test_llm_impl_refuses_the_removed_modes(monkeypatch, mode):
    with pytest.raises(ValueError, match="static.*contiguous"):
        _impl(monkeypatch, engine=mode)


def test_engine_takes_no_cache_argument(model):
    cfg, params = model
    with pytest.raises(TypeError):
        ContinuousEngine(gpt, cfg, params, cache="contiguous")


def test_llm_impl_ignores_the_removed_engine_flag(monkeypatch, model):
    """RAY_TPU_SERVE_ENGINE used to pick the served mode; a leftover
    setting changes nothing: the stream comes from the engine."""
    monkeypatch.setenv("RAY_TPU_SERVE_ENGINE", "static")
    srv = _impl(monkeypatch)
    try:
        toks = list(srv.stream_tokens(PROMPT, max_new_tokens=6))
        st = srv.engine_stats()
    finally:
        _stop(srv)
    assert toks == _expected(model, PROMPT, 6)
    assert st is not None and st["requests"] == 1 and st["steps"] >= 6


def test_llm_impl_call_returns_the_models_greedy_tokens(monkeypatch, model):
    """The request/response route on a directly built body (no cluster):
    `tokens`, `completion` and `batch_size` of one request alone."""
    srv = _impl(monkeypatch)
    try:
        got = asyncio.run(srv({"tokens": PROMPT, "max_new_tokens": 7}))
    finally:
        _stop(srv)
    want = _expected(model, PROMPT, 7)
    assert got["completion"] == want
    assert got["tokens"] == PROMPT + want
    assert got["batch_size"] == 1


@pytest.mark.parametrize("route", ["call", "stream_tokens"])
def test_llm_impl_varied_requests_compile_nothing(monkeypatch, model, route):
    """What a replica compiles is bounded by construction: one step
    program and one prefill program a padded length, whatever
    `max_new_tokens`, `temperature` and `top_k` its requests carry."""
    from ray_tpu.telemetry import device as devtel

    srv = _impl(monkeypatch)

    def ask(max_new, temperature, top_k):
        if route == "call":
            out = asyncio.run(srv({
                "tokens": PROMPT, "max_new_tokens": max_new,
                "temperature": temperature, "top_k": top_k, "seed": 3}))
            return out["completion"]
        return list(srv.stream_tokens(PROMPT, max_new, temperature, 3,
                                      top_k))

    try:
        ask(4, 0.0, None)                        # warm-up
        counts0 = devtel.get_ledger().counts()
        for i in range(12):
            t, k = 0.25 * (i % 4), (None, 5, 17)[i % 3]
            assert ask(3 + i, t, k) == _expected(
                model, PROMPT, 3 + i, temperature=t, seed=3, top_k=k)
        assert devtel.get_ledger().compiles_since(counts0) == {}
        assert set(srv._engine._fns) == {"step", "setrow", ("prefill", 8)}
    finally:
        _stop(srv)
