"""Device runtime observability (telemetry/device.py): the XLA
compilation ledger (recompile cause diffs, storm advisories), the
device-memory census (live buffers, PageAllocator pages, gauges), the
``_device`` KV flush/merge, and the read surfaces (CLI, HTTP,
chrome-trace compile slices, RemediationEngine advisory records).

The ledger units run against explicit CompilationLedger instances with
fake clocks/publishers; the cluster-backed roundtrip uses the process
singletons the production wiring feeds.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.telemetry import device as devtel

pytestmark = pytest.mark.device


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


@pytest.fixture(autouse=True)
def _fresh_singletons():
    devtel.reset_for_tests()
    yield
    devtel.reset_for_tests()


def _ledger(**kw):
    pubs = []
    kw.setdefault("storm_threshold", 3)
    kw.setdefault("storm_window_s", 30.0)
    led = devtel.CompilationLedger(publish=pubs.append, **kw)
    return led, pubs


# ---------------------------------------------------------------------------
# compilation ledger: detection, cause diffs, storms
# ---------------------------------------------------------------------------


def test_shape_unstable_workload_records_cause_diffs():
    """The e2e claim: a shape-unstable stream through an instrumented
    jit records every recompile with a cause diff naming the changed
    argument and its old -> new shape; a same-shape call is a cache hit
    and records nothing."""
    led, pubs = _ledger()

    def step(x):
        return x * 2.0

    prog = led.jit(step, name="e2e.step")
    prog(jnp.ones((2, 3), jnp.float32))
    mark = led.counts()
    prog(jnp.ones((2, 3), jnp.float32))          # cache hit
    assert led.compiles_since(mark) == {}
    prog(jnp.ones((2, 4), jnp.float32))          # recompile 1
    prog(jnp.zeros((2, 5), jnp.float32))         # recompile 2

    snap = led.snapshot()
    st = snap["programs"]["e2e.step"]
    assert st["compiles"] == 3 and st["recompiles"] == 2
    assert snap["total_compiles"] == 3 and snap["total_recompiles"] == 2

    cause = st["last_cause"]
    assert cause["arg"] == "x" and cause["kind"] == "shape"
    assert cause["old"] == "float32[2,4]"
    assert cause["new"] == "float32[2,5]"

    recs = snap["records"]
    assert [r["nth_compile"] for r in recs] == [1, 2, 3]
    assert recs[0]["cause"] is None              # first compile: no diff
    assert recs[1]["cause"]["old"] == "float32[2,3]"
    assert recs[1]["cause"]["new"] == "float32[2,4]"
    assert all(r["program"] == "e2e.step" for r in recs)
    # jax.monitoring durations attached to the compiling call
    assert recs[0]["compile_s"] > 0


# -- a compiling call by exclusive parts (PR 58) ------------------------------

TRACE, LOWER, BACKEND = devtel._DURATION_EVENTS
CACHE_LOAD = devtel._CACHE_LOAD_EVENT
CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"  # unheard
CACHE_HIT, CACHE_MISS = devtel._CACHE_EVENTS


class ScriptedProgram:
    """What the ledger sees of a jitted function — `_cache_size()` grows
    when a call compiles — saying through `jax.monitoring` what jax says
    on the calling thread while it compiles: ("enter", event) where
    `log_elapsed_time` is entered, ("exit", event, seconds) where it
    ends, ("event", name), ("tick", seconds) while the clock runs."""

    def __init__(self, script, clock):
        self.script, self.clock, self.size = script, clock, 0

    def _cache_size(self):
        return self.size

    def __call__(self, x):
        from jax import monitoring

        for step in self.script:
            if step[0] == "enter":
                monitoring.record_scalar(step[1], self.clock.t)
            elif step[0] == "exit":
                monitoring.record_event_duration_secs(step[1], step[2])
            elif step[0] == "event":
                monitoring.record_event(step[1])
            else:
                self.clock.advance(step[1])
        self.size += 1
        return x


def _scripted_record(monkeypatch, script, wall0=5000.0):
    """The record of ONE compiling call that hears `script`, the ledger's
    module on a clock that only the script's ticks move."""
    import types

    clock = FakeClock(wall0)
    monkeypatch.setattr(devtel, "time", types.SimpleNamespace(
        time=clock, perf_counter=clock, monotonic=clock))
    led, _ = _ledger(clock=clock, wall=clock)
    prog = led.instrument(ScriptedProgram(script, clock), name="scripted")
    prog(1)
    snap = led.snapshot()
    (rec,) = snap["records"]
    return rec, snap


def test_a_nested_jits_trace_counts_once(monkeypatch):
    """`jaxpr_trace_duration` nests: a jitted function traced inside a
    program fires its own inside its caller's, and both land in the
    calling program's frame.  The record keeps the outermost alone, so
    its parts are exclusive and sum to no more than the call."""
    script = [
        ("enter", TRACE),                          # the program
        ("tick", 0.25),
        ("enter", TRACE),                          # a jit called inside
        ("enter", TRACE), ("tick", 0.125), ("exit", TRACE, 0.125),  # jnp.sin
        ("tick", 0.125),
        ("exit", TRACE, 0.25),
        ("tick", 0.5),
        ("exit", TRACE, 1.0),                      # ... contains them all
        ("enter", LOWER), ("tick", 0.5), ("exit", LOWER, 0.5),
        ("enter", BACKEND), ("event", CACHE_MISS), ("tick", 2.0),
        ("exit", BACKEND, 2.0),
        ("tick", 0.25),                            # load + first execution
    ]
    rec, snap = _scripted_record(monkeypatch, script)
    d = rec["durations"]
    assert d == {"trace_s": 1.0, "lower_s": 0.5, "backend_s": 2.0}
    assert rec["call_s"] == 3.75 and rec["compile_s"] == 3.5
    assert d["trace_s"] + d["lower_s"] + d["backend_s"] <= rec["call_s"]
    assert rec["run_s"] == 0.25
    assert rec["cache_hit"] is False and rec["cache_load_s"] == 0.0
    # the call was entered at the wall stamp the record carries, and the
    # record was made when it returned
    assert rec["t_call_wall"] == 5000.0 and rec["ts"] == 5003.75
    tot = snap["programs"]["scripted"]["durations_total_s"]
    assert tot == {"trace_s": 1.0, "lower_s": 0.5, "backend_s": 2.0,
                   "call_s": 3.75, "cache_load_s": 0.0, "run_s": 0.25,
                   "t_first_call_wall": 5000.0}


def test_a_call_the_persistent_cache_served_says_so(monkeypatch):
    """What jax says where `compile_or_get_cached` finds the executable:
    a hit and the seconds the read took, inside
    `backend_compile_duration` (the compile seconds it saved are jax's
    to say: no reader here, so the ledger keeps none)."""
    script = [
        ("enter", TRACE), ("tick", 0.5), ("exit", TRACE, 0.5),
        ("enter", LOWER), ("tick", 0.25), ("exit", LOWER, 0.25),
        ("enter", BACKEND), ("event", CACHE_HIT), ("tick", 0.75),
        ("exit", CACHE_SAVED, 9.25), ("exit", CACHE_LOAD, 0.75),
        ("tick", 0.25), ("exit", BACKEND, 1.0),
        ("tick", 0.5),
    ]
    rec, snap = _scripted_record(monkeypatch, script)
    assert rec["cache_hit"] is True
    assert 0 < rec["cache_load_s"] == 0.75 <= rec["durations"]["backend_s"]
    assert "cache_saved_s" not in rec
    assert rec["run_s"] == 0.5 and rec["call_s"] == 2.25
    assert snap["programs"]["scripted"]["durations_total_s"][
        "cache_load_s"] == 0.75
    # the process's own count hears it too (the global ledger's)
    assert devtel.get_ledger().snapshot()["persistent_cache"] == {
        "hits": 1, "misses": 0, "load_s": 0.75}


def test_a_real_nested_program_keeps_its_parts_inside_the_call():
    """The same on jax's own events: a program that calls a jitted
    function that calls jitted `jnp` functions."""
    led, _ = _ledger()
    inner = jax.jit(lambda x: jnp.sin(x) * 2.0)

    def outer(x):
        return inner(inner(x)).sum() + jnp.cos(x).sum()

    before = time.time()
    led.jit(outer, name="nested.outer")(jnp.ones((4, 4), jnp.float32))
    (rec,) = led.snapshot()["records"]
    d = rec["durations"]
    assert set(d) == {"trace_s", "lower_s", "backend_s"}
    assert sum(d.values()) <= rec["call_s"]
    assert rec["run_s"] == pytest.approx(rec["call_s"] - sum(d.values()),
                                         abs=2e-6)
    assert rec["cache_load_s"] <= d["backend_s"]
    assert before <= rec["t_call_wall"] <= rec["ts"]
    assert rec["t_call_wall"] + rec["call_s"] <= rec["ts"] + 0.05


def test_a_warm_persistent_cache_is_read_per_program(tmp_path):
    """A fresh function of the same text, with the first one's executable
    in a persistent cache directory: where this backend's cache serves it,
    the record says `cache_hit` and how long the read took."""
    from jax._src import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    led, _ = _ledger()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        x = jnp.ones((3, 5), jnp.float32)
        for name in ("pc.first", "pc.second"):
            led.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0, name=name)(x)
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    first, second = led.snapshot()["records"]
    assert first["cache_hit"] is False          # compiled, and written
    if second["cache_hit"] is None:
        pytest.skip("this backend's persistent cache served nothing")
    assert second["cache_hit"] is True
    assert 0 < second["cache_load_s"] <= second["durations"]["backend_s"]
    assert first["cache_load_s"] == 0.0
    pc = devtel.get_ledger().snapshot()["persistent_cache"]
    assert pc["hits"] >= 1 and pc["misses"] >= 1 and pc["load_s"] > 0


def test_cause_diff_dtype_static_and_pytree():
    led, _ = _ledger()

    def g(x, flag=True):
        return x * 2.0 if flag else -x

    prog = led.jit(g, name="e2e.static", static_argnames=("flag",))
    x = jnp.ones((2, 2), jnp.float32)
    prog(x, flag=True)
    prog(x, flag=False)                          # static value change
    cause = led.snapshot()["programs"]["e2e.static"]["last_cause"]
    assert cause["arg"] == "flag" and cause["kind"] == "static"
    assert cause["old"] == "True" and cause["new"] == "False"

    def h(d):
        return d["a"] + 1

    tprog = led.jit(h, name="e2e.tree")
    tprog({"a": jnp.ones((2, 2), jnp.float32)})
    tprog({"a": jnp.ones((2, 3), jnp.float32)})  # leaf shape change
    cause = led.snapshot()["programs"]["e2e.tree"]["last_cause"]
    assert cause["kind"] == "shape"
    assert cause["arg"].startswith("d") and "a" in cause["arg"]

    dprog = led.jit(lambda x: x + 1, name="e2e.dtype")
    dprog(jnp.ones((4,), jnp.float32))
    dprog(jnp.ones((4,), jnp.int32))             # dtype change
    cause = led.snapshot()["programs"]["e2e.dtype"]["last_cause"]
    assert cause["kind"] == "dtype"
    assert "float32" in cause["old"] and "int32" in cause["new"]


def test_storm_advisory_fires_exactly_once_per_episode():
    """threshold compiles inside the window open ONE advisory; further
    compiles while the episode is open stay silent; once the window
    drains the detector re-arms and a second storm raises a second
    advisory."""
    clk = FakeClock()
    led, pubs = _ledger(storm_threshold=3, storm_window_s=30.0,
                        clock=clk)
    prog = led.jit(lambda x: x * 1.5, name="storm.prog")
    for n in (1, 2, 3, 4, 5):                    # 5 compiles, one episode
        prog(jnp.ones((2, n), jnp.float32))
        clk.advance(1.0)
    storms = led.storm_advisories()
    assert len(storms) == 1 and len(pubs) == 1
    adv = storms[0]
    assert adv["kind"] == "recompile_storm"
    assert adv["program"] == "storm.prog"
    assert adv["compiles_in_window"] == 3
    assert adv["cause"]["kind"] == "shape"
    st = led.snapshot()["programs"]["storm.prog"]
    assert st["storm_episodes"] == 1 and st["storm_open"]

    clk.advance(120.0)                           # window drains
    assert not led.snapshot()["programs"]["storm.prog"]["storm_open"]
    for n in (6, 7, 8):                          # second episode
        prog(jnp.ones((2, n), jnp.float32))
        clk.advance(1.0)
    assert len(led.storm_advisories()) == 2 and len(pubs) == 2
    assert led.snapshot()["programs"]["storm.prog"]["storm_episodes"] == 2


def test_drain_advisories_cursor():
    led, _ = _ledger()
    led.push_advisory({"kind": "memory_watermark", "ts": 1.0},
                      publish=False)
    first = led.drain_advisories()
    assert [a["kind"] for a in first] == ["memory_watermark"]
    assert led.drain_advisories() == []          # cursor advanced
    led.push_advisory({"kind": "recompile_storm", "ts": 2.0},
                      publish=False)
    assert [a["kind"] for a in led.drain_advisories()] \
        == ["recompile_storm"]


def test_instrumented_program_is_transparent():
    led, _ = _ledger()

    def step(x):
        """docstring survives"""
        return x + 1

    prog = led.jit(step, name="wrap.step")
    out = prog(jnp.ones((3,), jnp.float32))
    assert np.allclose(np.asarray(out), 2.0)
    assert prog.__doc__ == "docstring survives"
    # attribute proxying: the AOT path of the underlying jit works
    lowered = prog.lower(jnp.ones((3,), jnp.float32))
    assert lowered is not None
    # idempotent double-instrumentation
    assert led.instrument(prog) is prog


def test_executable_analysis_opt_in():
    led, _ = _ledger(analysis=True)
    prog = led.jit(lambda x: jnp.dot(x, x), name="an.prog")
    prog(jnp.ones((8, 8), jnp.float32))
    rec = led.snapshot()["records"][-1]
    assert "analysis" in rec
    assert rec["analysis"].get("cost") or rec["analysis"].get("memory")


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
def test_analysis_alias_bytes_say_whether_the_state_is_updated_in_place(
        donate):
    """`alias_bytes` of a program's record: the bytes of its output that
    live in a donated argument's buffer — all of the state where it is
    donated, none where the program returns a copy.  The analysis runs
    after the call, on arguments the call has consumed."""
    led, _ = _ledger(analysis=True)
    prog = led.jit(lambda c, i: c.at[i].set(1.0), name="al.prog",
                   donate_argnums=(0,) if donate else ())
    state = jnp.zeros((256, 128), jnp.float32)
    out = prog(state, 3)
    assert state.is_deleted() == donate
    mem = led.snapshot()["records"][-1]["analysis"]["memory"]
    assert mem["alias_bytes"] == (out.nbytes if donate else 0)
    assert mem["output_bytes"] >= out.nbytes


# ---------------------------------------------------------------------------
# memory census: live buffers, PageAllocator pages, gauges, watermark
# ---------------------------------------------------------------------------


def _gauge_value(name, tags=None):
    from ray_tpu.util.metrics import _registry

    for m in _registry.snapshot():
        if m["name"] != name:
            continue
        for key, val in m.get("series", {}).items():
            if json.loads(key) == (tags or {}):
                return val
    return None


def test_first_compile_in_flight_flag():
    """A call that finds its program never compiled flags itself while
    it runs (trace/lower/compile) and clears the flag when it returns;
    a warm call never raises it."""
    seen = []

    def f(x):
        seen.append(prog.first_compile_in_flight)   # at trace time
        return x + 1

    prog = devtel.instrument(jax.jit(f), name="flag.prog")
    assert not prog.first_compile_in_flight
    assert prog.first_compile_ended == 0.0
    prog(jnp.ones(3))
    assert seen == [True] and not prog.first_compile_in_flight
    ended = prog.first_compile_ended     # ... and says when it returned
    assert 0.0 < ended <= time.monotonic()
    prog(jnp.ones(3))                    # cache hit: f is not re-traced
    prog(jnp.ones(4))                    # a REcompile is not a first one
    assert seen == [True, False] and prog.first_compile_ended == ended


def test_engine_stall_probe_exempts_first_compile():
    """serve_engine_stall_s counts seconds without a decode step while
    slots are active.  A program's first compile (19.9 s for serve.step
    on a cold v5e, chip_smoke PR 21) is such a stretch and is not a
    stall: restarting the replica would only compile again."""
    from ray_tpu.models import gpt
    from ray_tpu.serve._engine import ContinuousEngine

    class Prog:
        first_compile_in_flight = True

    cfg = gpt.GPTConfig.nano(max_seq=64)
    eng = ContinuousEngine(gpt, cfg, None, stall_s=0.05)
    try:
        eng._slots[0] = object()          # an active slot, no step yet
        eng._fns["step"] = Prog()
        # (past its bring-up — until an engine's first launch nothing is
        # a stall, PR 59: `tests/test_control_stall.py`)
        eng._first_launch_wall["serve.prefill:8"] = time.time()
        assert eng.check_health()
        time.sleep(0.12)
        assert eng.check_health()         # compiling: the clock restarts
        Prog.first_compile_in_flight = False
        assert eng.check_health()
        time.sleep(0.12)
        with pytest.raises(RuntimeError, match="stalled"):
            eng.check_health()
        # a compile that began and ended between two probes (neither saw
        # the flag): the clock counts from where it ended
        Prog.first_compile_ended = time.monotonic()
        assert eng.check_health()
        time.sleep(0.12)
        with pytest.raises(RuntimeError, match="stalled"):
            eng.check_health()
    finally:
        eng._slots[0] = None
        eng.stop()


def test_snapshot_names_the_device_without_opening_one(monkeypatch):
    """platform / device_kind / device_count ride in the per-process
    snapshot — and reading them (or the live-buffer census) never
    initialises a backend: on a TPU host that would take the chip."""
    jnp.ones(2).block_until_ready()
    snap = devtel.device_snapshot()
    assert snap["platform"] == "cpu" and snap["device_kind"] == "cpu"
    assert snap["device_count"] == jax.device_count()
    assert set(snap["ledger"]["persistent_cache"]) >= {"hits", "misses"}

    monkeypatch.setattr(devtel, "backend_initialized", lambda: False)
    monkeypatch.setattr(jax, "devices", lambda *a: pytest.fail("opened"))
    monkeypatch.setattr(jax, "live_arrays", lambda *a: pytest.fail("opened"))
    cold = devtel.device_snapshot()
    assert cold["platform"] is None and cold["device_count"] is None
    assert cold["memory"]["live"]["count"] == 0


def test_census_counts_live_buffers_and_sets_hbm_gauge():
    keep = jnp.ones((64, 64), jnp.float32) + 0    # a live device buffer
    census = devtel.get_census()
    snap = census.census()
    assert snap["live"]["count"] >= 1
    assert snap["live"]["total_bytes"] >= keep.nbytes
    assert snap["live"]["by_dtype"].get("float32", 0) >= keep.nbytes
    assert any(s["shape"] == [64, 64] or tuple(s["shape"]) == (64, 64)
               for s in snap["live"]["top_shapes"])
    assert _gauge_value("ray_tpu_hbm_live_bytes") \
        == pytest.approx(snap["live"]["total_bytes"])


def test_page_allocator_occupancy_flows_to_census_and_gauges():
    """Satellite: shared-prefix decode -> engine_stats shared/cow ->
    census owner report pages -> ray_tpu_kv_pages{state=...} gauges."""
    from ray_tpu.models import gpt
    from ray_tpu.serve._engine import ContinuousEngine

    cfg = gpt.GPTConfig.nano(max_seq=64)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(gpt, cfg, params, max_slots=4,
                           page_size=8, prefill_bucket=8)
    # page-aligned prefix (2 full pages of 8): sharing needs fully
    # registered prompt pages, and the shared_len clamp to plen-1 forces
    # a COW copy of the last page for the joiner
    prompt = list(range(40, 56))
    try:
        a = eng.submit(prompt, max_new_tokens=24)
        deadline = time.time() + 60
        while eng.engine_stats()["prefills"] < 1:
            assert time.time() < deadline
            time.sleep(0.005)
        b = eng.submit(prompt, max_new_tokens=5)  # joins a's live prefix
        eng.collect(b, timeout=120)
        eng.collect(a, timeout=120)
        st = eng.engine_stats()
        assert st["shared_pages"] >= 1 and st["cow_copies"] >= 1

        snap = devtel.get_census().census()
        (tag, rep), = [(t, r) for t, r in snap["owners"].items()
                       if t.startswith("serve.engine.")]
        assert rep["pages"]["shared"] == st["shared_pages"]
        assert rep["pages"]["cow"] == st["cow_copies"]
        assert rep["pages"]["free"] + rep["pages"]["used"] \
            == eng.num_pages - 1                 # page 0 reserved
        for state in ("free", "used", "shared", "cow"):
            assert _gauge_value("ray_tpu_kv_pages",
                                {"state": state}) is not None
        assert _gauge_value("ray_tpu_kv_pages", {"state": "shared"}) >= 1
        assert _gauge_value("ray_tpu_kv_pages", {"state": "cow"}) >= 1
    finally:
        eng.stop()
    # stop() unregisters the owner
    assert not any(t.startswith("serve.engine.")
                   for t in devtel.get_census().census()["owners"])


def test_emergency_vault_footprint_in_census():
    from ray_tpu.elastic import emergency

    with emergency._LOCK:                        # as the replicator does
        emergency._VAULT[(7, 0)] = b"x" * 4096
        emergency._VAULT_WORLDS[7] = 1
    try:
        vf = emergency.vault_footprint()
        assert vf == {"entries": 1, "bytes": 4096, "steps": 1}
        snap = devtel.get_census().census()
        assert snap["owners"]["emergency_vault"]["bytes"] == 4096
    finally:
        emergency._clear_vault()
    # empty vault: the built-in owner stays silent
    assert "emergency_vault" not in devtel.get_census().census()["owners"]


def test_memory_watermark_advisory_once_per_episode():
    led, pubs = _ledger()
    census = devtel.DeviceMemoryCensus(watermark_bytes=1, ledger=led)
    keep = jnp.ones((16,), jnp.float32) + 0
    census.census()
    census.census()                              # still above: no repeat
    kinds = [a["kind"] for a in led.drain_advisories()]
    assert kinds == ["memory_watermark"]
    assert [p["kind"] for p in pubs] == ["memory_watermark"]
    del keep


# ---------------------------------------------------------------------------
# advisory -> remediation (advisory mode records, never acts)
# ---------------------------------------------------------------------------


def test_remediation_records_device_advisory():
    from ray_tpu.elastic import ElasticConfig
    from ray_tpu.elastic.remediation import RemediationEngine

    pub = []
    eng = RemediationEngine(ElasticConfig(), trial="t",
                            publish=pub.append,
                            control_call=lambda m, p: None)
    adv = {"event": "device_advisory", "kind": "recompile_storm",
           "program": "serve.step", "compiles_in_window": 4,
           "ts": 123.0, "cause": {"arg": "x", "kind": "shape",
                                  "old": "f32[2,3]", "new": "f32[2,4]"}}
    eng.observe_advisory(adv)
    assert len(eng.records) == 1
    rec = eng.records[0]
    assert rec["mode"] == "advisory"
    assert rec["action"]["kind"] == "observe_recompile_storm"
    assert rec["action"]["dry_run"] is True
    assert rec["cause"]["program"] == "serve.step"
    assert rec["ts"] == 123.0
    assert any(p.get("event") == "remediation_recommended" for p in pub)
    # malformed advisories never raise
    eng.observe_advisory(None)
    eng.observe_advisory({"no": "kind"})


# ---------------------------------------------------------------------------
# chrome-trace compile slices
# ---------------------------------------------------------------------------


def test_compile_trace_events():
    led, _ = _ledger()
    prog = led.jit(lambda x: x * 3, name="tr.prog")
    prog(jnp.ones((2, 2), jnp.float32))
    prog(jnp.ones((2, 3), jnp.float32))
    workers = {"w1": {"ledger": led.snapshot(), "memory": {}}}
    events = devtel.compile_trace_events(workers)
    slices = [e for e in events if e.get("ph") == "X"]
    assert len(slices) == 2
    assert all(e["name"].startswith("compile tr.prog") for e in slices)
    assert any("recompile" in e.get("args", {}).get("cause", "")
               or "shape" in e.get("args", {}).get("cause", "")
               for e in slices[1:])
    from ray_tpu.telemetry import validate_chrome_trace

    assert validate_chrome_trace({"traceEvents": events})
    # a slice is the compiling CALL, from where it was entered
    recs = workers["w1"]["ledger"]["records"]
    assert [e["ts"] for e in slices] == [r["t_call_wall"] * 1e6
                                         for r in recs]
    assert [e["dur"] for e in slices] == [r["call_s"] * 1e6 for r in recs]
    assert all(set(e["args"]["durations"]) <= {"trace_s", "lower_s",
                                               "backend_s"}
               and "run_s" in e["args"] and "cache_hit" in e["args"]
               for e in slices)


def test_trace_events_draw_boot_compiles_and_stalls_in_wall_order():
    """One worker row: the boot's parts, then its compiles, a stall where
    it fell — by their wall stamps, whatever order the snapshot lists."""
    rec = {"program": "p", "ts": 112.0, "t_call_wall": 110.0,
           "call_s": 2.0, "compile_s": 1.5, "nth_compile": 1,
           "durations": {"trace_s": 1.0, "backend_s": 0.5}, "run_s": 0.5,
           "cache_hit": True, "cache_load_s": 0.25, "cause": None}
    boot = {"start_wall": 100.0, "start_s": 0.5, "connect_wall": 100.5,
            "connect_s": 2.5, "register_wall": 103.0, "register_s": 0.0,
            "pool_wall": 103.0, "pool_s": 0.0,
            "actor_wait_wall": 103.0, "actor_wait_s": 1.0,
            "actor_init_wall": 104.0, "actor_init_s": 5.0,
            "until_wall": 109.0}
    stalls = [{"t_wall": 105.0, "late_s": 3.5, "by": "worker-main"}]
    events = devtel.compile_trace_events(
        {"w1": {"ledger": {"records": [rec]}, "boot": boot,
                "stalls": stalls},
         "w0": {"ledger": {"records": []}, "boot": {}, "stalls": []}})
    slices = [e for e in events if e.get("ph") == "X"]
    assert {e["tid"] for e in slices} == {1}       # w0 has nothing to draw
    assert [e["name"] for e in slices] == [
        "boot start", "boot connect", "boot register", "boot pool",
        "boot actor_wait", "boot actor_init", "stood still", "compile p"]
    assert [e["ts"] for e in slices] == sorted(e["ts"] for e in slices)
    init, stall = slices[5], slices[6]
    assert (init["ts"], init["dur"]) == (104.0e6, 5.0e6)
    assert (stall["ts"], stall["dur"]) == (105.0e6, 3.5e6)
    assert stall["args"] == {"by": "worker-main"}


# ---------------------------------------------------------------------------
# cluster roundtrip: KV flush -> collect -> CLI / HTTP
# ---------------------------------------------------------------------------


@pytest.fixture
def cluster():
    owned = not ray_tpu.is_initialized()
    if owned:
        ray_tpu.init(num_cpus=4)
    yield
    if owned:
        ray_tpu.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_device_flush_collect_cli_and_http(cluster, capsys):
    from ray_tpu._private.api import current_core
    from ray_tpu.dashboard import DashboardHead
    from ray_tpu.util.state import api as state

    keep = jnp.ones((32, 32), jnp.float32) + 0   # a live buffer to census
    prog = devtel.jit(lambda x: x + 1, name="clu.step")
    prog(jnp.ones((2, 2), jnp.float32))
    prog(jnp.ones((2, 3), jnp.float32))          # one recompile
    assert devtel.flush_device_snapshot(force=True)
    # rate limit: an immediate re-flush inside the interval is skipped
    assert not devtel.flush_device_snapshot(interval_s=60.0)

    merged = devtel.collect_device_stats(current_core().control)
    assert merged["total_compiles"] >= 2
    assert merged["total_recompiles"] >= 1
    st = merged["programs"]["clu.step"]
    assert st["compiles"] == 2 and st["recompiles"] == 1
    assert st["last_cause"]["arg"] == "x"
    assert st["last_cause"]["old"] == "float32[2,2]"
    assert st["last_cause"]["new"] == "float32[2,3]"
    assert merged["live_bytes"] >= 0
    # this process's own snapshot: the cluster may be one a file before
    # this one in the same xdist worker left up, with its workers' in it
    wsnap = merged["workers"][current_core().worker_id]
    assert wsnap["memory"]["live"]["count"] >= 1
    assert wsnap["boot"]["cluster_start_s"] > 0 and "stalls" in wsnap

    # state API mirrors the merge
    via_api = state.device_stats()
    assert via_api["programs"]["clu.step"]["recompiles"] == 1

    # HTTP route + timeline compile slices
    addr = ray_tpu.connection_info()["control_address"]
    head = DashboardHead(addr, port=0)
    head.start()
    try:
        status, body = _get(head.url + "/api/device/stats")
        assert status == 200
        got = json.loads(body)
        assert got["programs"]["clu.step"]["compiles"] == 2

        status, body = _get(head.url + "/api/train/timeline")
        assert status == 200
        trace = json.loads(body)
        names = {e.get("name") for e in trace["traceEvents"]}
        assert any(n and n.startswith("compile clu.step")
                   for n in names)
    finally:
        head.stop()

    # CLI rendering (text mode)
    from ray_tpu.scripts import cli as cli_mod

    parser = cli_mod.build_parser()
    args = parser.parse_args(["device-stats", "--address", addr])
    args.fn(args)
    out = capsys.readouterr().out
    assert "clu.step" in out
    assert "shape" in out and "float32[2,2] -> float32[2,3]" in out

    args = parser.parse_args(
        ["device-stats", "--address", addr, "--format", "json"])
    args.fn(args)
    out = capsys.readouterr().out
    assert json.loads(out)["total_recompiles"] >= 1


# ---------------------------------------------------------------------------
# what an observation costs: a series' key is serialised once (PR 58)
# ---------------------------------------------------------------------------


def test_a_series_key_is_serialised_once_per_tags(monkeypatch):
    from ray_tpu.util import metrics as mm

    h = mm.Histogram("test_key_cache_hist", boundaries=[1.0],
                     tag_keys=("phase", "node"))
    dumps = []
    real = mm.json.dumps
    monkeypatch.setattr(mm.json, "dumps",
                        lambda *a, **k: dumps.append(a) or real(*a, **k))
    try:
        for _ in range(5):
            h.observe(0.5, tags={"phase": "decode"})
            h.observe(0.5, tags={"phase": "swap"})
        assert len(dumps) == 2
        assert set(h._snapshot()["series"]) == {
            '{"phase": "decode"}', '{"phase": "swap"}'}
        assert h._snapshot()["series"]['{"phase": "decode"}'][2] == 5
        # default tags are part of a key: changing them forgets the keys
        h.set_default_tags({"node": "n1"})
        h.observe(0.5, tags={"phase": "decode"})
        assert '{"node": "n1", "phase": "decode"}' in h._snapshot()["series"]
        assert len(dumps) == 3
        # a tag the metric does not declare is refused every time
        for _ in range(2):
            with pytest.raises(ValueError, match="not in tag_keys"):
                h.observe(0.5, tags={"nope": "x"})
    finally:
        h.deregister()
