"""The control plane's failure detector does not count time it was deaf
itself (`ControlServer._credit_stall`, called by `_health_loop`).

On a TPU VM every process of the machine stands still for 4-9 s when a
worker first reaches the chip (PERF.md, PR 53): when that passed the
death timeout, the health loop woke first, read ten seconds of silence
it could not have heard anything in, and declared a healthy node dead
(`ActorDiedError: node ... died` out of `serve.run`).

Beside it (PR 59): every loop that sleeps says when it woke late, in the
same words (`common.note_late_wake`), and the serve engine keeps a ticker
beside its thread (`ContinuousEngine._tick`) whose late wakes are the
one witness that tells "this process was not run" from "the program took
longer" — `stalls` / `stall_s` / `stall_max_s` of `engine_stats()`, and
`stall_s` of a ring record.  The two other 10 s clocks a freeze could pass
take the same credit: the serve controller's probe loop
(`ServeController._credit_stall`) and the engine's stall probe
(`ContinuousEngine.check_health`, by the ticker's sum).
"""

import logging
import os
import signal
import threading
import time

import pytest

from ray_tpu._private import common
from ray_tpu._private import control as ctl
from ray_tpu._private.protocol import Client


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """This file builds a dozen engines within seconds, each compiling
    `serve.step` and its prefill anew under the same names: the process's
    compile ledger reads that as a recompile storm, and whichever test
    next drains its advisories in this process (a trainer's `fit()`)
    would find it."""
    from ray_tpu.telemetry import device as devtel

    devtel.reset_for_tests()
    yield
    devtel.reset_for_tests()


@pytest.fixture
def server():
    cs = ctl.ControlServer()            # built, never started: no thread
    yield cs
    cs.stop()


@pytest.mark.parametrize("late_s, credited", [
    (0.0, False),                           # a tick on time
    (ctl.HEARTBEAT_INTERVAL_S, False),      # scheduling noise: not a stall
    (3.0, True),
    (ctl.NODE_DEATH_TIMEOUT_S + 1.0, True),  # the stall that killed a node
])
def test_a_late_tick_is_credited_to_every_node(server, late_s, credited):
    now = time.monotonic()
    silent_s = {"a": late_s + 0.2, "b": late_s + 2.0}
    for nid, s in silent_s.items():
        rec = ctl.NodeRecord(nid, ("127.0.0.1", 1), {}, {})
        rec.last_heartbeat = now - s
        server.nodes[nid] = rec
    server._credit_stall(late_s)
    for nid, s in silent_s.items():
        left = time.monotonic() - server.nodes[nid].last_heartbeat
        want = s - late_s if credited else s
        assert want - 0.01 <= left <= want + 0.5, (nid, left, want)


def test_a_credit_never_reaches_past_now(server):
    rec = ctl.NodeRecord("a", ("127.0.0.1", 1), {}, {})
    server.nodes["a"] = rec                 # heard from just now
    server._credit_stall(5.0)
    assert rec.last_heartbeat <= time.monotonic()


def _state(c, nid):
    probe = Client(c.control_addr)
    try:
        nodes = probe.call("get_nodes", timeout=10.0)
    finally:
        probe.close()
    return [n["state"] for n in nodes if n["node_id"] == nid]


def test_a_frozen_machine_kills_no_node(multi_node_cluster, monkeypatch):
    """Control plane and raylet stopped together for longer than the death
    timeout, the control plane woken FIRST (so its health loop runs before
    any heartbeat can arrive, as after a machine-wide freeze it usually
    does): the node stays ALIVE.  A raylet that stays silent after that
    is still found dead."""
    monkeypatch.setenv("RAY_TPU_NODE_DEATH_TIMEOUT_S", "3")
    c = multi_node_cluster()
    node = c.add_node(resources={"CPU": 1})
    nid = node.node_id
    assert _state(c, nid) == ["ALIVE"]
    pids = [c.control_proc.pid, node.proc.pid]
    try:
        for pid in pids:
            os.kill(pid, signal.SIGSTOP)
        time.sleep(4.0)
        os.kill(pids[0], signal.SIGCONT)
        time.sleep(1.0)
        assert _state(c, nid) == ["ALIVE"]
        os.kill(pids[1], signal.SIGCONT)
        time.sleep(1.5)
        assert _state(c, nid) == ["ALIVE"]
        # really gone: found once the timeout has passed in earnest
        os.kill(pids[1], signal.SIGSTOP)
        deadline = time.monotonic() + 15.0
        while _state(c, nid) != ["DEAD"] and time.monotonic() < deadline:
            time.sleep(0.25)
        assert _state(c, nid) == ["DEAD"]
    finally:
        for pid in pids:
            try:
                os.kill(pid, signal.SIGCONT)
            except ProcessLookupError:
                pass


# -- a late wake, in every process's words -----------------------------------


@pytest.mark.parametrize("late_s, said", [
    (0.0, False), (common.STOOD_STILL_S, False),    # a loop's own jitter
    (common.STOOD_STILL_S + 0.25, True), (8.5, True),
])
def test_a_late_wake_is_said_once_in_the_same_words(caplog, late_s, said):
    common.STALLS.clear()
    log = logging.getLogger("test.stood_still")
    with caplog.at_level(logging.WARNING, logger=log.name):
        before = time.time()
        common.note_late_wake(log, late_s, "a-loop")
    lines = [r.getMessage() for r in caplog.records]
    if not said:
        assert not lines and not common.STALLS
        return
    (line,), (stall,) = lines, common.stalls()
    assert line.startswith(f"stood still {late_s:.1f} s until ")
    until = float(line.rsplit(" ", 1)[1])
    assert before - 0.001 <= until <= time.time() + 0.001   # (to the ms)
    # the wake was due `late_s` before it came
    assert stall["by"] == "a-loop" and stall["late_s"] == late_s
    assert abs(stall["t_wall"] + late_s - until) < 0.05


def test_a_watched_sleep_sleeps_and_says_nothing_on_time(caplog):
    common.STALLS.clear()
    with caplog.at_level(logging.WARNING):
        t = time.monotonic()
        common.sleep_watched(logging.getLogger("test.stood_still"), 0.05,
                             "a-loop")
    assert time.monotonic() - t >= 0.05
    assert not caplog.records and not common.STALLS


def test_a_busy_loops_stalls_do_not_push_out_another_loops(monkeypatch):
    """A loop keeps its own last 64: the engine's ticker notes short
    wakes by the dozen, and the one freeze the worker's main loop saw
    while its actor reached the chip is still in the snapshot after."""
    from ray_tpu.telemetry import device as devtel

    monkeypatch.setattr(common, "STALLS", {})
    common.note_late_wake(logging.getLogger("test.stood_still"), 6.5,
                          "worker-main")
    for _ in range(100):
        common.note_stall(0.085, "serve-engine-tick")
    kept = devtel.device_snapshot()["stalls"]
    assert [s["late_s"] for s in kept if s["by"] == "worker-main"] == [6.5]
    assert sum(s["by"] == "serve-engine-tick" for s in kept) == 64
    assert [s["t_wall"] for s in kept] == sorted(s["t_wall"] for s in kept)


# -- the ticker beside the serve engine's thread ------------------------------


@pytest.fixture(scope="module")
def engine_model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig.nano(max_seq=64, dtype=jnp.float32)
    return gpt, cfg, gpt.init(jax.random.PRNGKey(0), cfg)


def _engine(engine_model):
    from ray_tpu.serve._engine import ContinuousEngine

    return ContinuousEngine(*engine_model, max_slots=2, page_size=8,
                            prefill_bucket=8)


def _tick(eng, lates):
    """Run the ticker on an injected clock through one wake a lateness:
    each sleep lasts what was asked and `late_s` more."""
    clock = {"t": 100.0}
    done = threading.Event()
    left = list(lates)

    def sleep(s):
        clock["t"] += s + left.pop(0)
        if not left:
            done.set()

    eng._tick(done, clock=lambda: clock["t"], sleep=sleep)
    assert not left


@pytest.mark.parametrize("lates, stalls", [
    ([0.0, 0.0, 0.0], []),                  # wakes on time
    ([0.0, 0.015, 0.0], []),                # late, under the threshold
    ([0.0, 0.25, 0.0], [0.25]),             # one stall of 250 ms
    ([0.125, 0.0, 4.5], [0.125, 4.5]),
])
def test_the_ticker_counts_the_wakes_that_came_late(engine_model, lates,
                                                    stalls):
    eng = _engine(engine_model)
    common.STALLS.clear()
    try:
        before = time.time()
        _tick(eng, lates)
        st = eng.engine_stats()
        assert st["stalls"] == len(stalls)
        assert st["stall_s"] == pytest.approx(sum(stalls), abs=1e-9)
        assert st["stall_max_s"] == pytest.approx(max(stalls, default=0.0),
                                                  abs=1e-9)
        noted = list(common.STALLS.get("serve-engine-tick", ()))
        assert [s["late_s"] for s in noted] == pytest.approx(stalls)
        assert all(s["by"] == "serve-engine-tick"
                   and before - s["late_s"] <= s["t_wall"] <= time.time()
                   for s in noted)
        assert len(common.stalls()) == len(stalls)
    finally:
        eng.stop()


def test_a_ring_record_carries_the_stall_since_the_one_before(engine_model):
    eng = _engine(engine_model)
    ended = threading.Thread(target=lambda: None)
    ended.start()
    ended.join()
    eng._thread = ended                 # the test's iterations, no thread
    try:
        seq = eng.submit([3, 14, 15, 92, 6, 5], max_new_tokens=6)
        eng._iteration()
        eng._iteration()
        assert [r["stall_s"] for r in eng.phase_ring()] == [0.0, 0.0]
        _tick(eng, [0.0, 0.25])
        eng._iteration()                # the record made after it ...
        eng._iteration()                # ... and the one after that
        assert [r["stall_s"] for r in eng.phase_ring()] == [
            0.0, 0.0, pytest.approx(0.25), 0.0]
        while eng._busy():
            eng._iteration()
        assert seq.result.done()
        ring = eng.phase_ring()
        assert sum(r["stall_s"] for r in ring) == pytest.approx(
            eng.engine_stats()["stall_s"])
    finally:
        eng.stop()


def test_a_stall_of_an_idle_stretch_is_no_iterations(engine_model):
    """What the ticker counts while the loop idles (a warm-up's compiles
    starving it, a freeze between two requests) stays in the totals and
    is charged to no record: the first iteration after it did not wait."""
    eng = _engine(engine_model)
    try:
        eng.collect(eng.submit([3, 14, 15], max_new_tokens=3), timeout=120)
        deadline = time.monotonic() + 10.0
        while eng._busy() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)                # the loop is in its idle wait
        _tick(eng, [0.0, 0.25])         # ... and the process stands still
        eng.collect(eng.submit([3, 14, 15, 92], max_new_tokens=3),
                    timeout=120)
        while eng._busy() and time.monotonic() < deadline:
            time.sleep(0.01)
        in_records = sum(r["stall_s"] for r in eng.phase_ring())
        assert in_records <= eng.engine_stats()["stall_s"] - 0.25 + 1e-9
    finally:
        eng.stop()


def test_the_ticker_lives_and_ends_with_the_engine_thread(engine_model):
    eng = _engine(engine_model)
    before = time.time()
    try:
        assert eng._ticker is None      # no engine thread, no ticker
        out = eng.collect(eng.submit([3, 14, 15], max_new_tokens=3),
                          timeout=120)
        assert len(out["completion"]) == 3
        ticker = eng._ticker
        assert ticker.is_alive() and ticker.daemon
        assert ticker.name == "serve-engine-tick"
        ready = eng.engine_stats()["ready"]
        # when the thread started, on the wall clock
        assert before <= ready["thread_start_wall"] <= time.time()
    finally:
        eng.stop()
    ticker.join(timeout=2.0)
    assert not ticker.is_alive() and not eng._thread.is_alive()


def test_first_launches_are_dated_on_the_wall_clock(engine_model):
    """`engine_stats()["ready"]`: each program's first launch, between the
    engine thread's start and now — a nested value every consumer of
    `get_metrics()` carries whole."""
    import json

    eng = _engine(engine_model)
    before = time.time()
    try:
        eng.collect(eng.submit([3, 14, 15], max_new_tokens=3), timeout=120)
        first = dict(eng.engine_stats()["ready"]["first_launch_wall"])
        assert {"serve.step", "serve.prefill:8"} <= set(first)
        assert all(before - 0.05 <= t <= time.time() + 0.05
                   for t in first.values())
        assert first["serve.prefill:8"] <= first["serve.step"] + 0.05
        eng.collect(eng.submit([3, 14, 15], max_new_tokens=3), timeout=120)
        ready = eng.engine_stats()["ready"]
        assert ready["first_launch_wall"] == first       # first, not last
        assert ready["thread_start_wall"] <= min(first.values()) + 0.05
        assert json.loads(json.dumps(eng.engine_stats()))["ready"] == ready
    finally:
        eng.stop()


# -- the two 10 s clocks a freeze could still pass ----------------------------


class _Clock:
    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t


def _stalled_engine(engine_model, monkeypatch, clock):
    """An engine with a slot taken and no step coming, probed on an
    injected clock, its ticker's sums written by hand."""
    import types

    from ray_tpu.serve import _engine as eng_mod

    eng = _engine(engine_model)
    ended = threading.Thread(target=lambda: None)
    ended.start()
    ended.join()
    eng._thread = ended                 # the test's iteration, no thread
    eng.submit([3, 14, 15, 92, 6, 5], max_new_tokens=6)
    eng._iteration()                    # admitted: a slot is taken
    eng._thread = None                  # ... and nobody steps
    clock.t = time.monotonic()          # (its programs' compiles ended on it)
    monkeypatch.setattr(eng_mod, "time", types.SimpleNamespace(
        monotonic=clock, perf_counter=time.perf_counter, time=time.time))
    return eng


@pytest.mark.parametrize("stood_s, restarted", [
    (0.0, True),        # wedged while the process ran: a stall, as before
    (12.0, False),      # the process stood still all that time
    (1.5, True),        # ... or only a little of it
])
def test_a_stall_the_ticker_saw_is_not_the_engines(
        engine_model, monkeypatch, stood_s, restarted):
    clock = _Clock(0.0)
    eng = _stalled_engine(engine_model, monkeypatch, clock)
    try:
        assert eng.stall_s == 10.0      # the configured clock, as it was
        assert eng.check_health()
        clock.t += 12.0                 # no step for 12 s ...
        eng._totals["stall_s"] += stood_s       # ... of which it stood still
        if restarted:
            with pytest.raises(RuntimeError, match="engine stalled"):
                eng.check_health()
        else:
            assert eng.check_health()
            clock.t += 10.5             # and then wedged, the process running
            with pytest.raises(RuntimeError, match="engine stalled"):
                eng.check_health()
    finally:
        eng._thread = None
        eng.stop()


def test_the_first_admission_is_bring_up_and_no_stall(engine_model,
                                                      monkeypatch):
    """A slot is taken BEFORE the device state is built and the first
    program launched: on an empty cache that took 11.4 s in one cell.  The
    stall clock counts from the first launch."""
    clock = _Clock(0.0)
    eng = _stalled_engine(engine_model, monkeypatch, clock)
    try:
        launched = dict(eng._first_launch_wall)
        assert launched                 # the helper's iteration launched
        eng._first_launch_wall.clear()  # ... as if it had not yet
        assert eng.check_health()
        clock.t += 12.0
        assert eng.check_health()       # a slot taken, nothing launched
        eng._first_launch_wall.update(launched)
        clock.t += 9.0
        assert eng.check_health()       # 9 s since a probe saw bring-up
        clock.t += 1.5
        with pytest.raises(RuntimeError, match="engine stalled"):
            eng.check_health()
    finally:
        eng.stop()


def test_a_probe_that_runs_before_the_ticker_woke_counts_what_it_is_owed(
        engine_model, monkeypatch):
    """After a freeze every thread is runnable at once: the probe may run
    before the ticker has noted its late wake."""
    clock, tick_clock = _Clock(0.0), _Clock(50.0)
    eng = _stalled_engine(engine_model, monkeypatch, clock)
    try:
        eng._tick_due = (tick_clock, 50.05)     # asleep, due in 50 ms
        assert eng.check_health()
        clock.t += 12.0
        tick_clock.t += 12.0            # its wake is 11.95 s overdue
        assert eng.check_health()
        eng._tick_due = None            # no ticker: nothing is owed
        with pytest.raises(RuntimeError, match="engine stalled"):
            eng.check_health()
    finally:
        eng.stop()


class _Probe:
    """A replica's handle whose `check_health` never answers."""

    def __init__(self):
        self.fired = 0
        self.check_health = self.get_metrics = self
        self.killed = False

    def remote(self):
        self.fired += 1
        return object()


def _prober(monkeypatch, clock):
    """A `ServeController` built by hand around one RUNNING replica — no
    cluster, no thread — on an injected clock; `turn(late_s)` is one turn
    of its loop: the probes polled, then the loop's sleep, `late_s` late."""
    import types

    from ray_tpu.serve import _controller as sc
    from ray_tpu._private.config import cfg

    fake = types.SimpleNamespace(
        time=clock, monotonic=clock,
        sleep=lambda s: setattr(clock, "t", clock.t + s + late["s"]))
    monkeypatch.setattr(sc, "time", fake)
    monkeypatch.setattr(common, "time", fake)   # (the loop's watched sleep)
    monkeypatch.setattr(sc, "ray_tpu", types.SimpleNamespace(
        wait=lambda refs, **kw: ([], refs),     # nothing ever answers
        get=lambda ref: None, kill=lambda h: setattr(h, "killed", True)))
    late = {"s": 0.0}
    ctl_ = sc.ServeController.__new__(sc.ServeController)
    ctl_._apps, ctl_._lock = {}, threading.RLock()
    ctl_._replica_version = 0
    ctl_._health_period = cfg().serve_health_check_period_s
    ctl_._health_timeout = cfg().serve_health_check_timeout_s
    ds = sc._DeploymentState("app", {"name": "d", "num_replicas": 1})
    r = sc._ReplicaState("app#d#0", _Probe())
    r.state = sc.RUNNING
    ds.replicas[r.replica_id] = r
    ctl_._apps["app"] = {"deployments": {"d": ds}}

    def turn(late_s=0.0):
        ctl_._poll_replica_futures(ds)
        late["s"] = late_s
        ctl_._credit_stall(common.sleep_watched(
            sc.logger, sc.RECONCILE_PERIOD_S, "serve-controller"))

    return ctl_, ds, r, turn


def test_a_frozen_prober_restarts_no_replica(monkeypatch, caplog):
    from ray_tpu.serve import _controller as sc

    clock = _Clock(7000.0)
    ctl_, ds, r, turn = _prober(monkeypatch, clock)
    assert ctl_._health_timeout == 10.0         # the clock, as it was
    while r.health_ref is None:                 # until a probe is out
        turn()
    fired_at = clock.t
    with caplog.at_level(logging.WARNING, logger=sc.logger.name):
        turn(late_s=12.0)                       # the prober's process froze
    assert clock.t - fired_at > ctl_._health_timeout
    turn()
    assert r.replica_id in ds.replicas and not r.handle.killed
    said = [m for m in (x.getMessage() for x in caplog.records)
            if m.startswith("stood still 12.0 s until ")]
    assert len(said) == 1
    # the probe's own clock still runs: unanswered for the timeout of a
    # RUNNING prober, the replica is restarted as before
    t0 = clock.t
    while r.replica_id in ds.replicas and clock.t - t0 < 30.0:
        turn()
    assert r.replica_id not in ds.replicas
    assert 10.0 - 2.5 <= clock.t - t0 <= 10.0 + 0.5
    assert "health check timed out" in ds.message


def test_a_replica_silent_to_a_running_prober_is_restarted(monkeypatch):
    clock = _Clock(7000.0)
    ctl_, ds, r, turn = _prober(monkeypatch, clock)
    while r.health_ref is None:
        turn()
    fired_at = clock.t
    while r.replica_id in ds.replicas and clock.t - fired_at < 30.0:
        turn(late_s=0.1)                        # a loop's own jitter
    assert r.replica_id not in ds.replicas
    assert 10.0 < clock.t - fired_at <= 12.0
    assert "health check timed out" in ds.message
