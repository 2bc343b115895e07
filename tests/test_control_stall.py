"""The control plane's failure detector does not count time it was deaf
itself (`ControlServer._credit_stall`, called by `_health_loop`).

On a TPU VM every process of the machine stands still for 4-9 s when a
worker first reaches the chip (PERF.md, PR 53): when that passed the
death timeout, the health loop woke first, read ten seconds of silence
it could not have heard anything in, and declared a healthy node dead
(`ActorDiedError: node ... died` out of `serve.run`).
"""

import os
import signal
import time

import pytest

from ray_tpu._private import control as ctl
from ray_tpu._private.protocol import Client


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
