"""Tier-1 tests for the control-plane flight recorder.

Protocol-level: per-handler queue-wait/handle-time histograms under
concurrent load, the event-loop lag probe under an injected stall, the
per-handler budget warning counter and client-side retry counters.
Control-level: KV namespace accounting, pubsub publish->deliver fan-out
across several subscribers, the task-event relay envelope, the
``control_stats`` RPC shape, and the state-API / CLI surfaces.
Swarm: a 50-virtual-node run against a real control daemon.
"""

import json
import threading
import time

import pytest

from ray_tpu._private import rpc_stats
from ray_tpu._private.protocol import Client, ResilientClient, Server

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def control_addr():
    from ray_tpu._private.bootstrap import Cluster

    c = Cluster()
    addr = c.start_control()
    yield addr
    c.shutdown()


def _server(handlers):
    s = Server(name="t-flight")
    for name, fn in handlers.items():
        s.handle(name, fn)
    s.start()
    return s


# -- protocol layer ----------------------------------------------------------

def test_per_handler_histograms_under_concurrency():
    s = _server({"echo": lambda c, p: p,
                 "slow": lambda c, p: (time.sleep(0.003), p)[1]})
    clients = [Client(s.addr, name=f"t{i}") for i in range(4)]
    try:
        def worker(cli):
            for i in range(25):
                assert cli.call("echo", {"i": i, "pad": "x" * 64},
                                timeout=10.0) == {"i": i, "pad": "x" * 64}
            cli.call("slow", None, timeout=10.0)

        ts = [threading.Thread(target=worker, args=(c,)) for c in clients]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        st = s.stats()
        echo = st["echo"]
        assert echo["count"] == 100 and echo["errors"] == 0
        assert echo["in_flight"] == 0
        assert echo["bytes_in"] > 0 and echo["bytes_out"] > 0
        # legacy surface kept for pre-flight-recorder consumers
        assert {"count", "total_s", "mean_us", "max_us"} <= set(echo)
        for hist_key in ("queue_ms", "handle_ms"):
            h = echo[hist_key]
            assert h["count"] == 100
            assert sum(h["buckets"]) == 100
            assert h["p50_ms"] <= h["p99_ms"] <= max(h["max_ms"], h["p99_ms"])
        # the slow handler's handle-time is visibly larger than echo's
        assert st["slow"]["handle_ms"]["max_ms"] >= 3.0
        # every registered handler appears, zeros included
        assert st["rpc_stats"]["count"] == 0
    finally:
        for c in clients:
            c.close()
        s.stop()


def test_loop_lag_probe_under_stall():
    s = _server({"stall": lambda c, p: time.sleep(0.1)})
    cli = Client(s.addr, name="t-lag")
    try:
        cli.call("stall", None, timeout=10.0)
        time.sleep(0.1)     # let the loop observe the missed ticks
        lag = s.loop_stats()["lag_ms"]
        # a 100ms handler stall on a 20ms tick shows >= ~80ms of lag
        assert lag["count"] >= 1
        assert lag["max_ms"] >= 80.0
    finally:
        cli.close()
        s.stop()


def test_budget_exceeded_counter():
    # "ping" carries a 5ms budget in HANDLER_BUDGETS_MS; a 25ms handler
    # must count an over-budget completion
    assert rpc_stats.budget_ms("ping") == 5.0
    s = _server({"ping": lambda c, p: time.sleep(0.025)})
    cli = Client(s.addr, name="t-budget")
    try:
        cli.call("ping", None, timeout=10.0)
        # the server records a completion after it has sent the reply
        deadline = time.monotonic() + 5.0
        while (s.stats()["ping"]["budget_exceeded"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        st = s.stats()["ping"]
        assert st["budget_ms"] == 5.0
        assert st["budget_exceeded"] == 1
    finally:
        cli.close()
        s.stop()


def test_resilient_client_retry_counters():
    s = _server({"ping": lambda c, p: {"ok": True}})
    rc = ResilientClient(s.addr, name="t-rc")
    try:
        for _ in range(3):
            rc.call("ping", {}, timeout=10.0)
        cs = rc.client_stats()
        m = cs["methods"]["ping"]
        assert m["attempts"] == 3 and m["calls"] == 3
        assert m["retries"] == 0 and cs["reconnects"] <= 1
    finally:
        rc.close()
        s.stop()


# -- control plane -----------------------------------------------------------

def test_control_stats_shape_and_kv_accounting(control_addr):
    cli = Client(control_addr, name="t-cs")
    try:
        cli.call("kv_put", {"ns": "serve", "key": "k", "val": b"x" * 100,
                            "overwrite": True}, timeout=10.0)
        assert cli.call("kv_get", {"ns": "serve", "key": "k"},
                        timeout=10.0) == b"x" * 100
        cs = cli.call("control_stats", {}, timeout=10.0)
        assert {"uptime_s", "handlers", "loop", "kv", "pubsub",
                "events", "nodes"} <= set(cs)
        kv = cs["kv"]["serve"]
        assert kv["ops"] >= 2
        assert kv["bytes_in"] >= 100 and kv["bytes_out"] >= 100
        h = cs["handlers"]["kv_put"]
        assert h["count"] >= 1
        assert h["queue_ms"]["count"] >= 1
        assert h["handle_ms"]["count"] >= 1
        assert h["budget_ms"] == rpc_stats.budget_ms("kv_put")
        assert cs["loop"]["tick_s"] > 0
    finally:
        cli.close()


def test_pubsub_fanout_three_subscribers(control_addr):
    subs = [Client(control_addr, name=f"t-sub{i}") for i in range(3)]
    pub = Client(control_addr, name="t-pub")
    try:
        for c in subs:
            c.call("subscribe", {"topics": ["flight"]}, timeout=10.0)
        rpc_stats.pubsub_delivery_snapshot(reset=True)
        pub.call("publish", {"topic": "flight",
                             "payload": {"n": 1}}, timeout=10.0)
        deadline = time.monotonic() + 10.0
        snap = {}
        while time.monotonic() < deadline:
            snap = rpc_stats.pubsub_delivery_snapshot().get("flight", {})
            if snap.get("count", 0) >= 3:
                break
            time.sleep(0.02)
        # every subscribing client measured the wire-stamped latency
        assert snap["count"] == 3
        assert snap["max_ms"] >= 0.0
        cs = pub.call("control_stats", {}, timeout=10.0)
        ps = cs["pubsub"]["flight"]
        assert ps["publishes"] >= 1
        assert ps["deliveries"] >= 3
        assert ps["bytes_out"] > 0
        assert cs["subscriptions"]["flight"] >= 3
    finally:
        for c in subs:
            c.close()
        pub.close()


def test_task_event_relay_envelope(control_addr):
    cli = Client(control_addr, name="t-relay")
    try:
        batch = {"events": [{"kind": "status", "task_id": "t1",
                             "state": "RUNNING", "ts": time.time()}],
                 "dropped": 0, "common": {"node_id": "fake"}}
        cli.notify("report_task_events",
                   {"batches": [batch, batch], "dropped": 1,
                    "node_id": "fake"})
        deadline = time.monotonic() + 10.0
        ev = {}
        while time.monotonic() < deadline:
            ev = cli.call("control_stats", {},
                          timeout=10.0).get("events", {})
            if ev.get("relay_batches", 0) >= 1:
                break
            time.sleep(0.05)
        assert ev["relay_batches"] >= 1
        assert ev["relay_dropped"] >= 1
    finally:
        cli.close()


# -- surfaces ----------------------------------------------------------------

def test_state_api_control_stats(control_addr):
    from ray_tpu.util.state import api as state

    addr = f"{control_addr[0]}:{control_addr[1]}"
    snap = state.control_stats(address=addr)
    assert "control" in snap and "handlers" in snap["control"]
    # every control handler reports a row, zeros included
    assert "state_dump" in snap["control"]["handlers"]


def test_cli_control_stats(control_addr, capsys):
    from ray_tpu.scripts.cli import main

    addr = f"{control_addr[0]}:{control_addr[1]}"
    main(["control-stats", "--address", addr, "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert "control" in out and "loop" in out["control"]
    # text rendering smoke: table + loop/kv/events sections
    main(["control-stats", "--address", addr])
    text = capsys.readouterr().out
    assert "control plane" in text
    assert "loop:" in text
    assert "task events:" in text


def test_control_metrics_synthesis(control_addr):
    from ray_tpu.util.metrics import control_stats_metrics, prometheus_text

    cli = Client(control_addr, name="t-metrics")
    try:
        cli.call("kv_put", {"ns": "_metrics", "key": "m", "val": b"v",
                            "overwrite": True}, timeout=10.0)
        mets = control_stats_metrics(cli.call("control_stats", {},
                                              timeout=10.0))
    finally:
        cli.close()
    names = {m["name"] for m in mets}
    assert "ray_tpu_control_rpc_total" in names
    assert "ray_tpu_control_rpc_handle_ms" in names
    assert "ray_tpu_control_kv_ops_total" in names
    text = prometheus_text(mets)
    assert "ray_tpu_control_rpc_total{" in text
    assert 'ray_tpu_control_rpc_handle_ms_bucket{' in text


# -- swarm -------------------------------------------------------------------

def test_swarm_fifty_nodes_quick():
    from ray_tpu._private.swarm import run_swarm_bench

    row = run_swarm_bench(50, hb_interval_s=0.25, settle_s=0.4,
                          lease_secs=1.5, pub_msgs=5)
    assert row["n_nodes"] == 50
    assert row["heartbeat_count"] >= 50
    assert row["heartbeat_errors"] == 0
    assert row["heartbeat_ms_p99"] > 0
    assert row["lease_grants"] > 0
    assert row["pubsub_delivered"] == row["pubsub_expected"] == 250
    assert row["handler_p99_ms"].get("heartbeat", 0) > 0
