"""State API + task events + timeline (reference: python/ray/util/state)."""

import json
import time

import pytest

import ray_tpu
from ray_tpu.util import state


@pytest.fixture
def cluster():
    # reuse a live (session-fixture) cluster; only own/tear down one we
    # started ourselves — shutting down the shared cluster would break
    # every later test in the run
    owned = not ray_tpu.is_initialized()
    if owned:
        ray_tpu.init(num_cpus=4)
    yield
    if owned:
        ray_tpu.shutdown()


def _flush():
    from ray_tpu._private.api import current_core

    current_core().task_events.flush()


def _wait_for(pred, timeout=5.0):
    """Worker-side event buffers flush on a 1 s cadence; poll until
    visible instead of a fixed sleep."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(0.1)
    raise AssertionError(
        f"condition not met within timeout; "
        f"tasks={state.list_tasks(limit=50)}")


def test_list_nodes_and_workers(cluster):
    nodes = state.list_nodes()
    assert len(nodes) == 1
    assert nodes[0]["state"] == "ALIVE"
    assert "CPU" in nodes[0]["total"]
    # workers may still be prestarting; just check shape
    workers = state.list_workers()
    for w in workers:
        assert "worker_id" in w and "state" in w


def test_task_events_and_summary(cluster):
    @ray_tpu.remote
    def marked_task(x):
        return x + 1

    ray_tpu.get([marked_task.remote(i) for i in range(5)])
    _flush()

    def all_finished():
        # task names are qualnames (locals-scoped under pytest)
        ts = [t for t in state.list_tasks()
              if t.get("name", "").endswith("marked_task")]
        done = [t for t in ts if t["state"] == "FINISHED"]
        return done if len(done) == 5 else None

    finished = _wait_for(all_finished)
    # lifecycle timestamps present and ordered
    ts = finished[0]["state_ts"]
    assert ts["PENDING_ARGS_AVAIL"] <= ts["FINISHED"]

    s = state.summarize_tasks()
    by_name = {k: v for k, v in s["summary"].items()
               if k.endswith("marked_task")}
    assert sum(v.get("FINISHED", 0) for v in by_name.values()) == 5


def test_failed_task_recorded(cluster):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("nope")

    with pytest.raises(ray_tpu.TaskError):
        ray_tpu.get(boom.remote())
    _flush()
    # match this test's qualname exactly: other tests also name a task
    # "boom" and the shared session cluster retains their records
    tasks = _wait_for(lambda: [
        t for t in state.list_tasks()
        if t.get("name", "").endswith(
            "test_failed_task_recorded.<locals>.boom")
        and t["state"] == "FAILED"] or None)
    assert "nope" in tasks[0].get("error", "")


def test_list_actors_and_summary(cluster):
    @ray_tpu.remote
    class Counter:
        def bump(self):
            return 1

    c = Counter.remote()
    ray_tpu.get(c.bump.remote())
    # robust to leftover actors from other tests on a shared cluster
    mine = [a for a in state.list_actors(filters={"state": "ALIVE"})
            if "Counter" in (a.get("class_name") or "")]
    assert len(mine) == 1
    s = state.summarize_actors()
    assert s["total"] >= 1


def test_timeline_export(cluster, tmp_path):
    @ray_tpu.remote
    def traced():
        with ray_tpu.profile("inner_span"):
            time.sleep(0.01)
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)])
    _flush()
    out = tmp_path / "trace.json"

    def exported():
        ray_tpu.timeline(str(out))
        events = json.loads(out.read_text())
        names = {e["name"] for e in events}
        if any(n.endswith("traced") for n in names) and "inner_span" in names:
            return events

    events = _wait_for(exported)
    for e in events:
        assert e["ph"] == "X" and e["dur"] > 0


def test_state_api_with_explicit_address(cluster):
    addr = ray_tpu.connection_info()["control_address"]
    nodes = state.list_nodes(address=addr)
    assert len(nodes) == 1


def test_summarize_objects(cluster):
    import numpy as np

    ref = ray_tpu.put(np.zeros(1 << 20, np.uint8))
    s = state.summarize_objects()
    assert s["total_bytes"] >= (1 << 20)
    del ref


def test_list_and_get_logs(cluster):
    """Log listing + tail through the state API (reference: `ray logs`)."""
    @ray_tpu.remote
    def noisy():
        print("hello-from-noisy-task")
        return 1

    assert ray_tpu.get(noisy.remote(), timeout=60) == 1
    deadline = time.time() + 30
    found = None
    while time.time() < deadline and not found:
        logs = state.list_logs()
        for nid, entries in logs.items():
            workers = [e for e in entries
                       if e["name"].startswith("worker-")]
            if workers:
                found = (nid, workers)
                break
        time.sleep(0.5)
    assert found, f"no worker logs listed: {logs}"
    nid, workers = found
    # the print landed in some worker's log
    deadline = time.time() + 30
    while time.time() < deadline:
        texts = [t for e in workers
                 for t in [state.get_log(e["name"]).get(nid)] if t]
        if any("hello-from-noisy-task" in t for t in texts):
            break
        time.sleep(0.5)
        logs = state.list_logs()
        workers = [e for e in logs.get(nid, [])
                   if e["name"].startswith("worker-")]
    assert any("hello-from-noisy-task" in t for t in texts)
