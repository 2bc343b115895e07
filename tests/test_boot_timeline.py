"""How a process came up, and when it stood still, in its own words
(`_private/common.BOOT` / `STALLS`; PR 59).

`setup_s` is an end-to-end metric of every cell and had no layer under
it: what was known of a worker's start was pieced together from the
benchmark's drivers' stamps around the calls they make.  A worker now
dates the parts of its own boot on the wall clock, from the raylet's
stamp of the spawn to its actor's constructor returned, and every loop
that sleeps says when it woke late.
"""

import logging
import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu._private import common
from ray_tpu._private.api import current_core
from ray_tpu.telemetry import device as devtel

# (`pool`: only a worker the raylet had prestarted has it — which of the
# two an actor gets is the raylet's choice of the moment)
ALL_PARTS = ("start", "connect", "register", "pool", "actor_wait",
             "actor_init")


INIT_S = 0.2


def _parts(boot):
    return tuple(p for p in ALL_PARTS if f"{p}_s" in boot)


@ray_tpu.remote
class Booted:
    def __init__(self):
        time.sleep(INIT_S)                  # a constructor that takes time

    def ship(self):
        """Note a stall and ship this process's device snapshot."""
        from ray_tpu._private import common
        from ray_tpu.telemetry import device as devtel

        common.note_stall(0.01, "a-test")
        return (devtel.flush_device_snapshot(force=True)
                and os.environ["RAY_TPU_WORKER_ID"])

    def told(self):
        from ray_tpu._private import common
        from ray_tpu.telemetry import device as devtel

        return {"boot": dict(common.BOOT), "returned": time.time(),
                "snapshot": devtel.device_snapshot(), "pid": os.getpid(),
                "stalls": common.stalls(),
                "log": os.path.join(
                    os.environ["RAY_TPU_SESSION_DIR"], "logs",
                    f"worker-{os.environ['RAY_TPU_WORKER_ID'][:12]}.log")}


def _check_boot(told, asked):
    """A worker's parts are non-negative, in wall order, each beginning
    where the one before it ended, and sum to spawn -> constructor
    returned -> its parts."""
    boot = told["boot"]
    parts = _parts(boot)
    assert set(ALL_PARTS) - set(parts) <= {"pool"}
    for part in parts:
        assert boot[f"{part}_s"] >= 0.0, (part, boot)
    walls = [boot[f"{p}_wall"] for p in parts]
    assert walls == sorted(walls)
    # (the worker may have been spawned before the driver asked: then it
    # waited in the pool, and `pool_s` says how long) — the constructor
    # was called after the driver asked and done before a method answered
    assert asked <= boot["actor_init_wall"]
    for p, nxt in zip(parts, parts[1:]):
        assert abs(boot[f"{p}_wall"] + boot[f"{p}_s"]
                   - boot[f"{nxt}_wall"]) < 1e-6, (p, boot)
    assert boot["until_wall"] <= told["returned"]
    total = sum(boot[f"{p}_s"] for p in parts)
    assert abs(total - (boot["until_wall"] - boot["start_wall"])) < 0.05
    assert boot["actor_init_s"] >= INIT_S
    # the interpreter and its imports are not free: a start that read 0
    # would mean the raylet's stamp never reached the worker
    assert boot["start_s"] > 0.0
    # it ships wherever the ledger ships
    assert told["snapshot"]["boot"] == boot
    assert told["snapshot"]["stalls"] == told["stalls"]
    return parts


def test_a_spawned_workers_boot_parts_chain_from_spawn_to_constructor(
        private_cluster_slot, monkeypatch):
    """No prestarted worker to take: the raylet spawns this actor's
    process, whose boot has no `pool`."""
    monkeypatch.setenv("RAY_TPU_WORKER_PRESTART", "0")
    ray_tpu.init(num_cpus=2)
    asked = time.time()
    a = Booted.remote()
    told = ray_tpu.get(a.told.remote(), timeout=60)
    assert _check_boot(told, asked) == tuple(
        p for p in ALL_PARTS if p != "pool")
    # spawned for this actor, so the whole chain lies after the asking
    assert asked <= told["boot"]["start_wall"]
    with open(told["log"]) as f:
        (line,) = [ln for ln in f if "worker boot: start " in ln]
    assert line.rstrip().endswith(f"s from {told['boot']['start_wall']:.3f}")
    ray_tpu.kill(a)


def test_a_pooled_workers_boot_has_the_time_it_idled(ray_cluster):
    """A prestarted worker the raylet turns into an actor's (the path a
    TPU replica takes): `pool` runs from its registration to the
    assignment, and the chain still sums.  Which of the two an actor gets
    is the raylet's choice of the moment: ask until one came from the
    pool (it refills within a second)."""
    for _ in range(20):
        asked = time.time()
        a = Booted.remote()
        told = ray_tpu.get(a.told.remote(), timeout=60)
        parts = _check_boot(told, asked)
        ray_tpu.kill(a)
        if "pool" in parts:
            break
        time.sleep(0.5)
    assert parts == ALL_PARTS
    boot = told["boot"]
    # it idled until the driver asked: the assignment came after
    assert boot["pool_wall"] + boot["pool_s"] >= asked - 0.05


def test_a_shipped_snapshot_draws_the_boot_ahead_of_the_compiles(
        ray_cluster):
    """What a worker ships of its start is drawn by
    `compile_trace_events` on the worker's row, in wall order."""
    from ray_tpu.util.state import api as state

    a = Booted.remote()
    wid = ray_tpu.get(a.ship.remote(), timeout=60)
    assert wid
    try:
        snap = state.device_stats()["workers"][wid]
        assert snap["boot"]["actor_init_s"] >= INIT_S
        assert [s["by"] for s in snap["stalls"]] == ["a-test"]
        slices = [e for e in devtel.compile_trace_events({wid: snap})
                  if e["ph"] == "X"]
        names = [e["name"] for e in slices]
        boot = [n[len("boot "):] for n in names if n.startswith("boot ")]
        assert boot == [p for p in ALL_PARTS if p in boot]
        assert set(ALL_PARTS) - set(boot) <= {"pool"}
        assert names.index("boot actor_init") < names.index("stood still")
        assert [e["ts"] for e in slices] == sorted(e["ts"] for e in slices)
    finally:
        ray_tpu.kill(a)
        # the cluster is shared: leave it the snapshots it had
        current_core().control.call("kv_del", {
            "ns": devtel.DEVICE_NS,
            "key": f"{devtel.DEVICE_KEY_PREFIX}{wid}"})


@pytest.mark.parametrize("boot", [
    {},                                             # nothing was stamped
    {"actor_init_s": 1.5},                          # a part alone
    {"start_s": "soon", "start_wall": None},        # ... or unreadable
])
def test_a_boot_with_parts_missing_logs_and_does_not_raise(
        monkeypatch, caplog, boot):
    monkeypatch.setattr(common, "BOOT", dict(boot))
    log = logging.getLogger("test.boot")
    with caplog.at_level(logging.INFO, logger=log.name):
        common.log_boot(log)                        # never raises
        common.boot_part("actor_init")              # nor does a stamp
    lines = [r.getMessage() for r in caplog.records]
    if "start_s" not in boot:
        (line,) = lines
        assert line.startswith("worker boot: start 0.00 connect 0.00 ")
        assert f"init {boot.get('actor_init_s', 0.0):.2f} s from" in line
    assert common.BOOT["actor_init_s"] >= 0.0


@pytest.mark.parametrize("value, wall", [
    (None, None), ("", None), ("not a number", None), ("0", None),
    ("1790000000.25", 1790000000.25),
])
def test_the_spawn_stamp_is_read_or_left_out(monkeypatch, value, wall):
    if value is None:
        monkeypatch.delenv("RAY_TPU_SPAWN_WALL", raising=False)
    else:
        monkeypatch.setenv("RAY_TPU_SPAWN_WALL", value)
    assert common.spawn_wall() == wall


def test_the_driver_dates_its_cluster_start(ray_cluster):
    boot = common.BOOT
    assert boot["cluster_start_s"] > 0.0
    assert boot["cluster_start_wall"] + boot["cluster_start_s"] \
        <= time.time()


def test_a_worker_that_stood_still_says_so(ray_cluster):
    """A worker stopped for longer than `STOOD_STILL_S`: the loop its main
    thread sleeps in notes the late wake — one entry of `STALLS`, one
    line of its log in the words every process uses."""
    a = Booted.remote()
    told = ray_tpu.get(a.told.remote(), timeout=60)
    assert not [s for s in told["stalls"] if s["by"] == "worker-main"]
    stop_s = common.STOOD_STILL_S + 0.5 + 0.4     # + one sleep of the loop
    t_stop = time.time()
    os.kill(told["pid"], signal.SIGSTOP)
    try:
        time.sleep(stop_s)
    finally:
        os.kill(told["pid"], signal.SIGCONT)
    t_cont = time.time()
    deadline = time.monotonic() + 10.0
    while True:
        stalls = [s for s in ray_tpu.get(a.told.remote(),
                                         timeout=60)["stalls"]
                  if s["by"] == "worker-main"]
        if stalls or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    (stall,) = stalls
    # late by the stop, less what was left of the sleep it fell into
    assert common.STOOD_STILL_S < stall["late_s"] <= t_cont - t_stop + 0.6
    assert t_stop - 0.6 <= stall["t_wall"] <= t_cont
    with open(told["log"]) as f:
        lines = [ln for ln in f if "stood still" in ln]
    assert len(lines) == 1
    assert f"stood still {stall['late_s']:.1f} s until " in lines[0]
    ray_tpu.kill(a)
