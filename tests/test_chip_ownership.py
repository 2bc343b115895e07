"""One process for each chip: what the raylet gives a worker to see.

No chip is needed (or touched): the workers only report their
environment.  The host's chips are faked with RAY_TPU_NUM_CHIPS.
"""

import os
import time

import pytest

import ray_tpu


def _env_report():
    return {k: os.environ.get(k) for k in
            ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
             "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")} | {
        "pid": os.getpid()}


@ray_tpu.remote
class Probe:
    def env(self):
        return _env_report()


def _gone(pid: int, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with open(f"/proc/{pid}/stat") as f:
                # exited but not yet reaped by the raylet counts as gone:
                # a zombie holds no chip
                if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                    return True
        except FileNotFoundError:
            return True
        time.sleep(0.05)
    return False


def _wait_pooled(n_chips: int, timeout_s: float = 15.0):
    """Until the driver has returned its idle lease and a worker holding
    `n_chips` chips sits in the raylet's pool; returns that worker."""
    from ray_tpu.util.state import list_workers

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for w in list_workers():
            if len(w["chips"]) == n_chips and w["state"] == "idle":
                return w
        time.sleep(0.1)
    raise AssertionError(
        f"no idle worker holding {n_chips} chips: "
        f"{[(w['state'], w['chips']) for w in list_workers()]}")


@pytest.fixture
def four_chip_host(monkeypatch, private_cluster_slot):
    """A cluster whose raylet believes in four chips and whose own
    environment says NOTHING about the platform — the pin, where there
    is one, is the raylet's doing."""
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
              "TPU_HOST_BOUNDS"):
        monkeypatch.delenv(k, raising=False)
    ray_tpu.init(num_cpus=4)
    yield


def test_leaseless_worker_is_pinned_to_cpu(four_chip_host):
    """No TPU lease -> JAX_PLATFORMS=cpu, unconditionally: the first jnp
    call in an ordinary task must not open the chip and lock out the
    worker that holds the lease."""
    task = ray_tpu.remote(_env_report)
    got = ray_tpu.get(task.remote(), timeout=60)
    assert got["JAX_PLATFORMS"] == "cpu"
    assert got["TPU_VISIBLE_CHIPS"] is None
    actor = Probe.remote()
    assert ray_tpu.get(actor.env.remote(), timeout=60)["JAX_PLATFORMS"] \
        == "cpu"


def test_tpu_leases_get_disjoint_chips(four_chip_host):
    """Two TPU:1 actors on a four-chip host see one chip each, not the
    same one, with the sub-host bounds libtpu needs (established on a
    v5e 2x2: without the bounds every process but one dies on libtpu's
    lock); a TPU worker's platform is the host's, never a cpu pin."""
    a = Probe.options(resources={"TPU": 1}).remote()
    b = Probe.options(resources={"TPU": 1}).remote()
    ea, eb = ray_tpu.get([a.env.remote(), b.env.remote()], timeout=60)
    assert {ea["TPU_VISIBLE_CHIPS"], eb["TPU_VISIBLE_CHIPS"]} == {"0", "1"}
    for e in (ea, eb):
        assert e["JAX_PLATFORMS"] is None
        assert e["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
        assert e["TPU_HOST_BOUNDS"] == "1,1,1"
    # a two-chip lease takes the two that are left, as a 1x2 slice
    c = Probe.options(resources={"TPU": 2}).remote()
    ec = ray_tpu.get(c.env.remote(), timeout=60)
    assert ec["TPU_VISIBLE_CHIPS"] == "2,3"
    assert ec["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"


def test_whole_host_lease_keeps_host_bounds(four_chip_host):
    w = Probe.options(resources={"TPU": 4}).remote()
    e = ray_tpu.get(w.env.remote(), timeout=60)
    assert e["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert e["TPU_CHIPS_PER_HOST_BOUNDS"] is None


def test_pooled_tpu_worker_is_reused_or_retired_first(four_chip_host):
    """A pooled worker that has opened a chip keeps it until it exits.
    The next lease of the same width gets THAT process; a wider one gets
    a new process only after the pooled one is gone."""
    task = ray_tpu.remote(_env_report).options(resources={"TPU": 2})
    first = ray_tpu.get(task.remote(), timeout=60)
    assert first["TPU_VISIBLE_CHIPS"] == "0,1"
    again = ray_tpu.get(task.remote(), timeout=60)
    assert again["pid"] == first["pid"]
    assert _wait_pooled(2)["pid"] == first["pid"]
    # an actor of the same width adopts the pooled process, chips and all
    a = Probe.options(resources={"TPU": 2}).remote()
    ea = ray_tpu.get(a.env.remote(), timeout=60)
    assert (ea["pid"], ea["TPU_VISIBLE_CHIPS"]) == (first["pid"], "0,1")
    ray_tpu.kill(a)
    # pooled again — in a new process, on whichever two chips no live
    # process holds — then asked for more than is free: the whole host
    pooled = ray_tpu.get(task.remote(), timeout=60)
    assert pooled["pid"] != first["pid"]
    _wait_pooled(2)
    wide = Probe.options(resources={"TPU": 4}).remote()
    ew = ray_tpu.get(wide.env.remote(), timeout=60)
    assert ew["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert ew["pid"] not in (first["pid"], pooled["pid"])
    # the claim waited for every earlier holder to be gone
    assert _gone(first["pid"], 0.5) and _gone(pooled["pid"], 0.5)


def test_unopenable_slice_is_refused_with_the_reason():
    """Three chips of a 2x2 host is no slice libtpu can open: the raylet
    refuses the spawn (the lessee logs the reason with each retry)
    rather than start a process that would hang on the missing chip."""
    from ray_tpu._private.accelerators import visible_chip_env

    with pytest.raises(ValueError, match="chips"):
        visible_chip_env((0, 1, 2), 4)
    assert visible_chip_env((3,), 4) == {
        "TPU_VISIBLE_CHIPS": "3", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1"}
    assert visible_chip_env((0,), 1) == {"TPU_VISIBLE_CHIPS": "0"}
