"""ray_tpu: a TPU-native distributed compute framework.

Capability-parity redesign of the reference (Ray v2.38-class: tasks, actors,
objects, placement groups, collectives, Data/Train/Tune/Serve) built
TPU-first: device objects are jax.Arrays, collectives compile to XLA ICI
operations via shard_map/pjit, the scheduler is TPU-pod-topology aware, and
DP/FSDP/TP/PP/EP/SP parallelism is first-class.

Public core API mirrors the reference's (reference:
python/ray/_private/worker.py — init :1260, get :2617, put :2785,
wait :2850, remote :3239).
"""

from __future__ import annotations

import atexit
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ._private import common as _common
from ._private.api import (ActorClass, ActorHandle, RemoteFunction, get_actor,
                           kill, remote)
from ._private.common import (ActorDiedError, GetTimeoutError, ObjectLostError,
                              RayTpuError, TaskCancelledError, TaskError,
                              WorkerCrashedError)
from ._private.core import CoreWorker, ObjectRef, ObjectRefGenerator

__version__ = "0.1.0"

logger = logging.getLogger(__name__)

_lock = threading.RLock()
_core: Optional[CoreWorker] = None
_owned_cluster = None


def is_initialized() -> bool:
    return _core is not None


def init(address: Optional[str] = None, *, num_cpus: Optional[float] = None,
         num_tpus: Optional[float] = None,
         resources: Optional[Dict[str, float]] = None,
         namespace: Optional[str] = None,
         ignore_reinit_error: bool = False,
         log_to_driver: bool = True,
         _tracing_startup_hook: Optional[str] = None,
         _tracing_config: Optional[Dict[str, Any]] = None,
         _system_config: Optional[Dict[str, Any]] = None,
         logging_level: int = logging.INFO) -> Dict[str, Any]:
    """Start (or connect to) a ray_tpu cluster and connect this driver.

    With no address, boots a local single-node cluster: a control-plane
    process and one raylet (reference: ray.init starting a head node,
    worker.py:1260).
    """
    global _core, _owned_cluster
    t_entered = time.time()
    with _lock:
        if _core is not None:
            if ignore_reinit_error:
                return connection_info()
            raise RuntimeError("ray_tpu.init() called twice; use "
                               "ignore_reinit_error=True to allow")
        if _system_config:
            # typed flag overrides, inherited by every daemon this init
            # spawns (reference: _system_config through ray.init)
            from ._private.config import set_system_config

            set_system_config(_system_config)
        if address is None and os.environ.get("RAY_TPU_ADDRESS"):
            address = os.environ["RAY_TPU_ADDRESS"]
        if address and address.startswith("ray-tpu://"):
            # remote-driver client mode (reference: ray.init("ray://...")
            # through python/ray/util/client/)
            from ._private import core as core_mod
            from .util.client import ClientCore

            cc = ClientCore(address)
            _core = cc
            core_mod._current_core = cc
            atexit.register(shutdown)
            return {"control_address": "%s:%s" % cc._server_control_addr,
                    "job_id": cc.job_id, "client": True}
        if address == "auto":
            # connect to the CLI-started cluster (reference: address="auto"
            # reading /tmp/ray/ray_current_cluster)
            from .scripts.cli import read_cluster_file

            info = read_cluster_file()
            if info is None:
                raise ConnectionError(
                    "address='auto' but no running cluster found "
                    "(start one with `python -m ray_tpu start --head`)")
            address = info["control_address"]
        if address is None:
            from ._private import bootstrap

            cluster, node = bootstrap.start_local(num_cpus=num_cpus,
                                                  num_tpus=num_tpus,
                                                  resources=resources)
            _owned_cluster = cluster
            control_addr = cluster.control_addr
            raylet_addr = node.addr
        else:
            host, port = address.rsplit(":", 1)
            control_addr = (host, int(port))
            raylet_addr = None
        # find the local raylet & its store
        from ._private.protocol import Client

        node_id = None
        store_root = None
        if raylet_addr is None:
            probe = Client(control_addr, name="init-probe")
            nodes = probe.call("get_nodes", timeout=30.0)
            probe.close()
            alive = [n for n in nodes if n["state"] == "ALIVE"]
            if alive:
                raylet_addr = tuple(alive[0]["addr"])
        if raylet_addr is not None:
            probe = Client(raylet_addr, name="init-probe-raylet")
            info = probe.call("node_info", timeout=30.0)
            probe.close()
            node_id = info["node_id"]
            if os.path.isdir(info["store_root"]):
                store_root = info["store_root"]
        _core = CoreWorker(control_addr, raylet_addr, mode="driver",
                           namespace=namespace, log_to_driver=log_to_driver,
                           node_id=node_id, store_root=store_root)
        # the driver's side of the boot timeline: control plane and raylet
        # answering, this driver connected
        _common.boot_part("cluster_start", t_entered)
        logger.info("driver boot: cluster start %.1f s",
                    _common.BOOT.get("cluster_start_s", 0.0))
        atexit.register(shutdown)
        # metrics created before a previous shutdown() flush again
        _metrics = sys.modules.get("ray_tpu.util.metrics")
        if _metrics is not None:
            _metrics._registry.restart_if_needed()
        if _tracing_startup_hook:
            # run locally + register in KV so every worker applies it
            # (reference: ray.init(_tracing_startup_hook=...))
            from .util import tracing as _tracing

            _tracing.run_hook(_tracing_startup_hook, _tracing_config)
            _tracing.register_hook(_core.control, _tracing_startup_hook,
                                   _tracing_config)
            # the hook may have just enabled tracing — attach the span
            # collector the CoreWorker init skipped while it was off
            _tracing.ensure_collector(_core.control, proc="driver",
                                      worker_id=_core.worker_id,
                                      node_id=_core.node_id or "",
                                      job_id=_core.job_id)
        return connection_info()


def connection_info() -> Dict[str, Any]:
    core = _require()
    return {
        "control_address": f"{core.control.addr[0]}:{core.control.addr[1]}",
        "node_id": core.node_id,
        "job_id": core.job_id,
    }


def shutdown() -> None:
    global _core, _owned_cluster
    with _lock:
        core, _core = _core, None
        cluster, _owned_cluster = _owned_cluster, None
    if core is not None:
        core.shutdown()
        from ._private import core as core_mod

        if core_mod._current_core is core:
            core_mod._current_core = None
    # the metrics flusher must stop AT shutdown, not race it (it would
    # otherwise wake after teardown and trip on the dead core)
    _metrics = sys.modules.get("ray_tpu.util.metrics")
    if _metrics is not None:
        _metrics._registry.stop()
    if cluster is not None:
        cluster.shutdown()


def _require() -> CoreWorker:
    if _core is not None:
        return _core
    # inside a worker process the CoreWorker registers itself globally
    from ._private.core import current_core

    return current_core()


def put(value: Any) -> ObjectRef:
    return _require().put(value)


def get(refs, timeout: Optional[float] = None):
    return _require().get(refs, timeout=timeout)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None):
    return _require().wait(refs, num_returns=num_returns, timeout=timeout)


def cancel(ref: "ObjectRef", *, force: bool = False,
           recursive: bool = True) -> bool:
    """Cancel the task that produces `ref` (reference: ray.cancel —
    recursive defaults to True there too).  Works for normal AND actor
    tasks: queued tasks are dropped; running ones get TaskCancelledError
    injected (async actor methods get their coroutine cancelled).
    force=True kills the worker process (normal tasks only).
    recursive=True also cancels the tasks the cancelled task submitted.
    Getting the ref afterwards raises TaskCancelledError.  Cancelled
    tasks never retry."""
    return _require().cancel(ref, force=force, recursive=recursive)


def cluster_resources() -> Dict[str, float]:
    return _require().control.call("cluster_resources", {})["total"]


def available_resources() -> Dict[str, float]:
    return _require().control.call("cluster_resources", {})["available"]


def nodes() -> List[Dict[str, Any]]:
    return _require().control.call("get_nodes", {})


def timeline(filename: Optional[str] = None) -> Optional[str]:
    """Export the task timeline as Chrome trace JSON (reference:
    ray.timeline, python/ray/_private/worker.py)."""
    from .util.state import timeline as _timeline

    _require().task_events.flush()
    return _timeline(filename)


class profile:
    """Span context manager feeding the timeline (reference:
    ray._private.profiling / TaskEventBuffer profile events)."""

    def __init__(self, event_name: str, task_id: str = ""):
        self._name = event_name
        self._task_id = task_id

    def __enter__(self):
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        core = _require()
        if core is not None:
            core.task_events.record_profile(
                self._task_id, self._name, self._t0, time.time())
        return False


__all__ = [
    "init", "shutdown", "is_initialized", "put", "get", "wait", "remote",
    "kill", "cancel", "get_actor", "cluster_resources",
    "available_resources", "nodes", "timeline", "profile",
    "ObjectRef", "ObjectRefGenerator", "ActorHandle", "ActorClass",
    "RemoteFunction",
    "RayTpuError", "TaskError", "ActorDiedError", "WorkerCrashedError",
    "ObjectLostError", "GetTimeoutError", "TaskCancelledError",
]
