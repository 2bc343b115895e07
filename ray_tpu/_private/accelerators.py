"""TPU accelerator detection & topology labels.

Mirror of the reference's accelerator-manager layer
(reference: python/ray/_private/accelerators/tpu.py:71 TPUAcceleratorManager
— chip detection via /dev/accel*, GCE metadata probing :48
_get_tpu_metadata, TPU_VISIBLE_CHIPS env :155-195).

Detection precedence per field: GKE env vars (TPU_NAME /
TPU_WORKER_ID / TPU_ACCELERATOR_TYPE, preset by the webhook) first,
then the GCE instance-metadata server (gcloud-provisioned TPU VMs carry
no env but always have metadata).  Worker 0 of a pod additionally
exposes the `TPU-<pod_type>-head` resource (reference: tpu.py:381) —
the handle gang schedulers target to run exactly one coordinator per
pod slice.
"""

from __future__ import annotations

import glob
import os
import re
import threading
from typing import Dict, Optional, Tuple

# GCE VM instance metadata (reference: tpu.py:23-29; endpoint
# overridable so tests point it at a fake metadata server)
_DEFAULT_METADATA_ENDPOINT = (
    "http://metadata.google.internal/computeMetadata/v1/instance/attributes")
_METADATA_KEYS = {"accelerator_type": "accelerator-type",
                  "tpu_name": "instance-id",
                  "worker_id": "agent-worker-number"}
_ACCEL_TYPE_RE = re.compile(r"^v\d+[a-zA-Z]*-\d+$")

_meta_lock = threading.Lock()
_meta_cache: Dict[str, Optional[str]] = {}
_meta_dead = False  # no metadata server here; stop re-probing


def _metadata_endpoint() -> str:
    return os.environ.get("RAY_TPU_GCE_METADATA_ENDPOINT",
                          _DEFAULT_METADATA_ENDPOINT)


def _get_tpu_metadata(key: str) -> Optional[str]:
    """One metadata attribute, or None (reference: tpu.py:48).  A failed
    connect marks the server dead for the process — laptops and non-GCE
    clusters pay the probe timeout once, not per call."""
    global _meta_dead
    with _meta_lock:
        if key in _meta_cache:
            return _meta_cache[key]
        if _meta_dead:
            return None
    import urllib.error
    import urllib.request

    val: Optional[str] = None
    try:
        req = urllib.request.Request(
            f"{_metadata_endpoint()}/{key}",
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=1.0) as r:
            if r.status == 200:
                val = r.read().decode().strip() or None
    except urllib.error.HTTPError:
        # 404/5xx: the server is ALIVE (an absent attribute is normal on
        # some shapes) — cache the miss for this key only
        val = None
    except OSError:
        # connection-level failure: no metadata server here
        with _meta_lock:
            _meta_dead = True
        return None
    except Exception:
        val = None
    with _meta_lock:
        _meta_cache[key] = val
    return val


def _reset_metadata_cache() -> None:
    """Test hook: forget probe results (endpoint changed)."""
    global _meta_dead
    with _meta_lock:
        _meta_cache.clear()
        _meta_dead = False


def num_tpu_chips() -> int:
    """Chips attached to this host, counted WITHOUT touching jax (the
    raylet and the control process call this; whoever initialises a
    backend takes the chip).  The device nodes come before the bounds
    variable: a machine handed one chip of a 2x2 host still carries the
    whole host's TPU_CHIPS_PER_HOST_BOUNDS, but only its own vfio group."""
    env = os.environ.get("RAY_TPU_NUM_CHIPS")
    if env:
        return int(env)
    chips = glob.glob("/dev/accel*")
    if chips:
        return len(chips)
    # vfio-bound chips (reference: tpu.py get_current_node_num_accelerators)
    try:
        vfio = [e for e in os.listdir("/dev/vfio") if e.isdigit()]
        if vfio:
            return len(vfio)
    except FileNotFoundError:
        pass
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")  # e.g. "2,2,1"
    if bounds:
        n = 1
        for p in bounds.split(","):
            n *= int(p)
        return n
    return 0


def current_pod_type() -> Optional[str]:
    """Validated pod type, e.g. "v4-16" (reference: tpu.py
    _get_current_node_tpu_pod_type — GKE env, then GCE metadata)."""
    acc = os.environ.get("TPU_ACCELERATOR_TYPE")
    if not acc and num_tpu_chips():
        acc = _get_tpu_metadata(_METADATA_KEYS["accelerator_type"])
    if acc and _ACCEL_TYPE_RE.match(acc):
        return acc
    return None


def current_tpu_name() -> Optional[str]:
    """Pod/slice name (reference: tpu.py get_current_node_tpu_name)."""
    name = os.environ.get("TPU_NAME")
    if name:
        return name.split(",")[0]
    if num_tpu_chips():
        return _get_tpu_metadata(_METADATA_KEYS["tpu_name"])
    return None


def current_worker_id() -> Optional[int]:
    """This host's index within the pod (reference: tpu.py
    _get_current_node_tpu_worker_id)."""
    wid = os.environ.get("TPU_WORKER_ID")
    if not wid and num_tpu_chips():
        wid = _get_tpu_metadata(_METADATA_KEYS["worker_id"])
    try:
        return int(wid) if wid is not None and wid != "" else None
    except ValueError:
        return None


def tpu_labels() -> Dict[str, str]:
    labels = {}
    name = current_tpu_name()
    if not name:
        hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        name = hosts.split(",")[0] if hosts else None
    if name:
        labels["tpu_slice"] = name
    wid = current_worker_id()
    if wid is not None:
        labels["tpu_worker_id"] = str(wid)
    acc = current_pod_type()
    if acc:
        labels["tpu_accelerator_type"] = acc
    return labels


def pod_resources() -> Dict[str, float]:
    """Per-pod custom resources (reference: tpu.py:381
    get_additional_resources): every pod host exposes {<tpu_name>: 1};
    worker 0 additionally exposes {TPU-<pod_type>-head: 1} — request it
    to land exactly one coordinating task per pod slice."""
    out: Dict[str, float] = {}
    name = current_tpu_name()
    wid = current_worker_id()
    pod_type = current_pod_type()
    if name and wid is not None and pod_type:
        out[name] = 1.0
        if wid == 0:
            out[f"TPU-{pod_type}-head"] = 1.0
    return out


def default_resources() -> Dict[str, float]:
    res: Dict[str, float] = {"CPU": float(os.cpu_count() or 1)}
    chips = num_tpu_chips()
    if chips:
        res["TPU"] = float(chips)
        res.update(pod_resources())
    return res


#: chips -> TPU_CHIPS_PER_HOST_BOUNDS of a sub-host slice (reference:
#: tpu.py TPU_CHIPS_PER_HOST_BOUNDS_1_CHIP_CONFIG / _2_CHIP_CONFIG)
_SUBHOST_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def visible_chip_env(assigned: Tuple[int, ...], host_chips: int
                     ) -> Dict[str, str]:
    """Env vars confining a worker to its assigned chips (reference:
    tpu.py:155-195 set_current_process_visible_accelerator_ids).  A
    process that sees the whole host needs nothing but the list; one
    that sees a sub-host slice also has to be told the slice's shape,
    or libtpu waits for the host's other chips."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in assigned)}
    n = len(assigned)
    if n < host_chips:
        if n not in _SUBHOST_BOUNDS:
            raise ValueError(
                f"a worker can be given {sorted(_SUBHOST_BOUNDS)} or all "
                f"{host_chips} chips of a host, not {n}")
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = _SUBHOST_BOUNDS[n]
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    return env


def tpu_device_paths() -> list:
    """Host device nodes a TPU container must be granted
    (reference: image_uri.py device propagation): /dev/accel* for
    direct-attached chips, the vfio group nodes + /dev/vfio/vfio
    control node for vfio-bound ones.  RAY_TPU_TPU_DEVICES overrides
    (exotic device layouts, tests)."""
    env = os.environ.get("RAY_TPU_TPU_DEVICES")
    if env is not None:
        return [p for p in env.split(",") if p]
    devs = sorted(glob.glob("/dev/accel*"))
    try:
        vfio = [f"/dev/vfio/{e}" for e in os.listdir("/dev/vfio")
                if e.isdigit()]
        if vfio:
            devs += ["/dev/vfio/vfio", *sorted(vfio)]
    except FileNotFoundError:
        pass
    return devs


#: host env a TPU container needs forwarded (the runtime does not
#: inherit its client's environment): topology bounds + platform.  The
#: leased chips are the raylet's to give (visible_chip_env).
_TPU_FORWARD_ENV = ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
                    "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "TPU_NAME",
                    "JAX_PLATFORMS")


def tpu_container_env() -> Dict[str, str]:
    """Host env to forward into a TPU actor's container."""
    out = {k: os.environ[k] for k in _TPU_FORWARD_ENV if k in os.environ}
    if out.get("JAX_PLATFORMS", "").lower() == "cpu":
        # a host pinned to CPU (dev boxes keep host processes off the
        # chip) must NOT pin the TPU actor's container to CPU — that is
        # the silent-fallback-while-holding-the-lease failure mode
        del out["JAX_PLATFORMS"]
    return out
