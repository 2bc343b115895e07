"""Container / image_uri runtime env (reference:
_private/runtime_env/image_uri.py:106 ImageURIPlugin — the worker
command is wrapped in a container runtime invocation).  No container
runtime exists in this image, so the end-to-end path runs against a
SHIM binary injected via RAY_TPU_CONTAINER_RUNTIME: it logs the exact
argv it was exec'd with (the assertion surface), applies the -e env
pairs, and execs the inner worker command on the host."""

import json
import os
import stat
import sys

import pytest

import ray_tpu
from ray_tpu._private import runtime_env as rtenv


# ---------------------------------------------------------------------------
# validation / gating
# ---------------------------------------------------------------------------

def test_container_gated_by_default(monkeypatch):
    monkeypatch.delenv("RAY_TPU_ALLOW_PKG_INSTALL", raising=False)
    with pytest.raises(ValueError, match="egress"):
        rtenv.validate({"container": {"image": "img:1"}})


def test_image_uri_is_container_sugar(monkeypatch):
    monkeypatch.setenv("RAY_TPU_ALLOW_PKG_INSTALL", "1")
    env = rtenv.validate({"image_uri": "repo/img:2"})
    assert env["container"] == {"image": "repo/img:2"}
    assert "image_uri" not in env
    with pytest.raises(ValueError, match="exclusive"):
        rtenv.validate({"image_uri": "a", "container": {"image": "b"}})


def test_container_spec_validation(monkeypatch):
    monkeypatch.setenv("RAY_TPU_ALLOW_PKG_INSTALL", "1")
    with pytest.raises(ValueError, match="container"):
        rtenv.validate({"container": {"no_image": True}})
    with pytest.raises(ValueError, match="run_options"):
        rtenv.validate({"container": {"image": "i", "run_options": [1]}})
    with pytest.raises(ValueError, match="bake"):
        rtenv.validate({"container": {"image": "i"}, "pip": ["x"]})


def test_missing_runtime_is_loud(monkeypatch):
    monkeypatch.delenv("RAY_TPU_CONTAINER_RUNTIME", raising=False)
    monkeypatch.setenv("PATH", "/nonexistent")
    with pytest.raises(RuntimeError, match="podman"):
        rtenv.resolve_container_runtime()


def test_wrap_container_cmd_shape(tmp_path, monkeypatch):
    rt = tmp_path / "podman"
    rt.write_text("#!/bin/sh\n")
    rt.chmod(0o755)
    monkeypatch.setenv("RAY_TPU_CONTAINER_RUNTIME", str(rt))
    cmd = rtenv.wrap_container_cmd(
        ["python", "-m", "worker"], {"A": "1"},
        {"image": "img:3", "run_options": ["--gpus=all"]},
        "/sess", "/repo:/x")
    assert cmd[0] == str(rt)
    assert cmd[1] == "run"
    assert "--network=host" in cmd and "--ipc=host" in cmd
    assert "-v" in cmd and "/sess:/sess" in cmd
    assert "/repo:/repo:ro" in cmd and "/x:/x:ro" in cmd
    assert "A=1" in cmd and "RAY_TPU_IN_CONTAINER=1" in cmd
    i = cmd.index("img:3")
    assert cmd[i - 1] == "--gpus=all"        # run_options just before image
    assert cmd[i + 1:] == ["python", "-m", "worker"]


# ---------------------------------------------------------------------------
# end-to-end with a shim runtime
# ---------------------------------------------------------------------------

IMAGE = "ray-tpu-test-image:latest"


def _write_shim(path, log_file) -> str:
    """A fake container runtime: records argv, applies -e pairs, and
    execs the inner worker command on the host."""
    shim = path / "docker-shim"
    shim.write_text(f"""#!{sys.executable}
import json, os, sys
args = sys.argv[1:]
with open({str(log_file)!r}, "a") as f:
    f.write(json.dumps(args) + "\\n")
for j, a in enumerate(args):
    if a == "-e":
        k, _, v = args[j + 1].partition("=")
        os.environ[k] = v
i = args.index({IMAGE!r})
os.execvp(args[i + 1], args[i + 1:])
""")
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    return str(shim)


@pytest.fixture
def container_cluster(tmp_path, monkeypatch, private_cluster_slot):
    """Fresh cluster whose raylet resolves the shim as the runtime
    (env must be set BEFORE init so the raylet daemon inherits it)."""
    log_file = tmp_path / "shim_calls.jsonl"
    shim = _write_shim(tmp_path, log_file)
    monkeypatch.setenv("RAY_TPU_CONTAINER_RUNTIME", shim)
    monkeypatch.setenv("RAY_TPU_ALLOW_PKG_INSTALL", "1")
    ray_tpu.init(num_cpus=2)
    yield log_file


def test_containerized_actor_e2e(container_cluster):
    log_file = container_cluster

    @ray_tpu.remote
    class Probe:
        def where(self):
            return {"in_container": os.environ.get("RAY_TPU_IN_CONTAINER"),
                    "pid": os.getpid()}

    a = Probe.options(
        runtime_env={"container": {"image": IMAGE,
                                   "run_options": ["--memory=1g"]}}).remote()
    got = ray_tpu.get(a.where.remote(), timeout=120)
    # the worker really went through the runtime: the -e pair it applied
    # is visible inside the actor process
    assert got["in_container"] == "1"

    calls = [json.loads(ln) for ln in open(log_file)]
    assert len(calls) == 1
    argv = calls[0]
    # the exec line the runtime received, piece by piece
    assert argv[0] == "run" and "--rm" in argv
    assert "--network=host" in argv and "--ipc=host" in argv
    assert "/dev/shm:/dev/shm" in argv
    assert "--memory=1g" in argv
    i = argv.index(IMAGE)
    assert argv[i - 1] == "--memory=1g"
    inner = argv[i + 1:]
    assert inner[1:3] == ["-m", "ray_tpu._private.worker_proc"]
    assert any(e.startswith("RAY_TPU_ACTOR_ID=") for e in argv)
    ray_tpu.kill(a)


def test_image_uri_actor_and_warm_pool_not_reused(container_cluster):
    log_file = container_cluster

    @ray_tpu.remote
    class P:
        def ping(self):
            return os.environ.get("RAY_TPU_IN_CONTAINER")

    # a plain actor first — warms the pool with host workers
    plain = P.remote()
    assert ray_tpu.get(plain.ping.remote(), timeout=60) is None
    boxed = P.options(runtime_env={"image_uri": IMAGE}).remote()
    assert ray_tpu.get(boxed.ping.remote(), timeout=120) == "1"
    calls = [json.loads(ln) for ln in open(log_file)]
    assert len(calls) == 1      # exactly the containerized one


def test_plain_task_with_container_rejected(container_cluster):
    @ray_tpu.remote
    def f():
        return 1

    ref = f.options(
        runtime_env={"container": {"image": IMAGE}}).remote()
    with pytest.raises(Exception, match="actor"):
        ray_tpu.get(ref, timeout=60)


def test_actor_fails_loudly_without_runtime(tmp_path, monkeypatch,
                                            private_cluster_slot):
    monkeypatch.setenv("RAY_TPU_CONTAINER_RUNTIME",
                       str(tmp_path / "missing-runtime"))
    monkeypatch.setenv("RAY_TPU_ALLOW_PKG_INSTALL", "1")
    monkeypatch.setenv("PATH", "/nonexistent:" + os.environ.get("PATH", ""))
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    class P:
        def ping(self):
            return 1

    a = P.options(runtime_env={"image_uri": IMAGE}).remote()
    with pytest.raises(Exception, match="spawn failed|container"):
        ray_tpu.get(a.ping.remote(), timeout=90)


# ---------------------------------------------------------------------------
# containerized TPU actors: device grants + visibility env
# ---------------------------------------------------------------------------


@pytest.fixture
def tpu_container_cluster(tmp_path, monkeypatch, private_cluster_slot):
    """Container cluster whose node advertises one (fake) TPU chip with
    a fake device path — the shim records the exact runtime argv, which
    is the assertion surface for device grants."""
    log_file = tmp_path / "shim_calls.jsonl"
    shim = _write_shim(tmp_path, log_file)
    monkeypatch.setenv("RAY_TPU_CONTAINER_RUNTIME", shim)
    monkeypatch.setenv("RAY_TPU_ALLOW_PKG_INSTALL", "1")
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "1")
    monkeypatch.setenv("RAY_TPU_TPU_DEVICES", "/dev/null")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    ray_tpu.init(num_cpus=2)
    yield log_file


def test_containerized_tpu_actor_gets_devices_and_env(
        tpu_container_cluster):
    """The round-4 'no device mounts' rejection is lifted: a TPU actor's
    container gets --device grants for the host TPU nodes and the chip
    visibility env forwarded (reference: image_uri.py device
    propagation + tpu.py TPU_VISIBLE_CHIPS scoping)."""
    log_file = tpu_container_cluster

    @ray_tpu.remote
    class TpuProbe:
        def where(self):
            return {"in_container": os.environ.get("RAY_TPU_IN_CONTAINER"),
                    "visible": os.environ.get("TPU_VISIBLE_CHIPS")}

    a = TpuProbe.options(
        resources={"TPU": 1},
        runtime_env={"container": {"image": IMAGE}}).remote()
    got = ray_tpu.get(a.where.remote(), timeout=120)
    assert got["in_container"] == "1"
    # chip visibility rode the -e pairs into the worker
    assert got["visible"] == "0"

    argv = [json.loads(ln) for ln in open(log_file)][0]
    assert "--device=/dev/null" in argv
    assert "TPU_VISIBLE_CHIPS=0" in argv
    ray_tpu.kill(a)


def test_containerized_tpu_actor_rejected_without_devices(
        tmp_path, monkeypatch, private_cluster_slot):
    """Loud rejection remains ONLY when the host truly has no TPU
    device path (no /dev nodes): JAX silently falling back to CPU while
    holding the TPU lease is the guarded failure mode."""
    log_file = tmp_path / "shim_calls.jsonl"
    shim = _write_shim(tmp_path, log_file)
    monkeypatch.setenv("RAY_TPU_CONTAINER_RUNTIME", shim)
    monkeypatch.setenv("RAY_TPU_ALLOW_PKG_INSTALL", "1")
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "1")    # advertised...
    monkeypatch.setenv("RAY_TPU_TPU_DEVICES", "")   # ...but no devices
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    class P:
        def ping(self):
            return 1

    a = P.options(resources={"TPU": 1},
                  runtime_env={"container": {"image": IMAGE}}).remote()
    with pytest.raises(Exception, match="device|spawn failed"):
        ray_tpu.get(a.ping.remote(), timeout=90)
