#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ONE cell ONCE and prints the contract's object as the last line of
stdout: `correct`, `attempted`, `failed`, `metrics`, `device` and, traced,
`breakdown`.  `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics from a traced run of its own.  Everything that belongs
to one configuration, traffic mix or metric is found by name through
BENCHMARK.json (lib/manifest.py); the traffic file's `kind` names the driver
module (`drivers/<kind>.py`), a metric file's `reader` its reader
(`metrics/readers/<reader>.py`).

This process never initialises a jax backend: the driver's `run(ctx)` runs
in a child (a train cell's child owns the chips; a serve cell's child drives
the cluster whose replica owns the chip).  No TPU, or fewer chips than the
cell asks for, is exit 3 and no result line.

`--rehearse` is a test fixture only: tiny sizes on the CPU (virtual devices
for a 4-chip cell), numbers printed under `rehearsal.*` names, `correct`
false — no CPU number can appear under a device metric's name.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")       # fixed: part of the key
RUN_DIR = os.path.join(ROOT, ".bench_run")


def _args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--t0", type=float, help=argparse.SUPPRESS)
    ap.add_argument("--rundir", help=argparse.SUPPRESS)
    return ap.parse_args()


def _context(args, cell: dict) -> dict:
    rehearsal = {}
    if args.rehearse:
        with open(os.path.join(HERE, "tests", "rehearsal.json")) as f:
            rehearsal = json.load(f)
        cell["config"].update(rehearsal["config"])
        for k, v in rehearsal["traffic"].get(
                cell["traffic"]["kind"], {}).items():
            cell["traffic"][k] = v
    return {"cell": cell["cell"]["name"], "chips": cell["cell"]["chips"],
            "config": cell["config"], "traffic": cell["traffic"],
            "seed": args.seed, "seconds": args.seconds
            if args.seconds is not None else cell["run_seconds"],
            "trace": bool(args.trace), "rehearse": args.rehearse,
            "rehearsal": rehearsal, "t0": args.t0 or T0,
            "rundir": args.rundir,
            "trace_dir": os.path.join(args.rundir or "", "trace")}


def _child(args):
    """The child: the cell's driver, result to <rundir>/result.json."""
    from benchmarks.drivers._common import write_json
    from benchmarks.lib import manifest

    cell = manifest.resolve(manifest.load(), args.workload)
    ctx = _context(args, cell)
    drv = importlib.import_module(
        f"benchmarks.drivers.{cell['traffic']['kind']}")
    write_json(os.path.join(args.rundir, "result.json"), drv.run(ctx))
    # the result is on disk: leave without tearing the TPU client down (ten
    # seconds for four chips, and every run has sixty beside its window)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def _child_env(args, chips: int) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)          # the driver's own; no notice taken
    # one compile cache for every process of the run: where the variable is
    # set jax reads it itself, otherwise a FIXED directory of the checkout
    env.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    # cache every program, also those that compile in under a second
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                f"host_platform_device_count={chips}").strip()
    else:
        env.pop("JAX_PLATFORMS", None)
    return env


def _run_child(args, rundir: str, env: dict) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--seed", str(args.seed), "--trace",
           str(args.trace), "--child", "--t0", repr(T0), "--rundir", rundir]
    if args.seconds is not None:
        cmd += ["--seconds", repr(args.seconds)]
    if args.rehearse:
        cmd.append("--rehearse")
    proc = subprocess.Popen(cmd, env=env, cwd=ROOT, start_new_session=True)
    try:
        rc = proc.wait()
    finally:
        # whatever the child left in its process group ends with it
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    if rc != 0:
        print(f"bench: the run of {args.workload!r} exited {rc}",
              file=sys.stderr, flush=True)
        sys.exit(rc if rc in (2, 3) else 1)
    with open(os.path.join(rundir, "result.json")) as f:
        return json.load(f)


def main():
    args = _args()
    if args.child:
        return _child(args)
    os.environ["JAX_PLATFORMS"] = "cpu"     # this process stays off the chip
    from benchmarks.lib import manifest, result

    man = manifest.load()
    cell = manifest.resolve(man, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        print("bench: the program (ray_tpu/) is not in this directory",
              file=sys.stderr)
        sys.exit(2)
    rundir = os.path.join(RUN_DIR, f"{args.workload}-{args.trace}")
    shutil.rmtree(rundir, ignore_errors=True)
    os.makedirs(rundir)
    env = _child_env(args, cell["cell"]["chips"])
    obs = _run_child(args, rundir, env)
    correct, checks = bool(obs.pop("correct")), obs.pop("checks", {})
    device = dict(obs["device"])
    ctx = {"config": cell["config"], "traffic": cell["traffic"],
           "chips": cell["cell"]["chips"], "device": device}
    breakdown = None
    if args.trace:
        from benchmarks.trace import reduce as R

        try:
            red = R.reduce_trace(os.path.join(rundir, "trace"))
        except FileNotFoundError as e:
            print(f"bench: {e}", file=sys.stderr, flush=True)
            red = None
        if red is not None:
            obs["trace"] = red
            print(json.dumps({"phase": "trace", "planes": red["planes"],
                              "n_devices": red["n_devices"],
                              "window_s": red["window_s"],
                              "busy_s": red["busy_s"],
                              "collective_s": red["collective_s"],
                              "modules": red["modules"]}), flush=True)
            if red["n_devices"]:
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                breakdown = {"device_ops": R.top_ops(red),
                             "idle_gaps": red["gaps"]}
        if not os.environ.get("BENCH_KEEP_TRACE"):    # debugging aid only
            shutil.rmtree(os.path.join(rundir, "trace"), ignore_errors=True)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = result.read_metrics(
        cell[kind], obs, ctx, prefix="rehearsal." if args.rehearse else "",
        lenient=args.rehearse)
    print(json.dumps({"phase": "checks", "checks": checks,
                      "seconds_total": time.time() - T0}), flush=True)
    if args.rehearse:
        correct = False                     # a rehearsal is never a result
    print(result.last_line(correct, obs["attempted"], obs["failed"],
                           metrics, device, breakdown), flush=True)


if __name__ == "__main__":
    main()
