"""Delta resource sync microbench (VERDICT r3 #7, reference:
src/ray/common/ray_syncer/ray_syncer.h:44-70).

Drives N fake raylets against a REAL control daemon in two modes —
full-snapshot-every-beat (the pre-delta protocol) vs versioned delta
(availability only when changed) — and reports heartbeat wire bytes/s,
control-process CPU, and node-view read latency (the scheduling-view
proxy) for each.  Availability actually changes on ~10% of beats
(steady-state clusters mostly idle between scheduling bursts).

Usage: python scripts/bench_resource_sync.py [--nodes 50] [--secs 15]
Prints one JSON line.
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.bootstrap import Cluster  # noqa: E402
from ray_tpu._private.protocol import Client, _dumps  # noqa: E402

HB_INTERVAL = 0.1   # compressed time: 5x the real 0.5s rate, same ratio
CHURN = 0.1         # fraction of beats where availability changed


def _proc_cpu_s(pid: int) -> float:
    with open(f"/proc/{pid}/stat") as f:
        parts = f.read().split()
    return (int(parts[13]) + int(parts[14])) / os.sysconf("SC_CLK_TCK")


def run_mode(addr, n_nodes: int, secs: float, delta: bool,
             control_pid: int) -> dict:
    stop = threading.Event()
    bytes_sent = [0] * n_nodes
    beats = [0] * n_nodes

    def node_loop(i: int):
        cli = Client(addr, name=f"fake-node-{i}")
        nid = f"fake-{'d' if delta else 'f'}-{i}"
        cli.call("register_node", {
            "node_id": nid, "addr": ("127.0.0.1", 40000 + i),
            "resources": {"CPU": 16.0}, "labels": {}}, timeout=10)
        avail = 16.0
        version = 0
        last_sent = None
        k = 0
        while not stop.is_set():
            k += 1
            changed = (k * 7919 + i) % int(1 / CHURN) == 0
            if changed:
                avail = 16.0 if avail < 16.0 else 8.0
            payload = {"node_id": nid}
            if not delta or {"CPU": avail} != last_sent:
                version += 1
                payload["available"] = {"CPU": avail}
                payload["avail_version"] = version
            data = _dumps((1, 0, "heartbeat", payload))
            bytes_sent[i] += len(data)
            beats[i] += 1
            try:
                r = cli.call("heartbeat", payload, timeout=5)
                if r and r.get("ok") and "available" in payload:
                    last_sent = dict(payload["available"])
            except Exception:
                pass
            time.sleep(HB_INTERVAL)
        cli.close()

    threads = [threading.Thread(target=node_loop, args=(i,), daemon=True)
               for i in range(n_nodes)]
    for t in threads:
        t.start()
    time.sleep(2.0)              # settle
    cpu0 = _proc_cpu_s(control_pid)
    t0 = time.perf_counter()
    b0 = sum(bytes_sent)
    beats0 = sum(beats)
    # scheduling-view read latency while the sync load runs
    probe = Client(addr, name="probe")
    lat = []
    while time.perf_counter() - t0 < secs:
        p0 = time.perf_counter()
        probe.call("get_nodes", {}, timeout=10)
        lat.append(time.perf_counter() - p0)
        time.sleep(0.05)
    wall = time.perf_counter() - t0
    cpu1 = _proc_cpu_s(control_pid)
    b1 = sum(bytes_sent)
    stop.set()
    for t in threads:
        t.join(timeout=2)
    probe.close()
    lat.sort()
    return {
        "mode": "delta" if delta else "full",
        "hb_bytes_per_s": round((b1 - b0) / wall, 1),
        "control_cpu_frac": round((cpu1 - cpu0) / wall, 4),
        "view_read_ms_p50": round(lat[len(lat) // 2] * 1000, 2),
        "view_read_ms_p95": round(lat[int(len(lat) * 0.95)] * 1000, 2),
        "beats_per_s": round((sum(beats) - beats0) / wall, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=50)
    ap.add_argument("--secs", type=float, default=15.0)
    args = ap.parse_args()

    results = {}
    for delta in (False, True):
        # one control daemon PER MODE: the prior mode's 50 dead fake
        # nodes would otherwise sit in the node table timing out,
        # charging death-detection work and a 2x get_nodes table to
        # whichever mode runs second
        c = Cluster()
        addr = c.start_control()
        try:
            results[delta] = run_mode(addr, args.nodes, args.secs,
                                      delta=delta,
                                      control_pid=c.control_proc.pid)
        finally:
            c.shutdown()
    full, delta = results[False], results[True]
    out = {
        "bench": "resource_sync_delta",
        "n_nodes": args.nodes,
        "hb_interval_s": HB_INTERVAL,
        "churn": CHURN,
        "full": full,
        "delta": delta,
        "bytes_reduction": round(
            1 - delta["hb_bytes_per_s"] / full["hb_bytes_per_s"], 3),
        "cpu_reduction": round(
            1 - delta["control_cpu_frac"] / max(full["control_cpu_frac"],
                                               1e-9), 3),
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
