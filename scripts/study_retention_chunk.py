"""`ops/retention.retention_chunk` alone at `serve-brumby-streams`' widths,
the Pallas kernel beside the XLA body (PERF.md section 6, PR 57).

On the chip, one process: Brumby-14B's grouping (8 K/V heads, 5 query
heads each, d_head 128, state [8, 136, 8320] float32), bf16 operands, a
chunk of C = 128 / 256 / 384 / 512 rows (the engine's four prefill
programs) against a carried state.  As in a prefill program, a body runs
once a layer inside ONE jitted `lax.scan` over `--layers` stacked states
(8: the cell's stage of the model), so a timing is a program's retention
and holds one launch; run once, then timed `--iters` times by the host's
clock around `block_until_ready`.  The kernel's outputs and states are
held to the XLA body's (largest distance over the largest value), and the
least time of `benchmarks/lib/costs_retention.py` stands beside each.
`--module PATH` times the kernel of another `retention.py` (a variant, or
the parent's) beside this tree's.  `--toy` runs the control flow at toy
sizes on the CPU, the kernel in interpret mode (no times).

`--program` instead runs the cell's own prefill programs (the
configuration's model behind a `ContinuousEngine`, plain weights) of
`--rows` rows `--iters` times under the profiler and splits a program's
device time by scope as the cell does (`benchmarks/trace/scopes.py`:
`retention_chunk`, `mlp`, `unembed`; what is left is the projections,
norms, rope and the state's write-back), with its largest ops.

    python scripts/study_retention_chunk.py [--rows 128 256 384 512]
        [--iters 20] [--module PATH ...] [--program]

Writes chiprun_out/pr57/study_chunk.json (`--program`:
study_program.json).  Not wired into the benchmark.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from benchmarks.lib import costs_retention
from benchmarks.lib.peaks import peak as published_peak
from ray_tpu.ops import retention

HKV, G, DH = 8, 5, 128
CFG = {"head_dim": DH, "num_key_value_heads": HKV,
       "num_attention_heads": HKV * G, "num_hidden_layers": 1}


def load(path):
    spec = importlib.util.spec_from_file_location(
        "retention_" + str(abs(hash(path))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program(mod, impl, layers, dtype):
    """`layers` chunks, each from its own carried state, in one launch."""
    def f(q, k, v, log_g, states):
        def layer(_, state):
            return None, mod.retention_chunk(q, k, v, log_g, state,
                                             impl=impl, dtype=dtype)
        return jax.lax.scan(layer, None, states)[1]
    return jax.jit(f)


def far(x, y):
    """The largest distance over the largest value."""
    return float(jnp.abs(x - y).max() / jnp.abs(y).max())


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / iters


def programs(rows, iters, toy):
    """A prefill program's device time by scope, `rows` rows a program."""
    import shutil
    import tempfile

    import numpy as np

    from benchmarks.drivers._common import start_trace, stop_trace
    from benchmarks.drivers.replica_brumby import SCOPES
    from benchmarks.lib.brumbycfg import model_config
    from benchmarks.trace import reduce, scopes
    from ray_tpu.models import brumby as bm
    from ray_tpu.serve._engine import ContinuousEngine

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "brumby-14b-l8.json")) as f:
        conf = json.load(f)
    ek = dict(conf["serve"]["engine_kwargs"])
    if toy:
        cfg = bm.BrumbyConfig.nano(retention_impl="pallas_interpret")
        ek.update(max_slots=2, max_total=128, num_pages={bm.KIND: 3},
                  prefill_bucket=8, prefill_chunk=max(rows))
    else:
        cfg = model_config(conf)
    params = bm.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(bm, cfg, params, **ek)
    out = []
    try:
        eng._ensure_device_state()
        for T in rows:
            fn = eng._fn(("prefill", T))
            # a carried chunk (start = T) of entry 1, every row real
            rest = (np.ones(T, np.int32), {bm.KIND: np.array([1], np.int32)},
                    np.int32(T), np.int32(T - 1))

            def run():
                lg, eng._cache, _ = fn(eng._params, eng._cache, *rest)
                return jax.block_until_ready(lg)

            run()
            text = fn.lower(eng._params, eng._cache,
                            *rest).compile().as_text()
            trace = tempfile.mkdtemp(prefix="study_chunk_")
            start_trace(trace)
            t0 = time.perf_counter()
            for _ in range(iters):
                run()
            took = (time.perf_counter() - t0) / iters
            stop_trace()
            row = {"rows": T, "host_ms": None if toy else 1e3 * took}
            if not toy:
                red = reduce.reduce_trace(trace)
                by = scopes.scope_seconds(trace, {"jit_serve_prefill": [
                    scopes.scope_map(text, SCOPES)]})
                total, n = reduce.module_time(red, "jit_serve_prefill")
                row.update(
                    executions=n, device_ms=1e3 * total / n,
                    scope_ms={k: 1e3 * v / n for k, v in by.items()},
                    rest_ms=1e3 * (total - sum(by.values())) / n,
                    top_ops_ms=[[k, 1e3 * o["s"] / n] for k, o in sorted(
                        red["ops"].items(), key=lambda kv: -kv[1]["s"])[:12]])
            shutil.rmtree(trace, ignore_errors=True)
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        eng.stop()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[128, 256, 384, 512])
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--module", nargs="*", default=[])
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--program", action="store_true")
    a = ap.parse_args()
    out = os.path.join(ROOT, "chiprun_out", "pr57")
    os.makedirs(out, exist_ok=True)
    name = ("study_program" if a.program else "study_chunk") + (
        "_toy.json" if a.toy else ".json")
    if a.program:
        rows = programs(a.rows, 1 if a.toy else a.iters, a.toy)
        with open(os.path.join(out, name), "w") as f:
            json.dump(rows, f, indent=1)
        return
    hkv, g, dh, dtype = (2, 3, 16, jnp.float32) if a.toy else (
        HKV, G, DH, jnp.bfloat16)
    layers, iters = (2, 1) if a.toy else (a.layers, a.iters)
    kernel = "pallas_interpret" if a.toy else "pallas"
    device = jax.devices()[0]
    peak = None if a.toy else published_peak(device.device_kind)
    bodies = [("xla", retention, "xla"), ("kernel", retention, kernel)] + [
        (os.path.relpath(p, ROOT), load(p), kernel) for p in a.module]
    R, F = retention.state_shape(dh)
    rows = []
    for C in a.rows:
        ks = jax.random.split(jax.random.PRNGKey(C), 6)
        draw = lambda key, *shape: jax.random.normal(key, shape, dtype)
        args = (draw(ks[0], hkv, g, C, dh), draw(ks[1], hkv, C, dh),
                draw(ks[2], hkv, C, dh),
                jax.nn.log_sigmoid(5.5 + jax.random.normal(ks[3], (hkv, C))))
        # the carried states: what 128 earlier keys left (any array is no
        # state: its normaliser may cross zero)
        n = min(C, 128)
        before = (args[0][:, :, :n], draw(ks[4], hkv, n, dh),
                  draw(ks[5], hkv, n, dh), args[3][:, :n])
        args += (jnp.broadcast_to(retention.retention_chunk(
            *before, jnp.zeros((hkv, R, F)), impl="xla", dtype=dtype)[1],
            (layers, hkv, R, F)),)
        row = {"rows": C, "layers": layers, "device": device.device_kind}
        if peak:
            rec = {"chunk_tokens": C, "chunk_ret_states": 1}
            row["least_ms"] = 1e3 * layers * costs_retention.least_seconds(
                "chunk", rec, CFG, peak)
        want = None
        for body, mod, impl in bodies:
            (o, s), took = timed(program(mod, impl, layers, dtype), args,
                                 iters)
            if want is None:
                want = (o, s)
            row[body] = {"ms": None if a.toy else 1e3 * took,
                         "o_off_xla": far(o, want[0]),
                         "state_off_xla": far(s, want[1])}
        print(json.dumps(row), flush=True)
        rows.append(row)
    with open(os.path.join(out, name), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
