"""`ops/kda.kda_chunk` alone at `serve-ling3flash-reasoning`'s shape, the
parent's beside the tree's (PERF.md section 6, PR 60).

On the chip, one process: Ling-3.0-flash's KDA heads (H 32, dk = dv 128,
float32), one chunk of T 512 rows against a carried state.  As in the
cell's prefill program, the op runs once a layer inside ONE jitted
`lax.scan` over `--layers` layers (6: the KDA layers of the cell's seven),
EVERY LAYER ITS OWN ROWS AND STATE — handed the same rows a layer, the
compiler computes the half that reads no state once for all six, and PR
60's first readings (1.5 ms a program) were a sixth of that half; the
scan over stacked rows costs slices and copies the cell's program does not
have, so `--program` is the reading that counts and this one ranks
variants.  Run once, then `--iters` times by the host's clock around
`block_until_ready`, then `--iters` times under the profiler: the device
SELF time a call is the trace's, by the benchmark's own reduction
(`benchmarks/trace/reduce.py`), with the call's largest ops.  Every side's
output and state are held to the first side's (largest distance over the
largest value) and head 0 of layer 0 to the token-by-token recurrence in
float64 on the host; the least time of `benchmarks/lib/costs_kda.py`
stands beside each.  `--parent DIR` is a checkout of the parent commit
(`git archive` into `_parent/`); `--module PATH` times another `kda.py`
(a variant) the same way.  `--toy` runs the control flow at toy sizes on
the CPU (no times).

`--program` instead runs the cell's own `serve.prefill:512` (the
configuration's model behind a `ContinuousEngine`, plain weights) `--iters`
times under the profiler and splits the program's device time by scope as
the cell does (`benchmarks/trace/scopes.py`), with its largest ops, the
scope's own instructions by name and the compiled text they are read in
(`prefill_<side>.hlo.txt`); a side
is a child process (the chip is one process's at a time), the parent's
`kda_chunk` set on `models/ling3` from `--parent`'s `kda.py`.

    python scripts/study_kda_chunk.py [--parent _parent] [--module PATH ..]
        [--iters 20] [--layers 6] [--program] [--toy]

Writes chiprun_out/pr60/study_chunk.json (`--program`:
study_program.json).  Not wired into the benchmark.
"""
import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers._common import start_trace, stop_trace
from benchmarks.lib import costs_kda
from benchmarks.lib.peaks import peak as published_peak
from benchmarks.trace import reduce, scopes
from ray_tpu.ops import kda

H, T, D = 32, 512, 128
CONF = os.path.join(ROOT, "benchmarks", "configs",
                    "ling-3.0-flash-l7-e128.json")


def load(path):
    """Another `kda.py` as a module of `ray_tpu.ops` (its relative import
    of `retention` finds the tree's)."""
    spec = importlib.util.spec_from_file_location(
        "ray_tpu.ops._kda_%x" % abs(hash(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sides(a):
    """[(name, the side's `kda.py` or None for the tree's)], the parent
    first where `--parent` holds one."""
    out = [("tree", None)] + [(os.path.relpath(p, ROOT), p)
                              for p in a.module]
    path = os.path.join(a.parent, "ray_tpu", "ops", "kda.py")
    if os.path.exists(path):
        out.insert(0, ("parent", path))
    return out


def inputs(heads, rows, d, layers, seed, lower=-5.0):
    """`layers` layers' rows as `models/ling3` hands them over (q, k unit
    rows, q scaled; most gates near 0, a few down to the lower bound; the
    chunk's last 80 rows padding) and their carried states."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    real = (jnp.arange(rows) < rows - rows * 80 // 512)[:, None]
    draw = lambda key, *s: jax.random.normal(key, (layers, heads) + s)
    return (unit(draw(ks[0], rows, d)) * d ** -0.5,
            jnp.where(real, unit(draw(ks[1], rows, d)), 0.0),
            draw(ks[2], rows, d),
            jnp.where(real, lower * jax.random.uniform(
                ks[3], (layers, heads, rows, d)) ** 4, 0.0),
            jnp.where(real[:, 0], jax.random.uniform(
                ks[4], (layers, heads, rows), minval=0.05, maxval=0.95), 0.0),
            draw(ks[5], d, d))


def program(mod):
    """`layers` chunks, each of its own rows (the same rows a layer, and
    the compiler computes what reads no state once for all of them) from
    its own carried state, in one launch."""
    def f(*stacked):
        def layer(_, xs):
            return None, mod.kda_chunk(*xs)
        return jax.lax.scan(layer, None, stacked)[1]
    return jax.jit(f)


def recurrence(q, k, v, log_a, beta, state):
    """One head token by token in float64 (the module docstring's two
    lines); state [dv, dk] as the op keeps it."""
    q, k, v, log_a, beta = (np.asarray(x, np.float64)
                            for x in (q, k, v, log_a, beta))
    s = np.asarray(state, np.float64).T
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        s = np.exp(log_a[t])[:, None] * s
        s = s + np.outer(k[t], beta[t] * (v[t] - s.T @ k[t]))
        out[t] = s.T @ q[t]
    return out, s.T


def far(x, y):
    """The largest distance over the largest value."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    return float(np.abs(x - y).max() / np.abs(y).max())


def traced(run, iters):
    """(`reduce_trace` of `iters` runs, the trace's directory)."""
    d = tempfile.mkdtemp(prefix="study_kda_")
    start_trace(d)
    try:
        for _ in range(iters):
            out = run()
        jax.block_until_ready(out)
    finally:
        stop_trace()
    return reduce.reduce_trace(d), d


def top_ms(red, n, top=12):
    return [[k, 1e3 * s / n] for k, s in reduce.top_ops(red, top)]


def chunks(a):
    heads, rows, d = (2, 128, 32) if a.toy else (H, T, D)
    layers, iters = (2, 1) if a.toy else (a.layers, a.iters)
    device = jax.devices()[0]
    args = inputs(heads, rows, d, layers, a.seed)
    want64 = recurrence(*(x[0, 0] for x in args))
    row = {"shape": [heads, rows, d], "layers": layers, "iters": iters,
           "device": device.device_kind, "sides": {}}
    if not a.toy:
        with open(CONF) as f:
            cfg = json.load(f)
        rec = {"chunk_tokens": rows - rows * 80 // 512, "chunk_kda_live": 1}
        # the configuration's six KDA layers, scaled to `layers`
        row["least_ms"] = 1e3 * layers / 6 * costs_kda.least_seconds(
            "chunk", rec, cfg, published_peak(device.device_kind))
    first = None
    for name, path in sides(a):
        fn = program(load(path) if path else kda)
        o, s = jax.block_until_ready(fn(*args))
        first = first or (o, s)
        side = {"o_off_first": far(o, first[0]),
                "state_off_first": far(s, first[1]),
                "o_off_recurrence": far(o[0, 0], want64[0]),
                "state_off_recurrence": far(s[0, 0], want64[1]),
                "finite": bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())}
        if not a.toy:
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            side["host_ms"] = 1e3 * (time.perf_counter() - t0) / iters
            red, d = traced(lambda: fn(*args), iters)
            shutil.rmtree(d, ignore_errors=True)
            side["device_ms"] = 1e3 * sum(
                o["s"] for o in red["ops"].values()) / iters
            side["ops_a_call"] = sum(
                o["n"] for o in red["ops"].values()) / iters
            side["top_ops_ms"] = top_ms(red, iters)
            if "least_ms" in row:
                side["roofline_pct"] = 100 * row["least_ms"] / side[
                    "device_ms"]
        row["sides"][name] = side
        print(json.dumps({name: side}), flush=True)
    return row


def one_program(a):
    """This process's side of `--program`: the chunk program by scope."""
    from benchmarks.drivers.replica_ling3 import SCOPES
    from benchmarks.lib.ling3cfg import model_config
    from ray_tpu.models import ling3 as lm
    from ray_tpu.serve._engine import ContinuousEngine

    if a.kda:
        lm.kda_chunk = load(a.kda).kda_chunk
    with open(CONF) as f:
        conf = json.load(f)
    ek = dict(conf["serve"]["engine_kwargs"])
    if a.toy:
        cfg = lm.Ling3Config.nano(kda_impl="xla", dtype=jnp.float32)
        ek.update(max_slots=2, max_total=256, page_size=16,
                  num_pages={"full": 33, "kda": 3}, prefill_bucket=64,
                  prefill_chunk=64)
    else:
        cfg = model_config(conf)
    rows = ek["prefill_chunk"]
    eng = ContinuousEngine(lm, cfg, lm.init(jax.random.PRNGKey(0), cfg),
                           **ek)
    try:
        eng._ensure_device_state()
        fn = eng._fn(("prefill", rows))
        # entry 1's second chunk, every row real, its pages the table's first
        tabs = {k: np.arange(1, w + 1, dtype=np.int32) % eng._pool_pages[k]
                for k, w in eng._widths.items()}
        rest = (np.ones(rows, np.int32), tabs, np.int32(rows),
                np.int32(rows - 1))

        def run():
            out = fn(eng._params, eng._cache, *rest)
            eng._cache = out[1]
            return jax.block_until_ready(out[0])

        run()
        text = fn.lower(eng._params, eng._cache, *rest).compile().as_text()
        iters = 1 if a.toy else a.iters
        red, d = traced(run, iters)
        row = {"rows": rows, "iters": iters}
        if not a.toy:
            where = scopes.scope_map(text, SCOPES,
                                     {"ragged-dot": "moe_experts"})
            by = scopes.scope_seconds(d, {"jit_serve_prefill": [where]})
            total, n = reduce.module_time(red, "jit_serve_prefill")
            row.update(executions=n, device_ms=1e3 * total / n,
                       scope_ms={k: 1e3 * v / n for k, v in sorted(
                           by.items(), key=lambda kv: -kv[1])},
                       rest_ms=1e3 * (total - sum(by.values())) / n,
                       top_ops_ms=top_ms(red, n),
                       # the scope's own instructions, by name: the
                       # compiled text beside it says what each is
                       kda_chunk_ops_ms=sorted(
                           ([k, 1e3 * o["s"] / n, o["n"] / n]
                            for k, o in red["ops"].items()
                            if where.get(k) == "kda_chunk"),
                           key=lambda r: -r[1]))
            with open(os.path.join(ROOT, "chiprun_out", "pr60",
                                   "prefill_%s.hlo.txt" % a.name), "w") as f:
                f.write(text)
        shutil.rmtree(d, ignore_errors=True)
    finally:
        eng.stop()
    print("SIDE " + json.dumps(row), flush=True)


def programs(a):
    """Every side of `--program`, a child each; this process stays off the
    chip."""
    out = {}
    for name, kda_path in sides(a):
        cmd = [sys.executable, os.path.abspath(__file__), "--side", "--name",
               name.replace(os.sep, "_"), "--iters", str(a.iters)]
        cmd += ["--toy"] * a.toy + (["--kda", kda_path] if kda_path else [])
        done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        line = [ln for ln in done.stdout.splitlines()
                if ln.startswith("SIDE ")]
        out[name] = json.loads(line[-1][5:]) if line else {
            "error": done.returncode, "stdout": done.stdout[-2000:]}
        print(json.dumps({name: out[name]}), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=os.path.join(ROOT, "_parent"))
    ap.add_argument("--module", nargs="*", default=[])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--seed", type=int, default=60)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--side", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--kda", help=argparse.SUPPRESS)
    ap.add_argument("--name", default="tree", help=argparse.SUPPRESS)
    a = ap.parse_args()
    out = os.path.join(ROOT, "chiprun_out", "pr60")
    os.makedirs(out, exist_ok=True)
    if a.side:
        return one_program(a)
    name = ("study_program" if a.program else "study_chunk") + (
        "_toy.json" if a.toy else ".json")
    result = programs(a) if a.program else chunks(a)
    with open(os.path.join(out, name), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
