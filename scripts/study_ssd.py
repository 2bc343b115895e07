"""ops/ssd.py alone on the chip, at Falcon-H1-34B's widths (32 heads of
128 channels, 256 states, 2 groups): the step's kernel over an arena of
six layers by live slots, the chunk's kernel at both prefill programs'
rows (six layers chained in one program) — each held to the XLA form
first, then timed beside it, with the bytes and
operations benchmarks/lib/costs_ssd.py counts beside the time.

    python scripts/study_ssd.py [--slots 64] [--toy]

On the chip through `chiprun` (~1.5 min); `--toy` runs the control flow at
toy size on the CPU (its times are the interpreter's and are not
printed).  One process, no cluster.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOY = "--toy" in sys.argv
if TOY:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.lib import costs_ssd, peaks  # noqa: E402
from ray_tpu.ops.ssd import ssd_chunk, ssd_step  # noqa: E402

CONF = {"mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256,
        "mamba_n_groups": 2, "num_hidden_layers": 6}
if TOY:
    CONF.update(mamba_n_heads=4, mamba_d_head=8, mamba_d_state=128,
                num_hidden_layers=2)


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def main():
    slots = int(sys.argv[sys.argv.index("--slots") + 1]) \
        if "--slots" in sys.argv else (4 if TOY else 64)
    H, P, N, G, L = (CONF["mamba_n_heads"], CONF["mamba_d_head"],
                     CONF["mamba_d_state"], CONF["mamba_n_groups"],
                     CONF["num_hidden_layers"])
    kernel = "pallas_interpret" if TOY else "pallas"
    dev = jax.devices()[0]
    say(phase="device", platform=dev.platform, kind=dev.device_kind)
    pk = None if TOY else peaks.peak(dev.device_kind)
    r = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    a, d = -jnp.exp(f(H)), f(H)

    # -- the step: every layer of an arena, by live slots
    arena = f(L, slots + 1, H, P, N)
    x, dt, b, c = f(slots, H, P), jnp.exp(f(slots, H) - 3), f(slots, G, N), \
        f(slots, G, N)
    idx = jnp.arange(1, slots + 1, dtype=jnp.int32)
    for live_n in sorted({slots, slots // 2, 1}, reverse=True):
        live = (jnp.arange(slots) < live_n).astype(jnp.int32)

        def layers(arena, impl):
            ys = []
            for l in range(L):
                y, arena = ssd_step(x, dt, a, b, c, d, arena, l, idx, live,
                                    impl=impl)
                ys.append(y)
            return jnp.stack(ys), arena

        ker = jax.jit(lambda s: layers(s, kernel), donate_argnums=0)
        ref = jax.jit(lambda s: layers(s, "xla"))
        want_y, want_s = ref(arena)
        got_y, got_s = ker(jnp.array(arena))
        err = float(jnp.abs(got_y - want_y).max() / jnp.abs(want_y).max())
        err_s = float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max())
        state = got_s                   # donated from call to call
        t0 = time.perf_counter()
        for _ in range(10):
            _, state = ker(state)
        jax.block_until_ready(state)
        secs = (time.perf_counter() - t0) / 10
        rec = {"ssd_live": live_n}
        say(phase="step", live=live_n, slots=slots, rel_err_y=err,
            rel_err_state=err_s,
            **({} if TOY else {
                "ms": 1e3 * secs, "ms_a_live_slot": 1e3 * secs / live_n,
                "least_ms": 1e3 * costs_ssd.least_seconds(
                    "step", rec, CONF, pk),
                "bytes": costs_ssd.step_bytes(live_n, CONF)}))
        del state, got_s, want_s

    # -- the chunk: one sequence, both programs' rows, L layers chained in
    # -- ONE program (a call a layer is a dispatch's 0.4 ms whatever its
    # -- body: PERF.md section 5, PR 61)
    for T in ((32,) if TOY else (256, 512)):
        x, dt = f(T, H, P), jnp.exp(f(T, H) - 3)
        b, c, s0 = f(T, G, N), f(T, G, N), f(H, P, N)
        block = 16 if TOY else 128

        def run(impl, dtype):
            def layers(x, dt, a, b, c, d, s):
                for _ in range(L):
                    y, s = ssd_chunk(x, dt, a, b, c, d, s, impl=impl,
                                     dtype=dtype, block=block)
                    x = x + 1e-3 * y        # a layer feeds the next
                return y, s
            return jax.jit(layers)

        want_y, want_s = run("xla", jnp.float32)(x, dt, a, b, c, d, s0)
        for dtype in (jnp.float32, jnp.bfloat16):
            secs, (y, s1) = timed(run(kernel, dtype), x, dt, a, b, c, d, s0)
            xla_s, _ = timed(run("xla", dtype), x, dt, a, b, c, d, s0)
            rec = {"chunk_tokens": T, "chunk_ssd_live": 1}
            say(phase="chunk", rows=T, layers=L,
                dtype=jnp.dtype(dtype).name,
                rel_err_y=float(jnp.abs(y - want_y).max()
                                / jnp.abs(want_y).max()),
                rel_err_state=float(jnp.abs(s1 - want_s).max()
                                    / jnp.abs(want_s).max()),
                **({} if TOY else {
                    "ms_a_layer": 1e3 * secs / L,
                    "xla_ms_a_layer": 1e3 * xla_s / L,
                    "least_ms_a_layer": 1e3 * costs_ssd.least_seconds(
                        "chunk", rec, CONF, pk) / L}))

if __name__ == "__main__":
    main()
