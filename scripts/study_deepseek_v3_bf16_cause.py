"""Why the sound `deepseek_v3` program's logits stand 15-24% of their
spread off its float32 reference at published widths (PERF.md section 6,
PR 44), measured inside the REFERENCE alone, where the one thing changed
can be named.

One sequence through `benchmarks/reference/deepseek_v3_plain.py`'s pieces
at the cell's configuration (its own weight draw, a layer's leaves at a
time), three times:

  f32      the pieces as the check runs them (every product at the highest
           precision)
  bf16     the same pieces with every product at the chip's default
           precision — operands rounded to bfloat16, sums in float32: the
           program's arithmetic in kind — everything else float32
  bf16, routed as f32
           as `bf16`, but every token goes to the experts the `f32` pass
           chose (weighed by this pass's own scores)

and reports, for the last rows' logits, the root mean square of (pass -
f32) as a share of the f32 rows' standard deviation, beside the share of
(token, expert layer) whose set of eight experts differs between `f32` and
`bf16` (and of those whose HELD experts differ).  If the third pass reads far below the second, what separates
bfloat16 arithmetic from the reference is the router's near-ties; if not,
it is not.

    python scripts/study_deepseek_v3_bf16_cause.py [--toy] [seed]

On the chip (~3 min), one process, no cluster.  Writes
chiprun_out/pr44/cause.json.  `--toy`: the control flow at toy sizes on
the CPU, whose default precision IS float32 (every distance ~1e-6, no
flip).
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import manifest
from benchmarks.lib.deepseekcfg import reference_shape
from benchmarks.reference import deepseek_v3_plain as ref

TOY = "--toy" in sys.argv
N, KEEP, ROWS = (48, 16, 16) if TOY else (4096, 512, 1024)
F_PARTS = 2 if TOY else 8


def say(**kw):
    print(json.dumps(kw), flush=True)


def pieces(sz, precision: str, heads: int):
    """The reference's pieces, each one program, its products at
    `precision` (the piece itself asks for the highest: its undecorated
    body is what runs here)."""
    def at(fn):
        def run(*a):
            with jax.default_matmul_precision(precision):
                return fn(*a)
        return jax.jit(run)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    D, cap = sz["d_model"], -(-4 * N * sz["top_k"] // sz["n_experts"])

    def weigh(h, router, idx):
        s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
        w = jnp.take_along_axis(s, idx, axis=1)
        return (w / (jnp.sum(w, -1, keepdims=True) + ref.EPS_TOPK)
                * sz["routed_scale"])

    return {
        "latents": at(lambda x, wkv_a: ref.latents.__wrapped__(
            x, ones(D), wkv_a, ones(sz["kv_rank"]), sz)),
        "attend": at(lambda x, c_kv, k_pe, wq_a, wq_b, wkv_b, wo:
                     ref.attend.__wrapped__(
                         x, c_kv, k_pe, ones(D), wq_a, ones(sz["q_rank"]),
                         wq_b, wkv_b, wo, sz, heads, ROWS)),
        "normed": at(lambda x: ref.normed.__wrapped__(x, ones(D), sz)),
        "dense": at(lambda x, h, wg, wu, wd, i: x + ref.dense_part.__wrapped__(
            h, wg, wu, wd, sz, i, F_PARTS)),
        "route": at(lambda h, r, b: ref.route.__wrapped__(h, r, b, sz)),
        "weigh": at(weigh),
        "expert": at(lambda x, h, w, idx, e, wg, wu, wd:
                     x + ref.expert.__wrapped__(h, w, idx, e, wg, wu, wd, sz,
                                                cap)),
        "shared": at(lambda x, h, wg, wu, wd, i:
                     x + ref.shared_expert.__wrapped__(h, wg, wu, wd, sz, i)),
        "readout": at(lambda x, w: ref.readout.__wrapped__(x, ones(D), w,
                                                           sz)),
    }


def forward(run, sz, leaf, toks, forced=None):
    """(logits of the last KEEP rows [KEEP, V], [idx [N, k] of every
    expert layer]); `forced`: such a list, whose experts are used."""
    x = leaf(-1, "embed")[toks].astype(jnp.float32)
    at, routes = np.int32, []
    for l in range(sz["n_layers"]):
        c_kv, k_pe = run["latents"](x, leaf(l, "wkv_a"))
        x = run["attend"](x, c_kv, k_pe, *(leaf(l, n) for n in (
            "wq_a", "wq_b", "wkv_b", "wo")))
        h = run["normed"](x)
        if l < sz["n_dense"]:
            w = [leaf(l, n) for n in ("w_gate", "w_up", "w_down")]
            for i in range(F_PARTS):
                x = run["dense"](x, h, *w, at(i))
            continue
        router = leaf(l, "router")
        w, idx = run["route"](h, router, leaf(l, "router_bias"))
        if forced is not None:
            idx = forced[len(routes)]
            w = run["weigh"](h, router, idx)
        routes.append(idx)
        held = [leaf(l, n) for n in ("wg", "wu", "wd")]
        for e in range(sz["held"]):
            x = run["expert"](x, h, w, idx, at(sz["first"] + e), *held)
        del held
        side = [leaf(l, n) for n in ("shared_gate", "shared_up",
                                     "shared_down")]
        for i in range(sz["n_shared"]):
            x = run["shared"](x, h, *side, at(i))
    return run["readout"](x[-KEEP:], leaf(-1, "unembed")), routes


def main():
    seed = next((int(a) for a in sys.argv[1:] if a.isdigit()), 11)
    conf = manifest.resolve(manifest.load(),
                            "serve-deepseekv3-longctx")["config"]
    heads = 8
    if TOY:
        with open(os.path.join(ROOT, "benchmarks", "tests",
                               "rehearsal_longctx.json")) as f:
            conf = {**conf, **json.load(f)["config"]}
        heads = 2
    sz, weights = reference_shape(conf), conf["weights"]
    dev = jax.devices()[0]
    say(phase="device", platform=dev.platform, kind=dev.device_kind,
        tokens=N, rows_read=KEEP)
    leaf = lambda l, name: ref.draw_leaf(seed, sz, weights, l, name)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, sz["vocab"], N), jnp.int32)
    t0 = time.time()
    want, chosen = forward(pieces(sz, "highest", heads), sz, leaf, toks)
    rough = pieces(sz, "default", heads)
    got, theirs = forward(rough, sz, leaf, toks)
    held, _ = forward(rough, sz, leaf, toks, forced=chosen)
    spread = float(jnp.std(want))
    rel = lambda a: float(jnp.sqrt(jnp.mean((a - want) ** 2))) / spread
    sets = lambda idx: np.sort(np.asarray(idx), axis=1)
    flips = [float((sets(a) != sets(b)).any(axis=1).mean())
             for a, b in zip(chosen, theirs)]
    # what a chip's share of the experts feels: a token whose HELD experts
    # changed (an absent expert adds nothing here either way)
    lo, hi = sz["first"], sz["first"] + sz["held"]
    on = lambda idx: (np.asarray(idx)[:, :, None]
                      == np.arange(lo, hi)).any(axis=1)
    felt = [float((on(a) != on(b)).any(axis=1).mean())
            for a, b in zip(chosen, theirs)]
    out = {"phase": "cause", "seed": seed, "tokens": N, "rows_read": KEEP,
           "platform": dev.platform,
           "bf16_rel_rms": rel(got), "bf16_routed_as_f32_rel_rms": rel(held),
           "argmax_agree_bf16": float((got.argmax(-1)
                                       == want.argmax(-1)).mean()),
           "argmax_agree_routed_as_f32": float((held.argmax(-1)
                                                == want.argmax(-1)).mean()),
           "tokens_rerouted_share_by_layer": flips,
           "tokens_rerouted_on_held_share_by_layer": felt,
           "seconds": time.time() - t0}
    say(**out)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "pr44"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pr44", "cause.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
