"""What a seeded draw of `lfm2-8b-a1b-l13` has to be scaled by so that the
reference check's controls DECIDE something (PERF.md section 6, PR 63; the
numbers stand in the configuration's `weights.why`): the plain reference
alone (benchmarks/reference/lfm2_moe_plain.py, its own draw, float32) over
one random sequence at the published widths, a candidate `weights` a pass —
per layer the root mean square of the residual stream and of what the
mixer and the feed-forward ADD to it, per expert layer the share of
token-expert pairs the bias moves and the largest load over the mean, and
the logits' spread and the median lead of the best over the runner-up.

    python scripts/study_lfm2moe_draw.py [--rows 2048] [seed]

on the chip (~1 min a candidate, no cluster); `--toy` at the rehearsal's
sizes on the CPU.  Writes chiprun_out/pr63/draw.json.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOY = "--toy" in sys.argv

CANDIDATES = {
    "plain": {"router_bias_std": 0.06},
    "a": {"scales": {"q_norm": 2.0, "wo": 4.0, "wd": 2.0},
          "router_bias_std": 0.06},
    "b": {"scales": {"q_norm": 2.0, "wo": 4.0, "wd": 2.0, "w_in": 0.7},
          "router_bias_std": 0.06},
    "c": {"scales": {"q_norm": 2.5, "wo": 4.0, "wd": 3.0, "w_in": 0.7},
          "router_bias_std": 0.08},
}


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import manifest
    from benchmarks.lib.lfm2moecfg import reference_shape
    from benchmarks.reference import lfm2_moe_plain as ref

    seed = ([int(a) for a in sys.argv[1:] if a.isdigit()] or [63])[0]
    rows = int(sys.argv[sys.argv.index("--rows") + 1]) \
        if "--rows" in sys.argv else 2048
    conf = manifest.resolve(manifest.load(),
                            "serve-lfm2moe-ragextract")["config"]
    if TOY:
        with open(os.path.join(ROOT, "benchmarks", "tests",
                               "rehearsal_ragextract.json")) as f:
            conf.update(json.load(f)["config"])
        rows = 64
    sz = reference_shape(conf)
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    blk = min(rows, 256)

    @jax.jit
    def routing(x, norm, router, bias):
        u = ref.rms_norm(x, norm, sz["eps"])
        with jax.default_matmul_precision("highest"):
            w1 = ref.route(u, router, bias, sz) > 0
            w0 = ref.route(u, router, jnp.zeros_like(bias), sz) > 0
        loads = jnp.sum(w1, 0)
        return (jnp.sum(w1 & ~w0) / jnp.sum(w1),
                jnp.max(loads) / jnp.mean(loads))

    conv = jax.jit(lambda x, lp: ref.conv_layer(x, lp, sz))
    attn = jax.jit(lambda x, lp: ref.attention_layer(x, lp, sz, blk))
    ffn = {d: jax.jit(lambda x, lp, d=d: ref.ffn_layer(x, lp, sz, d, blk))
           for d in (True, False)}
    toks = np.random.default_rng(seed).integers(0, sz["vocab"], rows)
    out = {}
    for name, weights in CANDIDATES.items():
        t0 = time.time()
        fixed = ref.fixed_leaves(sz, weights)
        leaf = lambda l, n: ref.draw_leaf(seed, sz, weights, l, n)
        x = leaf(-1, "embed")[jnp.asarray(toks)].astype(jnp.float32)
        layers = []
        for l, kind in enumerate(sz["layer_types"]):
            lp = {**{n: leaf(l, n) for n in ref.MIXER_LEAVES[kind]}, **fixed}
            y = (conv if kind == "conv" else attn)(x, lp)
            dense = ref.is_dense(sz, l)
            lp = {**{n: leaf(l, n) for n in ref.FFN_LEAVES[dense]}, **fixed}
            z = ffn[dense](y, lp)
            row = {"layer": l, "mixer": kind, "x": rms(x),
                   "mixer_adds": rms(y - x), "ffn_adds": rms(z - y)}
            if not dense:
                moved, ratio = routing(y, fixed["ffn_norm"], lp["router"],
                                       lp["router_bias"])
                row.update(pairs_moved_by_bias=float(moved),
                           load_max_over_mean=float(ratio))
            layers.append(row)
            x = z
        lg = ref.readout(x, jnp.ones(sz["d_model"]), leaf(-1, "embed"), sz)
        top = jax.lax.top_k(lg, 2)[0]
        out[name] = {
            "weights": weights, "layers": layers, "logit_std": rms(
                lg - lg.mean(-1, keepdims=True)),
            "median_top2_gap": float(jnp.median(top[:, 0] - top[:, 1])),
            "argmax_is_input_share": float(jnp.mean(
                jnp.argmax(lg, -1) == jnp.asarray(toks))),
            "seconds": time.time() - t0}
        print(json.dumps({"candidate": name, **out[name]}), flush=True)
    dest = os.path.join(ROOT, "chiprun_out", "pr63")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, "draw.json"), "w") as f:
        json.dump({"seed": seed, "rows": rows, "candidates": out}, f,
                  indent=1)


if __name__ == "__main__":
    main()
