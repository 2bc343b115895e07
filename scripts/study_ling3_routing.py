"""Why `ling-3.0-flash-l7-e128.json`'s `weights` scale `w_qkv` by 1/4 and
draw the router's correction bias at std 0.005 (PERF.md section 6, PR 47;
the file's `weights.why` quotes these readings).

A CPU study at REDUCED widths (d_model 256, 4 heads of 64, experts of 32;
the published layer pattern, 512 experts in 8 groups, top-8 of 4 groups,
128 held): `ling3.apply` over 384 random tokens, seeds 1-6, with
`route_sigmoid_grouped` spied on.  For each recipe, by seed:

  P(held)       the share of (token, choice) pairs that fall on the 128
                held experts (groups 0 and 1): a quarter if routing is even
  load max/mean the hottest of 512 experts' pairs over the mean
  touched/15tok how many held experts 15 tokens' choices touch, mean over
                200 draws and the six expert layers: what a decode step of
                15 streams reads, 7-8 ms of the chip's ~22 a step
  cv%           the spread of `touched` over the six seeds: which experts a
                step touches must not be the SEED's, or `itl_p99_ms` is
  rho           |mean_t h|^2 / mean_t |h|^2 of the router's input by layer:
                the share of it that is one vector common to every token

    python scripts/study_ling3_routing.py [recipe ...]      (~1.5 min each)

Readings (this machine's CPU, 2026-10-01; the chip's spreads beside them
are two sets of six runs of the cell as it then stood, PR 47's first
session):

  base          (deepseek-v3-l5-e16.json's recipe: plain draw, bias 0.05)
                P(held) 0.240-0.285, load max/mean 15.9-22.4, touched
                19.3-21.9, cv 4.16%, rho 0.12-0.20
                -> on the chip a step read 19.0-21.4 ms BY SEED and
                `itl_p99_ms` spread 3.03% / 3.77%
  qkv025        (w_qkv x 1/4, bias 0.05)  P(held) 0.240-0.260, load
                max/mean 7.6-9.8, touched 23.1-24.7, cv 2.48%, rho 0.01
                -> 2.05% / 1.74%
  qkv025_b01    (bias 0.01)  load max/mean 3.5-3.7, touched 25.5-27.3,
                cv 2.15%
  qkv025_b005   (w_qkv x 1/4, bias 0.005: the file's)  P(held)
                0.241-0.258, load max/mean 3.1-3.5, touched 25.6-27.5,
                cv 2.09%, rho 0.01
                -> 0.93% / 0.71%
  qkv025_b0     (no bias at all)  load max/mean 3.0-3.3, cv 1.87%: what
                chance alone gives 512 experts; the file's 0.005 keeps a
                bias in the program's path and sits at that floor

The cause, not the fit: SiLU behind a unit-spread conv input has a positive
mean, q . k is then positive on average and every KDA layer adds nearly the
same vector to every token (rho); the router's scores then share a large
common part, and which experts it favours is the draw's.  A trained
correction bias BALANCES loads; a random one of the scores' own size
(0.05 against a spread of 0.2) un-balances them.  Both scales move the
random weights toward what training gives (a router input with no common
vector, balanced experts); neither touches a width or the program.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from benchmarks.drivers.replica_ling3 import shape_weights   # noqa: E402
from ray_tpu.models import deepseek_v3 as dm        # noqa: E402
from ray_tpu.models import ling3 as lm              # noqa: E402
from ray_tpu.models import served                   # noqa: E402

BASE = {"scales": {"w_f": 0.25}, "router_bias_std": 0.05,
        "a_range": [1.0, 16.0], "fresh_log_a": [0.002, 1.0]}
QKV = {"w_f": 0.25, "w_qkv": 0.25}
RECIPES = {
    "base": BASE,
    "qkv025": dict(BASE, scales=QKV),
    "qkv006": dict(BASE, scales=dict(QKV, w_qkv=0.0625)),
    "qkv025_b01": dict(BASE, scales=QKV, router_bias_std=0.01),
    "qkv025_b005": dict(BASE, scales=QKV, router_bias_std=0.005),
    "qkv025_b0": dict(BASE, scales=QKV, router_bias_std=0.0),
    "embed4": dict(BASE, scales={"w_f": 0.25, "embed": 4}),
}
HELD, STREAMS = 128, 15


def main():
    served.DRAW_PIECE = 1 << 16
    picked, rho = [], []
    route = dm.route_sigmoid_grouped

    def spy(h, router, bias, k, **kw):
        w, idx = route(h, router, bias, k, **kw)
        h = np.asarray(h)
        picked.append(np.asarray(idx))
        rho.append(float((h.mean(0) ** 2).sum() / (h ** 2).sum(1).mean()))
        return w, idx

    dm.route_sigmoid_grouped = spy
    cfg = lm.Ling3Config(
        vocab_size=2048, n_layers=7, n_dense=1, layer_group=6, d_model=256,
        n_heads=4, d_head=64, kv_rank=64, d_nope=32, d_rope=16, d_v=32,
        d_ff=512, d_expert=32, d_shared=32, n_experts=512, experts_first=0,
        experts_held=HELD, max_seq=1024, kv_block=128, moe_tile=128,
        dtype=jnp.float32, param_dtype=jnp.float32)
    for name in sys.argv[1:] or list(RECIPES):
        rows = []
        for seed in range(1, 7):
            params = shape_weights(lm.init(jax.random.PRNGKey(seed), cfg),
                                   RECIPES[name], seed, cfg.gate_lower)
            toks = jax.random.randint(jax.random.PRNGKey(100 + seed),
                                      (1, 384), 0, cfg.vocab_size)
            picked.clear()
            rho.clear()
            lm.apply(params, toks, cfg)
            held = float(np.mean([(i < HELD).mean() for i in picked]))
            load = [np.bincount(i.ravel(), minlength=cfg.n_experts)
                    for i in picked]
            rng = np.random.default_rng(0)
            touched = []
            for i in picked:
                for _ in range(200):
                    sub = i[rng.choice(i.shape[0], STREAMS,
                                       replace=False)].ravel()
                    touched.append(len(set(sub[sub < HELD].tolist())))
            rows.append((held, float(np.mean([c.max() / c.mean()
                                              for c in load])),
                         float(np.mean(touched))))
        t = [r[2] for r in rows]
        print(name, "P(held) by seed:", [round(r[0], 3) for r in rows],
              "load max/mean:", [round(r[1], 1) for r in rows],
              "touched/15tok:", [round(x, 2) for x in t],
              "cv%", round(100 * float(np.std(t) / np.mean(t)), 2),
              "rho by layer (seed 6):", [round(r, 2) for r in rho],
              flush=True)


if __name__ == "__main__":
    main()
