"""The plain reference against the program's gpt.apply / loss at a tiny
size on the CPU (float32 both sides: agreement to rounding), and the
benchmark's parameter count against the program's."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import costs, manifest
from benchmarks.lib.modelcfg import gpt_config
from benchmarks.reference import gpt2_plain as ref
from ray_tpu.models import gpt

TINY = {"vocab_size": 256, "n_layer": 3, "n_embd": 64, "n_head": 4,
        "head_dim": 16, "n_inner": 256, "n_positions": 64,
        "attn_bias": True, "compute_dtype": "float32",
        "param_dtype": "float32"}


@pytest.fixture(scope="module")
def model():
    cfg = gpt_config(TINY, remat=False, attention_impl="xla")
    p = gpt.init(jax.random.PRNGKey(1), cfg)
    # biases and norms are zeros/ones at init: perturb so they matter
    leaves, tree = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    p = jax.tree.unflatten(tree, [
        a + 0.02 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0, 256)
    return cfg, p, toks


def test_logits_agree_with_gpt_apply(model):
    cfg, p, toks = model
    a = gpt.apply(p, toks[:, :-1], cfg)
    b = ref.logits(p, toks[:, :-1])
    assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_loss_and_gradient_norm_agree(model):
    import optax

    cfg, p, toks = model
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    want, g = jax.value_and_grad(lambda q: gpt.loss_fn(q, batch, cfg))(p)
    got, gn = ref.loss_and_grad_norm(p, batch["inputs"], batch["targets"],
                                     z=cfg.z_loss)
    assert float(abs(want - got)) < 1e-5
    assert float(abs(optax.global_norm(g) - gn)) < 1e-4 * float(gn)
    plain = ref.loss(p, batch["inputs"], batch["targets"])
    assert float(plain) < float(got)            # z-loss adds, never removes


def test_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        src = f.read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-large",
                                  "gpt2-xl-fsdp4"])
def test_parameter_count_matches_the_program(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", name + ".json")) as f:
        conf = json.load(f)
    assert costs.n_params(conf) == gpt.num_params(gpt_config(conf))
    assert conf["n_embd"] == conf["n_head"] * conf["head_dim"]
    assert conf["n_inner"] == 4 * conf["n_embd"]


def test_served_tokens_check_passes_greedy_and_catches_a_wrong_token(model):
    from benchmarks.reference.check import served_gaps

    cfg, p, toks = model
    prompt = toks[0, :12]
    out = gpt.generate(p, cfg, prompt[None], 10)[0, 12:]
    good = {"rid": 1, "tokens": prompt.tolist(), "served": out.tolist()}
    bad = dict(good, rid=2, served=out.tolist()[:4]
               + [int(out[4] + 1) % 256] + out.tolist()[5:])
    res = {r["rid"]: r for r in served_gaps(p, [good, bad], 32)}
    assert res[1]["max_gap"] < 1e-4 and res[1]["n"] == 10
    assert res[1]["n_argmax"] == 10 and res[1]["median_top2_gap"] > 0
    assert res[2]["max_gap"] > 1e-3 and res[2]["n_argmax"] < 10
