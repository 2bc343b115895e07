"""lfm2_moe (`serve-lfm2moe-ragextract`): the program against its plain
reference at the rehearsal's sizes, sound and broken — the broken-path
cases the issue asked for in `test_reference.py`, in a file of their own:
a PR edits no file the benchmark already has."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import manifest


@pytest.fixture(scope="module")
def lfm2_toy():
    """The rehearsal's sizes: the program's tree by the loader, the
    reference's own draw, one sequence."""
    import numpy as np

    from benchmarks.drivers.replica_lfm2_moe import shape_weights
    from benchmarks.lib.lfm2moecfg import model_config, reference_shape
    from benchmarks.reference import lfm2_moe_plain as plain
    from ray_tpu.models import lfm2_moe as lm

    with open(os.path.join(manifest.BENCH_DIR, "tests",
                           "rehearsal_ragextract.json")) as f:
        conf = dict(manifest.resolve(
            manifest.load(), "serve-lfm2moe-ragextract")["config"],
            **json.load(f)["config"])
    conf["serve"] = dict(conf["serve"], max_seq=128)
    seed = 2147483659
    cfg = model_config(conf)
    params = shape_weights(lm.init(jax.random.PRNGKey(seed % 2 ** 31), cfg),
                           conf["weights"], seed)
    sz = reference_shape(conf)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 512, 48))
    want = jax.jit(lambda p, t: plain.logits(p, t, sz))(
        plain.draw(seed, sz, conf["weights"]), toks)
    return lm, cfg, params, toks, np.asarray(want), (seed, sz, conf)


def _off(got, want):
    import numpy as np

    return np.sqrt(np.mean((np.asarray(got) - want) ** 2)) / want.std()


def test_lfm2_reference_imports_nothing_of_the_program():
    from benchmarks.reference import check_lfm2_moe, lfm2_moe_plain

    for mod in (lfm2_moe_plain, check_lfm2_moe):
        with open(mod.__file__) as f:
            src = f.read()
        assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_lfm2_program_is_the_reference_at_the_rehearsal_sizes(lfm2_toy):
    lm, cfg, params, toks, want, _ = lfm2_toy
    got = jax.jit(lambda p, t: lm.apply(p, t, cfg))(params, toks[None])[0]
    assert _off(got, want) < 1e-5


@pytest.mark.parametrize("broken", [
    "conv_dropped", "no_out_gate", "silu_on_taps", "no_head_norms",
    "rope_shifted", "bias_unselecting", "bias_weighing", "no_normaliser",
    "top3_of_4"])
def test_lfm2_broken_path_stands_off_the_reference(lfm2_toy, broken,
                                                   monkeypatch):
    """What the cell's controls put into the program on the chip
    (scripts/study_lfm2moe_controls.py), at toy size: each moves the
    logits' relative rms past the rehearsal's limit (2e-4), a hundred
    times the sound program's distance.  (The two faults of the carried
    tail need a chunked prefill: tests/test_lfm2_moe.py holds them.)"""
    from ray_tpu.ops.moe import _sigmoid_scores

    lm, cfg, params, toks, want, _ = lfm2_toy
    route = lm.route_sigmoid_topk

    def rerouted(weigh):
        def routed(h, w, k, *, bias, eps):
            s = _sigmoid_scores(h, w)
            _, idx = jax.lax.top_k(s + bias, k)
            take = lambda a: jnp.take_along_axis(a, idx, axis=-1)
            return (weigh(take(s), take(jnp.broadcast_to(bias, s.shape)),
                          eps), idx.astype(jnp.int32))
        return routed

    patch = lambda name, fn: monkeypatch.setattr(lm, name, fn)
    if broken == "conv_dropped":
        out = lm._conv_out
        patch("_conv_out", lambda *a: jnp.zeros_like(out(*a)))
    elif broken == "no_out_gate":
        out = lm._conv_out
        patch("_conv_out", lambda y, c, *a: out(y, jnp.ones_like(c), *a))
    elif broken == "silu_on_taps":
        chunk = lm.conv_chunk
        patch("conv_chunk", lambda r, t, w, b, act, scope: chunk(
            r, t, w, b, jax.nn.silu, scope))
    elif broken == "no_head_norms":
        norm = lm.rms_norm
        patch("rms_norm", lambda x, w, eps: (
            x if x.ndim == 4 else norm(x, w, eps)))
    elif broken == "rope_shifted":
        rope = lm.apply_rope_halves
        patch("apply_rope_halves", lambda x, pos, th: rope(
            x, pos + (1 if x.shape[1] > cfg.n_kv_heads else 0), th))
    elif broken == "bias_unselecting":
        patch("route_sigmoid_topk", lambda h, w, k, *, bias, eps: route(
            h, w, k, bias=None, eps=eps))
    elif broken == "bias_weighing":
        patch("route_sigmoid_topk", rerouted(
            lambda s, b, eps: (s + b) / (jnp.sum(s + b, -1, keepdims=True)
                                         + eps)))
    elif broken == "no_normaliser":
        patch("route_sigmoid_topk", rerouted(lambda s, b, eps: s))
    else:
        patch("route_sigmoid_topk", lambda h, w, k, **kw: route(
            h, w, k - 1, **kw))
    got = jax.jit(lambda p, t: lm.apply(p, t, cfg))(params, toks[None])[0]
    assert _off(got, want) > 2e-4, broken


def test_lfm2_reference_in_fp8_stands_off_itself(lfm2_toy):
    """The control put into the REFERENCE: every matrix rounded to
    fp8-e4m3, the nearest precision below the configuration's."""
    from benchmarks.reference import lfm2_moe_plain as plain

    *_, toks, want, (seed, sz, conf) = lfm2_toy
    low = dict(sz, control="fp8_weights")
    got = jax.jit(lambda p, t: plain.logits(p, t, low))(
        plain.draw(seed, sz, conf["weights"]), toks)
    assert _off(got, want) > 2e-2


def test_lfm2_served_tokens_check_passes_greedy_and_catches_a_wrong_token(
        lfm2_toy):
    """`check_lfm2_moe.served_gaps` (the layers the outer loop, each
    layer's leaves drawn when its turn comes, the head a quarter of the
    vocabulary at a time) over a greedy continuation of the program's and
    over one with a token changed."""
    import numpy as np

    from benchmarks.reference.check_lfm2_moe import served_gaps

    lm, cfg, params, toks, want, (seed, sz, conf) = lfm2_toy
    seq = [int(t) for t in toks[:12]]
    fwd = jax.jit(lambda p, t: lm.apply(p, t, cfg))
    for _ in range(8):
        pad = jnp.zeros(24, jnp.int32).at[:len(seq)].set(jnp.asarray(seq))
        seq.append(int(jnp.argmax(fwd(params, pad[None])[0, len(seq) - 1])))
    good = {"rid": 1, "tokens": seq[:12], "served": seq[12:]}
    bad = dict(good, rid=2, served=seq[12:16] + [(seq[16] + 1) % 512]
               + seq[17:])
    spec = {"rows": 16, "max_context": 32, "replay_keep": 4}
    res = {r["rid"]: r for r in served_gaps(
        seed, sz, conf["weights"], [good, bad], spec, 8)}
    assert res[1]["max_gap"] < 1e-4 and res[1]["n"] == 8
    assert res[1]["n_argmax"] == 8 and res[1]["median_top2_gap"] > 0
    assert res[2]["max_gap"] > 1e-3 and res[2]["n_argmax"] < 8
