"""The cell `serve-deepseekv3-longctx`: its files resolve by name, the
configuration keeps every number of the catalog's row, the traffic's cycle
is the same for every seed, the attention's costs agree with hand counts,
each reader it brings reads a fixture (and reads nothing, without raising,
where a program lacks the counters), and the check's reference runs in
blocks as it runs whole."""

import json
import os

import pytest

from benchmarks.lib import costs_mla as costs
from benchmarks.lib import costs_moe, manifest, peaks
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import (trace_moe_roofline_at,
                                        trace_scope_roofline)

CELL = "serve-deepseekv3-longctx"
# the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 16160}
TWINS = ("engine.decode_step_device_ms", "engine.decode_step_ms",
         "engine.prefill_ms_per_token", "engine.prefill_share",
         "engine.prefill_pad_share", "engine.ttft_queue_share",
         "engine.chunk_blocked_share", "engine.decode_blocked_share",
         "engine.host_share", "engine.dispatch_share",
         "engine.step_dispatch_ms", "engine.step_wait_ms",
         "engine.admit_iter_ms", "router.hop_p50_ms", "moe.time_share",
         "moe.load_max_over_mean")


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load(), CELL)


def _params(name):
    with open(os.path.join(manifest.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)["params"]


def test_the_cell_resolves_with_every_metric_of_the_issue(cell):
    assert cell["cell"]["chips"] == 1
    assert cell["traffic"]["kind"] == "serve_open_longctx"
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p75_ms", "setup_s"}
    assert {m["moves"] for m in cell["per_layer"]} == {"ttft_p75_ms"}
    names = {m["name"]: m for m in cell["per_layer"]}
    # at least these: a later PR may append a metric to the cell
    assert set(names) >= {n + ".longctx" for n in TWINS + (
        "mla.time_share", "mla.step_roofline", "mla.chunk_roofline",
        "moe.experts_roofline")}
    assert all(m["workloads"] == [CELL] for m in names.values())
    for n in TWINS:                 # a twin reads what its sibling reads
        assert _params(n + ".longctx") == _params(n + ".mixedctx")
    assert {n: names[n + ".longctx"]["layer"] for n in (
        "mla.time_share", "mla.step_roofline", "moe.experts_roofline")} == {
        "mla.time_share": "kernels ops/attention",
        "mla.step_roofline": "kernels ops/attention",
        "moe.experts_roofline": "kernels ops/moe"}


def test_the_configuration_keeps_every_number_of_the_catalog_row(cell):
    cfg = cell["config"]
    for k, v in PUBLISHED.items():
        assert cfg[k] == REDUCED.get(k, v), k
    assert cfg["reduced"] == list(REDUCED) == cell["config_entry"]["reduced"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert set(cfg["assumed"]) >= {"router", "e_score_correction_bias",
                                   "rope", "softmax_scale", "dtype"}
    assert "multi-token" in cfg["out_of_scope"]
    assert cfg["deployment_share"] == {"chips_per_layer": 16,
                                       "experts_first": 0, "vocab_slices": 8}
    assert (cfg["compute_dtype"], cfg["param_dtype"]) == ("bfloat16",) * 2
    ek = cfg["serve"]["engine_kwargs"]
    assert ek["num_pages"] == {"full": 1 + ek["max_slots"] * (
        ek["max_total"] // ek["page_size"])} == {"full": 4353}
    assert (ek["max_slots"], ek["page_size"], ek["max_total"],
            ek["prefill_chunk"], ek["prefill_bucket"]) == (
        32, 128, 17408, 512, 512)
    assert set(cfg["memory"]) >= {"arithmetic", "rehearsed", "measured"}
    assert cfg["weights"]["scales"] == {"wq_b": 2}


def test_the_config_maps_onto_the_program_and_the_reference(cell):
    from benchmarks.lib.deepseekcfg import model_config, reference_shape

    cfg = model_config(cell["config"])
    assert (cfg.n_layers, cfg.n_dense, cfg.n_experts, cfg.experts_held,
            cfg.experts_first, cfg.vocab_size) == (5, 1, 256, 16, 0, 16160)
    assert (cfg.d_model, cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.d_nope,
            cfg.d_rope, cfg.d_v, cfg.d_ff, cfg.d_expert) == (
        7168, 128, 1536, 512, 128, 64, 128, 18432, 2048)
    assert (cfg.top_k, cfg.n_group, cfg.topk_group, cfg.routed_scale,
            cfg.n_shared) == (8, 8, 4, 2.5, 1)
    assert cfg.yarn == (40.0, 32.0, 1.0, 4096) and cfg.d_latent == 576
    assert cfg.softmax_scale == pytest.approx(0.135235, rel=1e-4)
    assert (cfg.kv_block, cfg.moe_tile) == (512, 512)
    sz = reference_shape(cell["config"])
    assert (sz["held"], sz["n_experts"], sz["vocab"], sz["n_dense"]) == (
        16, 256, 16160, 1)


def test_the_cycle_is_the_same_for_every_seed(cell):
    tr = cell["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                "sigma": 0.7, "min": 1024, "max": 16384}
    assert tr["output_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.5, "min": 128, "max": 1024,
                                "multiple_of": 32}
    assert tr["max_in_flight"] == 64 and tr["token_id_max"] == 16160
    assert tr["population_seed"] == 1
    # 0.7 x the knee by PERF.md section 4's rule (0.3/s), the issue's
    # fallback (at 0.8 x the check's runs spread over 2.5%): 10 a window
    assert tr["arrivals"] == {"process": "poisson", "rate_per_s": 0.21}
    plans = [T.open_schedule(tr, seed, 50.0, 16160)
             for seed in (1, 2147483659, 4000000007)]
    sizes = [sorted((len(p["tokens"]), p["max_new_tokens"]) for p in plan)
             for plan in plans]
    assert sizes[0] == sizes[1] == sizes[2]
    assert T.open_schedule(tr, 2147483659, 50.0, 16160) == plans[1]
    ek = cell["config"]["serve"]["engine_kwargs"]
    for plan in plans:
        assert plan[0]["due"] == 0.0
        assert max(t for p in plan for t in p["tokens"]) < 16160
        assert all(p["max_new_tokens"] % 32 == 0 for p in plan)
        assert max(len(p["tokens"]) + p["max_new_tokens"] for p in plan) \
            <= min(tr["reference"]["max_context"], ek["max_total"])
    assert plans[0][0]["tokens"] != plans[1][0]["tokens"]
    assert len(plans[0]) == 10
    # one entry a cycle: every seed opens the window at the same arrival
    assert len({tuple(len(p["tokens"]) for p in plan) for plan in plans}) == 1
    chunks = [-(-len(p["tokens"]) // 512) for p in plans[0]]
    assert min(chunks) >= 2 and 8 <= sorted(chunks)[len(chunks) // 2] <= 16
    # the reference's blocks tile its longest context
    ref = tr["reference"]
    assert ref["max_context"] % ref["rows"] == 0
    assert 128 % ref["heads"] == 0
    assert ref["replay_keep"] <= ref["replay_steps"] <= 1024


def test_mla_costs_against_hand_counts(cell):
    cfg = cell["config"]
    # a pair of one head: a 192-wide score and a 128-wide weighted sum
    assert costs.attend_flops(1, cfg) == 128 * 2 * (192 + 128) == 81920
    # a key's latent row: 576 values of 2 bytes, read once a program and
    # layer; a query row: 128 heads x (192 in + 128 out) a layer
    assert costs.attend_bytes(1, 0, cfg) == 1152
    assert costs.attend_bytes(0, 1, cfg) == 5 * 128 * 320 * 2
    pk = peaks.peak("TPU v5 lite")
    # a step of 20 streams at 8,000 of context: 20 x 8,000 pairs and as
    # many keys a layer.  By the algorithm's count a key's 1,152 B meet
    # 81,920 FLOP, 71 a byte: the bytes bound a step (the absorbed form
    # the program runs spends 278,528 FLOP a key, 242 a byte, at the
    # chip's ridge of 240.5 — its price for reading the latent as it lies)
    rec = {"active": 20, "mla_pairs": 5 * 160000.0, "mla_keys": 5 * 160000.0}
    flops, nbytes = 81920 * 800000, 1152 * 800000 + 20 * 5 * 128 * 320 * 2
    assert costs.least_seconds("step", rec, cfg, pk) == pytest.approx(
        max(flops / 197e12, nbytes / 819e9))
    assert nbytes / 819e9 > 3 * flops / 197e12
    assert 128 * 2 * (576 + 512) / 1152 == pytest.approx(241.8, abs=0.1)
    # a chunk of 512 rows from position 6,144: rows see 6,145 .. 6,656
    pairs = 5 * sum(range(6145, 6657))
    rec = {"chunk_tokens": 512, "chunk_mla_pairs": float(pairs),
           "chunk_mla_keys": 5 * 6656.0, "chunks": 1}
    assert costs.least_seconds("chunk", rec, cfg, pk) == pytest.approx(
        81920 * pairs / 197e12)                     # compute-bound, 6.8 ms
    assert 6e-3 < 81920 * pairs / 197e12 < 7e-3


RING = [
    {"ts": 10.5, "active": 20, "chunks": 0, "chunk_tokens": 0,
     "mla_pairs": 8e5, "mla_keys": 8e5, "chunk_mla_pairs": 0.0,
     "chunk_mla_keys": 0.0, "moe_pairs": 60.0, "moe_touched": 30.0,
     "chunk_moe_pairs": 0.0, "chunk_moe_touched": 0.0},
    {"ts": 10.8, "active": 19, "chunks": 1, "chunk_tokens": 512,
     "mla_pairs": 7e5, "mla_keys": 7e5, "chunk_mla_pairs": 1.6e7,
     "chunk_mla_keys": 33280.0, "moe_pairs": 50.0, "moe_touched": 28.0,
     "chunk_moe_pairs": 1000.0, "chunk_moe_touched": 64.0},
    {"ts": 99.0, "active": 32, "chunks": 1, "chunk_tokens": 512,
     "mla_pairs": 9e9, "mla_keys": 9e9, "chunk_mla_pairs": 9e9,
     "chunk_mla_keys": 9e9, "moe_pairs": 9e9, "moe_touched": 64.0,
     "chunk_moe_pairs": 9e9, "chunk_moe_touched": 64.0},
]


def test_readers_on_a_fixture(cell):
    cfg = cell["config"]
    pk = peaks.peak("TPU v5 lite")
    ctx = {"config": cfg, "device": {"kind": "TPU v5 lite"}}
    red = {"modules": {"jit_serve_step(1)": {"s": 0.05, "n": 3},
                       "jit_serve_prefill(2)": {"s": 0.1, "n": 2}},
           "ops": {"%ragged-dot.3": {"s": 0.004, "n": 40}}}
    obs = {"trace": red, "serve": {
        "ring": RING, "traced": [10.0, 11.0],
        "scopes": {"mla_attend_step": 0.02, "mla_attend_chunk": 0.03}}}
    read = trace_scope_roofline.read
    step, chunk = (_params(f"mla.{n}_roofline.longctx")
                   for n in ("step", "chunk"))
    steps = [costs.least_seconds("step", r, cfg, pk) for r in RING[:2]]
    assert read(obs, step, ctx) == pytest.approx(
        100 * sum(steps) / 2 * 3 / 0.02)
    one = costs.least_seconds("chunk", RING[1], cfg, pk)
    got = read(obs, chunk, ctx)
    assert got == pytest.approx(100 * one * 2 / 0.03) and 0 < got < 100
    # a program without the scopes, a run without a traced stretch, or a
    # ring without the counters (the parent's): nothing to read, and
    # nothing raised
    no_scopes = {"trace": red, "serve": {"ring": RING, "traced": [10, 11]}}
    untraced = {"trace": red, "serve": dict(obs["serve"], traced=None)}
    old_ring = {"trace": red, "serve": dict(
        obs["serve"], ring=[{"ts": 10.5, "chunks": 1, "active": 2}])}
    for bare in (no_scopes, untraced, old_ring):
        assert read(bare, step, ctx) is None
        assert read(bare, chunk, ctx) is None
    # the experts' roofline at THIS configuration's expert width (2,048;
    # `intermediate_size` is the dense layers' 18,432)
    moe = _params("moe.experts_roofline.longctx")
    assert moe["expert_width"] == "moe_intermediate_size"
    at = dict(cfg, intermediate_size=2048)
    need = (costs_moe.least_seconds(1000.0, 64.0, at, pk) * 2
            + sum(costs_moe.least_seconds(r["moe_pairs"], r["moe_touched"],
                                          at, pk) for r in RING[:2]) / 2 * 3)
    try:
        got = trace_moe_roofline_at.read(obs, moe, ctx)
    except Exception as e:      # the fixture's shape of `ops` is reduce.py's
        pytest.skip(f"trace fixture: {e}")
    if got is not None:
        assert got == pytest.approx(100 * need / 0.004)
    assert trace_moe_roofline_at.read(
        obs, moe, {"config": {"hidden_size": 1}, "device": ctx["device"]}) \
        is None


def test_the_reference_in_blocks_is_the_reference_whole():
    """`check_deepseek_v3.served_gaps` — its own weight draw a leaf at a
    time, rows in blocks against padded latents, heads in groups, experts
    gathered to a bound — gives the logits `deepseek_v3_plain.logits`
    gives whole: a served token that IS the whole reference's argmax has
    gap 0, and program logits equal to the whole reference's read 0."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.deepseekcfg import reference_shape
    from benchmarks.reference import check_deepseek_v3 as chk
    from benchmarks.reference import deepseek_v3_plain as ref

    with open(os.path.join(manifest.BENCH_DIR, "tests",
                           "rehearsal_longctx.json")) as f:
        toy = json.load(f)
    conf = dict(manifest.resolve(manifest.load(), CELL)["config"])
    conf.update(toy["config"])
    sz = reference_shape(conf)
    seed, n_logits = 4000000007, 8
    params = ref.draw(seed, sz, conf["weights"])
    toks = np.random.default_rng(1).integers(0, 512, 45).tolist()
    whole = np.asarray(ref.logits(params, jnp.asarray(toks, jnp.int32), sz))
    prompt, served = toks[:40], []
    rows = whole[39:39 + 5]
    for i in range(5):          # teacher-forced on `toks`: serve its argmax
        served.append(int(rows[i].argmax()) if i == 4 else toks[40 + i])
    spec = dict(toy["traffic"]["reference"])
    per = chk.served_gaps(
        seed, sz, conf["weights"],
        [{"rid": 0, "tokens": prompt, "served": toks[40:45]}], spec,
        n_logits, replay=(0, 0, jnp.asarray(rows)))
    assert per[0]["logit_rel_rms"] < 1e-5 and per[0]["replayed"] == 5
    want = [float(whole[39 + i].max() - whole[39 + i][toks[40 + i]])
            for i in range(5)]
    assert per[0]["max_gap"] == pytest.approx(max(want), abs=1e-4)
    assert per[0]["n"] == 5 and per[0]["blocks"] == 3


def test_the_check_sees_the_broken_paths_at_a_small_size():
    """The control study at toy size on the CPU, float32 on both sides:
    seven rehearsals of the CELL by its own driver.  The sound program
    comes out `correct` (every served token the reference's argmax, its
    replayed logits the reference's); each fault — put into the program
    in its replica (an 8-bit latent arena, chunks that lose earlier
    chunks' pages) or into the reference (fp8 matrices, no rope part, no
    YaRN scale, no groups) — comes out not correct, by the logits at the
    least."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "scripts",
                                      "study_deepseek_v3_controls.py"),
         "--toy", "5"], capture_output=True, text=True, timeout=900,
        cwd=manifest.ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    got = {r["variant"]: r for r in (
        json.loads(ln) for ln in out.stdout.splitlines()
        if ln.startswith('{"phase": "reading"'))}
    assert got["sound"]["correct"] is True
    assert got["sound"]["argmax_share"] == 1.0
    assert got["sound"]["worst_gap"] == 0.0
    assert got["sound"]["logit_rel_rms"] < 1e-5
    for broken in ("fp8_weights", "no_rope_score", "no_yarn_scale",
                   "no_groups", "latent_8bit", "lost_chunks"):
        r = got[broken]
        assert r["correct"] is False, broken
        assert not r["checks"]["program_logits_near_reference"], broken
        assert r["logit_rel_rms"] > 0.02, broken
        assert r["checks"]["every_request_full_length"], broken
    for lost in ("no_rope_score", "lost_chunks"):
        assert got[lost]["worst_gap"] > 1.0 and got[lost][
            "argmax_share"] < 0.6, lost


def test_the_cause_study_runs_at_a_small_size():
    """`scripts/study_deepseek_v3_bf16_cause.py` at toy size on the CPU,
    whose default precision is float32: the three passes agree, no token
    is routed elsewhere — the control flow, and that forcing a pass's
    experts changes nothing where nothing differs."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "scripts",
                                      "study_deepseek_v3_bf16_cause.py"),
         "--toy", "7"], capture_output=True, text=True, timeout=600,
        cwd=manifest.ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["phase"] == "cause" and got["platform"] == "cpu"
    assert got["bf16_rel_rms"] < 1e-5
    assert got["bf16_routed_as_f32_rel_rms"] < 1e-5
    assert got["tokens_rerouted_share_by_layer"] == [0.0, 0.0]
    assert got["argmax_agree_routed_as_f32"] == 1.0
