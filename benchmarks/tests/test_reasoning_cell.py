"""The cell `serve-ling3flash-reasoning`: its files resolve by name with
every metric the issue names (and whatever a later PR appends), the
configuration keeps every number of the catalog's row, the traffic's cycle
is the same for every seed, the delta rule's costs agree with hand counts,
and the roofline reader reads a fixture through `costs_kda` (and reads
nothing, without raising, where a program lacks the counters)."""

import json
import os

import pytest

from benchmarks.lib import costs_kda as costs
from benchmarks.lib import manifest, peaks
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import trace_scope_roofline

CELL = "serve-ling3flash-reasoning"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts": 128, "vocab_size": 39296}
PUBLISHED = {"num_hidden_layers": 42, "first_k_dense_replace": 2,
             "num_experts": 512, "vocab_size": 157184}
ENGINE = ("decode_step_device_ms", "decode_step_ms", "host_share",
          "decode_blocked_share", "prefill_ms_per_token", "prefill_pad_share",
          "dispatch_share", "step_dispatch_ms", "step_wait_ms",
          "admit_iter_ms")
NAMED = tuple(f"engine.{n}" for n in ENGINE) + (
    "kda.time_share", "kda.step_roofline", "kda.chunk_roofline",
    "mla.time_share", "moe.time_share", "moe.experts_roofline",
    "moe.load_max_over_mean", "cache.state_bytes_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load(), CELL)


def _params(name):
    with open(os.path.join(manifest.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)["params"]


def test_the_cell_resolves_with_every_metric_of_the_issue(cell):
    """At least the named ones: a later PR may append (the sibling tests
    that pin a cell's exact set fail at every append)."""
    assert cell["cell"]["chips"] == 1
    assert cell["traffic"]["kind"] == "serve_open_reasoning"
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p99_ms",
                                                       "setup_s"}
    names = {m["name"]: m for m in cell["per_layer"]}
    assert set(names) >= {n + ".reasoning" for n in NAMED}
    for n in NAMED:
        m = names[n + ".reasoning"]
        assert m["moves"] == "itl_p99_ms" and CELL in m["workloads"]
    for n in ENGINE:                # a twin reads what its sibling reads
        assert _params(f"engine.{n}.reasoning") == _params(
            f"engine.{n}.streams")
    assert {names[n + ".reasoning"]["layer"] for n in NAMED
            if n.startswith("kda.")} == {"kernels ops/kda"}
    assert _params("moe.load_max_over_mean.reasoning")["scale"] == \
        cell["config"]["num_experts"]
    assert _params("kda.step_roofline.reasoning")["costs"] == "costs_kda"


def test_the_configuration_keeps_every_number_of_the_catalog_row(cell):
    cfg = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    assert cell["config_entry"]["source"] == row["source_url"] == cfg["source"]
    for k, v in row["config"].items():
        assert cfg[k] == REDUCED.get(k, v), k
    assert cfg["reduced"] == list(REDUCED) == cell["config_entry"]["reduced"]
    assert cfg["published"] == PUBLISHED
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert set(cfg["assumed"]) >= {"kda", "kda_gate", "mla", "router"}
    assert "multi-token" in cfg["out_of_scope"]
    assert "swiglu_limit" in cfg["out_of_scope"]
    assert cfg["deployment_share"] == {"chips_per_layer": 4,
                                       "experts_first": 0, "vocab_slices": 4}
    ek = cfg["serve"]["engine_kwargs"]
    assert ek["num_pages"] == {
        "full": 1 + ek["max_slots"] * (ek["max_total"] // ek["page_size"]),
        "kda": 1 + ek["max_slots"]}
    assert (ek["page_size"], ek["max_total"], ek["prefill_chunk"],
            ek["prefill_bucket"]) == (128, 18432, 512, 512)
    assert set(cfg["memory"]) >= {"arithmetic", "rehearsed", "measured"}
    assert set(cfg["weights"]) >= {"made", "scales", "a_range",
                                   "fresh_log_a", "router_bias_std", "why"}


def test_the_config_maps_onto_the_program_and_the_reference(cell):
    from benchmarks.lib.ling3cfg import model_config, reference_shape

    cfg = model_config(cell["config"])
    assert (cfg.n_layers, cfg.n_dense, cfg.layer_group, cfg.n_experts,
            cfg.experts_held, cfg.experts_first, cfg.vocab_size) == (
        7, 1, 6, 512, 128, 0, 39296)
    assert cfg.kda_layers == [0, 1, 2, 3, 4, 6] and cfg.mla_layers == [5]
    assert (cfg.d_model, cfg.n_heads, cfg.d_head, cfg.kv_rank, cfg.d_nope,
            cfg.d_rope, cfg.d_v, cfg.d_ff, cfg.d_expert, cfg.d_shared) == (
        2560, 32, 128, 512, 128, 64, 128, 6144, 768, 768)
    assert (cfg.top_k, cfg.n_group, cfg.topk_group, cfg.routed_scale,
            cfg.gate_lower, cfg.rope_theta) == (8, 8, 4, 2.5, -5.0, 6e6)
    assert cfg.softmax_scale == 192 ** -0.5 and not any(cfg.swiglu_limits)
    sz = reference_shape(cell["config"])
    assert (sz["held"], sz["n_experts"], sz["vocab"], sz["n_dense"],
            sz["layer_group"], sz["yarn"]) == (128, 512, 39296, 1, 6, None)
    # a layer of the last pipeline stage carries a clamp nobody wrote down:
    # the program refuses it rather than guess
    late = dict(cell["config"], expert_swiglu_limit_list=[4] * 42)
    with pytest.raises(ValueError, match="SwiGLU limit"):
        model_config(late)
    with pytest.raises(ValueError, match="what is built"):
        model_config(dict(cell["config"], short_conv_kernel_size=3))


def test_every_seed_offers_the_same_cycle(cell):
    """The issue's traffic letter for letter — its prompts, its outputs,
    arrivals from the window's first second to its last — and one entry a
    cycle, so every seed offers the same requests in the same order.  The
    streams still running when the window closes are cut by the driver
    (`serve_open_reasoning.window`), not kept short by the file."""
    tr = cell["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 1.0, "min": 256, "max": 16384}
    assert tr["output_len"] == {"dist": "lognormal", "median": 768,
                                "sigma": 0.5, "min": 256, "max": 2048,
                                "multiple_of": 32}
    assert tr["max_in_flight"] == 128 and tr["token_id_max"] == 39296
    assert tr["arrivals"]["process"] == "poisson"
    assert "arrival_seconds" not in tr
    plans = [T.open_schedule(tr, seed, 50.0, 39296)
             for seed in (1, 3000000019, 4000000007)]
    shapes = [[(round(p["due"], 6), len(p["tokens"]), p["max_new_tokens"])
               for p in plan] for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert len(shapes[0]) == round(50 * tr["arrivals"]["rate_per_s"])
    assert plans[0][5]["tokens"] != plans[1][5]["tokens"]
    assert max(max(p["tokens"]) for p in plans[1]) < 39296
    assert all(p["max_new_tokens"] % 32 == 0 for p in plans[0])
    # arrivals to the window's end: its last gap is the cycle's longest
    assert 50 - 10 / tr["arrivals"]["rate_per_s"] < max(
        p["due"] for p in plans[0]) < 50
    # and answers that outlive it: the cut is the driver's business
    assert any(p["due"] + 0.025 * p["max_new_tokens"] > 50 for p in plans[0])
    ek = cell["config"]["serve"]["engine_kwargs"]
    longest = max(len(p["tokens"]) + p["max_new_tokens"] for p in plans[0])
    assert longest <= tr["reference"]["max_context"] <= ek["max_total"]
    assert tr["reference"]["max_context"] % tr["reference"]["rows"] == 0
    assert set(tr["reference"]) >= {
        "min_argmax_share", "logit_margin", "max_logit_rel_rms",
        "max_state_rel_rms", "max_state_half_share"}


def test_costs_against_hand_counts(cell):
    cfg = cell["config"]
    # 32 heads of 128 x 128 float32; six KDA layers of seven
    assert costs.state_bytes(cfg) == 32 * 128 * 128 * 4 == 2097152
    assert costs.tail_bytes(cfg) == 3 * 12288 * 2 == 73728
    per_state = 2 * 2097152 + 6 * 4 * 32 * 128     # read + write, six rows
    assert costs.step_bytes(10, cfg) == 10 * 6 * per_state
    assert costs.step_flops(10, cfg) == 7 * 10 * 6 * 32 * 128 * 128
    assert costs.chunk_flops(512, cfg) == 7 * 512 * 6 * 32 * 128 * 128
    assert costs.chunk_bytes(512, 1, cfg) == 6 * (
        2 * 2097152 + 512 * 32 * 128 * 14)
    pk = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    rec = {"kda_live": 64.0, "chunk_tokens": 512, "chunk_kda_live": 1.0}
    # both are bound by bytes: 64 live states a layer are 1.6 GB a step
    assert costs.least_seconds("step", rec, cfg, pk) == pytest.approx(
        64 * 6 * per_state / 819e9)
    assert 2.0e-3 < costs.least_seconds("step", rec, cfg, pk) < 2.1e-3
    assert costs.least_seconds("chunk", rec, cfg, pk) == pytest.approx(
        costs.chunk_bytes(512, 1, cfg) / 819e9)


def test_the_roofline_reader_reads_a_fixture_and_nothing_without_counters(
        cell):
    cfg = cell["config"]
    kind = "TPU v5 lite"
    pk = peaks.peak(kind)
    ring = [{"ts": 1.0 + i, "active": 40, "kda_live": 40.0, "chunks": 0}
            for i in range(4)]
    need = costs.least_seconds("step", ring[0], cfg, pk)
    obs = {"serve": {"traced": [0.0, 10.0], "ring": ring,
                     "scopes": {"kda_step": 4 * need / 0.5}},
           "trace": {"modules": {"jit_serve_step(1)": {"n": 4, "s": 1.0}}}}
    ctx = {"config": cfg, "device": {"kind": kind}}
    params = _params("kda.step_roofline.reasoning")
    assert trace_scope_roofline.read(obs, params, ctx) == pytest.approx(50.0)
    bare = {"serve": {"traced": [0.0, 10.0], "scopes": {"kda_step": 1.0},
                      "ring": [{"ts": 1.0, "active": 4, "chunks": 0}]},
            "trace": obs["trace"]}
    assert trace_scope_roofline.read(bare, params, ctx) is None
    assert trace_scope_roofline.read(
        {"serve": {"ring": ring}, "trace": {}}, params, ctx) is None
