"""The cell `serve-phi4flash-longgen-loaded`: its files resolve by name with
every metric the issue names (and whatever a later PR appends), the
configuration keeps every number of the catalog's row with nothing reduced,
the traffic's cycle is the same for every seed, the scan's and the shared
cache's costs agree with hand counts, and the roofline reader reads a
fixture through them (and reads nothing, without raising, where a program
lacks the counters)."""

import json
import os

import pytest

from benchmarks.lib import costs_mamba, costs_sharedkv, manifest, peaks
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import trace_scope_roofline

CELL = "serve-phi4flash-longgen-loaded"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ENGINE = ("decode_step_device_ms", "decode_step_ms", "host_share",
          "decode_blocked_share", "prefill_ms_per_token", "prefill_pad_share",
          "dispatch_share", "step_dispatch_ms", "step_wait_ms",
          "admit_iter_ms")
NAMED = tuple(f"engine.{n}" for n in ENGINE) + (
    "mamba.time_share", "mamba.step_roofline", "mamba.chunk_roofline",
    "attn.shared_kv_time_share", "attn.shared_kv_roofline",
    "attn.window_time_share", "prefill.cross_rows_share",
    "cache.state_bytes_share", "cache.window_pages_share")


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
    assert cell["traffic"]["kind"] == "serve_open_longgen"
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p99_ms",
                                                       "setup_s"}
    names = {m["name"]: m for m in cell["per_layer"]}
    assert set(names) >= {n + ".longgen" for n in NAMED}
    for n in NAMED:
        m = names[n + ".longgen"]
        assert m["moves"] == "itl_p99_ms" and m["workloads"] == [CELL]
    for n in ENGINE + ("state_bytes_share",):   # a twin reads as its sibling
        stem = ("engine." if n in ENGINE else "cache.") + n
        assert _params(stem + ".longgen") == _params(stem + ".reasoning")
    assert {names[n + ".longgen"]["layer"] for n in NAMED
            if n.startswith("mamba.")} == {"kernels ops/mamba"}
    assert _params("mamba.step_roofline.longgen")["costs"] == "costs_mamba"
    assert _params("attn.shared_kv_roofline.longgen")["costs"] == \
        "costs_sharedkv"
    assert len(cell["cell"]["why"]) <= 200
    assert len(cell["config_entry"]["why"]) <= 200


def test_the_configuration_is_the_catalog_row_with_nothing_reduced(cell):
    cfg = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert cell["config_entry"]["source"] == row["source_url"] == cfg["source"]
    for k, v in row["config"].items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == [] == cell["config_entry"]["reduced"]
    assert cfg["deployment_share"] == {"chips_per_layer": 1, "stages": 1}
    assert cfg["out_of_scope"].startswith("nothing")
    assert set(cfg["assumed"]) >= {"mamba", "differential_attention",
                                   "attention_biases", "positions", "plan",
                                   "gmu", "norms"}
    assert cfg["assumed_sizes"] == {"mamba_d_state": 16, "mamba_d_conv": 4,
                                    "mamba_expand": 2, "mamba_dt_rank": 160}
    ek = cfg["serve"]["engine_kwargs"]
    per_slot = ek["max_total"] // ek["page_size"]
    ring = (cfg["sliding_window"] + ek["prefill_chunk"]) // ek["page_size"] + 1
    assert ek["num_pages"] == {"full": 1 + ek["max_slots"] * per_slot,
                               "swa": 1 + ek["max_slots"] * ring,
                               "mamba": 1 + ek["max_slots"]}
    assert (ek["page_size"], ek["max_total"], ek["prefill_chunk"],
            ek["prefill_bucket"]) == (128, 20480, 512, 512)
    assert ek["max_slots"] in (24, 32)
    assert set(cfg["memory"]) >= {"arithmetic", "rehearsed", "measured"}
    assert set(cfg["weights"]) >= {"made", "why"}


def test_the_config_maps_onto_the_program_and_the_reference(cell):
    from benchmarks.lib.phi4flashcfg import model_config, reference_shape

    cfg = model_config(cell["config"])
    assert (cfg.n_layers, cfg.vocab_size, cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.sliding_window) == (
        32, 200064, 2560, 40, 20, 64, 10240, 512)
    assert (cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.memory_layer) == (
        5120, 16, 160, 16)
    assert cfg.plan[15:20] == ["swa", "mamba", "full", "gmu", "cross"]
    sz = reference_shape(cell["config"])
    assert (sz["vocab"], sz["n_layers"], sz["window"], sz["d_state"],
            sz["expand"], sz["dt_rank"]) == (200064, 32, 512, 16, 2, 160)
    with pytest.raises(ValueError, match="what is built"):
        model_config(dict(cell["config"], mb_per_layer=4))
    with pytest.raises(ValueError, match="taps"):
        model_config(dict(cell["config"], assumed_sizes=dict(
            cell["config"]["assumed_sizes"], mamba_d_conv=3)))


def test_every_seed_offers_the_same_cycle(cell):
    """The issue's traffic letter for letter — its prompts, its outputs,
    arrivals from the window's first second to its last — and one entry a
    cycle, so every seed offers the same requests in the same order."""
    tr = cell["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                "sigma": 1.0, "min": 256, "max": 16384}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.5, "min": 256, "max": 3072,
                                "multiple_of": 32}
    assert tr["max_in_flight"] == 64 and tr["token_id_max"] == 200064
    assert tr["arrivals"]["process"] == "poisson"
    plans = [T.open_schedule(tr, seed, 50.0, 200064)
             for seed in (1, 3000000019, 4000000007)]
    shapes = [[(round(p["due"], 6), len(p["tokens"]), p["max_new_tokens"])
               for p in plan] for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert abs(len(shapes[0]) - 50 * tr["arrivals"]["rate_per_s"]) <= 1
    assert plans[0][5]["tokens"] != plans[1][5]["tokens"]
    assert max(max(p["tokens"]) for p in plans[1]) < 200064
    assert all(p["max_new_tokens"] % 32 == 0 for p in plans[0])
    ek = cell["config"]["serve"]["engine_kwargs"]
    longest = max(len(p["tokens"]) + p["max_new_tokens"] for p in plans[0])
    assert longest <= ek["max_total"]
    assert tr["reference"]["max_context"] % tr["reference"]["rows"] == 0
    assert tr["output_len"]["max"] % 256 == 0          # the head's blocks
    assert set(tr["reference"]) >= {
        "min_argmax_share", "logit_margin", "max_logit_rel_rms",
        "max_state_rel_rms", "max_state_half_share"}


def test_costs_against_hand_counts(cell):
    cfg = cell["config"]
    pk = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    # 5,120 channels x 16 states float32; nine Mamba layers of 32
    assert costs_mamba.state_bytes(cfg) == 5120 * 16 * 4 == 327680
    assert costs_mamba.tail_bytes(cfg) == 3 * 5120 * 2 == 30720
    row = 4 * (3 * 5120 + 2 * 16)          # u, step size, y; B, C
    assert costs_mamba.step_bytes(10, cfg) == 10 * 9 * (2 * 327680 + row)
    assert costs_mamba.step_flops(10, cfg) == 7 * 10 * 9 * 5120 * 16
    assert costs_mamba.chunk_flops(512, cfg) == 7 * 512 * 9 * 5120 * 16
    assert costs_mamba.chunk_bytes(512, 1, cfg) == 9 * (2 * 327680
                                                        + 512 * row)
    rec = {"mamba_live": 32.0, "chunk_tokens": 512, "chunk_mamba_live": 1.0,
           "shared_kv_positions": 100000.0}
    # both are bound by bytes: 32 live states a layer are 0.2 GB a step
    assert costs_mamba.least_seconds("step", rec, cfg, pk) == pytest.approx(
        32 * 9 * (2 * 327680 + row) / 819e9)
    assert 2.3e-4 < costs_mamba.least_seconds("step", rec, cfg, pk) < 2.6e-4
    assert costs_mamba.least_seconds("chunk", rec, cfg, pk) == pytest.approx(
        costs_mamba.chunk_bytes(512, 1, cfg) / 819e9)
    # the shared cache: layer 17 and the seven cross layers read it; a
    # position is 20 heads x 64 keys and values in bfloat16
    assert costs_sharedkv.reads(cfg) == 8
    assert costs_sharedkv.position_bytes(cfg) == 5120
    assert costs_sharedkv.step_bytes(100000, cfg) == 100000 * 5120 * 8
    assert costs_sharedkv.step_flops(100000, cfg) == 100000 * 8 * 6 * 64 * 40
    assert costs_sharedkv.least_seconds("step", rec, cfg, pk) == \
        pytest.approx(100000 * 5120 * 8 / 819e9)
    with pytest.raises(ValueError):
        costs_sharedkv.least_seconds("chunk", rec, cfg, pk)


@pytest.mark.parametrize("metric,scope,costs", [
    ("mamba.step_roofline.longgen", "mamba_step", costs_mamba),
    ("attn.shared_kv_roofline.longgen", "shared_kv_attend_step",
     costs_sharedkv)])
def test_the_roofline_reader_reads_a_fixture_and_nothing_without_counters(
        cell, metric, scope, costs):
    cfg = cell["config"]
    kind = "TPU v5 lite"
    pk = peaks.peak(kind)
    ring = [{"ts": 1.0 + i, "active": 20, "mamba_live": 20.0, "chunks": 0,
             "shared_kv_positions": 80000.0} for i in range(4)]
    need = costs.least_seconds("step", ring[0], cfg, pk)
    obs = {"serve": {"traced": [0.0, 10.0], "ring": ring,
                     "scopes": {scope: 4 * need / 0.5}},
           "trace": {"modules": {"jit_serve_step(1)": {"n": 4, "s": 1.0}}}}
    ctx = {"config": cfg, "device": {"kind": kind}}
    params = _params(metric)
    assert trace_scope_roofline.read(obs, params, ctx) == pytest.approx(50.0)
    bare = {"serve": {"traced": [0.0, 10.0], "scopes": {scope: 1.0},
                      "ring": [{"ts": 1.0, "active": 4, "chunks": 0}]},
            "trace": obs["trace"]}
    assert trace_scope_roofline.read(bare, params, ctx) is None
    assert trace_scope_roofline.read(
        {"serve": {"ring": ring}, "trace": {}}, params, ctx) is None
