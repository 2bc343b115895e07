"""The cell `serve-lfm2moe-ragextract`: its files resolve by name, the
configuration keeps every number of the catalog's row but the depth (and
what follows from it: the leading dense layers counted once, the layer
plan cut with it), the traffic's cycle is the same for every seed and is
the issue's, the experts' costs agree with hand counts at a deployment's
whole load, and the readers of the metric files BENCHMARK.json has no room
to list read a fixture (and read nothing, without raising, where a program
lacks the counters)."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import costs_moe, manifest
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import ring_ratio, trace_scope_share

CELL = "serve-lfm2moe-ragextract"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types"]
# the per-layer metrics the cell reports: these entries' readers find the
# same scopes and counters here, and `per_layer` holds the contract's 128
TAKEN = ("moe.time_share", "moe.experts_roofline", "moe.reads_per_touched",
         "cache.state_bytes_share", "engine.decode_step_device_ms",
         "engine.decode_step_ms", "engine.host_share",
         "engine.decode_blocked_share", "engine.prefill_ms_per_token",
         "engine.prefill_pad_share", "engine.dispatch_share",
         "engine.step_dispatch_ms", "engine.step_wait_ms",
         "engine.admit_iter_ms")


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load(), CELL)


def _spec(name):
    with open(os.path.join(manifest.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def test_the_cell_resolves(cell):
    man = manifest.load()
    assert len(man["per_layer"]) == 128          # the contract's ceiling
    assert cell["cell"]["chips"] == 1
    assert cell["traffic"]["kind"] == "serve_open_ragextract"
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e >= {"itl_p99_ms", "setup_s"}
    assert "train_tokens_per_s" not in e2e
    names = {m["name"]: m for m in cell["per_layer"]}
    assert set(names) >= {n + ".reasoning" for n in TAKEN}
    # Ling's held count is the scale of this one: the cell's own reading
    # is a file the driver prints (LAYER_METRICS)
    assert "moe.load_max_over_mean.reasoning" not in names
    moves = {"itl_p99_ms"} | ({"ttft_p75_ms"} & e2e)
    assert all(m["moves"] in moves and CELL in m["workloads"]
               for m in names.values())
    assert len(cell["cell"]["why"]) <= 200
    assert len(cell["config_entry"]["why"]) <= 200


def test_the_configuration_is_the_catalog_row_less_its_depth(cell):
    cfg = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert cell["config_entry"]["source"] == row["source_url"] == cfg["source"]
    for k, v in row["config"].items():
        if k not in REDUCED:
            assert cfg[k] == v, k
    assert cfg["reduced"] == REDUCED == cell["config_entry"]["reduced"]
    pub = row["config"]
    assert cfg["published"] == {k: pub[k] for k in REDUCED}
    held = cfg["layers_held"]
    assert held == [0] + list(range(2, 14))
    assert cfg["layer_types"] == [pub["layer_types"][i] for i in held]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (13, 1)
    # three whole periods behind the dense layer, in the published 3:1
    assert cfg["layer_types"][1:] == ["full_attention", "conv", "conv",
                                      "conv"] * 3
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert cfg["deployment_share"] == {"chips_per_layer": 1,
                                       "pipeline_stages": 2,
                                       "experts_first": 0,
                                       "experts_held": 32}
    assert "two pipeline stages" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"tied_head", "block", "short_conv",
                                   "attention", "experts", "dense",
                                   "precision"}
    ek = cfg["serve"]["engine_kwargs"]
    per_slot = ek["max_total"] // ek["page_size"]
    assert ek["num_pages"] == {"full": 1 + ek["max_slots"] * per_slot,
                               "conv": 1 + ek["max_slots"]}
    assert (ek["max_slots"], ek["page_size"], ek["max_total"],
            ek["prefill_chunk"], ek["prefill_bucket"]) == (64, 128, 9216,
                                                           512, 256)
    assert ek["queue_cap"] == ek["shed_queue_depth"] == \
        cell["traffic"]["max_in_flight"] == 256
    assert set(cfg["weights"]) >= {"made", "why", "scales",
                                   "router_bias_std"}
    assert set(cfg["weights"]["why"]) >= set(cfg["weights"]["scales"]) | {
        "router_bias_std"}


def test_the_config_maps_onto_the_program_and_the_reference(cell):
    from benchmarks.lib.lfm2moecfg import model_config, reference_shape

    cfg = model_config(cell["config"])
    assert (cfg.n_layers, cfg.n_dense, cfg.vocab_size, cfg.d_model,
            cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff,
            cfg.d_expert) == (13, 1, 65536, 2048, 32, 8, 64, 7168, 1792)
    assert (cfg.n_experts, cfg.experts_first, cfg.experts_held, cfg.top_k,
            cfg.routed_scale, cfg.conv_taps, cfg.rope_theta, cfg.eps) == (
        32, 0, 32, 4, 1.0, 3, 1e6, 1e-5)
    assert (len(cfg.conv_layers), cfg.attn_layers) == (10, (1, 5, 9))
    sz = reference_shape(cell["config"])
    assert (sz["vocab"], sz["n_layers"], sz["held"], sz["first"]) == (
        65536, 13, 32, 0)
    assert sz["layer_types"] == cfg.layer_types
    # a share of 8 of 32 is one number away
    share = dict(cell["config"], deployment_share={
        "experts_first": 8, "experts_held": 8})
    assert (model_config(share).experts_first,
            reference_shape(share)["held"]) == (8, 8)
    with pytest.raises(ValueError, match="what is built"):
        model_config(dict(cell["config"], conv_bias=True))
    with pytest.raises(ValueError, match="layer_types"):
        model_config(dict(cell["config"], num_hidden_layers=24))


def test_every_seed_offers_the_same_cycle(cell):
    """The issue's traffic letter for letter — Poisson arrivals, its
    prompts, its outputs, token ids over the whole vocabulary — and ONE
    entry a cycle, so every seed offers the same arrivals in the same
    order."""
    tr = cell["traffic"]
    assert tr["arrivals"]["process"] == "poisson"
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                "sigma": 0.8, "min": 256, "max": 8192}
    out = dict(tr["output_len"])
    assert out.pop("min") in (32, 64)                  # 64: the 2nd fallback
    assert out == {"dist": "lognormal", "median": 160, "sigma": 0.7,
                   "max": 768, "multiple_of": 32}
    assert tr["max_in_flight"] == 256 and tr["token_id_max"] == 65536
    assert tr["population_seed"] == 1
    plans = [T.open_schedule(tr, seed, 50.0, 65536)
             for seed in (1, 3000000019, 4000000007)]
    shapes = [[(round(p["due"], 6), len(p["tokens"]), p["max_new_tokens"])
               for p in plan] for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert abs(len(shapes[0]) - 50 * tr["arrivals"]["rate_per_s"]) <= 1
    gaps = np.diff([s[0] for s in shapes[0]])
    assert tr["entry_after_idle_s"] > gaps.max()       # one entry a cycle
    assert 0.8 < gaps.std() / gaps.mean() < 1.25       # exponential gaps
    assert plans[0][5]["tokens"] != plans[1][5]["tokens"]
    assert max(max(p["tokens"]) for p in plans[1]) > 65000    # whole vocab
    assert all(p["max_new_tokens"] % 32 == 0 for p in plans[0])
    plens = [s[1] for s in shapes[0]]
    assert min(plens) >= 256 and max(plens) <= 8192
    assert 1500 < np.median(plens) < 2700
    ek = cell["config"]["serve"]["engine_kwargs"]
    longest = max(len(p["tokens"]) + p["max_new_tokens"] for p in plans[0])
    assert longest <= ek["max_total"] == tr["reference"]["max_context"]
    assert tr["reference"]["max_context"] % tr["reference"]["rows"] == 0
    assert tr["output_len"]["max"] % 256 == 0          # the head's blocks
    assert set(tr["reference"]) >= {
        "min_argmax_share", "logit_margin", "max_logit_rel_rms",
        "max_tail_rel_rms", "why"}


def test_the_experts_costs_at_the_whole_load(cell):
    """`lib/costs_moe.py` with this configuration's widths through the
    reader's `expert_width` key: a step of 36 streams (144 pairs a layer,
    every expert of 12 layers touched) and a 512-row chunk (2,048 pairs a
    layer, 64 rows an expert: a quarter of the 240 at which a product
    stops being its weights' read) are BOTH the experts' 8.46e9 B."""
    from benchmarks.metrics.readers import trace_moe_roofline_at

    spec = _spec("moe.experts_roofline.reasoning")
    assert spec["reader"] == "trace_moe_roofline_at"
    assert spec["params"]["expert_width"] == "moe_intermediate_size"
    assert cell["config"][spec["params"]["expert_width"]] == 1792
    assert hasattr(trace_moe_roofline_at, "read")
    D, F = 2048, 1792
    weights = 3 * 12 * 32 * D * F * 2
    assert weights == 8455716864
    pk = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    cfg = {"hidden_size": D, "intermediate_size": F}
    for pairs in (36 * 4 * 12, 512 * 4 * 12):
        by = costs_moe.expert_bytes(pairs, 12 * 32, D, F)
        fl = costs_moe.expert_flops(pairs, D, F)
        assert by > weights and by / 819e9 > fl / 197e12     # memory-bound
        assert costs_moe.least_seconds(pairs, 12 * 32, cfg, pk) == \
            pytest.approx(by / 819e9)
    assert 10.3e-3 < costs_moe.least_seconds(36 * 48, 384, cfg, pk) < 10.4e-3
    assert 11.2e-3 < costs_moe.least_seconds(512 * 48, 384, cfg, pk) < 11.3e-3


def test_the_share_and_ring_readers_of_the_unlisted_files():
    from benchmarks.drivers.replica_lfm2_moe import SCOPES
    from benchmarks.drivers.serve_open_ragextract import LAYER_METRICS

    assert set(LAYER_METRICS) == {
        f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR,
                                                "metrics"))
        if f.endswith(".ragextract.json")}
    conv, attn = (_spec(n + ".time_share.ragextract")
                  for n in ("conv", "attn"))
    assert conv["params"]["scopes"] == ["conv_proj", "short_conv", "conv_out"]
    assert set(conv["params"]["scopes"]) | set(attn["params"]["scopes"]) \
        <= set(SCOPES)
    obs = {"serve": {"scopes": {"short_conv": 0.1, "conv_proj": 0.2,
                                "conv_out": 0.1, "attn_step": 0.2,
                                "moe_experts": 1.0}},
           "trace": {"busy_s": 2.0}}
    assert trace_scope_share.read(obs, conv["params"], {}) == \
        pytest.approx(20.0)
    assert trace_scope_share.read(obs, attn["params"], {}) == \
        pytest.approx(10.0)
    assert trace_scope_share.read({"serve": {}, "trace": {"busy_s": 1.0}},
                                  conv["params"], {}) is None
    load = _spec("moe.load_max_over_mean.ragextract")
    assert load["params"]["scale"] == 32               # every expert held
    # 12 layers, 144 pairs each, the fullest expert 9 of a mean of 4.5
    ring = [{"moe_load_max": 12 * 9.0, "moe_pairs": 12 * 144.0}] * 3
    assert ring_ratio.read({"serve": {"ring": ring}}, load["params"],
                           {}) == pytest.approx(2.0)
    assert ring_ratio.read({"serve": {"ring": [{}]}}, load["params"],
                           {}) is None
