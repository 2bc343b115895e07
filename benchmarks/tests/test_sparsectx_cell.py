"""The cell `serve-dots3note-sparsectx`: its files resolve by name, the
configuration keeps every number of the catalog's row, the traffic's cycle
is the same for every seed and every prompt is past `index_topk`, the
metric files name readers that exist, `costs_dsa` agrees with a hand count,
and its rooflines read a fixture (and read nothing, without raising, where
a program lacks the counters)."""

import importlib
import json
import os

import pytest

from benchmarks.lib import costs_dsa, manifest, peaks
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import trace_scope_roofline

CELL = "serve-dots3note-sparsectx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 32,
           "vocab_size": 19008,
           "layer_types": ["full_attention", "full_attention"]
           + ["sliding_attention"] * 3}
# the contract allows 128 per-layer metrics and 114 stood: fourteen, the
# chunk's (a prompt's first token waits for chunks) and the engine's that
# split `ttft_p75_ms` before a step's rooflines and the cache's shares
METRICS = ("dsa.index_time_share", "dsa.index_chunk_roofline",
           "dsa.select_time_share", "dsa.selected_share",
           "dsa.walked_over_selected", "mla.time_share", "mla.chunk_roofline",
           "attn.window_latent_roofline", "moe.time_share",
           "moe.experts_roofline", "engine.ttft_queue_share",
           "engine.prefill_ms_per_token", "engine.chunk_blocked_share",
           "engine.prefill_share")
RING = [
    {"ts": 10.2, "active": 2, "chunks": 0, "chunk_tokens": 0,
     "dsa_keys_visible": 24000.0, "dsa_keys_selected": 8192.0,
     "dsa_keys_walked": 24320.0, "dsa_ctx": 24000.0, "swa_pairs": 3078.0,
     "swa_keys": 3078.0},
    {"ts": 10.6, "active": 2, "chunks": 1, "chunk_tokens": 512,
     "dsa_keys_visible": 24004.0, "dsa_keys_selected": 8192.0,
     "dsa_keys_walked": 24320.0, "dsa_ctx": 24004.0, "swa_pairs": 3078.0,
     "swa_keys": 3078.0,
     "chunk_dsa_keys_visible": 2.0 * sum(4097 + t for t in range(512)),
     "chunk_dsa_keys_selected": 2.0 * 512 * 2048,
     "chunk_dsa_keys_walked": 2.0 * 512 * 4608,
     "chunk_dsa_ctx": 2.0 * 4608, "chunk_swa_pairs": 3.0 * 512 * 513,
     "chunk_swa_keys": 3.0 * 1024},
    {"ts": 12.0, "active": 1, "chunks": 0, "chunk_tokens": 0,
     "dsa_keys_visible": 1.0, "dsa_keys_selected": 1.0,
     "dsa_keys_walked": 1.0, "dsa_ctx": 1.0, "swa_pairs": 1.0,
     "swa_keys": 1.0}]


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load(), CELL)


def _spec(name):
    with open(os.path.join(manifest.BENCH_DIR, "metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_cell_resolves_with_its_metrics(cell):
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "open-sparsectx"
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_p75_ms",
                                                       "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {n + ".sparsectx" for n in METRICS}
    for m in cell["per_layer"]:
        assert m["moves"] == "ttft_p75_ms" and m["workloads"] == [CELL]
        # a metric file names a reader that exists, and a roofline costs
        # that exist
        reader = importlib.import_module(
            "benchmarks.metrics.readers." + m["reader"])
        assert callable(reader.read)
        if "costs" in m["params"]:
            costs = importlib.import_module(
                "benchmarks.lib." + m["params"]["costs"])
            assert callable(costs.least_seconds)
    man = manifest.load()
    assert len(man["per_layer"]) <= 128     # the contract's cap


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_keeps_every_number_of_the_catalog_row(cell):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    conf, entry = cell["config"], cell["config_entry"]
    assert entry["source"] == conf["source"] == row["source_url"]
    assert set(entry["reduced"]) == set(conf["reduced"]) == set(REDUCED)
    for key, value in row["config"].items():
        if key in REDUCED:
            assert conf[key] == REDUCED[key]
            assert conf["published"][key] == value
        else:
            assert conf[key] == value, key
    assert conf["layer_types"] == row["config"]["layer_types"][:5]
    for key in ("assumed", "deployment", "deployment_share", "out_of_scope",
                "weights", "program", "serve", "memory", "reduced_why"):
        assert conf[key], key
    assert set(conf["reduced_why"]) == set(REDUCED)


def test_the_config_maps_onto_the_program_and_the_reference(cell):
    from benchmarks.lib.dots3cfg import model_config, reference_shape

    conf = cell["config"]
    cfg, sz = model_config(conf), reference_shape(conf)
    assert cfg.layer_types == ("full", "full", "sliding", "sliding",
                               "sliding") == tuple(sz["layer_types"])
    assert (cfg.n_heads, cfg.swa_heads, cfg.index_heads) == (128, 64, 64)
    assert (cfg.experts_held, cfg.n_experts, cfg.top_k) == (32, 256, 8)
    assert (cfg.view("full").kv_rank + cfg.d_rope,
            cfg.view("sliding").kv_rank + cfg.swa_d_rope) == (576, 1088)
    assert (sz["full"]["theta"], sz["sliding"]["theta"],
            sz["sliding"]["window"]) == (8e7, 5e4, 513)
    assert sz["held"] == 32 and sz["vocab"] == 19008 and sz["rescale"]
    ek = conf["serve"]["engine_kwargs"]
    assert ek["num_pages"] == {"full": 32 * 262 + 1, "sliding": 32 * 10 + 1}


def test_the_cycle_is_the_same_for_every_seed_and_past_index_topk(cell):
    traffic, conf = cell["traffic"], cell["config"]
    plans = [T.open_schedule(traffic, seed, 50.0, conf["vocab_size"])
             for seed in (1, 2147483659)]
    shape = lambda plan: [(round(p["due"], 6), len(p["tokens"]),
                           p["max_new_tokens"]) for p in plan]
    assert shape(plans[0]) == shape(plans[1])
    assert plans[0][3]["tokens"] != plans[1][3]["tokens"]
    assert len(plans[0]) >= 30
    for p in plans[0]:
        assert 2560 <= len(p["tokens"]) <= 32768
        assert len(p["tokens"]) > conf["index_topk"]
        assert 64 <= p["max_new_tokens"] <= 768
        assert p["max_new_tokens"] % 32 == 0
        assert max(p["tokens"]) < conf["vocab_size"]
        assert (len(p["tokens"]) + p["max_new_tokens"]
                <= conf["serve"]["max_seq"])
    # the check's sample is taken among the contexts its reference holds:
    # several of the cycle's, the longest of them near the bound
    held = [n for n in (len(p["tokens"]) + p["max_new_tokens"]
                        for p in plans[0])
            if n <= traffic["reference"]["max_context"]]
    assert len(held) >= 5
    assert max(held) > 0.85 * traffic["reference"]["max_context"]


def test_costs_dsa_against_a_hand_count(cell):
    """A chunk's windowed attention and a step's index pass by hand (the
    other parts' hand counts at these sizes: tests/test_dots3.py)."""
    cfg = cell["config"]
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    assert costs_dsa.least_seconds("index_step", RING[0], cfg, peak) == max(
        24000 * 64 * 258 / 1e12,
        (2 * (24000 * 128 + 2 * 2 * 64 * 129) + 4 * 2 * 2) / 1e9)
    assert costs_dsa.least_seconds("swa_chunk", RING[1], cfg, peak) == max(
        2 * 3 * 512 * 513 * 64 * 384 / 1e12,
        2 * (3 * 1024 * 1088 + 512 * 3 * 64 * 384) / 1e9)
    with pytest.raises(ValueError):
        costs_dsa.least_seconds("kda_step", RING[0], cfg, peak)


def test_rooflines_on_a_fixture(cell):
    cfg = cell["config"]
    pk = peaks.peak("TPU v5 lite")
    ctx = {"config": cfg, "device": {"kind": "TPU v5 lite"}}
    red = {"modules": {"jit_serve_step(1)": {"s": 0.05, "n": 3},
                       "jit_serve_prefill(2)": {"s": 0.1, "n": 2}}}
    scopes = {"dsa_index_step": 0.004, "dsa_index_chunk": 0.02,
              "mla_attend_step": 0.01, "mla_attend_chunk": 0.05,
              "swa_latent_attend_chunk": 0.01}
    obs = {"trace": red, "serve": {"ring": RING, "traced": [10.0, 11.0],
                                   "scopes": scopes}}
    read = trace_scope_roofline.read
    # a step's two forms have their costs and no metric file (the cap):
    # the reader's parameters as such a file would give them
    for program, scope, n in (("index_step", "dsa_index_step", 3),
                              ("mla_step", "mla_attend_step", 3)):
        need = sum(costs_dsa.least_seconds(program, r, cfg, pk)
                   for r in RING[:2]) / 2 * n
        got = read(obs, {"costs": "costs_dsa", "scope": scope,
                         "module": "^jit_serve_step", "count": "active",
                         "program": program}, ctx)
        assert got == pytest.approx(100 * need / scopes[scope])
        assert 0 < got < 100
    for name, program, scope in (
            ("dsa.index_chunk_roofline", "index_chunk", "dsa_index_chunk"),
            ("mla.chunk_roofline", "mla_chunk", "mla_attend_chunk"),
            ("attn.window_latent_roofline", "swa_chunk",
             "swa_latent_attend_chunk")):
        need = costs_dsa.least_seconds(program, RING[1], cfg, pk) * 2
        got = read(obs, _spec(name + ".sparsectx")["params"], ctx)
        assert got == pytest.approx(100 * need / scopes[scope])
        assert 0 < got < 100
    # a program without the scopes, a run without a traced stretch, or a
    # ring without the counters (the parent's): nothing to read, nothing
    # raised
    bare = [{"trace": red, "serve": {"ring": RING, "traced": [10, 11]}},
            {"trace": red, "serve": dict(obs["serve"], traced=None)},
            {"trace": red, "serve": dict(obs["serve"], ring=[
                {"ts": 10.5, "chunks": 1, "active": 2, "chunk_tokens": 512}
            ])}]
    for o in bare:
        for n in METRICS:
            spec = _spec(n + ".sparsectx")
            if spec["reader"] == "trace_scope_roofline":
                assert read(o, spec["params"], ctx) is None
