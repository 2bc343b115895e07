"""The cell `serve-falconh1-chatburst`: its files resolve by name, the
configuration keeps every number of the catalog's row but the depth, the
traffic's cycle is the same for every seed and is the issue's, the SSD
mixer's costs agree with hand counts, the readers of the metric files
BENCHMARK.json has no room to list read a fixture (and read nothing,
without raising, where a program lacks the counters), and the reference
check catches broken paths at toy size."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import costs_ssd, manifest, peaks
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import (ring_ratio, trace_scope_roofline,
                                        trace_scope_share)

CELL = "serve-falconh1-chatburst"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the per-layer metrics the cell reports: the `.reasoning` set's readers
# find the same counters here, and `per_layer` holds the contract's 128
TWINS = ("engine.decode_step_device_ms", "engine.decode_step_ms",
         "engine.host_share", "engine.decode_blocked_share",
         "engine.prefill_ms_per_token", "engine.prefill_pad_share",
         "engine.dispatch_share", "engine.step_dispatch_ms",
         "engine.step_wait_ms", "engine.admit_iter_ms",
         "cache.state_bytes_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load(), CELL)


def _spec(name):
    with open(os.path.join(manifest.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def test_the_cell_resolves(cell):
    man = manifest.load()
    assert len(man["per_layer"]) <= 128          # the contract's ceiling
    assert cell["cell"]["chips"] == 1
    assert cell["traffic"]["kind"] == "serve_open_chatburst"
    assert {m["name"] for m in cell["end_to_end"]} >= {"itl_p99_ms",
                                                       "setup_s"}
    names = {m["name"]: m for m in cell["per_layer"]}
    assert set(names) >= {n + ".reasoning" for n in TWINS}
    assert all(m["moves"] == "itl_p99_ms" and CELL in m["workloads"]
               for m in names.values())
    assert len(cell["cell"]["why"]) <= 200
    assert len(cell["config_entry"]["why"]) <= 200


def test_the_configuration_is_the_catalog_row_less_its_depth(cell):
    cfg = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert cell["config_entry"]["source"] == row["source_url"] == cfg["source"]
    for k, v in row["config"].items():
        if k != "num_hidden_layers":
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers"] == \
        cell["config_entry"]["reduced"]
    assert (cfg["num_hidden_layers"], cfg["published"]) == (
        6, {"num_hidden_layers": 72})
    assert cfg["deployment_share"] == {"chips_per_layer": 1,
                                       "pipeline_stages": 12}
    assert set(cfg["assumed"]) >= {"d_ssm", "in_proj", "conv", "ssm",
                                   "gated_norm", "block", "mlp", "attention",
                                   "precision"}
    ek = cfg["serve"]["engine_kwargs"]
    per_slot = ek["max_total"] // ek["page_size"]
    assert ek["num_pages"] == {"full": 1 + ek["max_slots"] * per_slot,
                               "ssm": 1 + ek["max_slots"]}
    assert (ek["page_size"], ek["max_total"], ek["prefill_chunk"],
            ek["prefill_bucket"]) == (128, 1536, 512, 256)
    assert ek["max_slots"] in (64, 96)
    assert ek["queue_cap"] == ek["shed_queue_depth"] == \
        cell["traffic"]["max_in_flight"]
    assert set(cfg["memory"]) >= {"arithmetic", "rehearsed", "slots"}
    assert set(cfg["weights"]) >= {"made", "why", "scales",
                                   "in_proj_scales", "memory_tokens"}
    assert set(cfg["weights"]["why"]) >= {"wk", "in_proj_scales", "lm_head"}


def test_the_config_maps_onto_the_program_and_the_reference(cell):
    from benchmarks.lib.falconh1cfg import model_config, reference_shape

    cfg = model_config(cell["config"])
    assert (cfg.n_layers, cfg.vocab_size, cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, cfg.d_head, cfg.d_ff) == (
        6, 261120, 5120, 20, 4, 128, 21504)
    assert (cfg.d_ssm, cfg.d_conv, cfg.ssm_heads, cfg.d_state, cfg.n_groups,
            cfg.ssm_chunk, cfg.rope_theta) == (4096, 5120, 32, 256, 2, 128,
                                               1e11)
    assert cfg.in_segments == (4096, 4096, 512, 512, 32)
    sz = reference_shape(cell["config"])
    assert (sz["vocab"], sz["n_layers"], sz["d_state"]) == (261120, 6, 256)
    assert sz["multipliers"]["ssm"] == cfg.ssm_multipliers
    assert sz["multipliers"]["key"] == cfg.key_multiplier
    with pytest.raises(ValueError, match="what is built"):
        model_config(dict(cell["config"], mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        model_config(dict(cell["config"], mamba_d_ssm=10240))


def test_every_seed_offers_the_same_cycle(cell):
    """The issue's traffic letter for letter — gamma arrivals at cv 2, its
    prompts, its outputs, token ids over the whole vocabulary — and ONE
    entry a cycle, so every seed offers the same bursts in the same
    order."""
    tr = cell["traffic"]
    assert tr["arrivals"]["process"] == "gamma"
    assert tr["arrivals"]["cv"] in (2.0, 1.0)          # 1.0: the fallback
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 192,
                                "sigma": 0.9, "min": 16, "max": 1024}
    assert tr["output_len"] == {"dist": "lognormal", "median": 160,
                                "sigma": 0.6, "min": 32, "max": 512,
                                "multiple_of": 32}
    assert tr["max_in_flight"] == 256 and tr["token_id_max"] == 261120
    assert tr["population_seed"] == 1
    plans = [T.open_schedule(tr, seed, 50.0, 261120)
             for seed in (1, 3000000019, 4000000007)]
    shapes = [[(round(p["due"], 6), len(p["tokens"]), p["max_new_tokens"])
               for p in plan] for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert abs(len(shapes[0]) - 50 * tr["arrivals"]["rate_per_s"]) <= 1
    gaps = np.diff([s[0] for s in shapes[0]])
    assert tr["entry_after_idle_s"] > gaps.max()       # one entry a cycle
    if tr["arrivals"]["cv"] == 2.0:                    # bursts: cv near 2
        assert 1.5 < gaps.std() / gaps.mean() < 2.6
    assert plans[0][5]["tokens"] != plans[1][5]["tokens"]
    assert max(max(p["tokens"]) for p in plans[1]) > 200064   # whole vocab
    assert all(p["max_new_tokens"] % 32 == 0 for p in plans[0])
    ek = cell["config"]["serve"]["engine_kwargs"]
    longest = max(len(p["tokens"]) + p["max_new_tokens"] for p in plans[0])
    assert longest <= ek["max_total"] == tr["reference"]["max_context"]
    assert tr["reference"]["max_context"] % tr["reference"]["rows"] == 0
    assert tr["output_len"]["max"] % 256 == 0          # the head's blocks
    assert set(tr["reference"]) >= {
        "min_argmax_share", "logit_margin", "max_logit_rel_rms",
        "max_state_rel_rms", "max_state_half_share", "why"}


def test_costs_against_hand_counts(cell):
    cfg = cell["config"]
    pk = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    state = 32 * 128 * 256 * 4
    assert costs_ssd.state_bytes(cfg) == state == 4194304
    row = 4 * (2 * 4096 + 2 * 512 + 32)        # x, y; B, C; dt
    assert costs_ssd.step_bytes(10, cfg) == 10 * 6 * (2 * state + row)
    assert costs_ssd.step_flops(10, cfg) == 5 * 10 * 6 * 32 * 128 * 256
    assert costs_ssd.chunk_bytes(512, 1, cfg) == 6 * (2 * state + 512 * row)
    assert costs_ssd.chunk_flops(512, cfg) == 2 * 512 * 6 * (
        2 * 64 * 256 + 32 * 64 * 128 + 2 * 32 * 128 * 256)
    rec = {"ssd_live": 40.0, "chunk_tokens": 512, "chunk_ssd_live": 1.0}
    # both are bound by bytes: 40 live states a layer are 2 GB a step
    assert costs_ssd.least_seconds("step", rec, cfg, pk) == pytest.approx(
        40 * 6 * (2 * state + row) / 819e9)
    assert 2.4e-3 < costs_ssd.least_seconds("step", rec, cfg, pk) < 2.6e-3
    assert costs_ssd.least_seconds("chunk", rec, cfg, pk) == pytest.approx(
        costs_ssd.chunk_bytes(512, 1, cfg) / 819e9)
    assert costs_ssd.chunk_flops(512, cfg) / 197e12 < \
        costs_ssd.chunk_bytes(512, 1, cfg) / 819e9


@pytest.mark.parametrize("metric,scope,count", [
    ("ssd.step_roofline.chatburst", "ssd_step", "active"),
    ("ssd.chunk_roofline.chatburst", "ssd_chunk", "chunks")])
def test_the_roofline_readers_read_a_fixture_and_nothing_without_counters(
        cell, metric, scope, count):
    cfg = cell["config"]
    kind = "TPU v5 lite"
    pk = peaks.peak(kind)
    spec = _spec(metric)
    assert spec["reader"] == "trace_scope_roofline"
    params = spec["params"]
    assert (params["costs"], params["scope"], params["count"]) == (
        "costs_ssd", scope, count)
    program = params["program"]
    module = "jit_serve_step(1)" if program == "step" else \
        "jit_serve_prefill(2)"
    ring = [{"ts": 1.0 + i, "active": 20 if program == "step" else 0,
             "ssd_live": 20.0, "chunks": 0 if program == "step" else 1,
             "chunk_tokens": 512, "chunk_ssd_live": 1.0} for i in range(4)]
    need = costs_ssd.least_seconds(program, ring[0], cfg, pk)
    obs = {"serve": {"traced": [0.0, 10.0], "ring": ring,
                     "scopes": {scope: 4 * need / 0.5}},
           "trace": {"modules": {module: {"n": 4, "s": 1.0}}}}
    ctx = {"config": cfg, "device": {"kind": kind}}
    assert trace_scope_roofline.read(obs, params, ctx) == pytest.approx(50.0)
    bare = {"serve": {"traced": [0.0, 10.0], "scopes": {scope: 1.0},
                      "ring": [{"ts": 1.0, "active": 4, "chunks": 1}]},
            "trace": obs["trace"]}
    assert trace_scope_roofline.read(bare, params, ctx) is None
    assert trace_scope_roofline.read(
        {"serve": {"ring": ring}, "trace": {}}, params, ctx) is None


def test_the_share_and_ring_readers_of_the_unlisted_files():
    from benchmarks.drivers.replica_falcon_h1 import SCOPES
    from benchmarks.drivers.serve_open_chatburst import LAYER_METRICS

    assert {n for n in LAYER_METRICS} == {
        f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR,
                                                "metrics"))
        if f.endswith(".chatburst.json")}
    ssd, attn = (_spec(n + ".time_share.chatburst") for n in ("ssd", "attn"))
    assert set(ssd["params"]["scopes"]) | set(attn["params"]["scopes"]) \
        <= set(SCOPES)
    obs = {"serve": {"scopes": {"ssd_step": 0.3, "ssd_proj": 0.1,
                                "attn_step": 0.2, "mlp": 1.0}},
           "trace": {"busy_s": 2.0}}
    assert trace_scope_share.read(obs, ssd["params"], {}) == \
        pytest.approx(20.0)
    assert trace_scope_share.read(obs, attn["params"], {}) == \
        pytest.approx(10.0)
    assert trace_scope_share.read({"serve": {}, "trace": {"busy_s": 1.0}},
                                  ssd["params"], {}) is None
    admits = _spec("engine.admits_per_iter.chatburst")
    ring = [{"admitted": n} for n in (0, 0, 1, 3, 0, 2)]
    # an admitted request's iteration admitted (1 + 9 + 4) / 6 on average
    assert ring_ratio.read({"serve": {"ring": ring}}, admits["params"],
                           {}) == pytest.approx(14 / 6)
    assert ring_ratio.read({"serve": {"ring": [{}]}}, admits["params"],
                           {}) is None


def test_the_reference_imports_nothing_of_the_program():
    from benchmarks.reference import falcon_h1_plain as ref

    with open(ref.__file__) as f:
        src = f.read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


@pytest.fixture(scope="module")
def toy():
    """The rehearsal's sizes: the program's tree by the loader, the
    reference's own draw, one sequence."""
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers.replica_falcon_h1 import shape_weights
    from benchmarks.lib.falconh1cfg import model_config, reference_shape
    from benchmarks.reference import falcon_h1_plain as ref
    from ray_tpu.models import falcon_h1 as fm

    with open(os.path.join(manifest.BENCH_DIR, "tests",
                           "rehearsal_chatburst.json")) as f:
        conf = dict(manifest.resolve(manifest.load(), CELL)["config"],
                    **json.load(f)["config"])
    conf["serve"] = dict(conf["serve"], max_seq=128)
    seed = 2147483659
    cfg = model_config(conf)
    params = shape_weights(fm.init(jax.random.PRNGKey(seed % 2 ** 31), cfg),
                           conf["weights"], seed)
    sz = reference_shape(conf)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 512, 48))
    want = ref.logits(ref.draw(seed, sz, conf["weights"]), toks, sz)
    return fm, cfg, params, toks, np.asarray(want)


def test_the_program_is_the_reference_at_the_rehearsal_sizes(toy):
    fm, cfg, params, toks, want = toy
    got = np.asarray(fm.apply(params, toks[None], cfg))[0]
    assert np.sqrt(np.mean((got - want) ** 2)) / want.std() < 1e-5


@pytest.mark.parametrize("broken", ["no_ssm", "no_attention", "no_key_mult",
                                    "rope_shifted", "group_mixed"])
def test_a_broken_path_stands_off_the_reference(toy, broken, monkeypatch):
    """What the cell's controls put into the program on the chip, at toy
    size: each moves the logits' relative rms past the rehearsal's limit
    (2e-4), a hundred times the sound program's distance."""
    import dataclasses

    import jax.numpy as jnp

    fm, cfg, params, toks, want = toy
    if broken == "no_ssm":
        out = fm._ssd_out
        monkeypatch.setattr(fm, "_ssd_out",
                            lambda *a: jnp.zeros_like(out(*a)))
    elif broken == "no_attention":
        out = fm._attn_out
        monkeypatch.setattr(fm, "_attn_out",
                            lambda *a: jnp.zeros_like(out(*a)))
    elif broken == "no_key_mult":
        cfg = dataclasses.replace(cfg, key_multiplier=1.0)
    elif broken == "rope_shifted":
        rope = fm.apply_rope_halves
        monkeypatch.setattr(fm, "apply_rope_halves", lambda x, pos, th: rope(
            x, pos + (1 if x.shape[1] > cfg.n_kv_heads else 0), th))
    else:
        operands = fm._ssd_operands

        def mixed(*a):
            x, dt, b, c = operands(*a)
            first = lambda g: jnp.broadcast_to(g[..., :1, :], g.shape)
            return x, dt, first(b), first(c)

        monkeypatch.setattr(fm, "_ssd_operands", mixed)
    got = np.asarray(fm.apply(params, toks[None], cfg))[0]
    assert np.sqrt(np.mean((got - want) ** 2)) / want.std() > 2e-4, broken
