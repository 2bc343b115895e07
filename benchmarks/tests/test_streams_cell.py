"""The cell `serve-brumby-streams`: its files resolve by name, the
configuration keeps every published width, the traffic's cycle is the same
for every seed, the costs agree with hand counts, each reader it brings
reads a fixture, and the check sees the broken paths at a small size."""

import json
import os

import pytest

from benchmarks.lib import costs_retention as costs
from benchmarks.lib import manifest, peaks
from benchmarks.lib import traffic as T
from benchmarks.metrics.readers import ring_ratio, trace_retention_roofline

CELL = "serve-brumby-streams"
# the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
             "hidden_size": 5120, "intermediate_size": 17408,
             "max_position_embeddings": 32768, "max_window_layers": 40,
             "model_type": "brumby", "num_attention_heads": 40,
             "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
             "rope_scaling": None, "rope_theta": 1000000,
             "sliding_window": None, "tie_word_embeddings": False,
             "use_sliding_window": False, "vocab_size": 151936}
TWINS = ("engine.decode_step_device_ms", "engine.decode_step_ms",
         "engine.host_share", "engine.decode_blocked_share",
         "engine.prefill_ms_per_token", "engine.prefill_pad_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load(), CELL)


def _params(name):
    with open(os.path.join(manifest.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)["params"]


def test_the_cell_resolves_with_every_metric_of_the_issue(cell):
    assert cell["cell"]["chips"] == 1
    assert cell["traffic"]["kind"] == "serve_open_streams"
    # the p75 TTFT is not among them: over seeds it spreads by more than
    # half its bound in this cell (PERF.md section 6, PR 40)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "itl_p99_ms", "setup_s"}
    assert {m["moves"] for m in cell["per_layer"]} == {"itl_p99_ms"}
    names = {m["name"]: m for m in cell["per_layer"]}
    # at least these: a later PR may append a metric to the cell
    assert set(names) >= {n + ".streams" for n in TWINS + (
        "retention.time_share", "retention.step_roofline",
        "retention.chunk_roofline", "retention.dead_state_share")}
    assert all(m["workloads"] == [CELL] for m in names.values())
    for n in TWINS:                 # a twin reads what its sibling reads
        assert _params(n + ".streams") == _params(n + ".mixedctx")


def test_the_configuration_keeps_every_published_key(cell):
    cfg = cell["config"]
    for k, v in PUBLISHED.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cell["config_entry"]["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["num_hidden_layers"] == 8
    assert set(cfg["assumed"]) >= {"degree", "gate", "normaliser",
                                   "rope_and_qk_norm"}
    assert cfg["deployment_share"] == {"chips_per_layer": 1,
                                       "pipeline_stages": 5}
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["state_dtype"]) \
        == ("bfloat16", "bfloat16", "float32")
    ek = cfg["serve"]["engine_kwargs"]
    assert ek["num_pages"] == {"ret": 1 + ek["max_slots"]}
    assert (ek["max_slots"], ek["max_total"], ek["prefill_chunk"],
            ek["prefill_bucket"]) == (16, 32768, 512, 128)
    assert set(cfg["memory"]) >= {"arithmetic", "rehearsed", "measured"}


def test_the_loader_shapes_the_leaves_the_configuration_names(cell):
    import numpy as np

    from benchmarks.drivers.replica_brumby import shape_weights

    w = cell["config"]["weights"]
    assert w["scales"] == {}            # the plain draw (the file says why)
    # the gate forgets over 50 to 1,000 tokens (the draw adds N(0, 1))
    g = 1 / (1 + np.exp(-(w["gate_bias"] + np.array([-1.5, 1.5]))))
    assert 0.98 <= g[0] < g[1] <= 0.9995
    one = np.ones((2, 3), np.float32)               # [layers, ..]
    plain = {"embed": one, "layers": {"wo": one, "bg": one, "wk": one}}
    got = shape_weights(plain, {"scales": {"wo": 8}, "gate_bias": 5.0})
    assert got["embed"] is one and got["layers"]["wk"] is one
    assert (got["layers"]["wo"] == 8).all()
    assert (got["layers"]["bg"] == 6).all()
    assert shape_weights(plain, w)["layers"]["wo"] is one


def test_the_cycle_is_the_same_for_every_seed(cell):
    tr = cell["traffic"]
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.8, "min": 64, "max": 2048}
    assert {k: tr["output_len"][k] for k in ("median", "sigma", "min",
                                             "multiple_of")} == {
        "median": 256, "sigma": 0.5, "min": 64, "multiple_of": 32}
    assert tr["output_len"]["max"] in (384, 512)
    assert tr["max_in_flight"] == 48 and tr["token_id_max"] == 151936
    plans = [T.open_schedule(tr, seed, 50.0, 151936)
             for seed in (1, 2147483659, 4000000007)]
    sizes = [sorted((len(p["tokens"]), p["max_new_tokens"]) for p in plan)
             for plan in plans]
    assert sizes[0] == sizes[1] == sizes[2]
    again = T.open_schedule(tr, 2147483659, 50.0, 151936)
    assert again == plans[1]                    # bit for bit
    for plan in plans:
        assert plan[0]["due"] == 0.0
        assert max(t for p in plan for t in p["tokens"]) > 140000
        assert all(p["max_new_tokens"] % 32 == 0 for p in plan)
        assert max(len(p["tokens"]) + p["max_new_tokens"] for p in plan) \
            <= tr["reference"]["max_context"]
    assert plans[0][0]["tokens"] != plans[1][0]["tokens"]
    # one to four chunks of 512; a good part of the prompts carry a state
    # from one chunk to the next
    chunks = [-(-len(p["tokens"]) // 512) for p in plans[0]]
    assert max(chunks) <= 4 and 0.2 < sum(c > 1 for c in chunks) / len(
        chunks) < 0.5


def test_retention_costs_against_hand_counts(cell):
    cfg = cell["config"]
    # a K/V head's state: 8,256 features x (128 values + the normaliser)
    assert costs.state_bytes(cfg) == 8 * 8256 * 129 * 4 == 34080768
    # a step: every live state of every layer read and written once
    assert costs.step_bytes(12, cfg) == 2 * 12 * 8 * 34080768
    # update 3 operations an element, read-out 2 for each of 5 query heads
    assert costs.step_flops(1, cfg) == 8 * 8 * 8256 * 129 * (3 + 10)
    # a chunk of 512 rows: 5 query heads and the keys against 8256 x 129,
    # and half of 512 x 512 scores and weighted values a query head
    per_head = 2 * 512 * 8256 * 129 * 6 + 5 * 512 * 512 * 257
    assert costs.chunk_flops(512, 1, cfg) == 8 * 8 * per_head
    assert 0.4e12 < costs.chunk_flops(512, 1, cfg) < 0.5e12   # ~ 1/7 of 3.2
    pk = peaks.peak("TPU v5 lite")
    step = costs.least_seconds("step", {"active": 12}, cfg, pk)
    assert step == pytest.approx(2 * 12 * 8 * 34080768 / 819e9)   # bytes
    chunk = costs.least_seconds(
        "chunk", {"chunk_tokens": 512, "chunk_ret_states": 1.0}, cfg, pk)
    assert chunk == pytest.approx(costs.chunk_flops(512, 1, cfg) / 197e12)


RING = [
    {"ts": 10.5, "active": 12, "chunks": 0, "chunk_tokens": 0,
     "ret_states": 16.0, "chunk_ret_states": 0.0, "states_live": 13},
    {"ts": 10.8, "active": 10, "chunks": 2, "chunk_tokens": 700,
     "ret_states": 16.0, "chunk_ret_states": 2.0, "states_live": 12},
    {"ts": 10.9, "active": 0, "chunks": 1, "chunk_tokens": 512,
     "ret_states": 0.0, "chunk_ret_states": 1.0, "states_live": 1},
    {"ts": 99.0, "active": 16, "chunks": 0, "chunk_tokens": 0,
     "ret_states": 16.0, "chunk_ret_states": 0.0, "states_live": 16},
]


def test_readers_on_a_fixture(cell):
    cfg = cell["config"]
    obs = {"serve": {"ring": RING[:3]}}
    dead = ring_ratio.read(obs, _params("retention.dead_state_share.streams"),
                           {})
    assert dead == pytest.approx(100 * (32 - 22) / 32)
    pk = peaks.peak("TPU v5 lite")
    ctx = {"config": cfg, "device": {"kind": "TPU v5 lite"}}
    red = {"modules": {"jit_serve_step(1)": {"s": 0.05, "n": 3},
                       "jit_serve_prefill(2)": {"s": 0.1, "n": 2},
                       "jit_serve_prefill(3)": {"s": 0.1, "n": 2}}}
    obs = {"trace": red, "serve": {
        "ring": RING, "traced": [10.0, 11.0],
        "scopes": {"retention_step": 0.03, "retention_chunk": 0.02}}}
    steps = [costs.least_seconds("step", r, cfg, pk) for r in RING[:2]]
    got = trace_retention_roofline.read(
        obs, _params("retention.step_roofline.streams"), ctx)
    assert got == pytest.approx(100 * sum(steps) / 2 * 3 / 0.03)
    chunks = [costs.least_seconds("chunk", r, cfg, pk) for r in RING[1:3]]
    got = trace_retention_roofline.read(
        obs, _params("retention.chunk_roofline.streams"), ctx)
    assert got == pytest.approx(100 * sum(chunks) / 3 * 4 / 0.02)
    assert 0 < got < 100
    # a program without the scopes, a run without a traced stretch, or a
    # ring without the counters (the parent's): nothing to read, and
    # nothing raised
    step, chunk = (_params(f"retention.{n}_roofline.streams")
                   for n in ("step", "chunk"))
    read = trace_retention_roofline.read
    no_scopes = {"trace": red, "serve": {"ring": RING, "traced": [10, 11]}}
    untraced = {"trace": red, "serve": dict(obs["serve"], traced=None)}
    old_ring = {"trace": red, "serve": dict(
        obs["serve"], ring=[{"ts": 10.5, "chunks": 1}])}
    for bare in (no_scopes, untraced, old_ring):
        assert read(bare, step, ctx) is None
        assert read(bare, chunk, ctx) is None


def test_the_check_sees_the_broken_paths_at_a_small_size():
    """`served_gaps` in row blocks against padded keys is the whole
    reference: the sound program's greedy tokens are its argmax everywhere
    and its replayed logits the reference's (float32 on both sides), and
    each broken path — a first chunk that reads its entry's last holder,
    chunks that drop the carried state — serves tokens that are not."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "scripts",
                                      "study_brumby_controls.py"), "--toy",
         "5"], capture_output=True, text=True, timeout=600,
        cwd=manifest.ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = {r["variant"]: r for r in (
        json.loads(ln) for ln in out.stdout.splitlines()
        if ln.startswith('{"phase": "reading"'))}
    assert got["sound"]["argmax_share"] == 1.0
    assert got["sound"]["worst_gap"] == 0.0
    assert got["sound"]["logit_rel_rms"] < 1e-5
    for broken in ("unzeroed", "dropped_carry"):
        assert got[broken]["argmax_share"] < 0.8, broken
        assert got[broken]["worst_gap"] > 1.0, broken
        assert got[broken]["logit_rel_rms"] > 0.5, broken
    # a state kept in bfloat16 flips hardly a token of these short
    # streams: the program's logits, replayed, are what shows it
    assert got["state_bf16"]["argmax_share"] > 0.95
    assert got["state_bf16"]["logit_rel_rms"] > 1e-3
    # a request of one chunk loses nothing to a dropped carry
    one_chunk = [p for p in got["dropped_carry"]["per_request"] if p[0] < 30]
    assert one_chunk and all(p[1] == 1.0 for p in one_chunk)
