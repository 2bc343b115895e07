"""The ring-ratio reader on a hand-made ring with known answers, through
the metric files the manifest names — and None, not an error, on the ring
of a program that does not record the keys."""

import pytest

from benchmarks.lib import manifest, result

RING = [
    # admits one request while two slots stream: 0.5 s x 2 slots held up
    {"swap_s": 0.5, "prefill_s": 0.4, "decode_s": 0.03, "active": 3,
     "admitted": 1, "ts": 10.0, "t0": 9.45, "device_wait_s": 0.45,
     "host_s": 0.05, "blocked_slots": 2, "kv_read": 3 * 256,
     "kv_span": 3 * 1024, "requests": [
         {"rid": 1, "request_id": "a", "queue_wait_s": 0.3, "prefill_s": 0.4,
          "first_step_wait_s": 0.1, "ttft_s": 0.8, "prompt_tokens": 40,
          "shared_tokens": 16, "scanned_tokens": 32}]},
    {"swap_s": 0.0, "prefill_s": 0.0, "decode_s": 0.03, "active": 3,
     "admitted": 0, "ts": 10.04, "t0": 10.0, "device_wait_s": 0.03,
     "host_s": 0.01, "blocked_slots": 0, "kv_read": 3 * 128,
     "kv_span": 3 * 1024, "requests": []},
    # admits two into an idle engine: nothing streams yet, nothing held up
    {"swap_s": 1.0, "prefill_s": 0.9, "decode_s": 0.04, "active": 2,
     "admitted": 2, "ts": 12.0, "t0": 10.9, "device_wait_s": 0.92,
     "host_s": 0.04, "blocked_slots": 0, "kv_read": 2 * 384,
     "kv_span": 2 * 1024, "requests": [
         {"rid": 2, "request_id": "b", "queue_wait_s": 0.1, "prefill_s": 0.3,
          "first_step_wait_s": 0.8, "ttft_s": 1.2, "prompt_tokens": 20,
          "shared_tokens": 0, "scanned_tokens": 32},
         {"rid": 3, "request_id": "c", "queue_wait_s": 0.6, "prefill_s": 0.6,
          "first_step_wait_s": 0.05, "ttft_s": 1.25, "prompt_tokens": 60,
          "shared_tokens": 0, "scanned_tokens": 64}]},
]
ANSWERS = {
    "engine.ttft_queue_share.chat": 100 * 1.0 / 3.25,
    "engine.prefill_ms_per_token.chat": 1000 * 1.3 / (24 + 20 + 60),
    "engine.prefill_pad_share.chat": 100 * (128 - 104) / 128,
    "engine.decode_blocked_share.chat":
        100 * 1.0 / (1.0 + 0.09 + 0.09 + 0.08),
    "engine.host_share.chat": 100 * 0.10 / (0.10 + 1.40),
    # key positions read (slots x live blocks x 128) over slots x max_total
    "model.kv_read_share.chat": 100 * (768 + 384 + 768) / (8 * 1024),
}


@pytest.fixture(scope="module")
def ring_metrics():
    cell = manifest.resolve(manifest.load(), "serve-large-chat-loaded")
    return [m for m in cell["per_layer"] if m["reader"] == "ring_ratio"]


def test_ring_ratio_known_answers(ring_metrics):
    # at least these: a later PR may append a `ring_ratio` metric
    assert {m["name"] for m in ring_metrics} >= set(ANSWERS)
    got = result.read_metrics(ring_metrics, {"serve": {"ring": RING}}, {})
    for name, want in ANSWERS.items():
        assert got[name]["value"] == pytest.approx(want), name


@pytest.mark.parametrize("ring", [
    [{k: r[k] for k in ("swap_s", "prefill_s", "decode_s", "active",
                        "admitted", "ts")} for r in RING],
    [],
], ids=["parent_ring", "empty_ring"])
def test_ring_ratio_reads_nothing_from_a_ring_without_the_keys(
        ring_metrics, ring, capsys):
    assert result.read_metrics(ring_metrics, {"serve": {"ring": ring}},
                               {}) == {}
    assert capsys.readouterr().err == ""        # None, not a caught error


def test_decode_step_device_ms_reads_the_serve_step_module_only():
    cell = manifest.resolve(manifest.load(), "serve-large-chat-loaded")
    m, = [m for m in cell["per_layer"]
          if m["name"] == "engine.decode_step_device_ms.chat"]
    mods = {"jit_serve_step(123)": {"s": 0.56, "n": 20.0},
            "jit_serve_prefill(9)": {"s": 0.8, "n": 2.0},
            "jit_step(77)": {"s": 5.0, "n": 1.0}}
    got = result.read_metrics([m], {"trace": {"modules": mods}}, {})
    assert got[m["name"]]["value"] == pytest.approx(28.0)
    parent = {"jit_step(77)": {"s": 0.56, "n": 20.0},
              "jit__unknown(9)": {"s": 0.8, "n": 2.0}}
    assert result.read_metrics([m], {"trace": {"modules": parent}}, {}) == {}
