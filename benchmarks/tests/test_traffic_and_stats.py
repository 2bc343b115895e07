"""The general generator and the percentile arithmetic."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import manifest, stats, traffic
from benchmarks.metrics.readers import itl_percentile, ttft_percentile


def _traffic(name):
    with open(os.path.join(manifest.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_schedule_and_lengths():
    tr = _traffic("open-chat-loaded")
    a = traffic.open_schedule(tr, 3000000019, 50, 50304)
    b = traffic.open_schedule(tr, 3000000019, 50, 50304)
    assert a == b


def _shape(r):
    return (len(r["tokens"]), r["max_new_tokens"])


def _gaps(win, seconds):
    due = [r["due"] for r in win] + [seconds]
    return [round(b - a, 9) for a, b in zip(due, due[1:])]


def _rotated(xs, k):
    return xs[k:] + xs[:k]


def test_other_seed_same_cycle_entered_elsewhere_other_token_ids():
    tr = dict(_traffic("open-chat-loaded"),
              entry_after_idle_s=0)                      # any arrival
    n = round(tr["arrivals"]["rate_per_s"] * 50)
    seeds = (1, 2, 2147483659, 3000000019)
    wins = {s: traffic.open_schedule(tr, s, 50, 50304) for s in seeds}
    a = wins[1]
    assert len(a) == n and a[0]["due"] == 0.0 and a[-1]["due"] < 50
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
    assert len({_shape(r) for r in a}) > n // 2        # sizes do vary
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert all(lo <= len(r["tokens"]) <= hi and max(r["tokens"]) < 50257
               for r in a)
    shapes = {s: [_shape(r) for r in wins[s]] for s in seeds}
    assert len({tuple(v) for v in shapes.values()}) > 1   # the seed moves it
    for s in seeds:        # the same requests and gaps, in cyclic order
        k = next(k for k in range(n) if _rotated(shapes[1], k) == shapes[s])
        assert _gaps(wins[s], 50) == pytest.approx(
            _rotated(_gaps(a, 50), k), abs=1e-6)
    assert wins[1][0]["tokens"] != wins[2][0]["tokens"]   # ids are the seed's


def test_the_window_opens_after_an_idle_stretch():
    tr = _traffic("open-chat-loaded")
    idle = tr["entry_after_idle_s"]
    firsts = set()
    for seed in range(40):
        win = traffic.open_schedule(tr, seed, 50, 50304)
        assert 50 - win[-1]["due"] >= idle        # the cycle's last gap
        firsts.add(_shape(win[0]))
    assert len(firsts) >= 2                       # more than one such place
    short = dict(tr, entry_after_idle_s=1e9)      # none: the longest is taken
    win = traffic.open_schedule(short, 3, 50, 50304)
    assert 50 - win[-1]["due"] == pytest.approx(max(_gaps(win, 50)))


def test_the_cycle_offers_at_least_eight_entries():
    """At this rate no stretch of the cycle is idle for seconds; a rule
    that found none would open every seed's window at ONE arrival."""
    tr = _traffic("open-chat-loaded")
    entries = set()
    for seed in range(64):
        win = traffic.open_schedule(tr, seed, 50, 50304)
        assert 50 - win[-1]["due"] >= tr["entry_after_idle_s"]
        entries.add((_shape(win[0]), round(50 - win[-1]["due"], 9)))
    assert len(entries) >= 8


def test_output_lengths_are_multiples_of_eight_inside_the_clip():
    tr = _traffic("open-chat-loaded")
    spec = tr["output_len"]
    assert spec["multiple_of"] == 8
    n = round(tr["arrivals"]["rate_per_s"] * 50)
    outs = [r["max_new_tokens"] for r in traffic.serve_population(tr, n)]
    assert all(o % 8 == 0 and spec["min"] <= o <= spec["max"] for o in outs)
    assert len(set(outs)) <= 31 and len(set(outs)) > 16
    # a clip that is no multiple itself is drawn in, never passed
    odd = dict(spec, min=13, max=61)
    got = traffic.draw_lengths(odd, 4000, np.random.default_rng(0))
    assert got.min() == 16 and got.max() == 56 and not (got % 8).any()
    # without the key: the plain rounding, as ever
    plain = {k: v for k, v in spec.items() if k != "multiple_of"}
    a = traffic.draw_lengths(plain, 4000, np.random.default_rng(0))
    assert (a % 8).any() and a.min() == spec["min"] and a.max() == spec["max"]


@pytest.mark.parametrize("name,seed,want", [
    ("open-mixedctx-loaded", 1, "e7a071b496e25281"),
    ("open-mixedctx-loaded", 3000000019, "432a2c47ec8a630a"),
    ("open-longgen-loaded", 1, "86176d3419c03691"),
    ("open-longgen-loaded", 3000000019, "50918199abf23f00"),
    ("packed-1024", 1, "896707878b440c2e"),
    ("packed-1024", 3000000019, "a414c38bc762d5b0"),
    ("open-chat-loaded", 1, "aba23c20711a1e26"),
    ("open-chat-loaded", 3000000019, "accbf5fc6304a326"),
    ("open-streams", 1, "71a8de709717e0bc"),
    ("open-streams", 3000000019, "d3e6fd710786e1e7"),
    ("open-longctx", 1, "99e697dc27e37930"),
    ("open-longctx", 3000000019, "a6750949688544c7"),
    ("open-reasoning", 1, "e569245e20ae8483"),
    ("open-reasoning", 3000000019, "579af2d52ea42993"),
])
def test_the_kept_cells_schedules_are_the_parents_bit_for_bit(name, seed,
                                                              want):
    """`packed-1024`: digests taken with commit 4ec08fa's `lib/traffic.py`
    (before the `multiple_of` key).  The four kept serve files (PR 55):
    taken at commit cc97c58, the parent of the PR that restated the two
    `-loaded` files below them; those two pin what PR 55 committed (a new
    rate is a new schedule: the retired files' read 9aa2d4cbe5355d35 /
    4010113c000e8ae7 and c7a260f898a291cb / 3b31f913bf2fbb21)."""
    import hashlib

    tr = _traffic(name)
    if tr["kind"] == "train_packed":
        it, h = traffic.packed_batches(tr, seed, 8, 50304), hashlib.sha256()
        for _ in range(3):
            b = next(it)
            h.update(b["inputs"].tobytes())
            h.update(b["targets"].tobytes())
        got = h.hexdigest()
    else:
        got = hashlib.sha256(json.dumps(
            traffic.open_schedule(tr, seed, 50, 32768),
            sort_keys=True).encode()).hexdigest()
    assert got[:16] == want


@pytest.mark.parametrize("name,old_rate,want", [
    ("open-longgen-loaded", 0.64, "dd67203705351a37"),
    ("open-mixedctx-loaded", 0.6, "81be949a1b49a29a"),
])
def test_a_restated_file_differs_from_the_retired_one_in_rate_and_why_only(
        name, old_rate, want):
    """PR 55 re-rated two cells as new files beside the old and retired
    the old.  `want` is the retired file's digest (commit cc97c58) with
    the rate and the `why` taken out: lengths, `population_seed`,
    `entry_after_idle_s`, `max_in_flight`, `token_id_max`, the trace's
    offsets and the whole `reference` block, its limits and its text, are
    the retired file's letter for letter."""
    import hashlib

    tr = _traffic(name)
    assert tr["arrivals"].pop("rate_per_s") >= old_rate   # never under it
    assert len(tr.pop("why")) > 200
    got = hashlib.sha256(json.dumps(tr, sort_keys=True).encode()).hexdigest()
    assert got[:16] == want


def test_gamma_arrivals_keep_the_mean_rate():
    tr = dict(_traffic("open-chat-loaded"),
              arrivals={"process": "gamma", "cv": 3, "rate_per_s": 5.0})
    s = [r["due"] for r in traffic.open_schedule(tr, 7, 100, 50304)]
    assert len(s) == 500 and s[-1] < 100
    gaps = np.diff(s)
    assert gaps.std() / gaps.mean() > 2.0       # burstier than Poisson


def test_packed_batches_are_full_rows_from_the_seed():
    tr = _traffic("packed-1024")
    a = next(traffic.packed_batches(tr, 5, 4, 50304))
    b = next(traffic.packed_batches(tr, 5, 4, 50304))
    c = next(traffic.packed_batches(tr, 6, 4, 50304))
    assert a["inputs"].shape == a["targets"].shape == (4, 1024)
    assert np.array_equal(a["inputs"], b["inputs"])
    assert not np.array_equal(a["inputs"], c["inputs"])
    assert np.array_equal(a["inputs"][:, 1:], a["targets"][:, :-1])
    assert (a["inputs"] == tr["eos_id"]).sum() >= 1      # document ends
    assert a["inputs"].max() < 50257
    it = traffic.packed_batches(tr, 5, 4, 50304)
    first, second = next(it), next(it)
    assert not np.array_equal(first["inputs"], second["inputs"])


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 0)


def test_iqr_spread_is_the_contracts():
    import statistics

    v = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(v, n=4)
    assert stats.iqr_spread(v) == (q[2] - q[0]) / statistics.median(v)


def _obs(reqs):
    return {"serve": {"requests": reqs, "timeout_ms": 160000.0}}


def test_a_stalled_sender_is_timed_from_the_due_time():
    # due at 10.0, the sender only got to it at 10.4, first token at 10.5:
    # the user waited 500 ms, not 100
    r = {"due": 10.0, "sent": 10.4, "times": [10.5, 10.6, 10.8],
         "error": None}
    assert ttft_percentile.read(_obs([r]), {"p": 90}, {}) == \
        pytest.approx(500.0)
    assert itl_percentile.read(_obs([r]), {"p": 95}, {}) == \
        pytest.approx(200.0)


def test_a_failed_request_is_worse_than_every_success():
    ok = [{"due": 0.0, "sent": 0.0, "times": [0.1 + i * 0.001], "error": None}
          for i in range(8)]
    bad = [{"due": 0.0, "sent": 0.0, "times": [], "error": "AdmissionRejected"}
           for _ in range(2)]
    assert ttft_percentile.read(_obs(ok + bad), {"p": 90}, {}) == 160000.0
    assert ttft_percentile.read(_obs(ok + bad), {"p": 80}, {}) < 200.0
