"""The ring-median reader on a hand-made ring with known medians, through
the metric files the manifest names; the dispatch share through
`ring_ratio` beside it — and None, not an error, on the ring of a program
that does not record the keys."""

import pytest

from benchmarks.lib import manifest, result

CELLS = {"chat": "serve-large-chat-loaded",
         "mixedctx": "serve-commandaplus-mixedctx-loaded",
         "streams": "serve-brumby-streams"}
NEW = ("engine.dispatch_share", "engine.step_dispatch_ms",
       "engine.step_wait_ms")

# (active, iter_s, dispatch_s, step_dispatch_s, step_wait_s): three plain
# steps, one iteration that admits (its dispatch holds the admission's
# launches too), one that ran a chunk and no step
RING = [{"active": a, "iter_s": i, "dispatch_s": d, "step_dispatch_s": sd,
         "step_wait_s": sw}
        for a, i, d, sd, sw in ((3, 0.0065, 0.0012, 0.0012, 0.0050),
                                (3, 0.0066, 0.0014, 0.0014, 0.0049),
                                (4, 0.0140, 0.0040, 0.0016, 0.0046),
                                (0, 0.0600, 0.0020, 0.0, 0.0),
                                (2, 0.0064, 0.0010, 0.0010, 0.0052))]
ANSWERS = {
    "engine.dispatch_share": 100 * 0.0096 / 0.0935,
    # medians over the four records that ran a step: the chunk-only
    # iteration's zeros are not among them
    "engine.step_dispatch_ms": 1000 * (0.0012 + 0.0014) / 2,
    "engine.step_wait_ms": 1000 * (0.0049 + 0.0050) / 2,
}


def _metrics(suffix):
    cell = manifest.resolve(manifest.load(), CELLS[suffix])
    return [m for m in cell["per_layer"]
            if m["name"] in {f"{n}.{suffix}" for n in NEW}]


@pytest.mark.parametrize("suffix", list(CELLS))
def test_known_answers(suffix):
    metrics = _metrics(suffix)
    assert {m["reader"] for m in metrics} == {"ring_ratio", "ring_median"}
    got = result.read_metrics(metrics, {"serve": {"ring": RING}}, {})
    assert set(got) == {f"{n}.{suffix}" for n in NEW}
    for name, want in ANSWERS.items():
        m = got[f"{name}.{suffix}"]
        assert m["value"] == pytest.approx(want), name
        assert m["unit"] == ("%" if "share" in name else "ms")


@pytest.mark.parametrize("ring", [
    [{k: r[k] for k in ("active", "iter_s")} for r in RING],
    [dict(r, active=0) for r in RING],
    [],
], ids=["parent_ring", "no_step_ran", "empty_ring"])
def test_reads_nothing_where_there_is_nothing(ring, capsys):
    got = result.read_metrics(_metrics("chat"), {"serve": {"ring": ring}}, {})
    # a ring that ran no step still has a dispatch share to give
    assert set(got) <= {"engine.dispatch_share.chat"}
    assert bool(got) == (bool(ring) and "dispatch_s" in ring[0])
    assert capsys.readouterr().err == ""        # None, not a caught error


def test_where_filters_on_the_named_key():
    m = dict(_metrics("chat")[1], params={"key": "step_wait_s",
                                          "where": "admitted",
                                          "scale": 1.0})
    ring = [dict(r, admitted=int(i == 2)) for i, r in enumerate(RING)]
    got = result.read_metrics([m], {"serve": {"ring": ring}}, {})
    assert got[m["name"]]["value"] == pytest.approx(0.0046)
