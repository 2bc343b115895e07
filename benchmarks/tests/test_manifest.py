"""The manifest loader: refuses names and units outside the allowed
characters, and finds every file of every cell of the repo's own manifest."""

import copy
import json
import os

import pytest

from benchmarks.lib import manifest


@pytest.fixture()
def man():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_repo_manifest_is_valid_and_every_cell_resolves(man):
    manifest.validate(man)
    for w in man["workloads"]:
        cell = manifest.resolve(man, w["name"])
        assert cell["traffic"]["kind"]
        listed = next(c for c in man["configs"] if c["name"] == w["config"])
        assert cell["config"]["reduced"] == listed["reduced"]
        published = ("n_layer", "n_embd", "n_head", "n_inner", "n_positions")
        assert not set(listed["reduced"]) & set(published)   # no width, no depth
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert m["moves"] in names, (w["name"], m["name"])
            assert os.path.exists(os.path.join(
                manifest.BENCH_DIR, "metrics", "readers", m["reader"] + ".py"))
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "drivers", cell["traffic"]["kind"] + ".py"))


@pytest.mark.parametrize("cell,traffic", [
    ("serve-large-chat", "open-chat"),                      # PR 39
    ("serve-phi4flash-longgen", "open-longgen"),            # PR 55
    ("serve-commandaplus-mixedctx", "open-mixedctx"),       # PR 55
])
def test_nothing_names_a_cell_or_a_traffic_file_that_is_gone(cell, traffic):
    """Not BENCHMARK.json, and no file under benchmarks/ but this one: a
    retired name is followed by `-loaded` wherever it still stands."""
    import re

    man = manifest.load()
    files = {f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR,
                                                     "traffic"))}
    assert {w["traffic"] for w in man["workloads"]} == files
    for m in man["end_to_end"] + man["per_layer"]:   # load() checked the names
        assert m.get("workloads", True), m["name"]     # none left empty
        assert cell not in m.get("workloads", ())
    assert cell not in {w["name"] for w in man["workloads"]}
    assert traffic not in files
    gone = re.compile("(%s|%s)(?!-loaded)(?![a-z])" % (re.escape(cell),
                                                       re.escape(traffic)))
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        assert not gone.search(f.read())
    for top, _, names in os.walk(manifest.BENCH_DIR):
        for n in names:
            path = os.path.join(top, n)
            if not n.endswith((".py", ".json", ".toml", ".txt", ".csv",
                               ".jsonl", ".md")) or \
                    os.path.samefile(path, __file__):
                continue
            with open(path) as f:
                assert not gone.search(f.read()), path


def test_contract_limits(man):
    cells = len(man["workloads"])
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, cells // 4)
    assert 1 <= man["run_seconds"] <= 51
    # a full check with the full 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (man["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for w in man["workloads"]:
        assert len(w["why"]) <= 200
    assert len(json.dumps(man)) < 64 * 1024


@pytest.mark.parametrize("where,key,bad", [
    ("workloads", "name", "serve large"),        # a space
    ("workloads", "name", "serve/large"),        # a slash
    ("workloads", "traffic", "open,chat"),       # a comma
    ("end_to_end", "name", "ttft p90"),
    ("end_to_end", "unit", "tokens per second"),  # spaces
    ("end_to_end", "unit", "µs"),            # the Greek letter
    ("end_to_end", "unit", "x" * 17),
    ("per_layer", "name", "-starts-with-dash"),
    ("per_layer", "name", "n" * 65),
    ("configs", "name", "gpt2 medium"),
])
def test_refuses_names_and_units_outside_the_character_set(man, where, key, bad):
    m = copy.deepcopy(man)
    m[where][0][key] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m)


def test_refuses_a_per_layer_metric_that_moves_nothing(man):
    m = copy.deepcopy(man)
    m["per_layer"][0]["moves"] = "no_such_metric"
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m)


def test_unknown_cell(man):
    with pytest.raises(manifest.ManifestError):
        manifest.resolve(man, "no-such-cell")
