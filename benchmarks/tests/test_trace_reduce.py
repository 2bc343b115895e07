"""The trace reduction on the recorded fixture beside this file
(`fixture_trace.textproto`, written by make_fixture.py, whose docstring
derives the known answers)."""

import os

import pytest

from benchmarks.lib import costs
from benchmarks.metrics.readers import (trace_collective_exposed_share,
                                        trace_module_ms, trace_op_share)
from benchmarks.trace import reduce as R

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture_trace.textproto")


@pytest.fixture(scope="module")
def red():
    return R.reduce_trace(FIXTURE)


def test_busy_share_and_window(red):
    assert red["n_devices"] == 2
    assert red["window_s"] == pytest.approx(170e-6)
    assert red["busy_s"] == pytest.approx(130e-6)       # overlaps once


def test_exposed_collective_time(red):
    assert red["collective_s"] == pytest.approx(50e-6)
    assert red["collective_exposed_s"] == pytest.approx(40e-6)
    assert trace_collective_exposed_share.read({"trace": red}, {}, {}) == \
        pytest.approx(100 * 40 / 170)


def test_ops_by_name_and_by_stats_text(red):
    assert R.ops_matching(red, "flash")[0] == pytest.approx(20e-6)
    assert R.ops_matching(red, r"^fusion")[0] == pytest.approx(70e-6)
    assert R.top_ops(red)[0] == ["fusion", pytest.approx(70e-6)]
    assert trace_op_share.read({"trace": red}, {"pattern": "flash"}, {}) == \
        pytest.approx(100 * 20 / 130)
    assert trace_op_share.read({"trace": red}, {"pattern": "nothing"},
                               {}) is None


def test_modules_and_gap_attribution(red):
    assert trace_module_ms.read({"trace": red}, {"module": "^jit_step"},
                                {}) == pytest.approx(0.07)
    gaps = dict(red["gaps"])
    assert gaps["bench.fence"] == pytest.approx(30e-6)
    assert gaps["bench.fetch"] == pytest.approx(10e-6)


def test_interval_arithmetic():
    assert R.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert R.subtract_length([(0, 10)], [(2, 3), (5, 20)]) == 4
    assert R.subtract_length([(0, 1), (4, 6)], []) == 3


def test_cost_functions():
    f = costs.flash_attention_flops(1, 1, 1024, 64, causal=True)
    assert f == 2 * 2 * 1024 * 1024 * 64 / 2
    assert costs.flash_attention_flops(1, 1, 1024, 64, backward=True) == 2.5 * f
    medium = {"n_layer": 24, "n_embd": 1024, "n_head": 16, "head_dim": 64,
              "n_inner": 4096, "vocab_size": 50304, "n_positions": 1024}
    n = costs.n_params(medium)
    assert 350e6 < n < 360e6
    assert costs.train_flops_per_token(medium, 1024) == pytest.approx(
        6 * (n - 1024 * 1024) + 12 * 24 * 1024 * 1024)
