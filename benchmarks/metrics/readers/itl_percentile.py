"""Gaps between consecutive streamed tokens of one request, all gaps of
the window pooled, p-th percentile."""

from benchmarks.lib.stats import percentile


def read(obs, params, ctx):
    gaps = [1000.0 * (b - a) for r in obs["serve"]["requests"]
            for a, b in zip(r["times"], r["times"][1:])]
    return percentile(gaps, params["p"])
