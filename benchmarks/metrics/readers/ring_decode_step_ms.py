"""Engine phase ring: median seconds of one decode step (host clock
around the step program and the transfer of its tokens)."""

from benchmarks.lib.stats import median


def read(obs, params, ctx):
    steps = [r["decode_s"] for r in obs["serve"]["ring"] if r["active"]]
    return 1000.0 * median(steps) if steps else None
