"""Time of the device operations matching `pattern` over the chip's busy
time, from the trace."""

from benchmarks.trace.reduce import ops_matching


def read(obs, params, ctx):
    red = obs["trace"]
    s, n = ops_matching(red, params["pattern"])
    return 100.0 * s / red["busy_s"] if n else None
