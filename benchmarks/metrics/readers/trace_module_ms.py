"""Device time of one program (an `XLA Modules` event whose name matches
`module`) per execution, from the trace; per chip."""

from benchmarks.trace.reduce import module_time


def read(obs, params, ctx):
    s, n = module_time(obs["trace"], params["module"])
    return 1000.0 * s / n if n else None
