"""Engine phase ring: `scale` x the median of `key` over the window's
records whose `where` is non-zero (`where`: "active" picks the iterations
that ran a decode step).

A ring without the key — a program from before the engine recorded it —
or with no such record is nothing to read: None, and the line leaves the
metric out."""

from benchmarks.lib.stats import median


def read(obs, params, ctx):
    key, where = params["key"], params["where"]
    vals = [r[key] for r in obs["serve"]["ring"]
            if r.get(where) and key in r]
    return params["scale"] * median(vals) if vals else None
