"""Time from when a request was DUE to its first streamed token at the
client, p-th percentile over every request of the window.  A failed or
rejected request counts as worse than every success (the time-out)."""

from benchmarks.lib.stats import percentile


def read(obs, params, ctx):
    sv = obs["serve"]
    vals = [sv["timeout_ms"] if r["error"] or not r["times"]
            else 1000.0 * (r["times"][0] - r["due"])
            for r in sv["requests"]]
    return percentile(vals, params["p"])
