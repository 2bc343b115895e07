"""Client-side time to first token (from SEND) minus the replica-side
one (arrival at stream_tokens to its first yield), paired per request:
what handle, router and the replica's transport add.  Median."""

from benchmarks.lib.stats import percentile


def read(obs, params, ctx):
    hops = [1000.0 * ((r["times"][0] - r["sent"])
                      - (r["replica"][1] - r["replica"][0]))
            for r in obs["serve"]["requests"]
            if r.get("replica") and r["times"]
            and not r["error"]]
    return percentile(hops, 50) if hops else None
