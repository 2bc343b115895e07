"""Model FLOP/s utilisation: tokens/s x (6N + 12 L d S) over chips x the
published peak.  Recomputation under remat is not counted."""

from benchmarks.lib import costs, peaks


def read(obs, params, ctx):
    tr = obs["train"]
    per_tok = costs.train_flops_per_token(ctx["config"], tr["seq"])
    peak = peaks.peak(ctx["device"]["kind"])["flops_per_s"]
    return 100.0 * (tr["tokens"] / obs["window_s"]) * per_tok \
        / (tr["chips"] * peak)
