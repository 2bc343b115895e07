"""`trace_moe_roofline` for a configuration that names one expert's width
otherwise: lib/costs_moe.least_seconds reads `intermediate_size`, which in
a `deepseek_v3` file is the DENSE layers' width; `expert_width` names the
key that holds an expert's (`moe_intermediate_size`)."""

from benchmarks.metrics.readers import trace_moe_roofline


def read(obs, params, ctx):
    cfg = ctx["config"]
    if params["expert_width"] not in cfg:
        return None
    at = dict(cfg, intermediate_size=cfg[params["expert_width"]])
    return trace_moe_roofline.read(obs, params, dict(ctx, config=at))
