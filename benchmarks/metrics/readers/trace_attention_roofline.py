"""Flash-attention kernels' share of their roofline in a train step: the
least time the chip could take for the calls seen in the trace (the larger
of operations/peak FLOP/s and bytes/peak bytes/s, from lib/costs.py at this
chip's shard of the batch) over the self time the trace shows for them.

The kernels carry no name of their own in the trace (`kernel_metadata={}`):
they are the `custom-call`s whose HLO text matches `match`
(`custom_call_target="tpu_custom_call"` on [B,H,S,64] operands).  A call
with `fwd_operands` operands (q, k, v) is a forward — remat runs it a second
time in the backward pass, and both runs are in numerator and denominator;
calls with more operands are the backward's kernels (`bwd_kernels` of them
per attention call: dq, and dk+dv), which together need 2.5x the forward's
operations however they are split."""

from benchmarks.lib import costs, peaks


def _operands(text: str) -> int:
    head = text.split("custom-call(", 1)[1].split("), custom_call_target", 1)
    return head[0].count("%")


def read(obs, params, ctx):
    import re

    red, cfg, tr = obs["trace"], ctx["config"], obs["train"]
    rx = re.compile(params["match"])
    fs = fn = bs = bn = 0.0
    for o in red["ops"].values():
        text = o.get("text", "")
        if o.get("op") != "custom-call" or not rx.search(text):
            continue
        if _operands(text) == params["fwd_operands"]:
            fs, fn = fs + o["s"], fn + o["n"]
        else:
            bs, bn = bs + o["s"], bn + o["n"]
    if not fn and not bn:
        return None
    pk = peaks.peak(ctx["device"]["kind"])
    shape = (tr["batch_per_chip"], cfg["n_head"], tr["seq"], cfg["head_dim"])

    def least(backward):
        return max(costs.flash_attention_flops(*shape, causal=True,
                                               backward=backward)
                   / pk["flops_per_s"],
                   costs.flash_attention_bytes(*shape, backward=backward)
                   / pk["bytes_per_s"])

    need = fn * least(False) + (bn / params["bwd_kernels"]) * least(True)
    return 100.0 * need / (fs + bs)
