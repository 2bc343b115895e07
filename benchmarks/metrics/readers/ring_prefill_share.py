"""Engine phase ring: seconds in prefill over seconds in all phases
(admission incl. prefill, plus decode) of the window's iterations."""


def read(obs, params, ctx):
    ring = obs["serve"]["ring"]
    total = sum(r["swap_s"] + r["decode_s"] for r in ring)
    return 100.0 * sum(r["prefill_s"] for r in ring) / total
