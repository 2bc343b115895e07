"""Operations and bytes of the grouped expert products of a share of a
routed SwiGLU layer, from counters alone (the yardstick's own function:
see lib/costs.py).

One program (a decode step or a prefill chunk) reports, summed over its
layers, `pairs` token-expert pairs on held experts and `touched` held
experts with at least one pair.  A pair costs three matrix products
(gate, up: D x F; down: F x D); a touched expert's three matrices are
read once."""

from __future__ import annotations


def expert_flops(pairs: float, d_model: int, d_expert: int) -> float:
    return 2.0 * 3.0 * pairs * d_model * d_expert


def expert_bytes(pairs: float, touched: float, d_model: int, d_expert: int,
                 itemsize: int = 2) -> float:
    """Weights of the touched experts + each pair's input row, its two
    hidden rows written and read again (gate, up in f32 as the program
    keeps them; their product in the weights' dtype) and its output row."""
    weights = 3.0 * touched * d_model * d_expert * itemsize
    rows = pairs * (2 * d_model * itemsize            # in, out
                    + 2 * d_expert * 4                # gate, up (f32)
                    + 2 * d_expert * itemsize)        # product: out, in
    return weights + rows


def least_seconds(pairs: float, touched: float, cfg: dict, peak: dict
                  ) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return max(expert_flops(pairs, d, f) / peak["flops_per_s"],
               expert_bytes(pairs, touched, d, f) / peak["bytes_per_s"])
