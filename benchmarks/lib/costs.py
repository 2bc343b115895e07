"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick's own functions: a later PR that claims a kernel
got closer to its roofline cannot change what the roofline is.
`cfg` is a configuration file's dict (benchmarks/configs/*.json).
"""

from __future__ import annotations


def n_params(cfg: dict) -> int:
    """GPT-2 parameters: tied embedding, learned positions, per layer
    4 attention matrices (+4 biases), 2 MLP matrices (+2 biases), 2
    LayerNorms; final LayerNorm."""
    L, d, F = cfg["n_layer"], cfg["n_embd"], cfg["n_inner"]
    hd = cfg["n_head"] * cfg["head_dim"]
    per_layer = (3 * d * hd + hd * d) + 2 * d * F + 2 * 2 * d + F + d
    if cfg.get("attn_bias", True):
        per_layer += 3 * hd + d
    return (cfg["vocab_size"] * d + cfg["n_positions"] * d
            + L * per_layer + 2 * d)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6N + 12*L*d*S (bench.py:365-371): forward and backward matmuls of
    every parameter (the tied embedding counted once, as the unembedding)
    plus attention's QK^T and PV at full (not causal-halved) length.
    Recomputation under remat is not counted."""
    n = n_params(cfg) - cfg["n_positions"] * cfg["n_embd"]
    return 6.0 * n + 12.0 * cfg["n_layer"] * cfg["n_embd"] * seq


def flash_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                          causal: bool = True, backward: bool = False
                          ) -> float:
    """Forward: QK^T and PV, 2*2*B*H*S*S*dh, halved for a causal mask.
    Backward (FlashAttention-2): recomputed QK^T plus dP, dV, dQ, dK —
    five matmuls against the forward's two, 2.5x."""
    f = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        f *= 0.5
    return f * (2.5 if backward else 1.0)


def flash_attention_bytes(batch: int, heads: int, seq: int, head_dim: int,
                          itemsize: int = 2, backward: bool = False
                          ) -> float:
    """Least HBM traffic: forward reads Q,K,V and writes O (+ f32
    logsumexp); backward reads Q,K,V,O,dO,lse and writes dQ,dK,dV."""
    t = batch * heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    return (8 * t + 2 * lse) if backward else (4 * t + lse)
