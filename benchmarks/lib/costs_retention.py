"""Operations and bytes of power retention (degree 2) from counters alone
(the yardstick's own functions: see lib/costs.py) — what the algorithm
needs, whatever implements it.

A K/V head's state is n_feat = dh (dh + 1) / 2 features by dh + 1 columns
(the values and the normaliser) in float32.  A decode step reads and
writes the state of every LIVE slot in every layer, updates it (a scale,
a product and a sum an element) and reads it out for the head's G query
heads.  A prefill chunk of `rows` tokens reads and writes one state a
layer; its queries meet the carried state (rows x n_feat x (dh + 1) a
query head), its keys extend it, and inside the chunk the quadratic form
is causal (half of rows^2)."""

from __future__ import annotations


def _sizes(cfg: dict):
    dh, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    return (dh, hkv, cfg["num_attention_heads"] // hkv,
            dh * (dh + 1) // 2, cfg["num_hidden_layers"])


def state_bytes(cfg: dict) -> float:
    """One sequence's state in one layer."""
    dh, hkv, _, n_feat, _ = _sizes(cfg)
    return 4.0 * hkv * n_feat * (dh + 1)


def step_flops(live: float, cfg: dict) -> float:
    dh, hkv, g, n_feat, layers = _sizes(cfg)
    return live * layers * hkv * n_feat * (dh + 1) * (3.0 + 2.0 * g)


def step_bytes(live: float, cfg: dict) -> float:
    return 2.0 * live * cfg["num_hidden_layers"] * state_bytes(cfg)


def chunk_flops(rows: float, chunks: float, cfg: dict) -> float:
    """`rows` tokens in `chunks` chunks (taken as equally long: the least
    the causal part can be)."""
    dh, hkv, g, n_feat, layers = _sizes(cfg)
    carried = 2.0 * rows * n_feat * (dh + 1) * (g + 1)
    inside = g * (rows * rows / max(chunks, 1.0)) * (2 * dh + 1)
    return layers * hkv * (carried + inside)


def chunk_bytes(rows: float, chunks: float, cfg: dict,
                itemsize: int = 2) -> float:
    dh, hkv, g, _, layers = _sizes(cfg)
    qkv = rows * layers * hkv * dh * (2 * g + 2) * itemsize   # q, o, k, v
    return 2.0 * chunks * layers * state_bytes(cfg) + qkv


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of the retention of one ring record's `program`:
    "step" (its `active` slots) or "chunk" (its `chunk_tokens` rows in
    `chunk_ret_states` chunks)."""
    if program == "step":
        flops = step_flops(record["active"], cfg)
        nbytes = step_bytes(record["active"], cfg)
    else:
        rows, n = record["chunk_tokens"], record["chunk_ret_states"]
        flops, nbytes = chunk_flops(rows, n, cfg), chunk_bytes(rows, n, cfg)
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
