"""Operations and bytes of Mamba-2's SSD mixer from counters alone (the
yardstick's own functions: see lib/costs.py) — what the equations need,
whatever implements them.

A layer's state is heads x channels x states float32 (32 x 128 x 256: 4 MB
a sequence).  A token's update of it is the recurrence's own passes over
every element — the decayed state (1), the input's outer product and its
addition (2), the read-out's product and sum (2): 5 an element (the decay
is one exponential a HEAD and is not counted).  A decode step reads and
writes the state of every slot its kernel MOVES (`ssd_live`: the live
slots a layer; an empty slot's state is not touched) in every layer, with
each slot's rows in (x and y: float32 a channel; B, C: float32 a state and
group; dt a head).  The conv tails move under another scope (`ssd_conv`)
and are not counted here.

A prefill chunk of `rows` tokens takes the chunked (dual) form's products,
the causal half of what lies inside a 128-row sub-chunk only: C B^T once a
GROUP (Q/2 x N a row), its masked product with dt x a head (Q/2 x P a
row), the carried state's read-out (P x N a row and head) and the state's
update (P x N a row and head), two operations a multiply-add; it reads and
writes one state a layer and chunk and moves each row's x and y (4 B a
channel each), B, C (4 B a state and group) and dt."""

from __future__ import annotations

SUB_CHUNK = 128


def _sizes(cfg: dict):
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["num_hidden_layers"])


def state_bytes(cfg: dict) -> float:
    """One sequence's state in one layer."""
    h, p, n, _, _ = _sizes(cfg)
    return 4.0 * h * p * n


def _row_bytes(cfg: dict) -> float:
    h, p, n, g, _ = _sizes(cfg)
    return 4.0 * (2 * h * p + 2 * g * n + h)


def step_flops(live: float, cfg: dict) -> float:
    h, p, n, _, layers = _sizes(cfg)
    return 5.0 * live * layers * h * p * n


def step_bytes(live: float, cfg: dict) -> float:
    layers = _sizes(cfg)[-1]
    return live * layers * (2.0 * state_bytes(cfg) + _row_bytes(cfg))


def chunk_flops(rows: float, cfg: dict) -> float:
    h, p, n, g, layers = _sizes(cfg)
    q = SUB_CHUNK / 2.0
    return 2.0 * rows * layers * (g * q * n + h * q * p + 2 * h * p * n)


def chunk_bytes(rows: float, chunks: float, cfg: dict) -> float:
    layers = _sizes(cfg)[-1]
    return layers * (2.0 * chunks * state_bytes(cfg) + rows * _row_bytes(cfg))


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of the SSD mixer of one ring record's `program`:
    "step" (the `ssd_live` states a layer its kernel moved) or "chunk"
    (its `chunk_tokens` rows in `chunk_ssd_live` chunks)."""
    if program == "step":
        flops = step_flops(record["ssd_live"], cfg)
        nbytes = step_bytes(record["ssd_live"], cfg)
    else:
        rows, n = record["chunk_tokens"], record["chunk_ssd_live"]
        flops, nbytes = chunk_flops(rows, cfg), chunk_bytes(rows, n, cfg)
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
