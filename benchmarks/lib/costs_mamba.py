"""Operations and bytes of Mamba-1's selective scan from counters alone
(the yardstick's own functions: see lib/costs.py) — what the recurrence
needs, whatever implements it.

A layer's state is d_inner x d_state float32.  A token's update of it is
the recurrence's own passes over every element — the decay's exponent and
exponential (2), the decayed state (1), the input's outer product and its
addition (2), the read-out's product and sum (2): 7 an element.  A decode
step reads and writes the state of every slot its kernel MOVES
(`mamba_live`: the live slots a layer; an empty slot's state is not
touched) in every Mamba layer, with each slot's rows in (u, the step size:
float32 a channel; B, C: float32 a state) and its output row out.  The conv
tails move under another scope (`kda_conv`) and are not counted here.  A
prefill chunk of `rows` tokens reads and writes one state a layer and
chunk, does the token's 7 an element for every row, and moves each row's
u, step size and output (4 B a channel each) and B, C (4 B a state)."""

from __future__ import annotations


def _sizes(cfg: dict):
    a = cfg["assumed_sizes"]
    half = cfg["num_hidden_layers"] // 2
    layers = sum(1 for l in range(half + 2) if l % cfg["mb_per_layer"] == 0)
    return a["mamba_expand"] * cfg["hidden_size"], a["mamba_d_state"], layers


def state_bytes(cfg: dict) -> float:
    """One sequence's state in one layer."""
    c, n, _ = _sizes(cfg)
    return 4.0 * c * n


def tail_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One sequence's conv tail in one layer: the last d_conv - 1 pre-conv
    rows."""
    c, _, _ = _sizes(cfg)
    return float(itemsize * (cfg["assumed_sizes"]["mamba_d_conv"] - 1) * c)


def _row_bytes(cfg: dict) -> float:
    c, n, _ = _sizes(cfg)
    return 4.0 * (3 * c + 2 * n)


def step_flops(live: float, cfg: dict) -> float:
    c, n, layers = _sizes(cfg)
    return 7.0 * live * layers * c * n


def step_bytes(live: float, cfg: dict) -> float:
    _, _, layers = _sizes(cfg)
    return live * layers * (2.0 * state_bytes(cfg) + _row_bytes(cfg))


def chunk_flops(rows: float, cfg: dict) -> float:
    c, n, layers = _sizes(cfg)
    return 7.0 * rows * layers * c * n


def chunk_bytes(rows: float, chunks: float, cfg: dict) -> float:
    _, _, layers = _sizes(cfg)
    return layers * (2.0 * chunks * state_bytes(cfg) + rows * _row_bytes(cfg))


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of the selective scan of one ring record's
    `program`: "step" (the `mamba_live` states a layer its kernel moved)
    or "chunk" (its `chunk_tokens` rows in `chunk_mamba_live` chunks)."""
    if program == "step":
        flops = step_flops(record["mamba_live"], cfg)
        nbytes = step_bytes(record["mamba_live"], cfg)
    else:
        rows, n = record["chunk_tokens"], record["chunk_mamba_live"]
        flops, nbytes = chunk_flops(rows, cfg), chunk_bytes(rows, n, cfg)
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
