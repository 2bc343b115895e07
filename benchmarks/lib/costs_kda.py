"""Operations and bytes of Kimi Delta Attention from counters alone (the
yardstick's own functions: see lib/costs.py) — what the recurrence needs,
whatever implements it.

A head's state is head_dim x head_dim float32.  A token's update of it is
the recurrence's own three passes — the decay (1 operation an element),
the read along k and the rank-one correction (2 + 2), the read-out along q
(2): 7 an element.  A decode step reads and writes the state of every slot
its kernel MOVES (`kda_live`: the live slots a layer; an empty slot's
state is not touched) in every KDA layer, with each slot's five rows (q,
k, b k, v, a: float32) in and its output row out.  The conv tails move
under another scope (`kda_conv`) and are not counted here.  A prefill chunk
of `rows` tokens reads and writes one state a layer and chunk, does the
token's 7 an element for every row (the chunked form does more: this is
the least), and moves each row's q~ k~ v~ (2 B), log a and output (4 B)."""

from __future__ import annotations


def _sizes(cfg: dict):
    layers = sum(1 for l in range(cfg["num_hidden_layers"])
                 if (l + 1) % cfg["layer_group_size"])
    return cfg["head_dim"], cfg["num_attention_heads"], layers


def state_bytes(cfg: dict) -> float:
    """One sequence's state matrices in one layer."""
    d, heads, _ = _sizes(cfg)
    return 4.0 * heads * d * d


def tail_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One sequence's conv tail in one layer: the last
    short_conv_kernel_size - 1 pre-conv rows of q~ | k~ | v~."""
    d, heads, _ = _sizes(cfg)
    return float(itemsize * (cfg["short_conv_kernel_size"] - 1)
                 * 3 * heads * d)


def step_flops(live: float, cfg: dict) -> float:
    d, heads, layers = _sizes(cfg)
    return 7.0 * live * layers * heads * d * d


def step_bytes(live: float, cfg: dict) -> float:
    d, heads, layers = _sizes(cfg)
    return live * layers * (2.0 * state_bytes(cfg) + 6 * 4.0 * heads * d)


def chunk_flops(rows: float, cfg: dict) -> float:
    d, heads, layers = _sizes(cfg)
    return 7.0 * rows * layers * heads * d * d


def chunk_bytes(rows: float, chunks: float, cfg: dict) -> float:
    d, heads, layers = _sizes(cfg)
    moved = rows * heads * d * (3 * 2 + 4 + 4)
    return layers * (2.0 * chunks * state_bytes(cfg) + moved)


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of the delta rule of one ring record's `program`:
    "step" (the `kda_live` states a layer its kernel moved) or "chunk"
    (its `chunk_tokens` rows in `chunk_kda_live` chunks)."""
    if program == "step":
        flops = step_flops(record["kda_live"], cfg)
        nbytes = step_bytes(record["kda_live"], cfg)
    else:
        rows, n = record["chunk_tokens"], record["chunk_kda_live"]
        flops, nbytes = chunk_flops(rows, cfg), chunk_bytes(rows, n, cfg)
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
