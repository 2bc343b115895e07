"""Operations and bytes of the reads of Phi-4-mini-flash's ONE shared
full-attention cache in a decode step, from counters alone (the
yardstick's own functions: see lib/costs.py).

Layer L/2 + 1 writes a position's keys and values (num_key_value_heads x
head_dim each, bfloat16) and reads the cache; every cross-attention layer
above reads the same cache again: 1 + (cross layers) reads a step, and
because each of those layers' queries depends on the layer below, no
implementation can read it less often.  A step's live slots hold
`shared_kv_positions` positions of it together (the sum of their contexts,
the engine's own counter: not the slots' capacity, not `max_total`), so
the least a step moves is positions x (K + V bytes) x reads.  A read scores
every query head against its key (2 x head_dim a head and position) and
weighs a value twice as wide (differential attention: a pair's two value
heads side by side) — 2 x 2 head_dim: 6 head_dim operations a query head
and position."""

from __future__ import annotations


def reads(cfg: dict) -> int:
    """Layers that read the shared cache in a step: the full layer and
    every odd layer above it."""
    first = cfg["num_hidden_layers"] // 2 + 1
    return sum(1 for l in range(first, cfg["num_hidden_layers"])
               if l % cfg["mb_per_layer"])


def position_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One position's keys and values in the one layer that caches them."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * itemsize * cfg["num_key_value_heads"] * d


def step_bytes(positions: float, cfg: dict) -> float:
    return positions * position_bytes(cfg) * reads(cfg)


def step_flops(positions: float, cfg: dict) -> float:
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return positions * reads(cfg) * 6.0 * d * cfg["num_attention_heads"]


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of a step's reads of the shared cache (`program` is
    "step": a chunk reads it once, under another scope)."""
    if program != "step":
        raise ValueError("the shared cache's eight reads are a step's")
    n = record["shared_kv_positions"]
    return max(step_flops(n, cfg) / peak["flops_per_s"],
               step_bytes(n, cfg) / peak["bytes_per_s"])
