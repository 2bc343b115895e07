"""Operations and bytes of multi-head latent attention ALONE — scores and
weighted sums, not the projections around them — from counters alone (the
yardstick's own functions: see lib/costs.py): what the algorithm needs,
whatever implements it (a program that absorbs the up-projection into the
query, or one that expands keys and values a head wide, is held to the
same count).

A program reports, summed over its layers, `pairs`: the (query, visible
key) pairs of ONE head, and `keys`: the keys visible to the program (a
decode step: the sum of its live rows' contexts, each slot its own; a
prefill chunk: its one context).  A pair of a head costs a score over the
head's key width (d_nope + d_rope) and a weighted sum over its value width
(d_v), two operations a product.  A visible key's latent row (kv_rank +
d_rope values) is read once a program and layer; beside it the program's
own query rows and output rows, a head wide, once each."""

from __future__ import annotations


def _sizes(cfg: dict):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["num_hidden_layers"])


def attend_flops(pairs: float, cfg: dict) -> float:
    heads, dn, dr, dv, _, _ = _sizes(cfg)
    return 2.0 * pairs * heads * (dn + dr + dv)


def attend_bytes(keys: float, rows: float, cfg: dict, itemsize: int = 2
                 ) -> float:
    """`rows`: the program's query rows (one layer's; every layer reads
    its own)."""
    heads, dn, dr, dv, rkv, layers = _sizes(cfg)
    return itemsize * (keys * (rkv + dr)
                       + rows * layers * heads * (dn + dr + dv))


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of the attention of one ring record's `program`:
    "step" (`mla_pairs`, `mla_keys`, its `active` rows) or "chunk"
    (`chunk_mla_pairs`, `chunk_mla_keys`, its `chunk_tokens` rows)."""
    if program == "step":
        pairs, keys, rows = (record["mla_pairs"], record["mla_keys"],
                             record["active"])
    else:
        pairs, keys, rows = (record["chunk_mla_pairs"],
                             record["chunk_mla_keys"], record["chunk_tokens"])
    return max(attend_flops(pairs, cfg) / peak["flops_per_s"],
               attend_bytes(keys, rows, cfg) / peak["bytes_per_s"])
