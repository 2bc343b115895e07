"""Operations and bytes of learned sparse latent attention — the indexer's
scoring pass, the selected latent attention in both forms, and latent
attention under a window — from the configuration and the counters alone
(the yardstick's own functions: see lib/costs.py): what the algorithm
needs, whatever implements it (a program that scores every key and masks,
or one that gathers the selected rows, is held to the same count).

A program reports, summed over the layers of the kind (models/dots3.py's
STEP_STATS; a chunk's under `chunk_<name>`), as (query row, key) pairs of
ONE head:

  `dsa_keys_visible`   pairs a full layer's rows may see (causal): what
                       the INDEXER has to score, index_n_heads heads of
                       index_head_dim a pair, two operations a product
                       (the heads' weighted sum is one more a head);
  `dsa_keys_selected`  pairs the selection keeps: what the ATTENTION has
                       to score — a score over qk_nope + qk_rope and a
                       weighted sum over v_head_dim a head;
  `dsa_ctx`            keys visible to the program (a step: the sum of its
                       live rows' contexts; a chunk: its one context): an
                       indexer key row (index_head_dim values) is read
                       once a program and layer; a latent row (kv_lora_rank
                       + qk_rope values) once for each key that some row
                       selected — at most the selected pairs, at most the
                       visible keys;
  `swa_pairs`, `swa_keys`  the same two counts of a sliding layer under
                       its window.

Beside the keys, a program's own query rows and output rows, a head wide,
once each a layer."""

from __future__ import annotations

ITEM = 2                # bytes of a cached value and of a row (bfloat16)


def _layers(cfg: dict, kind: str) -> int:
    return sum(1 for k in cfg["layer_types"] if k == kind + "_attention")


def _heads(cfg: dict, p: str):
    """(heads, key width, value width, latent row width) of the full ("")
    or sliding ("swa_") geometry."""
    dn, dr = cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"]
    return (cfg[p + "num_attention_heads"], dn + dr, cfg[p + "v_head_dim"],
            cfg[p + "kv_lora_rank"] + dr)


def index_flops(pairs: float, cfg: dict) -> float:
    return pairs * cfg["index_n_heads"] * (2.0 * cfg["index_head_dim"] + 2.0)


def index_bytes(keys: float, rows: float, cfg: dict) -> float:
    """`keys`: summed over the full layers; `rows`: one layer's."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return ITEM * (keys * di
                   + rows * _layers(cfg, "full") * hi * (di + 1)) + 4.0 * (
                       rows * _layers(cfg, "full"))       # thresholds out


def attend_flops(pairs: float, cfg: dict, p: str = "") -> float:
    heads, dk, dv, _ = _heads(cfg, p)
    return 2.0 * pairs * heads * (dk + dv)


def attend_bytes(keys: float, rows: float, layers: int, cfg: dict,
                 p: str = "") -> float:
    heads, dk, dv, row = _heads(cfg, p)
    return ITEM * (keys * row + rows * layers * heads * (dk + dv))


def counts(program: str, record: dict):
    """(prefix of the record's counters, the program's rows)."""
    if program.endswith("step"):
        return "", record["active"]
    return "chunk_", record["chunk_tokens"]


def least_seconds(program: str, record: dict, cfg: dict, peak: dict
                  ) -> float:
    """The least time of one part of one ring record's program.  `program`
    is `<part>_<step|chunk>`: `index` (the indexer's scoring pass), `mla`
    (the full layers' selected attention), `swa` (the sliding layers'
    windowed attention)."""
    part = program.split("_")[0]
    pre, rows = counts(program, record)
    get = lambda name: record[pre + name]
    if part == "index":
        flops = index_flops(get("dsa_keys_visible"), cfg)
        nbytes = index_bytes(get("dsa_ctx"), rows, cfg)
    elif part == "mla":
        sel = get("dsa_keys_selected")
        flops = attend_flops(sel, cfg)
        nbytes = attend_bytes(min(sel, get("dsa_ctx")), rows,
                              _layers(cfg, "full"), cfg)
    elif part == "swa":
        flops = attend_flops(get("swa_pairs"), cfg, "swa_")
        nbytes = attend_bytes(get("swa_keys"), rows,
                              _layers(cfg, "sliding"), cfg, "swa_")
    else:
        raise ValueError(f"no part {part!r} (index, mla, swa)")
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
