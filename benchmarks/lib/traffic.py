"""The one general traffic generator: a traffic file's parameters + a seed
-> the inputs of a run.

Steadiness rule: every seed gets the same set of sizes and arrivals, in
another order.  A serving mix's lengths and inter-arrival gaps are drawn
once from the traffic file's own `population_seed` and form one cycle, as
long as the window; `--seed` chooses at which of the cycle's idle
stretches the window opens (and draws the token ids and the weights).  So
every run offers the same requests with the same neighbours, whole.  Other
draws are other traffic files, that is other cells.  Training rows are
drawn from `--seed`: every row is full, so the work is the same whatever
the tokens.
"""

from __future__ import annotations

import math
from typing import Dict, Iterator, List

import numpy as np


def _rng(*parts: int) -> np.random.Generator:
    # seeds up to a little over 2**31 (and beyond): SeedSequence takes any
    # non-negative integers
    return np.random.default_rng([int(p) & 0xFFFFFFFFFFFFFFFF for p in parts])


def draw_lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths from {"dist": "lognormal", "median", "sigma",
    "min", "max"} or {"dist": "fixed", "value"} or {"dist": "uniform",
    "min", "max"}.  A lognormal spec may carry "multiple_of": each drawn
    length is then rounded to the nearest multiple of it that lies inside
    the clip (the same draws from the generator, so a spec without the key
    gives what it always gave)."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        if "multiple_of" in spec:
            m = int(spec["multiple_of"])
            lo, hi = -(-int(spec["min"]) // m) * m, int(spec["max"]) // m * m
            return np.clip(np.rint(x / m) * m, lo, hi).astype(np.int64)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_gaps(arrivals: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n inter-arrival gaps in seconds with mean 1/rate_per_s:
    "poisson" (exponential gaps) or "gamma" with coefficient of variation
    `cv` (cv 1 is Poisson; larger is burstier)."""
    mean = 1.0 / float(arrivals["rate_per_s"])
    proc = arrivals["process"]
    if proc == "poisson":
        return rng.exponential(mean, n)
    if proc == "gamma":
        k = 1.0 / float(arrivals["cv"]) ** 2
        return rng.gamma(k, mean / k, n)
    raise ValueError(f"unknown arrival process {proc!r}")


def serve_population(traffic: Dict, n: int) -> List[Dict]:
    """The mix's n requests as lengths only, in the population's own
    order: [{"plen", "max_new_tokens"}] — the same for every seed."""
    pop = _rng(traffic["population_seed"], 1)
    plens = draw_lengths(traffic["prompt_len"], n, pop)
    olens = draw_lengths(traffic["output_len"], n, pop)
    return [{"plen": int(p), "max_new_tokens": int(o)}
            for p, o in zip(plens, olens)]


def request_body(traffic: Dict, seed: int, index: int, shape: Dict,
                 vocab: int) -> Dict:
    """Request `index` of the cycle with its token ids drawn from `seed`."""
    hi = min(vocab, int(traffic.get("token_id_max", vocab)))
    toks = _rng(seed, 6, index).integers(0, hi, shape["plen"]).tolist()
    return {"tokens": toks, "max_new_tokens": shape["max_new_tokens"]}


def cycle_entry(traffic: Dict, seed: int, gaps: np.ndarray) -> int:
    """The arrival the window of `seed` opens with: one of those that
    follow an idle stretch of at least `entry_after_idle_s` (the longest
    stretch of the cycle where none is that long).  After such a stretch
    the cycle has left nothing running, so an idle system is the state the
    window should open in and its edges cut no request: opened elsewhere,
    the same cycle read `ttft_p75_ms` 2 to 23% lower and flipped
    `itl_p99_ms` between two values (PERF.md section 6)."""
    n = len(gaps)
    idle = float(traffic["entry_after_idle_s"])
    quiet = [k for k in range(n) if gaps[k - 1] >= idle] \
        or [(int(np.argmax(gaps)) + 1) % n]
    return quiet[int(_rng(seed, 4).integers(0, len(quiet)))]


def open_schedule(traffic: Dict, seed: int, seconds: float, vocab: int
                  ) -> List[Dict]:
    """The open loop's requests with their due times relative to the
    window's start: [{"due", "tokens", "max_new_tokens"}].

    The window is exactly ONE CYCLE of the population: n = rate x seconds
    arrivals whose gaps (drawn once, from the population's seed) are
    scaled to sum to `seconds`, each arrival tied to its request's sizes.
    The seed picks the arrival the window opens with (`cycle_entry`), so
    the cycle's idle stretch before it is the window's last gap."""
    n = max(1, int(round(float(traffic["arrivals"]["rate_per_s"]) * seconds)))
    gaps = draw_gaps(traffic["arrivals"], n,
                     _rng(traffic["population_seed"], 3))
    gaps = gaps * (seconds / gaps.sum())
    pop = serve_population(traffic, n)
    k0 = cycle_entry(traffic, seed, gaps)
    out, t = [], 0.0
    for i in range(n):                      # gap k follows arrival k
        k = (k0 + i) % n
        out.append({"due": t, **request_body(traffic, seed, k, pop[k], vocab)})
        t += float(gaps[k])
    return out


def packed_batches(traffic: Dict, seed: int, batch: int, vocab: int
                   ) -> Iterator[Dict[str, np.ndarray]]:
    """Endless host batches for training: documents with lengths from
    `doc_len`, token ids from `seed`, each ended by `eos_id`, packed end to
    end into full rows of seq_len+1 tokens ({"inputs","targets"} shifted
    by one)."""
    S = int(traffic["seq_len"])
    eos = int(traffic.get("eos_id", 0))
    rng = _rng(seed, 5)
    hi = min(vocab, int(traffic.get("token_id_max", vocab)))
    need = batch * (S + 1)
    carry = np.zeros(0, np.int32)
    while True:
        parts, have = [carry], carry.size
        while have < need:
            lens = draw_lengths(traffic["doc_len"], 64, rng)
            for ln in lens:
                doc = rng.integers(1, hi, int(ln), dtype=np.int32)
                doc[-1] = eos
                parts.append(doc)
                have += doc.size
        flat = np.concatenate(parts)
        rows, carry = flat[:need].reshape(batch, S + 1), flat[need:]
        yield {"inputs": np.ascontiguousarray(rows[:, :-1]),
               "targets": np.ascontiguousarray(rows[:, 1:])}
