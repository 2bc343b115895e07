"""Holding served tokens of an `lfm2_moe` replica to its plain reference
(inside the replica, after the window): check_falcon_h1.py's scheme — the
reference DRAWS ITS OWN WEIGHTS from the seed, a leaf at a time when a
layer's turn comes, and is driven piece by piece so that it fits beside
the engine — for a model whose layers are of four kinds (a conv or an
attention mixer, a dense or an expert feed-forward), whose sequence holds
pages in the attention layers and one entry of tails over the conv layers,
and whose head is its embedding."""

from __future__ import annotations

import concurrent.futures
import functools
import time
from typing import Dict, List

from .check_ling3 import join_replays  # noqa: F401  (the replica's)

DIRT = 1.0e3         # what the replay's entry and pages hold before it
HEAD_ROWS = 256      # rows of the head a loop turn
V_PARTS = 4          # ... a quarter of the vocabulary at a time
# the leaves the recipe does not draw, in the order the programs take them
FIXED = ("norm", "ffn_norm", "q_norm", "k_norm")


def replay_logits(eng, prompt: List[int], n: int, keep: int = None):
    """The logits rows the engine's OWN programs form for the first `n`
    tokens after `prompt` — `serve.prefill:<T>` chunk by chunk as the
    engine cuts them, `serve.setrow`, then `serve.step` with slot 0 live
    on pages 1.. of the full pool and entry 1 of the state pool — greedy,
    on an entry and pages DIRTIED first.  Run while the engine is idle.
    Returns (rows [keep, V] float32 on the device, the n tokens, what the
    replay left behind: {"tails": [2, conv layers, 2, D] its entry's tails
    after the prompt's last chunk and after the n steps, "keys": an
    attention layer's key rows of the prompt's positions [plen, Hkv * dh]
    as its pages hold them, a layer each})."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_device_state()
    plen = len(prompt)
    need = -(-(plen + n) // eng.page_size)

    # entry 1's tails and pages 0..need of every attention layer hold DIRT,
    # as if a careless holder had left them.  A sound program reads none of
    # it: a first chunk starts from zeros, a row masks what lies past it,
    # an empty slot's null page is no one's key.  Finite, so that a masked
    # 0 x DIRT stays 0.
    @functools.partial(jax.jit, donate_argnums=0)
    def dirtied(cache, last_page):
        def pages(a):
            mine = (jnp.arange(a.shape[0]) <= last_page)[:, None, None]
            return jnp.where(mine, jnp.asarray(DIRT, a.dtype), a)

        return {"k": [pages(a) for a in cache["k"]],
                "v": [pages(a) for a in cache["v"]],
                "tail": cache["tail"].at[:, 1].set(DIRT)}

    eng._cache = dirtied(eng._cache, np.int32(need))
    tabs = {}
    for kind, width in eng._widths.items():
        tabs[kind] = np.zeros(width, np.int32)
        if kind in eng._state_kinds:
            tabs[kind][0] = 1
        else:
            tabs[kind][:need] = np.arange(1, need + 1)
    start = 0
    while start < plen:
        m = min(eng.prefill_chunk or plen, plen - start)
        T = -(-m // eng.prefill_bucket) * eng.prefill_bucket
        chunk = np.zeros(T, np.int32)
        chunk[:m] = prompt[start:start + m]
        row, eng._cache, _ = eng._fn(("prefill", T))(
            eng._params, eng._cache, chunk,
            {k: t.copy() for k, t in tabs.items()}, np.int32(start),
            np.int32(m - 1))
        start += m
    entry = lambda: eng._cache["tail"][:, 1].reshape(
        eng._cache["tail"].shape[0], 2, -1)
    after_prompt = entry()
    eng._logits = eng._fn("setrow")(eng._logits, row, np.int32(0))
    B = eng.max_slots
    ptabs = {k: np.zeros((B, t.shape[0]), np.int32) for k, t in tabs.items()}
    for k, t in tabs.items():
        ptabs[k][0] = t
    zeros = lambda dt, *shape: np.zeros((B,) + shape, dt)
    rows, toks = [], []
    for i in range(n):
        if keep is None or i >= n - keep:
            rows.append(eng._logits[0])
        pos = zeros(np.int32)       # a new array a step: the call may
        pos[0] = plen + i           # still be reading the last one
        tok, eng._logits, eng._cache, _ = eng._fn("step")(
            eng._params, eng._cache, eng._logits, zeros(np.uint32, 2),
            zeros(np.float32), zeros(np.int32), ptabs, pos)
        toks.append(tok[0])
    pages = -(-plen // eng.page_size)
    left = {"tails": jnp.stack([after_prompt, entry()]),
            "keys": [a[1:pages + 1].reshape(pages * eng.page_size, -1)[:plen]
                     .astype(jnp.float32) for a in eng._cache["k"]]}
    return (jnp.stack(rows), [int(t) for t in np.asarray(jnp.stack(toks))],
            left)


def _programs(sz: Dict, spec: Dict, n_logits: int, keep: int):
    """name -> (function, operand shapes, jit options) of the reference's
    programs: a slice of the embedding's rows in, a layer's mixer (of
    either kind), its feed-forward (of either kind), a slice of the
    embedding's rows out."""
    import jax
    import jax.numpy as jnp

    from . import lfm2_moe_plain as ref
    from .deepseek_v3_plain import _dtype

    rows, S = int(spec["rows"]), int(spec["max_context"])
    D, V = sz["d_model"], sz["vocab"]
    head_rows = min(HEAD_ROWS, n_logits)
    assert S % rows == 0 and n_logits % head_rows == 0 and V % V_PARTS == 0
    f32, i32, pd = jnp.float32, jnp.int32, _dtype(sz["param_dtype"])
    sh = jax.ShapeDtypeStruct
    x_, n_, two_ = sh((S, D), f32), sh((), i32), sh((2,), i32)
    specs = ref.leaf_specs(sz)
    leaf = lambda n: (sh((sz["n_experts"],), f32) if n == "router_bias"
                      else sh(specs[n][0], f32 if n == "router" else pd))
    shapes = jax.eval_shape(lambda: ref.fixed_leaves(sz, {}))
    fixed_ = tuple(sh(shapes[k].shape, f32) for k in FIXED)

    def lp_of(names, w, fx):
        return {**dict(zip(names, w)), **dict(zip(FIXED, fx))}

    def conv(x, stops, fx, *w):
        return ref.conv_layer(x, lp_of(ref.MIXER_LEAVES["conv"], w, fx), sz,
                              stops)

    def attn(x, blocks, fx, *w):
        return ref.attention_layer(
            x, lp_of(ref.MIXER_LEAVES["full_attention"], w, fx), sz, rows,
            blocks, keys=True)

    def ffn(dense):
        return lambda x, blocks, fx, *w: ref.ffn_layer(
            x, lp_of(ref.FFN_LEAVES[dense], w, fx), sz, dense, rows, blocks)

    size = V // V_PARTS

    def embed(x, part, i, toks):
        """x [S, D] + the rows of slice i of the table (`part` [V /
        V_PARTS, D]) for the tokens that lie in it."""
        at = toks - i * size
        mine = (at >= 0) & (at < size)
        got = part[jnp.clip(at, 0, size - 1)].astype(f32)
        return x + jnp.where(mine[:, None], got, 0.0)

    def head(x, nxt, part, i):
        """x [n_logits, D] against slice i of the embedding's rows (`part`
        [V / V_PARTS, D]) -> (each row's two best logits of the slice,
        token `nxt`'s own logit where it lies in the slice and 0
        elsewhere, the first `keep` rows' logits of the slice)."""
        ones = jnp.ones(D, f32)

        def block(xs):
            xb, nb = xs
            lg = ref.readout(xb, ones, part, sz)
            at = nb - i * size
            mine = jnp.where(
                (at >= 0) & (at < size), jnp.take_along_axis(
                    lg, jnp.clip(at, 0, size - 1)[:, None], 1)[:, 0], 0.0)
            return jax.lax.top_k(lg, 2)[0], mine

        cut = lambda a: a.reshape((n_logits // head_rows, head_rows)
                                  + a.shape[1:])
        best, mine = jax.lax.map(block, (cut(x), cut(nxt)))
        return (best.reshape(n_logits, 2), mine.reshape(-1),
                ref.readout(x[:keep], ones, part, sz))

    don = dict(donate_argnums=0)
    mix = ref.MIXER_LEAVES
    return {
        "embed": (embed, (x_, sh((size, D), pd), n_, sh((S,), i32)), don),
        "conv": (conv, (x_, two_, fixed_)
                 + tuple(leaf(n) for n in mix["conv"]), don),
        "attn": (attn, (x_, n_, fixed_)
                 + tuple(leaf(n) for n in mix["full_attention"]), don),
        "dense": (ffn(True), (x_, n_, fixed_)
                  + tuple(leaf(n) for n in ref.FFN_LEAVES[True]), don),
        "moe": (ffn(False), (x_, n_, fixed_)
                + tuple(leaf(n) for n in ref.FFN_LEAVES[False]), don),
        "head": (head, (sh((n_logits, D), f32), sh((n_logits,), i32),
                        sh((size, D), pd), n_), {}),
    }


def build_programs(sz: Dict, spec: Dict, n_logits: int, weights: Dict = None,
                   seed: int = None):
    """The reference's programs, traced and compiled side by side; nothing
    of them runs on the device.  -> ({name: compiled}, the seconds it
    took).  The replica's loader starts this on a thread during set-up.
    With `seed`, the programs that DRAW the reference's leaves are made
    too, by drawing one leaf of each shape once and dropping it."""
    import jax

    from . import lfm2_moe_plain as ref

    programs = _programs(sz, spec, n_logits, int(spec["replay_keep"]))
    if not any(ref.is_dense(sz, l) for l in range(sz["n_layers"])):
        del programs["dense"]
    t_first = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(programs)) as pool:
        built = {name: pool.submit(
            lambda f, a, kw: jax.jit(f, **kw).lower(*a).compile(), *p)
            for name, p in programs.items()}
        run = {name: f.result() for name, f in built.items()}
    if seed is not None:
        l_moe = sz["n_dense"]
        for kind in set(sz["layer_types"]):
            for name in ref.MIXER_LEAVES[kind]:
                jax.block_until_ready(ref.draw_leaf(seed, sz, weights or {},
                                                    0, name))
        for l in {0, l_moe} & set(range(sz["n_layers"])):
            for name in ref.FFN_LEAVES[ref.is_dense(sz, l)]:
                jax.block_until_ready(ref.draw_leaf(seed, sz, weights or {},
                                                    l, name))
        jax.block_until_ready(ref.draw_rows(seed, sz, weights or {}, 0,
                                            V_PARTS))
    return run, time.time() - t_first


def served_gaps(seed: int, sz: Dict, weights: Dict, sample: List[Dict],
                spec: Dict, n_logits: int, replays=(), built=None
                ) -> List[Dict]:
    """check_falcon_h1.served_gaps over `lfm2_moe_plain`'s pieces: for each
    {"rid", "tokens" (prompt), "served"} one teacher-forced reference pass
    (float32, highest precision: the convolution three shifted sums over
    the whole sequence, attention explicit softmaxes over every position,
    the experts a loop over all held with a dense mask, EVERY layer on
    every position) over prompt + served tokens, padded to
    `spec["max_context"]` rows so that every program has ONE shape; per
    request the largest distance of a served token's reference logit below
    its position's maximum, how many served tokens are the reference's
    argmax, and the median lead of the reference's best logit.  Attention
    and the feed-forward work for a sequence's own `spec["rows"]`-row
    blocks only.  Each of `replays` = (rid, first, rows [m, V], left:
    `replay_logits`' third item) adds to its entry, every one against the
    reference's own pass over the same tokens: `logit_rel_rms` /
    `logit_max_abs` of the program's logits rows (m = `replay_keep` = the
    rows the head program keeps, so `first` is 0); `tail_rel_rms`: the
    FIRST conv layer's tail (its input is the embedding's own rows: no
    router upstream) after the prompt's last chunk and after the m steps,
    the larger of the two (`tail_rel_rms_deepest`: the largest over all
    conv layers, which an expert flipped upstream moves: printed, not
    held); `first_keys_max`: the FIRST attention layer's key rows of the
    prompt's positions — no expert layer stands before it — per position
    |program - reference| / |reference|, the LARGEST (one position wrong
    is seen: a chunk's first rows after a dropped tail, a sequence's first
    after a stale one); `second_keys_q25` / `_median`: the same of the
    SECOND attention layer (of a model with one: the first), behind four
    expert layers, the 25th percentile
    over positions — a position no expert flipped for stands a bfloat16
    rounding off, so the low quantile reads what moves EVERY position (a
    weight, a selection, a query's position) and not the flips.

    The layers are the OUTER loop: a layer's leaves are drawn once, every
    sampled sequence goes through it, and they are dropped before the next
    layer's turn (in float32 the cut's weights are 18e9 B: they never
    stand together)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import lfm2_moe_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    V, L = sz["vocab"], sz["n_layers"]
    leaf = lambda l, name: ref.draw_leaf(seed, sz, weights, l, name)
    run, t_first = built or build_programs(sz, spec, n_logits)
    drawn_not = ref.fixed_leaves(sz, weights)
    fixed = tuple(drawn_not[k] for k in FIXED)
    at = np.int32

    seqs = []
    for s in sample:
        seq = s["tokens"] + s["served"]
        if len(seq) > S:
            raise ValueError(f"a context of {len(seq)} tokens is past the "
                             f"reference's {S}")
        toks = np.random.default_rng(len(seq)).integers(
            0, V, S).astype(np.int32)
        toks[:len(seq)] = seq
        pos = np.minimum(len(s["tokens"]) - 1 + np.arange(n_logits), S - 1)
        stop = [len(s["tokens"]) + first + int(got.shape[0])
                for rid, first, got, _ in replays if rid == s["rid"]]
        seqs.append({"toks": toks, "pos": pos, "n": len(s["served"]),
                     "stops": np.asarray([len(s["tokens"]) if stop else 0,
                                          stop[0] if stop else 0], np.int32),
                     "tails": [], "keys": [],
                     "blocks": -(-len(seq) // rows), "seconds": 0.0})

    def through(piece):
        """Every sequence's x through one piece, its seconds its own."""
        for q in seqs:
            t0 = time.time()
            q["x"] = jax.block_until_ready(piece(q))
            q["seconds"] += time.time() - t0

    for q in seqs:
        q["x"] = jnp.zeros((S, sz["d_model"]), jnp.float32)
    for i in range(V_PARTS):
        part = ref.draw_rows(seed, sz, weights, i, V_PARTS)
        through(lambda q: run["embed"](q["x"], part, at(i),
                                       jnp.asarray(q["toks"])))
        del part
    for l, kind in enumerate(sz["layer_types"]):
        w = [leaf(l, n) for n in ref.MIXER_LEAVES[kind]]
        if kind == "conv":
            def mixer(q):
                x, tails = run["conv"](q["x"], q["stops"], fixed, *w)
                q["tails"].append(tails)
                return x
        else:
            def mixer(q):
                x, keys = run["attn"](q["x"], at(q["blocks"]), fixed, *w)
                if len(q["keys"]) < 2 and q["stops"][0]:
                    q["keys"].append(keys)
                return x
        through(mixer)
        dense = ref.is_dense(sz, l)
        w = [leaf(l, n) for n in ref.FFN_LEAVES[dense]]
        through(lambda q: run["dense" if dense else "moe"](
            q["x"], at(q["blocks"]), fixed, *w))
        del w

    for q in seqs:
        q["parts"] = []
        q["rows"] = q.pop("x")[q["pos"]]
    for i in range(V_PARTS):
        part = ref.draw_rows(seed, sz, weights, i, V_PARTS)
        for q in seqs:
            t0 = time.time()
            nxt = jnp.asarray(q["toks"][np.minimum(q["pos"] + 1, S - 1)])
            q["parts"].append(jax.block_until_ready(
                run["head"](q["rows"], nxt, part, at(i))))
            q["seconds"] += time.time() - t0
        del part
    out = []
    for s, q in zip(sample, seqs):
        n = q["n"]
        best = np.sort(np.concatenate(
            [np.asarray(p[0]) for p in q["parts"]], axis=1), axis=1)
        mine = sum(np.asarray(p[1]) for p in q["parts"])
        g = np.where(np.isnan(best[:, -1] - mine), np.inf,
                     best[:, -1] - mine)[:n]
        lead = (best[:, -1] - best[:, -2])[:n]
        first_rows = jnp.concatenate([p[2] for p in q["parts"]], axis=-1)
        extra = {}
        for rid, first, got, left in replays:
            if rid != s["rid"]:
                continue
            assert first == 0, "the head keeps a sequence's first rows"
            want = first_rows[:got.shape[0]]
            theirs = jnp.stack(q["tails"], 1)            # [2, Lc, 2, D]
            rel = jnp.sqrt(
                jnp.mean(jnp.square(left["tails"] - theirs), (2, 3))
                / jnp.mean(jnp.square(theirs), (2, 3)))  # [2, Lc]
            plen = len(s["tokens"])
            off = [np.asarray(
                jnp.linalg.norm(mine - ref_k[:plen], axis=-1)
                / jnp.linalg.norm(ref_k[:plen], axis=-1))
                for mine, ref_k in zip(left["keys"], q["keys"])]
            extra = {"tail_rel_rms": float(rel[:, 0].max()),
                     "tail_rel_rms_deepest": float(rel.max()),
                     "first_keys_max": float(off[0].max()),
                     "second_keys_q25": float(np.percentile(off[-1], 25)),
                     "second_keys_median": float(np.median(off[-1])),
                     "logit_rel_rms": float(jnp.sqrt(jnp.mean(
                         (got - want) ** 2)) / jnp.std(want)),
                     "logit_max_abs": float(jnp.abs(got - want).max()),
                     "replayed": int(got.shape[0])}
        out.append({"rid": s["rid"], "context": len(s["tokens"]) + n,
                    "blocks": q["blocks"], **extra,
                    "seconds": q["seconds"], "programs_s": t_first,
                    "max_gap": float(g.max()), "n": n,
                    "n_argmax": int((g <= 0.0).sum()),
                    "median_top2_gap": float(np.median(lead))})
    return out
