"""Holding served tokens of a `bailing_hybrid` (Ling-3.0) replica to its
plain reference (inside the replica, after the window): the scheme of
check_deepseek_v3.py — the reference DRAWS ITS OWN WEIGHTS from the seed, a
leaf at a time when a layer's turn comes, and is driven piece by piece so
that it fits beside the engine — for a model whose sequence holds a state
entry beside its pages."""

from __future__ import annotations

import concurrent.futures
import functools
import time
from typing import Dict, List

DIRT = 1.0e3         # what the replay's entry and pages hold before it
F_PARTS = 4          # the dense SwiGLU a quarter of its width at a time
V_PARTS = 4          # the head a quarter of the vocabulary at a time


def replay_logits(eng, prompt: List[int], n: int, keep: int = None):
    """check_deepseek_v3.replay_logits for an engine of several kinds: the
    logits rows the engine's OWN programs form for the first `n` tokens
    after `prompt` — `serve.prefill:<T>` chunk by chunk as the engine cuts
    them (every chunk but the first reads the latents in its pages AND the
    states and conv tails its entry carries), `serve.setrow`, then
    `serve.step` with slot 0 live on pages 1.. of every paged pool and
    entry 1 of every state pool — greedy, on an entry and pages DIRTIED
    first (below).  Run while the engine is idle.
    Returns (rows [keep, V] float32 on the device, the n tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_device_state()
    plen = len(prompt)
    need = -(-(plen + n) // eng.page_size)

    # what the replay is handed is DIRTIED first: entry 1's states and
    # tails, its pages 1..need and the null page hold DIRT, as if a
    # careless holder had left them.  A sound program reads none of it (a
    # first chunk starts from zeros, a chunk reads its own earlier pages
    # and masks what lies past its rows); one that reads its entry's last
    # holder, or the null page where its earlier latents lie, shows it in
    # every replayed row.  Finite, so that a masked 0 x DIRT stays 0.
    @functools.partial(jax.jit, donate_argnums=0)
    def dirtied(cache, last_page):
        def pages(a):
            mine = (jnp.arange(a.shape[0]) <= last_page)[:, None, None]
            return jnp.where(mine, jnp.asarray(DIRT, a.dtype), a)

        return {"latent": [pages(a) for a in cache["latent"]],
                "state": cache["state"].at[:, 1].set(DIRT),
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
    return jnp.stack(rows), [int(t) for t in np.asarray(jnp.stack(toks))]


def join_replays(sample: List[Dict], replays: List[tuple]):
    """The sample with replays joined in: `replays` = [(index into the
    sample, the replayed tokens, the first kept token's index, its logits
    rows)] -> (entries, `served_gaps`'s `replays`).  Where a replay's
    tokens are the ones its request was served — every sound run so far —
    the request's own reference pass yields the rows the replayed logits
    are held to; where they are not, the replay is an entry of its own."""
    entries, out = list(sample), []
    for k, toks, first, rows, states in replays:
        head = sample[k]
        rid = head["rid"]
        if toks != head["served"][:len(toks)]:
            rid = f"replay-{rid}"
            entries.append({"rid": rid, "tokens": head["tokens"],
                            "served": toks})
        out.append((rid, first, rows, states))
    return entries, out


def state_layers(sz: Dict) -> List[int]:
    """The KDA layers that no expert layer precedes (the first expert
    layer's mixer runs before its experts): where the program's state is
    held to the recurrence's.  They are the first of the state arena."""
    from . import ling3_plain as ref

    return [l for l in range(min(sz["n_dense"] + 1, sz["n_layers"]))
            if not ref.is_mla(sz, l)]


def build_programs(sz: Dict, spec: Dict, n_logits: int):
    """The reference's six programs (`served_gaps`), traced and compiled
    side by side; nothing runs on the device.  -> ({name: compiled}, the
    seconds it took).  The replica starts this on a thread during set-up
    (`bench_prepare_reference`): on a checkout's first run their 19 s of
    compiling then overlap the serve programs' instead of following the
    window."""
    import jax
    import jax.numpy as jnp

    from . import ling3_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    heads = int(spec["heads"])
    cap = -(-4 * S * sz["top_k"] // sz["n_experts"])
    ones = lambda n: jnp.ones((n,), jnp.float32)
    D, V, F, H, d = (sz["d_model"], sz["vocab"], sz["d_ff"], sz["n_heads"],
                     sz["d_head"])
    assert F % F_PARTS == 0 and V % V_PARTS == 0 and S % rows == 0

    f32, i32, pd = jnp.float32, jnp.int32, ref.dsp._dtype(sz["param_dtype"])
    sh = jax.ShapeDtypeStruct
    x_, n_ = sh((S, D), f32), sh((), i32)
    kda_w = ("w_qkv", "conv_w", "w_f", "w_b", "w_g", "wo")
    mla_w = ("wkv_a", "wq", "wkv_b", "w_head_gate", "wo")
    dense_w = ("w_gate", "w_up", "w_down")
    moe_w = ("router", "router_bias", "wg", "wu", "wd", "shared_gate",
             "shared_up", "shared_down")
    spec_of = lambda name, mla=False: sh(ref.leaf_specs(sz, mla)[name][0], pd)

    def kda(x, a_log, dt_bias, blocks, stop, *w):
        lp = dict(zip(kda_w, w), a_log=a_log, dt_bias=dt_bias,
                  o_norm=ones(d), conv_b=jnp.zeros((3 * H, d), f32))
        return ref.kda_layer(x, ones(D), lp, sz, rows, blocks, stop)

    def mla(x, first, blocks, *w):
        lp = dict(zip(mla_w, w), kv_norm=ones(sz["kv_rank"]))
        return ref.mla_layer(x, ones(D), lp, sz, heads, rows, blocks, first)

    def head(x, w):
        return jnp.concatenate([ref.readout(x, ones(D), w, sz, i, V_PARTS)
                                for i in range(V_PARTS)], -1)

    E = sz["n_experts"]
    programs = {
        "embed": (lambda table, toks: table[toks].astype(f32),
                  (sh((V, D), pd), sh((S,), i32)), {}),
        "kda": (kda, (x_, sh((H,), f32), sh((H, d), f32), n_, n_)
                + tuple(spec_of(n) for n in kda_w), dict(donate_argnums=0)),
        "mla": (mla, (x_, n_, n_) + tuple(spec_of(n, True) for n in mla_w),
                dict(donate_argnums=0)),
        "head": (head, (sh((n_logits, D), f32), sh((D, V), pd)), {}),
    }
    if sz["n_dense"]:
        programs["dense"] = (
            lambda x, *w: ref.dense_layer(x, ones(D), dict(zip(dense_w, w)),
                                          sz, F_PARTS),
            (x_,) + tuple(spec_of(n) for n in dense_w),
            dict(donate_argnums=0))
    if sz["n_dense"] < sz["n_layers"]:
        programs["moe"] = (
            lambda x, *w: ref.moe_layer(x, ones(D), dict(zip(moe_w, w)), sz,
                                        cap),
            (x_, sh((D, E), f32), sh((E,), f32))
            + tuple(spec_of(n) for n in moe_w[2:]), dict(donate_argnums=0))
    t_first = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(programs)) as pool:
        built = {name: pool.submit(
            lambda f, a, kw: jax.jit(f, **kw).lower(*a).compile(), *p)
            for name, p in programs.items()}
        run = {name: f.result() for name, f in built.items()}
    t_first = time.time() - t_first
    return run, t_first


def served_gaps(seed: int, sz: Dict, weights: Dict, sample: List[Dict],
                spec: Dict, n_logits: int, replays=(), built=None
                ) -> List[Dict]:
    """check_deepseek_v3.served_gaps over `ling3_plain`'s pieces: for each
    {"rid", "tokens" (prompt), "served"} one teacher-forced reference pass
    (float32, highest precision: the KDA recurrence a token at a time, MLA
    expanded over every position) over prompt + served tokens, padded to
    `spec["max_context"]` rows so that every program has ONE shape; per
    request the largest distance of a served token's reference logit below
    its position's maximum, how many served tokens are the reference's
    argmax, and the median lead of the reference's best logit.  Work is
    done for a sequence's own `spec["rows"]`-row blocks only.  Each of
    `replays` = (rid, first, rows [m, V], states) adds to its entry
    `logit_rel_rms` / `logit_max_abs` of the program's own logits rows
    against the reference's, and `state_rel_rms`: the replayed entry's
    state matrices (`states` [n, H, dv, dk], as the program keeps them,
    of the KDA layers that no expert layer precedes — `state_layers` —
    after prompt + `first` + m tokens) against the recurrence's own state
    at that position, root mean square of the difference over the
    recurrence's, by layer (`state_rel_rms_by_layer`).  Downstream
    of an expert layer a router's near-tie moves everything (the logits
    stand 23-40% of their spread apart in a sound run); upstream of the
    first one the two sides differ by their arithmetic alone, and in the
    FIRST layer, whose input is the embedding's own rows, by the mixer's
    alone: `state_rel_rms` is that layer's.

    The layers are the OUTER loop: a layer's leaves are drawn once (its
    expert stacks are 4.5e9 B of draws), every sampled sequence goes
    through it, and they are dropped before the next layer's turn.  A
    layer is one program a kind (`kda_layer`, `mla_layer`, `dense_layer`,
    `moe_layer`; with the embedding and the head six in all: on a warm
    checkout what a program costs is its tracing, ~0.7 s each)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import ling3_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    V = sz["vocab"]
    kda_w = ("w_qkv", "conv_w", "w_f", "w_b", "w_g", "wo")
    mla_w = ("wkv_a", "wq", "wkv_b", "w_head_gate", "wo")
    dense_w = ("w_gate", "w_up", "w_down")
    moe_w = ("router", "router_bias", "wg", "wu", "wd", "shared_gate",
             "shared_up", "shared_down")
    leaf = lambda l, name: ref.draw_leaf(seed, sz, weights, l, name)
    run, t_first = built or build_programs(sz, spec, n_logits)
    at = np.int32                       # a block's index, as compiled

    @jax.jit
    def gaps(lg, nxt, n_out):
        top = lg.max(-1)
        gap = top - jnp.take_along_axis(lg, nxt[:, None], 1)[:, 0]
        second = jnp.where(
            jnp.arange(lg.shape[-1]) == lg.argmax(-1)[:, None],
            -jnp.inf, lg).max(-1)
        gap = jnp.where(jnp.isnan(gap), jnp.inf, gap)   # a NaN passes nothing
        return (jnp.where(jnp.arange(n_logits) < n_out, gap, 0.0),
                top - second)

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
                     "stop": stop[0] if stop else 0, "states": {},
                     "blocks": -(-len(seq) // rows), "seconds": 0.0})

    def through(piece):
        """Every sequence's x through one piece, its seconds its own."""
        for q in seqs:
            t0 = time.time()
            q["x"] = jax.block_until_ready(piece(q))
            q["seconds"] += time.time() - t0

    table = leaf(-1, "embed")
    through(lambda q: run["embed"](table, jnp.asarray(q["toks"])))
    del table
    for l in range(sz["n_layers"]):
        last = l == sz["n_layers"] - 1
        if ref.is_mla(sz, l):
            w = [leaf(l, n) for n in mla_w]
            # the last layer's attention only for the rows that are read
            through(lambda q: run["mla"](
                q["x"], at(int(q["pos"][0]) // rows if last else 0),
                at(q["blocks"]), *w))
        else:
            w = [leaf(l, n) for n in ("a_log", "dt_bias") + kda_w]

            def kda(q):
                x, q["states"][l] = run["kda"](
                    q["x"], w[0], w[1], at(q["blocks"]), at(q["stop"]),
                    *w[2:])
                return x

            through(kda)
        dense = l < sz["n_dense"]
        w = [leaf(l, n) for n in (dense_w if dense else moe_w)]
        through(lambda q: run["dense" if dense else "moe"](q["x"], *w))
        del w
    unembed = leaf(-1, "unembed")
    through(lambda q: run["head"](q["x"][q["pos"]], unembed))
    del unembed

    out = []
    for s, q in zip(sample, seqs):
        lg, toks, pos, n = q["x"], q["toks"], q["pos"], q["n"]
        g, lead = gaps(lg, jnp.asarray(toks[np.minimum(pos + 1, S - 1)]), n)
        g, lead = np.asarray(g)[:n], np.asarray(lead)[:n]
        extra = {}
        for rid, first, got, states in replays:
            if rid != s["rid"]:
                continue
            want = lg[first:first + got.shape[0]]
            rel = [float(jnp.sqrt(jnp.mean(jnp.square(
                       jnp.swapaxes(states[i], -1, -2) - q["states"][l]))
                       / jnp.mean(jnp.square(q["states"][l]))))
                   for i, l in enumerate(state_layers(sz))]
            extra = {"state_rel_rms": rel[0], "state_rel_rms_by_layer": rel,
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
