"""Holding served tokens of a `phi4flash` (Phi-4-mini-flash) replica to its
plain reference (inside the replica, after the window): check_ling3.py's
scheme — the reference DRAWS ITS OWN WEIGHTS from the seed, a leaf at a
time when a layer's turn comes, and is driven piece by piece so that it
fits beside the engine — for a model whose sequence holds full pages, a
ring of window pages and a state entry, and whose logits are 200,064 wide
(the head is read a block of rows at a time and only what is compared
leaves the program)."""

from __future__ import annotations

import concurrent.futures
import functools
import time
from typing import Dict, List

from .check_ling3 import join_replays  # noqa: F401  (the replica's)

DIRT = 1.0e3         # what the replay's entry and pages hold before it
HEAD_ROWS = 256      # rows of the head a loop turn
V_PARTS = 4          # ... a quarter of the vocabulary at a time


def replay_logits(eng, prompt: List[int], n: int, keep: int = None):
    """check_ling3.replay_logits for an engine of three kinds: the logits
    rows the engine's OWN programs form for the first `n` tokens after
    `prompt` — `serve.prefill:<T>` chunk by chunk as the engine cuts them
    (told which is the last), `serve.setrow`, then `serve.step` with slot
    0 live on pages 1.. of the full pool, the whole ring of the windowed
    pool (logical page lp in entry lp % width, as the engine lays it) and
    entry 1 of the state pool — greedy, on an entry and pages DIRTIED
    first.  Run while the engine is idle.
    Returns (rows [keep, V] float32 on the device, the n tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_device_state()
    plen = len(prompt)
    need = -(-(plen + n) // eng.page_size)
    ring = eng._widths["swa"]

    # entry 1's states and tails, full pages 0..need, the ring's pages
    # 0..width hold DIRT, as if a careless holder had left them.  A sound
    # program reads none of it: a first chunk starts from zeros, a row
    # masks what lies past it or a window behind it, an empty slot's null
    # page is no one's key.  Finite, so that a masked 0 x DIRT stays 0.
    @functools.partial(jax.jit, donate_argnums=0)
    def dirtied(cache, last_page):
        def pages(a, last):
            mine = (jnp.arange(a.shape[0]) <= last)[:, None, None]
            return jnp.where(mine, jnp.asarray(DIRT, a.dtype), a)

        sides = lambda arena, last: {s: pages(a, last)
                                     for s, a in arena.items()}
        return {"full": sides(cache["full"], last_page),
                "swa": [sides(a, ring) for a in cache["swa"]],
                "state": cache["state"].at[:, 1].set(DIRT),
                "tail": cache["tail"].at[:, 1].set(DIRT)}

    eng._cache = dirtied(eng._cache, np.int32(need))
    tabs = {}
    for kind, width in eng._widths.items():
        tabs[kind] = np.zeros(width, np.int32)
        if kind in eng._state_kinds:
            tabs[kind][0] = 1
        elif kind in eng._windowed:
            tabs[kind][:] = np.arange(1, width + 1)
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
            np.int32(m - 1), np.bool_(start + m == plen))
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


def _programs(sz: Dict, spec: Dict, n_logits: int, keep: int):
    """name -> (function, operand shapes, jit options) of the reference's
    programs: one a layer kind, the embedding and the head."""
    import jax
    import jax.numpy as jnp

    from . import phi4flash_plain as ref
    from .deepseek_v3_plain import _dtype

    rows, S = int(spec["rows"]), int(spec["max_context"])
    D, V, C = sz["d_model"], sz["vocab"], ref.d_inner(sz)
    head_rows = min(HEAD_ROWS, n_logits)
    assert S % rows == 0 and n_logits % head_rows == 0 and V % V_PARTS == 0
    Hkv, dh = sz["n_kv_heads"], sz["d_head"]
    f32, i32, pd = jnp.float32, jnp.int32, _dtype(sz["param_dtype"])
    sh = jax.ShapeDtypeStruct
    x_, n_ = sh((S, D), f32), sh((), i32)
    kv_ = sh((S, Hkv, dh), f32)
    specs = ref.leaf_specs(sz)

    def leaves(kind):
        out = []
        for name in ref.KIND_LEAVES[kind]:
            if name == "b_dt":
                out.append(sh((C,), f32))
            elif name in ref.LAMBDAS:
                out.append(sh((dh,), f32))
            else:
                out.append(sh(specs[name][0], pd))
        return tuple(out)

    def lp_of(kind, w):
        return {**dict(zip(ref.KIND_LEAVES[kind], w)),
                **ref.fixed_leaves(sz, kind)}

    def mamba(x, blocks, stop, *w):
        return ref.mamba_layer(x, lp_of("mamba", w), sz, rows, blocks, stop)

    def attn(kind):
        def run(x, layer, blocks, *w):      # `layer`: lam0 is its index's
            lp = lp_of(kind, w)
            k, v = ref.keys_values(x, lp, sz)
            return ref.attend(x, k, v, lp, sz, layer,
                              sz["window"] if kind == "swa" else None,
                              rows, blocks), k, v
        return run

    def cross(x, k, v, layer, blocks, *w):
        return ref.attend(x, k, v, lp_of("cross", w), sz, layer, None, rows,
                          blocks)

    def gmu(x, mem, blocks, *w):
        return ref.gmu_layer(x, mem, lp_of("gmu", w), sz, rows, blocks)

    def mlp(x, blocks, *w):
        lp = {**dict(zip(ref.MLP_LEAVES, w)), **ref.fixed_leaves(sz, "gmu")}
        return ref.mlp_layer(x, lp, sz, rows, blocks)

    def head(x, nxt, n_out, table):
        """x [n_logits, D] -> (each row's distance of token `nxt` below the
        row's maximum — 0 past `n_out` —, the lead of the best logit over
        the second, the first `keep` rows' logits).  The vocabulary is
        read V_PARTS slices one after the other: the table in float32 is
        2e9 B."""
        ones, zero = jnp.ones(D, f32), jnp.zeros(D, f32)
        part = lambda xb, i: ref.readout(xb, ones, zero, table, sz, i,
                                         V_PARTS)
        size = V // V_PARTS

        def block(xs):
            xb, nb = xs
            best, mine = [], 0.0
            for i in range(V_PARTS):
                lg = part(xb, i)
                best.append(jax.lax.top_k(lg, 2)[0])
                at = nb - i * size
                mine = mine + jnp.where(
                    (at >= 0) & (at < size), jnp.take_along_axis(
                        lg, jnp.clip(at, 0, size - 1)[:, None], 1)[:, 0], 0.0)
            best = jax.lax.top_k(jnp.concatenate(best, -1), 2)[0]
            gap = best[:, 0] - mine
            return (jnp.where(jnp.isnan(gap), jnp.inf, gap),
                    best[:, 0] - best[:, 1])

        cut = lambda a: a.reshape((n_logits // head_rows, head_rows)
                                  + a.shape[1:])
        gap, lead = jax.lax.map(block, (cut(x), cut(nxt)))
        gap = jnp.where(jnp.arange(n_logits) < n_out, gap.reshape(-1), 0.0)
        return (gap, lead.reshape(-1), jnp.concatenate(
            [part(x[:keep], i) for i in range(V_PARTS)], -1))

    don = dict(donate_argnums=0)
    return {
        "embed": (lambda table, toks: table[toks].astype(f32),
                  (sh((V, D), pd), sh((S,), i32)), {}),
        "mamba": (mamba, (x_, n_, n_) + leaves("mamba"), don),
        "swa": (attn("swa"), (x_, n_, n_) + leaves("swa"), don),
        "full": (attn("full"), (x_, n_, n_) + leaves("full"), don),
        "cross": (cross, (x_, kv_, kv_, n_, n_) + leaves("cross"), don),
        "gmu": (gmu, (x_, sh((S, C), f32), n_) + leaves("gmu"), don),
        "mlp": (mlp, (x_, n_) + tuple(sh(specs[n][0], pd)
                                      for n in ref.MLP_LEAVES), don),
        "head": (head, (sh((n_logits, D), f32), sh((n_logits,), i32), n_,
                        sh((V, D), pd)), {}),
    }


def build_programs(sz: Dict, spec: Dict, n_logits: int, seed: int = None):
    """The reference's programs, traced and compiled side by side; nothing
    of them runs on the device.  -> ({name: compiled}, the seconds it
    took).  The replica's loader starts this on a thread during set-up.
    With `seed`, the programs that DRAW the reference's leaves are made
    too, by drawing one leaf of each shape once and dropping it (a jitted
    program a shape: ~2 s of loads that otherwise follow the window)."""
    import jax

    from . import phi4flash_plain as ref

    programs = _programs(sz, spec, n_logits, int(spec["replay_keep"]))
    t_first = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(programs)) as pool:
        built = {name: pool.submit(
            lambda f, a, kw: jax.jit(f, **kw).lower(*a).compile(), *p)
            for name, p in programs.items()}
        run = {name: f.result() for name, f in built.items()}
    if seed is not None:
        first = {}                      # a kind's first layer
        for l in range(sz["n_layers"]):
            first.setdefault(ref.kind(sz, l), l)
        for kind, l in first.items():
            for name in ref.KIND_LEAVES[kind] + ref.MLP_LEAVES:
                jax.block_until_ready(ref.draw_leaf(seed, sz, l, name))
        jax.block_until_ready(ref.draw_leaf(seed, sz, -1, "embed"))
    return run, time.time() - t_first


def served_gaps(seed: int, sz: Dict, sample: List[Dict], spec: Dict,
                n_logits: int, replays=(), built=None) -> List[Dict]:
    """check_ling3.served_gaps over `phi4flash_plain`'s pieces: for each
    {"rid", "tokens" (prompt), "served"} one teacher-forced reference pass
    (float32, highest precision: the selective scan a token at a time,
    every attention layer two explicit softmaxes over every position, the
    cross layers on layer L/2 + 1's keys and values, EVERY layer on every
    position) over prompt + served tokens, padded to `spec["max_context"]`
    rows so that every program has ONE shape; per request the largest
    distance of a served token's reference logit below its position's
    maximum, how many served tokens are the reference's argmax, and the
    median lead of the reference's best logit.  Work is done for a
    sequence's own `spec["rows"]`-row blocks only.  Each of `replays` =
    (rid, first, rows [m, V], state) adds to its entry `logit_rel_rms` /
    `logit_max_abs` of the program's own logits rows against the
    reference's (m = `replay_keep` = the rows the head program keeps, so
    `first` is 0), and `state_rel_rms`: the replayed entry's FIRST Mamba
    layer's state ([N, C], as the program keeps it, after prompt + m
    tokens) against the recurrence's own at that position — that layer's
    input is the embedding's own rows, so the two sides differ there by
    the scan's arithmetic alone.

    The layers are the OUTER loop: a layer's leaves are drawn once, every
    sampled sequence goes through it, and they are dropped before the next
    layer's turn (in float32 the weights are 15.4e9 B: they never stand
    together)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import phi4flash_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    V, L = sz["vocab"], sz["n_layers"]
    leaf = lambda l, name: ref.draw_leaf(seed, sz, l, name)
    run, t_first = built or build_programs(sz, spec, n_logits)
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
                     "stop": stop[0] if stop else 0,
                     "blocks": -(-len(seq) // rows), "seconds": 0.0})

    def through(piece):
        """Every sequence's x through one piece, its seconds its own."""
        for q in seqs:
            t0 = time.time()
            q["x"] = jax.block_until_ready(piece(q))
            q["seconds"] += time.time() - t0

    table = leaf(-1, "embed")
    through(lambda q: run["embed"](table, jnp.asarray(q["toks"])))
    for l in range(L):
        kind = ref.kind(sz, l)
        w = [leaf(l, n) for n in ref.KIND_LEAVES[kind]]

        def mixer(q):
            b = at(q["blocks"])
            if kind == "mamba":
                x, mem, state = run["mamba"](q["x"], b, at(q["stop"]), *w)
                if l == 0:
                    q["state"] = state
                if l == L // 2:
                    q["mem"] = mem
                return x
            if kind == "gmu":
                return run["gmu"](q["x"], q["mem"], b, *w)
            if kind == "cross":
                return run["cross"](q["x"], *q["kv"], at(l), b, *w)
            x, k, v = run[kind](q["x"], at(l), b, *w)
            if kind == "full":
                q["kv"] = (k, v)
            return x

        through(mixer)
        w = [leaf(l, n) for n in ref.MLP_LEAVES]
        through(lambda q: run["mlp"](q["x"], at(q["blocks"]), *w))
        del w

    out = []
    for s, q in zip(sample, seqs):
        toks, pos, n = q["toks"], q["pos"], q["n"]
        t0 = time.time()
        g, lead, first_rows = run["head"](
            q["x"][pos], jnp.asarray(toks[np.minimum(pos + 1, S - 1)]),
            at(n), table)
        g, lead = np.asarray(g)[:n], np.asarray(lead)[:n]
        q["seconds"] += time.time() - t0
        extra = {}
        for rid, first, got, state in replays:
            if rid != s["rid"]:
                continue
            assert first == 0, "the head keeps a sequence's first rows"
            want = first_rows[:got.shape[0]]
            rel = float(jnp.sqrt(
                jnp.mean(jnp.square(state.T - q["state"]))
                / jnp.mean(jnp.square(q["state"]))))
            extra = {"state_rel_rms": rel,
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
