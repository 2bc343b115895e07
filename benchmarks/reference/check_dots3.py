"""Holding served tokens of a `dots3_note` replica to its plain reference
(inside the replica, after the window): check_deepseek_v3.py's scheme — a
reference that DRAWS ITS OWN WEIGHTS from the seed, a leaf at a time, when a
layer's turn comes, driven piece by piece so that it fits beside the engine
— over two kinds of layer, a full layer's selection formed once (`select`:
a top-k over the whole causal row, kept as bits) before its attention."""

from __future__ import annotations

import concurrent.futures
import time
from typing import Dict, List

F_PARTS = 8          # the dense SwiGLU an eighth of its width at a time
V_PARTS = 4          # the head a quarter of the vocabulary at a time


def replay_logits(eng, prompt: List[int], n: int, keep: int = None):
    """The logits rows the engine's OWN programs form for the first `n`
    tokens after `prompt` — `serve.prefill:<T>` chunk by chunk as the
    engine cuts them over BOTH pools (a full kind's table pages 1.. of its
    pool in order; a windowed kind's a ring whose entry e is page 1 + e:
    logical page lp lies in entry lp % R as the engine lays it, and what a
    page held a ring ago lies past every query until it is overwritten),
    `serve.setrow`, then `serve.step` with slot 0 live — greedy, so the
    tokens are the ones a request with this prompt was served.  Run while
    the engine is idle (after the window): its arenas and logits are taken
    and handed back.  Returns (rows [keep, V] float32 on the device: the
    last `keep` of the n tokens', all where None; the n tokens).  Row i of
    all n is what token i was drawn from: the reference's row at position
    len(prompt) - 1 + i."""
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_device_state()
    plen = len(prompt)
    need = -(-(plen + n) // eng.page_size)
    tabs = {}
    for kind, window in eng._kinds.items():
        tab = np.zeros(eng._widths[kind], np.int32)
        m = need if window is None else tab.shape[0]
        tab[:m] = np.arange(1, m + 1)
        tabs[kind] = tab
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


def compile_pieces(sz: Dict, spec: Dict, n_logits: int):
    """The reference's pieces compiled for their ONE shape each (the
    sequence padded to `spec["max_context"]` rows), side by side: on a
    checkout's first run the compiles overlap.  Returns ({name: compiled},
    the seconds it took).  Takes no weights and touches no device memory:
    a replica may run it in a thread while it starts — not while its
    engine holds a request: the pool's threads trace under the one
    interpreter lock, and the engine's thread stands still meanwhile."""
    import jax
    import jax.numpy as jnp

    from . import dots3_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    heads = int(spec["heads"])
    cap = -(-4 * S * sz["top_k"] // sz["n_experts"])
    ones = lambda n: jnp.ones((n,), jnp.float32)
    D, V, F = sz["d_model"], sz["vocab"], sz["d_ff"]
    di = sz["index_dim"]
    kinds = sz["layer_types"]
    assert F % F_PARTS == 0 and V % V_PARTS == 0 and S % rows == 0
    assert rows % 32 == 0

    f32, i32, pd = jnp.float32, jnp.int32, ref._dtype(sz["param_dtype"])
    sh = jax.ShapeDtypeStruct
    x_, n_ = sh((S, D), f32), sh((), i32)
    w_ = lambda kind, name: sh(ref.leaf_specs(sz, kind)[name][0], pd)
    sized = {k: ref.kind_sizes(sz, k) for k in set(kinds)}
    if "sliding" in sized:
        sized["sliding"]["other_theta"] = sz["full"]["theta"]
    programs = {
        "embed": (lambda table, toks: table[toks].astype(f32),
                  (sh((V, D), pd), sh((S,), i32)), {}),
        "normed": (lambda x: ref.normed(x, ones(D), sz), (x_,), {}),
        "readout": (lambda x, w, i: ref.readout(x, ones(D), w, sz, i,
                                                V_PARTS),
                    (sh((n_logits, D), f32), sh((D, V), pd), n_), {}),
    }
    for kind, a in sized.items():
        rq, rkv = a["q_rank"], a["kv_rank"]
        full = kind == "full"
        programs["latents_" + kind] = (
            lambda x, wkv_a, a=a, rkv=rkv: ref.latents(
                x, ones(D), wkv_a, ones(rkv), a),
            (x_, w_(kind, "wkv_a")), {})
        programs["attend_" + kind] = (
            lambda x, c_kv, k_pe, kept, wq_a, wq_b, wkv_b, gate, wo, first,
            blocks, a=a, rq=rq, full=full: ref.attend(
                x, c_kv, k_pe, kept if full else None, ones(D), wq_a,
                ones(rq), wq_b, wkv_b, gate, wo, a, heads, rows, blocks,
                first),
            (x_, sh((S, rkv), f32), sh((S, a["d_rope"]), f32),
             sh((S, S // 32) if full else (1, 1), jnp.uint32),
             *(w_(kind, n) for n in ("wq_a", "wq_b", "wkv_b", "w_head_gate",
                                     "wo")), n_, n_),
            dict(donate_argnums=0))
    if "full" in sized:
        rq = sized["full"]["q_rank"]
        programs.update(
            index_keys=(lambda x, wi_k: ref.index_keys(
                x, ones(D), wi_k, ones(di), jnp.zeros((di,), f32), sz),
                (x_, w_("full", "wi_k")), {}),
            select=(lambda x, ki, wq_a, wi_q, wi_w, blocks: ref.select(
                x, ki, ones(D), wq_a, ones(rq), wi_q, wi_w, sz, heads, rows,
                blocks),
                (x_, sh((S, di), f32), w_("full", "wq_a"),
                 w_("full", "wi_q"), w_("full", "wi_w"), n_), {}))
    if sz["n_dense"]:
        programs["dense"] = (
            lambda x, h, wg, wu, wd, i: x + ref.dense_part(
                h, wg, wu, wd, sz, i, F_PARTS),
            (x_, x_, w_("full", "w_gate"), w_("full", "w_up"),
             w_("full", "w_down"), n_), dict(donate_argnums=0))
    if sz["n_dense"] < sz["n_layers"]:
        E, k = sz["n_experts"], sz["top_k"]
        programs.update(
            route=(lambda h, r, b: ref.route(h, r, b, sz),
                   (x_, sh((D, E), f32), sh((E,), f32)), {}),
            expert=(lambda x, h, w, idx, e, wg, wu, wd: x + ref.expert(
                h, w, idx, e, wg, wu, wd, sz, cap),
                (x_, x_, sh((S, k), f32), sh((S, k), i32), n_,
                 w_("full", "wg"), w_("full", "wu"), w_("full", "wd")),
                dict(donate_argnums=0)),
            shared=(lambda x, h, wg, wu, wd, i: x + ref.shared_expert(
                h, wg, wu, wd, sz, i),
                (x_, x_, w_("full", "shared_gate"), w_("full", "shared_up"),
                 w_("full", "shared_down"), n_), dict(donate_argnums=0)))
    # every program compiled for its ONE shape before any runs, side by
    # side: on a checkout's first run the compiles overlap.  The draw's
    # programs too, one a leaf size (into the compile cache: a checkout's
    # first pass compiled them one after the other, 18 s, inside the pass)
    t_first = time.time()
    draws = ref.draw_programs(sz)
    with concurrent.futures.ThreadPoolExecutor(
            len(programs) + len(draws)) as pool:
        built = {name: pool.submit(
            lambda f, a, kw: jax.jit(f, **kw).lower(*a).compile(), *p)
            for name, p in programs.items()}
        ahead = [pool.submit(lambda c, dt: ref._pieces.lower(
            0, 0, 0, jnp.float32(1), c, ref.DRAW_PIECE, dt).compile(), c, dt)
            for c, dt in draws]
        run = {name: f.result() for name, f in built.items()}
        for f in ahead:
            f.result()
    return run, time.time() - t_first


def served_gaps(seed: int, sz: Dict, weights: Dict, sample: List[Dict],
                spec: Dict, n_logits: int, replay=None,
                built=None) -> List[Dict]:
    """`check_deepseek_v3.served_gaps` for this model: for each {"rid",
    "tokens" (prompt), "served"} one teacher-forced reference pass
    (float32, highest precision, the published form: no cache, no
    absorption, the selection `jax.lax.top_k`'s over the whole causal row)
    over prompt + served tokens; per request the largest distance of a
    served token's reference logit below the maximum of its position, how
    many served tokens are that argmax, and the median distance between
    the reference's two largest logits at the served positions.

    The weights are the reference's own draw from `seed` (`dots3_plain.
    draw_leaf`: `sz` its sizes, `weights` the configuration's `weights`):
    a layer's leaves are drawn when its turn comes and dropped after it.
    The pieces (`dots3_plain.latents` / `index_keys` / `select` / `attend`
    / `dense_part` / `route` / `expert` / `shared_expert` / `readout`) run
    over the sequence padded to `spec["max_context"]` rows: programs of
    ONE shape each, compiled once a checkout whatever the sample.  The pad
    rows lie behind every real row, so no real row sees them; attention
    and the selection walk `spec["rows"]` rows at a time, `spec["heads"]`
    heads at a time, over the row blocks the sequence reaches only.
    Logits are formed for `n_logits` positions from the last prompt token
    on.

    `built`: `compile_pieces(sz, spec, n_logits)`'s result where a caller
    had them compiled ahead (the replica, from its loader on).

    `replay` = (rid, first, rows [m, V]): the program's own logits rows of
    that entry's tokens first..first+m-1 (`replay_logits`); the entry then
    also carries `logit_rel_rms`, the root mean square of program minus
    reference over those rows as a share of the reference rows' standard
    deviation, and `logit_max_abs`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import dots3_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    leaf = lambda l, name: ref.draw_leaf(seed, sz, weights, l, name)
    V, kinds = sz["vocab"], sz["layer_types"]
    run, t_first = built or compile_pieces(sz, spec, n_logits)
    at = np.int32                       # a piece's index, as compiled
    no_bits = jnp.zeros((1, 1), jnp.uint32)

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

    def feed_forward(l, x):
        """x [S, D] with what layer l's feed-forward adds to it."""
        h = run["normed"](x)
        if l < sz["n_dense"]:
            w = [leaf(l, n) for n in ("w_gate", "w_up", "w_down")]
            for i in range(F_PARTS):
                x = run["dense"](x, h, *w, at(i))
            return x
        w, idx = run["route"](h, leaf(l, "router"), leaf(l, "router_bias"))
        held = [leaf(l, n) for n in ("wg", "wu", "wd")]
        for e in range(sz["held"]):
            x = run["expert"](x, h, w, idx, at(sz["first"] + e), *held)
        del held
        side = [leaf(l, n) for n in ("shared_gate", "shared_up",
                                     "shared_down")]
        for i in range(sz["n_shared"]):
            x = run["shared"](x, h, *side, at(i))
        return x

    def hidden(toks, tail, blocks):
        """Final hidden rows of the padded sequence toks [S], right from
        row block `tail` on: the last layer's attention is formed for the
        rows whose logits are read and no others (every earlier layer's
        for all: their outputs are the next layer's keys)."""
        x = run["embed"](leaf(-1, "embed"), toks)
        for l, kind in enumerate(kinds):
            c_kv, k_pe = run["latents_" + kind](x, leaf(l, "wkv_a"))
            kept = no_bits
            if kind == "full":
                ki = run["index_keys"](x, leaf(l, "wi_k"))
                kept = run["select"](x, ki, leaf(l, "wq_a"), leaf(l, "wi_q"),
                                     leaf(l, "wi_w"), at(blocks))
                del ki
            last = l == sz["n_layers"] - 1
            x = run["attend_" + kind](x, c_kv, k_pe, kept, *(leaf(l, n) for n in (
                "wq_a", "wq_b", "wkv_b", "w_head_gate", "wo")),
                at(tail if last else 0), at(blocks))
            del kept, c_kv, k_pe
            x = feed_forward(l, x)
        return x

    out = []
    for s in sample:
        seq = s["tokens"] + s["served"]
        if len(seq) > S:
            raise ValueError(f"a context of {len(seq)} tokens is past the "
                             f"reference's {S}")
        toks = np.random.default_rng(len(seq)).integers(
            0, V, S).astype(np.int32)
        toks[:len(seq)] = seq
        n = len(s["served"])
        t0 = time.time()
        pos = np.minimum(len(s["tokens"]) - 1 + np.arange(n_logits), S - 1)
        x = hidden(jnp.asarray(toks), int(pos[0]) // rows,
                   -(-len(seq) // rows))[pos]
        unembed = leaf(-1, "unembed")
        lg = jnp.concatenate([run["readout"](x, unembed, at(i))
                              for i in range(V_PARTS)], -1)
        del unembed
        g, lead = gaps(lg, jnp.asarray(toks[np.minimum(pos + 1, S - 1)]), n)
        g, lead = np.asarray(g)[:n], np.asarray(lead)[:n]
        extra = {}
        if replay is not None and replay[0] == s["rid"]:
            got = replay[2]
            want = lg[replay[1]:replay[1] + got.shape[0]]
            extra = {"logit_rel_rms": float(jnp.sqrt(jnp.mean(
                         (got - want) ** 2)) / jnp.std(want)),
                     "logit_max_abs": float(jnp.abs(got - want).max()),
                     "replayed": int(got.shape[0])}
        out.append({"rid": s["rid"], "context": len(seq),
                    "blocks": -(-len(seq) // rows), **extra,
                    "seconds": time.time() - t0, "programs_s": t_first,
                    "max_gap": float(g.max()), "n": n,
                    "n_argmax": int((g <= 0.0).sum()),
                    "median_top2_gap": float(np.median(lead))})
    return out
