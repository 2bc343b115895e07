"""Holding served tokens of a `deepseek_v3` replica to its plain reference
(inside the replica, after the window): the scheme of check_brumby.py, for
a reference that DRAWS ITS OWN WEIGHTS from the seed — a leaf at a time,
when a layer's turn comes, so that it fits beside the engine — and forms
every head's keys and values from the sequence's latents, as published."""

from __future__ import annotations

import concurrent.futures
import time
from typing import Dict, List

F_PARTS = 8          # the dense SwiGLU an eighth of its width at a time
V_PARTS = 4          # the head a quarter of the vocabulary at a time


def replay_logits(eng, prompt: List[int], n: int, keep: int = None):
    """The logits rows the engine's OWN programs form for the first `n`
    tokens after `prompt` — `serve.prefill:<T>` chunk by chunk as the
    engine cuts them (every chunk but the first reads the latents earlier
    chunks left in its pages), `serve.setrow`, then `serve.step` with slot
    0 live on pages 1.. of the pool — greedy, so the tokens are the ones a
    request with this prompt was served.  Run while the engine is idle
    (after the window): its arena and logits are taken and handed back.
    Returns (rows [keep, V] float32 on the device: the last `keep` of the n
    tokens', all where None; the n tokens).  Row i of all n is what token
    i was drawn from: the reference's row at position len(prompt) - 1 + i."""
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_device_state()
    kind = eng._main
    plen = len(prompt)
    need = -(-(plen + n) // eng.page_size)
    tab = np.zeros(eng._widths[kind], np.int32)
    tab[:need] = np.arange(1, need + 1)
    start = 0
    while start < plen:
        m = min(eng.prefill_chunk or plen, plen - start)
        T = -(-m // eng.prefill_bucket) * eng.prefill_bucket
        chunk = np.zeros(T, np.int32)
        chunk[:m] = prompt[start:start + m]
        row, eng._cache, _ = eng._fn(("prefill", T))(
            eng._params, eng._cache, chunk, {kind: tab.copy()},
            np.int32(start), np.int32(m - 1))
        start += m
    eng._logits = eng._fn("setrow")(eng._logits, row, np.int32(0))
    B = eng.max_slots
    ptabs = {kind: np.zeros((B, tab.shape[0]), np.int32)}
    ptabs[kind][0] = tab
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


def join_replay(sample: List[Dict], toks: List[int], first: int, rows):
    """The sample with the replay of its FIRST entry's prompt joined in:
    (entries, `served_gaps`'s `replay`).  Where the replayed tokens are the
    ones that entry was served — every sound run so far — the entry's own
    reference pass yields the rows the replayed logits are held to, and no
    second pass over the longest context is made; where they are not, the
    replay is an entry of its own."""
    head = sample[0]
    if toks == head["served"][:len(toks)]:
        return sample, (head["rid"], first, rows)
    again = {"rid": "replay", "tokens": head["tokens"], "served": toks}
    return sample + [again], ("replay", first, rows)


def served_gaps(seed: int, sz: Dict, weights: Dict, sample: List[Dict],
                spec: Dict, n_logits: int, replay=None) -> List[Dict]:
    """For each {"rid", "tokens" (prompt), "served"}: one teacher-forced
    reference pass (float32, highest precision, the published form: no
    cache, no absorption) over prompt + served tokens; per request the
    largest distance of a served token's reference logit below the maximum
    of its position (0 where the served token IS the reference's argmax),
    how many served tokens are that argmax, and the median distance
    between the reference's two largest logits at the served positions.

    The weights are the reference's own draw from `seed` by the
    configuration's recipe (`deepseek_v3_plain.draw_leaf`: `sz` its sizes,
    `weights` the configuration's `weights`): a layer's leaves are drawn
    when its turn comes and dropped after it, so the reference holds one
    layer's weights at a time beside the engine's 12.4 GB.

    It is driven piece by piece (`deepseek_v3_plain.latents` / `attend` /
    `dense_part` / `route` / `expert` / `shared_expert` / `readout`: each
    upcasts only its own weights, the dense SwiGLU and the head a slice at
    a time) over the sequence padded to `spec["max_context"]` rows:
    programs of ONE shape each, compiled once a checkout whatever the
    sample.  The pad rows (other tokens each) lie behind every real row,
    so no real row sees them; attention — where the work grows with the
    square of the length — walks `spec["rows"]` rows at a time,
    `spec["heads"]` heads at a time, over the sequence's own blocks only.
    Logits are formed for `n_logits` positions from the last prompt token
    on.

    `replay` = (rid, first, rows [m, V]): the program's own logits rows of
    that entry's tokens first..first+m-1 (`replay_logits`); the entry then
    also carries `logit_rel_rms`, the root mean square of program minus
    reference over those rows as a share of the reference rows' standard
    deviation, and `logit_max_abs`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import deepseek_v3_plain as ref

    rows, S = int(spec["rows"]), int(spec["max_context"])
    heads = int(spec["heads"])
    cap = -(-4 * S * sz["top_k"] // sz["n_experts"])
    leaf = lambda l, name: ref.draw_leaf(seed, sz, weights, l, name)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    D, V, F = sz["d_model"], sz["vocab"], sz["d_ff"]
    assert F % F_PARTS == 0 and V % V_PARTS == 0 and S % rows == 0

    # Every program is compiled for its ONE shape before any runs, side by
    # side: on a checkout's first run the compiles overlap instead of
    # queueing (nothing is executed for it: each piece alone takes 1-3 GB
    # beside the engine).  x comes back where it stood (donated).
    f32, i32, pd = jnp.float32, jnp.int32, ref._dtype(sz["param_dtype"])
    sh = jax.ShapeDtypeStruct
    x_, n_ = sh((S, D), f32), sh((), i32)
    w_ = lambda name: sh(ref.leaf_specs(sz)[name][0], pd)
    programs = {
        "embed": (lambda table, toks: table[toks].astype(f32),
                  (sh((V, D), pd), sh((S,), i32)), {}),
        "latents": (lambda x, wkv_a: ref.latents(
            x, ones(D), wkv_a, ones(sz["kv_rank"]), sz),
            (x_, w_("wkv_a")), {}),
        "attend": (lambda x, c_kv, k_pe, wq_a, wq_b, wkv_b, wo, first, blocks:
                   ref.attend(x, c_kv, k_pe, ones(D), wq_a,
                              ones(sz["q_rank"]), wq_b, wkv_b, wo, sz, heads,
                              rows, blocks, first),
                   (x_, sh((S, sz["kv_rank"]), f32),
                    sh((S, sz["d_rope"]), f32), w_("wq_a"), w_("wq_b"),
                    w_("wkv_b"), w_("wo"), n_, n_), dict(donate_argnums=0)),
        "normed": (lambda x: ref.normed(x, ones(D), sz), (x_,), {}),
        "readout": (lambda x, w, i: ref.readout(x, ones(D), w, sz, i,
                                                V_PARTS),
                    (sh((n_logits, D), f32), sh((D, V), pd), n_), {}),
    }
    if sz["n_dense"]:
        programs["dense"] = (
            lambda x, h, wg, wu, wd, i: x + ref.dense_part(
                h, wg, wu, wd, sz, i, F_PARTS),
            (x_, x_, w_("w_gate"), w_("w_up"), w_("w_down"), n_),
            dict(donate_argnums=0))
    if sz["n_dense"] < sz["n_layers"]:
        E, k = sz["n_experts"], sz["top_k"]
        programs.update(
            route=(lambda h, r, b: ref.route(h, r, b, sz),
                   (x_, sh((D, E), f32), sh((E,), f32)), {}),
            expert=(lambda x, h, w, idx, e, wg, wu, wd: x + ref.expert(
                h, w, idx, e, wg, wu, wd, sz, cap),
                (x_, x_, sh((S, k), f32), sh((S, k), i32), n_, w_("wg"),
                 w_("wu"), w_("wd")), dict(donate_argnums=0)),
            shared=(lambda x, h, wg, wu, wd, i: x + ref.shared_expert(
                h, wg, wu, wd, sz, i),
                (x_, x_, w_("shared_gate"), w_("shared_up"),
                 w_("shared_down"), n_), dict(donate_argnums=0)))
    t_first = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(programs)) as pool:
        built = {name: pool.submit(
            lambda f, a, kw: jax.jit(f, **kw).lower(*a).compile(), *p)
            for name, p in programs.items()}
        run = {name: f.result() for name, f in built.items()}
    t_first = time.time() - t_first
    at = np.int32                       # a piece's index, as compiled

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
        for l in range(sz["n_layers"]):
            c_kv, k_pe = run["latents"](x, leaf(l, "wkv_a"))
            last = l == sz["n_layers"] - 1
            x = run["attend"](x, c_kv, k_pe, *(leaf(l, n) for n in (
                "wq_a", "wq_b", "wkv_b", "wo")), at(tail if last else 0),
                at(blocks))
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
