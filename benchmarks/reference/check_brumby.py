"""Holding served tokens of a `brumby` replica to its plain reference
(inside the replica, after the window, on the weights it serves): the
scheme of reference/check.py and check_cohere2_moe.py, for a model whose
reference forms its weights over all pairs of positions."""

from __future__ import annotations

import concurrent.futures
import time
from typing import Dict, List

F_PARTS = 4          # the SwiGLU a quarter of its width at a time
V_PARTS = 8          # the head an eighth of the vocabulary at a time


def replay_logits(eng, prompt: List[int], n: int, keep: int = None):
    """The logits rows the engine's OWN programs form for the first `n`
    tokens after `prompt` — `serve.prefill:<T>` chunk by chunk as the
    engine cuts them, `serve.setrow`, then `serve.step` with slot 0 live
    on entry 1 of the state arena — greedy, so the tokens are the ones a
    request with this prompt was served.  Run while the engine is idle
    (after the window): its arena and logits are taken and handed back.
    Returns (rows [keep, V] float32 on the device: the last `keep` of the n
    tokens', all where None; the n tokens).  Row i of all n is what token
    i was drawn from: the reference's row at position len(prompt) - 1 + i."""
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_device_state()
    kind = eng._state_kinds[0]
    tabs = {k: np.zeros(w, np.int32) for k, w in eng._widths.items()}
    tabs[kind][0] = 1
    plen, start = len(prompt), 0
    while start < plen:
        m = min(eng.prefill_chunk or plen, plen - start)
        T = -(-m // eng.prefill_bucket) * eng.prefill_bucket
        chunk = np.zeros(T, np.int32)
        chunk[:m] = prompt[start:start + m]
        row, eng._cache, _ = eng._fn(("prefill", T))(
            eng._params, eng._cache, chunk, tabs, np.int32(start),
            np.int32(m - 1))
        start += m
    eng._logits = eng._fn("setrow")(eng._logits, row, np.int32(0))
    B = eng.max_slots
    ptabs = {k: np.zeros((B, w), np.int32) for k, w in eng._widths.items()}
    ptabs[kind][0, 0] = 1
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


def served_gaps(params, sample: List[Dict], shape: Dict, rows: int,
                max_context: int, n_logits: int, replay=None) -> List[Dict]:
    """For each {"rid", "tokens" (prompt), "served"}: one teacher-forced
    reference pass (float32, highest precision, the quadratic form: no
    state, no chunks) over prompt + served tokens; per request the largest
    distance of a served token's reference logit below the maximum of its
    position (0 where the served token IS the reference's argmax), how
    many served tokens are that argmax, and the median distance between
    the reference's two largest logits at the served positions.

    The reference runs beside the engine, in the 3 GB the weights and the
    state arena leave, and inside the run's minute.  So it is driven piece
    by piece (`brumby_plain.project` / `retain` / `mix` / `readout`: each
    upcasts only its own weights, the SwiGLU and the head a slice at a
    time) over `rows` rows of the sequence at a time against keys and
    values padded to `max_context`: programs of fixed shapes, compiled once
    a checkout whatever the sample.  Rows past the sequence's end (the
    pad: other tokens each) lie behind every real row, so no real row sees
    them.  Logits are formed for `n_logits` positions from the last prompt
    token on (the longest answer the traffic asks for).

    `replay` = (rid, first, rows [m, V]): the program's own logits rows of
    that entry's tokens first..first+m-1 (`replay_logits`); the entry then
    also carries `logit_rel_rms`, the root mean square of program minus
    reference over those rows as a share of the reference rows' standard
    deviation, and `logit_max_abs`.  A served token says on which side of a
    near-tie the program fell; this says how far apart the two
    computations are."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import brumby_plain as ref

    project = jax.jit(lambda x, i0, *w: ref.project(x, *w, shape, i0))
    retain = jax.jit(lambda q, i0, k, v, c: ref.retain(q, k, v, c, shape,
                                                       i0))
    mix = jax.jit(lambda x, o, *w: ref.mix(x, o, *w, shape))
    readout = jax.jit(lambda x, norm, w: ref.readout(x, norm, w, shape))
    running = jax.jit(lambda lg: jnp.cumsum(lg, axis=0))

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

    V = params["embed"].shape[0]
    n_layers, _, F = params["layers"]["w_gate"].shape
    fs = [(i * F // F_PARTS, (i + 1) * F // F_PARTS) for i in range(F_PARTS)]
    vs = [(i * V // V_PARTS, (i + 1) * V // V_PARTS) for i in range(V_PARTS)]

    def mix_parts(x, o, lp):
        """`ref.mix` with the SwiGLU's three matrices as lists of slices
        (each upcast when its turn comes)."""
        return mix(x, o, lp["wo"], lp["mlp_norm"],
                   [lp["w_gate"][:, a:b] for a, b in fs],
                   [lp["w_up"][:, a:b] for a, b in fs],
                   [lp["w_down"][a:b] for a, b in fs])

    def logits(x):
        return jnp.concatenate(
            [readout(x, params["final_norm"], params["unembed"][:, a:b])
             for a, b in vs], axis=-1)

    def hidden(toks):
        """Final hidden rows of the padded sequence, a block at a time."""
        starts = range(0, toks.shape[0], rows)
        xs = [params["embed"][toks[i:i + rows]].astype(jnp.float32)
              for i in starts]
        fill = max_context - toks.shape[0]
        for l in range(n_layers):
            lp = ref.layer(params, l)       # a layer's slices at a time
            w = (lp["attn_norm"], lp["wq"], lp["wk"], lp["wv"], lp["q_norm"],
                 lp["k_norm"], lp["wg"], lp["bg"])
            qs, ks, vals, lgs = zip(*(project(x, i, *w)
                                      for x, i in zip(xs, starts)))
            pad = lambda parts: jnp.pad(
                jnp.concatenate(parts),
                ((0, fill),) + ((0, 0),) * (parts[0].ndim - 1))
            k, v, c = pad(ks), pad(vals), running(pad(lgs))
            xs = [mix_parts(x, retain(q, i, k, v, c), lp)
                  for x, q, i in zip(xs, qs, starts)]
        return jnp.concatenate(xs)

    # Every program once, side by side, on zeros of its one shape: on a
    # checkout's first run their compiles overlap instead of queueing.
    lp, D = ref.layer(params, 0), params["embed"].shape[1]
    Hkv, dh = lp["wk"].shape[1:]
    x0 = jnp.zeros((rows, D), jnp.float32)
    q0 = jnp.zeros((rows,) + lp["wq"].shape[1:], jnp.float32)
    kv0 = jnp.zeros((max_context, Hkv, dh), jnp.float32)
    c0 = jnp.zeros((max_context, Hkv), jnp.float32)
    first = [lambda: gaps(jnp.zeros((n_logits, V)),
                          jnp.zeros(n_logits, jnp.int32), 0),
             lambda: readout(x0[:1].repeat(n_logits, 0), params["final_norm"],
                             params["unembed"][:, vs[0][0]:vs[0][1]]),
             lambda: project(x0, 0, lp["attn_norm"], lp["wq"], lp["wk"],
                             lp["wv"], lp["q_norm"], lp["k_norm"], lp["wg"],
                             lp["bg"]),
             lambda: running(c0),
             lambda: retain(q0, 0, kv0, kv0, c0),
             lambda: mix_parts(x0, q0, lp)]
    t_first = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(first)) as pool:
        jax.block_until_ready([f.result() for f in
                               [pool.submit(fn) for fn in first]])
    t_first = time.time() - t_first

    out = []
    for s in sample:
        seq = s["tokens"] + s["served"]
        pad = -(-len(seq) // rows) * rows
        if pad > max_context:
            raise ValueError(f"a context of {len(seq)} tokens is past the "
                             f"reference's {max_context}")
        toks = np.random.default_rng(len(seq)).integers(
            0, V, pad).astype(np.int32)
        toks[:len(seq)] = seq
        n = len(s["served"])
        t0 = time.time()
        pos = np.minimum(len(s["tokens"]) - 1 + np.arange(n_logits), pad - 1)
        lg = logits(hidden(jnp.asarray(toks))[pos])
        g, lead = gaps(lg, jnp.asarray(toks[np.minimum(pos + 1, pad - 1)]), n)
        g, lead = np.asarray(g)[:n], np.asarray(lead)[:n]
        extra = {}
        if replay is not None and replay[0] == s["rid"]:
            got = replay[2]
            want = lg[replay[1]:replay[1] + got.shape[0]]
            extra = {"logit_rel_rms": float(jnp.sqrt(jnp.mean(
                         (got - want) ** 2)) / jnp.std(want)),
                     "logit_max_abs": float(jnp.abs(got - want).max()),
                     "replayed": int(got.shape[0])}
        out.append({"rid": s["rid"], "context": len(seq), "padded": pad,
                    **extra,
                    "seconds": time.time() - t0, "programs_s": t_first,
                    "max_gap": float(g.max()), "n": n,
                    "n_argmax": int((g <= 0.0).sum()),
                    "median_top2_gap": float(np.median(lead))})
    return out
