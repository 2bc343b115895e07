"""Holding served tokens of a `cohere2_moe` replica to its plain reference
(inside the replica, after the window, on the weights it serves): the
scheme of reference/check.py, for a model whose reference takes a `shape`
and is computed in query blocks."""

from __future__ import annotations

import concurrent.futures
import time
from typing import Dict, List


def served_gaps(params, sample: List[Dict], shape: Dict, q_block: int,
                rows: int, max_context: int, n_logits: int) -> List[Dict]:
    """For each {"rid", "tokens" (prompt), "served"}: one teacher-forced
    reference pass (float32, highest precision) over prompt + served
    tokens; per request the largest distance of a served token's reference
    logit below the maximum of its position (0 where the served token IS
    the reference's argmax), how many served tokens are that argmax, and
    the median distance between the reference's two largest logits at the
    served positions.

    The reference runs beside the engine, in the 5 GB the weights and the
    arenas leave, and inside the run's minute; at the highest precision a
    program of it compiles in 4-8 s.  So it is driven piece by piece
    (`cohere2_moe_plain.keys_values` / `attend` / `route` / `expert` /
    `shared_expert` / `readout`: each upcasts only its own weights) over
    `rows` rows of the sequence at a time against keys and values padded
    to `max_context`: nine programs of fixed shapes, compiled once a
    checkout whatever the sample, and work that grows with the request's
    own length.  Rows past the sequence's end (the last block's pad: other
    tokens each, not one repeated, whose rows would all fall on the same
    eight experts) lie behind every real row, so no real row sees them.
    Logits are formed for `n_logits` positions from the last prompt token
    on (the longest answer the traffic asks for).  An expert's rows are
    gathered up to four times its even share of a block (top_k / n_experts
    of its rows); a hotter expert is computed over every row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import cohere2_moe_plain as ref

    n_experts = params["layers"][0]["router"].shape[1]
    cap = -(-4 * rows * shape["top_k"] // n_experts)
    kv = jax.jit(lambda x, i0, *w, kind: ref.keys_values(
        x, *w, kind, shape, i0), static_argnames="kind")
    attend = jax.jit(lambda x, i0, k, v, *w, kind: ref.attend(
        x, k, v, *w, kind, shape, q_block, i0), static_argnames="kind")
    route = jax.jit(ref.route, static_argnames="top_k")
    expert = jax.jit(ref.expert, static_argnames="cap")
    shared, readout = jax.jit(ref.shared_expert), jax.jit(ref.readout)

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

    def feed_forward(h, lp):
        w, idx = route(h, lp["router"], top_k=shape["top_k"])
        y = jnp.zeros_like(h)
        for e in range(lp["wg"].shape[0]):
            y = y + expert(h, w, idx, shape["first"] + e, lp["wg"][e],
                           lp["wu"][e], lp["wd"][e], cap=cap)
        n = shape["n_shared"]
        F = lp["shared_gate"].shape[1] // n
        for i in range(n):
            y = y + shared(h, lp["shared_gate"][:, i * F:(i + 1) * F],
                           lp["shared_up"][:, i * F:(i + 1) * F],
                           lp["shared_down"][i * F:(i + 1) * F]) / n
        return y

    def hidden(toks):
        """Final hidden rows of the padded sequence, a block at a time."""
        starts = range(0, toks.shape[0], rows)
        xs = [params["embed"][toks[i:i + rows]].astype(jnp.float32)
              for i in starts]
        fill = max_context - toks.shape[0]
        for lp, kind in zip(params["layers"], shape["layer_types"]):
            norm = lp["attn_norm"]
            ks, vs = zip(*(kv(x, i, norm, lp["wk"], lp["wv"], kind=kind)
                           for x, i in zip(xs, starts)))
            k, v = (jnp.pad(jnp.concatenate(a), ((0, fill), (0, 0), (0, 0)))
                    for a in (ks, vs))
            out = []
            for x, i in zip(xs, starts):
                a, h = attend(x, i, k, v, norm, lp["wq"], lp["wo"], kind=kind)
                out.append(x + a + feed_forward(h, lp))
            xs = out
        return jnp.concatenate(xs)

    # Every program once, side by side, on zeros of its one shape: on a
    # checkout's first run their nine compiles (4-8 s each at the highest
    # precision) overlap instead of queueing behind one another; warm, it
    # is nine loads from the cache and a second of the chip.
    lp, D = params["layers"][0], params["embed"].shape[1]
    x0 = jnp.zeros((rows, D), jnp.float32)
    kv0 = jnp.zeros((max_context,) + lp["wk"].shape[1:], jnp.float32)
    w0, idx0 = (jnp.zeros((rows, shape["top_k"]), t)
                for t in (jnp.float32, jnp.int32))
    F = lp["shared_gate"].shape[1] // shape["n_shared"]
    first = [lambda: gaps(jnp.zeros((n_logits, params["embed"].shape[0])),
                          jnp.zeros(n_logits, jnp.int32), 0),
             lambda: readout(x0[:1].repeat(n_logits, 0), params["final_norm"],
                             params["embed"], shape["logit_scale"]),
             lambda: route(x0, lp["router"], top_k=shape["top_k"]),
             lambda: expert(x0, w0, idx0, 0, lp["wg"][0], lp["wu"][0],
                            lp["wd"][0], cap=cap),
             lambda: shared(x0, lp["shared_gate"][:, :F],
                            lp["shared_up"][:, :F], lp["shared_down"][:F])]
    for kind in sorted(set(shape["layer_types"])):
        first += [lambda kind=kind: kv(x0, 0, lp["attn_norm"], lp["wk"],
                                       lp["wv"], kind=kind),
                  lambda kind=kind: attend(x0, 0, kv0, kv0, lp["attn_norm"],
                                           lp["wq"], lp["wo"], kind=kind)]
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
            0, params["embed"].shape[0], pad).astype(np.int32)
        toks[:len(seq)] = seq
        n = len(s["served"])
        t0 = time.time()
        pos = np.minimum(len(s["tokens"]) - 1 + np.arange(n_logits), pad - 1)
        lg = readout(hidden(jnp.asarray(toks))[pos], params["final_norm"],
                     params["embed"], shape["logit_scale"])
        g, lead = gaps(lg, jnp.asarray(toks[np.minimum(pos + 1, pad - 1)]), n)
        g, lead = np.asarray(g)[:n], np.asarray(lead)[:n]
        out.append({"rid": s["rid"], "context": len(seq), "padded": pad,
                    "seconds": time.time() - t0, "programs_s": t_first,
                    "max_gap": float(g.max()), "n": n,
                    "n_argmax": int((g <= 0.0).sum()),
                    "median_top2_gap": float(np.median(lead))})
    return out
