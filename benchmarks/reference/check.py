"""Holding served tokens to the plain reference (used inside the replica,
after the window, on the weights it serves)."""

from __future__ import annotations

from typing import Dict, List


def served_gaps(params, sample: List[Dict], pad: int) -> List[Dict]:
    """For each {"rid", "tokens" (prompt), "served"}: one teacher-forced
    reference pass (float32, highest precision) over prompt + served tokens,
    padded to `pad` so one program serves all; returns per request the
    largest distance of a served token's reference logit below the maximum
    of its position (0 where the served token IS the reference's argmax),
    how many served tokens are that argmax, and the median distance
    between the reference's two largest logits at the served positions —
    how far a rounding would have to move a logit to change the token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import gpt2_plain as ref

    @jax.jit
    def gaps(p, toks, plen, n_out):
        lg = ref.logits(p, toks[None])[0]                      # [pad, V]
        pos = jnp.minimum(plen - 1 + jnp.arange(pad), pad - 1)
        rows = lg[pos]                  # row i predicts served token i
        nxt = toks[jnp.minimum(pos + 1, pad - 1)]
        top = rows.max(-1)
        gap = top - jnp.take_along_axis(rows, nxt[:, None], 1)[:, 0]
        second = jnp.where(jnp.arange(rows.shape[-1]) == rows.argmax(-1)[:, None],
                           -jnp.inf, rows).max(-1)
        return jnp.where(jnp.arange(pad) < n_out, gap, 0.0), top - second

    out = []
    for s in sample:
        seq = (s["tokens"] + s["served"])[:pad]
        toks = np.zeros(pad, np.int32)
        toks[:len(seq)] = seq
        n = len(seq) - len(s["tokens"])
        g, lead = gaps(params, jnp.asarray(toks), len(s["tokens"]), n)
        g, lead = np.asarray(g)[:n], np.asarray(lead)[:n]
        out.append({"rid": s["rid"], "max_gap": float(g.max()), "n": n,
                    "n_argmax": int((g <= 0.0).sum()),
                    "median_top2_gap": float(np.median(lead))})
    return out
