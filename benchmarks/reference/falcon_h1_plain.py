"""Falcon-H1 (`falcon_h1`: attention heads and Mamba-2 heads side by side
in every block, µP multipliers) as its configuration describes it, in
plain jax.numpy and float32 — the yardstick for `correct` of the cells
that serve it.

Written from the layer equations, not from the program; imports nothing
from `ray_tpu`.  The draw's piece generator and the fp8 control are
`deepseek_v3_plain`'s, the leaf drawn from pieces `ling3_plain`'s: all
three are this benchmark's own.  Per layer, n = RMSNorm(h; eps):

    h'  = h + m_ssm_out SSM(m_ssm_in n) + m_attn_out Attn(m_attn_in n)
    h'' = h' + m_mlp1 W_down(W_up x * SiLU(m_mlp0 W_gate x)), x = RMSNorm(h')

    Attn — q, k, v = u W_q, u W_k, u W_v; k <- m_key k; rotate-half RoPE
      (theta) on all dims of q and k; explicit causal softmax over masked
      scores at d_head^-1/2, query head i on key head i // (H / Hkv); W_o.
    SSM — [z | x B C | dt] = (u W_in) * mup (the five ssm_multipliers over
      the segments z, x, B, C, dt);
      (x B C)_t = SiLU(sum_{i=0..3} c_i (x B C)~_{t-3+i} + b_c)
      dt = softplus(dt + dt_bias);  a = -exp(A_log)
      S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t,  y_t = S_t C_t + D x_t
      per head, head i on group i // (Hs / G): the recurrence as written,
      one token after the other under `lax.scan`: no chunk.
      y <- RMSNorm over each group's d_ssm / G channels of (y * SiLU(z)),
      times a weight;  W_out.
    logits = m_lm_head RMSNorm(h_L) W_head;  h_0 = m_emb E[token]

EVERY multiplier stands where the equations put it; nothing is folded.
The weights are this file's OWN draw from the seed (`draw_leaf`): the
recipe the configuration's `weights.made` states, written a second time.

Departures, each forced by what it is compared with: it is computed in
pieces (`mixer_layer`, `mlp_layer`, `readout`: a program each, one
layer's weights at a time) so that it fits beside the engine and inside a
run's minute; rows are taken a block at a time — the state carried from
block to block, attention a block of rows against EVERY key.  Nothing
that enters a sum is left out.

`shape["control"]` names a fault put into THIS computation on purpose
(never in a benchmark run): `fp8_weights` (deepseek_v3_plain's).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import deepseek_v3_plain as dsp
from .deepseek_v3_plain import _f32, _highest
from .ling3_plain import _normal, _uniform

TAPS = 4
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0

# name -> place of a layer's leaf in the draw (the program's LEAVES)
LEAVES = ("w_in", "conv_w", "a_log", "dt_bias", "w_out", "wq", "wk", "wv",
          "wo", "w_gate_up", "w_down")
PLACES = {n: i for i, n in enumerate(LEAVES)}
DT_PLACE, MEMORY_PLACE = len(LEAVES), len(LEAVES) + 1
MIXER_LEAVES = ("w_in", "conv_w", "a_log", "dt_bias", "w_out", "wq", "wk",
                "wv", "wo")
MLP_LEAVES = ("w_gate_up", "w_down")


def d_ssm(sz: dict) -> int:
    return sz["ssm_heads"] * sz["ssm_head_dim"]


def d_conv(sz: dict) -> int:
    return d_ssm(sz) + 2 * sz["n_groups"] * sz["d_state"]


def segments(sz: dict):
    gn = sz["n_groups"] * sz["d_state"]
    return (d_ssm(sz), d_ssm(sz), gn, gn, sz["ssm_heads"])


def over_segments(sz: dict, five):
    """Five numbers (z, x, B, C, dt) spread over in_proj's columns."""
    return jnp.concatenate([jnp.full((n,), m, jnp.float32)
                            for n, m in zip(segments(sz), five)])


def leaf_specs(sz: dict) -> dict:
    """name -> (shape, fan in, scale) of every normally drawn leaf."""
    D, F = sz["d_model"], sz["d_ff"]
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    out = 1.0 / math.sqrt(2 * sz["n_layers"])
    return {
        "w_in": ((D, sum(segments(sz))), D, 1.0),
        "conv_w": ((TAPS, d_conv(sz)), TAPS, 1.0),
        "w_out": ((d_ssm(sz), D), d_ssm(sz), out),
        "wq": ((D, H * dh), D, 1.0), "wk": ((D, Hkv * dh), D, 1.0),
        "wv": ((D, Hkv * dh), D, 1.0), "wo": ((H * dh, D), H * dh, out),
        "w_gate_up": ((D, 2 * F), D, 1.0), "w_down": ((F, D), F, out),
    }


def inv_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


def draw_leaf(seed: int, sz: dict, weights: dict, layer: int, name: str):
    """One leaf as the replica's loader makes it: `layer` -1 holds the two
    vocabulary tables.  The plain draw (the program's `init`), then the
    configuration's `weights`: a leaf named in `scales` times its factor,
    W_in's columns times `in_proj_scales` (z, x, B, C, dt), and — where
    `memory_tokens` is given — dt_bias the inverse of softplus at a step
    size log-uniform in `dt_range` and A_log such that the head forgets
    over a number of tokens log-uniform in `memory_tokens` (both a head,
    float32, from uniform draws at the two places after the last leaf)."""
    seed, pd = seed % (2 ** 31), sz["param_dtype"]
    scales = weights.get("scales", {})
    Hs = sz["ssm_heads"]
    if layer < 0:
        return draw_rows(seed, sz, weights, name)
    if name in ("a_log", "dt_bias"):
        log_uniform = lambda place, lo, hi: jnp.exp(
            _uniform(seed, layer, place, (Hs,))
            * (math.log(hi) - math.log(lo)) + math.log(lo))
        if "memory_tokens" in weights:
            dt = log_uniform(DT_PLACE, *weights["dt_range"])
            if name == "dt_bias":
                return inv_softplus(dt)
            return -jnp.log(dt * log_uniform(MEMORY_PLACE,
                                             *weights["memory_tokens"]))
        if name == "dt_bias":
            return inv_softplus(log_uniform(PLACES[name], DT_MIN, DT_MAX))
        return jnp.log(A_MIN + (A_MAX - A_MIN) * _uniform(
            seed, layer, PLACES[name], (Hs,)))
    shape, fan_in, scale = leaf_specs(sz)[name]
    w = _normal(seed, layer, PLACES[name], shape, scale / math.sqrt(fan_in),
                pd, scales.get(name, 1))
    if name == "w_in" and "in_proj_scales" in weights:
        w = (w.astype(jnp.float32) * over_segments(
            sz, weights["in_proj_scales"])).astype(w.dtype)
    return w


@functools.partial(jax.jit, static_argnames=("count", "n", "dtype"))
def _piece_run(seed, place, std, first, count, n, dtype):
    """Pieces first..first+count-1 of a vocabulary table laid end to end
    (`ling3_plain._pieces` from a piece other than the first)."""
    return jax.lax.map(
        lambda i: dsp._piece(seed, -1, place, first + i, std, n, dtype),
        jnp.arange(count)).reshape(-1)


def draw_rows(seed: int, sz: dict, weights: dict, name: str, i: int = 0,
              parts: int = 1):
    """Slice i of `parts` of the rows of a vocabulary table (`embed`,
    place 0, std 0.02; `lm_head`, place 1, std 1/sqrt(D); both [V, D]) as
    the loader makes it: the pieces of the draw that hold those rows and
    no others — whole, a table is 2.67e9 B at the published sizes, and the
    check runs beside an engine that holds its own."""
    seed, n = seed % (2 ** 31), dsp.DRAW_PIECE
    V, D = sz["vocab"], sz["d_model"]
    std, place = {"embed": (0.02, 0),
                  "lm_head": (1.0 / math.sqrt(D), 1)}[name]
    rows = V // parts
    lo, hi = i * rows * D, (i + 1) * rows * D
    first = lo // n
    flat = _piece_run(seed, place, jnp.float32(std), first,
                      -(-hi // n) - first, n, sz["param_dtype"])
    w = flat[lo - first * n:hi - first * n].reshape(rows, D)
    factor = weights.get("scales", {}).get(name, 1)
    return w * factor if factor != 1 else w


def fixed_leaves(sz: dict, weights: dict) -> dict:
    """What the recipe does not draw: norm weights (ones), the conv's bias
    (zeros) and D (ones, times `scales.d_skip`)."""
    D, f = sz["d_model"], jnp.float32
    return {"norm": jnp.ones(D, f), "mlp_norm": jnp.ones(D, f),
            "conv_b": jnp.zeros(d_conv(sz), f),
            "ssm_norm": jnp.ones(d_ssm(sz), f),
            "d_skip": jnp.ones(sz["ssm_heads"], f)
            * weights.get("scales", {}).get("d_skip", 1)}


def draw(seed: int, sz: dict, weights: dict) -> dict:
    """The whole tree (small sizes: a test)."""
    layers = [{**{n: draw_leaf(seed, sz, weights, l, n)
                  for n in MIXER_LEAVES + MLP_LEAVES},
               **fixed_leaves(sz, weights)} for l in range(sz["n_layers"])]
    return {"embed": draw_leaf(seed, sz, weights, -1, "embed"),
            "lm_head": draw_leaf(seed, sz, weights, -1, "lm_head"),
            "final_norm": jnp.ones(sz["d_model"], jnp.float32),
            "layers": layers}


# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [S, heads, dh] at positions pos [S]: the pairs (x_i, x_{i+dh/2})
    turned by pos * theta^(-2i/dh)."""
    dh = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _token(a, d_skip, per_group):
    """The recurrence's one line: S [Hs, P, N], the token's x [Hs, P], dt
    [Hs], B, C [G, N] -> (S_t, y_t [Hs, P])."""
    def step(S, row):
        x, dt, b, c = row
        b, c = (jnp.repeat(v, per_group, axis=0) for v in (b, c))  # [Hs, N]
        S = (jnp.exp(dt * a)[:, None, None] * S
             + (dt[:, None] * x)[:, :, None] * b[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, c) + d_skip[:, None] * x
    return step


def _cut(n):
    return lambda arr, b, extra=0: jax.lax.dynamic_slice_in_dim(
        arr, b * n, n + extra, 0)


@_highest
def mixer_layer(x, lp, sz, rows: int = 0, blocks=None, stop=None):
    """The sequence's rows x [S, D] (positions 0..S-1) -> x + both
    branches' additions, each computed from the SAME n.  Rows are taken
    `rows` at a time (0: all at once; S a multiple of it), the blocks
    0..`blocks`-1 (may be traced; None: to the end): the others come back
    as they came.  With `stop` (may be traced) -> (that, the SSM state
    [Hs, P, N] as it stands when positions 0..`stop`-1 have gone in: zeros
    where `stop` lies in no block taken)."""
    m = sz["multipliers"]
    Hs, P, G, N = (sz["ssm_heads"], sz["ssm_head_dim"], sz["n_groups"],
                   sz["d_state"])
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    S = x.shape[0]
    n = rows or S
    if blocks is None:
        blocks = S // n
    w_in, conv_w, w_out, wq, wk, wv, wo = _f32(sz, *(lp[k] for k in (
        "w_in", "conv_w", "w_out", "wq", "wk", "wv", "wo")))
    f = lambda k: lp[k].astype(jnp.float32)
    h = rms_norm(x, f("norm"), sz["eps"])
    cut = _cut(n)

    # -- the SSM branch: projections of the whole sequence, then token by
    # -- token
    ds, dc = d_ssm(sz), d_conv(sz)
    proj = ((m["ssm_in"] * h) @ w_in) * over_segments(sz, m["ssm"])
    z, dt_raw = proj[:, :ds], proj[:, ds + dc:]
    pre = jnp.pad(proj[:, ds:ds + dc], ((TAPS - 1, 0), (0, 0)))
    a = -jnp.exp(f("a_log"))
    token = _token(a, f("d_skip"), Hs // G)

    def ssm_block(b, carry):
        Sh, acc, kept = carry
        ext = cut(pre, b, TAPS - 1)
        u = jax.nn.silu(f("conv_b") + sum(conv_w[i] * ext[i:i + n]
                                          for i in range(TAPS)))
        xs = u[:, :ds].reshape(n, Hs, P)
        bm = u[:, ds:ds + G * N].reshape(n, G, N)
        cm = u[:, ds + G * N:].reshape(n, G, N)
        dt = jax.nn.softplus(cut(dt_raw, b) + f("dt_bias"))
        if stop is not None:
            # the block that holds position `stop`-1 once more, the tokens
            # from `stop` on changed to ones that leave the state alone
            live = b * n + jnp.arange(n) < stop
            kept = jax.lax.cond(
                (b * n < stop) & (stop <= b * n + n),
                lambda: jax.lax.scan(token, Sh, (
                    xs, jnp.where(live[:, None], dt, 0.0), bm, cm))[0],
                lambda: kept)
        Sh, y = jax.lax.scan(token, Sh, (xs, dt, bm, cm))
        g = y.reshape(n, ds) * jax.nn.silu(cut(z, b))
        g = g.reshape(n, G, ds // G)
        g = g / jnp.sqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                         + sz["eps"])
        out = m["ssm_out"] * ((g.reshape(n, ds) * f("ssm_norm")) @ w_out)
        return Sh, jax.lax.dynamic_update_slice_in_dim(
            acc, cut(acc, b) + out, b * n, 0), kept

    zero = jnp.zeros((Hs, P, N), jnp.float32)
    _, ssm, kept = jax.lax.fori_loop(0, blocks, ssm_block,
                                     (zero, jnp.zeros_like(x), zero))

    # -- the attention branch: every key, a block of query rows at a time
    at = jnp.arange(S)
    u = m["attention_in"] * h
    q = rope((u @ wq).reshape(S, H, dh), at, sz["theta"])
    k = rope((m["key"] * (u @ wk)).reshape(S, Hkv, dh), at, sz["theta"])
    v = (u @ wv).reshape(S, Hkv, dh)
    q = q.reshape(S, Hkv, H // Hkv, dh)

    def attn_block(b, acc):
        rows_at = (b * n + jnp.arange(n))[:, None]
        see = at[None, :] <= rows_at
        s = jnp.einsum("njgd,sjd->jgns", cut(q, b), k) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("jgns,sjd->njgd", p, v).reshape(n, H * dh)
        return jax.lax.dynamic_update_slice_in_dim(
            acc, cut(acc, b) + m["attention_out"] * (o @ wo), b * n, 0)

    attn = jax.lax.fori_loop(0, blocks, attn_block, jnp.zeros_like(x))
    out = x + ssm + attn
    return out if stop is None else (out, kept)


@_highest
def mlp_layer(x, lp, sz, rows: int = 0, blocks=None):
    """x + the SwiGLU: one fused gate-and-up matrix, gate first, the
    gate's product times mlp_multipliers[0], the result times [1]."""
    m = sz["multipliers"]["mlp"]
    S, F = x.shape[0], sz["d_ff"]
    n = rows or S
    if blocks is None:
        blocks = S // n
    wgu, wd = _f32(sz, lp["w_gate_up"], lp["w_down"])
    cut = _cut(n)

    def block(b, acc):
        h = rms_norm(cut(acc, b), lp["mlp_norm"].astype(jnp.float32),
                     sz["eps"])
        gu = h @ wgu
        y = m[1] * ((gu[:, F:] * jax.nn.silu(m[0] * gu[:, :F])) @ wd)
        return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + y,
                                                   b * n, 0)

    return jax.lax.fori_loop(0, blocks, block, x)


@_highest
def readout(x, final_norm, lm_head, sz):
    """x [n, D] -> logits [n, rows of `lm_head`]: the head [V, D], or a
    slice of its rows (of the vocabulary)."""
    table, = _f32(sz, lm_head)
    return sz["multipliers"]["lm_head"] * (
        rms_norm(x, final_norm.astype(jnp.float32), sz["eps"]) @ table.T)


def logits(params, tokens, sz):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence)."""
    x = sz["multipliers"]["embedding"] * params["embed"][tokens].astype(
        jnp.float32)
    for lp in params["layers"]:
        x = mlp_layer(mixer_layer(x, lp, sz), lp, sz)
    return readout(x, params["final_norm"], params["lm_head"], sz)
