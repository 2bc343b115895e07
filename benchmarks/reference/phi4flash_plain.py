"""Phi-4-mini-flash (`phi4flash`: SambaY, arXiv 2507.06607, with
Differential Attention, arXiv 2410.05258, and Mamba-1, arXiv 2312.00752)
as its configuration and the papers describe it, in plain jax.numpy and
float32 — the yardstick for `correct` of the cells that serve it.

Written from the layer equations, not from the program; imports nothing
from `ray_tpu`.  The draw's piece generator and the fp8 control are
`deepseek_v3_plain`'s, the leaf drawn from pieces `ling3_plain`'s: all
three are this benchmark's own.  Per layer l of L (LayerNorm with weight
and bias, eps 1e-5; no position signal):

    x = x + mixer_l(LN(x));   x = x + (W_up x' * SiLU(W_gate x')) W_down

    kind(l): l <= L/2 + 1: `mamba` where l even, `swa` where l odd and
    l < L/2, `full` at l = L/2 + 1;  above: `gmu` where l even, `cross`
    where l odd.

    mamba — C = 2 D channels, N = 16 states, one token after the other:
        u~ | z = n W_in
        u_t  = SiLU(sum_{i=0..3} c_i u~_{t-3+i} + b_c)    four shifted rows
        r | B | C = u W_x;   d = softplus(r W_dt + b_dt)
        h_t  = exp(d_t (x) A) * h_{t-1} + (d_t u_t) (x) B_t    [C, N]
        y_t  = h_t C_t + D u_t;   out = (y * SiLU(z)) W_out
      the recurrence as written, under `lax.scan`: no chunk.  Layer L/2's
      y is the memory m.
    gmu — out = (m * SiLU(n W_1)) W_2, m of the same position.
    swa | full | cross — differential attention over every position, the
      unseen ones masked (causal; a band of `window` where `swa`): query
      heads (2p, 2p+1) = (q1_p, q2_p), key heads (2j, 2j+1) = (k1_j, k2_j),
      value heads (2j, 2j+1) side by side = V_j, j = p // (H / Hkv):
        a1 = softmax(q1 K1^T / sqrt(dh)) V,  a2 = softmax(q2 K2^T / sqrt(dh)) V
        lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 l)
        o_p = (1 - lam0) RMSNorm(a1 - lam a2);   y = [o_p] W_o + b_o
      two explicit softmaxes.  `cross` has W_q and W_o only and reads the
      K, V layer L/2 + 1 formed.
    logits = LN(x_L) E^T                                    (tied head)

The weights are this file's OWN draw from the seed (`draw_leaf`): the
recipe the configuration's `weights.made` states, written a second time.

Departures, each forced by what it is compared with: it is computed in
pieces (`mamba_layer`, `attn_layer`, `gmu_layer`, `mlp_layer`, `readout`:
a program each, one layer's weights at a time) so that it fits beside the
engine and inside a run's minute: a block of rows at a time — a Mamba
layer's projections, conv and gates of the block, then its rows through
the recurrence one by one, the state carried from block to block;
attention a block of rows at a time against EVERY key.  Nothing that
enters a sum is left out.

`shape["control"]` names a fault put into THIS computation on purpose
(never in a benchmark run): `fp8_weights` (deepseek_v3_plain's).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .deepseek_v3_plain import _f32, _highest
from .ling3_plain import _normal, _uniform

TAPS = 4
DT_MIN, DT_MAX = 1e-3, 1e-1
LAMBDA_STD = 0.1

# name -> place of a layer's leaf in the draw (the program's LEAVES)
PLACES = {n: i for i, n in enumerate(
    ("w_in", "conv_w", "w_x", "w_dt", "b_dt", "w_out", "w_qkv", "wq", "wo",
     "lam_q1", "lam_k1", "lam_q2", "lam_k2", "w1", "w2", "w_gate_up",
     "w_down"))}
LAMBDAS = ("lam_q1", "lam_k1", "lam_q2", "lam_k2")
KIND_LEAVES = {
    "mamba": ("w_in", "conv_w", "w_x", "w_dt", "b_dt", "w_out"),
    "swa": ("w_qkv", "wo") + LAMBDAS, "full": ("w_qkv", "wo") + LAMBDAS,
    "cross": ("wq", "wo") + LAMBDAS, "gmu": ("w1", "w2")}
MLP_LEAVES = ("w_gate_up", "w_down")


def kind(sz: dict, l: int) -> str:
    half = sz["n_layers"] // 2
    recurrent = l % sz["mb_per_layer"] == 0
    if l <= half + 1:
        return ("mamba" if recurrent else "full" if l == half + 1 else "swa")
    return "gmu" if recurrent else "cross"


def lam0(l):
    """The layer's lambda_init; `l` may be traced (one program a kind of
    layer in check_phi4flash)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


def d_inner(sz: dict) -> int:
    return sz["expand"] * sz["d_model"]


def leaf_specs(sz: dict) -> dict:
    """name -> (shape, fan in, scale) of every normally drawn leaf."""
    D, C, N, R, F = (sz["d_model"], d_inner(sz), sz["d_state"], sz["dt_rank"],
                     sz["d_ff"])
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    out = 1.0 / math.sqrt(2 * sz["n_layers"])
    return {
        "w_in": ((D, 2 * C), D, 1.0), "conv_w": ((TAPS, C), TAPS, 1.0),
        "w_x": ((C, R + 2 * N), C, 1.0), "w_dt": ((R, C), R, 1.0),
        "w_out": ((C, D), C, out),
        "w_qkv": ((D, (H + 2 * Hkv) * dh), D, 1.0), "wq": ((D, H * dh), D, 1.0),
        "wo": ((H * dh, D), H * dh, out),
        "w1": ((D, C), D, 1.0), "w2": ((C, D), C, out),
        "w_gate_up": ((D, 2 * F), D, 1.0), "w_down": ((F, D), F, out),
    }


def draw_leaf(seed: int, sz: dict, layer: int, name: str):
    """One leaf as the program's `init` makes it: `layer` -1 holds the
    (tied) vocabulary table; `b_dt` is the inverse of softplus at a step
    size log-uniform in [DT_MIN, DT_MAX], the lambdas normal x
    LAMBDA_STD, both float32."""
    seed, pd = seed % (2 ** 31), sz["param_dtype"]
    if layer < 0:
        return _normal(seed, -1, 0, (sz["vocab"], sz["d_model"]), 0.02, pd, 1)
    if name == "b_dt":
        dt = jnp.exp(_uniform(seed, layer, PLACES[name], (d_inner(sz),))
                     * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in LAMBDAS:
        return _normal(seed, layer, PLACES[name], (sz["d_head"],),
                       LAMBDA_STD, "float32", 1)
    shape, fan_in, scale = leaf_specs(sz)[name]
    return _normal(seed, layer, PLACES[name], shape,
                   scale / math.sqrt(fan_in), pd, 1)


def fixed_leaves(sz: dict, k: str) -> dict:
    """What the recipe does not draw: norms (ones, zeros), biases (zeros)
    and, of a Mamba layer, A = -(1..N) along the states and D = 1."""
    D, C, N = sz["d_model"], d_inner(sz), sz["d_state"]
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    f = jnp.float32
    out = {"attn_norm": jnp.ones(D, f), "attn_norm_b": jnp.zeros(D, f),
           "mlp_norm": jnp.ones(D, f), "mlp_norm_b": jnp.zeros(D, f)}
    if k == "mamba":
        out.update(conv_b=jnp.zeros(C, f), d_skip=jnp.ones(C, f),
                   a=-jnp.broadcast_to(jnp.arange(1, N + 1, dtype=f), (C, N)))
    elif k != "gmu":
        out.update(wo_b=jnp.zeros(D, f), sub_norm=jnp.ones(2 * dh, f))
        if k == "cross":
            out["wq_b"] = jnp.zeros(H * dh, f)
        else:
            out["w_qkv_b"] = jnp.zeros((H + 2 * Hkv) * dh, f)
    return out


def draw(seed: int, sz: dict) -> dict:
    """The whole tree (small sizes: a test)."""
    layers = []
    for l in range(sz["n_layers"]):
        k = kind(sz, l)
        lp = {n: draw_leaf(seed, sz, l, n)
              for n in KIND_LEAVES[k] + MLP_LEAVES}
        layers.append({**lp, **fixed_leaves(sz, k)})
    D = sz["d_model"]
    return {"embed": draw_leaf(seed, sz, -1, "embed"),
            "final_norm": jnp.ones(D, jnp.float32),
            "final_norm_b": jnp.zeros(D, jnp.float32), "layers": layers}


# ---------------------------------------------------------------------------


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _token(a):
    """The recurrence's one line for the layer's A [C, N]: h [C, N], the
    token's u, d [C], B, C [N] -> (h_t, h_t C_t [C])."""
    def step(h, row):
        u, d, b, c = row
        h = jnp.exp(d[:, None] * a) * h + (d * u)[:, None] * b[None, :]
        return h, h @ c
    return step


def _cut(n):
    return lambda arr, b, extra=0: jax.lax.dynamic_slice_in_dim(
        arr, b * n, n + extra, 0)


@_highest
def mamba_layer(x, lp, sz, rows: int = 0, blocks=None, stop=None):
    """The sequence's rows x [S, D] (positions 0..S-1) -> (x + the Mamba
    mixer's addition, y [S, C] — the scan's output with the skip, before
    the gate: the memory where this is layer L/2).  Rows are taken `rows`
    at a time (0: all at once; S a multiple of it), the blocks
    0..`blocks`-1 (may be traced; None: to the end): the others come back
    as they came.  With `stop` (may be traced) -> (those, the state [C, N]
    as it stands when positions 0..`stop`-1 have gone in: zeros where
    `stop` lies in no block taken)."""
    C, N, R = d_inner(sz), sz["d_state"], sz["dt_rank"]
    S = x.shape[0]
    n = rows or S
    if blocks is None:
        blocks = S // n
    w_in, conv_w, w_x, w_dt, w_out = _f32(
        sz, *(lp[k] for k in ("w_in", "conv_w", "w_x", "w_dt", "w_out")))
    f = lambda k: lp[k].astype(jnp.float32)
    conv_b, b_dt, a, d_skip = f("conv_b"), f("b_dt"), f("a"), f("d_skip")
    h = layer_norm(x, f("attn_norm"), f("attn_norm_b"), sz["eps"])
    # the pre-conv rows of the whole sequence, three rows of zeros before
    pre = jnp.pad(h @ w_in[:, :C], ((TAPS - 1, 0), (0, 0)))
    cut, token = _cut(n), _token(a)

    def block(b, carry):
        hs, acc, mem, kept = carry
        ext, hb = cut(pre, b, TAPS - 1), cut(h, b)
        u = jax.nn.silu(conv_b + sum(conv_w[i] * ext[i:i + n]
                                     for i in range(TAPS)))
        proj = u @ w_x
        bm, cm = proj[:, R:R + N], proj[:, R + N:]
        d = jax.nn.softplus(proj[:, :R] @ w_dt + b_dt)
        if stop is not None:
            # the block that holds position `stop`-1 once more, the tokens
            # from `stop` on changed to ones that leave the state alone
            live = b * n + jnp.arange(n) < stop
            kept = jax.lax.cond(
                (b * n < stop) & (stop <= b * n + n),
                lambda: jax.lax.scan(token, hs, (
                    u, jnp.where(live[:, None], d, 0.0), bm, cm))[0],
                lambda: kept)
        hs, y = jax.lax.scan(token, hs, (u, d, bm, cm))
        y = y + d_skip * u
        out = (y * jax.nn.silu(hb @ w_in[:, C:])) @ w_out
        put = lambda arr, new: jax.lax.dynamic_update_slice_in_dim(
            arr, new, b * n, 0)
        return hs, put(acc, cut(acc, b) + out), put(mem, y), kept

    zero = jnp.zeros((C, N), jnp.float32)
    _, out, mem, kept = jax.lax.fori_loop(
        0, blocks, block, (zero, x, jnp.zeros((S, C), jnp.float32), zero))
    return (out, mem) if stop is None else (out, mem, kept)


def _lambda(lp, l: int):
    f = lambda k: lp[k].astype(jnp.float32)
    return (jnp.exp(jnp.sum(f("lam_q1") * f("lam_k1")))
            - jnp.exp(jnp.sum(f("lam_q2") * f("lam_k2"))) + lam0(l))


@_highest
def keys_values(x, lp, sz):
    """What the rows x [S, D] of an `swa` / `full` layer give every later
    query: (K, V [S, Hkv, dh])."""
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    w, = _f32(sz, lp["w_qkv"])
    f = lambda k: lp[k].astype(jnp.float32)
    h = layer_norm(x, f("attn_norm"), f("attn_norm_b"), sz["eps"])
    kv = h @ w[:, H * dh:] + f("w_qkv_b")[H * dh:]
    S = x.shape[0]
    return (kv[:, :Hkv * dh].reshape(S, Hkv, dh),
            kv[:, Hkv * dh:].reshape(S, Hkv, dh))

@_highest
def attend(x, k, v, lp, sz, l: int, window=None, rows: int = 0, blocks=None,
           first=0):
    """The sequence's rows x [S, D] against keys and values k, v [S, Hkv,
    dh] -> x + the differential-attention mixer's addition.  Rows are
    taken `rows` at a time (0: all; S a multiple of it), the blocks
    `first`..`blocks`-1 only, each against EVERY key with the ones it
    cannot see masked (later ones; with `window`, those `window` or more
    behind)."""
    H, Hkv, dh = sz["n_heads"], sz["n_kv_heads"], sz["d_head"]
    J, G = Hkv // 2, H // Hkv
    S = x.shape[0]
    n = rows or S
    if blocks is None:
        blocks = S // n
    name = "wq" if "wq" in lp else "w_qkv"
    wq, wo = _f32(sz, lp[name][:, :H * dh], lp["wo"])
    f = lambda key: lp[key].astype(jnp.float32)
    h = layer_norm(x, f("attn_norm"), f("attn_norm_b"), sz["eps"])
    q = (h @ wq + f(name + "_b")[:H * dh]).reshape(S, J, G, 2, dh)
    k1, k2 = k[:, 0::2], k[:, 1::2]                       # [S, J, dh]
    v2 = v.reshape(S, J, 2 * dh)                          # a pair side by side
    lam, key_at, cut = _lambda(lp, l), jnp.arange(S), _cut(n)

    def row_block(b, acc):
        at = (b * n + jnp.arange(n))[:, None]
        see = key_at[None, :] <= at
        if window is not None:
            see = see & (at - key_at[None, :] < window)

        def softmax_v(qb, kk):
            s = jnp.einsum("njgd,sjd->jgns", qb, kk) * dh ** -0.5
            p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), -1)
            return jnp.einsum("jgns,sjd->njgd", p, v2)

        qb = cut(q, b)
        a1 = softmax_v(qb[:, :, :, 0], k1)
        a2 = softmax_v(qb[:, :, :, 1], k2)
        o = (1.0 - lam0(l)) * rms_norm(a1 - lam * a2, f("sub_norm"),
                                       sz["eps"])
        y = o.reshape(n, H * dh) @ wo + f("wo_b")
        return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + y,
                                                   b * n, 0)

    return jax.lax.fori_loop(first, blocks, row_block, x)


@_highest
def gmu_layer(x, mem, lp, sz, rows: int = 0, blocks=None):
    """x + the gated memory unit's addition: (m * SiLU(n W_1)) W_2."""
    S = x.shape[0]
    n = rows or S
    if blocks is None:
        blocks = S // n
    w1, w2 = _f32(sz, lp["w1"], lp["w2"])
    f = lambda k: lp[k].astype(jnp.float32)
    cut = _cut(n)

    def block(b, acc):
        h = layer_norm(cut(acc, b), f("attn_norm"), f("attn_norm_b"),
                       sz["eps"])
        y = (cut(mem, b) * jax.nn.silu(h @ w1)) @ w2
        return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + y,
                                                   b * n, 0)

    return jax.lax.fori_loop(0, blocks, block, x)


@_highest
def mlp_layer(x, lp, sz, rows: int = 0, blocks=None):
    """x + the SwiGLU: one fused gate-and-up product, gate first."""
    S, F = x.shape[0], sz["d_ff"]
    n = rows or S
    if blocks is None:
        blocks = S // n
    wgu, wd = _f32(sz, lp["w_gate_up"], lp["w_down"])
    f = lambda k: lp[k].astype(jnp.float32)
    cut = _cut(n)

    def block(b, acc):
        h = layer_norm(cut(acc, b), f("mlp_norm"), f("mlp_norm_b"), sz["eps"])
        gu = h @ wgu
        y = (gu[:, F:] * jax.nn.silu(gu[:, :F])) @ wd
        return jax.lax.dynamic_update_slice_in_dim(acc, cut(acc, b) + y,
                                                   b * n, 0)

    return jax.lax.fori_loop(0, blocks, block, x)


@_highest
def readout(x, final_norm, final_norm_b, embed, sz, i=0, parts: int = 1):
    """x [n, D] -> logits [n, V / parts] through the tied table: slice i
    of `parts` of its rows (the vocabulary)."""
    size = embed.shape[0] // parts
    table, = _f32(sz, jax.lax.dynamic_slice_in_dim(embed, i * size, size, 0))
    return layer_norm(x, final_norm.astype(jnp.float32),
                      final_norm_b.astype(jnp.float32), sz["eps"]) @ table.T


def logits(params, tokens, sz):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence)."""
    x = params["embed"][tokens].astype(jnp.float32)
    half = sz["n_layers"] // 2
    mem = shared = None
    for l, lp in enumerate(params["layers"]):
        k = kind(sz, l)
        if k == "mamba":
            x, y = mamba_layer(x, lp, sz)
            mem = y if l == half else mem
        elif k == "gmu":
            x = gmu_layer(x, mem, lp, sz)
        elif k == "cross":
            x = attend(x, *shared, lp, sz, l)
        else:
            kv = keys_values(x, lp, sz)
            shared = kv if k == "full" else shared
            x = attend(x, *kv, lp, sz, l,
                       sz["window"] if k == "swa" else None)
        x = mlp_layer(x, lp, sz)
    return readout(x, params["final_norm"], params["final_norm_b"],
                   params["embed"], sz)
