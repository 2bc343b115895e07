"""Ling-3.0 (`bailing_hybrid`) as its configuration and the Kimi Linear
report (arXiv 2510.26692) describe it, in plain jax.numpy and float32 — the
yardstick for `correct` of the cells that serve it.

Written from the layer equations, not from the program; imports nothing
from `ray_tpu`.  What it shares with `deepseek_v3_plain` (the draw's piece
generator, RMSNorm, the rope over interleaved pairs, the dense SwiGLU, the
group-limited router, one held expert, the shared expert, the head) it
takes from there: both are this benchmark's own.  Per layer l (RMSNorm eps
1e-6):

    x = x + mixer_l(RMSNorm(x));   x = x + ffn_l(RMSNorm(x))

    mixer_l, (l + 1) % layer_group != 0 — KDA, H heads of d wide, n the
    normed input, one token after the other:
        q~ | k~ | v~ = n Wqkv
        u_t  = SiLU(sum_{i=0..3} c_i u~_{t-3+i} + b)      four shifted rows
        q_t  = q_t / |q_t| * d^-1/2;  k_t = k_t / |k_t|
        log a_t = lower * sigmoid(exp(A_log_h) (n_t Wf + dt_bias))
        b_t  = sigmoid(n_t Wb)
        S_t  = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
        o_t  = S_t^T q_t
        y_t  = (RMSNorm_head(o_t) * sigmoid(n_t Wg)) Wo
    the recurrence as written, under `lax.scan`: no chunk, no WY form.

    mixer_l, (l + 1) % layer_group == 0 — MLA in its published expanded
    form over all positions, the query projected directly (no latent):
        q_h = n Wq -> (q_nope dn | q_pe dr);  c_kv | k_pe = n Wkva
        c_kv = RMSNorm(c_kv);  k_nope_h | v_h = c_kv Wkvb
        q_pe, k_pe <- RoPE over interleaved pairs, theta 6e6
        s_h[i,j] = (q_nope_h[i].k_nope_h[j] + q_pe_h[i].k_pe[j])
                   (dn + dr)^-1/2,  j <= i
        o_h = softmax(s_h) v_h * sigmoid(n w_h)           head-wise gate
        y   = concat_h(o_h) Wo

    ffn_l: l < n_dense a SwiGLU; else the router over ALL experts in
    groups (deepseek_v3_plain.route), the held experts' weighted sum, the
    shared expert unweighted.
    logits = RMSNorm(x_L) Wout                             (untied head)

The weights are this file's OWN draw from the seed (`draw_leaf`): the
recipe the configuration's `weights.made` states, written a second time.

Departures, each forced by what it is compared with: only the experts
`first..first+held-1` and `vocab` rows of the vocabulary are computed (the
chip's share); the multi-token-prediction layer is left out; it is
computed in pieces (`kda_layer`, `mla_layer`, `dense_layer`, `moe_layer`:
a program each, one layer's weights at a time) so that it fits beside the
engine and inside a run's minute:
a KDA layer a block of rows at a time — projections, conv and gates of the
block, then its rows through the recurrence one by one, the state carried
from block to block — and attention a few heads and a block of rows at a
time against every key, unseen ones masked; the held experts one after the
other over the rows routed to each, gathered to a static bound.  Nothing
that enters a sum is left out.

`shape["control"]` names a fault put into THIS computation on purpose
(never in a benchmark run): `fp8_weights`, `no_groups` (both
deepseek_v3_plain's).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import deepseek_v3_plain as dsp
from .deepseek_v3_plain import (_f32, _highest, dense_part, latents, normed,
                                readout, rms_norm, rope, route, shared_expert)

TAPS = 4

# name -> place of a layer's leaf in the draw (the program's LEAVES)
PLACES = {n: i for i, n in enumerate(
    ("w_qkv", "conv_w", "w_f", "w_b", "w_g", "wo", "wq", "wkv_a", "wkv_b",
     "w_head_gate", "w_gate", "w_up", "w_down", "router", "wg", "wu", "wd",
     "shared_gate", "shared_up", "shared_down"))}
BIAS_PLACE, A_LOG_PLACE, DT_BIAS_PLACE = (len(PLACES) + i for i in range(3))

KDA_LEAVES = ("w_qkv", "conv_w", "w_f", "w_b", "w_g", "wo", "a_log",
              "dt_bias")
MLA_LEAVES = ("wq", "wkv_a", "wkv_b", "w_head_gate", "wo")


def is_mla(sz: dict, l: int) -> bool:
    return (l + 1) % sz["layer_group"] == 0


def leaf_specs(sz: dict, mla: bool = False) -> dict:
    """name -> (shape, fan in, scale) of every normally drawn leaf of a
    layer (`wo`'s shape is its mixer's)."""
    D, H, d = sz["d_model"], sz["n_heads"], sz["d_head"]
    rkv, dn, dr, dv = sz["kv_rank"], sz["d_nope"], sz["d_rope"], sz["d_v"]
    F, Fe, C, Fs = sz["d_ff"], sz["d_expert"], sz["held"], sz["d_shared"]
    out = 1.0 / math.sqrt(2 * sz["n_layers"])
    return {
        "w_qkv": ((D, 3 * H, d), D, 1.0),
        "conv_w": ((TAPS, 3 * H, d), TAPS, 1.0),
        "w_f": ((D, H, d), D, 1.0), "w_b": ((D, H), D, 1.0),
        "w_g": ((D, H, d), D, 1.0),
        "wo": ((H, dv, D), H * dv, out) if mla else ((H, d, D), H * d, out),
        "wq": ((D, H, dn + dr), D, 1.0), "wkv_a": ((D, rkv + dr), D, 1.0),
        "wkv_b": ((rkv, H, dn + dv), rkv, 1.0),
        "w_head_gate": ((D, H), D, 1.0),
        "w_gate": ((D, F), D, 1.0), "w_up": ((D, F), D, 1.0),
        "w_down": ((F, D), F, out),
        "router": ((D, sz["n_experts"]), D, 1.0),
        "wg": ((C, D, Fe), D, 1.0), "wu": ((C, D, Fe), D, 1.0),
        "wd": ((C, Fe, D), Fe, out),
        "shared_gate": ((D, Fs), D, 1.0), "shared_up": ((D, Fs), D, 1.0),
        "shared_down": ((Fs, D), Fs, out),
    }


@functools.partial(jax.jit, static_argnames=("count", "n", "dtype"))
def _pieces(seed, layer, place, std, count, n, dtype):
    """Pieces 0..count-1 of a leaf laid end to end, one after the other
    inside one program (deepseek_v3_plain._piece a piece: the recipe's)."""
    return jax.lax.map(
        lambda i: dsp._piece(seed, layer, place, i, std, n, dtype),
        jnp.arange(count)).reshape(-1)


def _normal(seed, layer, place, shape, std, dtype, factor):
    size, n = math.prod(shape), dsp.DRAW_PIECE
    w = _pieces(seed, layer, place, jnp.float32(std), -(-size // n), n,
                dtype)[:size].reshape(shape)
    return w * factor if factor != 1 else w


def _uniform(seed, layer, place, shape):
    """Uniform draws in (0, 1): the normal distribution's own function of
    the draw's normals."""
    return jax.scipy.special.ndtr(
        _normal(seed, layer, place, shape, 1.0, "float32", 1))


def draw_leaf(seed: int, sz: dict, weights: dict, layer: int, name: str):
    """One leaf as the replica's loader makes it: `layer` -1 holds the two
    vocabulary tables; `router_bias`, `a_log` and `dt_bias` are the
    loader's own draws (the configuration's `weights` says what of)."""
    seed, pd = seed % (2 ** 31), sz["param_dtype"]
    scales = weights.get("scales", {})
    H, d = sz["n_heads"], sz["d_head"]
    if layer < 0:
        V, D = sz["vocab"], sz["d_model"]
        shape, std, place = {"embed": ((V, D), 0.02, 0),
                             "unembed": ((D, V), 1.0 / math.sqrt(D), 1)}[name]
        return _normal(seed, -1, place, shape, std, pd,
                           scales.get(name, 1))
    if name == "router_bias":
        return _normal(seed, layer, BIAS_PLACE, (sz["n_experts"],),
                           float(weights.get("router_bias_std", 0.0)),
                           "float32", 1)
    if name in ("a_log", "dt_bias"):
        lo, hi = weights["a_range"]
        a_log = jnp.log(lo + (hi - lo) * _uniform(seed, layer, A_LOG_PLACE,
                                                  (H,)))
        if name == "a_log":
            return a_log
        # a fresh gate, n Wf = 0, sits at log a = -t, t log-uniform
        t0, t1 = weights["fresh_log_a"]
        t = t0 * (t1 / t0) ** _uniform(seed, layer, DT_BIAS_PLACE, (H, d))
        p = t / -sz["gate_lower"]
        return jnp.log(p / (1.0 - p)) / jnp.exp(a_log)[:, None]
    shape, fan_in, scale = leaf_specs(sz, is_mla(sz, layer))[name]
    return _normal(seed, layer, PLACES[name], shape,
                       scale / math.sqrt(fan_in),
                       "float32" if name == "router" else pd,
                       scales.get(name, 1))


def layer_leaves(sz: dict, layer: int):
    mixer = MLA_LEAVES if is_mla(sz, layer) else KDA_LEAVES
    if layer < sz["n_dense"]:
        return mixer + ("w_gate", "w_up", "w_down")
    return mixer + ("router", "router_bias", "wg", "wu", "wd", "shared_gate",
                    "shared_up", "shared_down")


def draw(seed: int, sz: dict, weights: dict) -> dict:
    """The whole tree (small sizes: a test)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for l in range(sz["n_layers"]):
        lp = {n: draw_leaf(seed, sz, weights, l, n)
              for n in layer_leaves(sz, l)}
        lp.update(attn_norm=ones(sz["d_model"]), mlp_norm=ones(sz["d_model"]))
        if is_mla(sz, l):
            lp["kv_norm"] = ones(sz["kv_rank"])
        else:
            lp.update(o_norm=ones(sz["d_head"]),
                      conv_b=jnp.zeros((3 * sz["n_heads"], sz["d_head"])))
        layers.append(lp)
    return {"embed": draw_leaf(seed, sz, weights, -1, "embed"),
            "unembed": draw_leaf(seed, sz, weights, -1, "unembed"),
            "final_norm": ones(sz["d_model"]), "layers": layers}


# ---------------------------------------------------------------------------


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _token(S, row):
    """The recurrence's one line: S [H, dk, dv], the token's q, k [H, dk],
    v [H, dv], log a [H, dk], b [H] -> (S_t, o_t [H, dv])."""
    q, k, v, log_a, b = row
    S = jnp.exp(log_a)[:, :, None] * S                       # Diag(a) S
    held = jnp.sum(k[:, :, None] * S, axis=1)                # S^T k
    S = S + (b[:, None] * k)[:, :, None] * (v - held)[:, None, :]
    return S, jnp.sum(q[:, :, None] * S, axis=1)


@_highest
def kda_layer(x, attn_norm, lp, sz, rows: int = 0, blocks=None, stop=None):
    """The sequence's rows x [N, D] (positions 0..N-1) -> x + the KDA
    mixer's addition.  Rows are taken `rows` at a time (0: all at once; N
    a multiple of it), the blocks 0..`blocks`-1 (may be traced; None: to
    the end): the others come back as they came.  `lp` holds the layer's
    KDA leaves.  With `stop` (may be traced) -> (that, the state [H, dk,
    dv] as it stands when positions 0..`stop`-1 have gone in: zeros where
    `stop` lies in no block taken)."""
    H, d = sz["n_heads"], sz["d_head"]
    N = x.shape[0]
    n = rows or N
    if blocks is None:
        blocks = N // n
    w_qkv, conv_w, w_f, w_b, w_g, wo = _f32(
        sz, *(lp[k] for k in ("w_qkv", "conv_w", "w_f", "w_b", "w_g", "wo")))
    conv_b, o_norm = (lp[k].astype(jnp.float32) for k in ("conv_b", "o_norm"))
    a, dt_bias = jnp.exp(lp["a_log"]), lp["dt_bias"]
    h = rms_norm(x, attn_norm.astype(jnp.float32), sz["eps"])
    # the pre-conv rows of the whole sequence, three rows of zeros before
    pre = jnp.pad(jnp.einsum("nd,dck->nck", h, w_qkv),
                  ((TAPS - 1, 0), (0, 0), (0, 0)))

    def block(b, carry):
        S, acc, kept = carry
        cut = lambda arr, extra=0: jax.lax.dynamic_slice_in_dim(
            arr, b * n, n + extra, 0)
        ext, hb = cut(pre, TAPS - 1), cut(h)
        u = jax.nn.silu(conv_b + sum(conv_w[i] * ext[i:i + n]
                                     for i in range(TAPS)))
        q = _unit(u[:, :H]) * d ** -0.5
        k, v = _unit(u[:, H:2 * H]), u[:, 2 * H:]
        log_a = sz["gate_lower"] * jax.nn.sigmoid(
            a[:, None] * (jnp.einsum("nd,dhk->nhk", hb, w_f) + dt_bias))
        beta = jax.nn.sigmoid(hb @ w_b)
        if stop is not None:
            # the block that holds position `stop`-1 once more, the tokens
            # from `stop` on changed to ones that leave the state alone
            # (a = 1, b = 0)
            live = b * n + jnp.arange(n) < stop
            kept = jax.lax.cond(
                (b * n < stop) & (stop <= b * n + n),
                lambda: jax.lax.scan(_token, S, (
                    q, k, v, jnp.where(live[:, None, None], log_a, 0.0),
                    jnp.where(live[:, None], beta, 0.0)))[0],
                lambda: kept)
        S, o = jax.lax.scan(_token, S, (q, k, v, log_a, beta))
        y = rms_norm(o, o_norm, sz["eps"]) * jax.nn.sigmoid(
            jnp.einsum("nd,dhk->nhk", hb, w_g))
        return S, jax.lax.dynamic_update_slice_in_dim(
            acc, cut(acc) + jnp.einsum("nhk,hkd->nd", y, wo), b * n, 0), kept

    zero = jnp.zeros((H, d, d), jnp.float32)
    _, out, kept = jax.lax.fori_loop(0, blocks, block, (zero, x, zero))
    return out if stop is None else (out, kept)


@_highest
def attend(x, c_kv, k_pe, attn_norm, wq, wkv_b, w_head_gate, wo, sz,
           heads: int = 0, rows: int = 0, blocks=None, first=0):
    """The sequence's rows x [N, D] against its latents c_kv [N, rkv],
    k_pe [N, dr] (deepseek_v3_plain.latents) -> x + the MLA mixer's
    addition.  Every head's keys and values are formed from the latents,
    `heads` heads at a time (0: all); rows are taken `rows` at a time (0:
    all; N a multiple of it), the blocks `first`..`blocks`-1 only, each
    against EVERY key with the ones it cannot see masked."""
    wq, wkv_b, w_head_gate, wo = _f32(sz, wq, wkv_b, w_head_gate, wo)
    N = x.shape[0]
    H, dn = sz["n_heads"], sz["d_nope"]
    g, n = heads or H, rows or N
    if blocks is None:
        blocks = N // n
    h = rms_norm(x, attn_norm.astype(jnp.float32), sz["eps"])
    gate = jax.nn.sigmoid(h @ w_head_gate)                      # [N, H]
    scale = (dn + sz["d_rope"]) ** -0.5
    cut = lambda a, b: jax.lax.dynamic_slice_in_dim(a, b * n, n, 0)
    key_at = jnp.arange(N)

    def some_heads(acc, a):
        w_q, w, w_o, gt = a     # [D,g,dn+dr] [rkv,g,dn+dv] [g,dv,D] [N,g]
        q = jnp.einsum("nd,dhk->nhk", h, w_q)
        q_nope, q_pe = q[..., :dn], rope(q[..., dn:], sz)
        k_nope = jnp.einsum("sr,rhk->shk", c_kv, w[..., :dn])
        v = jnp.einsum("sr,rhk->shk", c_kv, w[..., dn:])

        def row_block(b, acc):
            s = (jnp.einsum("nhk,shk->hns", cut(q_nope, b), k_nope)
                 + jnp.einsum("nhk,sk->hns", cut(q_pe, b), k_pe)) * scale
            see = key_at[None, :] <= (b * n + jnp.arange(n))[:, None]
            p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hns,shk->nhk", p, v) * cut(gt, b)[..., None]
            return jax.lax.dynamic_update_slice_in_dim(
                acc, cut(acc, b) + jnp.einsum("nhk,hkd->nd", o, w_o),
                b * n, 0)

        return jax.lax.fori_loop(first, blocks, row_block, acc), None

    split = lambda a, ax: jnp.moveaxis(
        a.reshape(a.shape[:ax] + (H // g, g) + a.shape[ax + 1:]), ax, 0)
    out, _ = jax.lax.scan(some_heads, x, (split(wq, 1), split(wkv_b, 1),
                                          split(wo, 0), split(gate, 1)))
    return out


@_highest
def experts(x, h, w, idx, wg, wu, wd, sz, cap: int = 0):
    """x [n, D] with the held experts' weighted sum of h added: expert
    after expert (`wg`, `wu`, `wd` the held stacks), each over the rows
    routed to it — gathered (a static `cap` of them), through the expert,
    added where they came from; where more than `cap` rows fall on one —
    or no cap is given — over every row with the others' gates at zero
    (deepseek_v3_plain.expert's sum either way, laid over x in place)."""
    n = h.shape[0]

    def one(i, x):
        e = sz["first"] + i
        g, u, d = _f32(sz, *(jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False) for a in (wg, wu, wd)))
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # [n]
        on = jnp.any(idx == e, axis=-1)

        def gathered():
            rows = jnp.nonzero(on, size=cap, fill_value=n)[0]
            y = dsp.swiglu(h.at[rows].get(mode="fill", fill_value=0.0),
                           g, u, d)
            y = y * gate.at[rows].get(mode="fill", fill_value=0.0)[:, None]
            return x.at[rows].add(y, mode="drop")

        def every_row():
            return x + gate[:, None] * dsp.swiglu(h, g, u, d)

        if not cap or cap >= n:
            return every_row()
        return jax.lax.cond(jnp.sum(on) > cap, every_row, gathered)

    return jax.lax.fori_loop(0, wg.shape[0], one, x)


def moe_layer(x, mlp_norm, lp, sz, cap: int = 0):
    """x + the expert layer's feed-forward: the router over ALL experts
    in groups, the held experts' weighted sum, the shared expert
    unweighted."""
    h = normed(x, mlp_norm, sz)
    w, idx = route(h, lp["router"], lp["router_bias"], sz)
    x = experts(x, h, w, idx, lp["wg"], lp["wu"], lp["wd"], sz, cap)
    return x + shared_expert(h, lp["shared_gate"], lp["shared_up"],
                             lp["shared_down"], sz)


def dense_layer(x, mlp_norm, lp, sz, parts: int = 1):
    """x + the dense SwiGLU, `parts` slices of its width one after the
    other."""
    h = normed(x, mlp_norm, sz)
    return jax.lax.fori_loop(0, parts, lambda i, x: x + dense_part(
        h, lp["w_gate"], lp["w_up"], lp["w_down"], sz, i, parts), x)


def mla_layer(x, attn_norm, lp, sz, heads: int = 0, rows: int = 0,
              blocks=None, first=0):
    """x + the MLA mixer's addition: the sequence's latents, then
    `attend` over them."""
    c_kv, k_pe = latents(x, attn_norm, lp["wkv_a"], lp["kv_norm"], sz)
    return attend(x, c_kv, k_pe, attn_norm, lp["wq"], lp["wkv_b"],
                  lp["w_head_gate"], lp["wo"], sz, heads, rows, blocks, first)


def block(x, lp, sz):
    mixer = mla_layer if "wkv_a" in lp else kda_layer
    x = mixer(x, lp["attn_norm"], lp, sz)
    ffn = moe_layer if "router" in lp else dense_layer
    return ffn(x, lp["mlp_norm"], lp, sz)


def logits(params, tokens, sz):
    """tokens [S] int32 -> logits [S, V] float32 (one sequence)."""
    x = params["embed"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = block(x, lp, sz)
    return readout(x, params["final_norm"], params["unembed"], sz)
