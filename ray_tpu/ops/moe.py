"""Mixture-of-experts with expert parallelism (EP).

Absent from the reference (SURVEY.md §2.3: "EP (expert parallel): absent
in-tree — expert-sharded mesh axis + lax.all_to_all token dispatch"); built
natively here, TPU-first:

  * Routing uses the dense one-hot dispatch/combine formulation
    (GShard/Switch): static shapes, pure einsums — everything tiles onto
    the MXU and nothing falls off the compiled path.  Capacity is a static
    bound; overflow tokens are dropped (their combine weight is zero), the
    standard TPU MoE trade.
  * Expert parallelism is one `lax.all_to_all` each way over the `ep` mesh
    axis inside shard_map: dispatch [E, C, D] -> [E/n, n*C, D] so each
    device runs only its local experts, then the inverse on the way back.
  * Aux losses (load-balance, router z-loss) are returned to the caller —
    the trainer adds them to the objective.
  * Serving runs a chip's SHARE of an expert layer without capacity
    (`held_expert_ffn`, second half of the file): the pairs that fall on
    the held experts, sorted, through `jax.lax.ragged_dot` at most
    ROW_BLOCK sorted rows a product (a caller's `tile` may bound it lower;
    the row block is the usual bound), a trip of products ending where an
    expert ends.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class RouterOut(NamedTuple):
    dispatch: jax.Array   # [T, E, C] 0/1 dispatch tensor
    combine: jax.Array    # [T, E, C] gate-weighted combine tensor
    aux_loss: jax.Array   # scalar load-balance loss
    z_loss: jax.Array     # scalar router z-loss


def expert_capacity(num_tokens: int, num_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Static per-expert token budget (multiple of 8 for TPU tiling)."""
    c = int(math.ceil(k * num_tokens / num_experts * capacity_factor))
    return max(8, ((c + 7) // 8) * 8)


def route_topk(logits: jax.Array, k: int, capacity: int) -> RouterOut:
    """Top-k routing with slot-priority positioning (GShard).

    logits: [T, E] router scores.  Returns dense dispatch/combine tensors
    [T, E, C]; tokens beyond an expert's capacity get zero weight.
    """
    T, E = logits.shape
    compute_dtype = jnp.promote_types(logits.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(compute_dtype), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # [T, k]
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    dispatch = jnp.zeros((T, E, capacity), compute_dtype)
    combine = jnp.zeros((T, E, capacity), compute_dtype)
    counts = jnp.zeros((E,), compute_dtype)
    for j in range(k):  # k is tiny (1-2): unrolled at trace time
        oh = jax.nn.one_hot(expert_idx[:, j], E, dtype=compute_dtype)  # [T, E]
        pos = jnp.cumsum(oh, axis=0) - 1.0 + counts[None, :]  # queue position
        counts = counts + oh.sum(axis=0)
        within = (pos < capacity) * oh                        # [T, E]
        pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1).astype(jnp.int32),
                                capacity, dtype=compute_dtype)
        slot = pos_oh * within[..., None]                     # [T, E, C]
        dispatch = dispatch + slot
        combine = combine + slot * gate_vals[:, j][:, None, None]

    # load-balance: E * sum_e fraction_dispatched_e * mean_router_prob_e
    # (Switch Transformer eq. 4, over the top-1 assignment)
    top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=compute_dtype)
    frac = top1.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    z = jnp.mean(jax.scipy.special.logsumexp(
        logits.astype(compute_dtype), axis=-1) ** 2)
    return RouterOut(dispatch, combine, aux, z)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_in: jax.Array,
            w_out: jax.Array, *, k: int = 2, capacity_factor: float = 1.25,
            act: Callable = jax.nn.gelu,
            capacity: Optional[int] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense (single-device / GSPMD-auto) MoE feed-forward.

    x: [T, D] tokens; router_w: [D, E]; w_in: [E, D, F]; w_out: [E, F, D].
    Returns (out [T, D], aux_loss, z_loss).
    """
    T, D = x.shape
    E = router_w.shape[1]
    C = capacity if capacity is not None else expert_capacity(
        T, E, k, capacity_factor)
    logits = x @ router_w                               # [T, E]
    r = route_topk(logits, k, C)
    xe = jnp.einsum("td,tec->ecd", x, r.dispatch.astype(x.dtype))
    h = act(jnp.einsum("ecd,edf->ecf", xe, w_in))
    y = jnp.einsum("ecf,efd->ecd", h, w_out)
    out = jnp.einsum("ecd,tec->td", y, r.combine.astype(y.dtype))
    return out, r.aux_loss, r.z_loss


def moe_ffn_sharded(x: jax.Array, router_w: jax.Array, w_in_local: jax.Array,
                    w_out_local: jax.Array, *, axis_name: str = "ep",
                    k: int = 2, capacity_factor: float = 1.25,
                    act: Callable = jax.nn.gelu,
                    capacity: Optional[int] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-device MoE body for use inside an existing shard_map program.

    Token activations are sharded over `axis_name` ([T_local, D] here);
    expert weights are expert-sharded ([E/n, D, F] locally).  The router is
    replicated.  One all_to_all moves each device's dispatched tokens to
    the devices owning their experts; the inverse brings results home —
    the `lax.all_to_all` token dispatch SURVEY.md §2.3 calls for.
    """
    n = jax.lax.psum(1, axis_name)
    El = w_in_local.shape[0]
    E = El * n
    Tl, D = x.shape
    C = capacity if capacity is not None else expert_capacity(
        Tl, E, k, capacity_factor)
    logits = x @ router_w                               # [Tl, E]
    r = route_topk(logits, k, C)
    xe = jnp.einsum("td,tec->ecd", x, r.dispatch.astype(x.dtype))  # [E, C, D]
    # to expert owners: [E, C, D] -> [E/n, n*C, D]
    xe = jax.lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=1,
                            tiled=True)
    h = act(jnp.einsum("ecd,edf->ecf", xe, w_in_local))
    y = jnp.einsum("ecf,efd->ecd", h, w_out_local)      # [E/n, n*C, D]
    # back to token owners: [E/n, n*C, D] -> [E, C, D]
    y = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                           tiled=True)
    out = jnp.einsum("ecd,tec->td", y, r.combine.astype(y.dtype))
    # aux losses are per-shard means over the same token count: average
    aux = jax.lax.pmean(r.aux_loss, axis_name)
    z = jax.lax.pmean(r.z_loss, axis_name)
    return out, aux, z


# ---------------------------------------------------------------------------
# A chip's share of an expert layer (inference).  Under wide expert
# parallelism a chip holds `count` of `n_experts` experts, routes over all
# of them and computes what its own experts add for the token-expert pairs
# that fall on them; what the absent experts would add is left out here and
# summed by the exchange that a one-chip share runs without.  No capacity:
# every pair on a held expert is computed (`route_topk`'s [T, E, C] dispatch
# drops pairs past C and scores by softmax, so it is not used here).


def _sigmoid_scores(h: jax.Array, router_w: jax.Array) -> jax.Array:
    """Sigmoid scores over ALL experts in f32: h [N, D], router_w [D, E] ->
    [N, E]."""
    return jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


def route_sigmoid_topk(h: jax.Array, router_w: jax.Array, k: int, *,
                       bias: Optional[jax.Array] = None, eps: float = 0.0
                       ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores over ALL experts in f32, top-k, weights normalised
    over the chosen k (their sum plus `eps`).  With `bias` [E] (a
    correction bias: auxiliary-loss-free balancing) the scores plus the
    bias CHOOSE and the scores alone WEIGH; a tie goes to the lower index.
    h [N, D], router_w [D, E] -> (weights [N, k] f32, expert ids [N, k]
    int32)."""
    s = _sigmoid_scores(h, router_w)
    if bias is None:
        w, idx = jax.lax.top_k(s, k)
    else:
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    return w / (total + eps if eps else total), idx.astype(jnp.int32)


def route_sigmoid_grouped(h: jax.Array, router_w: jax.Array, bias: jax.Array,
                          k: int, *, n_group: int, topk_group: int,
                          scale: float = 1.0
                          ) -> Tuple[jax.Array, jax.Array]:
    """Group-limited routing with a correction bias (auxiliary-loss-free
    balancing): sigmoid scores s over ALL experts in f32; the scores plus
    `bias` [E] CHOOSE, the scores alone WEIGH.  The experts lie in
    `n_group` groups of E / n_group; a group's rank is the sum of its two
    largest biased scores, the `topk_group` best groups are kept, and the
    k largest biased scores inside them are the token's experts (a tie
    goes to the lower index, in both rankings).  Weights are s over the
    chosen k, normalised (+1e-20) and multiplied by `scale`.
    h [N, D], router_w [D, E] -> (weights [N, k] f32, ids [N, k] int32)."""
    s = _sigmoid_scores(h, router_w)
    N, E = s.shape
    c = s + bias.astype(jnp.float32)
    by_group = c.reshape(N, n_group, E // n_group)
    rank = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)  # [N, n_group]
    _, kept = jax.lax.top_k(rank, topk_group)
    keep = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], kept].set(True)
    c = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(N, E)
    _, idx = jax.lax.top_k(c, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return w, idx.astype(jnp.int32)


# The sorted rows ONE grouped product takes at most.  On a TPU
# `jax.lax.ragged_dot` is a grouped matmul kernel tiled `tm, tk, tn` with
# tm = min(rows of the call, 512): it visits every (expert, tm-row block)
# that holds one of the expert's rows and puts the WHOLE block of rows
# through the expert's tk x tn weight tile, keeping the expert's own.  A
# share's experts own a few rows each (8-32 of a chunk's, one or two of a
# step's), so at tm = 512 a visit is a 512-row product of which 500 rows
# are thrown away, and it outlasts its tile's read from HBM whatever D and
# F are.  The two meet at peak FLOP/s / peak bytes/s rows — 240 for bf16
# on a v5e (197 TFLOP/s, 819 GB/s) — so a block at or under that makes a
# touched expert cost its weights' read.  It depends on the chip alone,
# not on a model: no configuration sets it.
#
# tk and tn are the compiler's: each the LARGEST of 512 / 256 / 128 that
# divides the weights' K and N (read in the compiled text PR 60 kept,
# `chiprun_out/pr60/prefill_tree.hlo.txt`: K 2,560 x N 768 tiled
# "128,512,256", K 768 x N 2,560 "128,256,512"; `tests/test_chip_compile.py`
# holds K 2,560 x N 1,536 at "128,512,512").  A grid step costs ~0.26
# us beside its tile's bytes, so a 512 x 256 tile (0.32 us of reading)
# stands at 55% of the memory's speed where a 512 x 512 one stands at
# ~73%.  An F that is 3 x 256 (Ling's 768) halves every gate and up tile;
# gate and up side by side, N = 2F = 3 x 512, tile whole — which is why
# `held_expert_ffn` takes them as ONE leaf `[count, D, 2F]` where a model
# lays them so.
#
# What a trip costs beside its products (PR 64, read by instruction with
# `scripts/study_moe_row_block.py`, `chiprun_out/pr64/`; LFM2's chunk: 512
# rows, top-4, 32 of 32 held, D 2,048, 22 trips a layer): 15.9 us, of
# which 7.6 are its scatter-add into the [N, D] sum, 2.7 its gather of
# h's rows, ~4 the two products' `ragged-dot-metadata` and 1.5 the loop's
# own turn.  Gather and scatter-add are priced A ROW (21 ns a gathered
# bf16 row of 2,048, 59-74 ns a scatter-added f32 one, in a 128-row call
# as in a 640-row one), so ONE gather and ONE scatter-add over a block of
# trips cost what the trips' own did and more for the buffers between
# (measured: 0.349 -> 0.498 ms a layer outside the products), and products
# with a block's 0/1 matrix on the MXU cost 16 + 42 ns a row at N = 512
# but GROW WITH N (a 2,048-row program +17%).  The trip therefore keeps
# its gather and its scatter-add; what a layer paid ONCE before its trips
# (an argsort, a gather of the weights, a scatter of N*k ones: 0.045 ms at
# that shape, 0.078 at Ling's) is one sort and a comparison now.
ROW_BLOCK = 128


def held_expert_ffn(h: jax.Array, weights: jax.Array, idx: jax.Array,
                    w_gate: jax.Array, w_up: Optional[jax.Array],
                    w_down: jax.Array, *, first: int, tile: int = 512,
                    live: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The held experts' part of a routed SwiGLU layer, dropless.

    h [N, D]; (weights, idx) [N, k] from the router over all experts;
    w_down [count, F, D] and the gate and up matrices are experts
    first..first+count-1.  Gate and up come in one of two layouts, told
    apart by the operands alone: `w_up` None and `w_gate` ONE leaf
    [count, D, 2F], gate in columns [:F] and up in [F:] (F is
    w_down.shape[1]) — a trip is then TWO grouped products, the gathered
    rows are read once and N = 2F tiles whole where F alone does not (the
    comment above ROW_BLOCK) — or `w_gate` / `w_up` [count, D, F] apart,
    three products a trip.  The second stays for `cohere2_moe` only, whose
    tree the benchmark's own check reads `wg` / `wu` from (and whose
    F = 4,096 tiles whole either way); `deepseek_v3.layer_ffn`'s three
    models lay the leaf.  Both are the same sums: operands in h's dtype,
    f32 accumulation over D.
    The N*k pairs are sorted by held expert (pairs on absent experts last;
    one stable sort that carries each pair's token and weight along)
    and go through grouped matrix products (`jax.lax.ragged_dot`) a trip at
    a time, for as many trips as the pairs of held experts ask — a traced
    trip count, so a chunk whose pairs mostly fall elsewhere costs what
    falls here.  A trip takes at most
    M = min(`tile`, N*k, ROW_BLOCK) sorted rows and ENDS WHERE AN EXPERT
    ENDS: rows [lo, hi) with hi the largest end of an expert's rows at or
    under lo + M, so no expert's weights are read by two trips — but for
    an expert with more than M rows left, which takes M of them and needed
    the visits anyway.  `tile` is an upper bound a caller may set (the tiny
    test configurations': several trips at toy sizes); ROW_BLOCK is the
    usual one.  `live` [N] bool takes rows out of the routing (a slot
    batch's empty slots).
    Returns (out [N, D] f32, loads [count] int32: pairs per held expert,
    reads int32: experts visited summed over the trips — an expert counts
    once for each trip that holds a row of it, so `reads` over the experts
    with a pair is how often a touched expert's weights were read)."""
    N, D = h.shape
    k = idx.shape[1]
    count, F = w_down.shape[:2]
    local = idx - first
    held = (local >= 0) & (local < count)
    if live is not None:
        held = held & live[:, None]
    key = jnp.where(held, local, count).reshape(N * k)
    # held pairs first, by expert, a token's in its own order: ONE stable
    # sort carries each pair's token and weight to its sorted row, and the
    # loads are counted by comparison (the comment above ROW_BLOCK)
    _, tok_of, w_of = jax.lax.sort(
        (key, jnp.arange(N * k, dtype=jnp.int32) // k,
         weights.reshape(N * k)), num_keys=1)
    loads = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    ends = jnp.cumsum(loads)
    starts = ends - loads
    n_held = ends[-1]
    M = min(int(tile), N * k, ROW_BLOCK)
    tok_of = jnp.pad(tok_of, (0, M))                    # sorted row -> token
    w_of = jnp.pad(w_of, (0, M))
    dt = h.dtype

    def one_trip(carry):
        lo, out, reads = carry
        whole = jnp.max(jnp.where(ends <= lo + M, ends, 0))
        hi = jnp.where(whole > lo, whole, lo + M)
        tok = jax.lax.dynamic_slice_in_dim(tok_of, lo, M)
        wt = jax.lax.dynamic_slice_in_dim(w_of, lo, M)
        gs = jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)
        x = h[tok]
        g = jax.lax.ragged_dot(x, w_gate.astype(dt), gs,
                               preferred_element_type=jnp.float32)
        if w_up is None:
            g, u = g[:, :F], g[:, F:]
        else:
            u = jax.lax.ragged_dot(x, w_up.astype(dt), gs,
                                   preferred_element_type=jnp.float32)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(dt),
                               w_down.astype(dt), gs,
                               preferred_element_type=jnp.float32)
        valid = jnp.arange(M) < hi - lo            # rows of no group: zero
        y = jnp.where(valid[:, None], y * wt[:, None], 0.0)
        return hi, out.at[tok].add(y), reads + jnp.sum(gs > 0)

    _, out, reads = jax.lax.while_loop(
        lambda carry: carry[0] < n_held, one_trip,
        (jnp.zeros((), jnp.int32), jnp.zeros((N, D), jnp.float32),
         jnp.zeros((), jnp.int32)))
    return out, loads, reads


def held_experts_leaf(wg, wu):
    """The held experts' gate and up matrices [held, D, F] as the leaf
    `held_expert_ffn` multiplies by, [held, D, 2F]: gate in columns [:F], up in
    [F:].  Laid where the layer is drawn and not in a view beside the tree,
    which would hold both twice — and waited for: the host runs ahead of the
    draw, and every layer's `wg` and `wu` would stand beside its leaf until the
    device got to them (+2% of peak memory, PR 62)."""
    return jax.block_until_ready(jnp.concatenate([wg, wu], axis=-1))


def held_load_stats(held) -> list:
    """What a serve program reports of its expert layers (`held`: a list of
    `held_expert_ffn`'s (loads, reads), one a layer): [token-expert pairs
    that fell on held experts, the largest load of a held expert summed
    over the layers, held experts touched, held experts' visits by a trip
    of products], f32 scalars in the order the models' STEP_STATS name
    them (`moe_pairs`, `moe_load_max`, `moe_touched`, `moe_reads`); zeros
    where no layer routes.  `moe_reads` over `moe_touched` is how often a
    touched expert's weights were read: 1.0 unless an expert had more rows
    than a product takes."""
    if not held:
        return [jnp.zeros(())] * 4
    loads, reads = zip(*held)
    ld = jnp.stack(loads).astype(jnp.float32)              # [L_moe, held]
    return [ld.sum(), ld.max(axis=1).sum(),
            (ld > 0).sum().astype(jnp.float32),
            jnp.stack(reads).astype(jnp.float32).sum()]
