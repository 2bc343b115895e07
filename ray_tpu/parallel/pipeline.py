"""SPMD pipeline parallelism: GPipe-style microbatching over the `pp` axis.

The reference has no in-tree PP; its substrate is compiled multi-actor
graphs with NCCL p2p channels (reference: dag/compiled_dag_node.py:664,
experimental/channel/torch_tensor_nccl_channel.py — SURVEY.md §2.3).  The
TPU-native equivalent is collective pipelining *inside one compiled
program*: every `pp` rank holds one stage's layers; microbatch activations
rotate stage-to-stage via `lax.ppermute` in a `lax.scan` steady-state loop,
and reverse-mode AD differentiates straight through the rotation (the
transpose of ppermute is the reverse ppermute — backward pipelining for
free).

Schedule: plain GPipe fill-drain over T = M + n - 1 ticks (bubble fraction
(n-1)/T); the scan body is one tick.  Deeper schedules (1F1B, interleaved)
are compiler-level refinements of the same loop.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_mb, mesh: Mesh,
                   axis_name: str = "pp"):
    """Run microbatches through the pipeline.

    stage_fn(local_params, x) -> x  : one stage's computation
    stage_params: pytree with a leading *stage* axis sized pp on every leaf
                  (sharded P(axis_name) outside)
    x_mb: pytree whose leaves are [M, mb, ...] microbatched inputs
          (replicated over pp) — a bare array works as before; a tuple
          lets side outputs (e.g. MoE router aux losses) ride the
          rotation with the activations
    returns: same pytree structure, leaves [M, mb, ...] from the final
             stage (replicated over pp)

    Only `axis_name` goes manual; dp/fsdp/tp/sp stay automatic inside, so
    the stage_fn's own sharding constraints keep working.
    """
    tmap = jax.tree.map
    n = mesh.shape[axis_name]
    if n == 1:
        params_local = tmap(lambda p: p[0], stage_params)
        return jax.lax.map(lambda mb: stage_fn(params_local, mb), x_mb)

    M = jax.tree.leaves(x_mb)[0].shape[0]
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def body(params_local, x_local):
        r = jax.lax.axis_index(axis_name)
        params_sq = tmap(lambda p: p[0], params_local)
        state = tmap(lambda l: jnp.zeros_like(l[0]), x_local)
        out_buf = tmap(jnp.zeros_like, x_local)

        def tick(carry, t):
            state, out_buf = carry
            # stage 0 picks up a fresh microbatch while the fill lasts
            mb_idx = jnp.minimum(t, M - 1)
            fresh = tmap(lambda l: jax.lax.dynamic_index_in_dim(
                l, mb_idx, 0, keepdims=False), x_local)
            inp = tmap(lambda f, s: jnp.where(r == 0, f, s), fresh, state)
            out = stage_fn(params_sq, inp)
            # last stage banks its result for microbatch t-(n-1)
            done_idx = jnp.clip(t - (n - 1), 0, M - 1)
            take = jnp.logical_and(r == n - 1, t >= n - 1)

            def bank(buf, o):
                upd = jax.lax.dynamic_update_index_in_dim(
                    buf, o.astype(buf.dtype), done_idx, 0)
                return jnp.where(take, upd, buf)

            out_buf = tmap(bank, out_buf, out)
            state = tmap(lambda o: jax.lax.ppermute(o, axis_name, fwd),
                         out)
            return (state, out_buf), None

        (state, out_buf), _ = jax.lax.scan(tick, (state, out_buf),
                                           jnp.arange(M + n - 1))
        # replicate final-stage outputs to all pp ranks; psum in f32 (XLA:CPU
        # miscompiles sub-f32 all-reduce in partial-manual regions, and on
        # TPU the f32 cast fuses into the collective anyway)
        mask = (jax.lax.axis_index(axis_name) == n - 1).astype(jnp.float32)
        return tmap(
            lambda b: jax.lax.psum(b.astype(jnp.float32) * mask,
                                   axis_name).astype(b.dtype),
            out_buf)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )(stage_params, x_mb)


def split_microbatches(x, num_microbatches: int):
    """Leaves [B, ...] -> [M, B/M, ...] over any pytree (pipeline_apply
    already accepts pytrees; a bare array is the one-leaf case)."""
    M = int(num_microbatches)

    def split_leaf(path, leaf):
        b = leaf.shape[0] if leaf.ndim else 0
        if leaf.ndim == 0 or b % M:
            where = jax.tree_util.keystr(path) or "<root>"
            raise ValueError(
                f"batch {b if leaf.ndim else '<scalar>'} at leaf {where} "
                f"(shape {tuple(leaf.shape)}) not divisible by "
                f"microbatches {M}")
        return leaf.reshape(M, b // M, *leaf.shape[1:])

    return jax.tree_util.tree_map_with_path(split_leaf, x)


def merge_microbatches(x):
    """Leaves [M, mb, ...] -> [B, ...] (inverse of split_microbatches)."""
    return jax.tree.map(
        lambda l: l.reshape(l.shape[0] * l.shape[1], *l.shape[2:]), x)
