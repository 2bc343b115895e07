"""Sharded train-step builder: DP/FSDP/TP/SP via GSPMD partition specs.

The reference's gradient sync is a runtime NCCL allreduce issued by torch
DDP/FSDP inside Train workers (reference: train/torch/config.py process
groups); here the entire step — forward, backward, gradient reduction,
optimizer update — is ONE compiled XLA program over the mesh: data-parallel
gradient psums, ZeRO-3 parameter all-gathers/reduce-scatters, TP collectives
and SP ring exchanges are all inserted by the partitioner from the sharding
annotations, riding ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import batch_sharding
from ray_tpu.parallel.sharding import Logical, spec_from_logical, tree_shardings
from ray_tpu.telemetry import device as devtel

from . import gpt


def _use_mesh(mesh: Mesh):
    # jax>=0.7 context-manager form; lets bare PartitionSpecs flow to
    # with_sharding_constraint inside the jitted step
    return jax.set_mesh(mesh)


def param_shardings(cfg: gpt.GPTConfig, mesh: Mesh):
    return tree_shardings(gpt.logical_axes(cfg), mesh)


def opt_state_shardings(tx, params_shape, p_shardings, mesh: Mesh):
    """Optimizer state mirrors param sharding where shapes match, else
    replicated (adam mu/nu get the ZeRO treatment for free)."""
    state_shape = jax.eval_shape(tx.init, params_shape)
    flat_params = {id_shape(l): s for l, s in zip(
        jax.tree.leaves(params_shape), jax.tree.leaves(p_shardings))}

    def assign(leaf):
        return flat_params.get(id_shape(leaf), NamedSharding(mesh, P()))

    return jax.tree.map(assign, state_shape)


def id_shape(l) -> Tuple:
    return (tuple(l.shape), str(l.dtype)) if hasattr(l, "shape") else ("s",)


def init_sharded(key, cfg: gpt.GPTConfig, mesh: Mesh):
    """Initialize parameters directly sharded on the mesh (no host copy of
    the full model — each device materializes only its shard)."""
    shardings = param_shardings(cfg, mesh)
    with _use_mesh(mesh):
        # once-per-run init: jit only for out_shardings materialization
        init_fn = devtel.jit(functools.partial(gpt.init, cfg=cfg),  # jax-ok
                             name="train.init_sharded",
                             out_shardings=shardings)
        return init_fn(key)


def make_train_step(cfg: gpt.GPTConfig, mesh: Mesh, tx=None,
                    donate: bool = True) -> Tuple[Callable, Callable]:
    """Returns (init_state_fn, step_fn), both jitted over the mesh.

    state = {"params", "opt_state", "step"}
    step_fn(state, batch) -> (state, metrics)
    """
    if tx is None:
        tx = optax.adamw(3e-4, weight_decay=0.1)
    p_shardings = param_shardings(cfg, mesh)
    key_shard = NamedSharding(mesh, P())
    b_shard = NamedSharding(mesh, P(("dp", "fsdp", "ep"), None))

    def init_state(key):
        params = gpt.init(key, cfg)
        opt_state = tx.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    params_shape = jax.eval_shape(functools.partial(gpt.init, cfg=cfg),
                                  jax.random.PRNGKey(0))
    o_shardings = opt_state_shardings(tx, params_shape, p_shardings, mesh)
    state_shardings = {"params": p_shardings, "opt_state": o_shardings,
                       "step": NamedSharding(mesh, P())}

    with _use_mesh(mesh):
        init_state_fn = devtel.jit(init_state, name="train.init_state",
                                   out_shardings=state_shardings)

    def step(state, batch):
        def loss(p):
            return gpt.loss_fn(p, batch, cfg, mesh)

        loss_val, grads = jax.value_and_grad(loss)(state["params"])
        updates, new_opt = tx.update(grads, state["opt_state"],
                                     state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        gnorm = optax.global_norm(grads)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1},
                {"loss": loss_val.astype(jnp.float32),
                 "grad_norm": gnorm.astype(jnp.float32)})

    with _use_mesh(mesh):
        step_fn = devtel.jit(
            step,
            name="train.step",
            in_shardings=(state_shardings, None),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )

    def wrapped_step(state, batch):
        with _use_mesh(mesh):
            return step_fn(state, batch)

    def lower(state, batch):
        """`train.step` lowered under the mesh, for reading the program
        (kernels, collectives, memory) without running it; arguments may
        be shapes."""
        with _use_mesh(mesh):
            return step_fn.lower(state, batch)

    wrapped_step.lower = lower

    def wrapped_init(key):
        with _use_mesh(mesh):
            return init_state_fn(key)

    return wrapped_init, wrapped_step


def make_eval_step(cfg: gpt.GPTConfig, mesh: Mesh):
    p_shardings = param_shardings(cfg, mesh)

    def eval_step(params, batch):
        return gpt.loss_fn(params, batch, cfg, mesh)

    with _use_mesh(mesh):
        fn = devtel.jit(eval_step, name="train.eval_step",
                        in_shardings=(p_shardings, None))

    def wrapped(params, batch):
        with _use_mesh(mesh):
            return fn(params, batch)

    return wrapped


def shard_batch(batch: Dict[str, Any], mesh: Mesh):
    """Place a host batch onto the mesh with canonical batch sharding."""
    sh = NamedSharding(mesh, P(("dp", "fsdp", "ep"), None))
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)
