"""Sharded training step: pjit over a mesh, logical-rule param layout.

This is the TPU-native replacement for the reference's DDP/FSDP wrap +
NCCL allreduce (reference: python/ray/train/torch/train_loop_utils.py:158
`prepare_model`, train/torch/config.py:112 process-group setup): gradients
are never "all-reduced" by the framework — the mesh sharding of params and
batch makes XLA insert the right psum/reduce-scatter/all-gather over ICI.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import (
    TransformerConfig, init_params, loss_fn, param_logical_axes)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES, LogicalRules, logical_to_mesh, param_shardings)
from ray_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


def default_optimizer(lr: float = 3e-4, *, warmup: int = 100,
                      total_steps: int = 10000, weight_decay: float = 0.1,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), lr * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def make_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    *,
    optimizer: optax.GradientTransformation | None = None,
    rules: LogicalRules = DEFAULT_RULES,
    seq_shards: int | None = None,
) -> tuple[Callable[..., TrainState], Callable[..., tuple[TrainState, dict]]]:
    """Returns (init_fn(rng) -> TrainState, step_fn(state, batch) -> (state, metrics)),
    both jitted against `mesh` with logical-rule shardings.

    The whole state has a declared layout, going out of `init_fn` and
    out of every step: parameters by the rule table, and each optimizer
    leaf that mirrors a parameter (Adam's moments) laid out like it.
    Left to propagation XLA replicates the moments — they are zeros, a
    function of no sharded input — and the step then all-reduces full
    gradients instead of reduce-scattering them.
    """
    optimizer = optimizer or default_optimizer()
    if seq_shards is None:
        seq_shards = dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS_SEQ, 1)
    p_shard = param_shardings(param_logical_axes(cfg), mesh, rules)

    def init(rng) -> TrainState:
        params = init_params(rng, cfg)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    abstract = jax.eval_shape(init, jax.random.key(0))
    replicated = NamedSharding(mesh, P())
    # Factored moments (adafactor) keep the parameter tree but not the
    # parameter shapes; a layout only carries over to an equal shape.
    opt_shard = optax.tree_utils.tree_map_params(
        optimizer,
        lambda leaf, like, sharding: (
            sharding if leaf.shape == like.shape else replicated),
        abstract.opt_state, abstract.params, p_shard,
        transform_non_params=lambda _: replicated)
    state_shard = TrainState(step=replicated, params=p_shard,
                             opt_state=opt_shard)

    loss = functools.partial(loss_fn, cfg=cfg, rules=rules, mesh=mesh,
                             seq_shards=seq_shards)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        l, grads = jax.value_and_grad(loss)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = {"loss": l, "grad_norm": optax.global_norm(grads),
                   "step": state.step + 1}
        return TrainState(state.step + 1, params, opt_state), metrics

    with mesh:
        init_fn = jax.jit(init, out_shardings=state_shard)
        step_fn = jax.jit(step, donate_argnums=(0,),
                          out_shardings=(state_shard, replicated))
    return init_fn, step_fn


def make_eval_step(cfg: TransformerConfig, mesh: Mesh, *,
                   rules: LogicalRules = DEFAULT_RULES, seq_shards: int = 1):
    loss = functools.partial(loss_fn, cfg=cfg, rules=rules, mesh=mesh,
                             seq_shards=seq_shards)
    with mesh:
        return jax.jit(lambda params, batch: loss(params, batch))
