"""Pipeline parallelism: GPipe loss/grads must match the single-stage model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import configs
from ray_tpu.models.transformer import init_params, loss_fn
from ray_tpu.parallel.pipeline import (
    build_pipeline_mesh, dryrun_pipeline, make_pipeline_loss,
    make_pipeline_train_step)


def tiny_cfg(n_layers=4, compute_dtype=jnp.bfloat16):
    return dataclasses.replace(
        configs.TINY, n_layers=n_layers, d_model=32, d_ff=64,
        n_heads=4, n_kv_heads=4, vocab_size=128, remat=False,
        compute_dtype=compute_dtype)


def reference_loss(cfg):
    """`loss_fn` of `cfg` under `jit`, as a train step runs it: op by op
    the same arithmetic costs a compile an op (and the pipelined loss's
    gradient 25 s where the jitted one is 2)."""
    return jax.jit(lambda params, batch: loss_fn(params, batch, cfg))


def pipeline_loss(cfg, mesh, n_micro):
    return jax.jit(make_pipeline_loss(cfg, mesh, n_micro))


def make_batch(key, cfg, batch=8, seq=16):
    tokens = jax.random.randint(key, (batch, seq + 1), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    return {"tokens": tokens}


@pytest.mark.parametrize("pp,n_micro", [(2, 2), (4, 4), (2, 4)])
def test_pipeline_loss_matches_reference(pp, n_micro):
    cfg = tiny_cfg(n_layers=4)
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(jax.random.key(1), cfg)

    ref = reference_loss(cfg)(params, batch)
    mesh = build_pipeline_mesh(pp, dp=1)
    pl = pipeline_loss(cfg, mesh, n_micro)(params, batch)
    np.testing.assert_allclose(float(pl), float(ref), rtol=2e-4)


def test_pipeline_grads_match_reference():
    # f32 compute: bf16 would add reordering noise bigger than the check.
    cfg = tiny_cfg(n_layers=4, compute_dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(jax.random.key(1), cfg)

    g_ref = jax.jit(jax.grad(reference_loss(cfg)))(params, batch)
    mesh = build_pipeline_mesh(2, dp=1)
    g_pp = jax.jit(jax.grad(make_pipeline_loss(cfg, mesh, 2)))(
        params, batch)

    flat_ref, _ = jax.tree.flatten(g_ref)
    flat_pp, _ = jax.tree.flatten(g_pp)
    for a, b in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=2e-5)


def test_pipeline_with_dp_axis():
    cfg = tiny_cfg(n_layers=2)
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(jax.random.key(1), cfg)

    ref = reference_loss(cfg)(params, batch)
    mesh = build_pipeline_mesh(2, dp=2)
    pl = pipeline_loss(cfg, mesh, 2)(params, batch)
    np.testing.assert_allclose(float(pl), float(ref), rtol=2e-4)


def test_pipeline_masked_loss_matches_reference():
    cfg = tiny_cfg(n_layers=2)
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(jax.random.key(1), cfg)
    tgt_shape = (batch["tokens"].shape[0], batch["tokens"].shape[1] - 1)
    batch["mask"] = (jax.random.uniform(jax.random.key(2), tgt_shape)
                     > 0.3).astype(jnp.float32)

    ref = reference_loss(cfg)(params, batch)
    mesh = build_pipeline_mesh(2, dp=1)
    pl = pipeline_loss(cfg, mesh, 2)(params, batch)
    np.testing.assert_allclose(float(pl), float(ref), rtol=1e-3)


def test_pipeline_train_step_runs_and_learns():
    cfg = tiny_cfg(n_layers=2)
    mesh = build_pipeline_mesh(2, dp=1)
    init_fn, step_fn = make_pipeline_train_step(
        cfg, mesh, n_microbatches=2, optimizer=optax.adam(1e-2))
    state = init_fn(jax.random.key(0))
    batch = make_batch(jax.random.key(1), cfg)
    losses = []
    for _ in range(5):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert int(state.step) == 5
    assert losses[-1] < losses[0]


def test_dryrun_pipeline():
    dryrun_pipeline(len(jax.devices()))
