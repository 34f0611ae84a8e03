"""Transformer forward/loss/train-step under sharded meshes (8 CPU devices)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params, loss_fn, param_logical_axes
from ray_tpu.models.training import (
    default_optimizer, make_eval_step, make_train_step)
from ray_tpu.parallel import MeshConfig, build_mesh, param_shardings
from ray_tpu.parallel.sharding import DDP_RULES, DEFAULT_RULES

CFG = configs.TINY


def _batch(rng, b=4, t=32, vocab=CFG.vocab_size):
    return {"tokens": jax.random.randint(rng, (b, t + 1), 0, vocab)}


def test_param_tree_matches_logical_tree():
    params = init_params(jax.random.key(0), CFG)
    axes = param_logical_axes(CFG)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # every logical tuple has the same rank as its param
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a), (p.shape, a)


def test_forward_shapes_and_finite():
    params = init_params(jax.random.key(0), CFG)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_loss_decreases_under_training():
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2))
    init_fn, step_fn = make_train_step(
        CFG, mesh, optimizer=default_optimizer(1e-2, warmup=1, total_steps=50))
    state = init_fn(jax.random.key(0))
    batch = _batch(jax.random.key(1))
    first = None
    for _ in range(8):
        state, metrics = step_fn(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first
    assert int(state.step) == 8


def test_ddp_and_fsdp_rules_agree():
    """Same init, same batch, one step under DDP vs FSDP rules → same loss."""
    losses = {}
    for name, rules in [("ddp", DDP_RULES), ("fsdp", DEFAULT_RULES)]:
        mesh = build_mesh(MeshConfig(fsdp=8))
        init_fn, step_fn = make_train_step(
            CFG, mesh, rules=rules,
            optimizer=default_optimizer(1e-3, warmup=1, total_steps=50))
        state = init_fn(jax.random.key(0))
        _, metrics = step_fn(state, _batch(jax.random.key(1)))
        losses[name] = float(metrics["loss"])
    assert losses["ddp"] == pytest.approx(losses["fsdp"], rel=1e-4)


def test_sequence_parallel_forward_matches():
    cfg = dataclasses.replace(CFG, n_kv_heads=CFG.n_heads)  # sp path, MHA
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    ref = forward(params, tokens, cfg)

    mesh = build_mesh(MeshConfig(fsdp=2, sp=4))
    shardings = param_shardings(param_logical_axes(cfg), mesh)
    sharded_params = jax.tree.map(jax.device_put, params, shardings)
    with mesh:
        out = jax.jit(
            lambda p, t: forward(p, t, cfg, mesh=mesh, seq_shards=4)
        )(sharded_params, tokens)
    # bf16 compute: blockwise (ring) vs full softmax reduction order differ.
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=1e-1)


# The default `seq_shards == 1` path on a mesh: attention runs inside
# shard_map on per-device shards (a Pallas kernel cannot be partitioned
# by XLA).  B splits over dp/fsdp and H over tp; T must stay whole even
# when the mesh has sp > 1, because the plain kernel's causal mask is
# local to the block it is handed.
_PLAIN_MESHES = {
    "fsdp4": (MeshConfig(fsdp=4), 4),
    "fsdp2_sp4": (MeshConfig(fsdp=2, sp=4), 8),
    "dp2_tp2_sp2": (MeshConfig(dp=2, tp=2, sp=2), 8),
}


@pytest.mark.parametrize("name", list(_PLAIN_MESHES))
def test_plain_attention_forward_on_mesh_matches_single_device(name):
    mesh_cfg, n_dev = _PLAIN_MESHES[name]
    params = init_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, CFG.vocab_size)
    ref = forward(params, tokens, CFG)

    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:n_dev])
    shardings = param_shardings(param_logical_axes(CFG), mesh)
    sharded_params = jax.tree.map(jax.device_put, params, shardings)
    with mesh:
        fwd = jax.jit(lambda p, t: forward(p, t, CFG, mesh=mesh))
        out = fwd(sharded_params, tokens)
        assert "shard_map" in str(jax.make_jaxpr(fwd)(sharded_params, tokens))
    assert len(out.sharding.device_set) == n_dev
    # bf16 compute; only the reduction order of sharded matmuls differs
    # (a bf16 step at |logit| ~ 8 is 0.06).  Attention over T/sp blocks
    # is off by whole units.
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=1e-1)


def test_eval_step_default_seq_shards_on_sp_mesh_matches_single_device():
    """`make_eval_step(cfg, mesh)` defaults to seq_shards=1 whatever the
    mesh: on sp=4 its loss is the unsharded loss, not that of four causal
    blocks attended separately."""
    params = init_params(jax.random.key(0), CFG)
    batch = _batch(jax.random.key(1), b=4, t=64)
    ref = float(loss_fn(params, batch, CFG))

    mesh = build_mesh(MeshConfig(fsdp=2, sp=4))
    shardings = param_shardings(param_logical_axes(CFG), mesh)
    sharded_params = jax.tree.map(jax.device_put, params, shardings)
    got = float(make_eval_step(CFG, mesh)(sharded_params, batch))
    assert got == pytest.approx(ref, rel=1e-3)


def test_gqa_matches_mha_when_kv_repeated():
    cfg = dataclasses.replace(CFG, n_kv_heads=2, n_heads=4)
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.ones((1, 8), jnp.int32)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (1, 8, cfg.vocab_size)


def test_num_params_property():
    cfg = configs.GPT2_124M
    params = init_params(jax.random.key(0), cfg)
    n = sum(p.size for p in jax.tree.leaves(params))
    assert n == cfg.num_params
