"""MoE: routing correctness, expert-parallel sharded training step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params, param_logical_axes
from ray_tpu.models.training import default_optimizer, make_train_step
from ray_tpu.ops.moe import MoEConfig, top_k_routing
from ray_tpu.parallel import MeshConfig, build_mesh

CFG = configs.TINY_MOE


def test_top_k_routing_shapes_and_capacity():
    rng = jax.random.key(0)
    logits = jax.random.normal(rng, (1, 16, 4))
    dispatch, combine, probs = top_k_routing(logits, k=2, capacity=4)
    assert dispatch.shape == (1, 16, 4, 4)
    assert combine.shape == (1, 16, 4, 4)
    # each expert's capacity slots hold at most one token
    per_slot = np.asarray(dispatch).sum(axis=1)  # (1, E, C)
    assert (per_slot <= 1.0 + 1e-6).all()
    # each token occupies at most k slots total
    per_token = np.asarray(dispatch).sum(axis=(2, 3))
    assert (per_token <= 2 + 1e-6).all()
    # combine weights for a token sum to <= 1 (==1 if none dropped)
    cw = np.asarray(combine).sum(axis=(2, 3))
    assert (cw <= 1.0 + 1e-5).all()


def test_moe_forward_finite_and_param_tree():
    params = init_params(jax.random.key(0), CFG)
    axes = param_logical_axes(CFG)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0,
                                CFG.vocab_size)
    aux = {}
    logits = forward(params, tokens, CFG, return_aux=aux)
    assert logits.shape == (2, 32, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert "moe_load_balance_loss" in aux
    assert float(aux["moe_load_balance_loss"]) > 0


def test_moe_training_step_expert_parallel():
    """Train step with experts sharded over the ep mesh axis."""
    mesh = build_mesh(MeshConfig(fsdp=2, ep=4))
    init_fn, step_fn = make_train_step(
        CFG, mesh, optimizer=default_optimizer(1e-2, warmup=1,
                                               total_steps=20))
    state = init_fn(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 33), 0,
                                          CFG.vocab_size)}
    first = None
    for _ in range(5):
        state, m = step_fn(state, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first
    # expert-sharded param really is distributed over ep
    wg = state.params["blocks"]["w_gate"]
    shard = wg.sharding.shard_shape(wg.shape)
    assert shard[1] == CFG.n_experts // 4  # ep=4


# ---------------------------------------------------------------------------
# the served expert FFN: a visit of the experts that live rows are routed to
# ---------------------------------------------------------------------------
def _dense_over_experts(x, params, cfg):
    """The plain reference: every expert's FFN over every token, combined
    with top-k weights that are zero for the experts a token did not
    choose (the form `moe_mlp_dropless` had before it visited the set).
    Returns (out (B,T,d), chosen expert ids (B,T,k))."""
    dtype = x.dtype
    logits = jnp.einsum("btd,de->bte", x, params["router"].astype(dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    w = jnp.sum(jax.nn.one_hot(expert_idx, cfg.num_experts,
                               dtype=jnp.float32)
                * gate_vals[..., None], axis=2)
    gate = jnp.einsum("btd,edf->btef", x, params["w_gate"].astype(dtype))
    up = jnp.einsum("btd,edf->btef", x, params["w_up"].astype(dtype))
    out_e = jnp.einsum("btef,efd->bted", jax.nn.silu(gate) * up,
                       params["w_down"].astype(dtype))
    out = jnp.einsum("bte,bted->btd", w, out_e.astype(jnp.float32))
    return out.astype(dtype), expert_idx


_LIVE = {"all": lambda s: np.ones((s,), bool),
         "some": lambda s: np.arange(s) % 4 == 0,   # one lane of four
         "none": lambda s: np.zeros((s,), bool)}


@pytest.mark.parametrize("live_kind", list(_LIVE))
@pytest.mark.parametrize("lanes,width", [(1, 1), (4, 1), (8, 1), (16, 1),
                                         (32, 1), (1, 16)],
                         ids=lambda v: str(v))
@pytest.mark.parametrize("n_experts,top_k", [(4, 2), (8, 2), (64, 8)])
def test_served_expert_ffn_visits_the_live_rows_experts(
        n_experts, top_k, lanes, width, live_kind):
    from ray_tpu.ops.moe import init_moe_params, moe_mlp_dropless

    d, f = 32, 64
    cfg = MoEConfig(num_experts=n_experts, top_k=top_k)
    params = init_moe_params(jax.random.key(n_experts), d, f, cfg,
                             jnp.bfloat16)
    x = jax.random.normal(jax.random.key(lanes * 100 + width),
                          (lanes, width, d), jnp.bfloat16)
    live = _LIVE[live_kind](lanes)
    served = jax.jit(lambda x, live: moe_mlp_dropless(
        x, params, cfg, live=live))
    got, visited = served(x, jnp.asarray(live))
    want, chosen = _dense_over_experts(x, params, cfg)

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    assert not got[~live].any()                 # an idle lane: zeros
    # the count is numpy's, of the distinct experts the live rows chose
    assert int(visited) == len(np.unique(np.asarray(chosen)[live]))
    if live.sum() * width == 1:                 # one live token: top_k trips
        assert int(visited) == top_k
    # an idle lane's token moves no live row
    other = np.asarray(x, np.float32)
    other[~live] = np.asarray(jax.random.normal(
        jax.random.key(7), other[~live].shape))
    moved, visited_2 = served(jnp.asarray(other, jnp.bfloat16),
                              jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(moved, np.float32)[live],
                                  got[live])
    assert int(visited_2) == int(visited)
    # asked for the routing, it gives the experts every row took and the
    # same output
    routed, n, taken = jax.jit(lambda x, live: moe_mlp_dropless(
        x, params, cfg, live=live, return_routing=True))(x, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(routed, np.float32), got)
    assert int(n) == int(visited) and taken.shape == (lanes, width, top_k)
    np.testing.assert_array_equal(np.asarray(taken), np.asarray(chosen))
    # no mask means every lane is live
    if live_kind == "all":
        unmasked, n = moe_mlp_dropless(x, params, cfg)
        np.testing.assert_array_equal(np.asarray(unmasked, np.float32), got)
        assert int(n) == int(visited)
    # the stacks of all layers and a layer's index: the same visit
    stacks = {k: (jnp.stack([v * 0, v, v * 0]) if k != "router" else v)
              for k, v in params.items()}
    stacked, n = jax.jit(lambda x, live, li: moe_mlp_dropless(
        x, stacks, cfg, live=live, layer=li))(x, jnp.asarray(live),
                                              jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(stacked, np.float32), got)
    assert int(n) == int(visited)
