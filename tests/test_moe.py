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
                                         (32, 1), (1, 16),
                                         # rows grouped by expert where
                                         # the experts are many
                                         (1, 128), (4, 32), (64, 1)],
                         ids=lambda v: str(v))
@pytest.mark.parametrize("n_experts,top_k", [(4, 2), (8, 2), (64, 8)])
def test_served_expert_ffn_visits_the_live_rows_experts(
        n_experts, top_k, lanes, width, live_kind):
    from ray_tpu.ops.moe import (
        grouped_tile_rows, init_moe_params, moe_mlp_dropless)

    d, f = 32, 64
    cfg = MoEConfig(num_experts=n_experts, top_k=top_k)
    params = init_moe_params(jax.random.key(n_experts), d, f, cfg,
                             jnp.bfloat16)
    x = jax.random.normal(jax.random.key(lanes * 100 + width),
                          (lanes, width, d), jnp.bfloat16)
    live = _LIVE[live_kind](lanes)
    assert bool(grouped_tile_rows(lanes * width, cfg)) == (
        lanes * width >= 64 and lanes * width * n_experts >= 2048)
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


# ---------------------------------------------------------------------------
# a launch of many rows: the rows' choices grouped by expert
# ---------------------------------------------------------------------------
def _plain_routed_sum(x, params, cfg, live=None):
    """The plain reference with everything `MoEConfig` states: every held
    expert's FFN over every row, combined with the top-k gates of the
    configuration's scoring, zero for an expert a row did not choose, for
    one held elsewhere and for a row that is not live ((B, T) bool).
    Returns (out (B,T,d), chosen expert ids (B,T,k), the combine weights
    over the held experts (B,T,held))."""
    dtype = x.dtype
    logits = jnp.einsum("btd,de->bte", x, params["router"].astype(dtype))
    if cfg.scoring == "softmax":
        scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        picked = scores
    else:
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        picked = scores + params["router_bias"].astype(jnp.float32)
    _, expert_idx = jax.lax.top_k(picked, cfg.top_k)
    gates = jnp.take_along_axis(scores, expert_idx, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * cfg.route_scale
    w = jnp.sum(jax.nn.one_hot(expert_idx, cfg.num_experts,
                               dtype=jnp.float32) * gates[..., None], axis=2)
    if live is not None:
        w = w * live[..., None]
    first, count = cfg.held or (0, cfg.num_experts)
    w = w[..., first:first + count]
    gate = jnp.einsum("btd,edf->btef", x, params["w_gate"].astype(dtype))
    up = jnp.einsum("btd,edf->btef", x, params["w_up"].astype(dtype))
    out_e = jnp.einsum("btef,efd->bted", jax.nn.silu(gate) * up,
                       params["w_down"].astype(dtype))
    out = jnp.einsum("bte,bted->btd", w, out_e.astype(jnp.float32))
    return out.astype(dtype), expert_idx, w


def _as_the_visit(monkeypatch, fn):
    """`fn()` traced with no launch wide enough to group its rows."""
    from ray_tpu.ops import moe

    with monkeypatch.context() as m:
        m.setattr(moe, "_GROUPED_FROM_ROWS", 1 << 30)
        return fn()


_SCORINGS = {"softmax": {},
             "sigmoid": {"scoring": "sigmoid", "route_scale": 1.8}}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("scoring", list(_SCORINGS))
@pytest.mark.parametrize("held", [None, (8, 16)], ids=["all", "share"])
@pytest.mark.parametrize("rows", [32, 128, 512])
def test_a_wide_launch_groups_its_rows_by_expert(
        rows, held, scoring, dtype, monkeypatch):
    """A chunk of `rows` rows, the last 9 a padded tail (`live` by row),
    top-4 of 32 experts of which all or 16 are held: 32 rows visit, 128
    and 512 are grouped, and both give the plain reference's sum (float32:
    to rounding) and each other's, count what the visit counts and take
    the stacks of all layers with the layer's index."""
    from ray_tpu.ops.moe import (
        grouped_tile_rows, init_moe_params, moe_mlp_dropless)

    d, f = 32, 64
    cfg = MoEConfig(num_experts=32, top_k=4, held=held, **_SCORINGS[scoring])
    params = init_moe_params(jax.random.key(rows), d, f, cfg, dtype)
    if scoring == "sigmoid":
        params["router_bias"] = 0.3 * jax.random.normal(
            jax.random.key(5), (32,), jnp.float32)
    if held:
        params = {k: v if k.startswith("router")
                  else v[held[0]:held[0] + held[1]]
                  for k, v in params.items()}
    x = jax.random.normal(jax.random.key(rows + 1), (1, rows, d), dtype)
    live = jnp.asarray(np.arange(rows) < rows - 9)[None]
    tile = grouped_tile_rows(rows, cfg)
    assert tile == {32: 0, 128: 32, 512: 128}[rows]

    def run(**kw):
        return jax.jit(lambda x, live: moe_mlp_dropless(
            x, params, cfg, live=live, **kw))(x, live)

    got, visited, taken, counted = run(return_routing=True,
                                       return_routed=True)
    want, chosen, w = _plain_routed_sum(x, params, cfg, live)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got32 = np.asarray(got, np.float32)
    np.testing.assert_allclose(got32, np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    assert not got32[0, rows - 9:].any()            # the tail: zeros
    np.testing.assert_array_equal(np.asarray(taken), np.asarray(chosen))
    assert int(visited) == int((np.asarray(w).sum((0, 1)) > 0).sum())
    here = int((np.asarray(w) > 0).sum())
    # the visit: the same sum, the same counts; the grouped form also
    # the tiles it multiplied, at least one a held expert that was hit
    v_got, v_visited, v_counted = _as_the_visit(
        monkeypatch, lambda: run(return_routed=True))
    np.testing.assert_allclose(got32, np.asarray(v_got, np.float32),
                               atol=tol, rtol=tol)
    assert int(v_visited) == int(visited) and int(v_counted) == here
    if tile:
        routed, tiles = np.asarray(counted)
        assert routed == here
        assert int(visited) <= tiles <= here // tile + int(visited)
    else:
        assert int(counted) == here
    # without the counts and the routing: the same output
    plain, n = run()
    np.testing.assert_array_equal(np.asarray(plain, np.float32), got32)
    assert int(n) == int(visited)
    # the stacks of all layers and a layer's index: the same launch
    stacks = {k: v if k.startswith("router")
              else jnp.stack([v * 0, v, v * 0]) for k, v in params.items()}
    stacked, n = jax.jit(lambda x, live, li: moe_mlp_dropless(
        x, stacks, cfg, live=live, layer=li))(x, live, jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(stacked, np.float32), got32)
    assert int(n) == int(visited)


# rows' scores by hand: the router is the identity on the first E features
_BY_HAND = {
    # every row takes experts 0 and 1: two groups of 256 rows in tiles of
    # 128, six experts that no row chose
    "two experts take every row": dict(
        e=8, k=2, rows=256, score=lambda r, e: (e >= 2) * -9.0 + e * 0.1,
        visited=2, tiles=4, tile=128),
    # top-1 and every row on expert 3
    "one expert takes every row": dict(
        e=4, k=1, rows=512, score=lambda r, e: (e == 3) * 9.0,
        visited=1, tiles=2, tile=256),
    # rows 0..191 on experts (0, 1), the rest on (6, 7): groups of 192
    # (two tiles of 128, the second ragged: half its slots hold no row)
    # and of 64, experts 2..5 unread
    "a ragged second tile": dict(
        e=8, k=2, rows=256,
        score=lambda r, e: np.where(r < 192, (e < 2), (e >= 6)) * 9.0
        + e * 0.1, visited=4, tiles=6, tile=128),
}


@pytest.mark.parametrize("case", list(_BY_HAND))
def test_groups_larger_than_a_tile_and_experts_no_row_chose(
        case, monkeypatch):
    from ray_tpu.ops.moe import (
        grouped_tile_rows, init_moe_params, moe_mlp_dropless)

    c = _BY_HAND[case]
    e, rows, d, f = c["e"], c["rows"], 32, 64
    cfg = MoEConfig(num_experts=e, top_k=c["k"])
    assert grouped_tile_rows(rows, cfg) == c["tile"]
    params = init_moe_params(jax.random.key(3), d, f, cfg, jnp.float32)
    params["router"] = jnp.eye(d, e, dtype=jnp.float32)
    x = np.array(jax.random.normal(jax.random.key(4), (1, rows, d)))
    x[0, :, :e] = c["score"](np.arange(rows)[:, None], np.arange(e)[None])
    x = jnp.asarray(x)

    def run():
        return jax.jit(lambda x: moe_mlp_dropless(
            x, params, cfg, return_routed=True))(x)

    got, visited, (routed, tiles) = run()
    want, _, _ = _plain_routed_sum(x, params, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert (int(visited), int(tiles)) == (c["visited"], c["tiles"])
    assert int(routed) == rows * c["k"]
    v_got, v_visited, v_routed = _as_the_visit(monkeypatch, run)
    np.testing.assert_allclose(np.asarray(got), np.asarray(v_got),
                               atol=2e-5, rtol=2e-5)
    assert int(v_visited) == c["visited"] and int(v_routed) == int(routed)


def test_a_chunk_and_a_decode_step_compute_one_function():
    """The rows of a 256-row chunk, grouped by expert, four by four
    through the visit (a decode step's launch): the same rows come out."""
    from ray_tpu.ops.moe import (
        grouped_tile_rows, init_moe_params, moe_mlp_dropless)

    cfg = MoEConfig(num_experts=16, top_k=4, held=(4, 8),
                    scoring="sigmoid", route_scale=2.5)
    params = init_moe_params(jax.random.key(0), 32, 64, cfg, jnp.float32)
    params = {k: v if k == "router" else v[4:12] for k, v in params.items()}
    x = jax.random.normal(jax.random.key(1), (1, 256, 32), jnp.float32)
    assert grouped_tile_rows(256, cfg) and not grouped_tile_rows(4, cfg)
    chunk, _ = jax.jit(lambda x: moe_mlp_dropless(x, params, cfg))(x)
    step = jax.jit(lambda x: moe_mlp_dropless(x, params, cfg)[0])
    steps = jnp.concatenate([step(x[0, i:i + 4, None])[:, 0]
                             for i in range(0, 256, 4)])
    np.testing.assert_allclose(np.asarray(chunk[0]), np.asarray(steps),
                               atol=2e-5, rtol=2e-5)


# what a TPU runs in place of the loop of tiles: this repo's kernel
_KERNEL_CASES = {
    "every expert held": dict(e=32, k=4, rows=128, held=None, tail=0),
    "a share, sigmoid scores, a padded tail": dict(
        e=16, k=4, rows=256, held=(4, 8), tail=9, scoring="sigmoid",
        route_scale=2.5),
    "wide tiles": dict(e=4, k=2, rows=512, held=None, tail=3),
    "one expert takes every row": dict(e=4, k=1, rows=512, held=None,
                                       tail=0, one=3),
}


@pytest.mark.parametrize("layered", [False, True], ids=["a layer", "stacks"])
@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_the_tile_kernel_agrees_with_the_loop_of_tiles(
        case, layered, monkeypatch):
    """`_fused_ffn_kernel` (the grid over tiles, a tile's rows picked and
    its results summed inside it) against the loop of tiles that every
    other platform runs, on the CPU in Pallas's TPU interpret mode, in
    bfloat16 as it is served: the same sum (to the rounding of a row's
    float32 sum in another order), the same counts, zeros for a row that
    is not live, and the plain reference's sum."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import moe
    from ray_tpu.ops.moe import init_moe_params, moe_mlp_dropless

    c = _KERNEL_CASES[case]
    e, rows, held, d, f = c["e"], c["rows"], c["held"], 128, 256
    cfg = MoEConfig(num_experts=e, top_k=c["k"], held=held,
                    scoring=c.get("scoring", "softmax"),
                    route_scale=c.get("route_scale", 1.0))
    params = init_moe_params(jax.random.key(2), d, f, cfg, jnp.bfloat16)
    if cfg.scoring == "sigmoid":
        params["router_bias"] = 0.3 * jax.random.normal(
            jax.random.key(5), (e,), jnp.float32)
    x = jax.random.normal(jax.random.key(6), (1, rows, d), jnp.bfloat16)
    if "one" in c:
        params["router"] = jnp.zeros((d, e), jnp.bfloat16).at[
            :, c["one"]].set(1.0)
        x = jnp.abs(x)
    if held:
        params = {k: v if k.startswith("router")
                  else v[held[0]:held[0] + held[1]]
                  for k, v in params.items()}
    live = jnp.asarray(np.arange(rows) < rows - c["tail"])[None]
    want, _, _ = _plain_routed_sum(x, params, cfg, live)
    layer = None
    if layered:
        params = {k: v if k.startswith("router")
                  else jnp.stack([v * 0, v, v * 0])
                  for k, v in params.items()}
        layer = jnp.int32(1)
    assert moe._kernel_takes(x[0], params["w_gate"],
                             moe.grouped_tile_rows(rows, cfg))

    def run():
        return jax.jit(lambda x, live: moe_mlp_dropless(
            x, params, cfg, live=live, layer=layer, return_routed=True))(
                x, live)

    loop, visited, counted = run()
    calls = []

    def as_for_a_tpu(*args, tpu, default):
        calls.append(tpu)
        return tpu(*args)

    moe._grouped.clear_cache()      # jitted: else the loop's trace again
    try:
        with monkeypatch.context() as m, pltpu.force_tpu_interpret_mode():
            m.setattr(jax.lax, "platform_dependent", as_for_a_tpu)
            kernel, k_visited, k_counted = run()
    finally:
        moe._grouped.clear_cache()
    assert len(calls) == 1
    assert kernel.dtype == loop.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(k_counted), np.asarray(counted))
    assert int(k_visited) == int(visited)
    loop, kernel = (np.asarray(a, np.float32) for a in (loop, kernel))
    assert not kernel[0, rows - c["tail"]:].any() or not c["tail"]
    np.testing.assert_allclose(kernel, loop, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(kernel, np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)
    if "one" in c:
        assert int(visited) == 1 and int(counted[1]) == 2


# -- group-limited routing -------------------------------------------------------
# sha256 of the StableHLO text of a sigmoid-scored layer that holds a share,
# taken on the parent's tree (commit 3c96652): a launch of 4 rows (the
# visit) and one of 256 (rows grouped by expert).
_ONE_GROUP_AT_THE_PARENT = {4: "04b99dc734b3be50", 256: "74f268b0c76ad236"}


def _grouped_oracle(logits, bias, n_groups, kept, k, scale):
    """NumPy: (kept groups, taken experts, gates) a row, ties by the lower
    index: a group's score the sum of its two largest z, the `kept` groups
    of largest score, the k largest z inside them, gates the unbiased
    scores renormalised over the k taken x scale."""
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    z = (s + bias).astype(np.float32)
    size = z.shape[-1] // n_groups
    groups, taken, gates = [], [], []
    for row_s, row_z in zip(s, z):
        by_group = row_z.reshape(n_groups, size)
        score = np.sort(by_group, axis=-1)[:, -2:].sum(-1, dtype=np.float32)
        keep = np.argsort(-score, kind="stable")[:kept]
        inside = np.where(np.isin(np.arange(z.shape[-1]) // size, keep),
                          row_z, -np.inf)
        took = np.argsort(-inside, kind="stable")[:k]
        groups.append(np.sort(keep))
        taken.append(took)
        gates.append(scale * row_s[took] / row_s[took].sum())
    return np.array(groups), np.array(taken), np.array(gates)


@pytest.mark.parametrize("rows", [8, 512], ids=["visit", "grouped"])
@pytest.mark.parametrize("held", [None, (0, 4), (12, 8)],
                         ids=["all", "half a group", "across two groups"])
def test_group_limited_routing_is_the_oracle_s(held, rows):
    """32 experts in 4 groups of 8, 2 kept, top-4 x 2.5; the router's
    logits and its bias lie on a grid of quarters, so that scores tie
    (the logits ride in a row's last 32 columns, which the router copies
    out): the kept groups, the experts taken, the routed sum with the
    oracle's gates, the choices that fell here and the rows whose kept
    groups hold a held expert."""
    from ray_tpu.ops.moe import (
        grouped_tile_rows, init_moe_params, moe_mlp_dropless)

    e, d, f = 32, 48, 24
    cfg = MoEConfig(num_experts=e, top_k=4, held=held, scoring="sigmoid",
                    route_scale=2.5, n_groups=4, groups_kept=2)
    assert bool(grouped_tile_rows(rows, cfg)) == (rows == 512)
    first, count = held or (0, e)
    params = init_moe_params(jax.random.key(0), d, f, cfg, jnp.float32)
    params = {k: v[first:first + count] for k, v in params.items()
              if k != "router"}
    rng = np.random.default_rng(1)
    logits = np.round(rng.normal(size=(rows, e)) * 4) / 4
    bias = (np.round(rng.normal(size=(e,)) * 8) / 16).astype(np.float32)
    x = np.concatenate([rng.normal(size=(rows, d - e)) * 0.5, logits],
                       -1).astype(np.float32)
    params["router"] = jnp.concatenate([jnp.zeros((d - e, e)), jnp.eye(e)])
    params["router_bias"] = jnp.asarray(bias)
    live = np.arange(rows) < rows - 3
    out, visited, taken, routed = jax.jit(lambda x: moe_mlp_dropless(
        x, params, cfg, live=jnp.asarray(live)[None], return_routing=True,
        return_routed=True))(jnp.asarray(x)[None])
    groups, want, gates = _grouped_oracle(logits, bias, 4, 2, 4, 2.5)
    taken = np.asarray(taken[0])
    np.testing.assert_array_equal(taken[:, :4], want)
    np.testing.assert_array_equal(np.sort(taken[:, 4:], axis=-1), groups)
    ref = np.zeros((rows, d), np.float32)
    for r in np.flatnonzero(live):
        for ex, g in zip(want[r], gates[r]):
            if first <= ex < first + count:
                wg, wu, wd = (np.asarray(params[k][ex - first])
                              for k in ("w_gate", "w_up", "w_down"))
                h = x[r] @ wg
                ref[r] += g * ((h / (1 + np.exp(-h)) * (x[r] @ wu)) @ wd)
    np.testing.assert_allclose(np.asarray(out[0]), ref, atol=2e-5, rtol=2e-5)
    here = (want >= first) & (want < first + count) & live[:, None]
    size = e // 4
    open_rows = live & np.array([
        any(first // size <= g <= (first + count - 1) // size for g in row)
        for row in groups])
    routed = np.asarray(routed)
    assert routed.shape == (3,)
    assert routed[0] == here.sum() and routed[2] == open_rows.sum()
    assert (routed[1] > 0) == (rows == 512)
    assert int(visited) == len(set(want[here].tolist()))
    if held is None:
        assert routed[2] == live.sum()
    elif rows == 512:
        assert 0 < routed[2] < live.sum()


def test_one_group_lowers_to_the_text_before_there_were_groups():
    """`n_groups` = `groups_kept` = 1 is today's routing: the program a
    sigmoid-scored layer lowered to on the parent's tree (commit 3c96652,
    digests taken there), a launch that visits and one that groups."""
    import hashlib

    from ray_tpu.ops.moe import init_moe_params, moe_mlp_dropless

    cfg = MoEConfig(num_experts=16, top_k=4, held=(4, 8), scoring="sigmoid",
                    route_scale=2.5, n_groups=1, groups_kept=1)
    assert cfg == MoEConfig(num_experts=16, top_k=4, held=(4, 8),
                            scoring="sigmoid", route_scale=2.5)
    params = jax.eval_shape(lambda: init_moe_params(
        jax.random.key(0), 32, 64, cfg, jnp.float32))
    params = {k: v if k == "router" else jax.ShapeDtypeStruct(
        (8,) + v.shape[1:], v.dtype) for k, v in params.items()}
    params["router_bias"] = jax.ShapeDtypeStruct((16,), jnp.float32)
    got = {}
    for rows in (4, 256):
        lowered = jax.jit(lambda x, p: moe_mlp_dropless(
            x, p, cfg, return_routing=True, return_routed=True)).lower(
            jax.ShapeDtypeStruct((1, rows, 32), jnp.float32), params)
        got[rows] = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()[:16]
    assert got == _ONE_GROUP_AT_THE_PARENT


def test_groups_that_cannot_be_are_refused():
    with pytest.raises(ValueError, match="sigmoid"):
        MoEConfig(num_experts=16, top_k=4, n_groups=4, groups_kept=2)
    with pytest.raises(ValueError, match="does not divide"):
        MoEConfig(num_experts=16, top_k=4, scoring="sigmoid", n_groups=3,
                  groups_kept=2)
    with pytest.raises(ValueError, match="must hold"):
        MoEConfig(num_experts=16, top_k=4, scoring="sigmoid", n_groups=8,
                  groups_kept=1)
    with pytest.raises(ValueError, match="must hold"):
        MoEConfig(num_experts=16, top_k=4, scoring="sigmoid", n_groups=4,
                  groups_kept=5)
