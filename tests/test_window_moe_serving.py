"""A layer pattern in the homogeneous stack on the served path
(`TransformerConfig.layer_pattern`: three window layers to one full layer
with its own YaRN rope, a head size the width does not give, QK-norm,
eight small experts top-4), held to the mellum family's plain float32
reference (`bench/families/mellum.py`, which imports nothing of the
program) and to `transformer.forward`; its slots hold a block table for
the full layers and a ring of window KV for each window layer.  The served
contract's cases are `tests/served_contract.py`'s."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from bench.harness import reference
from ray_tpu.models import configs, decoding, init_params
from ray_tpu.models.transformer import forward
from ray_tpu.serve.llm import PagedLLMEngine
from served_contract import Family, Teeth, on_the_engine, seqs

FAM = Family(
    tiny="mellumfamily/configs/tinymellum-serve.json",
    registry="tiny-window-moe",
    as_registry=dict(param_dtype=contract.FLOAT32,
                     compute_dtype=contract.FLOAT32),
    published=("mellum2-12b", 1e7, 1215),               # "12B"
    leaves=("tiny-window-moe", None), own_init=False,
    front=("windowmoe", {}), slot_leaves=("wk", "wv"), written=("wk", "wv"),
    refusals=dict(speculation_k="rows of the window rings",
                  export_streams="export_streams.*ring of",
                  import_prefix="import_prefix.*ring of",
                  frame=(2, 2, 4, 8, 2, 16),
                  deployment="ring of window KV by slot"),
    # The family's LOGITS_REL_EXPERTS was measured at the published widths;
    # at a width of 48 bfloat16 rounds coarser (as it is: 0.020 at most,
    # 8-bit cache: 0.09-0.12), so the bound here is 0.04.
    teeth=Teeth(tolerances={"LOGITS_REL_EXPERTS": 0.04}, sound_margin=0.6))
EXACT = FAM.exact
engines, served = contract.fixtures(FAM)


def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.period == ("window",) * 3 + ("full",)
    assert cfg.n_of("window") == 6 and cfg.n_of("full") == 2
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    assert cfg.state_by_slot and not getattr(cfg, "recurrent", False)


def test_an_old_configuration_is_the_object_it_was():
    cfg = configs.get("tiny")
    assert cfg.period == ("full",) and not cfg.state_by_slot
    assert cfg.head_dim == cfg.d_model // cfg.n_heads and cfg.d_head == 0
    assert cfg.expert_width == cfg.d_ff and cfg.rope("full") == {
        "theta": cfg.rope_theta, "yarn": None}
    assert cfg.kv_read_tokens([3, 9]) == cfg.n_layers * 12
    moe = configs.get("tiny-moe")
    assert moe.num_params == 2 * (64 * 64 * 4 + 4 * 3 * 64 * 128 + 64 * 4
                                  + 2 * 64) + 2 * 512 * 64 + 64


def test_published_sizes_give_the_published_parameter_count():
    cfg, _ = contract.published_parameter_count(FAM)
    active = cfg.num_params - cfg.n_layers * (64 - 8) * 3 * 2304 * 896
    assert round(active / 1e7) == 244                  # "A2.5B"


def test_bad_patterns_are_refused():
    tiny = configs.get("tiny-window-moe")
    for over in ({"layer_pattern": ("window", "dense")}, {"n_layers": 6},
                 {"window": 0}, {"layer_pattern": ()}):
        with pytest.raises(ValueError):
            dataclasses.replace(tiny, **over)


# -- (i) the window slides, the ring wraps, two ropes ------------------------
def test_prompt_longer_than_window_and_one_turn_of_the_ring(served):
    """100 prompt tokens against a window of 12 and a ring of 12 + 32
    rows: every window layer's ring wraps twice, the last chunk (4 tokens
    of 32) is padded, and the compared positions lie beyond both.  Equal
    to the family's reference and to `transformer.forward` (capacity
    routing off: nothing dropped)."""
    e, c = served
    assert e.cache.wk.shape == (6, 5, 44, 2, 16)
    assert e.cache.k.shape[0] == 2
    contract.prefill_then_decode_equals_the_reference(FAM, e, c, 3, 100, 10)
    rows = seqs(3, 100 + 10)
    cfg = dataclasses.replace(e.cfg, capacity_factor=2.0)    # E / top_k
    got = e.score(rows, 100)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        with jax.default_matmul_precision("highest"):
            want = forward(e.params, jnp.asarray(rows, jnp.int32), cfg)
    assert any("window attention" in str(w.message) for w in seen)
    for lane in range(3):
        err = reference.position_errors(jnp.stack(got[lane]),
                                        want[lane, 99:])
        assert float(err.max()) < EXACT, err


def test_forward_refuses_a_window_under_sequence_parallelism():
    cfg = configs.get("tiny-window-moe")
    with pytest.raises(ValueError, match="sliding-window"):
        forward(None, jnp.zeros((1, 8), jnp.int32), cfg, seq_shards=2,
                mesh=object())


# -- (ii) every chunk tier, and a padded last chunk ---------------------------
@pytest.mark.parametrize("n_prompt", [64, 81, 97, 128])
def test_every_chunk_tier_and_a_padded_tail(engines, n_prompt):
    """prefill_chunk 64 has the tiers 32 and 64: 64 = one whole chunk,
    81 = 64 + 17 (tier 32, padded), 97 = 64 + 33 (tier 64, padded),
    128 = two whole."""
    e = contract.every_chunk_tier_and_a_padded_tail(FAM, engines, n_prompt,
                                                    [32, 64])
    assert e.cache.wk.shape[2] == 76


# -- (iii) unequal lanes and an idle lane between them ------------------------
def test_unequal_lanes_with_an_idle_lane_between(served):
    contract.unequal_lanes_with_an_idle_lane_between(FAM, *served)


def test_a_burst_equals_its_steps(served):
    b_state, state, visited, _, _ = contract.burst_equals_its_steps(served[0])
    contract.leaves_agree(b_state, state)
    assert not np.asarray(b_state.wk[:, 1]).any()     # a slot no lane had
    # three live lanes x top-4 of 8 experts, 3 steps x 8 layers
    assert 4 * 24 <= visited <= 8 * 24


# -- (iv), (v): the engine's own scheduling ------------------------------------
def test_a_slot_reused_without_a_reset(served):
    """The second request takes slot 0 with the first's rows still in its
    rings: a stale row lies at a position the new sequence has not
    reached, and is masked.  Nothing is zeroed."""
    e, c = served
    _, stats, ticks = contract.a_slot_reused_by_a_second_request(
        FAM, e, c, (90, 35))
    assert np.asarray(e.cache.wk[:, 0]).any()
    state = stats["state"]
    assert state["state_resets"] == 0
    assert state["kv_paged"] == 2 * 2 * e.num_blocks * 8 * 2 * 16 * 4
    assert state["kv_window"] == 2 * 6 * 5 * 44 * 2 * 16 * 4
    assert state["recurrent"] == 0
    assert all(t["reset_s"] == 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    # one lane of length n > 12: two full layers see n, six windows 12
    n = (one["kv_read_tokens"] - 6 * 12) // 2
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([n]) and n > 12
    assert 4 <= one["experts_read"] <= 4.0           # one lane: its top-4


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger re-prefills over the rows its first pass left in the
    rings: nothing is zeroed."""
    stats = contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)
    assert stats["state"]["state_resets"] == 0


def test_streams_equal_the_step_reference_while_lanes_join_and_leave(engines):
    """On slots that hold rings beside the pool, with experts whose count
    is read with the tokens."""
    contract.streams_equal_the_step_reference_while_lanes_join_and_leave(
        FAM, engines)


# -- (vi) what a ring forbids is refused, and says why --------------------------
def test_refusals():
    cfg, params, e = contract.refusals(FAM)
    assert e._reset_state is None             # and nothing to zero
    with pytest.raises(ValueError, match="ring of window KV by slot"):
        PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16, mesh=object())
    dense = configs.get("tiny")
    twin = PagedLLMEngine(dense, init_params(jax.random.key(0), dense),
                          num_slots=2, max_len=64, block_size=8,
                          prefill_chunk=16)
    try:
        with pytest.raises(ValueError, match="no experts"):
            twin.score(np.ones((1, 9), np.int64), 8, routing=True)
    finally:
        twin.shutdown()
    with pytest.raises(ValueError, match="slots"):
        decoding._paged_forward(params, e.cache, None, None, None, None, cfg)


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        assert dep._disagg is None
        with pytest.raises(ValueError, match="import_prefix"):
            dep.adopt_kv(list(range(8)), np.zeros((2, 2, 1, 8, 2, 16)), 8)
        state = dep.stats()["state"]
        assert state["kv_window"] > 0 and state["recurrent"] == 0


# -- (vii) the routing is handed over, and held to a slack -----------------------
def test_the_routing_score_returns_is_the_reference_s_own(served):
    """Float32 on both sides: the experts the program took at every
    position (prompt positions too) and layer are the reference's own
    top-4 wherever the reference's margin is clear of rounding, and
    handing them over leaves the reference's logits as they were."""
    e, c = served
    fam = FAM.reference(c)
    rows = seqs(2, 70 + 5, seed=31)
    got, taken = e.score(rows, 70, routing=True)
    plain = e.score(rows, 70)
    for lane in range(2):
        assert taken[lane].shape == (75, 8, 4)
        assert taken[lane].dtype == np.int32
        np.testing.assert_array_equal(np.stack(got[lane]),
                                      np.stack(plain[lane]))
        own, margin = fam.forward(e.params, jnp.asarray(rows[lane]), c,
                                  jit=contract.jit, routing=None)
        handed, decided = fam.forward(e.params, jnp.asarray(rows[lane]), c,
                                      jit=contract.jit, routing=taken[lane])
        assert float(margin.min()) < reference.ROUTER_MARGIN   # the trap
        assert float(decided.min()) >= 1.0 - 1e-4
        np.testing.assert_allclose(np.asarray(handed), np.asarray(own),
                                   atol=2e-5)
        x = e.params["embed"][jnp.asarray(rows[lane])]
        first = fam._rms_norm(
            x + fam.attention(fam._rms_norm(
                x, e.params["blocks"]["attn_norm"][0], 1e-6),
                {k: v[0] for k, v in e.params["blocks"].items()}, c,
                "sliding_attention"),
            e.params["blocks"]["mlp_norm"][0], 1e-6)
        logits = first @ e.params["blocks"]["router"][0]
        want = np.sort(np.asarray(jax.lax.top_k(logits, 4)[1]), axis=-1)
        np.testing.assert_array_equal(
            np.sort(taken[lane][:, 0], axis=-1), want)


def test_forced_routing_outside_the_slack_fails(served):
    e, c = served
    fam = FAM.reference(c)
    seq = seqs(1, 40, seed=32)[0]
    _, taken = e.score(seq[None], 36, routing=True)
    _, own = fam.forward(e.params, jnp.asarray(seq), c, jit=contract.jit,
                         routing=taken[0])
    assert bool(jnp.all(jnp.isfinite(own)))
    forced = taken[0].copy()
    # position 20, layer 3: the expert the reference likes least
    worst = [x for x in range(8) if x not in set(forced[20, 3])]
    forced[20, 3, 0] = worst[-1]
    probe, _ = fam.forward(e.params, jnp.asarray(seq), c, jit=contract.jit,
                           routing=forced)
    bad = ~np.isfinite(np.asarray(probe)).all(axis=-1)
    assert bad.sum() <= 4 and bad[20]     # the gap there is over the slack
    twice = taken[0].copy()
    twice[7, 5, 1] = twice[7, 5, 0]                   # one expert twice
    again, _ = fam.forward(e.params, jnp.asarray(seq), c, jit=contract.jit,
                           routing=twice)
    assert not np.isfinite(np.asarray(again[7])).any()
    short, _ = fam.forward(e.params, jnp.asarray(seq), c, jit=contract.jit,
                           routing=taken[0][:, :, :3])
    assert not np.isfinite(np.asarray(short)).any()


# -- (viii) the benchmark's comparison has teeth ----------------------------------
def _window_mask_dropped(monkeypatch, cfg):
    inner = decoding.window_attention
    monkeypatch.setattr(
        decoding, "window_attention", lambda q, k, v, pos, n, window:
        inner(q, k, v, pos, n, 1 << 30))
    return cfg


def _yarn_left_off(monkeypatch, cfg):
    return dataclasses.replace(cfg, yarn=None)


def _qk_norm_left_out(monkeypatch, cfg):
    return dataclasses.replace(cfg, qk_norm=False)


def _top_k_one_short(monkeypatch, cfg):
    return dataclasses.replace(cfg, expert_top_k=cfg.expert_top_k - 1)


def _one_expert_dropped(monkeypatch, cfg):
    import ray_tpu.ops.moe as moe

    inner = moe.moe_mlp_dropless

    def faulty(x, params, *a, **kw):
        return inner(x, dict(params, w_down=params["w_down"]
                             .at[:, 0].set(0)), *a, **kw)

    monkeypatch.setattr(moe, "moe_mlp_dropless", faulty)
    return cfg


@on_the_engine
def _cache_in_8_bits(e, ref, monkeypatch):
    contract.score_keeps(e, monkeypatch, lambda cache: jax.tree.map(
        contract.as_float8, cache))


@pytest.mark.parametrize("fault", [
    None, _window_mask_dropped, _yarn_left_off, _qk_norm_left_out,
    _one_expert_dropped, _top_k_one_short, _cache_in_8_bits],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


# -- the served path: serve.run -> proxy -> handle -> replica -> engine ---------
def test_served_through_the_front_like_any_model():
    stats = contract.served_through_the_front_like_any_model(FAM)
    assert stats["state"]["kv_window"] > 0
    assert stats["state"]["state_resets"] == 0


# -- a model without a pattern lowers to the program it lowered to ---------------
# sha256 of the StableHLO text (`lowered.as_text()`: no locations) of the
# served programs and of `forward`, taken on PR 33's tree (commit 3782f4e)
# with the shapes below; the scan over periods, the rings in the cache's
# pytree, the ropes and norms by kind and the routing output leave the
# old configurations' programs as they were, to the letter.  A PR that
# means to change these programs replaces the digests and says so.
_LOWERED_AT_PR_33 = {
    ("tiny", "chunk"): "011fc65882f1d997",
    ("tiny", "burst"): "de79f35fa15a540e",
    ("tiny", "forward"): "e4e41c8eb629e3da",
    ("tiny-moe", "chunk"): "46948ed989777a6e",
    ("tiny-moe", "burst"): "b9dd8b33061cf86f",
    ("tiny-moe", "forward"): "4f39717e1e023344",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_33),
                         ids=lambda v: str(v))
def test_old_configurations_lower_as_before(name, program):
    assert contract.lowered_digest(
        name, program, state=False, **contract.SMALL_SHAPES) \
        == _LOWERED_AT_PR_33[(name, program)]
