"""The state-space / attention hybrid with routed experts
(`ray_tpu.models.mamba2_moe`) on the served path, held to the
granitehybrid family's plain float32 reference
(`bench/families/granitehybrid.py`, which imports nothing of the program
and carries the recurrence a position at a time): a prefill launch is
chunks of the state-space-duality form from the slot's state, a decode
step the one-position recurrence; one rank's share of the experts beside a
shared expert.  The served contract's cases are
`tests/served_contract.py`'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from ray_tpu.models import configs, decoding, mamba2_moe
from ray_tpu.ops import moe
from ray_tpu.serve import llm
from ray_tpu.serve.llm import PagedLLMEngine
from served_contract import Family, Teeth, seqs


def _a_burst_counts_its_share(e, t):
    # lanes x 8 steps x 8 layers x top-3
    assert 0 < t["routed_here"] < t["lanes"] * 8 * 8 * 3


FAM = Family(
    tiny="granitefamily/configs/tinygranite-serve.json",
    registry="tiny-mamba2-moe",
    as_registry=dict(compute_dtype=contract.FLOAT32),
    published=("granite-4.0-h-small", 1e8, 322),       # "32B" published
    # norms, conv rows and biases, dt_bias, A_log and D on top
    leaves=("granite-4.0-h-small", 0.001),
    handed=lambda taken: {"routing": np.asarray(taken)},
    front=("mamba2moe", {"engine": "paged"}),
    deployment=dict(contract.SMALL, engine="paged"),
    slot_leaves=("conv", "h"), written=("h",),
    refusals=dict(speculation_k="speculation_k",
                  export_streams="export_streams",
                  import_prefix="import_prefix", frame=(2, 2, 4, 8, 2, 16),
                  deployment="recurrent state"),
    burst_tick=_a_burst_counts_its_share,
    # twice the family's LOGITS_REL_EXPERTS: a width of 64 rounds more than
    # one of 4096, the program as it is reads up to 0.031 here and 0.0235
    # on the chip
    teeth=Teeth(tolerances=lambda ref: {
        "LOGITS_REL_EXPERTS": 2 * ref.TOLERANCES["LOGITS_REL_EXPERTS"]},
        fault_reads=None))
EXACT = FAM.exact
engines, served = contract.fixtures(FAM)


def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.n_of("mamba") == 6 and cfg.n_of("attention") == 2
    assert cfg.moe.held == (4, 4) and cfg.moe.num_experts == 8


def test_published_sizes_give_the_published_parameter_count():
    cfg, _ = contract.published_parameter_count(FAM)
    assert cfg.layer_pattern.count("mamba") == 9 and cfg.periods == 4


def test_a_held_range_must_lie_inside_the_experts():
    with pytest.raises(ValueError, match="held"):
        moe.MoEConfig(num_experts=8, top_k=2, held=(6, 4))
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(configs.get("tiny-mamba2-moe"),
                            experts_held=(0, 9))


# -- (a) chunks, then decode steps, against the full forward ---------------
def test_chunks_of_carried_state_then_decode(served):
    """100 prompt tokens in one launch of 128 rows: four chunks of 32,
    the state carried from chunk to chunk inside the program, the last
    4 valid of 32; then 10 decode steps on the lanes' state."""
    contract.prefill_then_decode_equals_the_reference(FAM, *served, 3, 100,
                                                      10)


@pytest.fixture
def served_chunk64(engines):
    return engines(prefill_chunk=64)


@pytest.mark.parametrize("n_prompt", [64, 81, 97, 128])
def test_every_chunk_tier_and_a_padded_tail(engines, n_prompt):
    """prefill_chunk 64 has the tier 64 and, above it, 128 and 256
    (launches of two and four chunks): 64 = one whole chunk, 81 and 97 =
    a launch of 128 rows whose valid rows end inside its second chunk,
    128 = two whole.  The padded tail must not advance the recurrence."""
    contract.every_chunk_tier_and_a_padded_tail(FAM, engines, n_prompt,
                                                [64, 128, 256])


# A launch of 2Q rows after one of Q, its valid rows ending inside its
# first chunk, on the boundary, inside its second, and at its end.
@pytest.mark.parametrize("n_valid", [40, 64, 100, 128])
def test_a_launch_of_two_chunks_equals_two_launches(served_chunk64, n_valid):
    """Q = 64: 64 + `n_valid` tokens as launches of 64 rows, and as one
    of 64 and one of 128 (two chunks, the state handed on inside the
    program), leave the same conv rows and state in the slot and give
    the same last logits; padded rows hold a real token id."""
    e, _ = served_chunk64
    tokens = contract.prompt(64 + n_valid, n_valid)
    state, last = contract.prefill_alone(e.cfg, e.params, tokens, 64,
                                         pad_with=9, chunk=64, blocks=24)
    head, _ = contract.prefill_alone(e.cfg, e.params, tokens[:64], 64,
                                     chunk=64, blocks=24)
    toks = np.full((128,), 9, np.int32)
    toks[:n_valid] = tokens[64:]
    chunk = contract.bound(decoding.paged_prefill_chunk, e.cfg)
    wide, got, _ = chunk(e.params, head, jnp.asarray(toks),
                         jnp.arange(1, 25, dtype=jnp.int32), jnp.int32(64),
                         jnp.int32(n_valid), slot=jnp.int32(1))
    np.testing.assert_allclose(got, last, atol=EXACT)
    np.testing.assert_array_equal(wide.conv[:, 1], state.conv[:, 1])
    np.testing.assert_allclose(wide.h[:, 1], state.h[:, 1], atol=EXACT)
    assert not np.asarray(wide.h[:, 0]).any()        # nobody's slot


def test_chunk_sizes_one_three_and_whole_give_the_same_state(served):
    """48 positions a position at a time (the one-position recurrence),
    three at a time and as whole chunks of 32 (the duality form) leave
    the same recurrent state and the same last logits; what stands in a
    chunk's padded tail changes neither, to the bit; the null slot and
    the slot nobody had stay zero."""
    e, _ = served
    cfg, tokens = e.cfg, seqs(1, 48, seed=7)[0]
    whole, last = contract.prefill_alone(cfg, e.params, tokens, 32)
    for size in (1, 3):
        other, last_o = contract.prefill_alone(cfg, e.params, tokens,
                                               size)
        for name in ("h", "conv"):
            np.testing.assert_allclose(
                np.asarray(getattr(other, name)),
                np.asarray(getattr(whole, name)), atol=2e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(last_o), np.asarray(last),
                                   atol=2e-5)
    junk, last_j = contract.prefill_alone(cfg, e.params, tokens, 32,
                                          pad_with=77)
    assert np.array_equal(np.asarray(junk.h), np.asarray(whole.h))
    assert np.array_equal(np.asarray(junk.conv), np.asarray(whole.conv))
    assert np.array_equal(np.asarray(last_j), np.asarray(last))
    assert np.asarray(whole.h[:, 1]).any()
    assert not np.asarray(whole.h[:, (0, 2)]).any()
    assert not np.asarray(whole.conv[:, (0, 2)]).any()


def test_unequal_lanes_with_an_idle_lane_between(served):
    contract.unequal_lanes_with_an_idle_lane_between(FAM, *served)


def test_a_burst_equals_its_steps_and_counts_what_it_routed(served):
    cfg = served[0].cfg
    b_state, state, visited, routed, here = contract.burst_equals_its_steps(
        served[0], held=(4, 4))
    contract.leaves_agree(b_state, state)
    assert not np.asarray(b_state.h[:, 1]).any()      # a slot no lane had
    # experts 4..7 are held here: the burst counted the choices on them
    assert routed == here and 0 < here < 3 * 3 * 3 * cfg.n_layers
    assert 0 < visited <= 4 * 3 * cfg.n_layers


# -- (b) the shares add up --------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    contract.shares_cut_in_the_program_add_up(
        FAM, mamba2_moe, mamba2_moe._ffn, 3, "num_local_experts")


def test_an_idle_row_is_routed_nowhere():
    contract.an_idle_row_is_routed_nowhere(FAM, mamba2_moe, mamba2_moe._ffn,
                                           cast=True)


# -- (c) the engine's own scheduling ----------------------------------------
def test_a_slot_reused_by_a_second_request_and_the_tick_log(served):
    e, c = served
    (first, second), stats, ticks = \
        contract.a_slot_reused_by_a_second_request(FAM, e, c)
    assert stats["state"]["state_resets"] == 2
    assert stats["state"]["recurrent"] == 6 * 5 * (
        8 * 16 * 8 * 4 + 3 * (8 * 16 + 2 * 8) * 4)
    assert any(t["reset_s"] > 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    # one lane of length n: the two attention layers read its KV
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([45]) == 2 * 45
    assert 0 < one["experts_read"] <= 3
    # what the prompts' rows routed to experts 4..7, by the program's own
    # routing of the same tokens
    _, taken = e.score(np.asarray([first[:45], second]), 45, routing=True)
    _, taken60 = e.score(np.asarray([first]), 60, routing=True)
    here = sum(int(np.sum(t >= 4)) for t in (taken60[0], taken[1]))
    prefill = [t for t in ticks if t["prefill_tokens"] and not t["lanes"]]
    assert sum(t["prefill_tokens"] for t in prefill) == 60 + 45
    assert sum(t["routed_here"] for t in prefill) == here
    total = 3 * 8 * (60 + 45)
    assert 0.3 * total < here < 0.7 * total


@pytest.fixture()
def grouping_chunk64(engines, monkeypatch):
    """An engine of 64-row chunks that group their rows by expert: as at
    many experts (4 held ones would keep the visit up to 512 rows).  Its
    own: both are read when its programs are traced."""
    monkeypatch.setattr(moe, "_GROUPED_FROM_PRODUCTS", 0)
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)     # the tier 64 alone
    with engines.private(prefill_chunk=64) as held:
        yield held


def test_the_tick_log_counts_the_tiles_a_wide_chunk_multiplied(
        grouping_chunk64):
    """A 64-row chunk groups its rows by expert and counts the tiles it
    multiplied (`moe_tiles` of the tick log): at this width a
    tile holds a whole group, so a tile a held expert that was hit in
    each of the 8 layers, and the rows routed here fit the tiles.  The
    prompt's last 20 tokens go in 64 rows too (this model has no tier
    under `prefill_chunk`) and count theirs; the bursts visit and count
    none."""
    e, c = grouping_chunk64
    tile = moe.grouped_tile_rows(64, e.cfg.moe)
    assert tile == 64 and not moe.grouped_tile_rows(32, e.cfg.moe)
    n_logged = len(e.engine_stats()["tick_log"])
    prompt = contract.prompt(64 + 20, 31)
    out = e.generate(prompt, max_tokens=5)
    assert FAM.is_greedy(e, c, prompt, out)
    with e._tick_lock:
        stats = e.engine_stats()
    assert stats["tick_fields"][-7] == "moe_tiles"
    ticks = [dict(zip(stats["tick_fields"], t))
             for t in stats["tick_log"]][n_logged:]
    wide = [t for t in ticks if t["prefill_tokens"] == 64]
    assert len(wide) == 1 and not wide[0]["lanes"]
    assert 8 * 1 <= wide[0]["moe_tiles"] <= 8 * 4
    assert 0 < wide[0]["routed_here"] <= wide[0]["moe_tiles"] * tile
    (last,) = [t for t in ticks if t["prefill_tokens"] == 20]
    assert 8 * 1 <= last["moe_tiles"] <= 8 * 4 and not last["lanes"]
    assert 0 < last["routed_here"] <= 20 * 3 * 8
    bursts = [t for t in ticks if t["lanes"]]
    assert bursts and all(t["moe_tiles"] == 0 and t["routed_here"] > 0
                          for t in bursts)


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger's state is zeroed with its lengths, and its re-prefill
    of prompt + emitted tokens rebuilds it."""
    stats = contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)
    assert stats["state"]["state_rebuilds"] >= 1


def test_streams_equal_the_step_reference_while_lanes_join_and_leave(engines):
    """On slots that hold recurrent state and a model whose burst hands
    out one more count."""
    contract.streams_equal_the_step_reference_while_lanes_join_and_leave(
        FAM, engines)


# -- (d) what this model cannot have yet is refused --------------------------
def test_refusals():
    cfg, params, _ = contract.refusals(FAM)
    with pytest.raises(ValueError, match="mesh"):
        from jax.sharding import Mesh
        PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16,
                       mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        assert dep._disagg is None
        with pytest.raises(ValueError, match="import_prefix"):
            dep.adopt_kv(list(range(8)), np.zeros((2, 2, 1, 8, 2, 16)), 8)
        state = dep.stats()["state"]
        assert state["kv_window"] == 0 and state["recurrent"] > 0
        assert state["kv_paged"] > 0


# -- (e) the other models lower to the programs they lowered to --------------
# sha256 of the StableHLO text of the served programs, taken on PR 35's
# tree (commit dab40ad) with the shapes below: the held range in
# `MoEConfig`, `live` by row, the scale argument of `paged_attention`, the
# routed count and the fifth value of `_served_forward` leave them as they
# were, to the letter.  (`tiny-moe` is held by tests/
# test_window_moe_serving.py since PR 34, and still is.)
_LOWERED_AT_PR_35 = {
    ("tiny-moe", "chunk"): "46948ed989777a6e",
    ("tiny-moe", "burst"): "b9dd8b33061cf86f",
    ("tiny-window-moe", "chunk"): "c240418b7a1b34bd",
    ("tiny-window-moe", "burst"): "04989cf7fe6f4582",
    ("tiny-hybrid", "chunk"): "cdfa4237730d621f",
    ("tiny-hybrid", "burst"): "ba0b5af0011aa764",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_35),
                         ids=lambda v: str(v))
def test_other_models_lower_as_before(name, program):
    assert contract.lowered_digest(name, program, **contract.SMALL_SHAPES) \
        == _LOWERED_AT_PR_35[(name, program)]


# -- the benchmark's comparison has teeth ------------------------------------
def _padded_tail_advances(monkeypatch, cfg):
    inner = mamba2_moe._mamba2
    monkeypatch.setattr(
        mamba2_moe, "_mamba2", lambda bp, x, conv, h, valid, *a:
        inner(bp, x, conv, h, jnp.ones_like(valid), *a))


def _usual_attention_scale(monkeypatch, cfg):
    inner = mamba2_moe.paged_attention
    monkeypatch.setattr(
        mamba2_moe, "paged_attention",
        lambda *a, scale=None, **kw: inner(*a, **kw))


def _residual_multiplier_dropped(monkeypatch, cfg):
    inner = mamba2_moe._served_step
    monkeypatch.setattr(
        mamba2_moe, "_served_step", lambda *a: inner(
            *a[:7], dataclasses.replace(a[7], residual_multiplier=1.0),
            *a[8:]))


def _one_expert_fewer_a_token(monkeypatch, cfg):
    prop = mamba2_moe.Mamba2MoEConfig.moe
    monkeypatch.setattr(
        mamba2_moe.Mamba2MoEConfig, "moe", property(
            lambda self: dataclasses.replace(
                prop.fget(self), top_k=self.expert_top_k - 1)))


def _one_held_expert_dropped(monkeypatch, cfg):
    inner = mamba2_moe.moe_mlp_dropless

    def dropped(h, params, *a, **kw):
        return inner(h, dict(params, w_down=params["w_down"].at[:, 1].set(0)),
                     *a, **kw)

    monkeypatch.setattr(mamba2_moe, "moe_mlp_dropless", dropped)


@pytest.mark.parametrize("fault", [
    None, _padded_tail_advances, _usual_attention_scale,
    _residual_multiplier_dropped, _one_expert_fewer_a_token,
    _one_held_expert_dropped],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


def test_state_kept_in_bfloat16_shows_in_float32_arithmetic(engines):
    """With everything else in float32 a recurrent state kept in
    bfloat16 is far over the engine's own error of 1e-6 from the first
    position that reads what a launch left in the slot: every decode
    step (the prompt's last position is inside its one launch, where
    the state is handed on in float32)."""
    e, _ = engines(config={"state_dtype": "bfloat16"})
    assert e.cache.h.dtype == jnp.bfloat16
    errs = FAM.errors(e, FAM.config(), seqs(2, 100 + 6), 100).reshape(2, 7)
    assert errs[:, 0].max() < EXACT < 5 * EXACT < errs[:, 1:].min(), errs


# -- the served path: serve.run -> proxy -> handle -> replica -> engine -----
def test_served_through_the_front_like_any_model():
    stats = contract.served_through_the_front_like_any_model(FAM)
    assert stats["state"]["state_resets"] == 2
