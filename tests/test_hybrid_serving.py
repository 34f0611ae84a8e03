"""The decoder-hybrid-decoder model (`ray_tpu.models.hybrid`) on the
served path, held to the phi4flash family's plain float32 reference
(`bench/families/phi4flash.py`, which imports nothing of the program);
its slots hold a block table, a ring of window KV and recurrent state.
The served contract's cases are `tests/served_contract.py`'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from ray_tpu.models import configs, hybrid
from ray_tpu.serve.llm import PagedLLMEngine
from served_contract import Family, Teeth, seqs

FAM = Family(
    tiny="phi4flashfamily/configs/tinyphi-serve.json",
    registry="tiny-hybrid", as_registry=dict(compute_dtype=contract.FLOAT32),
    published=("phi4-mini-flash", 1e6, 3851),        # "3.8B" published
    # norms, biases, conv rows, A_log, D and the lambda vectors on top
    leaves=("phi4-mini-flash", 0.001), routes=False,
    front=("hybrid", {}), slot_leaves=("wk", "wv", "conv", "h"),
    written=("h",),
    refusals=dict(speculation_k="speculation_k",
                  export_streams="export_streams",
                  import_prefix="import_prefix", frame=(2, 1, 4, 8, 4, 8),
                  deployment="recurrent state"),
    teeth=Teeth(bound_key="LOGITS_REL", fault_reads="worst",
                decided_under_fault=True))
EXACT = FAM.exact
engines, served = contract.fixtures(FAM)


def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.n_window == 2 and cfg.n_cross == 1 and cfg.n_mamba == 3


def test_published_sizes_give_the_published_parameter_count():
    contract.published_parameter_count(FAM)


# -- (i) the window slides and the ring wraps ------------------------------
def test_prompt_longer_than_window_and_chunk(served):
    """100 prompt tokens against a ring of 24 + 32 rows: every window
    layer's ring wraps, and the last chunk (4 tokens of 32) is padded."""
    e, c = served
    ring = e.cfg.ring_len(e.prefill_chunk)
    assert e.cache.wk.shape[2] == ring == 56 < 100
    contract.prefill_then_decode_equals_the_reference(FAM, e, c, 3, 100, 10)


# -- (ii) every chunk tier, and a padded last chunk ------------------------
@pytest.mark.parametrize("n_prompt", [64, 81, 97, 128])
def test_every_chunk_tier_and_a_padded_tail(engines, n_prompt):
    """prefill_chunk 64 has the tiers 32 and 64: 64 = one whole chunk,
    81 = 64 + 17 (tier 32, padded), 97 = 64 + 33 (tier 64, padded),
    128 = two whole.  The padded tail must not advance the recurrence."""
    contract.every_chunk_tier_and_a_padded_tail(FAM, engines, n_prompt,
                                                [32, 64])


# -- (iii) unequal lanes and an idle lane between them ----------------------
def test_unequal_lanes_with_an_idle_lane_between(served):
    contract.unequal_lanes_with_an_idle_lane_between(FAM, *served)


def test_a_burst_equals_its_steps_on_every_kind_of_state(served):
    b_state, state, *_ = contract.burst_equals_its_steps(served[0])
    contract.leaves_agree(b_state, state)
    assert not np.asarray(b_state.h[:, 1]).any()      # a slot no lane had


# -- (iv), (v): the engine's own scheduling --------------------------------
def test_a_slot_reused_by_a_second_request(served):
    e, c = served
    _, stats, ticks = contract.a_slot_reused_by_a_second_request(FAM, e, c)
    assert stats["state"]["state_resets"] == 2
    assert stats["tick_fields"][-12:-10] == ("kv_read_tokens", "reset_s")
    assert any(t["reset_s"] > 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    # one lane of length n: 2 readers of the full KV (itself, one cross
    # layer) and two window layers of at most 24
    n = (one["kv_read_tokens"] - 2 * 24) // 2
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([n]) and n > 24


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger's state is zeroed with its lengths, and its re-prefill
    of prompt + emitted tokens rebuilds it."""
    stats = contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)
    assert stats["state"]["state_rebuilds"] >= 1


def test_streams_equal_the_step_reference_while_lanes_join_and_leave(engines):
    """On slots that hold rings, conv rows and recurrent state."""
    contract.streams_equal_the_step_reference_while_lanes_join_and_leave(
        FAM, engines)


# -- (vi) what this model cannot have yet is refused -------------------------
def test_refusals():
    contract.refusals(FAM)


def test_deployment_takes_the_configuration_and_refuses_adoption():
    with contract.deployed(FAM) as dep:
        assert dep._disagg is None
        with pytest.raises(ValueError, match="import_prefix"):
            dep.adopt_kv(list(range(8)), np.zeros((2, 1, 1, 8, 4, 8)), 8)
        state = dep.stats()["state"]
        assert state["kv_window"] > 0 and state["recurrent"] > 0


def test_a_transformer_s_state_is_the_pool_alone():
    from ray_tpu.models import init_params

    cfg = configs.get("tiny")
    e = PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg),
                       num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16)
    try:
        e.generate(list(range(1, 20)), max_tokens=3)
        # generate() returns from inside the tick that finished the
        # request; that tick logs itself when it ends, under this lock.
        with e._tick_lock:
            stats = e.engine_stats()
        assert stats["state"] == {
            "kv_paged": 2 * 2 * 17 * 8 * 2 * 16 * 2, "kv_window": 0,
            "recurrent": 0, "state_resets": 0, "state_rebuilds": 0}
        # The last tick that decoded: a later one may have prefilled or
        # admitted only, with no lane and nothing read.
        ticks = [dict(zip(stats["tick_fields"], t))
                 for t in stats["tick_log"]]
        tick = [t for t in ticks if t["lanes"] > 0][-1]
        assert tick["reset_s"] == 0.0
        assert tick["kv_read_tokens"] % cfg.n_layers == 0
        assert tick["kv_read_tokens"] > 0
    finally:
        e.shutdown()


# -- (vii) the benchmark's comparison has teeth ------------------------------
def _padded_tail_advances(monkeypatch, cfg):
    inner = hybrid._mamba
    monkeypatch.setattr(
        hybrid, "_mamba", lambda bp, x, conv, h, valid, cfg:
        inner(bp, x, conv, h, jnp.ones_like(valid), cfg))


def _window_mask_dropped(monkeypatch, cfg):
    inner = hybrid.window_diff_attention
    monkeypatch.setattr(
        hybrid, "window_diff_attention", lambda q, k, v, pos, n, window:
        inner(q, k, v, pos, n, 1 << 30))


def _lambda_zero(monkeypatch, cfg):
    monkeypatch.setattr(hybrid.HybridConfig, "lambda_init",
                        lambda self, layer: jnp.zeros((), jnp.float32))


@pytest.mark.parametrize("fault", [
    None, _padded_tail_advances, _window_mask_dropped, _lambda_zero],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    """Held to the family's own LOGITS_REL: there is no router."""
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


def test_state_kept_in_bfloat16_shows_in_float32_arithmetic(engines):
    """The logits check in bfloat16 cannot tell a recurrent state in
    bfloat16 from one in float32 (the family's TOLERANCES says by how
    little it moves); with everything else in float32 it is a hundred
    times the engine's own error of 1e-6 and fails the exact bound."""
    e, _ = engines(config={"state_dtype": "bfloat16"})
    assert e.cache.h.dtype == jnp.bfloat16
    errs = FAM.errors(e, FAM.config(), seqs(2, 100 + 6), 100)
    assert errs.min() > 5 * EXACT, errs


# -- the served path: serve.run -> proxy -> handle -> replica -> engine -----
def test_served_through_the_front_like_any_model():
    stats = contract.served_through_the_front_like_any_model(FAM)
    assert stats["state"]["state_resets"] == 2
