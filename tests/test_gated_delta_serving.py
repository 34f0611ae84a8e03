"""A third kind of layer in `TransformerConfig`'s stack, on the served path
(`configs.get("tiny-gated-delta-moe")`: periods of three linear layers to
one full layer; a linear layer is a Gated DeltaNet mixer, `ops.gated_delta`,
that keeps by the engine's slot a float32 state a head and the rows of a
short convolution; the full layer's output is gated element by element and
its rope turns a quarter of a head; every norm's gain is 1 + w; one rank's
share of 8 experts beside a shared expert under a gate of its own), held to
the qwen3next family's plain float32 reference (`bench/families/
qwen3next.py`, which imports nothing of the program and carries the
recurrence a position at a time).  The served contract's cases are
`tests/served_contract.py`'s."""
import dataclasses
import hashlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import served_contract as contract
from ray_tpu.models import configs, decoding, init_params
from ray_tpu.models.transformer import forward
from ray_tpu.ops import gated_delta
from ray_tpu.ops.moe import MoEConfig
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine
from served_contract import Family, Teeth, on_the_engine, seqs

# Float32 on both sides agrees to 7e-5 of the logits' rms at this size, with
# the rule's chunk at 1, 8 or 32 positions alike (a delta rule subtracts what
# the state answers from what is written: rounding is amplified as by no
# other layer here); the other families' 2e-5 is a third of that.
EXACT = 2e-4
# Readings at this size in bfloat16 (CPU, seeds 5-7; a width of 48 rounds
# coarsely and the rule amplifies it): as it is, a position's error has
# medians 0.11-0.17 and a largest of 0.28 / 0.41 / 0.31, and strays by at most
# 0.66 / 1.29 / 0.59; pool, conv rows and state in 8-bit floats, medians
# 0.20-0.38, largest 0.77 / 1.07 / 0.77, strays to 2.3-2.8; the weights
# rounded once more, a held expert or a linear layer dropped, medians
# 0.69-0.97.  The family's own two limits are the published widths'.
TINY_BOUND, TINY_SLACK, TEETH_SEED = 0.55, 1.8, 5


def _a_burst_counts_its_share(e, t):
    # lanes x 8 steps x 8 layers x top-3; six linear layers' state a lane
    assert 0 < t["routed_here"] < t["lanes"] * 8 * 8 * 3
    assert t["linear_state_rows"] == 6 * t["lanes"]


FAM = Family(
    tiny="qwen3nextfamily/configs/tinyqwen3next-serve.json",
    registry="tiny-gated-delta-moe", as_registry={},
    published=("qwen3-next-80b-a3b", 1e8, 797),        # "80B" published
    leaves=("tiny-gated-delta-moe", None),
    exact=EXACT, own_init=False, handed=lambda taken: {"routing": taken},
    front=None,
    deployment=dict(contract.SMALL, engine="paged"),
    slot_leaves=("lconv", "lstate"), written=("lstate",),
    refusals=dict(speculation_k="speculation_k",
                  export_streams="export_streams",
                  import_prefix="import_prefix", frame=(2, 2, 4, 8, 2, 16),
                  deployment="recurrent state"),
    burst_tick=_a_burst_counts_its_share,
    teeth=Teeth(tolerances={"LOGITS_REL_EXPERTS": TINY_BOUND,
                            "ROUTER_SLACK": TINY_SLACK},
                seed=TEETH_SEED))
engines, served = contract.fixtures(FAM)


# -- the configuration ---------------------------------------------------------
def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.kinds == ("linear", "linear", "linear", "full") * 2
    assert cfg.n_of("linear") == 6 and cfg.n_of("full") == 2
    assert cfg.n_expert_layers == 8 and cfg.n_periods == 2
    assert cfg.rope("full") == {"theta": 10000.0, "yarn": None,
                                "rotary_dim": 4}
    assert cfg.moe == MoEConfig(num_experts=8, top_k=3, held=(4, 4))
    assert cfg.state_by_slot and cfg.recurrent and cfg.launch_spans_chunks
    assert cfg.mixers_by_kind and not cfg.heads_by_kind
    assert decoding.counts_routed(cfg)
    assert cfg.kv_read_tokens([10, 20]) == 2 * 30      # the full layers'


def test_published_keys_give_the_published_parameter_count():
    """79.67 B ("80B"), ~3 B of them active a token ("A3B"); the tiny
    preset's arrays count what `num_params` says, norms included."""
    cfg, shapes = contract.published_parameter_count(FAM)
    assert cfg.n_layers == 48 and cfg.n_periods == 12
    assert cfg.n_of("linear") == 36 and cfg.n_of("full") == 12
    idle = 48 * (512 - 10) * 3 * 2048 * 512
    assert 2.5e9 < cfg.num_params - idle < 4.0e9
    assert sorted(shapes["kinds"]) == ["full", "linear"]
    assert shapes["kinds"]["full"]["head_gate"].shape == (2, 48, 4 * 16)
    assert shapes["kinds"]["linear"]["in_qkvz"].shape == (6, 48, 64 + 32)
    assert shapes["kinds"]["linear"]["conv_w"].shape == (6, 4, 64)
    assert shapes["blocks"]["shared_scale"].shape == (8, 48, 1)
    assert not {"wq", "wk", "wv", "wo", "q_norm"} & set(shapes["blocks"])


def test_the_old_configurations_are_the_objects_they_were():
    for name in ("tiny", "tiny-moe", "tiny-window-moe", "tiny-gated-moe",
                 "tiny-block-diffusion-moe"):
        cfg = configs.get(name)
        assert not (cfg.recurrent or cfg.launch_spans_chunks
                    or cfg.mixers_by_kind or cfg.norm_plus_one
                    or cfg.shared_gate)
        assert cfg.state_by_slot == (cfg.window > 0)
        assert int(cfg.attn_gate) == (name == "tiny-gated-moe")
        shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
        assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg.num_params
        state = jax.eval_shape(lambda: decoding.init_sequence_state(
            cfg, 9, 8, num_slots=2, prefill_chunk=16))
        assert state.lconv is None and state.lstate is None
        assert state.resident_bytes()["recurrent"] == 0


def test_bad_settings_are_refused():
    tiny = configs.get("tiny-gated-delta-moe")
    for over in ({"layer_pattern": ("linear", "window"), "window": 8},
                 {"lead_pattern": ("linear",)}, {"linear_k_heads": 3},
                 {"linear_d_v": 0}, {"attn_gate": 3}, {"d_shared": 0},
                 {"diffusion_block": 4, "denoise_steps": 2},
                 {"n_layers": 6}):
        with pytest.raises(ValueError):
            dataclasses.replace(tiny, **over)
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), tiny))
    with pytest.raises(ValueError, match="served model.*linear"):
        forward(params, jnp.zeros((1, 8), jnp.int32), tiny)
    with pytest.raises(ValueError, match="whole chunks"):
        decoding.init_sequence_state(tiny, 9, 8, num_slots=2,
                                     prefill_chunk=12)


# -- (i) the rule's forms ---------------------------------------------------------
def _rule_inputs(t, seed=0, lanes=2, heads=3, dk=8, dv=16):
    k = jax.random.split(jax.random.key(seed), 6)

    def unit(a):
        return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(k[0], (lanes, t, heads, dk))) * dk ** -0.5
    key = unit(jax.random.normal(k[1], (lanes, t, heads, dk)))
    v = jax.random.normal(k[2], (lanes, t, heads, dv))
    g = -0.5 * jax.nn.softplus(jax.random.normal(k[3], (lanes, t, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (lanes, t, heads)))
    state = jax.random.normal(k[5], (lanes, heads, dk, dv))
    return q, key, v, g, beta, state


def _a_position_at_a_time(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = gated_delta.gated_delta_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("chunks,ragged", [(1, 5), (2, 3), (8, 7), (8, 0)])
def test_the_chunk_form_equals_the_recurrence(chunks, ragged):
    """`chunks` chunks of 8 positions from a state that is not zero, the
    last `ragged` rows of lane 1 not valid (beta = 0, g = 0: the state
    passes them): outputs at the valid rows and the state handed on are
    the recurrence's a position at a time."""
    t = 8 * chunks
    q, k, v, g, beta, state = _rule_inputs(t, seed=chunks)
    valid = jnp.arange(t)[None] < jnp.asarray([[t], [t - ragged]])
    g, beta = (jnp.where(valid[..., None], a, 0.0) for a in (g, beta))
    want, after = _a_position_at_a_time(q, k, v, g, beta, state)
    got, handed = gated_delta.gated_delta_chunks(
        q, k, v, g, beta, state, chunk=8, cd=jnp.float32)
    np.testing.assert_allclose(
        np.where(valid[..., None, None], got, 0.0),
        np.where(valid[..., None, None], want, 0.0), atol=2e-6)
    np.testing.assert_allclose(handed, after, atol=2e-6)
    if ragged:           # lane 1's state stopped at its last valid row
        _, short = _a_position_at_a_time(*(a[1:, :t - ragged] for a in (
            q, k, v, g, beta)), state[1:])
        np.testing.assert_allclose(handed[1:], short, atol=2e-6)


def test_a_step_after_a_chunk_is_one_more_position():
    q, k, v, g, beta, state = _rule_inputs(17, seed=3)
    want, after = _a_position_at_a_time(q, k, v, g, beta, state)
    _, handed = gated_delta.gated_delta_chunks(
        *(a[:, :16] for a in (q, k, v, g, beta)), state, chunk=8,
        cd=jnp.float32)
    o, handed = gated_delta.gated_delta_step(
        q[:, 16], k[:, 16], v[:, 16], g[:, 16], beta[:, 16], handed)
    np.testing.assert_allclose(o, want[:, 16], atol=2e-6)
    np.testing.assert_allclose(handed, after, atol=2e-6)
    with pytest.raises(ValueError, match="whole chunks"):
        gated_delta.gated_delta_chunks(q, k, v, g, beta, state, chunk=8,
                                       cd=jnp.float32)


def test_rows_that_are_not_valid_leave_the_state_to_the_bit():
    q, k, v, g, beta, state = _rule_inputs(16, seed=4)
    zero = jnp.zeros_like(g)
    _, handed = gated_delta.gated_delta_chunks(
        q, k, v, zero, zero, state, chunk=8, cd=jnp.float32)
    assert np.array_equal(np.asarray(handed), np.asarray(state))
    rows = jax.random.normal(jax.random.key(1), (2, 3, 5))
    x = jax.random.normal(jax.random.key(2), (2, 8, 5))
    w = jax.random.normal(jax.random.key(3), (4, 5))
    out, kept = gated_delta.causal_conv(rows, x, w, jnp.asarray([8, 0]))
    assert np.array_equal(np.asarray(kept[1]), np.asarray(rows[1]))
    assert np.array_equal(np.asarray(kept[0]), np.asarray(x[0, 5:]))
    cat = jnp.concatenate([rows, x], 1)
    np.testing.assert_allclose(
        out[:, 2], sum(w[j] * cat[:, 2 + j] for j in range(4)), atol=1e-6)


# -- (i') a step on the slots' state rows, where they lie ---------------------------
# What a TPU runs in place of gather, plain step and scatter
# (`gated_delta._step_kernel`), here in Pallas's interpreter: 22 cases, ~55
# CPU-seconds together.
def _row_inputs(lanes, idle=(), seed=0, heads=32, dk=8, dv=128, n_rows=12):
    """A step's inputs at whole (8, 128) tiles over `n_rows` state rows,
    the lanes' rows in any order; the lanes `idle` idle (beta = 0, g = 0)
    on one row that no live lane names."""
    q, k, v, g, beta, _ = (a[:, 0] for a in _rule_inputs(
        1, seed=seed, lanes=lanes, heads=heads, dk=dk, dv=dv))
    states = jax.random.normal(jax.random.key(seed + 100),
                               (n_rows, heads, dk, dv))
    order = jax.random.permutation(jax.random.key(seed + 200), n_rows)
    rows = order[:lanes].astype(jnp.int32)
    if idle:
        dead = jnp.isin(jnp.arange(lanes), jnp.asarray(idle))
        g, beta = (jnp.where(dead[:, None], 0.0, a) for a in (g, beta))
        rows = jnp.where(dead, order[lanes], rows)
    return q, k, v, g, beta, states, rows


def _as_the_plain_step(got, q, k, v, g, beta, states, rows, live=None):
    """`got` = (o, states) of a step over rows against gather, plain step
    and scatter; the rows no `live` lane names are `states`' to the bit."""
    live = np.ones(len(rows), bool) if live is None else live
    o, after = gated_delta.gated_delta_step(q, k, v, g, beta, states[rows])
    np.testing.assert_allclose(got[0][live], o[live], atol=2e-6)
    named = np.asarray(rows)[live]
    np.testing.assert_allclose(got[1][named], after[live], atol=2e-6)
    others = np.setdiff1d(np.arange(states.shape[0]), named)
    assert np.array_equal(np.asarray(got[1])[others],
                          np.asarray(states)[others])


@pytest.mark.parametrize("head_block", [8, 16, 32])
@pytest.mark.parametrize("lanes", [1, 5, 8])
def test_the_step_kernel_agrees_with_the_plain_step(lanes, head_block):
    """Rows in any order out of 12, 32 heads in blocks of `head_block`: the
    lanes' outputs and rows are the plain step's, and the rows no lane
    names are unchanged to the bit."""
    args = _row_inputs(lanes, seed=lanes)
    with pltpu.force_tpu_interpret_mode():
        got = gated_delta._step_kernel(*args, head_block=head_block)
    _as_the_plain_step(got, *args)


@pytest.mark.parametrize("head_block", [8, 32])
@pytest.mark.parametrize("idle", [(6, 7), (2, 5, 6), (0, 3, 4)],
                         ids=lambda lanes: "".join(map(str, lanes)))
def test_idle_lanes_share_a_row_and_leave_it_to_the_bit(idle, head_block):
    """A burst's idle lanes all name the spare slot's row, side by side or
    with live lanes between: it is written once an idle lane and stays
    what it was to the bit; the live lanes are right."""
    args = _row_inputs(8, idle=idle, seed=len(idle))
    with pltpu.force_tpu_interpret_mode():
        got = gated_delta._step_kernel(*args, head_block=head_block)
    _as_the_plain_step(got, *args, live=~np.isin(np.arange(8), idle))


def _takes_its_tpu_branch(monkeypatch, calls):
    """`jax.lax.platform_dependent` as a program lowered for a TPU has it,
    the kernels in Pallas's interpreter."""
    def as_for_a_tpu(*args, tpu, default):
        calls.append(tpu)
        return tpu(*args)

    monkeypatch.setattr(jax.lax, "platform_dependent", as_for_a_tpu)
    return pltpu.force_tpu_interpret_mode()


@pytest.mark.parametrize("heads,dk,dv,dtype,takes", [
    (32, 128, 128, jnp.float32, True), (8, 8, 128, jnp.float32, True),
    (32, 8, 128, jnp.bfloat16, False), (32, 8, 64, jnp.float32, False),
    (32, 4, 128, jnp.float32, False), (4, 8, 128, jnp.float32, False)],
    ids=["the published widths", "whole tiles, 8 heads", "a bfloat16 state",
         "a d_v of 64", "a d_k of 4", "4 heads"])
def test_which_step_takes_the_kernel(heads, dk, dv, dtype, takes,
                                     monkeypatch):
    """The predicate reads what the call sees: a float32 state of whole
    (8, 128) tiles in blocks of whole sublanes of heads.  Anything else is
    the plain form between a gather and a scatter, on a TPU too
    (`platform_dependent` is not asked)."""
    states = jax.ShapeDtypeStruct((12, heads, dk, dv), dtype)
    assert gated_delta._step_kernel_takes(states) == takes
    if dk == 128:                  # the sweep's choice; too large to run here
        assert gated_delta._step_head_block(heads, dk, dv) == 32
        return
    *args, states, rows = _row_inputs(3, heads=heads, dk=dk, dv=dv)
    states, calls = states.astype(dtype), []
    with _takes_its_tpu_branch(monkeypatch, calls):
        got = gated_delta.gated_delta_step_rows(*args, states, rows)
    assert len(calls) == takes and got[1].dtype == dtype
    o, after = gated_delta.gated_delta_step(*args, states[rows])
    atol = 2e-6 if dtype == jnp.float32 else 0.0
    np.testing.assert_allclose(got[0], o, atol=atol)
    np.testing.assert_allclose(
        np.asarray(got[1][rows], np.float32),
        np.asarray(after.astype(dtype), np.float32), atol=atol)


def test_a_burst_through_the_kernel_equals_its_plain_steps(monkeypatch):
    """The served burst at a state of whole tiles (8 value heads of
    8 x 128): 8 steps whose 6 linear layers each take the kernel on
    `lstate` viewed as rows, lanes on slots of their own with an idle lane
    between, against the same burst in the plain form."""
    cfg = dataclasses.replace(configs.get("tiny-gated-delta-moe"),
                              linear_v_heads=8, linear_d_v=128)
    params = init_params(jax.random.key(0), cfg)
    state = decoding.init_sequence_state(cfg, 17, 8, num_slots=4,
                                         prefill_chunk=32)
    # states that are not zero, but the spare slot's, as an engine keeps it
    state = dataclasses.replace(state, lstate=(0.3 * jax.random.normal(
        jax.random.key(1), state.lstate.shape)).at[:, 4].set(0.0))
    args = (jnp.asarray([5, 0, 7, 9], jnp.int32),
            jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(4, 4)),
            jnp.asarray([3, 0, 9, 1], jnp.int32),
            jnp.asarray([True, False, True, True]),
            jnp.zeros((4,), jnp.float32), jax.random.key(0))

    def burst():
        return jax.jit(decoding._bind_cfg(decoding.paged_decode_burst, cfg),
                       static_argnames=("n_steps",))(
            params, state, *args, n_steps=8,
            slots=jnp.asarray([2, 4, 0, 3], jnp.int32))

    plain, p_toks, *_ = burst()
    calls = []
    with _takes_its_tpu_branch(monkeypatch, calls):
        kernel, k_toks, *_ = burst()
    assert len(calls) == 3       # the period's three, traced once
    live = np.asarray(args[3])
    assert np.array_equal(np.asarray(k_toks)[:, live],
                          np.asarray(p_toks)[:, live])
    # a step's 2e-6, handed through 8 steps of 8 layers
    contract.leaves_agree(kernel, plain, atol=5e-5)
    assert np.array_equal(np.asarray(kernel.lstate[:, (1, 4)]),
                          np.asarray(state.lstate[:, (1, 4)]))
    assert np.abs(np.asarray(kernel.lstate - state.lstate)[:, 2]).max() > 0.1


# -- (ii) through the cache, against the full forward -------------------------------
@pytest.mark.parametrize("n_prompt", [100, 70, 33])
def test_prefill_in_chunks_then_decode_equals_the_reference(served, n_prompt):
    """A prompt is one launch of the tier that holds it (128 rows at 100
    and 70, 64 at 33: sixteen or eight chunks of 8 positions, the state
    handed on inside the program, the launch's tail padded); then 10
    decode steps of the rule's step form on the lanes' state."""
    e, c = served
    assert e.cache.lstate.shape == (6, 5, 4, 8, 8)
    assert e.cache.lstate.dtype == jnp.float32
    assert e.cache.lconv.shape == (6, 5, 3, 64) and e.cache.k.shape[0] == 2
    assert e.cache.wk is None
    contract.prefill_then_decode_equals_the_reference(
        FAM, e, c, 3, n_prompt, 10, seed=n_prompt)


@pytest.fixture
def one_tier(engines, monkeypatch):
    """An engine whose only launch is `prefill_chunk` = 32 rows, so that a
    prompt is several launches and the state goes from one to the next
    through the slot.  Its own: the tiers are read when it is built."""
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    with engines.private() as held:
        assert held[0]._chunk_tiers == [32]
        yield held


def test_state_handed_from_launch_to_launch_through_the_slot(one_tier):
    """100 positions as four launches of 32 rows (the last 4 of 32
    valid), each four chunks of 8; then decode steps."""
    contract.prefill_then_decode_equals_the_reference(
        FAM, *one_tier, 3, 100, 6, seed=1)


@pytest.mark.parametrize("n_prompt", [64, 81, 128])
def test_every_chunk_tier_and_a_padded_tail(engines, n_prompt):
    """prefill_chunk 64 has the tier 64 and, above it, 128 and 256: 81 is
    a launch of 128 rows whose valid rows end inside its third chunk of
    the rule.  The padded tail must not advance the recurrence."""
    contract.every_chunk_tier_and_a_padded_tail(FAM, engines, n_prompt,
                                                [64, 128, 256])


def _prefill_alone(cfg, params, tokens, size, pad_with=0):
    """`tokens` through `paged_prefill_chunk` in launches of `size` rows on
    a state of its own, slot 1 of two.  Returns (state, last logits)."""
    state = decoding.init_sequence_state(cfg, 17, 8, num_slots=2,
                                         prefill_chunk=32)
    run = contract.bound(decoding.paged_prefill_chunk, cfg)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    for start in range(0, len(tokens), size):
        toks = np.full((size,), pad_with, np.int32)
        nv = min(size, len(tokens) - start)
        toks[:nv] = tokens[start:start + nv]
        state, last, *_ = run(params, state, jnp.asarray(toks), table,
                              jnp.int32(start), jnp.int32(nv),
                              slot=jnp.int32(1))
    return state, last


def test_launch_sizes_give_the_same_state(served):
    """44 positions as launches of 8 rows (one chunk of the rule each), of
    16 and of 32 (two and four chunks, the last launch ragged) leave the
    same conv rows and state and give the same last logits; what stands in
    a launch's padded tail changes neither, to the bit; the null slot and
    the slot nobody had stay zero."""
    e, _ = served
    cfg, tokens = e.cfg, seqs(1, 44, seed=7)[0]
    whole, last = _prefill_alone(cfg, e.params, tokens, 32)
    for size in (8, 16):
        other, last_o = _prefill_alone(cfg, e.params, tokens, size)
        for name in ("lstate", "lconv"):
            np.testing.assert_allclose(
                np.asarray(getattr(other, name)),
                np.asarray(getattr(whole, name)), atol=2e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(last_o), np.asarray(last),
                                   atol=5e-5)
    junk, last_j = _prefill_alone(cfg, e.params, tokens, 32, pad_with=77)
    assert np.array_equal(np.asarray(junk.lstate), np.asarray(whole.lstate))
    assert np.array_equal(np.asarray(junk.lconv), np.asarray(whole.lconv))
    assert np.array_equal(np.asarray(last_j), np.asarray(last))
    assert np.asarray(whole.lstate[:, 1]).any()
    assert not np.asarray(whole.lstate[:, (0, 2)]).any()
    assert not np.asarray(whole.lconv[:, (0, 2)]).any()
    zeroed = cfg.reset_slot(whole, jnp.int32(1))
    assert not np.asarray(zeroed.lstate).any()
    assert not np.asarray(zeroed.lconv).any()
    assert np.array_equal(np.asarray(zeroed.k), np.asarray(whole.k))


def test_unequal_lanes_with_an_idle_lane_between(served):
    contract.unequal_lanes_with_an_idle_lane_between(FAM, *served)


def test_a_burst_equals_its_steps_and_counts_what_it_routed(served):
    cfg = served[0].cfg
    b_state, state, visited, routed, here = contract.burst_equals_its_steps(
        served[0], held=(4, 4))
    contract.leaves_agree(b_state, state)
    assert not np.asarray(b_state.lstate[:, 1]).any()   # a slot no lane had
    assert routed == here and 0 < here < 3 * 3 * 3 * cfg.n_layers
    assert 0 < visited <= 4 * 3 * cfg.n_layers


def test_the_routing_handed_out_is_of_every_layer(served):
    e, c = served
    fam = FAM.reference(c)
    rows = seqs(2, 40, seed=3)
    got, taken = e.score(rows, 36, routing=True)
    plain = e.score(rows, 36)
    for lane in range(2):
        assert taken[lane].shape == (40, 8, 3)
        assert taken[lane].min() < 4 <= taken[lane].max()  # router whole
        np.testing.assert_array_equal(np.stack(got[lane]),
                                      np.stack(plain[lane]))
        own, _ = fam.forward(e.params, jnp.asarray(rows[lane]), c,
                             jit=contract.jit, routing=None)
        handed, decided = fam.forward(e.params, jnp.asarray(rows[lane]), c,
                                      jit=contract.jit, routing=taken[lane])
        assert float(decided.min()) >= 1.0 - 1e-3
        np.testing.assert_allclose(handed, own, atol=5e-5)


# -- (iii) what is left out is seen ---------------------------------------------------
FAULTS = {"gate_left_out": dict(attn_gate=0),
          "whole_head_roped": dict(rotary_dim=0),
          "gains_without_the_one": dict(norm_plus_one=False),
          "shared_expert_ungated": dict(shared_gate=False),
          "shared_expert_dropped": dict(d_shared=0, shared_gate=False),
          "qk_norm_left_out": dict(qk_norm=False),
          "state_kept_in_bfloat16": dict(linear_state_dtype=jnp.bfloat16)}


@pytest.mark.parametrize("change", FAULTS.values(), ids=list(FAULTS))
def test_what_is_left_out_is_seen(engines, change, monkeypatch):
    """Float32 on both sides, 50 positions as two launches of 32 rows.  A
    state kept in bfloat16 (rounded where it is written to the slot, launch
    after launch and step after step) is 25 times the sound program's
    error or more: 5e-3 of the logits' rms."""
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    contract.a_fault_is_seen(FAM, engines, dataclasses.replace(
        FAM.program_config(FAM.config()), **change), times=25)


def _beta_dropped(monkeypatch):
    for name in ("gated_delta_step", "gated_delta_chunks"):
        inner = getattr(gated_delta, name)
        monkeypatch.setattr(
            gated_delta, name,
            lambda q, k, v, g, beta, *a, _f=inner, **kw: _f(
                q, k, v, g, jnp.where(beta > 0, 1.0, 0.0), *a, **kw))


def _decay_dropped(monkeypatch):
    for name in ("gated_delta_step", "gated_delta_chunks"):
        inner = getattr(gated_delta, name)
        monkeypatch.setattr(
            gated_delta, name,
            lambda q, k, v, g, *a, _f=inner, **kw: _f(
                q, k, v, jnp.zeros_like(g), *a, **kw))


def _the_gate_norm_without_silu_z(monkeypatch):
    monkeypatch.setattr(
        decoding, "_gated_norm",
        lambda o, z, gain, eps: decoding.rms_norm(o, gain, eps=eps))


def _state_not_handed_from_launch_to_launch(monkeypatch):
    inner = gated_delta.gated_delta_chunks
    monkeypatch.setattr(
        gated_delta, "gated_delta_chunks",
        lambda q, k, v, g, beta, state, **kw: inner(
            q, k, v, g, beta, jnp.zeros_like(state), **kw))


def _conv_rows_not_kept(monkeypatch):
    inner = gated_delta.causal_conv
    monkeypatch.setattr(
        gated_delta, "causal_conv",
        lambda rows, *a: inner(jnp.zeros_like(rows), *a))


@pytest.mark.parametrize("fault", [
    _beta_dropped, _decay_dropped, _the_gate_norm_without_silu_z,
    _state_not_handed_from_launch_to_launch, _conv_rows_not_kept],
    ids=lambda f: f.__name__.strip("_"))
def test_a_fault_of_the_rule_is_seen(engines, fault, monkeypatch):
    """Patched in before the engine's programs are traced; 50 prompt
    positions are two launches of 32 rows, so a state or conv rows lost
    between them reach the compared positions 18 and more rows on."""
    fault(monkeypatch)
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    contract.a_fault_is_seen(FAM, engines, None, times=25)


# -- (iv) the share tied to the model ------------------------------------------------
def test_the_four_quarters_add_up_to_the_uncut_layer():
    """The program's expert layer run as each of four ranks (two of the 8
    experts each; the router 8 wide on all), each with the gated shared
    expert: the four routed parts, the shared expert counted once, are the
    uncut reference's layer, and each part is the reference's given that
    share."""
    whole = FAM.config(num_experts=8, first_local_expert=0)
    fam = FAM.reference(whole)
    cfg8 = fam.program_config(whole)
    assert cfg8.experts_held is None
    layer = next(p for i, p in enumerate(fam.layer_weights(
        FAM.params(cfg8), whole)) if i == 3)
    u = jax.random.normal(jax.random.key(2), (1, 40, 48), jnp.float32)
    stacks = ("w_gate", "w_up", "w_down")
    h = fam._rms_norm(u[0], layer["mlp_norm"], 1e-6)
    shared = fam.shared(h, layer)
    parts, counts = [], []
    for first in (0, 2, 4, 6):
        quarter = FAM.config(num_experts=2, first_local_expert=first)
        cfg = fam.program_config(quarter)
        assert cfg.experts_held == (first, 2)
        bp = {k: (v[first:first + 2] if k in stacks else v)
              for k, v in layer.items()}
        out, visited, taken, routed = decoding._mlp(
            bp, u, cfg, {k: bp[k][None] for k in stacks}, 0,
            jnp.ones((1, 40), bool), True)
        want, _, bad = fam.experts(h, bp, taken[0], quarter)
        assert not bool(bad.any()) and int(visited) == 2
        np.testing.assert_allclose(out[0], want + shared, atol=2e-5)
        parts.append(out[0] - shared)
        counts.append(int(routed))
    assert sum(counts) == 40 * 3 and min(counts) > 10
    uncut, _, _ = fam.experts(h, layer, None, whole)
    np.testing.assert_allclose(sum(parts) + shared, uncut + shared,
                               atol=4e-5)
    assert float(jnp.abs(shared).max()) > 0.01
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 0.01


# -- (v) through the tick: slots, streams, counts --------------------------------------
def test_a_slot_reused_by_a_second_request_and_the_tick_log(served):
    e, c = served
    _, stats, ticks = contract.a_slot_reused_by_a_second_request(FAM, e, c)
    assert stats["state"]["state_resets"] == 2
    assert stats["state"]["recurrent"] == 6 * 5 * (
        4 * 8 * 8 * 4 + 3 * 64 * 4)
    assert stats["state"]["kv_window"] == 0
    assert stats["tick_fields"][-2:] == ("linear_state_rows",
                                         "delta_chunks")
    assert any(t["reset_s"] > 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([45]) == 2 * 45
    assert one["linear_state_rows"] == 6 and 0 < one["experts_read"] <= 3
    assert one["ring_slots"] == 0
    prefill = [t for t in ticks if t["prefill_tokens"] and not t["lanes"]]
    assert sum(t["prefill_tokens"] for t in prefill) == 60 + 45
    # 60 and 45 tokens are a launch of 64 rows each: eight chunks of 8
    # positions, six linear layers
    assert sum(t["delta_chunks"] for t in ticks) == 2 * 8 * 6


def test_a_slot_s_state_is_zero_after_admission(engines):
    """A request that ends leaves its state in the slot; the next one
    admitted to it starts from zero, which its first launch shows: its
    logits are a fresh engine's."""
    e, c = engines()
    prompt = contract.prompt(40, 3)
    first = e.generate(prompt, max_tokens=4)
    with e._tick_lock:
        e._drain()
        assert np.asarray(e.cache.lstate[:, 0]).any()
        zeroed = e._reset_state(e.cache, jnp.int32(0))
        assert not np.asarray(zeroed.lstate[:, 0]).any()
        assert not np.asarray(zeroed.lconv[:, 0]).any()
        e.cache = zeroed
    assert e.generate(prompt, max_tokens=4) == first
    assert FAM.is_greedy(e, c, prompt, first)


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger's state is zeroed with its lengths, and its re-prefill
    of prompt + emitted tokens rebuilds it."""
    stats = contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)
    assert stats["state"]["state_rebuilds"] >= 1


def test_streams_equal_the_step_reference_while_lanes_join_and_leave(engines):
    contract.streams_equal_the_step_reference_while_lanes_join_and_leave(
        FAM, engines)


# -- (vi) what this model cannot have yet is refused ------------------------------------
def test_refusals():
    cfg, params, _ = contract.refusals(FAM)
    with pytest.raises(ValueError, match="mesh"):
        from jax.sharding import Mesh
        PagedLLMEngine(cfg, params, num_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16,
                       mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        assert dep.engine.cfg.layer_pattern[-1] == "full"
        state = dep.stats()["state"]
        assert state["kv_window"] == 0 and state["recurrent"] > 0
        with pytest.raises(ValueError, match="recurrent state"):
            LLMDeployment("tiny-gated-delta-moe", engine="paged",
                          tensor_parallel=2)


# -- (vii) the benchmark's comparison has teeth ------------------------------------------
@on_the_engine
def _cache_in_8_bits(e, fam, monkeypatch):
    """Pool, conv rows and state through 8-bit floats after every launch
    and step."""
    contract.score_keeps(e, monkeypatch, lambda cache: jax.tree.map(
        contract.as_float8, cache))


@on_the_engine
def _weights_rounded_once_more(e, fam, monkeypatch):
    contract.program_with(e, fam, monkeypatch, jax.tree.map(
        lambda a: contract.as_float8(a) if a.ndim >= 2 else a, e.params))


@on_the_engine
def _one_held_expert_dropped(e, fam, monkeypatch):
    blocks = e.params["blocks"]
    contract.program_with(e, fam, monkeypatch, dict(e.params, blocks=dict(
        blocks, w_down=blocks["w_down"].at[:, 1].set(0))))


@on_the_engine
def _a_linear_layer_dropped(e, fam, monkeypatch):
    lin = e.params["kinds"]["linear"]
    contract.program_with(e, fam, monkeypatch, dict(e.params, kinds=dict(
        e.params["kinds"], linear=dict(
            lin, out_proj=lin["out_proj"].at[2].set(0)))))


@pytest.mark.parametrize("fault", [
    None, _cache_in_8_bits, _weights_rounded_once_more,
    _one_held_expert_dropped, _a_linear_layer_dropped],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    """bfloat16 as the benchmark's configuration states it; the family's
    limits are the published widths', so the bound here lies between this
    size's readings (above `TINY_BOUND`)."""
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


# -- (viii) the other models lower to the programs they lowered to ------------------------
# sha256 of the StableHLO text (`lowered.as_text()`: no locations) of the
# served programs and of the scoring step, taken on PR 63's tree (commit
# c62b809, this PR's parent: `git archive` of it unpacked beside this one,
# the lowering run there under `JAX_PLATFORMS=cpu`) at `SMALL_SHAPES`: the
# kind "linear" and its carry in `_paged_forward`, the two leaves of
# `PagedKVCache`, the gate's width, `gain_of` at every norm and the shared
# expert's gate leave the programs of the Mistral-, Mixtral-, Mellum-,
# Laguna- and SDAR-shaped presets as they were, to the letter: their
# compiled programs come from the cache as before.  (The other families'
# files hold some of these presets at `WIDE_SHAPES`, and still do.)
_LOWERED_AT_PR_63 = {
    ("tiny", "chunk"): "011fc65882f1d997",
    ("tiny", "burst"): "de79f35fa15a540e",
    ("tiny", "score_step"): "ef774f015ad15a75",
    ("tiny-moe", "chunk"): "46948ed989777a6e",
    ("tiny-moe", "burst"): "b9dd8b33061cf86f",
    ("tiny-moe", "score_step"): "47a6399944af0200",
    ("tiny-window-moe", "chunk"): "c240418b7a1b34bd",
    ("tiny-window-moe", "burst"): "04989cf7fe6f4582",
    ("tiny-window-moe", "score_step"): "3ddb031968909036",
    ("tiny-gated-moe", "chunk"): "184ea08b13ddc491",
    ("tiny-gated-moe", "burst"): "79f4b4afc8474661",
    ("tiny-gated-moe", "score_step"): "05723e77354bbe58",
    ("tiny-block-diffusion-moe", "chunk"): "8f5abadb2ba18576",
    ("tiny-block-diffusion-moe", "score_step"): "5d00aa7061c9146c",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_63),
                         ids=lambda v: str(v))
def test_the_other_presets_lower_as_at_the_parent(name, program):
    assert contract.lowered_digest(name, program, **contract.SMALL_SHAPES) \
        == _LOWERED_AT_PR_63[(name, program)]


def test_the_denoise_burst_lowers_as_at_the_parent():
    """`lowered_digest`'s burst takes `n_steps`; a model that fills blocks
    takes `n_blocks`: 4 lanes, a table of 8 pages of 8, 2 blocks."""
    cfg = configs.get("tiny-block-diffusion-moe")
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    state = jax.eval_shape(lambda: decoding.init_sequence_state(
        cfg, 33, 8, num_slots=4, prefill_chunk=16))
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    _, burst, _ = decoding.make_paged_engine_fns(cfg)
    lowered = burst.lower(
        params, state, arr(4, 4), arr(4, 4, dtype=jnp.bool_), arr(4, 8),
        arr(4), arr(4, dtype=jnp.bool_), arr(4, dtype=jnp.float32), key,
        n_blocks=2)
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16] \
        == "163f6d33b01feb36"


def test_a_pattern_without_linear_layers_imports_nothing_new():
    """`ops.gated_delta` is imported where a linear layer is traced, and
    nowhere else: the older families' path loads what it loaded."""
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "from ray_tpu.models import configs, decoding, init_params\n"
        "cfg = configs.get('tiny-gated-moe')\n"
        "p = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))\n"
        "s = jax.eval_shape(lambda: decoding.init_sequence_state(\n"
        "    cfg, 9, 8, num_slots=2, prefill_chunk=16))\n"
        "chunk, _, _ = decoding.make_paged_engine_fns(cfg)\n"
        "a = jax.ShapeDtypeStruct\n"
        "chunk.lower(p, s, a((16,), jnp.int32), a((8,), jnp.int32),\n"
        "            a((), jnp.int32), a((), jnp.int32),\n"
        "            slot=a((), jnp.int32))\n"
        "assert 'ray_tpu.ops.gated_delta' not in sys.modules\n"
        "cfg = configs.get('tiny-gated-delta-moe')\n"
        "p = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))\n"
        "s = jax.eval_shape(lambda: decoding.init_sequence_state(\n"
        "    cfg, 9, 8, num_slots=2, prefill_chunk=16))\n"
        "chunk, _, _ = decoding.make_paged_engine_fns(cfg)\n"
        "chunk.lower(p, s, a((16,), jnp.int32), a((8,), jnp.int32),\n"
        "            a((), jnp.int32), a((), jnp.int32),\n"
        "            slot=a((), jnp.int32))\n"
        "assert 'ray_tpu.ops.gated_delta' in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": contract.ROOT})
