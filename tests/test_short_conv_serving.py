"""A fourth kind of layer in `TransformerConfig`'s stack, on the served path
(`configs.get("tiny-short-conv-moe")`: two leading conv layers with a dense
FFN, two periods of one full layer to three conv layers and a tail that is
no prefix of a period; a conv layer is a gated short convolution of three
rows, `ops.short_conv`, that keeps by the engine's slot its last two gated
rows and nothing else; 8 experts top-4 by sigmoid scores under a selection
bias, all held; the head is the embedding), held to the lfm2moe family's
plain float32 reference (`bench/families/lfm2moe.py`, which imports nothing
of the program and convolves the whole sequence by three shifted products).
The served contract's cases are `tests/served_contract.py`'s."""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from ray_tpu.models import configs, decoding, init_params
from ray_tpu.models.transformer import forward
from ray_tpu.ops import attention, gated_delta, short_conv
from ray_tpu.ops.moe import MoEConfig
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine
from served_contract import Family, Teeth, on_the_engine, seqs

# Float32 on both sides agrees to 2e-6 of the logits' rms at this size
# (three products and a sum a channel: nothing amplifies rounding).
EXACT = 2e-5
# Readings at this size in bfloat16 (CPU, seeds 5-7; a width of 64 rounds
# coarsely): beside `test_logits_check_has_teeth`.  The family's own two
# limits are the published widths'.
TINY_BOUND, TINY_SLACK, TEETH_SEED = 0.12, 1.0, 5
N_CONV, N_FULL = 9, 4


def _a_burst_counts_its_rows(e, t):
    assert t["conv_state_rows"] == N_CONV * t["lanes"]
    assert 0 < t["experts_read"] <= 8


FAM = Family(
    tiny="lfm2moefamily/configs/tinylfm2moe-serve.json",
    registry="tiny-short-conv-moe", as_registry={},
    published=("lfm2-8b-a1b", 1e7, 834),               # "8.3B" published
    leaves=("tiny-short-conv-moe", None),
    exact=EXACT, own_init=False, handed=lambda taken: {"routing": taken},
    front=None,
    deployment=dict(contract.SMALL, engine="paged"),
    slot_leaves=("lconv",), written=("lconv",),
    refusals=dict(speculation_k="speculation_k",
                  export_streams="export_streams",
                  import_prefix="import_prefix", frame=(2, 4, 4, 8, 2, 16),
                  deployment="recurrent state"),
    burst_tick=_a_burst_counts_its_rows,
    teeth=Teeth(tolerances={"LOGITS_REL_EXPERTS": TINY_BOUND,
                            "ROUTER_SLACK": TINY_SLACK},
                seed=TEETH_SEED))
engines, served = contract.fixtures(FAM)


# -- the configuration ---------------------------------------------------------
def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.lead_pattern == ("conv", "conv")
    assert cfg.kinds == ("conv", "conv") + ("full", "conv", "conv", "conv") \
        * 2 + ("full", "conv", "full")
    assert cfg.tail_pattern == ("full", "conv", "full") == cfg.layer_tail
    assert cfg.n_of("conv") == N_CONV and cfg.n_of("full") == N_FULL
    assert cfg.n_expert_layers == 11 and cfg.n_periods == 2
    assert cfg.moe == MoEConfig(num_experts=8, top_k=4, scoring="sigmoid")
    assert cfg.state_by_slot and cfg.recurrent and cfg.launch_spans_chunks
    assert cfg.mixers_by_kind and not cfg.heads_by_kind
    assert cfg.tie_embeddings and not decoding.counts_routed(cfg)
    assert cfg.kv_read_tokens([10, 20]) == N_FULL * 30     # the full layers'


def test_the_published_kinds_are_the_catalog_s_item_by_item():
    """`LFM2_8B_A1B.kinds` against the catalog row's `layer_types`: the
    published list is no whole periods (its last six layers are full, conv,
    conv, full, conv, conv), which `layer_tail` says."""
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    cfg = configs.get("lfm2-8b-a1b")
    kinds = {"conv": "conv", "full_attention": "full"}
    assert list(cfg.kinds) == [kinds[k] for k in row["config"]["layer_types"]]
    assert [i for i, k in enumerate(cfg.kinds) if k == "full"] \
        == [2, 6, 10, 14, 18, 21]
    assert cfg.lead_pattern == ("conv",) * row["config"]["num_dense_layers"]
    assert (cfg.n_periods, len(cfg.layer_tail)) == (4, 6)
    assert (cfg.head_dim, cfg.n_heads, cfg.n_kv_heads) == (64, 32, 8)
    assert (cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.expert_top_k) == (
        row["config"]["intermediate_size"],
        row["config"]["moe_intermediate_size"], 32, 4)
    assert cfg.conv_kernel == row["config"]["conv_L_cache"] == 3


def test_published_keys_give_the_published_parameter_count():
    """8.34 B ("8.3B"), ~1.5 B of them active a token ("A1B": 1.0 B
    without the embedding); the tiny preset's arrays count what
    `num_params` says, norms and the experts' bias included."""
    cfg, shapes = contract.published_parameter_count(FAM)
    assert cfg.n_layers == 24 and cfg.n_of("conv") == 18
    idle = 22 * (32 - 4) * 3 * 2048 * 1792
    assert 1.4e9 < cfg.num_params - idle < 1.6e9
    assert sorted(shapes["kinds"]) == ["conv", "full"]
    assert shapes["kinds"]["conv"]["in_proj"].shape == (7, 64, 192)
    assert shapes["kinds"]["conv"]["conv_w"].shape == (7, 3, 64)
    assert shapes["kinds"]["full"]["wq"].shape == (4, 64, 64)
    assert shapes["blocks"]["router_bias"].shape == (11, 8)
    assert shapes["blocks"]["router_bias"].dtype == jnp.float32
    assert sorted(shapes["lead"][0]) == [
        "attn_norm", "conv_w", "in_proj", "mlp_norm", "out_proj", "w_down",
        "w_gate", "w_up"]
    assert not {"wq", "wk", "in_proj", "lm_head"} & set(shapes["blocks"])
    assert "lm_head" not in shapes


def test_the_old_configurations_are_the_objects_they_were():
    for name in ("tiny", "tiny-moe", "tiny-window-moe", "tiny-gated-moe",
                 "tiny-block-diffusion-moe", "tiny-gated-delta-moe"):
        cfg = configs.get(name)
        assert not (cfg.layer_tail or cfg.router_bias or cfg.n_of("conv"))
        assert cfg.recurrent == (name == "tiny-gated-delta-moe")
        shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
        assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg.num_params
        assert "router_bias" not in shapes["blocks"]
    state = jax.eval_shape(lambda: decoding.init_sequence_state(
        configs.get("tiny-gated-delta-moe"), 9, 8, num_slots=2,
        prefill_chunk=16))
    assert state.lconv.shape == (6, 3, 3, 64) and state.lstate is not None


def test_bad_settings_are_refused():
    tiny = configs.get("tiny-short-conv-moe")
    for over in ({"layer_pattern": ("conv", "window"), "window": 8},
                 {"layer_pattern": ("conv", "linear", "full"),
                  "linear_k_heads": 2, "linear_v_heads": 2, "linear_d_k": 8,
                  "linear_d_v": 8}, {"conv_kernel": 1},
                 {"layer_tail": ("window",)}, {"layer_tail": ("full",)},
                 {"expert_scoring": "softmax"},
                 {"diffusion_block": 4, "denoise_steps": 2},
                 {"n_layers": 5}):
        with pytest.raises(ValueError):
            dataclasses.replace(tiny, **over)
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), tiny))
    with pytest.raises(ValueError, match="served model.*conv"):
        forward(params, jnp.zeros((1, 8), jnp.int32), tiny)


# -- (i) the op ---------------------------------------------------------------------
def _op_inputs(t, seed=0, lanes=2, c=5, width=3):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (lanes, width - 1, c)),
            jax.random.normal(k[1], (lanes, t, 3 * c)),
            jax.random.normal(k[2], (width, c)))


def _a_position_at_a_time(rows, bcz, w):
    """y_t = C_t * sum_j w_j h_{t - 2 + j}, h = B * z, from `rows`."""
    b, c, z = jnp.split(bcz, 3, axis=-1)
    h = jnp.concatenate([rows, b * z], 1)
    width = w.shape[0]
    return jnp.stack([c[:, t] * sum(w[j] * h[:, t + j] for j in range(width))
                      for t in range(bcz.shape[1])], 1), h[:, -(width - 1):]


@pytest.mark.parametrize("t,ragged", [(1, 0), (1, 1), (2, 1), (8, 3), (8, 8)])
def test_the_op_continues_the_convolution_from_the_kept_rows(t, ragged):
    """Any number of rows a launch, lane 1's last `ragged` not valid: the
    valid rows' outputs are the convolution's a position at a time, and
    the rows kept are the last two valid gated rows (the rows it had, to
    the bit, where none is valid)."""
    rows, bcz, w = _op_inputs(t, seed=t)
    n_valid = jnp.asarray([t, t - ragged])
    got, kept = short_conv.gated_short_conv(rows, bcz, w, n_valid)
    want, after = _a_position_at_a_time(rows, bcz, w)
    valid = jnp.arange(t)[None] < n_valid[:, None]
    np.testing.assert_allclose(np.where(valid[..., None], got, 0.0),
                               np.where(valid[..., None], want, 0.0),
                               atol=2e-6)
    np.testing.assert_allclose(kept[0], after[0], atol=1e-7)
    if ragged == t:
        assert np.array_equal(np.asarray(kept[1]), np.asarray(rows[1]))
    else:
        _, short = _a_position_at_a_time(rows[1:], bcz[1:, :t - ragged], w)
        assert np.array_equal(np.asarray(kept[1]), np.asarray(short[0]))


def test_a_step_after_a_chunk_is_one_more_position():
    """16 rows in one launch, then one: what two launches of 8 and a step
    give, and what one launch of 17 gives."""
    rows, bcz, w = _op_inputs(17, seed=3)
    want, after = _a_position_at_a_time(rows, bcz, w)
    full = jnp.asarray([16, 16])
    y, kept = short_conv.gated_short_conv(rows, bcz[:, :16], w, full)
    half = jnp.asarray([8, 8])
    y1, kept1 = short_conv.gated_short_conv(rows, bcz[:, :8], w, half)
    y2, kept2 = short_conv.gated_short_conv(kept1, bcz[:, 8:16], w, half)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=2e-6)
    np.testing.assert_allclose(kept2, kept, atol=1e-7)
    one = jnp.asarray([1, 1])
    y, kept = short_conv.gated_short_conv(kept, bcz[:, 16:], w, one)
    np.testing.assert_allclose(y[:, 0], want[:, 16], atol=2e-6)
    np.testing.assert_allclose(kept, after, atol=1e-7)


def test_the_op_is_the_linear_layers_convolution_under_a_gate():
    """`ops.gated_delta.causal_conv` is what both mixers continue."""
    rows, bcz, w = _op_inputs(8, seed=5)
    n = jnp.asarray([8, 5])
    b, c, z = jnp.split(bcz, 3, axis=-1)
    conv, kept = gated_delta.causal_conv(rows, b * z, w, n)
    y, kept_op = short_conv.gated_short_conv(rows, bcz, w, n)
    assert np.array_equal(np.asarray(y), np.asarray(c * conv))
    assert np.array_equal(np.asarray(kept), np.asarray(kept_op))


# -- (ii) through the cache, against the full forward -------------------------------
@pytest.mark.parametrize("n_prompt", [100, 70, 33, 1])
def test_prefill_in_chunks_then_decode_equals_the_reference(served, n_prompt):
    """A prompt is one launch of the tier that holds it (128 rows at 100
    and 70, 64 at 33, 32 for one row: the launch's tail padded); then 10
    decode steps on the lanes' two rows."""
    e, c = served
    assert e.cache.lconv.shape == (N_CONV, 5, 2, 64)
    assert e.cache.lstate is None and e.cache.wk is None
    assert e.cache.k.shape[0] == N_FULL
    contract.prefill_then_decode_equals_the_reference(
        FAM, e, c, 3, n_prompt, 10, seed=n_prompt)


@pytest.fixture
def one_tier(engines, monkeypatch):
    """An engine whose only launch is `prefill_chunk` = 32 rows, so that a
    prompt is several launches and the rows go from one to the next
    through the slot.  Its own: the tiers are read when it is built."""
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    with engines.private() as held:
        assert held[0]._chunk_tiers == [32]
        yield held


@pytest.mark.parametrize("n_prompt", [31, 32, 33, 63, 64, 65, 95, 96, 97])
def test_rows_handed_from_launch_to_launch_at_every_offset(one_tier,
                                                           n_prompt):
    """Launches of 32 rows: prompts of k x 32 - 1, k x 32 and k x 32 + 1
    positions end one row before a launch's end, on it and one row into
    the next, so the three-row window lies across the boundary at each of
    its offsets (and, at k x 32 + 1, the last launch holds one valid
    row); then decode steps."""
    contract.prefill_then_decode_equals_the_reference(
        FAM, *one_tier, 2, n_prompt, 5, seed=n_prompt)


@pytest.mark.parametrize("n_prompt", [64, 81, 128])
def test_every_chunk_tier_and_a_padded_tail(engines, n_prompt):
    """prefill_chunk 64 has the tier 64 and, above it, 128 and 256: 81 is
    a launch of 128 rows of which 47 are padding, which must leave the
    rows kept as the 81st position left them."""
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


def test_launch_sizes_give_the_same_rows(served):
    """44 positions as launches of 8 rows, of 16 and of 32 (the last launch
    ragged) leave the same rows, leading layers' and scanned ones', and
    give the same last logits; what stands in a launch's padded tail
    changes neither, to the bit; the null slot and the slot nobody had
    stay zero; `reset_slot` zeroes the rows and leaves the pool."""
    e, _ = served
    cfg, tokens = e.cfg, seqs(1, 44, seed=7)[0]
    whole, last = _prefill_alone(cfg, e.params, tokens, 32)
    for size in (8, 16):
        other, last_o = _prefill_alone(cfg, e.params, tokens, size)
        np.testing.assert_allclose(np.asarray(other.lconv),
                                   np.asarray(whole.lconv), atol=2e-5)
        np.testing.assert_allclose(np.asarray(last_o), np.asarray(last),
                                   atol=5e-5)
    junk, last_j = _prefill_alone(cfg, e.params, tokens, 32, pad_with=77)
    assert np.array_equal(np.asarray(junk.lconv), np.asarray(whole.lconv))
    assert np.array_equal(np.asarray(last_j), np.asarray(last))
    for layer in range(N_CONV):         # the two leading layers' come first
        assert np.asarray(whole.lconv[layer, 1]).any(), layer
    assert not np.asarray(whole.lconv[:, (0, 2)]).any()
    zeroed = cfg.reset_slot(whole, jnp.int32(1))
    assert not np.asarray(zeroed.lconv).any() and zeroed.lstate is None
    assert np.array_equal(np.asarray(zeroed.k), np.asarray(whole.k))


def test_unequal_lanes_with_an_idle_lane_between(served):
    contract.unequal_lanes_with_an_idle_lane_between(FAM, *served)


def test_a_burst_equals_its_steps(served):
    b_state, state, visited, _, _ = contract.burst_equals_its_steps(served[0])
    contract.leaves_agree(b_state, state)
    assert not np.asarray(b_state.lconv[:, 1]).any()    # a slot no lane had
    assert 0 < visited <= 8 * 3 * 11


def test_the_routing_handed_out_is_of_every_expert_layer(served):
    """The experts are taken by s + bias and gated by s: the reference,
    handed what the program took, agrees with its own choice; a reference
    that selects on the scores alone takes other experts somewhere."""
    e, c = served
    fam = FAM.reference(c)
    rows = seqs(2, 40, seed=3)
    got, taken = e.score(rows, 36, routing=True)
    plain = e.score(rows, 36)
    unbiased = 0
    for lane in range(2):
        assert taken[lane].shape == (40, 11, 4)
        np.testing.assert_allclose(np.stack(got[lane]),
                                   np.stack(plain[lane]), atol=5e-6)
        own, _ = fam.forward(e.params, jnp.asarray(rows[lane]), c,
                             jit=contract.jit, routing=None)
        handed, decided = fam.forward(e.params, jnp.asarray(rows[lane]), c,
                                      jit=contract.jit, routing=taken[lane])
        assert float(decided.min()) >= 1.0 - 1e-3
        np.testing.assert_allclose(handed, own, atol=5e-5)
        # layer 2's choice, had the bias been left out of the selection
        layer = next(p for i, p in enumerate(fam.layer_weights(e.params, c))
                     if i == 2)
        x = e.params["embed"][rows[lane]]
        for i, p in enumerate(fam.layer_weights(e.params, c)):
            if i == 2:
                break
            x = fam.dense_block(x, p, c, "conv")
        u = fam._rms_norm(x + fam.mixer(fam._rms_norm(
            x, layer["attn_norm"], 1e-5), layer, c, "full"),
            layer["mlp_norm"], 1e-5)
        s, pick = fam.scores(u, layer, c)
        by_s = np.sort(np.asarray(jax.lax.top_k(s, 4)[1]), -1)
        by_pick = np.sort(np.asarray(jax.lax.top_k(pick, 4)[1]), -1)
        np.testing.assert_array_equal(
            by_pick, np.sort(np.asarray(taken[lane][:, 0]), -1))
        unbiased += int((by_s != by_pick).any(-1).sum())
    assert unbiased > 0


# -- (iii) what is left out is seen ---------------------------------------------------
FAULTS = {"qk_norm_left_out": dict(qk_norm=False),
          "selection_without_the_bias": dict(router_bias=False),
          "softmax_scores": dict(router_bias=False,
                                 expert_scoring="softmax"),
          "the_head_untied": dict(tie_embeddings=False)}


@pytest.mark.parametrize("change", FAULTS.values(), ids=list(FAULTS))
def test_what_is_left_out_is_seen(engines, change, monkeypatch):
    """Float32 on both sides, 50 positions as two launches of 32 rows."""
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    stated = FAM.program_config(FAM.config())
    params = FAM.params(stated)
    if not change.get("router_bias", True):
        params = dict(params, blocks={k: v for k, v in params["blocks"].items()
                                      if k != "router_bias"})
    if "tie_embeddings" in change:
        params = dict(params, lm_head=params["embed"].T * 1.01)
    ref, stated_params = FAM.reference(), FAM.params(stated)
    plain = ref.forward         # the reference keeps the stated parameters
    monkeypatch.setattr(ref, "forward",
                        lambda p, *a, **kw: plain(stated_params, *a, **kw))
    with engines.private(cfg=dataclasses.replace(stated, **change),
                         params=params) as (e, c):
        errs = FAM.errors(e, c, seqs(2, 54, seed=9), 50)
    assert not np.isfinite(errs).all() or errs.min() > 25 * EXACT, errs


def _rows_not_kept(monkeypatch):
    inner = gated_delta.causal_conv
    monkeypatch.setattr(short_conv, "causal_conv",
                        lambda rows, *a: inner(jnp.zeros_like(rows), *a))


def _the_gate_by_c_dropped(monkeypatch):
    inner = short_conv.gated_short_conv

    def ungated(rows, bcz, w, n):
        b, c, z = jnp.split(bcz, 3, axis=-1)
        return inner(rows, jnp.concatenate([b, jnp.ones_like(c), z], -1),
                     w, n)

    monkeypatch.setattr(short_conv, "gated_short_conv", ungated)


def _the_gate_by_z_dropped(monkeypatch):
    inner = short_conv.gated_short_conv

    def ungated(rows, bcz, w, n):
        b, c, z = jnp.split(bcz, 3, axis=-1)
        return inner(rows, jnp.concatenate([b, c, jnp.ones_like(z)], -1),
                     w, n)

    monkeypatch.setattr(short_conv, "gated_short_conv", ungated)


def _rows_kept_in_bfloat16(monkeypatch):
    """The rows a slot keeps rounded to bfloat16 where the configuration
    says float32: the nearest precision below the stated one."""
    inner = gated_delta.causal_conv

    def rounded(rows, x, w, n):
        out, kept = inner(rows, x, w, n)
        return out, kept.astype(jnp.bfloat16).astype(kept.dtype)

    monkeypatch.setattr(short_conv, "causal_conv", rounded)


def _the_convolution_summed_in_bfloat16(monkeypatch):
    """The three products and their sum in bfloat16 where the file says
    float32 accumulation."""
    def conv(rows, x, w, n):
        width, k_w = w.shape[0], x.shape[1]
        cat = jnp.concatenate([rows.astype(x.dtype), x], axis=1)
        out = sum((w[j].astype(jnp.bfloat16)
                   * cat[:, j:j + k_w].astype(jnp.bfloat16))
                  for j in range(width))
        keep = jax.vmap(lambda c, m: jax.lax.dynamic_slice_in_dim(
            c, m, width - 1, axis=0))(cat, n)
        return out.astype(jnp.float32), keep.astype(rows.dtype)

    monkeypatch.setattr(short_conv, "causal_conv", conv)


def _router_scores_in_bfloat16(monkeypatch):
    """The sigmoid scores rounded to bfloat16 before selection and gates,
    where the file says float32."""
    from ray_tpu.ops import moe

    inner, sigmoid = moe.moe_mlp_dropless, jax.nn.sigmoid

    def rounded(*a, **kw):
        # for the program's expert layer alone: the reference's is jax's
        jax.nn.sigmoid = lambda x: sigmoid(x).astype(
            jnp.bfloat16).astype(jnp.float32)
        try:
            return inner(*a, **kw)
        finally:
            jax.nn.sigmoid = sigmoid

    monkeypatch.setattr(moe, "moe_mlp_dropless", rounded)


@pytest.mark.parametrize("fault", [
    _rows_not_kept, _the_gate_by_c_dropped, _the_gate_by_z_dropped,
    _rows_kept_in_bfloat16, _the_convolution_summed_in_bfloat16,
    _router_scores_in_bfloat16],
    ids=lambda f: f.__name__.strip("_"))
def test_a_fault_of_the_mixer_or_a_lower_precision_is_seen(engines, fault,
                                                           monkeypatch):
    """Patched in before the engine's programs are traced; 33 prompt
    positions are a launch of 32 rows and one of a single row, whose
    convolution reads the slot's two rows, as every decode step's does.
    A lower precision than the configuration states (rows kept in
    bfloat16, the convolution summed in bfloat16, the router's scores in
    bfloat16) is 25 times the sound program's error or more, or routes
    outside the reference's slack."""
    fault(monkeypatch)
    monkeypatch.setattr(llm, "_CHUNK_TOP_ROWS", 0)
    contract.a_fault_is_seen(FAM, engines, None, n_prompt=33, times=25)


# -- (iv) heads of half a lane tile ---------------------------------------------------
@pytest.mark.parametrize("hkv,d,bs,dtype", [
    (8, 64, 16, jnp.bfloat16), (2, 64, 8, jnp.float32),
    (4, 64, 8, jnp.float32)], ids=["lfm2", "two_of_64", "four_of_64"])
def test_a_pool_of_narrow_heads_is_kept_as_rows_of_whole_lanes(hkv, d, bs,
                                                               dtype):
    """`pages_as_rows` for heads of 64: two of a position's heads side by
    side in a row of 128 lanes; `paged_attention` over such a pool, a decode
    step and a chunk, is what it is over the pool kept by position, to the
    bit; a decode step says once why it takes the loop."""
    assert attention.pages_as_rows(hkv, d, bs, dtype)
    rows, lanes = attention.page_rows(hkv, d, bs)
    assert lanes == 128 and rows * lanes == bs * hkv * d
    assert not attention.pages_as_rows(2, 16, 8, jnp.float32)   # the presets'
    assert not attention.pages_as_rows(4, 32, 8, jnp.float32)
    assert not attention.pages_as_rows(3, 64, 16, jnp.float32)
    assert not attention.pages_as_rows(8, 128, 16, jnp.bfloat16)
    assert attention.page_rows(2, 256, 16) == (32, 256)
    lanes_n, blocks, rep = 3, 9, 2
    k = jax.random.split(jax.random.key(d), 3)
    pool_k = jax.random.normal(k[0], (2, blocks, bs, hkv, d)).astype(dtype)
    pool_v = jax.random.normal(k[1], (2, blocks, bs, hkv, d)).astype(dtype)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]])
    as_rows = lambda p: p.reshape(2, blocks, rows, lanes)  # noqa: E731
    for k_w in (1, 5):
        lens = jnp.asarray([3 * bs - 2, bs + 1, 2 * bs + k_w])
        pos = lens[:, None] - k_w + jnp.arange(k_w)[None]
        q = jax.random.normal(k[2], (lanes_n, k_w, hkv * rep, d)).astype(dtype)
        want = attention.paged_attention(q, pool_k, pool_v, 1, tables, pos,
                                         lens, kv_heads=hkv)
        got = attention.paged_attention(q, as_rows(pool_k), as_rows(pool_v),
                                        1, tables, pos, lens, kv_heads=hkv)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # three KV heads of 64 stay by position, and a decode step says why it
    # takes the loop
    odd = jax.random.normal(k[0], (2, blocks, bs, 3, d)).astype(dtype)
    with pytest.warns(UserWarning, match=f"not whole tiles.*head_dim {d} "
                                         f"against 128 lanes"):
        attention.paged_attention(
            q[:, :1, :3], odd, odd, 0, tables, lens[:, None] - 1, lens,
            kv_heads=3)


@pytest.mark.parametrize("hkv,rep,bs,dtype,lengths", [
    (8, 4, 16, jnp.bfloat16, [100, 0, 5, 64, 33]),
    (2, 3, 8, jnp.float32, [70, 16]), (4, 1, 8, jnp.bfloat16, [0, 96, 7])],
    ids=["lfm2", "two_heads_float32", "no_group"])
def test_the_decode_kernel_reads_heads_side_by_side(monkeypatch, hkv, rep,
                                                    bs, dtype, lengths):
    """`_paged_decode_side_by_side` in Pallas's TPU interpret mode (the
    decode kernel told of Hkv / 2 heads of 128, a query zero in the other
    head's lanes) against `paged_attention` as the CPU lowers it (the block
    loop) over the same pool kept by position: within 1e-2 of the loop's
    rms (the kernel rounds the probabilities to the rows' dtype), idle
    lanes exactly 0, float32 out, (S, 1, H, 64)."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_PAGED_KERNEL_PAGES", 2)
    d, s, entries = 64, len(lengths), -(-max(lengths) // bs) + 1
    rows, lanes = attention.page_rows(hkv, d, bs)
    k = jax.random.split(jax.random.key(hkv), 3)
    shape = (3, 1 + s * entries, bs, hkv, d)
    pool_k = jax.random.normal(k[0], shape).astype(dtype)
    pool_v = jax.random.normal(k[1], shape).astype(dtype)
    q = jax.random.normal(k[2], (s, 1, hkv * rep, d)).astype(dtype)
    tables = np.zeros((s, entries), np.int32)
    for lane, n in enumerate(lengths):
        used = -(-n // bs)
        tables[lane, :used] = 1 + lane * entries + np.arange(used)
    tables, kv_len = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    as_rows = lambda p: p.reshape(*p.shape[:2], rows, lanes)  # noqa: E731
    with pltpu.force_tpu_interpret_mode():
        got = attention._paged_decode_side_by_side(
            q, as_rows(pool_k), as_rows(pool_v), jnp.int32(2), tables, kv_len,
            scale=d ** -0.5, kv_heads=hkv)
    assert got.shape == (s, 1, hkv * rep, d) and got.dtype == jnp.float32
    live = np.asarray(kv_len) > 0
    assert not np.asarray(got[~live]).any()
    want = attention.paged_attention(
        q, pool_k, pool_v, jnp.int32(2), tables,
        jnp.maximum(kv_len - 1, 0)[:, None], kv_len, kv_heads=hkv)
    err = np.asarray(got - want)[live]
    assert np.sqrt((err ** 2).mean()) < 1e-2 * np.sqrt(
        (np.asarray(want)[live] ** 2).mean())


def test_the_served_path_writes_and_reads_such_a_pool(engines):
    """The tiny model with two KV heads of 64 (width 128): the pool is
    (L, N, 8 rows, 128), `_paged_forward` writes a position's two heads as
    one row, and prefill in launches then decode is the reference's."""
    with engines.private(config=dict(hidden_size=128, num_attention_heads=2,
                                     num_key_value_heads=2)) as (e, c):
        assert e.cfg.head_dim == 64
        assert e.cache.k.shape == (N_FULL, e.cache.k.shape[1], 8, 128)
        errs = FAM.errors(e, c, seqs(2, 75, seed=4), 70)
    assert errs.max() < EXACT, errs


# -- (v) through the tick: slots, streams, counts --------------------------------------
def test_a_slot_reused_by_a_second_request_and_the_tick_log(served):
    e, c = served
    _, stats, ticks = contract.a_slot_reused_by_a_second_request(FAM, e, c)
    assert stats["state"]["state_resets"] == 2
    assert stats["state"]["recurrent"] == N_CONV * 5 * 2 * 64 * 4
    assert stats["state"]["kv_window"] == 0
    assert stats["tick_fields"][-1] == "conv_state_rows"
    assert "linear_state_rows" not in stats["tick_fields"]
    assert any(t["reset_s"] > 0 for t in ticks)
    one = [t for t in ticks if t["lanes"] == 1][-1]
    assert one["kv_read_tokens"] == e.cfg.kv_read_tokens([45]) == N_FULL * 45
    assert one["conv_state_rows"] == N_CONV and 0 < one["experts_read"] <= 4
    assert one["ring_slots"] == 0
    prefill = [t for t in ticks if t["prefill_tokens"] and not t["lanes"]]
    assert sum(t["prefill_tokens"] for t in prefill) == 60 + 45
    assert all(t["conv_state_rows"] == 0 for t in prefill)


def test_a_slot_s_rows_are_zero_after_admission(engines):
    """A request that ends leaves its rows in the slot; the next one
    admitted to it starts from zero, which its first launch shows: its
    tokens are a fresh engine's."""
    e, c = engines()
    prompt = contract.prompt(40, 3)
    first = e.generate(prompt, max_tokens=4)
    with e._tick_lock:
        e._drain()
        assert np.asarray(e.cache.lconv[:, 0]).any()
        zeroed = e._reset_state(e.cache, jnp.int32(0))
        assert not np.asarray(zeroed.lconv[:, 0]).any()
        e.cache = zeroed
    assert e.generate(prompt, max_tokens=4) == first
    assert FAM.is_greedy(e, c, prompt, first)


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger's rows are zeroed with its lengths, and its re-prefill
    of prompt + emitted tokens rebuilds them."""
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
        assert dep.engine.cfg.lead_pattern == ("conv", "conv")
        state = dep.stats()["state"]
        assert state["kv_window"] == 0 and state["recurrent"] > 0
        with pytest.raises(ValueError, match="recurrent state"):
            LLMDeployment("tiny-short-conv-moe", engine="paged",
                          tensor_parallel=2)


# -- (vii) the benchmark's comparison has teeth ------------------------------------------
@on_the_engine
def _cache_in_8_bits(e, fam, monkeypatch):
    """Pool and conv rows through 8-bit floats after every launch and
    step."""
    contract.score_keeps(e, monkeypatch, lambda cache: jax.tree.map(
        contract.as_float8, cache))


@on_the_engine
def _weights_rounded_once_more(e, fam, monkeypatch):
    contract.program_with(e, fam, monkeypatch, jax.tree.map(
        lambda a: contract.as_float8(a) if a.ndim >= 2 else a, e.params))


@on_the_engine
def _one_expert_dropped(e, fam, monkeypatch):
    blocks = e.params["blocks"]
    contract.program_with(e, fam, monkeypatch, dict(e.params, blocks=dict(
        blocks, w_down=blocks["w_down"].at[:, 1].set(0))))


@on_the_engine
def _a_conv_layer_dropped(e, fam, monkeypatch):
    conv = e.params["kinds"]["conv"]
    contract.program_with(e, fam, monkeypatch, dict(e.params, kinds=dict(
        e.params["kinds"], conv=dict(
            conv, out_proj=conv["out_proj"].at[2].set(0)))))


@on_the_engine
def _a_leading_layer_s_mixer_dropped(e, fam, monkeypatch):
    lead = e.params["lead"]
    contract.program_with(e, fam, monkeypatch, dict(e.params, lead=[
        dict(lead[0], out_proj=jnp.zeros_like(lead[0]["out_proj"])),
        lead[1]]))


@pytest.mark.parametrize("fault", [
    None, _cache_in_8_bits, _weights_rounded_once_more, _one_expert_dropped,
    _a_conv_layer_dropped, _a_leading_layer_s_mixer_dropped],
    ids=lambda f: f.__name__.strip("_") if f else "as_it_is")
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    """bfloat16 as the benchmark's configuration states it; the family's
    limits are the published widths', so the bound here lies between this
    size's readings (above `TINY_BOUND`)."""
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


# -- (viii) nothing new is loaded where no layer convolves -------------------------------
def test_a_pattern_without_conv_layers_imports_nothing_new():
    """`ops.short_conv` is imported where a conv layer is traced, and
    nowhere else: the older families' path loads what it loaded."""
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "from ray_tpu.models import configs, decoding, init_params\n"
        "a = jax.ShapeDtypeStruct\n"
        "def lower(name):\n"
        "    cfg = configs.get(name)\n"
        "    p = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))\n"
        "    s = jax.eval_shape(lambda: decoding.init_sequence_state(\n"
        "        cfg, 9, 8, num_slots=2, prefill_chunk=16))\n"
        "    chunk, _, _ = decoding.make_paged_engine_fns(cfg)\n"
        "    chunk.lower(p, s, a((16,), jnp.int32), a((8,), jnp.int32),\n"
        "                a((), jnp.int32), a((), jnp.int32),\n"
        "                slot=a((), jnp.int32))\n"
        "lower('tiny-gated-delta-moe')\n"
        "assert 'ray_tpu.ops.short_conv' not in sys.modules\n"
        "lower('tiny-short-conv-moe')\n"
        "assert 'ray_tpu.ops.short_conv' in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": contract.ROOT})
