"""A stack run more than once on the served path (`tiny-looped`: Ouro's
layout at test size, 3 layers x 3 passes over one set of weights), held to
the ouro family's plain float32 reference (`bench/families/ouro.py`, which
imports nothing of the program and keeps no cache: pass r attends over
pass r's own keys of the whole sequence): a position keeps a plane of the
pool a pass and layer, a sub-block stands between two norms, the final
norm follows every pass and an exit gate says which pass's state a row's
logits are read from.  The blocks alone are the sequence, so prefixes are
shared, blocks copied on write, frames shipped and drafts verified, every
one on all the passes' planes.  The served contract's cases are
`tests/served_contract.py`'s."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from bench.harness import reference, spec
from burst_ahead_cases import park, run_until_done, submit, tick
from ray_tpu.models import configs, decoding, init_params
from ray_tpu.models.transformer import TransformerConfig, forward
from ray_tpu.serve.llm import TICK_FIELDS
from served_contract import ROOT, Family, Teeth, seqs

LAYERS, PASSES = 3, 3
PLANES = LAYERS * PASSES
# Readings at this size in bfloat16 (CPU, seed 5: a width of 64 rounds
# coarsely): the program as it is reads 0.03-0.05 a position; the family's
# own limit is the published widths'.
TINY_BOUND = 0.12


def _a_burst_counts_its_passes(e, t):
    assert t["loop_passes"] == PLANES


FAM = Family(
    tiny="ourofamily/configs/tinyouro-serve.json",
    registry="tiny-looped", as_registry={},
    published=("ouro-2.6b", 1e6, 2668),                # "2.6B" published
    leaves=("tiny-looped", None),
    own_init=False, routes=False,
    deployment=dict(contract.SMALL, engine="paged"),
    burst_tick=_a_burst_counts_its_passes,
    teeth=Teeth(bound_key="LOGITS_REL", fault_reads="worst",
                tolerances={"LOGITS_REL": TINY_BOUND},
                decided_under_fault=True))
SEED, EXACT = FAM.seed, FAM.exact
LAST_PASS = {"early_exit_threshold": 1}    # the published threshold
engines, served = contract.fixtures(FAM)


# -- the configuration --------------------------------------------------------
def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.kinds == ("full",) * LAYERS and not cfg.state_by_slot
    assert (cfg.loop_passes, cfg.post_norm, cfg.exit_threshold) == (
        PASSES, True, 0.6)
    assert cfg.kv_planes == PLANES
    assert cfg.kv_read_tokens([10, 4]) == PLANES * 14
    pool = jax.eval_shape(lambda: decoding.init_paged_cache(cfg, 9, 8))
    assert pool.k.shape == pool.v.shape == (PLANES, 9, 8, 4, 16)
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    assert params["blocks"]["attn_post_norm"].shape == (LAYERS, 64) \
        == params["blocks"]["mlp_post_norm"].shape
    assert params["exit_gate"]["w"].shape == (64,)
    assert params["exit_gate"]["b"].shape == ()
    # what every other model is: one pass, no such norm, no gate
    plain = TransformerConfig()
    assert (plain.loop_passes, plain.post_norm, plain.exit_threshold) == (
        1, False, 0.0) and plain.kv_planes == plain.n_layers


@pytest.mark.parametrize("beside,says", [
    (dict(layer_pattern=("window", "full", "full"), window=8), "window"),
    (dict(layer_pattern=("linear", "linear", "full"), linear_k_heads=2,
          linear_v_heads=4, linear_d_k=8, linear_d_v=8), "'linear'"),
    (dict(layer_pattern=("conv", "conv", "full")), "'conv'"),
    (dict(diffusion_block=4, denoise_steps=2, mask_token_id=500),
     "diffusion_block"),
    (dict(n_experts=4), "n_experts"),
    (dict(loop_passes=1), "loop_passes is 1"),
    (dict(loop_passes=0), "1 or more"),
    (dict(exit_threshold=1.5), "share of 1"),
])
def test_what_stands_beside_the_passes_is_refused_by_name(beside, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(configs.get("tiny-looped"), **beside)


def test_the_train_and_offline_path_refuses_it_by_name():
    cfg = configs.get("tiny-looped")
    with pytest.raises(ValueError, match="loop_passes.*post_norm.*exit_thr"):
        forward({}, jnp.zeros((1, 4), jnp.int32), cfg)


def test_published_sizes_give_the_published_parameter_count():
    cfg, _ = contract.published_parameter_count(FAM)
    assert cfg.n_of("full") == 48 and cfg.loop_passes == 4
    assert cfg.kv_planes == 192
    # K and V of a position in every plane, bfloat16: 1.5 MiB
    assert cfg.kv_planes * 2 * cfg.n_kv_heads * cfg.head_dim * 2 == 1572864
    with open(os.path.join(ROOT, "bench", "configs",
                           "ouro-2.6b-serve-1chip.json")) as f:
        c = json.load(f)
    fam = spec.family(c)
    assert fam.program_config(c) == dataclasses.replace(cfg, name=c["name"])
    # the gains of the norms and the gate on top of the matrices
    assert cfg.num_params - fam.total_params(c) \
        == 48 * 4 * 2048 + 2048 + 2048 + 1


# -- the engine computes the reference's function ----------------------------------
def _exits(e, c, row):
    """The pass each position of `row` leaves at, by the reference."""
    with jax.default_matmul_precision("highest"):
        *_, took = spec.family(c).forward(
            e.params, jnp.asarray(row, jnp.int32), c, jit=contract.jit,
            passes=True)
    return np.asarray(took)


@pytest.mark.parametrize("threshold", [1, 0.6], ids=["last", "0.6"])
@pytest.mark.parametrize("n_prompt", [31, 32, 33, 67, 1])
def test_prefill_across_launches_then_decode_equals_the_reference(
        engines, threshold, n_prompt):
    """Prompts prefilled in launches of `prefill_chunk` = 32 rows (the
    family's `score`: one row short of a launch, a whole one, one row over,
    two and a tail of 3, a single row), every pass's KV handed from launch
    to launch through the pool, then 6 decode steps through the function
    the burst scans, two lanes a step: logits against the reference's full
    forward.  At the published threshold every row's logits are the last
    pass's; at 0.6 the compared rows leave at different passes."""
    e, c = engines(config={"early_exit_threshold": threshold})
    fam = FAM.reference(c)
    rows = seqs(2, n_prompt + 6, seed=n_prompt)
    before = dict(e.stats["prefill_launch_tokens"])
    got = fam.score(e, c, rows, n_prompt)
    assert e._chunk_tiers[-1] > e.prefill_chunk      # put back
    launched = {t: n - before[t]
                for t, n in e.stats["prefill_launch_tokens"].items()}
    assert {t for t, n in launched.items() if n} <= {32}    # score() counts
    took = set()
    for lane in range(2):
        errs = np.asarray(reference.position_errors(
            jnp.stack(got[lane]), FAM.want(e, c, rows[lane])[n_prompt - 1:]))
        assert errs.shape == (7,) and errs.max() < EXACT, errs
        took |= set(_exits(e, c, rows[lane])[n_prompt - 1:])
    if threshold == 1:
        assert took == {PASSES - 1}
    else:
        assert len(took) >= 2, took


def test_rows_of_one_launch_leave_at_every_pass(served):
    """Threshold 0.6 over a prompt of 200: the reference's exits take every
    pass, and the engine's widest launch (256 rows, one launch) agrees with
    it at its last row and through 4 decode steps."""
    e, c = served
    rows = seqs(1, 204, seed=7)
    assert set(_exits(e, c, rows[0])) == set(range(PASSES))
    errs = FAM.errors(e, c, rows, 200)
    assert errs.shape == (5,) and errs.max() < EXACT, errs


def test_no_gate_reads_the_last_pass(engines):
    """`exit_threshold` 0 is a model without a gate: the head reads the last
    pass, what the gated model does at the published threshold 1."""
    c = FAM.config(**LAST_PASS)
    cfg = dataclasses.replace(FAM.program_config(c), exit_threshold=0.0)
    assert cfg.num_params == FAM.program_config(c).num_params - 65
    with engines.private(config=LAST_PASS, cfg=cfg) as (e, c):
        errs = FAM.errors(e, c, seqs(2, 44, seed=3), 40)
    assert errs.max() < EXACT, errs


# -- a pass's planes are its own -----------------------------------------------
def _prefilled(e, rows):
    """A state of its own with `rows` prefilled (lane i in blocks
    1 + 8 i ..), the tables, and the step the burst scans."""
    cfg = e.cfg
    state = decoding.init_paged_cache(cfg, 1 + 8 * len(rows), 8)
    chunk = contract.bound(decoding.paged_prefill_chunk, cfg)
    tables = np.zeros((len(rows), 8), np.int32)
    for lane, row in enumerate(rows):
        tables[lane] = 1 + 8 * lane + np.arange(8)
        toks = np.zeros((64,), np.int32)
        toks[:len(row) - 1] = row[:-1]
        state, _ = chunk(e.params, state, jnp.asarray(toks),
                         jnp.asarray(tables[lane]), jnp.int32(0),
                         jnp.int32(len(row) - 1))
    return state, tables, contract.bound(decoding.paged_decode_step, cfg)


def test_a_pass_never_reads_a_later_pass_s_planes(served):
    """Six lanes' decode step at threshold 0.6, their rows leaving at
    passes 0, 1 and 2.  With every plane of the passes behind pass `upto`
    poisoned (NaN in every block), the lanes that leave at `upto` or before
    read logits equal to the bit: passes 0 .. upto read none of those
    planes, nor does the head; and the lanes that leave later read NaN:
    those passes do read their own."""
    e, c = served
    # prefixes of one sequence that end where the reference leaves at
    # pass 0, 1 and 2 (a row's exit depends on the tokens up to it alone)
    whole = seqs(1, 204, seed=7)[0]
    exits = _exits(e, c, whole)
    ends = [p for r in range(PASSES)
            for p in np.flatnonzero(exits[20:62] == r)[:2] + 20]
    rows = [whole[:p + 1] for p in ends]
    took = exits[ends]
    assert len(rows) == 6 and set(took) == set(range(PASSES)), took
    state, tables, step = _prefilled(e, rows)
    # every pass wrote planes of its own: no two of a layer's are equal
    for layer in range(LAYERS):
        planes = [np.asarray(state.k[r * LAYERS + layer, 1:6])
                  for r in range(PASSES)]
        assert all(p.any() for p in planes)
        assert not np.allclose(planes[0], planes[1], atol=1e-3)
        assert not np.allclose(planes[1], planes[2], atol=1e-3)
    args = (jnp.asarray([row[-1] for row in rows], jnp.int32),
            jnp.asarray(tables),
            jnp.asarray([len(row) - 1 for row in rows], jnp.int32),
            jnp.ones((6,), bool))
    _, clean = step(e.params, state, *args)
    clean = np.asarray(clean)
    assert np.isfinite(clean).all()
    for upto in range(PASSES - 1):
        behind = (upto + 1) * LAYERS
        poisoned = dataclasses.replace(
            state, k=state.k.at[behind:].set(jnp.nan),
            v=state.v.at[behind:].set(jnp.nan))
        _, got = step(e.params, poisoned, *args)
        got = np.asarray(got)
        assert np.array_equal(got[took <= upto], clean[took <= upto])
        assert np.isnan(got[took > upto]).all()


def test_idle_lanes_and_a_padded_tail_leave_the_live_blocks_as_they_were(
        served):
    """A decode step with an idle lane between two live ones, then a launch
    of 64 rows of which 5 are valid: in all nine planes the idle lane's
    blocks, a bystander's blocks and every block nobody holds stay equal to
    the bit; a live lane changes its own blocks alone, one position of
    them a plane."""
    e, _ = served
    rows = [seqs(1, 30 + lane, seed=40 + lane)[0] for lane in range(4)]
    state, tables, step = _prefilled(e, rows)
    before = jax.tree.map(np.asarray, state)
    state, _ = step(
        e.params, state, jnp.asarray([row[-1] for row in rows], jnp.int32),
        jnp.asarray(tables), jnp.asarray([29, 30, 31, 32], jnp.int32),
        jnp.asarray([True, False, True, False]))
    after = jax.tree.map(np.asarray, state)
    for leaf in ("k", "v"):
        was, now = getattr(before, leaf), getattr(after, leaf)
        assert np.array_equal(now[:, 9:17], was[:, 9:17])        # idle
        assert np.array_equal(now[:, 25:], was[:, 25:])          # idle
        changed = np.argwhere((now != was).any(axis=(3, 4)))
        # (plane, block, offset): position 29 of lane 0 (block 1 + 3,
        # offset 5), 31 of lane 2 (block 17 + 3, offset 7), the null block
        assert {tuple(x) for x in changed if x[1]} == {
            (p, blk, off) for p in range(PLANES)
            for blk, off in ((4, 5), (20, 7))}
    chunk = contract.bound(decoding.paged_prefill_chunk, e.cfg)
    toks = np.zeros((64,), np.int32)
    toks[:5] = rows[1][30:35] if len(rows[1]) >= 35 else 7
    state, _ = chunk(e.params, state, jnp.asarray(toks),
                     jnp.asarray(tables[1]), jnp.int32(30), jnp.int32(5))
    later = jax.tree.map(np.asarray, state)
    for leaf in ("k", "v"):
        was, now = getattr(after, leaf), getattr(later, leaf)
        mine = np.zeros(now.shape[1], bool)
        mine[9:17] = mine[0] = True
        assert np.array_equal(now[:, ~mine], was[:, ~mine])
        # the lane's own earlier positions too: 30 rows in blocks 9 .. 12
        assert np.array_equal(now[:, 9:12], was[:, 9:12])
        assert np.array_equal(now[:, 12, :6], was[:, 12, :6])
        assert (now[:, 12, 6:] != was[:, 12, 6:]).any(axis=(2, 3)).all()


# -- the blocks alone are the sequence, all passes of them ---------------------------
def test_a_block_copied_on_write_carries_every_pass(served):
    e, _ = served
    state = decoding.init_paged_cache(e.cfg, 5, 8)
    marks = jnp.arange(1.0, PLANES + 1)[:, None, None, None]
    state = dataclasses.replace(
        state, k=state.k.at[:, 1].set(jnp.broadcast_to(marks, (PLANES, 8, 4,
                                                               16))),
        v=state.v.at[:, 1].set(-jnp.broadcast_to(marks, (PLANES, 8, 4, 16))))
    out = decoding.copy_block(state, jnp.int32(3), jnp.int32(1))
    for plane in range(PLANES):
        assert float(out.k[plane, 3].min()) == plane + 1 \
            == float(out.k[plane, 3].max()) == -float(out.v[plane, 3].min())
    assert not np.asarray(out.k[:, 2]).any()


def test_a_prefix_shared_by_two_requests_carries_every_pass(served, engines):
    """The same prompt again hits the first one's blocks (45 tokens: five
    whole blocks shared, the sixth copied on write) and streams the same
    tokens, the reference's greedy ones: the shared blocks hold the KV of
    all three passes (the KV of pass r at position t depends on tokens
    <= t alone).  A stream's blocks shipped to another engine as a frame
    of (2, 9 planes, blocks, 8, 4, 16) are adopted there, and the prompt
    then hits them and streams the same."""
    src, c = served
    dst, _ = engines(num_slots=2, max_len=128)
    prompt = contract.prompt(45, 45)
    since = contract.Since(src)
    first = src.generate(prompt, max_tokens=36)
    assert FAM.is_greedy(src, c, prompt, first)
    assert src.generate(prompt, max_tokens=36) == first
    stats = since.stats()
    assert stats["prefix_hits"] == 1 and stats["cow_copies"] >= 1
    assert stats["prefill_launch_tokens"] == {
        t: 45 if t == 64 else 0 for t in stats["prefill_launch_tokens"]}
    park(src)
    req = submit(src, prompt, 36, stream=True)
    req.trace = {"trace_id": "rid-looped"}
    for _ in range(50):
        tick(src)
        if len(req.out_tokens) >= 4:
            break
    (ticket,) = src.export_streams()
    n_kv = len(ticket["tokens"])
    kv = np.asarray(ticket["kv"])
    assert kv.shape == (2, PLANES, -(-n_kv // 8), 8, 4, 16)
    assert all(kv[:, plane].any() for plane in range(PLANES))
    assert dst.import_prefix(ticket["tokens"], kv[:, :LAYERS], 8) == 0
    assert dst.import_prefix(ticket["tokens"], kv, 8) == -(-n_kv // 8)
    hits = dst.stats["prefix_hits"]
    assert dst.generate(prompt, max_tokens=36) == first
    assert dst.stats["prefix_hits"] == hits + 1
    run_until_done(src, [req])
    assert req.out_tokens == first


def test_speculation_equals_the_plain_stream(engines):
    """A verify step writes its drafts' KV in every pass's plane and rolls
    a rejected tail back by length alone."""
    prompt = [100, 200] * 12
    plain, _ = engines(max_burst=1)
    spec_, _ = engines(max_burst=1, speculation_k=4)
    since = contract.Since(spec_)
    assert plain.generate(prompt, max_tokens=12) \
        == spec_.generate(prompt, max_tokens=12)
    assert since.stats()["spec_accepted"] > 0


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    stats = contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)
    assert stats["state"]["state_resets"] == 0      # nothing by slot


def test_a_slot_reused_and_the_tick_log_counts_the_passes(served):
    e, c = served
    prompts, stats, ticks = contract.a_slot_reused_by_a_second_request(
        FAM, e, c)
    assert stats["tick_fields"] == TICK_FIELDS + ("loop_passes",)
    bursts = [t for t in ticks if t["lanes"]]
    assert bursts and {t["loop_passes"] for t in bursts} == {PLANES}
    assert {t["loop_passes"] for t in ticks if not t["lanes"]} <= {0}
    # the first burst of a request: its prompt's positions in nine planes
    assert {t["kv_read_tokens"] for t in bursts} >= {
        PLANES * len(p) for p in prompts}
    assert "loop_passes" not in contract.Since(
        e).stats()["request_phases"][:1]


def test_a_model_run_once_logs_no_passes():
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg = configs.get("tiny")
    e = PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg),
                       **contract.SMALL)
    try:
        e.generate(list(range(1, 20)), max_tokens=3)
        assert e.engine_stats()["tick_fields"] == TICK_FIELDS
    finally:
        e.shutdown()


def test_a_burst_equals_its_steps(served):
    contract.burst_equals_its_steps(served[0])


def test_streams_equal_the_step_reference_while_lanes_join_and_leave(engines):
    contract.streams_equal_the_step_reference_while_lanes_join_and_leave(
        FAM, engines)


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        state = dep.stats()["state"]
        assert state["kv_paged"] > 0 and state["kv_window"] == 0 \
            == state["recurrent"]


def test_tensor_parallel_serving_splits_the_planes_heads():
    """A mesh over the KV heads' axis: the pool of nine planes is split as
    any other, and the greedy stream is the single device's."""
    from jax.sharding import Mesh

    from ray_tpu.serve.llm import PagedLLMEngine

    cfg = configs.get("tiny-looped")
    params = FAM.params(cfg, seed=0)
    prompt = contract.prompt(40, 4)
    outs = []
    for mesh in (None, Mesh(np.array(jax.devices()[:2]), ("tp",))):
        e = PagedLLMEngine(cfg, params, **contract.SMALL, mesh=mesh)
        try:
            outs.append(e.generate(prompt, max_tokens=8))
            if mesh is not None:
                assert e.cache.k.shape[0] == PLANES
                assert e.cache.k.sharding.spec[3] == "tp"
        finally:
            e.shutdown()
    assert outs[0] == outs[1]


# -- the comparison that decides `correct` sees each fault -------------------------
@pytest.mark.parametrize("fault", ("sound",) + FAM.reference().FAULTS)
def test_logits_check_sees_each_control(engines, fault, monkeypatch):
    """`deployment.logits_check` (2 lanes x (the last of 40 prompt
    positions, two launches, + 6 decode steps), float32 throughout, the
    published threshold, its limit tightened to what float32 leaves) passes
    the program as it is and refuses it under each fault of the family's
    `control`: a pass left out, the planes of pass 0 read by every pass, a
    sub-block's second norm left out, the final norm applied twice, the
    pool in 8-bit floats.  (An engine a control: it patches what the
    programs are traced from.)"""
    from bench.harness.deployment import logits_check

    over = dict(LAST_PASS,
                check={"lanes": 2, "prompt_len": 40, "decode_steps": 6})
    c = FAM.config(**over)
    fam = spec.family(c)
    monkeypatch.setitem(fam.TOLERANCES, "LOGITS_REL", 100 * EXACT)
    cfg, undo = fam.control(fault, fam.program_config(c))
    try:
        with engines.private(config=over, cfg=cfg) as (e, c):
            v = logits_check(e, c, SEED)
    finally:
        undo()
    assert v["positions"] == 14 == v["decided"]
    if fault == "sound":
        assert v["ok"] and v["worst"] < EXACT, v
    else:
        assert not v["ok"] and v["worst"] > 10 * v["bound"], v


def _without_the_gate_s_bias(e, ref, monkeypatch):
    gate = e.params["exit_gate"]
    contract.program_with(e, ref, monkeypatch, {
        **e.params, "exit_gate": {**gate, "b": gate["b"] + 4.0}})


contract.on_the_engine(_without_the_gate_s_bias)


@pytest.mark.parametrize("fault", [None, _without_the_gate_s_bias],
                         ids=["sound", "gate_bias"])
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    """bfloat16 as the benchmark's configuration states it, threshold 0.6:
    the program as it is passes (a row whose summed exit probability lies
    within rounding of the threshold would leave at another pass; none of
    the 27 does on this seed), and a gate whose bias is off by 4 (every row
    leaves at the first pass) is refused."""
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


# -- every other model lowers to the program it lowered to ---------------------------
# sha256 of the StableHLO text of the served programs at `SMALL_SHAPES`,
# taken on PR 68's tree (commit cb5f228, this PR's parent: `git archive`
# of it unpacked beside this one, the lowering run there under
# `JAX_PLATFORMS=cpu`).  "plain-of-looped" is `tiny-looped` with one pass,
# no norm behind the sub-blocks and no gate, which on the parent is a
# `TransformerConfig` of those sizes: with the three fields at their
# defaults the passes' scan, the norms and the gate leave no trace in a
# program.  (`tiny`'s and the older presets' digests stand in
# tests/test_gated_delta_serving.py and the other families' files, and
# pass unedited.)
_LOWERED_AT_PR_68 = {
    ("plain-of-looped", "chunk"): "806b992129eba773",
    ("plain-of-looped", "burst"): "1288f5150c6f0d2e",
    ("plain-of-looped", "verify"): "1b0fbd77afda6f8c",
    ("plain-of-looped", "score_step"): "31496a9383b900b5",
    ("plain-of-looped", "copy_block"): "1e96187bc25b0f61",
    ("tiny", "verify"): "52829069696501de",
    ("tiny", "copy_block"): "bd7b78018a15c81a",
    ("tiny-short-conv-moe", "chunk"): "8ca4178c0965d1ef",
    ("tiny-short-conv-moe", "burst"): "ad69f6ed8acde3ac",
    ("tiny-short-conv-moe", "score_step"): "d5c8510f32e1eb25",
    ("tiny-gated-delta-moe", "chunk"): "59d91ff2834fa68a",
    ("tiny-gated-delta-moe", "burst"): "de1e5791221e3002",
    ("tiny-gated-delta-moe", "score_step"): "b56462bba856d89e",
}


@pytest.mark.parametrize("name,program", list(_LOWERED_AT_PR_68),
                         ids=lambda v: str(v))
def test_one_pass_and_no_second_norm_lower_as_at_the_parent(
        name, program, monkeypatch):
    monkeypatch.setitem(configs.REGISTRY, "plain-of-looped",
                        dataclasses.replace(
                            configs.get("tiny-looped"), name="plain-of-looped",
                            loop_passes=1, post_norm=False,
                            exit_threshold=0.0))
    assert contract.lowered_digest(name, program, **contract.SMALL_SHAPES) \
        == _LOWERED_AT_PR_68[(name, program)]
