"""`MLAMoEConfig` with a selection in every layer and experts chosen group
by group on the served path (`tiny-group-moe`: DeepSeek-V3.2-Exp's layout
at test size), held to the deepseek_v32 family's plain float32 reference
(`bench/families/deepseek_v32.py`, which imports nothing of the program,
attends in the plain form, scores its own groups and takes its own top-k):
the shares of a layer add up to the uncut layer, the blocks alone are the
sequence (prefixes shared, blocks copied on write, frames shipped, with a
selection on), and every fault of the family's `control` is seen by the
comparison that decides `correct`.  The served contract's cases are
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
from ray_tpu.models import configs, decoding
from ray_tpu.serve.llm import TICK_FIELDS
from served_contract import ROOT, Family, seqs

FAM = Family(
    tiny="deepseekv32family/configs/tinydsv32-serve.json",
    registry="tiny-group-moe",
    as_registry=dict(compute_dtype=contract.FLOAT32),
    published=("deepseek-v3.2-exp", 1e8, 6719),      # the published 671B
    deployment=dict(engine="paged", num_slots=2, max_len=128, block_size=8,
                    prefill_chunk=32), request=(50, 4))
SEED, EXACT = FAM.seed, FAM.exact
engines, served = contract.fixtures(FAM)


# -- the configuration ---------------------------------------------------------
def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.kinds == ("full",) * 5 and not cfg.state_by_slot
    moe = cfg.moe
    assert (moe.n_groups, moe.groups_kept, moe.held) == (4, 2, (0, 2))
    assert configs.get("tiny-mla-moe").moe.n_groups == 1
    assert cfg.num_params == dataclasses.replace(
        cfg, expert_groups=1, expert_groups_kept=1).num_params
    state = jax.eval_shape(lambda: cfg.init_state(9, 8, 4, 32))
    assert state.pooled == ("kv", "idx") and state.ring is None
    assert state.kv.shape == (5, 9, 8, 128) and state.idx.shape == (5, 9, 8, 16)
    assert decoding.counts_groups(cfg)
    assert not decoding.counts_groups(configs.get("tiny-mla-moe"))
    assert not decoding.counts_groups(
        dataclasses.replace(cfg, experts_held=None))
    with pytest.raises(ValueError, match="does not divide"):
        dataclasses.replace(cfg, expert_groups=3)


def test_published_sizes_give_the_published_parameter_count():
    cfg, _ = contract.published_parameter_count(FAM)
    assert cfg.n_of("full") == 61 and cfg.n_dense_layers == 3
    assert (cfg.moe.n_groups, cfg.moe.groups_kept) == (8, 4)
    assert abs(cfg.attention_scale - 192 ** -0.5 * 1.8739) < 1e-5
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v3.2-exp-serve-1chip.json")) as f:
        c = json.load(f)
    fam = spec.family(c)
    whole = dict(c, **c["published"])
    assert fam.program_config(whole) == dataclasses.replace(
        cfg, name=c["name"])
    assert fam.matrix_params(whole)["total"] == cfg.num_params
    assert "671.9 B" in c["assumed"]["parameter_count"]
    cut = fam.program_config(c)
    assert cut.num_params == fam.matrix_params(c)["total"] == 3226140672
    assert cut.experts_held == (0, 8) and cut.n_experts == 256


# -- the engine computes the reference's function ----------------------------------
@pytest.mark.parametrize("n_prompt", [70, 9])
def test_prefill_in_chunks_then_decode_equals_the_reference(served, n_prompt):
    """Prompts prefilled in launches of 32 rows through the pool and the
    index keys, then 8 decode steps through the function the burst scans,
    two lanes a step: past the selection's 16 positions and (9) under
    them.  Against the reference handed the program's groups, experts and
    sets, which it holds to its own scores (float32 on both sides: nothing
    strays), and, for the routing, against the reference's own groups and
    top-k with no routing handed over."""
    e, c = served
    fam = FAM.reference(c)
    rows = seqs(2, n_prompt + 8, seed=n_prompt)
    got = fam.score(e, c, rows, n_prompt)
    for lane in range(2):
        tokens = jnp.asarray(rows[lane], jnp.int32)
        left = fam._HANDED[fam._key(rows[lane])]
        assert left["groups"].shape == (n_prompt + 8, 4, 2)
        want, margin = fam.forward(e.params, tokens, c, jit=contract.jit)
        errs = np.asarray(reference.position_errors(
            jnp.stack(got[lane]), want[n_prompt - 1:]))
        assert errs.shape == (9,) and errs.max() < EXACT, errs
        assert float(margin.min()) == 1.0 and fam.LAST["route_stray"] == 0.0
        if lane:
            continue
        own, _ = fam.forward(e.params, tokens, c, jit=contract.jit,
                             routing=None, selection=(0, left["selected"]))
        errs = np.asarray(reference.position_errors(
            jnp.stack(got[lane]), own[n_prompt - 1:]))
        assert errs.max() < EXACT, errs


def test_a_burst_equals_its_steps_and_the_tick_log_counts(served):
    """A stream through the scheduler (chunks, then bursts of 8 steps):
    each token is the arg-max of the scoring entry's logits for the same
    sequence, and the ticks' `group_open_rows` are the rows, a layer,
    whose handed-out groups hold group 0 (where experts 0-1 stand); a
    burst on this host fetches its selections (`select_masked` 0)."""
    e, c = served
    before = len(e.engine_stats()["tick_log"])
    prompt = contract.prompt(45, 45)
    out = e.generate(prompt, max_tokens=17)
    got, taken = e.score(np.asarray(prompt + out)[None], len(prompt),
                         routing=True)
    assert [int(jnp.argmax(g)) for g in got[0]][:-1] == out
    stats = e.engine_stats()
    assert stats["tick_fields"] == TICK_FIELDS + ("group_open_rows",
                                                  "select_masked")
    ticks = [dict(zip(stats["tick_fields"], t))
             for t in stats["tick_log"][before:]]
    assert sum(t["prefill_tokens"] for t in ticks) == 45
    assert sum(t["lanes"] for t in ticks) == 2          # two bursts of 8
    assert {t["select_masked"] for t in ticks} == {0.0}
    groups = taken[0]["groups"][:45 + 16]               # (rows, 4, 2)
    experts = taken[0]["experts"][:45 + 16]
    assert sum(t["group_open_rows"] for t in ticks) == int(
        (groups == 0).any(-1).sum())
    assert sum(t["routed_here"] for t in ticks) == int((experts < 2).sum())
    # a taken expert stands in a kept group; half the rows keep group 0
    assert (np.sort(groups, -1)[..., :, None] == experts[..., None, :] // 4
            ).any(-2).all()
    assert 0.2 < (groups == 0).any(-1).mean() < 0.8


def test_a_burst_counts_the_reads_that_took_the_mask(monkeypatch):
    """The burst's program at widths the masked kernel takes (a value of
    128 columns, 8 heads, float32), lowered twice: as on this host (the
    fetch: the count 0) and with the read sent where a TPU's goes
    (`_attend_masked`, its kernel in Pallas's interpret mode: the count
    its steps x the five layers, by the branch's own predicate).  Both
    sample the same tokens: a burst equals its steps either way."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import attention

    cfg = dataclasses.replace(
        configs.get("tiny-group-moe"), kv_rank=128, n_heads=8,
        compute_dtype=jnp.float32, param_dtype=jnp.float32)
    params = cfg.init_params(jax.random.key(SEED))
    lanes, steps, bs = 3, 4, 8

    def burst(cache):
        # lanes of 37 and 20 positions and an idle one, blocks of their own
        tables = jnp.arange(1, 1 + lanes * 8, dtype=jnp.int32).reshape(
            lanes, 8)
        return decoding.paged_decode_burst(
            params, cache, jnp.array([5, 7, 0], jnp.int32), tables,
            jnp.array([37, 20, 0], jnp.int32), jnp.array([True, True, False]),
            jnp.zeros((lanes,), jnp.float32), jax.random.key(1), cfg, steps)

    def fresh():
        state = decoding.init_sequence_state(cfg, 1 + lanes * 8, bs,
                                             num_slots=lanes, prefill_chunk=32)
        # seeded rows in every block (a seed whose steps have no tie at a
        # set's edge: one there sends a read to the fetch, and is counted)
        return jax.tree.map(
            lambda a: jax.random.normal(jax.random.key(6), a.shape, a.dtype),
            state)

    assert attention._masked_takes(
        (lanes, 1, 8, cfg.row_width), fresh().kv.shape, jnp.float32, 128) \
        is None
    *_, fetched = out = jax.jit(burst)(fresh())
    assert len(out) == 6 and int(fetched) == 0      # .., routed, masked

    def as_on_a_tpu(q, pool, layer, tables, kv_len, scores, at, k,
                    handed=False, *, d_v, scale):
        return attention._attend_masked(q, pool, layer, tables, kv_len,
                                        scores, at, k=k, handed=handed,
                                        d_v=d_v, scale=scale)

    monkeypatch.setattr(attention, "_attend_selected", as_on_a_tpu)
    monkeypatch.setattr(attention, "_MASKED_DECODE_PAGES", 2)
    with pltpu.force_tpu_interpret_mode():
        *_, masked = got = jax.jit(burst)(fresh())
    assert int(masked) == steps * cfg.n_layers
    # the live lanes' tokens and rows (an idle lane samples from garbage
    # and writes the null block)
    assert (np.asarray(got[1])[:, :2] == np.asarray(out[1])[:, :2]).all()
    np.testing.assert_allclose(got[0].kv[:, 1:], out[0].kv[:, 1:], atol=1e-5)


def test_the_tick_log_carries_the_burst_s_share_of_masked_reads(
        engines, monkeypatch):
    """`select_masked`: 100 x the burst's count over its steps x the
    layers that select, read with the burst's tokens; 0 in a tick without
    a burst.  (The count is made to say 1 a read: this host fetches; an
    engine of its own, traced with the count in place.)"""
    from ray_tpu.ops import attention

    fetch = attention._attend_selected

    def counted(*a, **kw):
        out, positions, _ = fetch(*a, **kw)
        return out, positions, jnp.int32(a[0].shape[1] == 1)

    monkeypatch.setattr(attention, "_attend_selected", counted)
    with engines.private() as (e, _):
        prompt = contract.prompt(45, 46)
        assert len(e.generate(prompt, max_tokens=17)) == 17
        stats = e.engine_stats()
    ticks = [dict(zip(stats["tick_fields"], t)) for t in stats["tick_log"]]
    assert [t["select_masked"] for t in ticks if t["lanes"]] == [100.0] * 2
    assert {t["select_masked"] for t in ticks if not t["lanes"]} == {0.0}


def test_the_benchmark_reads_the_mask_s_share_weighted_by_lanes():
    """`dsa_mask_share.decode` (bench/metrics): `select_masked` of the
    window's ticks weighted by their `lanes` (a tick without a burst
    weighs nothing), None from a log without the field (a parent
    commit's)."""
    import types

    from bench.harness import report

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == "dsa_mask_share.decode"]
    assert entry["workloads"] == ["dsv32-agent"]
    with open(spec.metric_file(spec.BENCH_DIR, entry["name"], ".json")) as f:
        metric = dict(json.load(f), **entry)
    fields = ("start", "tick_s", "lanes", "select_masked")
    ticks = ((9.0, 0.1, 8, 0.0),                # before the window
             (10.0, 0.1, 6, 100.0), (10.1, 0.1, 0, 0.0), (10.2, 0.1, 2, 50.0))

    def ctx(fields, ticks):
        return {"run": {"outcomes": [types.SimpleNamespace(
                    cause=None, first=1.0, request_id="r")]},
                "replica": {"stats": {
                    "request_phases": [{"id": "r", "submitted": 9.95,
                                        "ttft_s": 0.4}],
                    "tick_fields": fields, "tick_log": ticks}}}

    read = report._reader(metric)
    assert read(ctx(fields, ticks), **metric["args"]) == pytest.approx(87.5)
    assert read(ctx(fields[:3], [t[:3] for t in ticks]),
                **metric["args"]) is None


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        state = dep.stats()["state"]
        assert state["kv_paged"] > 0 and state["kv_window"] == 0


# -- blocks alone are the sequence, with a selection on ----------------------------
def test_prefixes_are_shared_copied_on_write_and_shipped(served, engines):
    src, _ = served                 # unparked when it is handed out again
    contract.copy_block_copies_both_pooled_leaves(src.cfg)
    dst, _ = engines(num_slots=2, max_len=128)
    contract.prefix_shared_and_both_leaves_shipped(
        src, dst, contract.prompt(70, 70), 36, "rid-group")


def test_speculation_equals_the_plain_stream(engines):
    """Speculation stays on for full layers alone: a verify step selects
    as a chunk does."""
    prompt = [100, 200] * 12
    plain, _ = engines(max_burst=1)
    spec_, _ = engines(max_burst=1, speculation_k=4)
    assert plain.generate(prompt, max_tokens=12) \
        == spec_.generate(prompt, max_tokens=12)


# -- the share tied to the model ---------------------------------------------------
@pytest.mark.parametrize("held", [2, 4], ids=["half a group", "a group"])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """16 experts in 4 groups, 2 kept, under group-limited routing: the
    ranks' `group_open_rows` are the rows that keep a group of theirs."""
    rows, _, opened = contract.ranks_shares_add_up(FAM, 16, held, 1e-6,
                                                   groups=True)
    assert opened == rows * 2 * (4 // held)     # 2 kept groups a row


# -- the comparison that decides `correct` sees each fault -------------------------
FAULTS = ("sound", "one_group", "group_max", "no_scale", "top_half",
          "keys_fp8", "pool_fp8")


@pytest.mark.parametrize("fault", FAULTS)
def test_logits_check_sees_each_control(engines, fault, monkeypatch):
    """`deployment.logits_check` (2 lanes x (the last of 40 prompt
    positions + 6 decode steps), float32 throughout, its limits tightened
    to what float32 leaves: nothing strays) passes the program as it is
    with every position decided and refuses the program under each fault
    of the family's `control`: by a group or an expert outside its slack,
    by a selection of the wrong size or outside its slack (NaN), or by
    the error of its logits.  (An engine a control: it patches what the
    programs are traced from.)"""
    from bench.harness.deployment import logits_check

    check = {"check": {"lanes": 2, "prompt_len": 40, "decode_steps": 6}}
    c = FAM.config(**check)
    fam = spec.family(c)
    for name, value in (("LOGITS_REL_EXPERTS", 100 * EXACT),
                        ("ROUTER_SLACK", 0.02), ("SELECT_SLACK", 0.02),
                        ("SELECT_SLACK_MEDIAN", 0.002)):
        monkeypatch.setitem(fam.TOLERANCES, name, value)
    cfg, undo = fam.control(fault, fam.program_config(c))
    try:
        with engines.private(config=check, cfg=cfg) as (e, c):
            v = logits_check(e, c, SEED)
    finally:
        undo()
    assert v["positions"] == 14
    if fault == "sound":
        assert v["ok"] and v["decided"] == 14 and v["worst"] < EXACT, v
    else:
        assert not v["ok"], v
        assert not v["finite"] or v["worst_decided"] > v["bound"], v
