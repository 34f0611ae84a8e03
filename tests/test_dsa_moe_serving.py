"""`MLAMoEConfig`'s two kinds of layer on the served path (`models.mla_moe`
with a layer pattern): full layers that attend to a learned selection of
their positions (an indexer's keys in a pooled leaf of their own, an exact
top-k, the absorbed read over the selected rows) and window layers that
keep a ring of latent rows by slot, a gate a head, rescaled latents, a
held share of scored experts; held to the dots3note family's plain
float32 reference (`bench/families/dots3note.py`, which imports nothing of
the program, attends in the plain, expanded form and takes its own top-k).
The old latent configuration (`tiny-mla-moe`) lowers to the parent's
programs to the letter.  The served contract's cases are
`tests/served_contract.py`'s; the selection by itself (kernels, search,
ties) is `tests/test_selection.py`."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from ray_tpu.models import configs, mla_moe
from ray_tpu.ops import attention
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine
from served_contract import Family, Teeth, on_the_engine, seqs

# Readings at this size (CPU, seed 8, bfloat16 parameters, compute and
# cache; a position's error: median, largest; the largest stray of its
# experts or its selection; the last lane's median stray of its selection
# over all rows): as it is 0.019, 0.034; 0.117; 0.0001.  The index keys in
# 8-bit floats 0.020, 0.058; 0.199; 0.022: the selection strays.  The rings
# 0.026, 0.066; 0.203; 0.  Pool, keys and rings 0.069, 0.132; 0.573; 0.057.
# One held expert dropped 0.270, 0.604; 3.4.  The family's own limits (0.05,
# 0.2, 0.3, 0.065) are the published widths'; here they are 0.048, 0.2,
# 0.15 and 0.005, between this size's readings.
TINY_BOUND, TINY_ROUTER_SLACK, TINY_SELECT_SLACK, TINY_SELECT_MEDIAN = \
    0.048, 0.2, 0.15, 0.005
TEETH_SEED = 8

FAM = Family(
    tiny="dotsfamily/configs/tinydots-serve.json", registry="tiny-dsa-moe",
    as_registry=dict(compute_dtype=contract.FLOAT32),
    published=("dots3-note-prev", 1e8, 2796),       # of the published 288B
    # the norms' gains and biases and the routers' biases on top
    leaves=("dots3-note-prev", 1e-4),
    # the program's experts and every row's selection, both of which the
    # reference refuses (NaN) outside their slack: among exactly equal
    # scores the two may take different positions
    handed=lambda taken: {"routing": taken["experts"],
                          "selection": (0, taken["selected"])},
    greedy_by_reference=False,
    deployment=dict(engine="paged", num_slots=2, max_len=128, block_size=8,
                    prefill_chunk=32), request=(50, 4),
    preempt=dict(engine=dict(num_blocks=15), prompts=((40, 40), (44, 44)),
                 max_tokens=40, stagger=0.0),
    teeth=Teeth(tolerances={"LOGITS_REL_EXPERTS": TINY_BOUND,
                            "ROUTER_SLACK": TINY_ROUTER_SLACK,
                            "SELECT_SLACK": TINY_SELECT_SLACK,
                            "SELECT_SLACK_MEDIAN": TINY_SELECT_MEDIAN},
                seed=TEETH_SEED))
SEED = FAM.seed
engines, served = contract.fixtures(FAM)


# -- the configuration ---------------------------------------------------------
def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert cfg.kinds == ("full", "full", "window", "window", "window",
                         "full", "window")
    assert cfg.state_by_slot and not cfg.recurrent
    assert not configs.get("tiny-mla-moe").state_by_slot
    assert cfg.kind("full").row_width == cfg.kind("window").row_width == 128
    assert cfg.ring_rows(32) == 48          # 12 + 32 in whole tiles of 16
    state = jax.eval_shape(lambda: cfg.init_state(9, 8, 4, 32))
    assert state.pooled == ("kv", "idx")
    assert state.kv.shape == (3, 9, 8, 128) and state.idx.shape == (3, 9, 8, 16)
    assert state.ring.shape == (4, 5, 48, 128)
    plain = jax.eval_shape(
        lambda: configs.get("tiny-mla-moe").init_state(9, 8, 4, 32))
    assert plain.pooled == ("kv",) and plain.idx is None and plain.ring is None


def test_published_sizes_give_the_published_parameter_count():
    cfg, _ = contract.published_parameter_count(FAM)
    assert cfg.n_of("full") == 13 and cfg.n_of("window") == 33


def test_bad_settings_are_refused():
    tiny = configs.get("tiny-dsa-moe")
    for change, match in ((dict(lead_pattern=("full", "full")), "leading"),
                          (dict(layer_pattern=("full", "local")), "one of"),
                          (dict(window=0), "window"),
                          (dict(index_heads=0), "selection"),
                          (dict(index_dim=4), "selection")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(tiny, **change)


def test_what_a_step_reads_is_counted_by_kind():
    cfg = configs.get("tiny-dsa-moe")     # 3 full (top 16), 4 window (12)
    assert cfg.kv_read_tokens([5]) == 7 * 5
    assert cfg.kv_read_tokens([14, 100]) == 3 * (14 + 16) + 4 * (12 + 12)
    assert cfg.selection_counts(0, 20) == (3 * 210, 3 * (136 + 4 * 16))
    assert cfg.selection_counts(30, 5) == (3 * 165, 3 * 5 * 16)
    assert configs.get("tiny-mla-moe").selection_counts(0, 20) == (0, 0)
    assert configs.get("tiny-mla-moe").kv_read_tokens([5, 7]) == 4 * 12


# -- (i) the engine computes the reference's function -----------------------------
@pytest.mark.parametrize("n_prompt", [100, 70, 33, 9])
def test_prefill_in_chunks_then_decode_equals_the_reference(served, n_prompt):
    """Prompts prefilled in launches of 32 rows through pool, index keys
    and rings, then 8 decode steps, three lanes a step: past the
    selection's 16 positions, past the window of 12 and past a turn of the
    48-row ring, and (9) under all three.  Against the reference handed
    the program's sets, which it holds to its own scores (float32 on both
    sides: no set strays at all; among exactly equal scores, zeros behind
    the relu, the two may take different positions, which is why the sets
    are handed over)."""
    e, c = served
    contract.prefill_then_decode_equals_the_reference(
        FAM, e, c, 3, n_prompt, 8, seed=n_prompt)
    assert FAM.reference(c).LAST["select_stray"] < 1e-4


def test_what_the_rows_took_is_handed_out_by_layer(served):
    e, c = served
    rows = seqs(2, 60, seed=3)
    _, taken = e.score(rows, 50, routing=True)
    for took in taken:
        assert took["experts"].shape == (60, 6, 3)
        sel = took["selected"]
        assert sel.shape == (60, 3, 16) and sel.dtype == np.int32
        for t in (0, 7, 15, 16, 40, 59):
            for layer in range(3):
                seen = sel[t, layer][sel[t, layer] <= t]
                assert len(set(seen.tolist())) == min(t + 1, 16)
    # layer 0's sets are the reference's own top-16, up to equal scores
    fam = FAM.reference(c)
    p = {k: v[0] for k, v in e.params["attn"].items()}
    u = fam._rms_norm(e.params["embed"][rows[0]], p["norm"], 1e-5)
    cq = fam.sizes(c, "full_attention")["r_q"] * fam._rms_norm(
        u @ p["wq_a"], p["q_norm"], 1e-5)
    scores = np.asarray(fam.index_scores(u, cq, p, c))
    for t in (20, 41, 59):
        mine = np.sort(scores[t, taken[0]["selected"][t, 0]])
        np.testing.assert_allclose(mine, np.sort(scores[t, :t + 1])[-16:],
                                   atol=1e-5)
    # the sets are learned: not the last 16, and not one layer's for all
    late = taken[0]["selected"][59]
    assert sorted(late[0].tolist()) != list(range(44, 60))
    assert sorted(late[0].tolist()) != sorted(late[1].tolist())


def test_a_burst_equals_its_steps_and_the_tick_log_counts(served):
    """Streams through the scheduler (chunks, then bursts of 8 steps): each
    token is the arg-max of the scoring entry's logits for the same
    sequence, and the ticks' `index_scored_tokens` / `kv_selected_tokens`
    are the model's count for the rows they ran; a burst on this host
    fetches its selections (`select_masked` 0)."""
    e, c = served
    cfg = e.cfg
    before = len(e.engine_stats()["tick_log"])
    base = dict(e.stats)
    prompt = contract.prompt(45, 45)
    out = e.generate(prompt, max_tokens=17)
    assert len(out) == 17
    got, _ = e.score(np.asarray(prompt + out)[None], len(prompt),
                     routing=True)
    assert [int(jnp.argmax(g)) for g in got[0]][:-1] == out
    stats = e.engine_stats()
    assert stats["tick_fields"][-7:-5] == ("index_scored_tokens",
                                           "kv_selected_tokens")
    assert stats["tick_fields"][-1] == "select_masked"
    ticks = [dict(zip(stats["tick_fields"], t))
             for t in stats["tick_log"][before:]]
    assert sum(t["prefill_tokens"] for t in ticks) == 45
    scored, selected = cfg.selection_counts(0, 45)
    bursts = [t for t in ticks if t["lanes"]]
    assert len(bursts) == 2                   # 16 of the 17 tokens
    assert {t["select_masked"] for t in ticks} == {0.0}
    for j in range(2):
        a, b = cfg.selection_counts(45 + 8 * j, 8)
        scored, selected = scored + a, selected + b
    assert sum(t["index_scored_tokens"] for t in ticks) == scored
    assert sum(t["kv_selected_tokens"] for t in ticks) == selected
    assert stats["index_scored_tokens"] - base["index_scored_tokens"] == scored
    assert stats["kv_selected_tokens"] - base["kv_selected_tokens"] \
        == selected
    assert all(t["kv_read_tokens"] == cfg.kv_read_tokens([n])
               for t, n in zip(bursts, (45, 53)))
    state = stats["state"]
    assert state["kv_paged"] == (e.cache.kv.size + e.cache.idx.size) * 4
    assert state["kv_window"] == e.cache.ring.size * 4
    assert state["state_resets"] == 0


def test_a_preempted_stream_equals_the_undisturbed_one(engines):
    """The younger's blocks are freed, and it re-prefills prompt + emitted
    through pool, index keys and its slot's rings (never zeroed: a row is
    seen only by the position that wrote it); every stream is what it is
    alone."""
    contract.preempted_stream_equals_the_undisturbed_one(FAM, engines)


def test_copy_on_write_copies_both_pooled_leaves():
    state, out = contract.copy_block_copies_both_pooled_leaves(
        configs.get("tiny-dsa-moe"))
    assert out.ring is state.ring


def test_full_layers_alone_share_and_ship_both_leaves():
    """An indexer without window layers: the blocks are the sequence (two
    engines of a configuration of its own)."""
    cfg = dataclasses.replace(
        configs.get("tiny-dsa-moe"), name="tiny-dsa-full", n_layers=4,
        layer_pattern=("full",), window=0, compute_dtype=jnp.float32)
    assert not cfg.state_by_slot
    params = cfg.init_params(jax.random.key(SEED))

    def engine():
        return PagedLLMEngine(cfg, params, num_slots=2, max_len=128,
                              block_size=8, prefill_chunk=32, max_burst=4,
                              prefix_sharing=True)

    src, dst = engine(), engine()
    try:
        contract.prefix_shared_and_both_leaves_shipped(
            src, dst, contract.prompt(70, 70), 12, "rid-dsa")
    finally:
        src.shutdown()
        dst.shutdown()


def test_what_rings_refuse_is_refused(served):
    """As for a `TransformerConfig` with window layers: blocks alone are
    not the sequence."""
    e, c = served
    with pytest.raises(ValueError, match="by slot"):
        e.import_prefix(list(range(8)), np.zeros((1, 3, 1, 8, 128)), 8)
    with pytest.raises(ValueError, match="by slot"):
        e.export_streams()
    with pytest.raises(ValueError, match="speculation_k"):
        FAM.build(c, speculation_k=4)
    with pytest.raises(ValueError, match="by slot"):
        LLMDeployment("tiny-dsa-moe", engine="paged", tensor_parallel=2)
    assert e.allocator.prefix_sharing is False      # turned off by the engine
    with pytest.raises(ValueError, match="slots"):
        e.cfg.served_step(e.params, e.cache, jnp.zeros((1, 4), jnp.int32),
                          jnp.zeros((1, 32), jnp.int32),
                          jnp.arange(4)[None], jnp.array([4]))


def test_deployment_takes_the_configuration_by_name():
    with contract.deployed(FAM) as dep:
        state = dep.stats()["state"]
        assert state["kv_window"] > 0 and state["recurrent"] == 0


# -- (iii) the share tied to the model ---------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """As each of two ranks of the tiny model (the router 8 wide on both)
    and, at the published split, as each of eight ranks of a 16-expert
    router."""
    for published, held in ((8, 4), (16, 2)):
        contract.ranks_shares_add_up(FAM, published, held, 1e-5)


# -- (iv) what is left out is seen --------------------------------------------------
def _last_in_place_of_best(scores, k, live=None):
    """The last k positions a row sees (a window, not a selection)."""
    seen = jnp.sum(scores > -1e29, axis=-1, keepdims=True)
    return ((seen - 1 - jnp.arange(k)) % scores.shape[-1]).astype(jnp.int32)


def _half_the_selection(scores, k, live=None):
    """The best k / 2, filled up with positions no row sees."""
    width = scores.shape[-1]
    best = jax.lax.top_k(scores, k // 2)[1]
    rest = jnp.broadcast_to(width - 1 - jnp.arange(k - k // 2),
                            best.shape[:-1] + (k - k // 2,))
    return jnp.concatenate([best, rest.astype(best.dtype)], axis=-1)


def _index_scores(relu=True, weights=True):
    def scores(q, w, pool, layer, tables, positions, kv_len):
        keys = pool[layer][tables].reshape(tables.shape[0], -1,
                                           pool.shape[-1])
        sc = jnp.einsum("sqhd,std->sqht", q, keys,
                        preferred_element_type=jnp.float32)
        sc = jax.nn.relu(sc) if relu else sc
        sc = jnp.sum(sc * (w[..., None] if weights else 1.0), axis=2)
        seen = jnp.arange(sc.shape[-1]) <= positions[:, :, None]
        return jnp.where(seen, sc, -1e30)
    return scores


def _patched(name, value):
    def fault(monkeypatch, cfg):
        monkeypatch.setattr(mla_moe, name, value)
        return cfg
    return fault


def _selecting(pick):
    """The program with `pick(scores, k, live)` for its selection: what it
    fetches and what it hands over."""
    def rows_of(scores, k, live, rows):
        at = pick(scores, k, live)
        picked = jnp.take_along_axis(scores, at, axis=-1)
        return (jnp.take_along_axis(
            jnp.broadcast_to(rows[:, None, :], scores.shape), at, axis=-1),
            picked > -1e29, jnp.maximum(picked.min(axis=-1), -5e29))

    def fault(monkeypatch, cfg):
        monkeypatch.setattr(attention, "select_positions", pick)
        monkeypatch.setattr(attention, "select_rows", rows_of)
        # the fetch as written, not the jitted one: an earlier test's
        # trace of it holds the sound selection
        monkeypatch.setattr(attention, "_fetch_best",
                            attention._fetch_best.__wrapped__)
        return cfg
    return fault


def _changed(**change):
    def fault(monkeypatch, cfg):
        return dataclasses.replace(cfg, **change)
    return fault


FAULTS = {
    "the_last_in_place_of_the_best": _selecting(_last_in_place_of_best),
    "half_the_selection": _selecting(_half_the_selection),
    "the_relu_dropped": _patched("paged_index_scores",
                                 _index_scores(relu=False)),
    "the_weights_dropped": _patched("paged_index_scores",
                                    _index_scores(weights=False)),
    "the_head_gate_dropped": _changed(attn_gate=False),
    "the_rescale_dropped": _changed(latent_rescale=False),
    "the_window_one_short": _changed(window=11),
}


def test_the_stand_in_scores_are_the_program_s():
    """`_index_scores()` with nothing dropped is `paged_index_scores`: the
    two faults built on it differ from the program by what they drop."""
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(1, 9, 8, 16)), jnp.float32)
    args = (jnp.asarray(rng.normal(size=(1, 3, 2, 16)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 3, 2)), jnp.float32), pool, 0,
            jnp.arange(1, 9)[None], jnp.array([[50, 51, 52]]),
            jnp.array([53]))
    np.testing.assert_allclose(_index_scores()(*args),
                               attention.paged_index_scores(*args),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fault", FAULTS.values(), ids=list(FAULTS))
def test_what_is_left_out_is_seen(engines, fault, monkeypatch):
    """Or its selection or routing strays outside the slack (NaN)."""
    cfg = fault(monkeypatch, FAM.program_config(FAM.config()))
    contract.a_fault_is_seen(FAM, engines, cfg)


# -- (v) the benchmark's comparison has teeth -------------------------------------
def _leaf_in_8_bits(*names):
    @on_the_engine
    def fault(e, fam, monkeypatch):
        contract.score_keeps(e, monkeypatch, lambda cache: dataclasses.replace(
            cache, **{n: contract.as_float8(getattr(cache, n))
                      for n in names}))
    return fault


@on_the_engine
def _one_held_expert_dropped(e, fam, monkeypatch):
    ffn = e.params["ffn"]
    contract.program_with(e, fam, monkeypatch, dict(e.params, ffn=dict(
        ffn, w_down=ffn["w_down"].at[:, 1].set(0))))


TEETH = {"as_it_is": None,
         "the_index_keys_in_8_bits": _leaf_in_8_bits("idx"),
         "the_rings_in_8_bits": _leaf_in_8_bits("ring"),
         "pool_keys_and_rings_in_8_bits": _leaf_in_8_bits("kv", "idx", "ring"),
         "one_held_expert_dropped": _one_held_expert_dropped}


@pytest.mark.parametrize("fault", TEETH.values(), ids=list(TEETH))
def test_logits_check_has_teeth(engines, fault, monkeypatch):
    """Experts and selection handed over and held to their slacks: fails a
    program whose index keys, whose rings or whose whole state are kept in
    8-bit floats, and one that drops a held expert."""
    contract.logits_check_has_teeth(FAM, engines, fault, monkeypatch)


# -- (vi) the old latent models lower to the programs they lowered to -------------
# sha256 of the StableHLO text (`lowered.as_text()`: no locations) of the
# served programs with the shapes below.  `tiny-mla-moe`: taken on PR 48's
# tree (commit e33cf29): the kinds of layer, the leaves of `LatentState`,
# the period's scan and the trees of what the rows took leave
# GLM-4.7-Flash's programs as they were, to the letter, so that its
# compiled programs come from the cache as before.  `tiny-dsa-moe` (dots3's
# layout) and `tiny-mhc-mla-moe` (Xing4.0's): taken on PR 58's tree (commit
# 3c96652, PR 59's parent): groups of experts in the selection
# (`MoEConfig.n_groups`) and their count in the tick log leave the
# programs of a configuration without groups as they were.  `tiny-dsa-moe`'s
# burst: taken on PR 60's tree, whose burst of a configuration that selects
# hands out one count more (its reads by the mask); its chunk, and every
# program of a configuration that selects nothing, are the older trees'.
_LOWERED_AT_THE_PARENT = {
    "tiny-mla-moe": {"chunk": "d7d54907d46b5ad4", "burst": "99cd8866034ddb13",
                     "copy_block": "de83fbd14fd07de6",
                     "verify": "c783a012baeae859"},
    "tiny-dsa-moe": {"chunk": "a6e75b7f8777bca9", "burst": "75cee36e4489b129",
                     "copy_block": "0ccb71cf37b52b1b"},
    "tiny-mhc-mla-moe": {"chunk": "ae4eefdbdedeed4b",
                         "burst": "5967f98f16941ca8",
                         "copy_block": "4e367a9e3bd6f856",
                         "verify": "ce4fab5ba1546dcf"}}


@pytest.mark.parametrize("name,program", [
    (name, program) for name, programs in _LOWERED_AT_THE_PARENT.items()
    for program in programs])
def test_the_old_latent_model_lowers_as_at_the_parent(name, program):
    assert contract.lowered_digest(name, program, **contract.SMALL_SHAPES) \
        == _LOWERED_AT_THE_PARENT[name][program]
