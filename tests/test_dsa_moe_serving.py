"""`MLAMoEConfig`'s two kinds of layer on the served path (`models.mla_moe`
with a layer pattern): full layers that attend to a learned selection of
their positions (an indexer's keys in a pooled leaf of their own, an exact
top-k, the absorbed read over the selected rows) and window layers that
keep a ring of latent rows by slot, a gate a head, rescaled latents, a
held share of scored experts; held to the dots3note family's plain
float32 reference (`bench/families/dots3note.py`, which imports nothing of
the program, attends in the plain, expanded form and takes its own top-k).
Tiny widths, seeded weights, float32 compute where the claim is that the
engine computes the same function, bfloat16 where it is that the
benchmark's comparison tells a fault from rounding.  The old latent
configuration (`tiny-mla-moe`) lowers to the parent's programs to the
letter."""
import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.harness import reference, spec  # noqa: E402
from ray_tpu.models import configs, decoding, mla_moe  # noqa: E402
from ray_tpu.ops import attention  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine  # noqa: E402

TINY = os.path.join(ROOT, "bench", "tests", "data", "dotsfamily",
                    "configs", "tinydots-serve.json")
SEED = 5
EXACT = 2e-5          # float32 engine against float32 reference


def _config(**over):
    with open(TINY) as f:
        return dict(json.load(f), **over)


def _engine(c, cfg=None, params=None, **over):
    fam = spec.family(c)
    true = fam.program_config(c)
    eng = dict(c["engine"], **over)
    return PagedLLMEngine(
        cfg or true,
        true.init_params(jax.random.key(SEED)) if params is None else params,
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"],
        max_burst=eng["max_burst"], num_blocks=eng.get("num_blocks"),
        speculation_k=eng["speculation_k"])


def _errors(e, c, seqs, n_prompt, handed=True):
    """Every compared position's error against the reference: handed the
    program's experts and (`handed`) every row's selection, both of which
    it refuses (NaN) outside their slack; else its own top-k."""
    fam = spec.family(c)
    got, taken = e.score(seqs, n_prompt, routing=True)
    out = []
    for lane in range(len(seqs)):
        sel = (0, taken[lane]["selected"]) if handed else None
        want, _ = fam.forward(e.params, jnp.asarray(seqs[lane], jnp.int32),
                              c, jit=jax.jit, selection=sel,
                              routing=taken[lane]["experts"])
        out.append(np.asarray(reference.position_errors(
            jnp.stack(got[lane]), want[n_prompt - 1:])))
    return np.concatenate(out)


def _seqs(lanes, total, seed=0):
    return np.random.default_rng(seed).integers(1, 512, (lanes, total))


@pytest.fixture(scope="module")
def served():
    c = _config()
    e = _engine(c)
    yield e, c
    e.shutdown()


# -- the configuration ---------------------------------------------------------
def test_the_tiny_configuration_is_the_registry_s():
    c = _config()
    cfg = spec.family(c).program_config(c)
    assert cfg == dataclasses.replace(
        configs.get("tiny-dsa-moe"), name=c["name"],
        compute_dtype=jnp.dtype("float32"))
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
    cfg = configs.get("dots3-note-prev")
    assert round(cfg.num_params / 1e8) == 2796      # of the published 288B
    assert cfg.n_of("full") == 13 and cfg.n_of("window") == 33
    shapes = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    # the norms' gains and biases and the routers' biases on top
    assert 0 < total - cfg.num_params < 1e-4 * cfg.num_params


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
    and rings, then 8 decode steps through the function the burst scans,
    three lanes a step: past the selection's 16 positions, past the window
    of 12 and past a turn of the 48-row ring, and (9) under all three.
    Against the reference handed the program's sets, which it holds to
    its own scores (float32 on both sides: no set strays at all; among
    exactly equal scores, zeros behind the relu, the two may take
    different positions, which is why the sets are handed over)."""
    e, c = served
    fam = spec.family(c)
    seqs = _seqs(3, n_prompt + 8, seed=n_prompt)
    errs = _errors(e, c, seqs, n_prompt)
    assert errs.shape == (27,) and errs.max() < EXACT, errs
    assert fam.LAST["select_stray"] < 1e-4


def test_what_the_rows_took_is_handed_out_by_layer(served):
    e, c = served
    seqs = _seqs(2, 60, seed=3)
    _, taken = e.score(seqs, 50, routing=True)
    for took in taken:
        assert took["experts"].shape == (60, 6, 3)
        sel = took["selected"]
        assert sel.shape == (60, 3, 16) and sel.dtype == np.int32
        for t in (0, 7, 15, 16, 40, 59):
            for layer in range(3):
                seen = sel[t, layer][sel[t, layer] <= t]
                assert len(set(seen.tolist())) == min(t + 1, 16)
    # layer 0's sets are the reference's own top-16, up to equal scores
    fam = spec.family(c)
    p = {k: v[0] for k, v in e.params["attn"].items()}
    u = fam._rms_norm(e.params["embed"][seqs[0]], p["norm"], 1e-5)
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
    prompt = list(map(int, _seqs(1, 45, seed=45)[0]))
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


def test_a_preempted_stream_equals_the_undisturbed_one():
    """A pool too small for two growing streams: the younger is preempted,
    its blocks freed, and re-prefills prompt + emitted through pool, index
    keys and its slot's rings (never zeroed: a row is seen only by the
    position that wrote it); every stream is what it is alone."""
    import threading

    c = _config()
    prompts = [list(map(int, _seqs(1, n, seed=n)[0])) for n in (40, 44)]
    alone = []
    e = _engine(c)
    try:
        for p in prompts:
            alone.append(e.generate(p, max_tokens=40))
    finally:
        e.shutdown()
    e = _engine(c, num_blocks=15)
    outs = [None, None]
    try:
        def run(i):
            outs[i] = e.generate(prompts[i], max_tokens=40)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert e.stats["preemptions"] >= 1
    finally:
        e.shutdown()
    assert outs == alone


def test_copy_on_write_copies_both_pooled_leaves():
    cfg = configs.get("tiny-dsa-moe")
    state = cfg.init_state(5, 8, 2, 16)
    state = dataclasses.replace(
        state, kv=state.kv.at[:, 1].set(1.0), idx=state.idx.at[:, 1].set(2.0))
    out = decoding.copy_block(state, jnp.int32(3), jnp.int32(1))
    assert float(out.kv[:, 3].min()) == 1.0 == float(out.kv[:, 1].min())
    assert float(out.idx[:, 3].min()) == 2.0 and float(out.idx[:, 2].max()) == 0
    assert out.ring is state.ring


def test_full_layers_alone_share_and_ship_both_leaves():
    """An indexer without window layers: the blocks are the sequence, so
    a second request hits the first one's prefix (latent rows and index
    keys), and a stream's blocks shipped to another engine as a frame of
    both leaves side by side are adopted there, where the prompt then hits
    them and streams what it streamed at home."""
    from burst_ahead_cases import park, run_until_done, submit, tick

    cfg = dataclasses.replace(
        configs.get("tiny-dsa-moe"), name="tiny-dsa-full", n_layers=4,
        layer_pattern=("full",), window=0, compute_dtype=jnp.float32)
    assert not cfg.state_by_slot
    params = cfg.init_params(jax.random.key(SEED))

    def engine():
        return PagedLLMEngine(cfg, params, num_slots=2, max_len=128,
                              block_size=8, prefill_chunk=32, max_burst=4,
                              prefix_sharing=True)

    prompt = list(map(int, _seqs(1, 70, seed=70)[0]))
    src, dst = engine(), engine()
    try:
        first = src.generate(prompt, max_tokens=12)
        hits = src.stats["prefix_hits"]
        assert src.generate(prompt, max_tokens=12) == first
        assert src.stats["prefix_hits"] == hits + 1
        park(src)
        req = submit(src, prompt, 12, stream=True)
        req.trace = {"trace_id": "rid-dsa"}
        for _ in range(50):
            tick(src)
            if len(req.out_tokens) >= 4:
                break
        (ticket,) = src.export_streams()
        n_kv = len(ticket["tokens"])
        kv = np.asarray(ticket["kv"])
        assert kv.shape == (1, 4, -(-n_kv // 8), 8, 128 + 16)
        assert kv[..., 128:].any()                   # the index keys ride
        assert dst.import_prefix(ticket["tokens"], kv[..., :128], 8) == 0
        assert dst.import_prefix(ticket["tokens"], kv, 8) == -(-n_kv // 8)
        hits = dst.stats["prefix_hits"]
        assert dst.generate(prompt, max_tokens=12) == first
        assert dst.stats["prefix_hits"] == hits + 1
        run_until_done(src, [req])
        assert req.out_tokens == first
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
        _engine(c, speculation_k=4)
    with pytest.raises(ValueError, match="by slot"):
        LLMDeployment("tiny-dsa-moe", engine="paged", tensor_parallel=2)
    assert e.allocator.prefix_sharing is False      # turned off by the engine
    with pytest.raises(ValueError, match="slots"):
        e.cfg.served_step(e.params, e.cache, jnp.zeros((1, 4), jnp.int32),
                          jnp.zeros((1, 32), jnp.int32),
                          jnp.arange(4)[None], jnp.array([4]))


def test_deployment_takes_the_configuration_by_name():
    dep = LLMDeployment("tiny-dsa-moe", engine="paged", num_slots=2,
                        max_len=128, block_size=8, prefill_chunk=32)
    try:
        out = dep({"tokens": list(range(1, 50)), "max_tokens": 4})
        assert len(out["tokens"]) == 4
        state = dep.stats()["state"]
        assert state["kv_window"] > 0 and state["recurrent"] == 0
    finally:
        dep.engine.shutdown()


# -- (ii) the ops --------------------------------------------------------------
def test_the_index_scores_are_the_formula_s_through_the_table():
    """`paged_index_scores` against the formula written out, over a
    scattered table, two lanes of unlike lengths and a chunk's rows."""
    rng = np.random.default_rng(0)
    bs, hi, di, n_blocks = 8, 3, 16, 40
    pool = jnp.asarray(rng.normal(size=(2, n_blocks, bs, di)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_blocks))[:24]
                         .reshape(2, 12), jnp.int32)
    kv_len = jnp.array([90, 37])
    positions = jnp.stack([86 + jnp.arange(4), 33 + jnp.arange(4)])
    q = jnp.asarray(rng.normal(size=(2, 4, hi, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 4, hi)), jnp.float32)
    got = attention.paged_index_scores(q, w, pool, 1, tables, positions,
                                       kv_len)
    assert got.shape == (2, 4, 96)
    keys = pool[1][tables].reshape(2, 96, di)
    want = jnp.einsum("sqh,sqht->sqt", w, jax.nn.relu(
        jnp.einsum("sqhd,std->sqht", q, keys)))
    seen = np.arange(96)[None, None, :] <= np.asarray(positions)[:, :, None]
    np.testing.assert_allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(got)[~seen] < -1e29).all()


def test_the_selection_is_an_exact_top_k():
    scores = jnp.asarray(np.random.default_rng(1).normal(size=(2, 3, 64)),
                         jnp.float32).at[0, 0, 5].set(9.0).at[0, 0, 3].set(9.0)
    got = np.asarray(attention.select_positions(scores, 8))
    assert got.shape == (2, 3, 8) and got.dtype == np.int32
    want = np.sort(np.asarray(scores), axis=-1)[..., ::-1][..., :8]
    assert (np.take_along_axis(np.asarray(scores), got, -1) == want).all()
    assert set(got[0, 0, :2]) == {3, 5}              # the two equal bests
    assert attention.select_positions(scores, 100).shape == (2, 3, 64)


@pytest.mark.parametrize("live", [5, 8, 9, 16, 17, 40, 64])
@pytest.mark.parametrize("span", [16384, 16])
def test_tiers_and_spans_give_the_one_top_k(live, span, monkeypatch):
    """Candidates cut to the tier that holds the call's longest lane (8,
    16, 32 or all 64 here), a tier wider than a span sorted span by span
    and merged: the scores selected are those of one top-k over
    everything, equals included, each position once; and the rows the
    fetch gets are those positions' rows, in the same order (one
    selection, whatever rides with the scores)."""
    monkeypatch.setattr(attention, "_SELECT_SPAN", span)
    rng = np.random.default_rng(live)
    scores = np.round(rng.normal(size=(2, 3, 64)), 1).astype(np.float32)
    scores[..., live:] = -1e30                # nothing is live past it
    want = np.sort(scores, axis=-1)[..., ::-1][..., :8]
    got = np.asarray(jax.jit(
        lambda s, n: attention.select_positions(s, 8, n))(
            jnp.asarray(scores), jnp.int32(live)))
    assert (np.take_along_axis(scores, got, -1) == want).all()
    assert all(len(set(row.tolist())) == 8 for row in got.reshape(-1, 8))
    rows_of = jnp.asarray(rng.permutation(1000)[:128].reshape(2, 64),
                          jnp.int32)
    rows, seen, least = jax.jit(
        lambda s, n, r: attention.select_rows(s, 8, n, r))(
        jnp.asarray(scores), jnp.int32(live), rows_of)
    assert (np.asarray(seen) == (want > -1e29)).all()
    # the least score of the set: its last, or for a row that sees fewer
    # than 8 a number between every real score and the mask's
    assert (np.asarray(least) == np.maximum(want[..., -1], -5e29)).all()
    assert (np.asarray(rows) == np.take_along_axis(
        np.asarray(rows_of)[:, None, :].repeat(3, 1), got, -1)).all()


_SCORES = {
    "rounded": lambda rng, shape: np.round(rng.normal(size=shape), 1),
    "negative": lambda rng, shape: -1 - 1e3 * np.abs(rng.normal(size=shape)),
    "all_equal": lambda rng, shape: np.full(shape, 0.25),
    "zeros_of_both_signs": lambda rng, shape: rng.choice(
        [-0.0, 0.0, -2.0, 3.0], size=shape),
}


@pytest.mark.parametrize("live", [5, 8, 9, 16, 17, 40, 64])
@pytest.mark.parametrize("kind", list(_SCORES))
def test_the_search_ends_on_the_sort_s_least_score(live, kind):
    """`_edge_of_best`, the search that sorts nothing, over the tiers of
    `test_tiers_and_spans_give_the_one_top_k` and rows that see 0 to
    `live` positions: its least score is the sort's to the bit (halfway to
    the mask's for a row that sees under 8), it stands in the row, the
    counts around it say so (above < k <= above + equal), and `first` is
    the lowest position that holds it."""
    k = 8
    scores = _SCORES[kind](np.random.default_rng(live), (2, 3, 64)) \
        .astype(np.float32)
    sees = np.maximum(live - 3 * np.arange(3), 0)[None, :, None]
    scores[np.broadcast_to(np.arange(64) >= sees, scores.shape)] = -1e30
    least, above, equal, first = map(np.asarray, attention._edge_of_best(
        jnp.asarray(scores), k, jnp.int32(live)))
    by_the_sort = attention.select_rows(
        jnp.asarray(scores), k, None, jnp.zeros((2, 64), jnp.int32))[2]
    assert (least == np.asarray(by_the_sort)).all()
    assert (above == (scores > least[..., None]).sum(-1)).all()
    assert (equal == (scores == least[..., None]).sum(-1)).all()
    full = np.broadcast_to(sees[..., 0] >= k, least.shape)
    assert ((above < k) & (k <= above + equal))[full].all()
    assert (first == (scores == least[..., None]).argmax(-1))[full].all()
    # a row that sees under k: everything it sees is above, nothing at it
    assert (least[~full] == -5e29).all() and not equal[~full].any()
    assert (above == np.minimum(sees[..., 0], 64))[~full].all()


def test_the_selected_read_is_a_soft_max_over_exactly_the_set():
    """The fetch (`_selected_latent_attention`) against a masked dense
    soft-max over the same positions, with a set that holds positions the
    row does not see, handed over as rows of the pool laid flat; a chunk
    wider than `_SELECT_QUERY_ROWS` goes in groups."""
    rng = np.random.default_rng(2)
    bs, w, d_v, h, n_blocks, k = 8, 32, 24, 2, 20, 6
    pool = jnp.asarray(rng.normal(size=(2, n_blocks, bs, w)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_blocks))[:12]
                         .reshape(1, 12), jnp.int32)
    n_rows = 16
    positions = (40 + jnp.arange(n_rows))[None]
    q = jnp.asarray(rng.normal(size=(1, n_rows, h, w)), jnp.float32)
    selected = jnp.asarray(np.stack([
        rng.permutation(60)[:k] for _ in range(n_rows)])[None], jnp.int32)
    flat = pool[0][tables[0]].reshape(96, w)
    sc = jnp.einsum("qhe,te->qht", q[0], flat) * 0.3
    allowed = np.zeros((n_rows, 96), bool)
    for r in range(n_rows):
        allowed[r, [s for s in np.asarray(selected[0, r]) if s <= 40 + r]] = 1
    prob = jax.nn.softmax(jnp.where(allowed[:, None, :], sc, -jnp.inf), -1)
    want = jnp.einsum("qht,te->qhe", prob, flat[:, :d_v])
    rows = jnp.take_along_axis(tables[:, None, :], selected // bs,
                               axis=2) * bs + selected % bs
    seen = selected <= positions[:, :, None]
    for group in (128, 4):
        old, attention._SELECT_QUERY_ROWS = \
            attention._SELECT_QUERY_ROWS, group
        try:
            got = attention._selected_latent_attention(
                q, pool, 0, rows, seen, d_v=d_v, scale=0.3)
        finally:
            attention._SELECT_QUERY_ROWS = old
        np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-5)


def _a_chunk_that_selects(dtype, lengths, k_w, scores_of, *, heads=4, w=128,
                          d_v=24, k=16, bs=8, entries=6, seed=0):
    """`lengths` lanes of `k_w` query rows at `tiny-dsa-moe`'s widths (4
    heads over rows of 128, 24 of them the value; 16 selected): a pool
    whose null block and last block both stand in a table, index scores
    `scores_of(shape, key)` masked to the positions a row sees, each
    position's row of the pool, and `select_rows`' selection of them."""
    keys = jax.random.split(jax.random.key(seed), 4)
    lanes, width = len(lengths), entries * bs
    n_blocks = lanes * entries
    pool = jax.random.normal(keys[0], (2, n_blocks, bs, w),
                             jnp.float32).astype(dtype)
    q = jax.random.normal(keys[1], (lanes, k_w, heads, w),
                          jnp.float32).astype(dtype)
    # every block once, the pool's first row and its last among them
    tables = jax.random.permutation(keys[2], n_blocks).reshape(
        lanes, entries).astype(jnp.int32)
    kv_len = jnp.asarray(lengths, jnp.int32)
    positions = jnp.maximum(
        kv_len[:, None] - k_w + jnp.arange(k_w)[None, :], 0)
    scores = jnp.where(
        (jnp.arange(width) <= positions[:, :, None])
        & (kv_len > 0)[:, None, None],
        scores_of((lanes, k_w, width), keys[3]), attention._NEG_INF)
    at = jnp.repeat(tables, bs, axis=1) * bs + jnp.arange(width) % bs
    selection = attention.select_rows(scores, k, jnp.max(kv_len), at)
    return (q, pool, 1, tables, kv_len), selection, (scores, at, k)


def _ties(selection, scores):
    """(the rows whose set holds every position that ties with its last,
    those whose set holds one of them) as `_attend_masked` counts them."""
    _, seen, least = selection
    kept = seen.sum(-1) - (scores > least[..., None]).sum(-1)
    return (scores == least[..., None]).sum(-1) == kept, kept == 1


def _the_rule_s_set(scores, at, k):
    """What the threshold path attends, written out: the k best scores of
    a row (all it sees, if fewer), of equal scores the lower position.
    Returns (rows (S, K, k) of the pool, seen, positions)."""
    sc = np.asarray(scores)
    order = np.argsort(-sc, axis=-1, kind="stable")[..., :k]
    picked = np.take_along_axis(sc, order, -1)
    rows = np.take_along_axis(
        np.broadcast_to(np.asarray(at)[:, None, :], sc.shape), order, -1)
    return jnp.asarray(rows), jnp.asarray(picked > -1e29), order


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lengths,k_w", [
    ((40,), 8),        # every row sees more than the 16 it selects
    ((11,), 11),       # rows that see 1, 2, .. 11: all of them selected
    ((18,), 4),        # the count's edge: rows that see 15, 16, 17, 18
    ((48, 0, 5), 1),   # a row a lane, one of them idle (nothing selected)
    ((17, 30), 5),     # query rows that are no multiple of the group
    ((33, 48), 8),     # and a multiple, a lane that fills its table
])
def test_the_masked_kernel_agrees_with_the_fetch(dtype, lengths, k_w,
                                                 monkeypatch):
    """`_masked_latent_kernel` in Pallas's interpret mode (Mosaic needs a
    TPU; `tests/test_tpu_compile.py` compiles it for a described one)
    against `_selected_latent_attention` over `select_rows`' selection:
    the same set read two ways, the kernel's in steps of two pages under
    a running soft-max and groups of four query rows."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_MASKED_QUERY_ROWS", 4)
    monkeypatch.setattr(attention, "_MASKED_KERNEL_PAGES", 2)
    lanes, (rows, seen, least), (scores, _, _) = _a_chunk_that_selects(
        dtype, lengths, k_w, lambda shape, key: jax.random.normal(key, shape))
    want = attention._selected_latent_attention(
        *lanes[:3], rows, seen, d_v=24, scale=0.2)
    with pltpu.force_tpu_interpret_mode():
        got = attention._masked_latent_kernel(
            *lanes, scores, least, jnp.full(least.shape, -1), d_v=24,
            scale=0.2)
    assert got.shape == want.shape and got.dtype == jnp.float32
    live = np.asarray(lanes[4]) > 0
    assert not np.asarray(got[~live]).any()
    # float32: the two differ by the order of their sums; bfloat16: the
    # kernel rounds exp(s - m) to the rows' dtype, the fetch the
    # normalised probabilities (2**-9 each)
    bound = 2e-6 if dtype == jnp.float32 else 1.5e-2
    rms = float(jnp.sqrt(jnp.mean(want[live] ** 2)))
    assert float(jnp.abs(got - want)[live].max()) < bound * rms


@pytest.mark.parametrize("decimals,seed,every_row_settled", [
    (1, 0, True), (1, 3, False), (0, 4, False)])
def test_equal_scores_at_a_set_s_edge_are_the_sort_s_to_settle(
        decimals, seed, every_row_settled, monkeypatch):
    """Index scores rounded until positions tie with a set's last: where
    every row keeps all of them or one alone the launch reads the mask,
    and that is a fetch of the rule's set (of equal scores the lowest
    position, found by its row of the pool); a launch in which a row
    keeps several and leaves one out takes the fetch of the sort's set,
    to the bit.  The positions handed over are the set that was read."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_MASKED_QUERY_ROWS", 4)
    monkeypatch.setattr(attention, "_MASKED_KERNEL_PAGES", 2)
    lanes, selection, best = _a_chunk_that_selects(
        jnp.float32, (48, 40), 6, seed=seed,
        scores_of=lambda shape, key: jnp.round(
            jax.random.normal(key, shape), decimals))
    scores, at, k = best
    every, one = _ties(selection, scores)
    assert not bool(every.all()) and bool((~every & one).any())
    assert bool((every | one).all()) == every_row_settled
    rows, seen, positions = _the_rule_s_set(*best)
    by_the_rule = attention._selected_latent_attention(
        *lanes[:3], rows, seen, d_v=24, scale=0.2)
    by_the_sort = attention._selected_latent_attention(
        *lanes[:3], *selection[:2], d_v=24, scale=0.2)
    with pltpu.force_tpu_interpret_mode():
        got, handed, masked = attention._attend_masked(
            *lanes, scores, at, k=k, handed=True, d_v=24, scale=0.2)
    assert int(masked) == every_row_settled
    if every_row_settled:
        np.testing.assert_allclose(got, by_the_rule, atol=2e-6)
        # the sort took another of the tied positions in some row
        assert float(jnp.abs(by_the_sort - by_the_rule).max()) > 1e-3
        assert (np.sort(positions, -1) == np.sort(handed, -1)).all()
    else:
        np.testing.assert_array_equal(got, by_the_sort)
        assert (np.asarray(handed) == np.asarray(
            attention.select_positions(scores, k, jnp.max(lanes[4])))).all()


def test_a_lane_past_the_kernel_s_reach_takes_the_fetch(monkeypatch):
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_MASKED_KERNEL_PAGES", 2)
    lanes, selection, best = _a_chunk_that_selects(
        jnp.float32, (40,), 8, lambda shape, key: jax.random.normal(key, shape))
    want = attention._selected_latent_attention(
        *lanes[:3], *selection[:2], d_v=24, scale=0.2)
    for reach, same in ((39, True), (40, False)):
        monkeypatch.setattr(attention, "_MASKED_LIVE_MAX", reach)
        with pltpu.force_tpu_interpret_mode():
            got, handed, masked = attention._attend_masked(
                *lanes, *best[:2], k=best[2], handed=False, d_v=24, scale=0.2)
        assert handed is None and int(masked) == (not same)
        assert bool((got == want).all()) == same
        np.testing.assert_allclose(got, want, atol=2e-6)


def _late(key, shape):
    """Scores whose best stand behind a lane's first 16 positions."""
    return jax.random.normal(key, shape) + 10.0 * (jnp.arange(shape[-1]) >= 16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lengths,scores_of", [
    ((40, 33), jax.random.normal),   # every lane sees more than its 16
    ((48, 0, 5), jax.random.normal),  # an idle lane, a lane that sees 5
    ((0, 16, 17, 0), jax.random.normal),  # the count's edge, idle ends
    ((48, 40), _late),        # a first kernel step that holds none of them
    ((48, 48, 48), jax.random.normal),    # lanes that fill their tables
], ids=["longer", "idle-and-fewer", "edge", "late", "full"])
def test_the_masked_decode_kernel_agrees_with_the_fetch(dtype, lengths,
                                                        scores_of,
                                                        monkeypatch):
    """`_masked_decode_kernel` (one query row a lane, the lane's heads the
    score tile's rows, on `_paged_decode_body`'s pipeline through the
    lanes) in Pallas's interpret mode against `_selected_latent_attention`
    over `select_rows`' selection, in steps of two pages (16 positions,
    as many as are selected)."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_MASKED_DECODE_PAGES", 2)
    lanes, (rows, seen, least), (scores, _, _) = _a_chunk_that_selects(
        dtype, lengths, 1, lambda shape, key: scores_of(key, shape))
    if scores_of is _late:      # nothing of a long lane's set in step 0
        assert int((rows[0, 0] // 8 == lanes[3][0, :2, None]).sum()) == 0
    want = attention._selected_latent_attention(
        *lanes[:3], rows, seen, d_v=24, scale=0.2)
    with pltpu.force_tpu_interpret_mode():
        got = attention._masked_decode_kernel(
            *lanes, scores, least, jnp.full(least.shape, -1), d_v=24,
            scale=0.2)
    assert got.shape == want.shape and got.dtype == jnp.float32
    live = np.asarray(lanes[4]) > 0
    assert not np.asarray(got[~live]).any()
    bound = 2e-6 if dtype == jnp.float32 else 1.5e-2    # as the chunk's
    rms = float(jnp.sqrt(jnp.mean(want[live] ** 2)))
    assert float(jnp.abs(got - want)[live].max()) < bound * rms


@pytest.mark.parametrize("decimals,seed,ties", [
    (1, 5, "every"), (1, 0, "one"), (0, 4, "several")])
def test_equal_scores_at_a_decode_step_s_edge(decimals, seed, ties,
                                              monkeypatch):
    """`test_equal_scores_at_a_set_s_edge_are_the_sort_s_to_settle` for
    one query row a lane: lanes that keep every position tied with their
    set's last, or one of them alone (the lowest, by its row of the
    pool), read the mask; a burst in which a lane keeps several and
    leaves one out takes the fetch of the sort's set, to the bit."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_MASKED_DECODE_PAGES", 2)
    lanes, selection, best = _a_chunk_that_selects(
        jnp.float32, (48, 40, 44), 1, seed=seed,
        scores_of=lambda shape, key: jnp.round(
            jax.random.normal(key, shape), decimals))
    scores, at, k = best
    every, one = _ties(selection, scores)
    assert {"every": bool(every.all()),
            "one": bool((every | one).all() and not every.all()),
            "several": not bool((every | one).all())}[ties]
    assert bool(((scores == selection[2][..., None]).sum(-1) > 1).any())
    rows, seen, positions = _the_rule_s_set(*best)
    by_the_rule = attention._selected_latent_attention(
        *lanes[:3], rows, seen, d_v=24, scale=0.2)
    by_the_sort = attention._selected_latent_attention(
        *lanes[:3], *selection[:2], d_v=24, scale=0.2)
    with pltpu.force_tpu_interpret_mode():
        got, handed, masked = attention._attend_masked(
            *lanes, scores, at, k=k, handed=True, d_v=24, scale=0.2)
    assert int(masked) == (ties != "several")
    if ties == "several":
        np.testing.assert_array_equal(got, by_the_sort)
        assert (np.asarray(handed) == np.asarray(
            attention.select_positions(scores, k, jnp.max(lanes[4])))).all()
    else:
        np.testing.assert_allclose(got, by_the_rule, atol=2e-6)
        assert (np.sort(positions, -1) == np.sort(handed, -1)).all()


def test_a_lane_past_the_decode_reach_takes_the_fetch(monkeypatch):
    """`_MASKED_DECODE_LIVE_MAX` against the burst's longest lane, inside
    the program: within it the mask, past it the fetch to the bit."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(attention, "_MASKED_DECODE_PAGES", 2)
    lanes, selection, best = _a_chunk_that_selects(
        jnp.float32, (40, 22), 1,
        lambda shape, key: jax.random.normal(key, shape))
    want = attention._selected_latent_attention(
        *lanes[:3], *selection[:2], d_v=24, scale=0.2)
    for reach, same in ((39, True), (40, False)):
        monkeypatch.setattr(attention, "_MASKED_DECODE_LIVE_MAX", reach)
        with pltpu.force_tpu_interpret_mode():
            got, handed, masked = attention._attend_masked(
                *lanes, *best[:2], k=best[2], handed=False, d_v=24, scale=0.2)
        assert handed is None and int(masked) == (not same)
        assert bool((got == want).all()) == same
        np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("q_shape,pool_shape,dtype,d_v,taken", [
    ((1, 512, 128, 640), (2, 16385, 16, 640), jnp.bfloat16, 512, True),
    ((4, 64, 16, 128), (2, 9, 16, 128), jnp.bfloat16, 128, True),
    ((1, 512, 128, 640), (2, 16385, 8, 640), jnp.float32, 512, True),
    ((1, 512, 128, 576), (2, 16385, 16, 576), jnp.bfloat16, 512, False),
    ((1, 512, 128, 640), (2, 16385, 16, 640), jnp.bfloat16, 448, False),
    ((1, 512, 8, 640), (2, 16385, 16, 640), jnp.bfloat16, 512, False),
    ((1, 512, 128, 640), (2, 16385, 8, 640), jnp.bfloat16, 512, False),
    ((1, 512, 128, 640), (2, 16385, 16, 640), jnp.float8_e4m3fn, 512, False),
    ((1, 32, 4, 128), (2, 17, 8, 128), jnp.bfloat16, 24, False),
], ids=["dots3-note-prev", "whole-tiles", "float32", "row-of-576",
        "value-of-448", "8-heads", "page-of-8", "8-bit", "tiny-dsa-moe"])
def test_the_rule_that_picks_the_selected_read(q_shape, pool_shape, dtype,
                                               d_v, taken, recwarn):
    """Shapes alone decide (`_masked_takes`): rows, values, heads and
    pages in whole tiles of the pool's dtype.  What is refused says so
    and fetches; a decode step goes by the same rule and, refused, says
    nothing more than its model's chunk has."""
    assert (attention._masked_takes(q_shape, pool_shape, dtype, d_v)
            is None) == taken
    if max(q_shape + pool_shape) > 1024:
        return
    q = jnp.zeros(q_shape, dtype)
    pool = jnp.zeros(pool_shape, dtype)
    lanes, k_w = q_shape[:2]
    tables = jnp.zeros((lanes, 2), jnp.int32)
    at = jnp.zeros((lanes, 2 * pool_shape[2]), jnp.int32)

    def lower(q):
        scores = jnp.zeros((lanes, q.shape[1], 2 * pool_shape[2]))
        return jax.jit(lambda q, pool: attention.paged_latent_attention(
            q, pool, 0, tables, jnp.zeros((lanes, q.shape[1]), jnp.int32),
            jnp.ones((lanes,), jnp.int32), d_v=d_v, scale=1.0,
            selected=(scores, at, 8, False))[0]).lower(q, pool)

    chunk = lower(q)
    said = [str(w.message) for w in recwarn.list
            if "fetches its selected rows" in str(w.message)]
    assert bool(said) == (not taken)
    # this host lowers for its CPU: the fetch, the kernel's branch nowhere
    assert "masked_latent_attention" not in chunk.as_text()
    recwarn.clear()
    # a decode step on this host: the fetch (its sort and its gather), no
    # kernel of either form, and not a word
    step = lower(q[:, :1]).as_text()
    assert "stablehlo.sort" in step and "masked_" not in step
    assert not recwarn.list


def test_the_latent_ring_reader_sees_the_window_and_no_more():
    rng = np.random.default_rng(3)
    ring_rows, w, d_v, h, window = 16, 32, 24, 2, 5
    ring = jnp.asarray(rng.normal(size=(1, ring_rows, w)), jnp.float32)
    kv_len = jnp.array([22])                 # positions 6..21 are in the ring
    positions = jnp.array([[20, 21]])
    q = jnp.asarray(rng.normal(size=(1, 2, h, w)), jnp.float32)
    got = attention.latent_window_attention(
        q, ring, ring, positions, kv_len, window, d_v=d_v, scale=0.2)
    for i, t in enumerate((20, 21)):
        rows = ring[0, [p % ring_rows for p in range(t - window + 1, t + 1)]]
        prob = jax.nn.softmax(jnp.einsum("he,te->ht", q[0, i], rows) * 0.2)
        np.testing.assert_allclose(got[0, i], prob @ rows[:, :d_v],
                                   rtol=2e-5, atol=2e-5)


# -- (iii) the share tied to the model ---------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """The program's expert layer run as each of two ranks of the tiny
    model (the router 8 wide on both) and, at the published split, as each
    of eight ranks of a 16-expert router: the ranks' routed parts plus the
    shared expert counted once are the uncut reference's layer, and each
    rank's part is the reference's given that share."""
    for published, held in ((8, 4), (16, 2)):
        whole = _config(n_routed_experts=published, published={
            "n_routed_experts": published})
        fam = spec.family(whole)
        cfg_all = fam.program_config(whole)
        assert cfg_all.experts_held is None
        params = cfg_all.init_params(jax.random.key(SEED))
        fp = {k: v[2] for k, v in params["ffn"].items()}
        x = jax.random.normal(jax.random.key(2), (1, 40, 64), jnp.float32)
        stacks = ("w_gate", "w_up", "w_down")
        u = fam._rms_norm(x[0], fp["norm"], 1e-5)
        shared = fam.shared_expert(u, fp)
        parts, counts = [], []
        for first in range(0, published, held):
            share = _config(n_routed_experts=held, first_local_expert=first,
                            published={"n_routed_experts": published})
            cfg = fam.program_config(share)
            assert cfg.experts_held == (first, held)
            mine = {k: (v[first:first + held] if k in stacks else v)
                    for k, v in fp.items()}
            out, visited, routed, taken = mla_moe._expert_ffn(
                {k: v for k, v in mine.items() if k not in stacks},
                {k: mine[k][None] for k in stacks}, 0, x,
                jnp.ones((1, 40), bool), cfg, True)
            want, _, bad = fam.experts(u, mine, taken[0], share)
            assert not bool(bad.any())
            np.testing.assert_allclose(out[0], want + shared, atol=2e-5)
            parts.append(out[0] - shared)
            counts.append(int(routed))
        assert sum(counts) == 40 * 3
        uncut, _, _ = fam.experts(u, fp, None, whole)
        np.testing.assert_allclose(sum(parts) + shared, uncut + shared,
                                   atol=6e-5)
        assert float(jnp.abs(parts[0] - parts[1]).max()) > 0.01


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
def test_what_is_left_out_is_seen(fault, monkeypatch):
    """Float32 on both sides: a program that leaves one mechanism out is
    thousands of times further from the reference than one that does not,
    or its selection or routing strays outside the slack (NaN)."""
    c = _config()
    cfg = fault(monkeypatch, spec.family(c).program_config(c))
    e = _engine(c, cfg)
    try:
        errs = _errors(e, c, _seqs(2, 50 + 4, seed=9), 50)
    finally:
        e.shutdown()
    assert not np.isfinite(errs).all() or errs.min() > 100 * EXACT, errs


# -- (v) the benchmark's comparison has teeth -------------------------------------
def _as_float8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def _leaf_in_8_bits(*names):
    def fault(e, fam, monkeypatch):
        e.score(np.ones((1, 9), np.int64), 8, routing=True)   # builds them
        for name in ("_score_chunk", "_score_step"):
            inner = getattr(e, name)

            def program(*a, _inner=inner, **kw):
                cache, *rest = _inner(*a, **kw)
                return (dataclasses.replace(cache, **{
                    n: _as_float8(getattr(cache, n)) for n in names}), *rest)

            setattr(e, name, program)
    return fault


def _one_held_expert_dropped(e, fam, monkeypatch):
    """The program runs without expert 1, the reference on the stated
    parameters."""
    stated, plain = e.params, fam.forward
    ffn = e.params["ffn"]
    e.params = dict(e.params, ffn=dict(
        ffn, w_down=ffn["w_down"].at[:, 1].set(0)))
    monkeypatch.setattr(fam, "forward",
                        lambda p, *a, **kw: plain(stated, *a, **kw))


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
TEETH = {"as_it_is": None,
         "the_index_keys_in_8_bits": _leaf_in_8_bits("idx"),
         "the_rings_in_8_bits": _leaf_in_8_bits("ring"),
         "pool_keys_and_rings_in_8_bits": _leaf_in_8_bits("kv", "idx", "ring"),
         "one_held_expert_dropped": _one_held_expert_dropped}


@pytest.mark.parametrize("fault", TEETH.values(), ids=list(TEETH))
def test_logits_check_has_teeth(fault, monkeypatch):
    """`deployment.logits_check` (3 lanes x (the last of 100 prompt
    positions + 8 decode steps), bfloat16 parameters, compute and cache as
    the benchmark's configuration has them, experts and selection handed
    over and held to their slacks) passes the program as it is with every
    position decided and fails a program whose index keys, whose rings or
    whose whole state are kept in 8-bit floats, and one that drops a held
    expert."""
    from bench.harness.deployment import logits_check

    c = _config(param_dtype="bfloat16", compute_dtype="bfloat16",
                cache_dtype="bfloat16")
    fam = spec.family(c)
    monkeypatch.setitem(fam.TOLERANCES, "LOGITS_REL_EXPERTS", TINY_BOUND)
    monkeypatch.setitem(fam.TOLERANCES, "ROUTER_SLACK", TINY_ROUTER_SLACK)
    monkeypatch.setitem(fam.TOLERANCES, "SELECT_SLACK", TINY_SELECT_SLACK)
    monkeypatch.setitem(fam.TOLERANCES, "SELECT_SLACK_MEDIAN",
                        TINY_SELECT_MEDIAN)
    e = _engine(c, params=fam.program_config(c).init_params(
        jax.random.key(TEETH_SEED)))
    try:
        if fault:
            fault(e, fam, monkeypatch)
        v = logits_check(e, c, TEETH_SEED)
    finally:
        e.shutdown()
    assert v["positions"] == 27 and v["bound"] == TINY_BOUND
    if fault is None:
        assert v["ok"] and v["decided"] == 27, v
    else:
        assert not v["ok"], v
        assert not v["finite"] or v["worst_decided"] > v["bound"], v


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
    cfg = configs.get(name)
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: decoding.init_sequence_state(
        cfg, 17, 8, num_slots=4, prefill_chunk=32))
    chunk, burst, _ = decoding.make_paged_engine_fns(cfg)
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    lanes = (arr(4, 8), arr(4), arr(4, dtype=jnp.bool_),
             arr(4, dtype=jnp.float32), key)
    by_slot = cfg.state_by_slot         # rings: the lanes' slots ride along
    if program == "chunk":
        lowered = chunk.lower(params, cache, arr(32), arr(8), arr(), arr(),
                              **({"slot": arr()} if by_slot else {}))
    elif program == "burst":
        lowered = burst.lower(params, cache, arr(4), *lanes, n_steps=4,
                              **({"slots": arr(4)} if by_slot else {}))
    elif program == "copy_block":
        lowered = jax.jit(decoding.copy_block).lower(cache, arr(), arr())
    else:
        lowered = decoding.make_paged_spec_fns(cfg).lower(
            params, cache, arr(4, 3), *lanes)
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == _LOWERED_AT_THE_PARENT[name][program]
