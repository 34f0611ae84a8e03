"""The selection of `ops.attention` by itself, no engine: the index scores
through a table, the exact top-k over tiers and spans, the search that
sorts nothing, the fetch of the selected rows, the two masked kernels
(a chunk's and a decode step's, in Pallas's interpret mode) against it,
what happens to equal scores at a set's edge, the reach past which a lane
takes the fetch, the rule of shapes that picks the read, and the latent
ring reader.  The model that selects is held to its reference on the
served path in `tests/test_dsa_moe_serving.py` and
`tests/test_group_moe_serving.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention


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
