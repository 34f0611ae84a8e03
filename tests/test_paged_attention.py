"""The served step's one attention body (`ops.attention.paged_attention`)
and the one forward built on it (`models.decoding._paged_forward`), held
to the formula they replaced: gather the whole table, repeat the KV
heads, cast to float32, one soft-max.  That formula lives on here as the
plain reference.  Also: the pool is written in place (a step changes the
entries it writes and no other, a burst equals its steps) and is never
copied (the compiled programs' temporaries stay far under the pool's
size); and the engine on top (prefix sharing and `copy_block`, preemption
and re-prefill, speculation) still gives the reference's tokens."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, init_params
from ray_tpu.models import decoding
from ray_tpu.models.decoding import (
    PagedKVCache,
    init_paged_cache,
    make_paged_engine_fns,
    paged_decode_burst,
    paged_decode_step,
    paged_prefill_chunk,
    paged_verify_step,
)
from ray_tpu.ops import attention

BS = 16            # block size
B_MAX = 7          # table depth: 112 positions, ragged against a group of 2
T_MAX = BS * B_MAX


@pytest.fixture(params=[2, attention._PAGED_GROUP_BLOCKS],
                ids=["group2", "group_own"])
def group_blocks(request, monkeypatch):
    """2: a long lane takes several trips of the loop and the last group is
    ragged; the program's own: the whole table is one group."""
    monkeypatch.setattr(attention, "_PAGED_GROUP_BLOCKS", request.param)
    return request.param


# ---------------------------------------------------------------------------
# the plain reference: the body as it stood before, for any (S, K)
# ---------------------------------------------------------------------------
def ref_attention(q, k_layer, v_layer, block_tables, positions):
    """q (S,K,H,D) over one layer's pool slice (N,bs,Hkv,D): the whole
    table width gathered, KV heads repeated, everything in float32."""
    s, _, h, _ = q.shape
    t_w = block_tables.shape[1] * k_layer.shape[1]
    kh = k_layer[block_tables].reshape(s, t_w, *k_layer.shape[2:])
    vh = v_layer[block_tables].reshape(s, t_w, *v_layer.shape[2:])
    rep = h // kh.shape[2]
    kh, vh = jnp.repeat(kh, rep, axis=2), jnp.repeat(vh, rep, axis=2)
    sc = jnp.einsum("sqhd,sthd->sqht", q.astype(jnp.float32),
                    kh.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    seen = jnp.arange(t_w)[None, None, :] <= positions[:, :, None]
    sc = jnp.where(seen[:, :, None, :], sc, -1e30)
    return jnp.einsum("sqht,sthd->sqhd", jax.nn.softmax(sc, axis=-1),
                      vh.astype(jnp.float32))


def _by_position(cache, cfg):
    """`cache`'s pool as (L, N, block_size, Hkv, D), whichever way
    `init_paged_cache` stores a page: kept as rows, row t x Hkv + g is
    position t of KV head g."""
    def view(a):
        return a.reshape(*a.shape[:2], -1, cfg.n_kv_heads, cfg.head_dim)

    return PagedKVCache(k=view(cache.k), v=view(cache.v))


def ref_forward(params, cache, tokens, block_tables, positions, active, cfg):
    """tokens (S,K) -> (cache, logits (S,K,vocab)), the pool slices going
    through the layer scan as xs / ys as they used to."""
    cd = cfg.compute_dtype
    stored = cache.k.shape
    cache = _by_position(cache, cfg)
    bs = cache.k.shape[2]
    x = params["embed"].astype(cd)[tokens]
    wb = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    wb = jnp.where(active[:, None], wb, 0)
    off = jnp.where(active[:, None], positions % bs, 0)

    def layer(x, layer_in):
        bp, li, k_layer, v_layer = layer_in
        q, k, v = decoding._qkv(bp, x, cfg, positions)
        k_layer = k_layer.at[wb, off].set(k.astype(k_layer.dtype))
        v_layer = v_layer.at[wb, off].set(v.astype(v_layer.dtype))
        attn = ref_attention(q, k_layer, v_layer, block_tables, positions)
        attn = attn.reshape(*tokens.shape, -1).astype(cd)
        x = x + jnp.einsum("bth,hd->btd", attn, bp["wo"].astype(cd))
        x = x + decoding._mlp(bp, x, cfg, experts, li, active)[0]
        return x, (k_layer, v_layer)

    blocks, experts = decoding._layer_xs(params["blocks"], cfg)
    x, (k, v) = jax.lax.scan(
        layer, x, (blocks, jnp.arange(cfg.n_layers), cache.k, cache.v))
    return (PagedKVCache(k=k.reshape(stored), v=v.reshape(stored)),
            decoding._final_logits(params, x, cfg))


# ---------------------------------------------------------------------------
# the body alone, on a random pool
# ---------------------------------------------------------------------------
def _lanes(lengths, k_w, rng, b_max=B_MAX):
    """Tables and positions for lanes whose context is `lengths` long
    before the call's K tokens; each lane owns its own shuffled blocks,
    entries past its end name the null block.  None = an idle lane."""
    s = len(lengths)
    tables = np.zeros((s, b_max), np.int32)
    positions = np.zeros((s, k_w), np.int32)
    kv_len = np.zeros((s,), np.int32)
    free = list(rng.permutation(np.arange(1, 1 + s * b_max)))
    for i, n in enumerate(lengths):
        if n is None:
            continue
        need = -(-(n + k_w) // BS)
        tables[i, :need] = [free.pop() for _ in range(need)]
        positions[i] = n + np.arange(k_w)
        kv_len[i] = n + k_w
    return tables, positions, kv_len


def _body_case(lengths, k_w, rep, dtype, seed=0, b_max=B_MAX):
    hkv, d = 2, 32
    rng = np.random.default_rng(seed)
    tables, positions, kv_len = _lanes(lengths, k_w, rng, b_max)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    shape = (3, 1 + len(lengths) * b_max, BS, hkv, d)
    q = jax.random.normal(kq, (len(lengths), k_w, hkv * rep, d), dtype)
    k_pool = jax.random.normal(kk, shape, dtype)
    v_pool = jax.random.normal(kv, shape, dtype)
    layer = 1
    # A new function each time: `jax.jit` of the same one would hand back
    # the trace made under another `_PAGED_GROUP_BLOCKS`.
    got = jax.jit(lambda *a: attention.paged_attention(*a, kv_heads=hkv))(
        q, k_pool, v_pool, jnp.int32(layer), jnp.asarray(tables),
        jnp.asarray(positions), jnp.asarray(kv_len))
    want = ref_attention(q, k_pool[layer], v_pool[layer],
                         jnp.asarray(tables), jnp.asarray(positions))
    return np.asarray(got), np.asarray(want), kv_len > 0


# The algorithm must be the reference's to rounding, in float32 and in the
# served dtype alike: products of bfloat16 values are exact in the float32
# accumulator and the probabilities stay float32, so only the order of the
# sums and the running soft-max's rescaling differ.
ATOL = 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rep", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("k_w", [1, 4, 32], ids=["decode", "verify", "chunk"])
def test_body_matches_reference_lanes_of_every_length(group_blocks, k_w, rep,
                                                      dtype):
    """One call whose lanes stand at length 0, 1, a block's edge (15, 16,
    17), mid-table and the table's last position, beside an idle lane."""
    lengths = [0, 1, 15, 16, 17, 61, T_MAX - k_w, None]
    got, want, live = _body_case(lengths, k_w, rep, dtype)
    assert np.isfinite(got).all()          # the idle lane too
    np.testing.assert_allclose(got[live], want[live], atol=ATOL, rtol=0)


# Tables: (group the loop reads per trip, table depth).  A group of 2 on a
# depth of 7: several trips, the last one ragged.  The program's own group
# (16) on a depth of 20: group edges at the size that is served.  The
# program's own group on a depth of 7: a table narrower than one group.
TABLES = {"group2_ragged": (2, 7), "own_group_wide": (None, 20),
          "own_group_narrow": (None, 7)}
EDGES = ["one", "block-1", "block", "block+1", "group-1", "group",
         "group+1", "full-1", "full"]


def _table(monkeypatch, name):
    """-> (table depth, {edge name: live length}) under that table's group."""
    group, b_max = TABLES[name]
    if group:
        monkeypatch.setattr(attention, "_PAGED_GROUP_BLOCKS", group)
    t = min(attention._PAGED_GROUP_BLOCKS, b_max) * BS
    full = b_max * BS
    live = [1, BS - 1, BS, BS + 1, t - 1, t, min(t + 1, full), full - 1, full]
    return b_max, dict(zip(EDGES, live))


@pytest.mark.parametrize("rep", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("width", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("table", list(TABLES))
def test_body_every_width_live_lengths_on_the_edges(monkeypatch, table,
                                                    width, rep):
    """Decode calls of 1-16 lanes whose live lengths (the new token
    counted) sit on and across block and group edges, up to the table's
    full width; from width 4 on one lane is idle (`kv_len` 0)."""
    b_max, edge = _table(monkeypatch, table)
    lives = list(edge.values())
    shift = width + 3 * rep              # another draw of edges per case
    lengths = [lives[(shift + 2 * i) % len(lives)] - 1 for i in range(width)]
    if width >= 4:
        lengths[1] = None
    got, want, live = _body_case(lengths, 1, rep, jnp.bfloat16,
                                 seed=shift, b_max=b_max)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=ATOL, rtol=0)


@pytest.mark.parametrize("name", EDGES)
@pytest.mark.parametrize("table", list(TABLES))
def test_body_single_lane(monkeypatch, table, name):
    """A lane alone sets the loop's trip count: nothing longer beside it
    covers for a group read too few."""
    b_max, edge = _table(monkeypatch, table)
    got, want, _ = _body_case([edge[name] - 1], 1, 4, jnp.float32,
                              seed=edge[name], b_max=b_max)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_body_all_lanes_idle_is_finite(group_blocks):
    got, _, _ = _body_case([None, None], 1, 4, jnp.bfloat16)
    assert np.isfinite(got).all()


# ---------------------------------------------------------------------------
# a decode step lowered for a TPU: the kernel against the block loop
# ---------------------------------------------------------------------------
# (pages a step, table entries, Hkv, rep, lane lengths, dtype, scale, the
# lanes' blocks in table order: None = shuffled, an int = that one block
# for every entry, D: 128 unless given).  Lengths count the step's own
# token; 0: an idle lane.  Fewer than 4 KV heads: the pool is kept as rows
# (`attention.pages_as_rows`, `init_paged_cache`'s rule) and handed over
# as it is.
KERNEL_CASES = {
    "whole_steps_and_a_part": (2, 7, 8, 4, [32, 0, 64, 100, 1], "bf16",
                               None, None),
    "table_narrower_than_a_step": (8, 3, 8, 4, [48, 17, 0], "bf16", None,
                                   None),
    "one_whole_step": (4, 4, 8, 4, [64], "bf16", None, None),
    "a_block_repeated": (2, 6, 8, 4, [96, 40], "bf16", None, 5),
    "idle_lanes_first_and_last": (2, 5, 4, 8, [0, 0, 70, 16, 0], "bf16",
                                  None, None),
    "every_lane_idle": (2, 4, 8, 4, [0, 0], "bf16", None, None),
    "rep6_over_8": (2, 5, 8, 6, [80, 33, 15], "bf16", None, None),
    "rep8_over_4": (4, 9, 4, 8, [144, 65], "bf16", None, None),
    "a_handed_over_scale": (2, 5, 8, 4, [79, 2], "bf16", 1.0 / 128, None),
    "float32_rows": (2, 5, 8, 4, [80, 31], "f32", None, None),
    "the_row_cap_sets_the_step": (2, 7, 8, 4, [100, 33], "bf16", None, None),
    # a lane over four steps, an idle one, one shorter than a page
    "two_heads_of_256_as_rows": (2, 7, 2, 8, [100, 0, 5, 64, 33], "bf16",
                                 None, None, 256),
    "two_heads_of_128_as_rows": (4, 6, 2, 8, [96, 7, 0], "bf16", None, None),
    "one_head_float32_as_rows": (2, 5, 1, 8, [70, 16], "f32", None, None),
}


def _stored(pool, hkv, d):
    """A pool drawn as (L, N, block_size, Hkv, D), as `init_paged_cache`
    would keep it."""
    if attention.pages_as_rows(hkv, d, BS, pool.dtype):
        return pool.reshape(*pool.shape[:2], BS * hkv, d)
    return pool


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_decode_kernel_agrees_with_the_block_loop(monkeypatch, case):
    """`_paged_decode_kernel` in Pallas's TPU interpret mode against
    `paged_attention` as the CPU lowers it (the block loop) on the same
    pool: within 1e-2 of the loop's rms (the kernel rounds the
    probabilities to the rows' dtype, 2**-9), idle lanes exactly 0,
    float32 out, (S, 1, H, D), the pools untouched."""
    from jax.experimental.pallas import tpu as pltpu

    pages, entries, hkv, rep, lengths, dtype, scale, block, *d = \
        KERNEL_CASES[case]
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    if case == "the_row_cap_sets_the_step":
        monkeypatch.setattr(attention, "_PAGED_KERNEL_ROWS",
                            pages * BS * hkv)
    else:
        monkeypatch.setattr(attention, "_PAGED_KERNEL_PAGES", pages)
    (d,), s = d or (128,), len(lengths)
    tables, positions, kv_len = _lanes(
        [n - 1 if n else None for n in lengths], 1,
        np.random.default_rng(len(case)), entries)
    if block:
        tables = np.where(tables > 0, block, 0)
    kq, kk, kv = jax.random.split(jax.random.key(len(case)), 3)
    shape = (3, 1 + s * entries, BS, hkv, d)
    q = jax.random.normal(kq, (s, 1, hkv * rep, d), dtype)
    by_position = (jax.random.normal(kk, shape, dtype),
                   jax.random.normal(kv, shape, dtype))
    k_pool, v_pool = (_stored(pool, hkv, d) for pool in by_position)
    assert (k_pool.ndim == 4) == (hkv < 4)
    before = np.asarray(k_pool, np.float32), np.asarray(v_pool, np.float32)
    assert list(kv_len) == lengths
    layer, tables = jnp.int32(2), jnp.asarray(tables)
    kv_len = jnp.asarray(kv_len)
    with pltpu.force_tpu_interpret_mode():
        got = attention._paged_decode_kernel(
            q, k_pool, v_pool, layer, tables, kv_len,
            scale=d ** -0.5 if scale is None else scale, kv_heads=hkv)
    assert got.shape == (s, 1, hkv * rep, d) and got.dtype == jnp.float32
    live = np.asarray(kv_len) > 0
    assert not np.asarray(got[~live]).any()
    np.testing.assert_array_equal(np.asarray(k_pool, np.float32), before[0])
    np.testing.assert_array_equal(np.asarray(v_pool, np.float32), before[1])
    if not live.any():
        return
    want = attention.paged_attention(
        q, k_pool, v_pool, layer, tables, jnp.asarray(positions), kv_len,
        scale=scale, kv_heads=hkv)
    assert want.shape == got.shape
    rms = float(jnp.sqrt(jnp.mean(want[live] ** 2)))
    assert float(jnp.abs(got - want)[live].max()) < 1e-2 * rms
    if scale is None:         # and the loop, over either layout, the formula
        plain = ref_attention(q, by_position[0][2], by_position[1][2], tables,
                              jnp.asarray(positions))
        np.testing.assert_allclose(np.asarray(want)[live],
                                   np.asarray(plain)[live], atol=ATOL, rtol=0)


def _no_kernel(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the decode kernel was reached")
    monkeypatch.setattr(attention, "_paged_decode_kernel", refuse)


def test_a_head_that_is_not_whole_tiles_warns_and_takes_the_loop(
        monkeypatch):
    """A decode step over heads the kernel cannot take says so, once a
    call site, and is the block loop op for op: the kernel is not even
    traced."""
    _no_kernel(monkeypatch)
    with pytest.warns(UserWarning, match="not whole tiles"):
        got, want, live = _body_case([40, 3, None], 1, 4, jnp.bfloat16)
    np.testing.assert_allclose(got[live], want[live], atol=ATOL, rtol=0)


@pytest.mark.parametrize("as_rows", [False, True],
                         ids=["by_position", "as_rows"])
@pytest.mark.parametrize("k_w", [4, 32], ids=["verify", "chunk"])
def test_a_chunk_never_reaches_the_kernel(monkeypatch, k_w, as_rows):
    """More than one query row a lane (a verify step, a prefill chunk)
    keeps the loop whatever the head size and however the pages are
    kept, and says nothing."""
    import warnings

    _no_kernel(monkeypatch)
    lengths = [40, 3]
    tables, positions, kv_len = _lanes(lengths, k_w, np.random.default_rng(0))
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    shape = (2, 1 + len(lengths) * B_MAX, BS, 2, 128)
    q = jax.random.normal(kq, (len(lengths), k_w, 8, 128), jnp.bfloat16)
    k_pool = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pool = jax.random.normal(kv, shape, jnp.bfloat16)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = attention.paged_attention(
            q, *((_stored(pool, 2, 128) if as_rows else pool)
                 for pool in (k_pool, v_pool)),
            jnp.int32(1), jnp.asarray(tables), jnp.asarray(positions),
            jnp.asarray(kv_len), kv_heads=2)
    want = ref_attention(q, k_pool[1], v_pool[1], jnp.asarray(tables),
                         jnp.asarray(positions))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL,
                               rtol=0)


# ---------------------------------------------------------------------------
# the three programs against the reference forward
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module",
                params=["tiny-mha", "tiny-gqa4", "tiny-moe", "tiny-rows"])
def model(request):
    """`tiny` with 4 query heads on 4 / 1 KV heads, `tiny-moe` (4 on 4,
    4 experts top-2) and `tiny` with its 2 KV heads of 128, whose pool is
    kept as rows (`attention.pages_as_rows`); float32 throughout so that
    the comparison is of the algorithm, not of roundings."""
    name, _, variant = request.param.rpartition("-")
    cfg = configs.get(request.param if variant == "moe" else name)
    if variant != "moe":
        cfg = dataclasses.replace(cfg, **{
            "mha": {"n_kv_heads": 4}, "gqa4": {"n_kv_heads": 1},
            "rows": {"d_head": 128}}[variant])
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    assert (init_paged_cache(cfg, 2, BS).k.ndim == 4) == (variant == "rows")
    return cfg, init_params(jax.random.key(1), cfg)


def _noise_cache(cfg, lanes, seed=3):
    """A pool of `lanes` tables' worth of blocks, every entry of it noise
    (the null block too: nothing may leak from there).  The comparisons
    below are of two formulas on one pool, so what the pool holds need
    not be anybody's context."""
    shape = init_paged_cache(cfg, 1 + lanes * B_MAX, BS).k.shape
    kk, kv = jax.random.split(jax.random.key(seed))
    return PagedKVCache(k=jax.random.normal(kk, shape, cfg.compute_dtype),
                        v=jax.random.normal(kv, shape, cfg.compute_dtype))


def _assert_pools_close(a, b, atol=1e-5):
    np.testing.assert_allclose(np.asarray(a.k), np.asarray(b.k), atol=atol)
    np.testing.assert_allclose(np.asarray(a.v), np.asarray(b.v), atol=atol)


LENGTHS = [0, 1, 16, 17, 70, None]      # None: inactive, on the null block


def _decode_inputs(cfg, k_w):
    lengths = [n if n is not None else 0 for n in LENGTHS]
    active = np.array([n is not None for n in LENGTHS])
    tables, _, _ = _lanes([n if a else None for n, a in zip(lengths, active)],
                          k_w + 8, np.random.default_rng(5))
    cache = _noise_cache(cfg, len(lengths))
    toks = np.random.default_rng(7).integers(
        1, cfg.vocab_size, (len(lengths), k_w)).astype(np.int32)
    return (cache, jnp.asarray(toks), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active))


def test_decode_step_matches_reference(group_blocks, model):
    cfg, params = model
    cache, toks, tables, lengths, active = _decode_inputs(cfg, 1)
    got_cache, got = paged_decode_step(params, cache, toks[:, 0], tables,
                                       lengths, active, cfg=cfg)
    want_cache, want = ref_forward(params, cache, toks, tables,
                                   lengths[:, None], active, cfg)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live, 0], atol=2e-4)
    _assert_pools_close(got_cache, want_cache)


def test_verify_step_matches_reference(group_blocks, model):
    cfg, params = model
    cache, cand, tables, lengths, active = _decode_inputs(cfg, 4)
    temps = jnp.zeros((len(LENGTHS),), jnp.float32)
    got_cache, tok_out, accepted, _ = paged_verify_step(
        params, cache, cand, tables, lengths, active, temps,
        jax.random.key(0), cfg=cfg)
    positions = lengths[:, None] + jnp.arange(4)
    want_cache, logits = ref_forward(params, cache, cand, tables, positions,
                                     active, cfg)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    live = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(tok_out)[live], greedy[live])
    match = np.asarray(cand)[:, 1:] == greedy[:, :-1]
    np.testing.assert_array_equal(
        np.asarray(accepted)[live],
        np.cumprod(match, axis=1).sum(axis=1)[live])
    _assert_pools_close(got_cache, want_cache)


@pytest.mark.parametrize("start,n_valid", [(0, 32), (17, 20),
                                           (T_MAX - 32, 32)])
def test_prefill_chunk_matches_reference(group_blocks, model, start, n_valid):
    cfg, params = model
    tables, _, _ = _lanes([T_MAX - 1], 1, np.random.default_rng(9))
    cache = _noise_cache(cfg, 1)
    toks = np.zeros((32,), np.int32)
    toks[:n_valid] = np.random.default_rng(11).integers(
        1, cfg.vocab_size, n_valid)
    got_cache, got = paged_prefill_chunk(
        params, cache, jnp.asarray(toks), jnp.asarray(tables[0]),
        jnp.int32(start), jnp.int32(n_valid), cfg=cfg)
    positions = start + jnp.arange(32, dtype=jnp.int32)
    want_cache, logits = ref_forward(
        params, cache, jnp.asarray(toks)[None], jnp.asarray(tables),
        positions[None], jnp.ones((1,), bool), cfg)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(logits)[0, n_valid - 1], atol=2e-4)
    _assert_pools_close(got_cache, want_cache)


# ---------------------------------------------------------------------------
# the pool is written in place
# ---------------------------------------------------------------------------
def test_burst_pool_equals_steps_and_spares_other_blocks(model):
    """The pool after an 8-step burst is the pool after the same eight
    steps one at a time, bit for bit, and every block that no active lane
    owns (the null block apart: idle lanes write there) is bit-identical
    to what it was."""
    cfg, params = model
    cache, toks, tables, lengths, active = _decode_inputs(cfg, 1)
    # The pool is noise throughout, so a stray write would show.
    owned = np.unique(np.asarray(tables))
    spare = np.setdiff1d(np.arange(1, cache.k.shape[1]), owned)
    temps = jnp.zeros((len(LENGTHS),), jnp.float32)
    burst_cache, tok_mat, _, _ = paged_decode_burst(
        params, cache, toks[:, 0], tables, lengths, active, temps,
        jax.random.key(0), cfg=cfg, n_steps=8)
    step_cache, cur, cur_len = cache, toks[:, 0], lengths
    for i in range(8):
        step_cache, logits = paged_decode_step(
            params, step_cache, cur, tables, cur_len, active, cfg=cfg)
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        live = np.asarray(active)
        np.testing.assert_array_equal(np.asarray(cur)[live],
                                      np.asarray(tok_mat[i])[live])
        cur_len = jnp.where(active, cur_len + 1, cur_len)
    for got, want, before in ((burst_cache.k, step_cache.k, cache.k),
                              (burst_cache.v, step_cache.v, cache.v)):
        np.testing.assert_array_equal(np.asarray(got[:, 1:]),
                                      np.asarray(want[:, 1:]))
        np.testing.assert_array_equal(np.asarray(got[:, spare]),
                                      np.asarray(before[:, spare]))


def _changed(before, after, cfg):
    """The pool entries {(layer, block, offset)} at which two caches differ."""
    before, after = _by_position(before, cfg), _by_position(after, cfg)
    diff = (np.asarray(before.k) != np.asarray(after.k)).any(axis=(3, 4))
    diff |= (np.asarray(before.v) != np.asarray(after.v)).any(axis=(3, 4))
    return {tuple(int(i) for i in idx) for idx in np.argwhere(diff)}


def _entries(cfg, tables, positions):
    """{(layer, block, offset)} of positions (S, K) under tables (S, B)."""
    return {(layer, int(tables[s, p // BS]), int(p % BS))
            for layer in range(cfg.n_layers)
            for s in range(positions.shape[0]) for p in positions[s]}


@pytest.mark.parametrize("caller", ["decode", "verify", "chunk"])
def test_step_changes_the_entries_it_writes_and_no_other(model, caller):
    """The pool after a step differs from the pool before at exactly the
    [layer, block, offset] entries of the live lanes' new tokens, every
    one of them, and nowhere in the null block: with every lane live and
    the chunk full nothing is routed there.  (Idle lanes and a chunk's
    padding are: the null block is the sink for writes nobody reads.)"""
    cfg, params = model
    k_w = {"decode": 1, "verify": 4, "chunk": 32}[caller]
    lengths = [17] if caller == "chunk" else [0, 1, 15, 16, 70]
    tables, _, _ = _lanes(lengths, k_w, np.random.default_rng(13))
    cache = _noise_cache(cfg, len(lengths))
    toks = jnp.asarray(np.random.default_rng(17).integers(
        1, cfg.vocab_size, (len(lengths), k_w)).astype(np.int32))
    lens = jnp.asarray(lengths, jnp.int32)
    active = jnp.ones((len(lengths),), bool)
    if caller == "decode":
        after, _ = paged_decode_step(params, cache, toks[:, 0],
                                     jnp.asarray(tables), lens, active,
                                     cfg=cfg)
    elif caller == "verify":
        after, _, _, _ = paged_verify_step(
            params, cache, toks, jnp.asarray(tables), lens, active,
            jnp.zeros((len(lengths),), jnp.float32), jax.random.key(0),
            cfg=cfg)
    else:
        after, _ = paged_prefill_chunk(
            params, cache, toks[0], jnp.asarray(tables[0]), lens[0],
            jnp.int32(k_w), cfg=cfg)
    positions = np.asarray(lengths)[:, None] + np.arange(k_w)
    changed = _changed(cache, after, cfg)
    assert changed == _entries(cfg, tables, positions)
    assert all(block != 0 for _, block, _ in changed)


def test_idle_lane_and_chunk_padding_write_the_null_block_only(model):
    """What a call routes away from its lanes' blocks lands in the null
    block and nowhere else: an idle lane's token, a chunk's padded tail
    past the blocks its table holds."""
    cfg, params = model
    tables, _, _ = _lanes([5, None], 1, np.random.default_rng(19))
    cache = _noise_cache(cfg, 2)
    after, _ = paged_decode_step(
        params, cache, jnp.asarray([3, 4], jnp.int32), jnp.asarray(tables),
        jnp.asarray([5, 9], jnp.int32), jnp.asarray([True, False]), cfg=cfg)
    changed = _changed(cache, after, cfg)
    mine = _entries(cfg, tables, np.array([[5]]))
    assert mine <= changed
    assert all(block == 0 for _, block, _ in changed - mine)
    # 12 valid tokens of a 32-wide chunk from position 0 on a table that
    # holds one block: positions 12-15 pad inside it, 16-31 go to the null
    # block.
    toks = jnp.asarray(np.random.default_rng(23).integers(
        1, cfg.vocab_size, 32).astype(np.int32))
    row = np.zeros((B_MAX,), np.int32)
    row[0] = tables[0, 0]
    after, _ = paged_prefill_chunk(params, cache, toks, jnp.asarray(row),
                                   jnp.int32(0), jnp.int32(12), cfg=cfg)
    changed = _changed(cache, after, cfg)
    mine = _entries(cfg, row[None], np.arange(16)[None])
    assert mine <= changed
    assert all(block == 0 for _, block, _ in changed - mine)


def test_a_block_copied_and_a_frame_out_and_in_again_read_the_same(model):
    """The block operations index `[:, block]` and take a page however it
    is kept: a lane whose last block was copied (`copy_block`) and its
    table pointed at the copy, and lanes whose blocks went out as a frame
    (`gather_blocks`) and into an empty pool (`scatter_blocks`), decode
    to the logits they decoded to, bit for bit."""
    cfg, params = model
    cache, toks, tables, lengths, active = _decode_inputs(cfg, 1)

    def step(cache, tables):
        return np.asarray(paged_decode_step(
            params, cache, toks[:, 0], jnp.asarray(tables), lengths, active,
            cfg=cfg)[1])[np.asarray(active)]

    want = step(cache, tables)
    tables = np.array(tables)
    lane, entry = 4, int(lengths[4]) // BS          # 70 positions: entry 4
    spare = int(np.setdiff1d(np.arange(1, cache.k.shape[1]), tables)[0])
    copied = decoding.copy_block(cache, jnp.int32(spare),
                                 jnp.int32(tables[lane, entry]))
    tables[lane, entry] = spare
    np.testing.assert_array_equal(step(copied, tables), want)
    owned = np.unique(tables[tables > 0])
    frame = np.asarray(decoding.gather_blocks(copied, owned))
    empty = init_paged_cache(cfg, cache.k.shape[1], BS)
    assert decoding.frame_fits(empty, frame.shape)
    assert frame.shape[:3] == (2, cfg.n_layers, len(owned))
    np.testing.assert_array_equal(
        step(decoding.scatter_blocks(empty, owned, frame), tables), want)


# ---------------------------------------------------------------------------
# the engine on top: sharing, preemption, speculation give the same tokens
# ---------------------------------------------------------------------------
_REF_JIT = jax.jit(ref_forward, static_argnums=6)


def _reference_tokens(cfg, params, prompt, n):
    """Greedy continuation by the plain reference: the whole prompt in one
    call of the old formula, then one token at a time."""
    b_max = 4                               # 64 positions: the longest case
    table = jnp.arange(1, 1 + b_max, dtype=jnp.int32)[None]
    cache = init_paged_cache(cfg, 1 + b_max, BS)
    one = jnp.ones((1,), bool)
    toks, out = jnp.asarray(prompt, jnp.int32)[None], []
    pos = jnp.arange(len(prompt), dtype=jnp.int32)[None]
    for _ in range(n):
        cache, logits = _REF_JIT(params, cache, toks, table, pos, one, cfg)
        out.append(int(jnp.argmax(logits[0, -1])))
        toks, pos = jnp.asarray([[out[-1]]], jnp.int32), pos[:, -1:] + 1
    return out


@pytest.fixture(scope="module")
def served():
    """`tiny` (GQA 4 on 2) in float32, and the reference's tokens for the
    prompts the engine tests below share."""
    cfg = dataclasses.replace(configs.get("tiny"),
                              compute_dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    prompts = {"a": list(range(1, 11)), "a_fork": list(range(1, 9)) + [99, 98],
               "b": list(range(101, 109)), "c": list(range(1, 9)),
               "rep": [1, 2, 3, 1, 2, 3, 1, 2]}
    lengths = {"a": 6, "a_fork": 6, "b": 16, "c": 16, "rep": 48}
    want = {k: _reference_tokens(cfg, params, p, lengths[k])
            for k, p in prompts.items()}
    return cfg, params, prompts, want


def _engine(served, **kw):
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params, _, _ = served
    kw = dict(dict(num_slots=4, max_len=64, block_size=4, prefill_chunk=8),
              **kw)
    return PagedLLMEngine(cfg, params, **kw)


def test_engine_prefix_sharing_and_copy_block_give_the_reference_tokens(
        served):
    """A whole-prompt hit (the shared partial tail block is copied before
    it is written: `copy_block`), then a fork off the shared prefix, then
    the first prompt again: the reference's tokens each time."""
    _, _, prompts, want = served
    eng = _engine(served, prefix_sharing=True)
    try:
        for name in ("a", "a", "a_fork", "a"):
            assert eng.generate(prompts[name], max_tokens=6,
                                timeout=120) == want[name]
        snap = eng.allocator.snapshot()
        assert snap["reuse_hits"] > 0 and snap["cow_copies"] >= 1
    finally:
        eng.shutdown()


def test_engine_preemption_then_reprefill_gives_the_reference_tokens(served):
    """Two requests whose growth exhausts the pool: the younger is
    preempted, its blocks freed and its KV prefilled again later."""
    import threading

    _, _, prompts, want = served
    eng = _engine(served, num_slots=2, max_len=32, prefill_chunk=16,
                  max_burst=4, prefix_sharing=False, num_blocks=9)
    done = {}

    def run(name):
        done[name] = eng.generate(prompts[name], max_tokens=16, timeout=180)

    try:
        threads = [threading.Thread(target=run, args=(n,)) for n in "bc"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert eng.stats["preemptions"] >= 1
        assert done == {"b": want["b"], "c": want["c"]}
    finally:
        eng.shutdown()


def test_engine_speculation_accepts_and_gives_the_reference_tokens(served):
    """`paged_verify_step` under the engine: drafts are proposed and
    accepted on a repetitive prompt, and the tokens are the reference's."""
    _, _, prompts, want = served
    eng = _engine(served, max_len=256, max_burst=2, prefix_sharing=False,
                  speculation_k=4)
    try:
        assert eng.generate(prompts["rep"], max_tokens=48,
                            timeout=300) == want["rep"]
        assert eng.stats["spec_accepted"] > 0
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# the pool is not copied
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("program", ["paged_decode_burst",
                                     "paged_prefill_chunk"])
def test_program_temporaries_are_far_under_the_pool(program):
    """A shape where the pool dwarfs everything else (tiny widths, 4097
    blocks): a program that slices the pool out of a scan, restacks it or
    copies it holds at least one pool's worth of temporaries; one that
    updates the donated pool in place holds the step's activations.
    A float32 pool: the CPU backend has no bfloat16 scatter and would
    widen the whole pool around each one, which the chip does not (its
    guard is in test_tpu_compile.py)."""
    cfg = dataclasses.replace(configs.get("tiny"), compute_dtype=jnp.float32)
    n_blocks, b_max, w = 4097, 256, 4
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, n_blocks, BS))
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
    chunk, burst, _ = make_paged_engine_fns(cfg)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    if program == "paged_decode_burst":
        lowered = burst.lower(
            params, cache, arr((w,), jnp.int32), arr((w, b_max), jnp.int32),
            arr((w,), jnp.int32), arr((w,), jnp.bool_),
            arr((w,), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0)), n_steps=8)
    else:
        lowered = chunk.lower(
            params, cache, arr((128,), jnp.int32), arr((b_max,), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32))
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes        # donated, reused
    assert mem.temp_size_in_bytes < pool_bytes / 2, (
        mem.temp_size_in_bytes, pool_bytes)
