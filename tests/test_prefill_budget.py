"""The prompt tokens one prefill launch carries (serve/llm.py
`_prefill_budget`): wide launches for a model whose sequences are pool
blocks alone and for one whose state by slot spans chunks,
`prefill_chunk` rows at most for one whose slots keep rings, the cap
beside a decode burst, what `warmup()` compiles, and the counter that
says which tiers ran."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, init_params
from ray_tpu.serve import llm
from ray_tpu.serve.llm import PagedLLMEngine

from burst_ahead_cases import park, run_until_done, submit, tick

CHUNK = 16


def _params(cfg):
    own = getattr(cfg, "init_params", None)
    return own(jax.random.key(0)) if own else init_params(
        jax.random.key(0), cfg)


def _engine(cfg, params, **kw):
    kw = dict(dict(num_slots=2, max_len=256, block_size=8,
                   prefill_chunk=CHUNK), **kw)
    return PagedLLMEngine(cfg, params, **kw)


def _prompt(n, seed=5):
    return np.random.default_rng(seed).integers(1, 500, (n,)).tolist()


@pytest.fixture(scope="module", params=["tiny", "tiny-moe"])
def wide_and_narrow(request):
    """One model served twice: with tiers up to 64 rows, and with the
    tiers of an engine that stops at `prefill_chunk` (the constant is
    the test's to steer: the engine has no option for it)."""
    cfg = configs.get(request.param)
    params = _params(cfg)
    top = llm._CHUNK_TOP_ROWS
    try:
        llm._CHUNK_TOP_ROWS = 64
        wide = _engine(cfg, params)
        llm._CHUNK_TOP_ROWS = 0
        narrow = _engine(cfg, params)
    finally:
        llm._CHUNK_TOP_ROWS = top
    assert wide._chunk_tiers == [16, 32, 64]
    assert narrow._chunk_tiers == [16]
    yield wide, narrow
    wide.shutdown()
    narrow.shutdown()


# 64 + a launch that ends inside a tier, on one, and one row past one.
@pytest.mark.parametrize("n_prompt", [100, 128, 129])
def test_wide_launches_equal_narrow_ones(wide_and_narrow, n_prompt):
    wide, narrow = wide_and_narrow
    prompt = _prompt(n_prompt, seed=n_prompt)
    seqs = np.asarray([prompt + [7]])
    got = wide.score(seqs, n_prompt)[0][0]
    want = narrow.score(seqs, n_prompt)[0][0]
    # tests/test_llm.py's tolerance of a chunked prefill against the
    # whole forward
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.15)
    before = dict(wide.stats["prefill_launch_tokens"])
    assert wide.generate(prompt, max_tokens=6) \
        == narrow.generate(prompt, max_tokens=6)
    ran = {t: n - before[t]
           for t, n in wide.stats["prefill_launch_tokens"].items()}
    last = n_prompt - 64 * (n_prompt // 64)
    want_ran = {16: 0, 32: 0, 64: 64 * (n_prompt // 64)}
    if last:
        want_ran[wide._tier_for(wide._chunk_tiers, last)] += last
    assert ran == want_ran
    assert set(narrow.stats["prefill_launch_tokens"]) == {16}


@pytest.mark.parametrize("name", ["tiny-hybrid", "tiny-window-moe"])
def test_state_by_slot_never_launches_past_prefill_chunk(name):
    """Rings hold window + prefill_chunk rows: such a model's tiers are
    the ones it always had, and no wider program is launched, so none is
    compiled."""
    cfg = configs.get(name)
    e = _engine(cfg, _params(cfg))
    rows = []
    chunk_fn = e._prefill_chunk_fn

    def recorded(params, cache, toks, *a, **kw):
        rows.append(toks.shape[0])
        return chunk_fn(params, cache, toks, *a, **kw)

    e._prefill_chunk_fn = recorded
    try:
        assert e._by_slot
        assert e._chunk_tiers == e._tiers(32, CHUNK) == [CHUNK]
        assert e._prefill_budget() == e._chunk_beside_burst == CHUNK
        with e._tick_lock:
            e.warmup()
        n = 100
        assert len(e.generate(_prompt(n), max_tokens=4)) == 4
        e.score(np.asarray([_prompt(40)]), 39)
        assert rows and max(rows) <= CHUNK
        assert chunk_fn._cache_size() == 1
        assert e.stats["prefill_launch_tokens"] == {CHUNK: n}
    finally:
        e.shutdown()


@pytest.fixture(scope="module")
def spans_chunks():
    """`tiny-mamba2-moe` (state by slot that no launch's rows lay out)
    at `prefill_chunk` 64, warmed, with the rows of every launch of its
    chunk program recorded."""
    cfg = dataclasses.replace(configs.get("tiny-mamba2-moe"),
                              compute_dtype=jnp.float32)
    e = _engine(cfg, _params(cfg), max_len=1024, prefill_chunk=64)
    rows, chunk_fn = [], e._prefill_chunk_fn

    def recorded(params, cache, toks, *a, **kw):
        rows.append(toks.shape[0])
        return chunk_fn(params, cache, toks, *a, **kw)

    e._prefill_chunk_fn = recorded
    with e._tick_lock:
        e.warmup()
    yield e, rows, chunk_fn
    e.shutdown()


def test_state_that_spans_chunks_has_the_ladder_above_prefill_chunk(
        spans_chunks):
    """Tiers go on above `prefill_chunk` to `_CHUNK_TOP_ROWS` as a
    pool-only model's do, and none under it is built: `warmup()`
    launches one program a tier, four and not five."""
    e, rows, chunk_fn = spans_chunks
    assert e._by_slot and e._recurrent and e._spans_chunks
    assert e.prefill_chunk == 64 and e.cache.chunk == 64
    assert e._chunk_tiers == [64, 128, 256, 512]
    assert rows[:4] == e._chunk_tiers and chunk_fn._cache_size() == 4
    assert e._prefill_budget() == 512
    assert e._chunk_beside_burst == min(512, llm._ROWS_A_STEP * e.max_burst)
    # prefill_chunk 256, as granite's: a short last launch is one of 256
    wide = _engine(e.cfg, e.params, max_len=1024, prefill_chunk=256)
    try:
        assert wide._chunk_tiers == [256, 512]
        assert wide._tier_for(wide._chunk_tiers, 20) == 256
    finally:
        wide.shutdown()


def test_state_that_spans_chunks_compiles_nothing_after_warmup(spans_chunks):
    """A long prompt after `warmup()` goes in launches of the top tier
    and a last one of its own, compiles nothing, and is counted by the
    rows of its launches; what it generates is what launches of
    `prefill_chunk` rows give."""
    e, rows, chunk_fn = spans_chunks
    fns = (chunk_fn, e._decode, e._take_last, e._put_last)
    before = [f._cache_size() for f in fns]
    del rows[:]
    counted = dict(e.stats["prefill_launch_tokens"])
    prompt = _prompt(700)
    got = e.generate(prompt, max_tokens=6)
    assert rows == [512, 256]
    assert [f._cache_size() for f in fns] == before
    by_rows = e.stats["prefill_launch_tokens"]
    assert list(by_rows) == e._chunk_tiers
    assert {t: n - counted[t] for t, n in by_rows.items() if n != counted[t]} \
        == {512: 512, 256: 188}
    top = llm._CHUNK_TOP_ROWS
    try:
        llm._CHUNK_TOP_ROWS = 0
        narrow = _engine(e.cfg, e.params, max_len=1024, prefill_chunk=64)
    finally:
        llm._CHUNK_TOP_ROWS = top
    try:
        assert narrow._chunk_tiers == [64]
        assert narrow.generate(prompt, max_tokens=6) == got
    finally:
        narrow.shutdown()


def test_score_with_routing_builds_one_chunk_program(spans_chunks):
    """`score(routing=True)` compiles a chunk program of its own: for
    this kind of model the last launch takes the top tier's rows too,
    so one is built, and its logits are those of the served launches."""
    e, rows, _ = spans_chunks
    seqs = np.asarray([_prompt(700, seed=9)])
    del rows[:]
    want = e.score(seqs, 699)[0][0]
    assert rows == [512, 256]
    got, taken = e.score(seqs, 699, routing=True)
    assert e._score_chunk._cache_size() == 1
    assert taken[0].shape == (700, e.cfg.n_layers, e.cfg.expert_top_k)
    np.testing.assert_allclose(np.asarray(got[0][0]), np.asarray(want),
                               atol=2e-5)


def test_after_warmup_a_long_prompt_compiles_nothing():
    cfg = configs.get("tiny")
    e = _engine(cfg, _params(cfg), max_len=1024)
    try:
        assert e._chunk_tiers == [16, 32, 64, 128, 256, 512]
        assert e.prefill_chunk == CHUNK
        with e._tick_lock:
            e.warmup()
        fns = (e._prefill_chunk_fn, e._decode, e._take_last, e._put_last)
        before = [f._cache_size() for f in fns]
        assert before[0] == len(e._chunk_tiers)
        for n in (700, 513, 37):
            assert len(e.generate(_prompt(n, seed=n), max_tokens=9)) == 9
        assert [f._cache_size() for f in fns] == before
    finally:
        e.shutdown()


def test_budget_beside_a_burst_and_with_nobody_decoding():
    """Rule (ii): with a lane decoding, a launch carries no more rows
    than `_ROWS_A_STEP` a step of the burst beside it; rule (i): with
    none, the widest tier."""
    cfg = configs.get("tiny")
    e = park(_engine(cfg, _params(cfg), max_len=1024, max_burst=2))
    try:
        beside = llm._ROWS_A_STEP * e.max_burst
        assert e._chunk_tiers[-1] == 512 and beside == 128
        assert e._chunk_beside_burst == beside

        def launched(req, n_ticks):
            rows, pos = [], req.pos
            for _ in range(n_ticks):
                tick(e)
                rows.append(req.pos - pos)
                pos = req.pos
            return rows

        alone = submit(e, _prompt(700), 40)
        assert launched(alone, 2) == [512, 188]      # nobody decodes
        assert not alone.prefilling
        late = submit(e, _prompt(600, seed=6), 3)
        rows = launched(late, 5)
        assert e._acct.lanes == 1                    # `alone` decodes
        assert rows == [128, 128, 128, 128, 88]
        run_until_done(e, [alone, late])
        # An engine whose burst is shorter than a chunk's worth of rows
        # keeps `prefill_chunk`, the budget's floor.
        assert _floor_of(cfg, e.params) == 256
    finally:
        e.shutdown()


def _floor_of(cfg, params):
    e = _engine(cfg, params, max_len=1024, prefill_chunk=256, max_burst=1)
    try:
        assert e._chunk_tiers[-1] == 512
        return e._chunk_beside_burst
    finally:
        e.shutdown()


def test_launch_tokens_sum_to_the_prompt_tokens_prefilled(monkeypatch):
    cfg = configs.get("tiny-moe")
    e = _engine(cfg, _params(cfg), num_slots=4, max_len=512)
    spans = []
    monkeypatch.setattr(
        llm.tracing, "record_serve_span",
        lambda ctx, name, t0, t1, **attrs: spans.append((name, attrs)))
    try:
        lengths = [300, 17, 64, 129, 5]
        threads = [threading.Thread(
            target=e.generate, args=(_prompt(n, seed=n),),
            kwargs={"max_tokens": 5}) for n in lengths]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = e.engine_stats()
        by_tier = stats["prefill_launch_tokens"]
        assert sum(by_tier.values()) == sum(lengths)
        assert set(by_tier) == set(e._chunk_tiers)
        ticks = [dict(zip(stats["tick_fields"], t))
                 for t in stats["tick_log"]]
        assert sum(t["prefill_tokens"] for t in ticks) == sum(lengths)
        # the span of a launch carries its tier beside its tokens
        by_rows = dict.fromkeys(e._chunk_tiers, 0)
        for name, attrs in spans:
            if name == "serve.engine.prefill_chunk":
                by_rows[attrs["rows"]] += attrs["tokens"]
        assert by_rows == by_tier
        # a copy: the engine's own dict is not handed out
        by_tier[16] += 1
        assert e.engine_stats()["prefill_launch_tokens"][16] \
            == by_tier[16] - 1
    finally:
        e.shutdown()
