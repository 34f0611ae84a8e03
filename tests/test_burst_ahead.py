"""The engine's tick runs one decode burst ahead of its own reads
(`PagedLLMEngine._decode_tick`): the next burst is launched from the
host's own arithmetic and the device's vector of last tokens before the
burst in flight is read.  Held here, on the pool-only `tiny` model and a
parked engine whose ticks the test steps: greedy streams equal the
step-by-step reference whatever joins and leaves between bursts; EOS,
learnt one burst late, ends the stream where it stood; nothing that needs
the tokens on the host meets an unread burst; the tick log says which
bursts were launched ahead; a warmed engine compiles nothing when the
lane set crosses a tier.  (`tests/test_hybrid_serving.py` and
`tests/test_window_moe_serving.py` run the first scenario on models that
keep state by slot.)"""
import jax
import numpy as np
import pytest

from burst_ahead_cases import (join_and_leave, park, run_join_and_leave,
                               run_until_done, step_reference, submit, tick,
                               ticks_of)
from ray_tpu.models import configs, init_params
from ray_tpu.serve.llm import TICK_FIELDS, PagedLLMEngine

_CFG = configs.get("tiny")
_PARAMS = init_params(jax.random.key(0), _CFG)


def _engine(**kw):
    kw.setdefault("num_slots", 8)
    kw.setdefault("max_len", 128)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("prefix_sharing", False)
    kw.setdefault("max_burst", 4)
    return park(PagedLLMEngine(_CFG, _PARAMS, **kw))


@pytest.fixture
def eng(request):
    e = _engine(**getattr(request, "param", {}))
    yield e
    e.shutdown()


def _prompt(n, seed):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, 500, (n,))]


def _until_in_flight(e, limit=50):
    for _ in range(limit):
        tick(e)
        if e._inflight is not None:
            return
    raise AssertionError("no burst was ever left in flight")


# -- (a) streams equal the reference while lanes join and leave ------------
def test_streams_equal_the_step_reference_across_tier_changes(eng):
    widths = join_and_leave(eng)
    assert widths.count(8) >= 2        # consecutive bursts at the wide tier
    assert sum(t["ahead"] for t in ticks_of(eng)) == len(widths) - 1


def test_the_next_burst_takes_its_tokens_from_the_device(eng):
    """Between two ticks the host has not seen the tokens the next burst
    starts from: `_last_tokens` is a burst behind, and the stream is
    right all the same."""
    req = submit(eng, _prompt(6, 1), 21)
    _until_in_flight(eng)
    tick(eng)
    n_read = len(req.out_tokens)
    assert req.ahead == eng.max_burst and eng._inflight is not None
    # what the host holds for the slot is the last token it read, while
    # the device is already a burst past it
    assert eng._last_tokens[req.slot] == req.out_tokens[-1]
    assert int(eng._lengths[req.slot]) \
        == len(req.prompt) + n_read - 1 + eng.max_burst
    run_until_done(eng, [req])
    assert req.out_tokens == step_reference(eng, req.prompt, 21)


def test_a_request_that_ends_by_count_is_not_launched_again(eng):
    """`max_tokens` is known at the launch: the request's slot is free
    from the launch of its last burst, the burst after it is without
    it, and its blocks go back when its last tokens are read."""
    short = submit(eng, _prompt(5, 2), 1 + eng.max_burst)     # one burst
    long = submit(eng, _prompt(7, 3), 1 + 3 * eng.max_burst)
    _until_in_flight(eng)
    assert eng._slots[short.slot] is None and not short.done.is_set()
    assert short.blocks and eng._slots[long.slot] is long
    live = [r is not None and not r.prefilling for r in eng._slots]
    assert sum(live) == 1              # what a traced run's wrapper counts
    tick(eng)                          # launches `long` alone, reads both
    assert short.done.is_set() and not short.blocks
    assert [lane[0] for lane in eng._inflight.lanes] == [long]
    run_until_done(eng, [long])
    for r in (short, long):
        assert r.out_tokens == step_reference(eng, r.prompt, r.max_tokens)
    assert [t["lanes"] for t in ticks_of(eng) if t["lanes"]] == [2, 1, 1]
    assert eng.stats["tokens_generated"] == 4 * eng.max_burst


# -- (b) EOS is learnt one burst late ---------------------------------------
def _eos_case(e):
    """A prompt whose greedy stream first shows some token mid-burst (not
    at a burst's last step, not in its first burst): that token as EOS."""
    for seed in range(200):
        prompt = _prompt(6, 100 + seed)
        stream = step_reference(e, prompt, 14)
        for k in range(e.max_burst + 1, len(stream)):
            if (k - 1) % e.max_burst != e.max_burst - 1 \
                    and stream[k] not in stream[:k]:
                return prompt, stream, k
    raise AssertionError("no seed gives such a stream")


@pytest.mark.parametrize("eng", [{"num_slots": 1}], indirect=True)
def test_eos_mid_burst_ends_the_stream_and_the_burst_ahead_is_dropped(eng):
    prompt, stream, k = _eos_case(eng)
    eng.eos_id = stream[k]
    first = submit(eng, prompt, 64)
    second = submit(eng, _prompt(9, 7), 6)       # waits for the one slot
    for _ in range(50):
        tick(eng)
        if first.done.is_set():
            break
    # the stream ended at EOS, though the lane was already in the burst
    # ahead, which is still unread
    assert first.out_tokens == stream[:k + 1]
    assert eng._inflight is not None
    assert [lane[0] for lane in eng._inflight.lanes] == [first]
    assert eng._slots[0] is None and not first.blocks
    want = step_reference(eng, second.prompt, 6)
    if eng.eos_id in want:
        want = want[:want.index(eng.eos_id) + 1]
    tick(eng)         # admits `second` to the same slot, reads that burst
    assert eng._slots[0] is second and eng._inflight is None
    # nothing of that burst went to either: `second` holds at most the
    # first token of its own prompt
    assert first.out_tokens == stream[:k + 1]
    assert second.out_tokens == want[:1]
    run_until_done(eng, [second])
    assert second.out_tokens == want
    assert eng.stats["tokens_generated"] == k + len(want) - 1
    assert eng.allocator.snapshot()["blocks_active"] == 0


# -- (c) preemption reads the burst in flight first -------------------------
def test_preempt_with_a_burst_in_flight_loses_and_repeats_no_token(eng):
    a = submit(eng, _prompt(6, 11), 30)
    b = submit(eng, _prompt(8, 12), 30)
    _until_in_flight(eng)
    tick(eng)
    assert eng._inflight is not None and b.ahead == eng.max_burst
    n_read = len(b.out_tokens)
    with eng._tick_lock:
        eng._preempt(b.slot)
    assert eng._inflight is None and b.ahead == 0 and b.prefilling
    assert len(b.out_tokens) == n_read + eng.max_burst
    assert len(a.out_tokens) == n_read + eng.max_burst    # read with it
    run_until_done(eng, [a, b])
    for r in (a, b):
        assert r.out_tokens == step_reference(eng, r.prompt, 30)
    assert eng.stats["preemptions"] == 1


def test_preempt_of_a_request_its_last_read_ends_is_a_no_op(eng):
    a = submit(eng, _prompt(6, 13), 1 + 2 * eng.max_burst)
    keep = submit(eng, _prompt(5, 14), 40)
    _until_in_flight(eng)
    eng.eos_id = step_reference(eng, a.prompt, 3)[2]    # in the first burst
    with eng._tick_lock:
        eng._preempt(a.slot)
    assert a.done.is_set() and not a.prefilling
    assert eng.stats["preemptions"] == 0
    assert a.out_tokens == step_reference(eng, a.prompt, 3)
    eng.eos_id = None
    run_until_done(eng, [keep])


@pytest.mark.parametrize("eng", [{"num_slots": 2, "max_len": 32,
                                  "prefill_chunk": 16, "num_blocks": 9}],
                         indirect=True)
def test_two_stalled_decoders_are_unblocked_without_losing_a_token(eng):
    """The pool deadlock of tests/test_paged_kv.py under the tick that
    covers a burst ahead: both stall on growth blocks, the younger is
    preempted (after the burst in flight is read) and re-prefills."""
    reqs = [submit(eng, list(range(1, 9)), 16),
            submit(eng, list(range(101, 109)), 16)]
    run_until_done(eng, reqs)
    assert eng.stats["preemptions"] >= 1
    for r in reqs:
        assert r.error is None
        assert r.out_tokens == step_reference(eng, r.prompt, 16)
    assert eng.allocator.snapshot()["blocks_active"] == 0


# -- (d) whatever needs the tokens on the host drains first -----------------
def _score(e):
    seqs = np.random.default_rng(0).integers(1, 500, (2, 12))
    got = e.score(seqs, 8)
    assert len(got) == 2 and len(got[0]) == 5


def _import_prefix(e):
    kv = np.zeros((2, _CFG.n_layers, 2, e.block_size, _CFG.n_kv_heads,
                   _CFG.head_dim), np.float32)
    e.import_prefix(list(range(1, 2 * e.block_size + 1)), kv, e.block_size)


@pytest.mark.parametrize("call", [
    _score, lambda e: e.warmup(), lambda e: e.export_streams(),
    _import_prefix, lambda e: e.shutdown()],
    ids=["score", "warmup", "export_streams", "import_prefix", "shutdown"])
def test_callers_between_ticks_see_no_unread_burst(eng, call):
    """A request whose last burst is in flight has left its slot: the
    engine looks idle, and only the read of that burst ends the request."""
    req = submit(eng, _prompt(6, 21), 1 + eng.max_burst)
    _until_in_flight(eng)
    assert all(r is None for r in eng._slots) and not req.done.is_set()
    call(eng)
    assert eng._inflight is None and req.done.is_set()
    assert req.error is None
    assert req.out_tokens == step_reference(eng, req.prompt, req.max_tokens)
    assert eng.allocator.snapshot()["blocks_active"] == 0


def test_export_streams_reads_the_burst_in_flight_first(eng):
    req = submit(eng, _prompt(6, 22), 40, stream=True)
    req.trace = {"trace_id": "rid-ahead"}
    _until_in_flight(eng)
    tick(eng)
    assert req.ahead == eng.max_burst
    (ticket,) = eng.export_streams()
    assert req.ahead == 0 and eng._inflight is None
    # the context whose KV is written: all but the last emitted token
    assert ticket["tokens"] == req.prompt + req.out_tokens[:-1]
    assert len(ticket["tokens"]) == int(eng._lengths[req.slot])
    run_until_done(eng, [req])
    assert req.out_tokens == step_reference(eng, req.prompt, 40)


# -- (e) speculation stays one burst deep ------------------------------------
@pytest.mark.parametrize("eng", [{"speculation_k": 4, "max_burst": 2}],
                         indirect=True)
def test_with_speculation_every_burst_is_read_in_its_own_tick(eng):
    req = submit(eng, [100, 200] * 4, 24)
    other = submit(eng, _prompt(7, 31), 24)
    for _ in range(400):
        if req.done.is_set() and other.done.is_set():
            break
        tick(eng)
        assert eng._inflight is None and req.ahead == other.ahead == 0
    for r in (req, other):
        assert r.out_tokens == step_reference(eng, r.prompt, 24)
    assert all(t["ahead"] == 0 for t in ticks_of(eng))


# -- (f) the tick log says which bursts ran ahead ----------------------------
def test_tick_log_marks_every_burst_but_a_busy_periods_first(eng):
    assert TICK_FIELDS[-9:-7] == ("ahead", "starved_s")
    assert eng.engine_stats()["tick_fields"][-10:-4] == (
        "experts_read", "ahead", "starved_s", "moe_tiles",
        "index_scored_tokens", "kv_selected_tokens")
    for period in range(2):
        reqs = [submit(eng, _prompt(5, 40 + period), 14),
                submit(eng, _prompt(8, 50 + period), 19)]
        run_until_done(eng, reqs)
        tick(eng, 2)                   # idle: nothing is logged
    ticks = ticks_of(eng)
    assert all(t["lanes"] or t["prefill_tokens"] or t["decode_s"] > 0
               for t in ticks)
    launched = [t for t in ticks if t["lanes"]]
    # two busy periods: each one's first burst has no burst before it
    per_period = len(launched) // 2
    assert [t["ahead"] for t in launched] \
        == ([0] + [1] * (per_period - 1)) * 2
    assert all(t["ahead"] == 0 for t in ticks if not t["lanes"])
    assert "bursts_ahead" not in eng.stats      # the tick log says it
    assert [t["start"] for t in ticks] == sorted(t["start"] for t in ticks)
    # each period ends with a tick that only read: its wait is its decode_s
    assert ticks[-1]["lanes"] == 0 and ticks[-1]["decode_s"] > 0


# -- (g) a warmed engine compiles nothing across a tier ----------------------
_COMPILES = []


def _on_compile(event, _secs, **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(event)


def test_a_warmed_engine_compiles_nothing_when_lanes_cross_a_tier(eng):
    eng.warmup()
    warm = submit(eng, _prompt(5, 60), 3)    # the first token's sampler
    run_until_done(eng, [warm])
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    _COMPILES.clear()
    run_join_and_leave(eng)
    assert _COMPILES == []
    widths = [t["width"] for t in ticks_of(eng) if t["lanes"]]
    assert set(widths) == {4, 8}
