"""The engine's phase clock (`serve/llm.py:_PhaseClock`): the loop thread
is in exactly one leaf phase at a time, every second of it is credited to
a leaf, a request's decode is closed from two readings of the clock, and
the host's knowledge of an empty device queue is summed a tick as
`starved_s`."""
import threading
import time

import pytest

from burst_ahead_cases import park, run_until_done, submit, tick, ticks_of
from ray_tpu.serve import llm
from ray_tpu.serve.llm import PHASES, TICK_FIELDS, PagedLLMEngine
from ray_tpu.util import tracing

_CHUNK = 8
_SUMS = ("burst_read_s", "first_read_s", "host_s")


def _engine(model="tiny", **kw):
    import jax

    from ray_tpu.models import configs, init_params

    cfg = configs.get(model)
    kw.setdefault("num_slots", 8)
    kw.setdefault("max_len", 96)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", _CHUNK)
    kw.setdefault("prefix_sharing", False)
    # a prompt is several launches of `prefill_chunk` rows: no wide tiers
    top, llm._CHUNK_TOP_ROWS = llm._CHUNK_TOP_ROWS, 0
    try:
        return PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg), **kw)
    finally:
        llm._CHUNK_TOP_ROWS = top


def _prompt(n, base=1):
    return [base + j for j in range(n)]


def _records(eng):
    return {r["id"]: r for r in eng.engine_stats()["request_phases"]}


def _closed(rec):
    assert all(rec[k] is not None for k in llm._DECODE_KEYS), rec
    assert sum(rec[k] for k in _SUMS) == pytest.approx(rec["decode_s"],
                                                       abs=1e-6)
    assert min(rec[k] for k in _SUMS) >= 0.0


# -- (a) a request's decode, closed ----------------------------------------
@pytest.mark.parametrize("model", ["tiny", "tiny-moe"])
def test_a_requests_decode_sums_to_its_wall_time_on_a_known_schedule(model):
    """R decodes alone; Q and S arrive while it does, and the one prefill
    lane gives them their first tokens a tick apart.  By the ticks: R is
    read from bursts of 1, 1 and 2 lanes (the last with Q), Q from 2 and
    2 (with R, then with S), S from 2 and 1."""
    eng = park(_engine(model))
    try:
        burst = eng.max_burst
        r = submit(eng, _prompt(8), 1 + 3 * burst)
        tick(eng, 2)        # prompt -> first token; R's first burst launched
        assert eng._inflight is not None and not r.prefilling
        q = submit(eng, _prompt(8, 100), 1 + 2 * burst)
        s = submit(eng, _prompt(8, 200), 1 + 2 * burst)
        run_until_done(eng, [r, q, s])
        recs = _records(eng)
    finally:
        eng.shutdown()
    for req, lanes in ((r, (1 + 1 + 2) / 3), (q, (2 + 2) / 2),
                       (s, (2 + 1) / 2)):
        rec = recs[req.trace["trace_id"]]
        _closed(rec)
        assert rec["n_out"] == req.max_tokens == len(req.out_tokens)
        assert rec["lanes_seen"] == pytest.approx(lanes)
        # it waited for the device in every burst it was read from
        assert rec["burst_read_s"] > 0.0
    # R decoded while two other prompts got their first tokens; nobody
    # else's first token fell into Q's or S's decode but each other's
    assert recs[r.trace["trace_id"]]["first_read_s"] > 0.0
    assert recs[r.trace["trace_id"]]["first_read_s"] >= max(
        recs[x.trace["trace_id"]]["first_read_s"] for x in (q, s))


def test_every_request_of_a_threaded_run_closes():
    eng = _engine(max_len=64)
    try:
        outs = {}

        def one(i, n):
            outs[i] = eng.generate(_prompt(n, 50 * i + 1), max_tokens=12,
                                   timeout=120)

        threads = [threading.Thread(target=one, args=(i, n))
                   for i, n in enumerate((16, 8, 24, 8))]
        for t in threads[:2]:
            t.start()
        time.sleep(0.05)            # the others arrive while these decode
        for t in threads[2:]:
            t.start()
        for t in threads:
            t.join(timeout=120)
        recs = eng.engine_stats()["request_phases"]
    finally:
        eng.shutdown()
    assert len(recs) == 4 and all(len(o) == 12 for o in outs.values())
    for rec in recs:
        _closed(rec)
        assert rec["n_out"] == 12
        assert 1.0 <= rec["lanes_seen"] <= 4.0


def test_a_record_is_unfinished_until_its_request_ends_and_if_it_fails():
    eng = park(_engine())
    try:
        r = submit(eng, _prompt(8), 1 + 2 * eng.max_burst)
        tick(eng)
        (rec,) = eng.engine_stats()["request_phases"]
        assert rec["ttft_s"] > 0.0
        assert all(rec[k] is None for k in llm._DECODE_KEYS)
        eng._fail_request(r, RuntimeError("injected"))
        tick(eng, 2)
        (rec,) = eng.engine_stats()["request_phases"]
        assert rec["decode_s"] is None and r.done.is_set()
    finally:
        eng.shutdown()


def test_a_preempted_requests_reprefill_falls_into_its_decode():
    # the pool deadlock of tests/test_paged_kv.py: the younger request is
    # preempted after its first token and prefills its context again
    eng = park(_engine(num_slots=2, max_len=32, prefill_chunk=16,
                       max_burst=4, num_blocks=9))
    try:
        reqs = [submit(eng, _prompt(8), 16), submit(eng, _prompt(8, 101), 16)]
        run_until_done(eng, reqs)
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert stats["preemptions"] >= 1
    assert len(stats["request_phases"]) == 2
    for rec in stats["request_phases"]:
        _closed(rec)
        assert rec["n_out"] == 16


# -- (b) every second of the loop thread -----------------------------------
def test_the_phase_seconds_are_the_loop_threads_wall_time():
    t_lo = time.time()
    eng = _engine()
    t_hi = time.time()
    try:
        def reading():
            a = time.time()
            s = sum(eng.engine_stats(records=False)["phase_seconds"].values())
            b = time.time()
            return (a + b) / 2, s, (b - a) / 2

        at0, s0, e0 = reading()
        # since the clock was made, inside the constructor
        assert at0 - t_hi - e0 <= s0 <= at0 - t_lo + e0
        eng.generate(_prompt(16), max_tokens=20, timeout=120)
        at1, s1, e1 = reading()
        assert s1 - s0 == pytest.approx(at1 - at0, abs=1e-3 + e0 + e1)
        time.sleep(0.3)                     # an idle stretch: all `wait`
        before = eng.engine_stats(records=False)["phase_seconds"]
        at2, s2, e2 = reading()
        assert s2 - s1 == pytest.approx(at2 - at1, abs=1e-3 + e1 + e2)
        time.sleep(0.2)
        after = eng.engine_stats(records=False)["phase_seconds"]
        assert after["wait"] - before["wait"] >= 0.19
        # an idle loop still looks for work every 20 ms: microseconds
        busy = sum(after[k] - before[k] for k in PHASES if k != "wait")
        assert 0.0 <= busy < 0.01
        assert all(after[k] == before[k]
                   for k in ("burst_read", "first_read", "emit"))
    finally:
        eng.shutdown()


# -- (c) the leaves are flat ------------------------------------------------
class _Notes:
    """Stands in for `jax.profiler.TraceAnnotation` (an event that opens
    when it is made and closes at `__exit__`): keeps what was opened, on
    which thread, and fails the moment two are open at once."""

    def __init__(self):
        self.opened, self.open_now, self.faults = [], [], []

    def __call__(self, name):
        return _Note(self, name)


class _Note:
    def __init__(self, notes, name):
        self.notes, self.name = notes, name
        if notes.open_now:
            notes.faults.append((name, "inside", notes.open_now[-1].name))
        notes.open_now.append(self)
        notes.opened.append((name, threading.get_ident()))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if not self.notes.open_now or self.notes.open_now[-1] is not self:
            self.notes.faults.append((self.name, "closed out of turn"))
        else:
            self.notes.open_now.pop()


def _recorded(eng):
    eng._clock.note = _Notes()
    return eng._clock.note


def _flat(notes, want=()):
    assert notes.faults == []
    names = [n for n, _ in notes.opened]
    assert set(names) <= {"serve.engine.phase." + p for p in PHASES}
    # consecutive leaves differ: re-entering the open leaf opens nothing
    assert all(a != b for a, b in zip(names, names[1:]))
    # at most the leaf the thread is in is still open
    assert len(notes.open_now) <= 1
    for leaf in want:
        assert "serve.engine.phase." + leaf in names, leaf
    return names


def test_no_leaf_opens_inside_another_in_plain_ticks_and_shutdown():
    eng = _engine()
    notes = _recorded(eng)
    try:
        outs = [None, None]

        def one(i):
            outs[i] = eng.generate(_prompt(16, 40 * i + 1), max_tokens=20,
                                   timeout=120)

        threads = [threading.Thread(target=one, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        loop_thread = eng._thread.ident
    finally:
        eng.shutdown()
    assert all(len(o) == 20 for o in outs)
    _flat(notes, want=PHASES)
    # the clock is the loop thread's alone: shutdown's last read of a
    # burst, on this thread, opened nothing
    assert {t for _, t in notes.opened} == {loop_thread}
    assert [n.name for n in notes.open_now] == ["serve.engine.phase.wait"]


def test_other_threads_under_the_tick_lock_switch_no_phase():
    eng = _engine()
    try:
        eng.generate(_prompt(8), max_tokens=4, timeout=120)
        notes = _recorded(eng)
        time.sleep(0.05)
        before = eng.engine_stats(records=False)["phase_seconds"]
        with eng._tick_lock:
            eng.warmup()
        eng.score([_prompt(12)], 8)
        after = eng.engine_stats(records=False)["phase_seconds"]
    finally:
        eng.shutdown()
    me = threading.get_ident()
    assert notes.faults == [] and me not in {t for _, t in notes.opened}
    # the loop thread waited meanwhile
    assert all(after[k] == before[k] for k in PHASES
               if k not in ("wait", "admit", "burst_launch",
                            "chunk_launch", "book"))
    assert after["burst_read"] == before["burst_read"]


def test_no_leaf_opens_inside_another_in_a_spec_tick():
    eng = park(_engine(max_burst=1, speculation_k=6, speculation_ngram=2))
    notes = _recorded(eng)
    try:
        r = submit(eng, [100, 200] * 4, 24)
        run_until_done(eng, [r])
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert stats["spec_accepted"] > 0 and len(r.out_tokens) == 24
    _flat(notes, want=("burst_launch", "burst_read", "emit"))
    (rec,) = stats["request_phases"]
    _closed(rec)
    assert rec["lanes_seen"] == 1.0


def test_no_leaf_opens_inside_another_in_a_preemption():
    eng = park(_engine(num_slots=2, max_len=32, prefill_chunk=16,
                       max_burst=4, num_blocks=9))
    notes = _recorded(eng)
    try:
        reqs = [submit(eng, _prompt(8), 16), submit(eng, _prompt(8, 101), 16)]
        run_until_done(eng, reqs)
        assert eng.stats["preemptions"] >= 1
    finally:
        eng.shutdown()
    _flat(notes, want=PHASES)


@pytest.mark.parametrize("where", ["first_read", "burst_read"])
def test_no_leaf_opens_inside_another_when_a_request_fails(where):
    eng = park(_engine())
    notes = _recorded(eng)
    try:
        ok = submit(eng, _prompt(8), 1 + 2 * eng.max_burst)
        tick(eng, 2)
        bad = submit(eng, _prompt(8, 100), 12)
        if where == "first_read":
            real = eng._sample_one

            def failing(*a):
                eng._sample_one = real
                raise RuntimeError("injected: the sampler")

            eng._sample_one = failing
        else:
            # `tiny` has no experts: its read is np.asarray of the tokens
            eng._inflight.tok_mat = _Unreadable()
        run_until_done(eng, [ok, bad])
        assert bad.error is not None or ok.error is not None
    finally:
        eng.shutdown()
    _flat(notes)
    assert eng._clock.leaf == llm._WAIT and not eng._clock.ticking


class _Unreadable:
    def __array__(self, *a, **kw):
        raise RuntimeError("injected: the read")


# -- (d) starved_s -----------------------------------------------------------
def test_starved_seconds_count_an_empty_queue_with_work_in_hand():
    eng = park(_engine())
    try:
        real = eng._begin_decode

        def slow_begin(req, tok):
            time.sleep(0.03)        # the host dawdles before its next launch
            return real(req, tok)

        eng._begin_decode = slow_begin
        r = submit(eng, _prompt(2 * _CHUNK), 1 + 3 * eng.max_burst)
        tick(eng)                   # a chunk, and no read
        tick(eng)                   # the last chunk: the first token's read
        assert eng._idle_from > 0.0
        tick(eng)                   # the first burst ends the stretch
        assert eng._idle_from == 0.0
        tick(eng, 2)                # bursts launched ahead of their reads
        run_until_done(eng, [r])
        ticks = ticks_of(eng)
        assert eng._idle_from == 0.0    # nothing in hand: not starved
    finally:
        eng.shutdown()
    first, last_chunk, first_burst, *ahead = ticks
    assert first["prefill_tokens"] == _CHUNK and first["starved_s"] == 0.0
    assert last_chunk["sample_s"] > 0.0
    assert 0.03 <= last_chunk["starved_s"] <= last_chunk["tick_s"]
    # the wait between the two ticks is the host's too
    assert first_burst["lanes"] == 1 and first_burst["starved_s"] > 0.0
    launched_ahead = [t for t in ahead if t["ahead"]]
    assert launched_ahead and all(t["starved_s"] == 0.0
                                  for t in launched_ahead)
    # the busy period's last read returns to an engine without work
    assert ticks[-1]["lanes"] == 0 and ticks[-1]["starved_s"] == 0.0


# -- (e) the shape of the records --------------------------------------------
def test_the_tick_fields_end_with_ahead_and_starved_seconds():
    # and, behind them since PR 46, the tiles a tick's chunks multiplied,
    # since PR 49 what a learned selection scored and attended, since
    # PR 52 what a burst of denoising passes filled, since PR 58 the
    # slots whose rings a burst's window layer reads
    assert TICK_FIELDS[-9:] == ("ahead", "starved_s", "moe_tiles",
                                "index_scored_tokens", "kv_selected_tokens",
                                "blocks", "passes", "block_tokens",
                                "ring_slots")
    assert PHASES == ("wait", "admit", "burst_launch", "burst_read", "emit",
                      "chunk_launch", "first_read", "book")
    eng = _engine()
    try:
        eng.generate(_prompt(8), max_tokens=4, timeout=120)
        counters = eng.engine_stats(records=False)
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert tuple(counters["phase_seconds"]) == PHASES
    assert not {"tick_log", "request_phases", "tick_fields"} & set(counters)
    assert stats["tick_fields"] == TICK_FIELDS
    assert all(len(t) == len(TICK_FIELDS) for t in stats["tick_log"])
    assert "p_ttft_mean" not in stats


def test_serve_status_shows_the_phase_seconds_as_shares():
    from ray_tpu.scripts.cli import _phase_shares

    assert _phase_shares(None) == "" and _phase_shares({}) == ""
    line = _phase_shares({"wait": 6.0, "admit": 0.0, "burst_launch": 0.5,
                          "burst_read": 3.0, "emit": 0.25,
                          "chunk_launch": 0.0, "first_read": 0.0,
                          "book": 0.25})
    assert line == ("wait=60.0%  burst_launch=5.0%  burst_read=30.0%  "
                    "emit=2.5%  book=2.5%  (host-bound 25.0%)")


def test_the_replicas_state_push_carries_the_phase_seconds():
    from ray_tpu.serve.llm import LLMDeployment

    d = LLMDeployment("tiny", num_slots=2, max_len=64, disagg=False)
    try:
        d({"tokens": _prompt(6), "max_tokens": 3})
        state = d.serve_state()
    finally:
        d.engine.shutdown()
    assert tuple(state["phase_seconds"]) == PHASES
    assert state["phase_seconds"]["wait"] >= 0.0


# -- (f) the decode span -------------------------------------------------------
def test_the_decode_span_parents_its_bursts_and_carries_the_sums():
    minted = []
    real_init = tracing.Span.__init__

    def keeping_init(self, name, *a, **kw):
        real_init(self, name, *a, **kw)
        minted.append(self)

    eng = park(_engine())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing.Span, "__init__", keeping_init)
        try:
            rid = "rid-decode-span"
            r = submit(eng, _prompt(8), 1 + 2 * eng.max_burst)
            r.trace = tracing.serve_ctx(rid)
            run_until_done(eng, [r])
            (rec,) = eng.engine_stats()["request_phases"]
        finally:
            eng.shutdown()
    (decode,) = [s for s in minted if s.name == "serve.engine.decode"]
    (prefill,) = [s for s in minted if s.name == "serve.engine.prefill"]
    bursts = [s for s in minted if s.name == "serve.engine.decode_burst"]
    assert len(bursts) == 2
    assert all(b.parent_id == decode.span_id for b in bursts)
    assert decode.parent_id == prefill.parent_id
    # first token -> the request's end, with the record's sums
    assert decode.start == prefill.end
    assert decode.end - decode.start == pytest.approx(rec["decode_s"],
                                                      abs=1e-9)
    assert all(decode.start <= b.start and b.end <= decode.end
               for b in bursts)
    for key in llm._DECODE_KEYS:
        assert decode.attrs[key] == rec[key]
    assert rec["id"] == rid and rec["n_out"] == r.max_tokens
    _closed(rec)


def test_no_decode_span_is_minted_with_the_kill_switch_off():
    from ray_tpu.core import config as cfg_mod

    minted = []
    real_init = tracing.Span.__init__

    def keeping_init(self, name, *a, **kw):
        real_init(self, name, *a, **kw)
        minted.append(name)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RAY_TPU_SERVE_TRACE_ENABLED", "0")
        cfg_mod.reset_config()
        mp.setattr(tracing.Span, "__init__", keeping_init)
        eng = _engine()
        try:
            eng.generate(_prompt(8), max_tokens=10, timeout=120)
            (rec,) = eng.engine_stats()["request_phases"]
        finally:
            eng.shutdown()
    cfg_mod.reset_config()
    # The engine's start keeps its own records whatever the switch says
    # (it silences their second sink: tests/test_setup_timeline.py).
    assert [n for n in minted if not n.startswith("serve.setup")] == []
    _closed(rec)            # the record fills all the same
    assert rec["id"] is None and rec["n_out"] == 10
