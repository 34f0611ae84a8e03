"""Serving-plane observability: per-request traces (trace id == request
id) through proxy -> handle -> replica -> engine, trace continuity
across mid-stream failover, the RAY_TPU_SERVE_TRACE_ENABLED kill
switch, and the serve metrics federation path (worker registry push ->
daemon merge -> GCS rollup)."""
import json
import os
import signal
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import tracing


@pytest.fixture(scope="module", autouse=True)
def ray_cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _poll_spans(trace_id, want, timeout=60, pred=None):
    """Poll the GCS span sink until every name in `want` appears for
    `trace_id` — and `pred(spans)`, when given, holds (the worker
    flushers back off to 16s when idle, so hops land at different
    times)."""
    from ray_tpu.api import _global_worker

    gcs = _global_worker().gcs
    deadline = time.monotonic() + timeout
    spans = []
    while time.monotonic() < deadline:
        spans = gcs.call("TaskEvents", "list_spans", trace_id=trace_id,
                         limit=10000, timeout=10)
        if want <= {s["name"] for s in spans} and (
                pred is None or pred(spans)):
            return spans
        time.sleep(0.5)
    return spans


# ---------------------------------------------------------------------------
# unit: trace context helpers + kill switch
# ---------------------------------------------------------------------------
def test_serve_ctx_and_child_ctx():
    ctx = tracing.serve_ctx("rid-unit-1")
    assert ctx == {"trace_id": "rid-unit-1", "span_id": None}
    with tracing.serve_span(ctx, "serve.test.root", k=1) as s:
        assert s.trace_id == "rid-unit-1" and s.parent_id is None
        child = tracing.child_ctx(ctx, s)
        assert child["trace_id"] == "rid-unit-1"
        assert child["span_id"] == s.span_id
    with tracing.serve_span(child, "serve.test.child") as c:
        assert c.parent_id == s.span_id


def test_resumed_flag_propagates_into_span_attrs():
    rid = f"rid-unit-2-{os.getpid()}"
    ctx = tracing.serve_ctx(rid, resumed=1)
    with tracing.serve_span(ctx, "serve.test.hop") as s:
        pass
    assert s.attrs["resumed"] == 1
    # record_serve_span (the engine's after-the-fact path) too; read it
    # back through the GCS sink — the driver's flusher races any direct
    # peek at the local buffer.
    t0 = time.time()
    tracing.record_serve_span(ctx, "serve.test.recorded", t0)
    spans = _poll_spans(rid, {"serve.test.recorded"})
    rec = [r for r in spans if r["name"] == "serve.test.recorded"]
    assert rec and rec[-1]["attrs"]["resumed"] == 1
    assert rec[-1]["start_ts"] == t0
    # child_ctx keeps the resumed marker for downstream hops
    assert tracing.child_ctx(ctx, s)["resumed"] == 1


def test_kill_switch_disables_serve_tracing():
    from ray_tpu.core import config as cfg_mod

    os.environ["RAY_TPU_SERVE_TRACE_ENABLED"] = "0"
    cfg_mod.reset_config()
    try:
        assert not tracing.serve_enabled()
        assert tracing.serve_ctx("rid-off") is None
        with tracing.serve_span({"trace_id": "rid-off"}, "serve.x") as s:
            assert s is None
        tracing.record_serve_span({"trace_id": "rid-off"}, "serve.y",
                                  time.time())
        assert not [r for r in tracing._buffer
                    if r.get("trace_id") == "rid-off"]
    finally:
        os.environ.pop("RAY_TPU_SERVE_TRACE_ENABLED", None)
        cfg_mod.reset_config()
    assert tracing.serve_enabled()  # default is on


# ---------------------------------------------------------------------------
# unit: metrics plumbing (merge, gauge removal, engine mirror)
# ---------------------------------------------------------------------------
def test_merge_dump_lists_sums_counters_and_histograms():
    from ray_tpu.util.metrics import merge_dump_lists

    key = [["app", "a"]]
    c1 = {"name": "raytpu_serve_tokens_total", "description": "",
          "kind": "counter", "samples": [[key[0], 5.0]]}
    c2 = {"name": "raytpu_serve_tokens_total", "description": "",
          "kind": "counter", "samples": [[key[0], 7.0]]}
    h1 = {"name": "raytpu_serve_ttft_seconds", "description": "",
          "kind": "histogram", "boundaries": [0.1, 1.0],
          "hist": [[key, [1, 0, 0], 0.05, 1]]}
    h2 = {"name": "raytpu_serve_ttft_seconds", "description": "",
          "kind": "histogram", "boundaries": [0.1, 1.0],
          "hist": [[key, [0, 2, 0], 0.8, 2]]}
    g1 = {"name": "raytpu_serve_inflight", "description": "",
          "kind": "gauge", "samples": [[key[0], 3.0]]}
    g2 = {"name": "raytpu_serve_inflight", "description": "",
          "kind": "gauge", "samples": [[key[0], 1.0]]}
    merged = {r["name"]: r for r in merge_dump_lists(
        [[c1, h1, g1], [c2, h2, g2]])}
    assert merged["raytpu_serve_tokens_total"]["samples"] == [
        [["app", "a"], 12.0]]
    hrow = merged["raytpu_serve_ttft_seconds"]["hist"][0]
    assert hrow[1] == [1, 2, 0] and hrow[2] == pytest.approx(0.85)
    assert hrow[3] == 3
    # gauges: last write wins, no summing
    assert merged["raytpu_serve_inflight"]["samples"] == [
        [["app", "a"], 1.0]]


def test_gauge_remove_drops_labelset():
    from ray_tpu.util.metrics import Gauge

    g = Gauge("test_obs_remove_gauge", tag_keys=("app",))
    g.set(4.0, {"app": "x"})
    g.set(9.0, {"app": "y"})
    g.remove({"app": "x"})
    samples = dict(g.samples())
    assert [dict(k)["app"] for k in samples] == ["y"]


class _FakeEngine:
    def __init__(self):
        self.stats = {"tokens_generated": 0, "reuse_hits": 0,
                      "preemptions": 0, "requests": 0, "completed": 0,
                      "active": 0, "blocks_total": 8, "blocks_free": 8,
                      "blocks_cached": 0, "blocks_active": 0,
                      "occupancy": 0.0}

    def engine_stats(self, records=True):
        return dict(self.stats)


def _sample(metric, **tags):
    for key, value in metric.samples():
        if all(dict(key).get(k) == v for k, v in tags.items()):
            return value
    return None


def test_mirror_engine_counts_deltas_not_totals():
    from ray_tpu.serve import observability as obs

    m = obs.metrics()
    eng = _FakeEngine()
    app = f"mirrortest{os.getpid()}"
    obs.mirror_engine(eng, app)          # baseline: all zeros
    eng.stats.update(tokens_generated=10, reuse_hits=3, preemptions=1,
                     blocks_active=4, blocks_free=4, occupancy=0.5)
    obs.mirror_engine(eng, app)
    assert _sample(m["tokens"], app=app) == 10.0
    assert _sample(m["kv_events"], app=app, event="reuse_hit") == 3.0
    assert _sample(m["kv_events"], app=app, event="preemption") == 1.0
    assert _sample(m["kv_blocks"], app=app, state="active") == 4.0
    assert _sample(m["kv_occupancy"], app=app) == 0.5
    # a second mirror with unchanged stats must not double-count
    obs.mirror_engine(eng, app)
    assert _sample(m["tokens"], app=app) == 10.0
    assert _sample(m["kv_events"], app=app, event="reuse_hit") == 3.0
    # ...and further growth adds only the delta
    eng.stats["tokens_generated"] = 15
    obs.mirror_engine(eng, app)
    assert _sample(m["tokens"], app=app) == 15.0


def test_kv_allocator_counts_reuse_misses():
    from ray_tpu.serve.kv_cache import KVBlockAllocator

    a = KVBlockAllocator(9, 4)
    assert a.lookup_prefix([1, 2, 3, 4]) == ([], 0, None)
    assert a.stats["reuse_misses"] == 1
    blocks = a.alloc(1)
    a.register_prefix([1, 2, 3, 4], blocks, meta="m")
    got, covered, _meta = a.lookup_prefix([1, 2, 3, 4, 5])
    assert covered == 4 and got
    assert a.stats["reuse_hits"] == 1
    assert a.stats["reuse_misses"] == 1  # the hit did not count a miss
    snap = a.snapshot()
    assert snap["reuse_misses"] == 1 and snap["reuse_hits"] == 1


# ---------------------------------------------------------------------------
# unit: perfetto rendering of a request track
# ---------------------------------------------------------------------------
def test_request_chrome_trace_renders_hop_rows():
    from ray_tpu.util.timeline import request_chrome_trace

    rid = "rid-render-000"
    spans = [
        {"name": "serve.proxy.request", "trace_id": rid, "span_id": "p",
         "parent_id": None, "start_ts": 1.0, "end_ts": 2.0,
         "attrs": {"app": "a"}},
        {"name": "serve.handle.route", "trace_id": rid, "span_id": "h",
         "parent_id": "p", "start_ts": 1.1, "end_ts": 1.2, "attrs": {}},
        {"name": "serve.engine.decode_burst", "trace_id": rid,
         "span_id": "e", "parent_id": "r", "start_ts": 1.3,
         "end_ts": 1.4, "attrs": {"resumed": 1}},
        {"name": "serve.handle.route", "trace_id": rid, "span_id": "x",
         "parent_id": None, "start_ts": None, "end_ts": None,
         "attrs": {}},  # unfinished: skipped
    ]
    rows = request_chrome_trace(spans)
    assert len(rows) == 3
    assert all(r["pid"] == f"request:{rid[:12]}" for r in rows)
    tids = [r["tid"] for r in rows]
    assert tids[0] == "0:proxy" and tids[1] == "1:handle"
    assert tids[2] == "3:engine (resumed)"
    assert rows[0]["args"]["span_id"] == "p"
    assert rows[1]["args"]["parent_id"] == "p"
    assert rows[0]["dur"] == pytest.approx(1e6)


# ---------------------------------------------------------------------------
# engine spans: direct engine use mints its own trace; spans cover
# queue_wait / prefill chunks / per-burst decode
# ---------------------------------------------------------------------------
def test_paged_engine_emits_phase_spans():
    import jax

    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg = configs.get("tiny")
    params = init_params(jax.random.key(0), cfg)
    eng = PagedLLMEngine(cfg, params, num_slots=2, max_len=64,
                         block_size=4, prefill_chunk=8)
    rid = f"rid-engine-{os.getpid()}"
    try:
        out = eng.generate([5, 7, 11, 13], max_tokens=8,
                           temperature=0.0, timeout=60,
                           trace=tracing.serve_ctx(rid))
        assert out
    finally:
        eng.shutdown()
    spans = _poll_spans(rid, {"serve.engine.queue_wait",
                              "serve.engine.prefill_chunk",
                              "serve.engine.decode_burst"})
    names = {s["name"] for s in spans}
    assert {"serve.engine.queue_wait", "serve.engine.prefill_chunk",
            "serve.engine.decode_burst"} <= names, names
    assert all(s["trace_id"] == rid for s in spans)
    bursts = [s for s in spans
              if s["name"] == "serve.engine.decode_burst"]
    assert all(s["end_ts"] >= s["start_ts"] for s in spans)
    # The first generated token falls out of prefill's last step, so
    # decode bursts account for every token after it.
    assert sum(s["attrs"].get("tokens", 0)
               for s in bursts) >= len(out) - 1


# ---------------------------------------------------------------------------
# engine records: request_phases and tick_log of engine_stats().  One
# scenario, run with serve tracing on and with the kill switch off: six
# requests of two prompt lengths submitted together (the tick lock is
# held until all six are pending, so one tick admits them in order and
# the one prefill lane serves them first in, first out).
# ---------------------------------------------------------------------------
_CHUNK = 8
_PROMPT_LENS = (16, 32, 16, 32, 16, 32)    # whole chunks: no budget is
#                                            left over for the next prompt


def _tiny_paged(model="tiny", **kw):
    import jax

    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import PagedLLMEngine

    from ray_tpu.serve import llm

    cfg = configs.get(model)
    kw.setdefault("num_slots", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", _CHUNK)
    kw.setdefault("prefix_sharing", False)
    # These tests count a prompt's chunks: an engine whose tiers stop at
    # `prefill_chunk`, so that a prompt is several launches (the wider
    # tiers are tests/test_prefill_budget.py's).
    top, llm._CHUNK_TOP_ROWS = llm._CHUNK_TOP_ROWS, 0
    try:
        return PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg),
                              **kw)
    finally:
        llm._CHUNK_TOP_ROWS = top


def _generate_together(eng, prompts, traces, max_tokens=6):
    import threading

    outs = [None] * len(prompts)

    def one(i):
        outs[i] = eng.generate(prompts[i], max_tokens=max_tokens,
                               timeout=120, trace=traces[i])

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    with eng._tick_lock:
        for i, t in enumerate(threads):
            t.start()
            deadline = time.monotonic() + 30
            while len(eng._pending) <= i and time.monotonic() < deadline:
                time.sleep(0.001)
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return outs


@pytest.fixture(scope="module", params=[True, False],
                ids=["traced", "kill_switch_off"])
def six_requests(request):
    from ray_tpu.core import config as cfg_mod

    traced = request.param
    minted = []
    real_init = tracing.Span.__init__

    def counting_init(self, name, *a, **kw):
        real_init(self, name, *a, **kw)
        minted.append(self)

    with pytest.MonkeyPatch.context() as mp:
        if not traced:
            mp.setenv("RAY_TPU_SERVE_TRACE_ENABLED", "0")
            cfg_mod.reset_config()
        mp.setattr(tracing.Span, "__init__", counting_init)
        eng = _tiny_paged()
        try:
            rids = [f"rid-phases-{os.getpid()}-{i}"
                    for i in range(len(_PROMPT_LENS))]
            prompts = [[100 * i + j + 1 for j in range(n)]
                       for i, n in enumerate(_PROMPT_LENS)]
            outs = _generate_together(
                eng, prompts, [tracing.serve_ctx(r) for r in rids])
            stats = eng.engine_stats()
            counters = eng.engine_stats(records=False)
        finally:
            eng.shutdown()
    cfg_mod.reset_config()
    assert all(len(o) == 6 for o in outs)
    return {"traced": traced, "rids": rids, "stats": stats,
            "counters": counters,
            "minted": [s for s in minted
                       if s.name.startswith("serve.engine.")]}


def test_request_phases_sum_to_the_engine_ttft(six_requests):
    recs = six_requests["stats"]["request_phases"]
    assert len(recs) == len(_PROMPT_LENS)
    for r in recs:
        assert set(r) == {"id", "submitted", "queue_wait_s",
                          "prefill_wait_s", "prefill_span_s", "ttft_s",
                          "decode_s", "n_out", "burst_read_s",
                          "first_read_s", "host_s", "lanes_seen"}
        assert r["queue_wait_s"] + r["prefill_wait_s"] \
            + r["prefill_span_s"] == pytest.approx(r["ttft_s"], abs=1e-6)
        assert min(r["queue_wait_s"], r["prefill_wait_s"],
                   r["prefill_span_s"]) >= 0.0
    # one prefill lane, first in first out: a prompt's first chunk waits
    # for the whole prefill span of the prompt before it
    for ahead, behind in zip(recs, recs[1:]):
        assert behind["submitted"] >= ahead["submitted"]
        assert behind["submitted"] + behind["queue_wait_s"] \
            + behind["prefill_wait_s"] >= ahead["submitted"] \
            + ahead["ttft_s"] - 1e-6
    # the record fills with tracing off too; only the ids go
    assert [r["id"] for r in recs] == (
        six_requests["rids"] if six_requests["traced"]
        else [None] * len(recs))


def test_spans_are_minted_only_while_tracing_is_on(six_requests):
    minted = six_requests["minted"]
    if not six_requests["traced"]:
        assert minted == []
        return
    names = [s.name for s in minted]
    n = len(_PROMPT_LENS)
    for name, count in (("queue_wait", n), ("prefill_wait", n),
                        ("prefill", n),
                        ("prefill_chunk", sum(_PROMPT_LENS) // _CHUNK)):
        assert names.count("serve.engine." + name) == count, name
    # the prefill span sums its chunks: their count, their tokens and
    # their own wall time, which its length covers
    spans = [s for s in minted if s.name == "serve.engine.prefill"]
    recs = six_requests["stats"]["request_phases"]
    for s, r, n_prompt in zip(spans, recs, _PROMPT_LENS):
        assert s.attrs["tokens"] == n_prompt
        assert s.attrs["chunks"] == -(-n_prompt // _CHUNK)
        assert 0.0 <= s.attrs["chunk_s"] <= r["prefill_span_s"] + 1e-9


def test_the_counters_carry_the_phase_seconds_and_neither_log(
        six_requests):
    from ray_tpu.serve.llm import PHASES

    stats = six_requests["stats"]
    assert "p_ttft_mean" not in stats and "ttft_sum" not in stats
    # the gauge loop's view: the counters, without the logs
    counters = six_requests["counters"]
    assert counters["completed"] == len(_PROMPT_LENS)
    logs = {"request_phases", "tick_log", "tick_fields", "setup"}
    assert not logs & set(counters)
    assert set(counters) | logs == set(stats)
    seconds = counters["phase_seconds"]
    assert tuple(seconds) == PHASES
    assert all(v >= 0.0 for v in seconds.values())
    # six prompts were prefilled, read and decoded
    for leaf in ("admit", "burst_launch", "burst_read", "emit",
                 "chunk_launch", "first_read", "book"):
        assert seconds[leaf] > 0.0, leaf


def test_tick_log_accounts_for_every_tick_that_progressed(six_requests):
    from ray_tpu.serve.llm import TICK_FIELDS

    stats = six_requests["stats"]
    assert stats["tick_fields"] == TICK_FIELDS
    ticks = [dict(zip(TICK_FIELDS, t)) for t in stats["tick_log"]]
    assert ticks
    for t in ticks:
        assert t["tick_s"] >= t["decode_s"] + t["prefill_s"] \
            + t["sample_s"] >= 0.0
        assert t["lanes"] <= t["width"]
        assert t["prefill_tokens"] <= _CHUNK
        # it progressed: launched a burst, launched a chunk, or (the
        # tick after a busy period's last launch) only read a burst
        assert t["lanes"] or t["prefill_tokens"] or t["decode_s"] > 0.0
        assert t["ahead"] in (0, 1) and t["ahead"] <= t["lanes"]
        assert 0.0 <= t["starved_s"]
    assert [t["start"] for t in ticks] == sorted(t["start"] for t in ticks)
    assert sum(t["prefill_tokens"] for t in ticks) == sum(_PROMPT_LENS)
    # every prompt token went through a chunk the stats counted
    assert stats["prefill_chunks"] == sum(_PROMPT_LENS) // _CHUNK
    # a tick's decode burst is launched before its chunk, so the first
    # tick has no lanes yet.  (The tick that ends the last request may
    # still be writing its record when generate() returns.)
    assert ticks[0]["lanes"] == 0
    # one read of a first token for each prompt, in the tick that
    # launched its last chunk
    assert sum(t["sample_s"] > 0.0 for t in ticks) == len(_PROMPT_LENS)
    assert all(t["prefill_tokens"] for t in ticks if t["sample_s"])


@pytest.mark.parametrize("model", ["tiny", "tiny-moe"])
def test_tick_log_says_how_many_experts_a_burst_read(model):
    """`experts_read` of `tick_fields`: distinct experts the live lanes
    of the burst a tick launched were routed to, per expert layer and
    step, logged with that burst's `lanes` though read a tick later.  0.0
    for a model without experts; with them between top_k (one live
    token) and all of them on every tick that decoded, whatever the
    burst's idle lanes hold."""
    from ray_tpu.models import configs

    cfg = configs.get(model)
    eng = _tiny_paged(model)
    try:
        prompts = [[50 * i + j + 1 for j in range(n)]
                   for i, n in enumerate((8, 16, 8))]
        outs = _generate_together(
            eng, prompts, [None] * len(prompts), max_tokens=12)
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert all(len(o) == 12 for o in outs)
    fields = stats["tick_fields"]
    assert fields[-10:-6] == ("experts_read", "ahead", "starved_s",
                             "moe_tiles")
    ticks = [dict(zip(fields, t)) for t in stats["tick_log"]]
    decoded = [t for t in ticks if t["lanes"] > 0]
    assert decoded and len(decoded) < len(ticks)
    assert all(t["experts_read"] == 0.0 for t in ticks if not t["lanes"])
    if cfg.n_experts <= 0:
        assert all(t["experts_read"] == 0.0 for t in decoded)
        return
    for t in decoded:
        assert cfg.expert_top_k <= t["experts_read"] <= cfg.n_experts, t
    # one or three of a width tier's four lanes are live in these ticks:
    # the idle ones are routed nowhere, so a lone lane reads top_k
    assert {t["experts_read"] for t in decoded if t["lanes"] == 1} \
        <= {float(cfg.expert_top_k)}


@pytest.mark.parametrize("six_requests", [True], indirect=True,
                         ids=["traced"])
def test_request_spans_are_contiguous_and_in_order(six_requests):
    rid = six_requests["rids"][1]            # a prompt that waited
    rec = six_requests["stats"]["request_phases"][1]
    n_chunks = _PROMPT_LENS[1] // _CHUNK
    spans = _poll_spans(
        rid, {"serve.engine.prefill", "serve.engine.decode_burst"},
        pred=lambda ss: sum(s["name"] == "serve.engine.prefill_chunk"
                            for s in ss) == n_chunks)
    by = {}
    for s in sorted(spans, key=lambda s: (s["start_ts"], s["end_ts"])):
        by.setdefault(s["name"].rpartition(".")[2], []).append(s)
    (queue,), (wait,), (prefill,) = (by["queue_wait"], by["prefill_wait"],
                                     by["prefill"])
    chunks, bursts = by["prefill_chunk"], by["decode_burst"]
    assert len(chunks) == n_chunks
    # queue_wait | prefill_wait | prefill share their edges exactly
    assert queue["start_ts"] == rec["submitted"]
    assert queue["end_ts"] == wait["start_ts"]
    assert wait["end_ts"] == prefill["start_ts"] == chunks[0]["start_ts"]
    assert prefill["end_ts"] - queue["start_ts"] == pytest.approx(
        rec["ttft_s"], abs=1e-6)
    assert wait["end_ts"] - wait["start_ts"] == pytest.approx(
        rec["prefill_wait_s"], abs=1e-6)
    # the chunks lie inside their parent, one after the other
    assert all(c["parent_id"] == prefill["span_id"] for c in chunks)
    assert all(a["end_ts"] <= b["start_ts"]
               for a, b in zip(chunks, chunks[1:]))
    assert chunks[-1]["end_ts"] <= prefill["end_ts"]
    assert prefill["attrs"]["chunks"] == n_chunks
    assert prefill["attrs"]["tokens"] == _PROMPT_LENS[1]
    assert prefill["attrs"]["chunk_s"] == pytest.approx(
        sum(c["end_ts"] - c["start_ts"] for c in chunks), abs=1e-6)
    # decode bursts follow the first token
    assert bursts and bursts[0]["start_ts"] >= prefill["end_ts"]
    assert prefill["parent_id"] == queue["parent_id"] == wait["parent_id"]


def test_an_idle_iteration_leaves_no_tick_record():
    eng = _tiny_paged()
    try:
        eng._stop = True            # park the loop: ticks by hand
        eng._work.set()
        eng._thread.join(timeout=10)
        assert not eng._thread.is_alive()
        with eng._tick_lock:
            assert eng._tick() is False
        assert eng.engine_stats()["tick_log"] == ()
        assert eng.engine_stats()["request_phases"] == ()
    finally:
        eng.shutdown()


def test_a_preempted_requests_reprefill_adds_no_ttft_record():
    # The pool deadlock of tests/test_paged_kv.py: both requests stall on
    # growth blocks, the younger is preempted and prefills its whole
    # context again, after its first token.
    eng = _tiny_paged(num_slots=2, max_len=32, prefill_chunk=16,
                      max_burst=4, num_blocks=9)
    minted = []
    real_init = tracing.Span.__init__

    def keeping_init(self, name, *a, **kw):
        real_init(self, name, *a, **kw)
        minted.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing.Span, "__init__", keeping_init)
        try:
            prompts = [list(range(1, 9)), list(range(101, 109))]
            outs = _generate_together(
                eng, prompts, [tracing.serve_ctx(f"rid-preempt-{i}")
                               for i in range(2)], max_tokens=16)
            stats = eng.engine_stats()
        finally:
            eng.shutdown()
    assert all(len(o) == 16 for o in outs)
    assert stats["preemptions"] >= 1
    assert len(stats["request_phases"]) == 2
    assert [s.attrs["chunks"] for s in minted
            if s.name == "serve.engine.prefill"] == [1, 1]
    chunks = [s for s in minted if s.name == "serve.engine.prefill_chunk"]
    again = [s for s in chunks if s.attrs.get("resumed")]
    assert len(again) >= 1 and len(chunks) - len(again) == 2
    assert sum(s.name == "serve.engine.prefill" for s in minted) == 2
    # the re-prefill's tokens are in the tick log all the same
    fields = stats["tick_fields"]
    assert sum(dict(zip(fields, t))["prefill_tokens"]
               for t in stats["tick_log"]) > sum(map(len, prompts))


def test_snapshot_survives_appends_from_the_engine_thread():
    import collections
    import sys
    import threading

    from ray_tpu.serve.llm import _snapshot

    log = collections.deque(maxlen=64)
    stop = threading.Event()

    def append():
        i = 0
        while not stop.is_set():
            log.append((i, i))
            i += 1

    writers = [threading.Thread(target=append) for _ in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in writers:
            w.start()
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            snap = _snapshot(log)
            assert len(snap) <= 64
            assert all(a == b for a, b in snap)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for w in writers:
            w.join(timeout=10)
    assert not any(w.is_alive() for w in writers)


# ---------------------------------------------------------------------------
# cluster: the full proxy -> handle -> replica span chain for one HTTP
# request, plus the federated serve metrics that request produces
# ---------------------------------------------------------------------------
def test_http_request_trace_parentage_and_federation():
    @serve.deployment(num_replicas=1)
    def echo(request):
        return {"ok": True, "n": request.get("n")}

    serve.run(echo.bind(), name="obs_http", _http=True,
              route_prefix="/obs_http")
    rid = f"rid-http-{os.getpid()}"
    try:
        port = serve.http_port()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/obs_http",
            data=json.dumps({"n": 1}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": rid})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.headers.get("X-Request-Id") == rid
            assert json.loads(r.read())["ok"] is True

        want = {"serve.proxy.request", "serve.handle.route",
                "serve.replica.request"}
        spans = _poll_spans(rid, want)
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], s)
        assert want <= set(by_name), set(by_name)
        # the request id IS the trace id on every hop
        assert all(s["trace_id"] == rid for s in spans)
        # causal parentage across process boundaries
        proxy = by_name["serve.proxy.request"]
        route = by_name["serve.handle.route"]
        replica = by_name["serve.replica.request"]
        assert proxy["parent_id"] is None
        assert route["parent_id"] == proxy["span_id"]
        assert replica["parent_id"] == route["span_id"]
        assert proxy["attrs"]["app"] == "obs_http"
        assert proxy["attrs"]["status"] == 200
        assert replica["attrs"]["method"] == "__call__"

        # federation: the proxy's requests counter reaches the GCS
        # rollup (worker push -> daemon merge -> syncer -> federation)
        from ray_tpu.api import _global_worker

        gcs = _global_worker().gcs
        deadline = time.monotonic() + 60
        counters = {}
        while time.monotonic() < deadline:
            summary = gcs.call("Metrics", "cluster_summary",
                               timeout=10).get("serve") or {}
            counters = (summary.get("counters") or {}).get("obs_http", {})
            if counters.get("requests_total.200", 0) >= 1:
                break
            time.sleep(0.5)
        assert counters.get("requests_total.200", 0) >= 1, counters
        # ...and the same series is in the federated exposition
        text = gcs.call("Metrics", "federated_text", timeout=10)
        assert "raytpu_serve_requests_total" in text
    finally:
        serve.delete("obs_http")


# ---------------------------------------------------------------------------
# cluster: mid-stream SIGKILL — the resumed stream keeps the ORIGINAL
# request id, and the failover leg is marked resumed=1
# ---------------------------------------------------------------------------
def test_stream_failover_keeps_trace_id_and_marks_resumed():
    @serve.deployment(num_replicas=2)
    def ticker(request):
        for i in range(int(request["n"])):
            time.sleep(0.03)
            yield {"i": i, "pid": os.getpid()}

    h = serve.run(ticker.bind(), name="obs_kill")
    try:
        resp = h.remote_streaming({"n": 30})
        rid = resp.request_id
        assert rid
        got, killed = [], False
        for item in resp:
            got.append(item)
            if len(got) == 5 and not killed:
                killed = True
                os.kill(item["pid"], signal.SIGKILL)
        assert [x["i"] for x in got] == list(range(30))
        assert resp.resumes >= 1

        def has_resumed_replica(spans):
            return any(s["name"].startswith("serve.replica.")
                       and s["attrs"].get("resumed") for s in spans)

        spans = _poll_spans(rid, {"serve.handle.route",
                                  "serve.handle.resume"},
                            pred=has_resumed_replica)
        names = {s["name"] for s in spans}
        assert "serve.handle.route" in names, names
        assert "serve.handle.resume" in names, names
        # every hop of BOTH legs shares the original request id
        assert all(s["trace_id"] == rid for s in spans)
        resume = [s for s in spans if s["name"] == "serve.handle.resume"]
        assert all(s["attrs"].get("resumed") == 1 for s in resume)
        assert any(s["attrs"].get("offset", 0) >= 5 for s in resume)
        # the survivor's replica-side spans carry the marker too
        resumed_replica = [
            s for s in spans
            if s["name"].startswith("serve.replica.")
            and s["attrs"].get("resumed")]
        assert resumed_replica
    finally:
        serve.delete("obs_kill")
