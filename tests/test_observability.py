"""Observability floor tests: metrics registry/exposition, task events ->
state list + chrome trace, CLI surfaces (VERDICT r1 item 7; ref:
src/ray/stats/metric_defs.cc, python/ray/util/state/state_cli.py,
_private/profiling.py timeline)."""
import io
import json
import time
from contextlib import redirect_stdout

import pytest

import ray_tpu
from ray_tpu.util.metrics import Counter, Gauge, Histogram, get_registry


# ---------------------------------------------------------------------------
# metrics unit tests
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_exposition():
    c = Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(2, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    g = Gauge("test_inflight", "in flight")
    g.set(5)
    g.dec()
    h = Histogram("test_latency_seconds", "lat", boundaries=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(3.0)
    text = get_registry().prometheus_text()
    assert 'test_requests_total{route="/a"} 2.0' in text
    assert 'test_requests_total{route="/b"} 1.0' in text
    assert "test_inflight 4.0" in text
    assert 'test_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'test_latency_seconds_bucket{le="1.0"} 2' in text
    assert 'test_latency_seconds_bucket{le="+Inf"} 3' in text
    assert "test_latency_seconds_count 3" in text
    assert "# TYPE test_requests_total counter" in text


def test_counter_rejects_negative():
    c = Counter("test_neg_total")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_collision_reuses_matching_metric():
    """Re-registering the same name/kind/tag_keys ADOPTS the existing
    sample storage (in-process daemon restarts re-create every metric;
    the old replace-on-register orphaned all prior samples); a shape
    mismatch raises."""
    a = Counter("test_collide_total", "first", tag_keys=("node",))
    b = Counter("test_collide_total", "second", tag_keys=("node",))
    a.inc(2, tags={"node": "a"})
    b.inc(3, tags={"node": "b"})
    text = get_registry().prometheus_text()
    assert 'test_collide_total{node="a"} 2.0' in text
    assert 'test_collide_total{node="b"} 3.0' in text
    # Both instances share one sample set.
    assert dict(a.samples()) == dict(b.samples())
    with pytest.raises(ValueError):
        Gauge("test_collide_total")                   # kind mismatch
    with pytest.raises(ValueError):
        Counter("test_collide_total", tag_keys=("other",))  # tags mismatch
    h1 = Histogram("test_collide_seconds", boundaries=(0.1, 1))
    with pytest.raises(ValueError):                   # boundaries mismatch
        Histogram("test_collide_seconds", boundaries=(0.5, 5))
    h2 = Histogram("test_collide_seconds", boundaries=(0.1, 1))
    h1.observe(0.05)
    h2.observe(0.5)
    assert h1.snapshot() == h2.snapshot()


def test_histogram_time_context_manager():
    h = Histogram("test_timer_seconds", "t", tag_keys=("m",))
    with h.time({"m": "x"}):
        time.sleep(0.002)
    counts, sums, totals = h.snapshot()
    key = (("m", "x"),)
    assert totals[key] == 1
    assert 0.0005 < sums[key] < 1.0
    # Default boundaries resolve sub-millisecond RPC latencies.
    assert Histogram("test_default_bounds").boundaries[0] < 0.001


# ---------------------------------------------------------------------------
# task-event pipeline: bounded buffer, drop accounting, GCS-side caps
# ---------------------------------------------------------------------------

def _drive(coro):
    import asyncio

    return asyncio.run(coro)


def test_task_event_buffer_bounded_with_drop_counters(monkeypatch):
    """GCS down: the ring stays bounded, execution never blocks, and
    every dropped record is counted per kind."""
    import asyncio

    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed.task_events import TaskEventBuffer

    cfg = get_config()
    monkeypatch.setattr(cfg, "task_events_enabled", True)
    monkeypatch.setattr(cfg, "task_events_max_buffer", 16)
    monkeypatch.setattr(cfg, "task_events_profile", True)

    async def dead_gcs(**payload):
        raise ConnectionError("gcs down")

    buf = TaskEventBuffer(flush_fn=dead_gcs, node_id="n1", pid=1)
    for i in range(50):
        buf.record_status(f"task{i:04d}", 0, "RUNNING", ts=float(i))
    assert buf.stats()["pending"] == 16
    assert buf.stats()["dropped"]["status"] == 34
    for i in range(20):
        buf.record_profile(f"p{i}", "transfer", float(i), float(i) + 1)
    assert buf.stats()["pending_profile"] == 16
    assert buf.stats()["dropped"]["profile"] == 4

    # A failed flush re-buffers (no loss beyond the cap) and counts.
    assert _drive(buf.flush_once()) is False
    assert buf.stats()["flush_failures"] == 1
    assert buf.stats()["pending"] == 16

    # Coalescing: transitions for one attempt merge into ONE record.
    shipped = []

    async def live_gcs(**payload):
        shipped.append(payload)

    buf2 = TaskEventBuffer(flush_fn=live_gcs, node_id="n1", pid=1)
    buf2.record_status("t1", 0, "SUBMITTED", ts=1.0, name="t")
    buf2.record_status("t1", 0, "RUNNING", ts=2.0)
    buf2.record_status("t1", 0, "FINISHED", ts=3.0)
    assert _drive(buf2.flush_once()) is True
    (payload,) = shipped
    (rec,) = payload["events"]
    assert rec["state"] == "FINISHED"
    assert rec["state_ts"] == {"SUBMITTED": 1.0, "RUNNING": 2.0,
                               "FINISHED": 3.0}
    # Unreported drop counts ride the next successful flush.
    assert _drive(buf.flush_once()) in (True, False)


def test_gcs_task_manager_eviction_and_gc(monkeypatch):
    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed.task_events import GcsTaskManager

    cfg = get_config()
    monkeypatch.setattr(cfg, "task_events_max_per_job", 5)
    monkeypatch.setattr(cfg, "task_events_finished_job_ttl_s", 0.0)
    mgr = GcsTaskManager()
    for i in range(12):
        mgr.add_task_events(events=[{
            "task_id": f"t{i:03d}", "attempt": 0, "state": "FINISHED",
            "state_ts": {"FINISHED": float(i)}, "job_id": "j1",
            "name": "w", "end_ts": float(i)}])
    s = mgr.stats()
    assert s["stored"] == 5 and s["evicted"] == 7
    assert s["evicted_by_job"]["j1"] == 7
    # Oldest attempts went first.
    kept = {r["task_id"] for r in mgr.list_events()}
    assert kept == {f"t{i:03d}" for i in range(7, 12)}
    # Worker-side drop counts accumulate into completeness accounting.
    mgr.add_task_events(events=[], dropped={"status": 9, "profile": 2})
    summ = mgr.summarize()
    assert summ["completeness"]["worker_dropped_status"] == 9
    assert summ["tasks"]["w"]["FINISHED"] == 5
    # Job-completion GC frees the job's storage and counts it.
    mgr.on_job_finished("j1")
    assert mgr.gc_finished_jobs() == 5
    assert mgr.stats()["stored"] == 0
    assert mgr.stats()["gc_events"] == 5


def test_gcs_task_manager_merges_driver_and_worker_halves():
    from ray_tpu.core.distributed.task_events import GcsTaskManager

    mgr = GcsTaskManager()
    # Driver's half arrives first...
    mgr.add_task_events(events=[{
        "task_id": "tt", "attempt": 0, "state": "LEASED",
        "state_ts": {"SUBMITTED": 1.0, "LEASED": 1.5}, "job_id": "j",
        "name": "f", "submit_node_id": "head", "submit_pid": 10}])
    # ...then the executor's, out of order.
    mgr.add_task_events(events=[{
        "task_id": "tt", "attempt": 0, "state": "FINISHED",
        "state_ts": {"RUNNING": 2.0, "FINISHED": 3.0}, "job_id": "j",
        "name": "f", "node_id": "worker_node", "pid": 20,
        "start_ts": 2.0, "end_ts": 3.0}])
    (rec,) = mgr.get_task("tt")
    assert rec["state"] == "FINISHED"
    assert list(sorted(rec["state_ts"])) == ["FINISHED", "LEASED",
                                             "RUNNING", "SUBMITTED"]
    assert rec["submit_node_id"] == "head" and rec["submit_pid"] == 10
    assert rec["node_id"] == "worker_node" and rec["pid"] == 20


# ---------------------------------------------------------------------------
# hung-task watchdog policy (node_daemon.HangWatchdog; the e2e path
# with real workers lives in test_diagnosis.py)
# ---------------------------------------------------------------------------

def _watchdog(dumps, records, **kw):
    from ray_tpu.core.distributed.node_daemon import HangWatchdog

    async def dump(info):
        dumps.append(info)
        return "Thread 0x1 (most recent call first):\n" \
               '  File "x.py", line 1 in hang\n'

    def record(info, raw):
        records.append((info, raw))

    return HangWatchdog(dump=dump, record=record, **kw)


def test_watchdog_fires_once_per_attempt():
    dumps, records = [], []
    wd = _watchdog(dumps, records, threshold_s=5.0,
                   min_dump_interval_s=0.0)
    task = {"task_id": "t1", "attempt": 0, "start_ts": 100.0}

    async def run():
        # Under threshold: never flagged.
        assert await wd.scan([task], now=104.0) == 0
        # Over threshold: exactly one dump...
        assert await wd.scan([task], now=106.0) == 1
        # ...and NEVER again for the same attempt, however long it
        # stays hung.
        assert await wd.scan([task], now=200.0) == 0
        assert await wd.scan([task], now=10000.0) == 0
        # A retry is a NEW attempt with its own budget.
        retry = dict(task, attempt=1, start_ts=300.0)
        assert await wd.scan([retry], now=310.0) == 1

    _drive(run())
    assert len(records) == 2 and wd.fired_total == 2
    assert records[0][1].endswith("in hang\n")


def test_watchdog_respects_rate_limit_and_under_threshold():
    dumps, records = [], []
    wd = _watchdog(dumps, records, threshold_s=5.0,
                   min_dump_interval_s=60.0)
    a = {"task_id": "a", "attempt": 0, "start_ts": 0.0}
    b = {"task_id": "b", "attempt": 0, "start_ts": 0.0}
    quick = {"task_id": "q", "attempt": 0, "start_ts": 97.0}

    async def run():
        # Two hung tasks, one capture budget: only one dumps now, the
        # other stays eligible and fires after the interval.
        assert await wd.scan([a, b], now=100.0) == 1
        assert await wd.scan([a, b], now=101.0) == 0
        assert await wd.scan([a, b], now=161.0) == 1
        # A task that completed just under the threshold (gone from
        # the running set by the next scan) is never flagged.
        assert await wd.scan([quick], now=101.5) == 0
        assert await wd.scan([], now=300.0) == 0

    _drive(run())
    assert {r[0]["task_id"] for r in records} == {"a", "b"}


def test_watchdog_record_rides_bounded_ring_without_evicting(monkeypatch):
    """The auto-dump ships through the same bounded task-event ring:
    on a full ring (GCS down) the hung record lands, the OLDEST attempt
    is the one evicted (counted), and every record newer than it
    survives — the dump can never displace fresher telemetry."""
    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed.task_events import TaskEventBuffer

    cfg = get_config()
    monkeypatch.setattr(cfg, "task_events_enabled", True)
    monkeypatch.setattr(cfg, "task_events_max_buffer", 16)

    async def dead_gcs(**payload):
        raise ConnectionError("gcs down")

    buf = TaskEventBuffer(flush_fn=dead_gcs, node_id="n1", pid=1)
    for i in range(16):
        buf.record_status(f"new{i:03d}", 0, "RUNNING", ts=float(i))
    before = buf.stats()           # ring at capacity
    assert before["pending"] == 16
    buf.record_status("hungtask", 0, "RUNNING", ts=0.0, hung=True,
                      hung_stack="File x.py line 1", hung_ts=1.0)
    after = buf.stats()
    assert after["pending"] == 16  # still bounded
    assert after["dropped"]["status"] == before["dropped"]["status"] + 1
    payload = buf.drain()
    ids = {r["task_id"] for r in payload["events"]}
    # The hung record made it in WITH its dump; the single eviction
    # took the oldest attempt, never a newer one.
    hung = [r for r in payload["events"] if r["task_id"] == "hungtask"]
    assert hung and hung[0]["hung"] and hung[0]["hung_stack"]
    assert "new000" not in ids
    assert all(f"new{i:03d}" in ids for i in range(1, 16))


def test_hung_fields_merge_and_survive_terminal_record():
    """The watchdog's RUNNING+hung record merges into the attempt; the
    executor's later FINISHED record keeps the flag for post-mortems
    but removes the attempt from the LIVE hung_tasks view."""
    from ray_tpu.core.distributed.task_events import GcsTaskManager

    mgr = GcsTaskManager()
    mgr.add_task_events(events=[{
        "task_id": "h1", "attempt": 0, "state": "RUNNING",
        "state_ts": {"RUNNING": 1.0}, "job_id": "j", "name": "stuck",
        "node_id": "n1", "pid": 7}])
    mgr.add_task_events(events=[{
        "task_id": "h1", "attempt": 0, "state": "RUNNING",
        "state_ts": {"RUNNING": 1.0}, "job_id": "j", "name": "stuck",
        "hung": True, "hung_stack": "File x", "hung_ts": 400.0}])
    (hung,) = mgr.hung_tasks()
    assert hung["task_id"] == "h1" and hung["hung_ts"] == 400.0
    (rec,) = mgr.get_task("h1")
    assert rec["hung"] and rec["hung_stack"] == "File x"
    mgr.add_task_events(events=[{
        "task_id": "h1", "attempt": 0, "state": "FINISHED",
        "state_ts": {"FINISHED": 500.0}, "job_id": "j", "name": "stuck",
        "end_ts": 500.0, "cpu_time_s": 1.5, "rss_delta_bytes": 1024}])
    assert mgr.hung_tasks() == []
    (rec,) = mgr.get_task("h1")
    assert rec["hung"] and rec["state"] == "FINISHED"
    # Resource attribution merged onto the same record and rolls up.
    assert rec["cpu_time_s"] == 1.5
    summ = mgr.summarize()
    assert summ["usage"]["stuck"]["cpu_time_s"]["p50"] == 1.5
    assert summ["usage"]["stuck"]["rss_delta_bytes"]["max"] == 1024


# ---------------------------------------------------------------------------
# state API filter predicates + profiling guards (ISSUE 5 satellites)
# ---------------------------------------------------------------------------

def test_state_filter_predicates():
    from ray_tpu.util.state import _apply_filters

    rows = [{"name": "all_reduce_step", "state": "RUNNING"},
            {"name": "decode", "state": "FINISHED"},
            {"name": None, "state": "RUNNING"}]
    assert _apply_filters(rows, [("name", "contains", "reduce")]) == \
        [rows[0]]
    assert _apply_filters(rows, [("name", "prefix", "dec")]) == [rows[1]]
    assert _apply_filters(rows, [("state", "=", "RUNNING"),
                                 ("name", "contains", "_")]) == [rows[0]]
    with pytest.raises(ValueError) as ei:
        _apply_filters(rows, [("name", "~=", "x")])
    # The error names the valid predicate set.
    for p in ("=", "!=", "contains", "prefix"):
        assert p in str(ei.value)


def test_profile_zero_samples_and_sampler_exclusion():
    from ray_tpu.util.profiling import (
        merge_reports, profile_here, render_report, sample_stacks)

    # duration < interval on a loaded box => zero samples, an honest
    # empty report, and a render that does not divide by zero.
    report = profile_here(duration_s=0.0, interval_s=0.01)
    assert report["samples"] == 0 and report["top"] == []
    assert "0 samples" in render_report(report)
    assert "0 samples" in render_report(merge_reports([report, report]))

    # A concurrent sampler thread (the RPC executor driving a worker's
    # `profile` call) never shows up in another capture's samples.
    import threading

    stop = threading.Event()
    t = threading.Thread(
        target=lambda: sample_stacks(duration_s=1.0, interval_s=0.005),
        name="rival-sampler", daemon=True)
    t.start()
    time.sleep(0.05)
    stacks = sample_stacks(duration_s=0.2, interval_s=0.01)
    stop.set()
    t.join()
    assert not any("sample_stacks" in s for s in stacks), stacks


# ---------------------------------------------------------------------------
# cluster: task events, daemon metrics, timeline, CLI
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def obs_cluster():
    import ray_tpu as rt

    rt.init(num_cpus=2, ignore_reinit_error=True)
    yield rt
    rt.shutdown()


def test_task_events_and_timeline(obs_cluster, tmp_path):
    @ray_tpu.remote
    def traced(x):
        return x + 1

    @ray_tpu.remote
    def boom():
        raise ValueError("intentional")

    assert ray_tpu.get([traced.remote(i) for i in range(5)],
                       timeout=120) == [1, 2, 3, 4, 5]
    with pytest.raises(Exception):
        ray_tpu.get(boom.remote(), timeout=120)

    # Events are flushed on a short period; poll the sink until the
    # driver-side (SUBMITTED/LEASED) and executor-side (terminal)
    # halves have both landed and merged.
    from ray_tpu.api import _global_worker

    w = _global_worker()
    deadline = time.monotonic() + 20
    events = []
    while time.monotonic() < deadline:
        events = w.gcs.call("TaskEvents", "list_events", timeout=15)
        if (any("traced" in (e.get("name") or "")
                and e.get("state") == "FINISHED" for e in events)
                and any("boom" in (e.get("name") or "")
                        and e.get("state") == "FAILED" for e in events)):
            break
        time.sleep(0.3)
    assert any("traced" in e["name"] and e["state"] == "FINISHED"
               for e in events)
    failed = [e for e in events if "boom" in (e.get("name") or "")]
    assert failed and failed[0]["state"] == "FAILED"
    assert "intentional" in failed[0]["error"]
    # Full status-transition history on a completed attempt: every stage
    # of SUBMITTED -> LEASED -> RUNNING -> FINISHED, monotonically
    # ordered, merged across the driver's and executor's reports.
    done = [e for e in events if "traced" in (e.get("name") or "")
            and e.get("state") == "FINISHED"]
    hist = done[0]["state_ts"]
    assert ["SUBMITTED", "LEASED", "RUNNING", "FINISHED"] == [
        s for s in ("SUBMITTED", "LEASED", "RUNNING", "FINISHED")
        if s in hist]
    ts = [hist[s] for s in ("SUBMITTED", "LEASED", "RUNNING", "FINISHED")]
    assert ts == sorted(ts)
    # Submission identity (driver) is kept apart from execution identity
    # (worker) — the timeline's flow arrows need both ends.
    assert done[0]["submit_pid"] and done[0]["pid"]

    from ray_tpu.util.timeline import timeline

    out = timeline(str(tmp_path / "trace.json"))
    trace = json.load(open(out))
    assert any("traced" in ev["name"] and ev["ph"] == "X" for ev in trace)
    # Merged trace: a submit slice on the caller's row plus s->f flow
    # arrows binding submit to run.
    assert any(ev["name"].startswith("submit:") for ev in trace)
    starts = [ev for ev in trace if ev.get("ph") == "s"]
    ends = {ev["id"] for ev in trace if ev.get("ph") == "f"}
    assert starts and any(ev["id"] in ends for ev in starts)


def test_per_task_resource_attribution(obs_cluster, capsys):
    """Executor-side attribution: a CPU-burning, allocating task shows
    thread CPU-time + RSS fields on its list_tasks row, per-name
    p50/p99 rollups in task_summary, and a `ray-tpu top` row."""

    @ray_tpu.remote
    def burner():
        acc = 0
        for i in range(600_000):
            acc += i * i
        blob = bytearray(8 << 20)     # ~8 MB transient RSS
        return acc + len(blob)

    ray_tpu.get([burner.remote() for _ in range(3)], timeout=120)

    from ray_tpu.api import _global_worker

    w = _global_worker()
    row = None
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        events = w.gcs.call("TaskEvents", "list_events", timeout=15)
        done = [e for e in events if "burner" in (e.get("name") or "")
                and e.get("state") == "FINISHED"
                and e.get("cpu_time_s") is not None]
        if done:
            row = done[0]
            break
        time.sleep(0.3)
    assert row, "no attributed burner attempt reached the GCS"
    assert row["cpu_time_s"] > 0.001, row
    assert row.get("rss_peak_bytes", 0) > 0, row
    assert "rss_delta_bytes" in row, row

    summ = w.gcs.call("TaskEvents", "summarize", timeout=15)
    usage = {k: v for k, v in summ["usage"].items() if "burner" in k}
    assert usage, summ["usage"]
    (u,) = usage.values()
    assert u["cpu_time_s"]["p99"] >= u["cpu_time_s"]["p50"] > 0

    from ray_tpu.scripts import cli

    cli.main(["--address", w.gcs_address, "top"])
    out = capsys.readouterr().out
    assert "burner" in out and "CPU_P99_S" in out, out


def test_rpc_instrumentation_and_loop_lag_in_exposition(obs_cluster):
    """The transport self-instruments: per-service/method histograms,
    bytes counters, and the event-loop lag probe all land in the
    process registry after ordinary cluster traffic."""
    text = get_registry().prometheus_text()
    assert "# TYPE raytpu_rpc_client_seconds histogram" in text
    assert 'service="NodeInfo"' in text or 'service="TaskEvents"' in text
    assert "raytpu_rpc_bytes_total" in text
    assert "raytpu_event_loop_lag_seconds" in text


def test_metrics_federation_from_two_nodes():
    """InProcDaemonCluster x2: each daemon piggybacks registry snapshots
    on its syncer pushes; the GCS serves ONE federated exposition with
    per-method RPC latency histograms labelled by >=2 distinct nodes."""
    import asyncio

    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed.rpc import AsyncRpcClient
    from ray_tpu.core.distributed.virtual_node import InProcDaemonCluster

    cfg = get_config()
    saved = cfg.metrics_sync_interval_ms
    cfg.metrics_sync_interval_ms = 200

    async def run():
        cluster = InProcDaemonCluster(2, store_capacity=64 << 20)
        await cluster.start()
        client = AsyncRpcClient(cluster.gcs.server.address)
        node_ids = [d.node_id[:12] for d in cluster.daemons]
        try:
            text = ""
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                text = await client.call("Metrics", "federated_text",
                                         timeout=10)
                if all(f'node="{nid}"' in text for nid in node_ids):
                    break
                await asyncio.sleep(0.2)
            # Per-method RPC latency histograms, from >= 2 nodes.
            assert "# TYPE raytpu_rpc_client_seconds histogram" in text
            for nid in node_ids:
                assert f'node="{nid}"' in text
            assert 'method="push_update"' in text
            # The GCS's own registry federates too, labelled with the
            # GCS's durable node id (not a bare "gcs" placeholder).
            assert f'node="gcs:{cluster.gcs.node_id[:12]}"' in text
            stats = await client.call("Metrics", "stats", timeout=10)
            assert stats["nodes_reporting"] >= 2
            summary = await client.call("Metrics", "cluster_summary",
                                        timeout=10)
            assert "task_events" in summary and "metrics" in summary

            # Task events through the same cluster's RPC surface: a
            # full-history attempt round-trips into list_events and a
            # flow-arrowed merged timeline.
            nid = cluster.daemons[0].node_id
            await client.call("TaskEvents", "add_task_events", events=[{
                "task_id": "fedtask00", "attempt": 0,
                "state": "FINISHED", "name": "fed_task",
                "job_id": "fedjob",
                "state_ts": {"SUBMITTED": 10.0, "LEASED": 10.1,
                             "RUNNING": 10.2, "FINISHED": 10.5},
                "start_ts": 10.2, "end_ts": 10.5,
                "submit_node_id": "drivernode", "submit_pid": 1,
                "node_id": nid, "pid": 2}], timeout=10)
            rows = await client.call("TaskEvents", "list_events",
                                     timeout=10)
            (row,) = [r for r in rows if r.get("task_id") == "fedtask00"]
            assert row["state"] == "FINISHED"
            assert list(row["state_ts"]) == ["SUBMITTED", "LEASED",
                                             "RUNNING", "FINISHED"]
            from ray_tpu.util.timeline import chrome_trace

            trace = chrome_trace(rows)
            assert any(ev.get("ph") == "s" for ev in trace)
            assert any(ev.get("ph") == "f"
                       and ev["pid"] == f"node:{nid[:8]}"
                       for ev in trace)
        finally:
            await client.close()
            await cluster.stop()

    try:
        asyncio.run(run())
    finally:
        cfg.metrics_sync_interval_ms = saved


def test_metrics_federation_daemon_churn():
    """Federation under churn: kill one of two daemons and the GCS's
    health check marks it dead, which expires its gauges from the
    federated exposition and cluster_summary — stale metrics from a
    dead node must not masquerade as live.  The death lands in the
    flight recorder, and `doctor` turns it into a ranked node-churn
    finding (the 2-node chaos acceptance check)."""
    import asyncio

    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed.rpc import AsyncRpcClient
    from ray_tpu.core.distributed.virtual_node import InProcDaemonCluster

    cfg = get_config()
    saved = (cfg.metrics_sync_interval_ms, cfg.health_check_period_ms,
             cfg.health_check_initial_delay_ms,
             cfg.health_check_failure_threshold, cfg.syncer_keepalive_ms)
    cfg.metrics_sync_interval_ms = 100
    cfg.health_check_period_ms = 100
    cfg.health_check_initial_delay_ms = 0
    cfg.health_check_failure_threshold = 3
    cfg.syncer_keepalive_ms = 50

    async def run():
        cluster = InProcDaemonCluster(2, store_capacity=64 << 20)
        await cluster.start()
        client = AsyncRpcClient(cluster.gcs.server.address)
        victim, survivor = [d.node_id[:12] for d in cluster.daemons]
        try:
            loop = asyncio.get_running_loop()
            text = ""
            deadline = loop.time() + 20
            while loop.time() < deadline:
                text = await client.call("Metrics", "federated_text",
                                         timeout=10)
                if (f'node="{victim}"' in text
                        and f'node="{survivor}"' in text):
                    break
                await asyncio.sleep(0.1)
            assert f'node="{victim}"' in text

            # Kill daemon 0: its syncer keepalives stop, the health
            # check marks it dead, and the federation drops its dump.
            await cluster.daemons[0].stop()
            deadline = loop.time() + 30
            while loop.time() < deadline:
                text = await client.call("Metrics", "federated_text",
                                         timeout=10)
                if f'node="{victim}"' not in text:
                    break
                await asyncio.sleep(0.2)
            assert f'node="{victim}"' not in text
            assert f'node="{survivor}"' in text

            summary = await client.call("Metrics", "cluster_summary",
                                        timeout=10)
            assert victim not in summary["metrics"]["staleness_s"]
            assert summary["metrics"]["nodes_reporting"] == 1

            # The death was journalled and doctor ranks it.
            deaths = await client.call("FlightRecorder", "list_events",
                                       kind="node.death", timeout=10)
            assert any((e.get("node_id") or "").startswith(victim)
                       for e in deaths)
            rep = await client.call("Metrics", "doctor", timeout=10)
            assert rep["healthy"] is False
            churn = [f for f in rep["findings"]
                     if f["kind"] == "node-churn"]
            assert churn and churn[0]["severity"] == "warning"
            assert "node death" in churn[0]["message"]
        finally:
            await client.close()
            cluster.daemons = cluster.daemons[1:]
            await cluster.stop()

    try:
        asyncio.run(run())
    finally:
        (cfg.metrics_sync_interval_ms, cfg.health_check_period_ms,
         cfg.health_check_initial_delay_ms,
         cfg.health_check_failure_threshold,
         cfg.syncer_keepalive_ms) = saved


def test_gcs_load_attribution_and_slow_handler_audit():
    """GCS load attribution end to end: tagged callers land in
    per-service x per-component share rows, untagged callers bucket
    under 'unknown', and a handler over the (here: zero) slow budget
    is captured by the audit with method + caller + args digest."""
    import asyncio

    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed import rpc as rpc_mod
    from ray_tpu.core.distributed.gcs_server import GcsServer
    from ray_tpu.core.distributed.rpc import AsyncRpcClient

    cfg = get_config()
    saved_slow = cfg.gcs_slow_handler_ms

    async def run():  # noqa: C901
        # Sub-microsecond budget (read once at GCS start): every
        # handler is "slow", so the audit path is deterministic.
        cfg.gcs_slow_handler_ms = 0.001
        gcs = GcsServer()
        port = await gcs.start()
        tagged = AsyncRpcClient(f"127.0.0.1:{port}")
        try:
            rpc_mod.set_caller_identity("nodeA" + "0" * 11, "syncer")
            for i in range(10):
                await tagged.call("KV", "put", namespace="t",
                                  key=b"k%d" % i, value=b"v" * 64,
                                  timeout=10)
            rpc_mod._caller_identity = None
            await tagged.call("KV", "get", namespace="t", key=b"k0",
                              timeout=10)

            load = (await tagged.call("Metrics", "gcs_load",
                                      timeout=10))["load"]
            by = {(r["service"], r["component"]): r
                  for r in load["rows"]}
            assert by[("KV", "syncer")]["requests"] == 10
            assert by[("KV", "syncer")]["bytes"] > 0
            assert ("KV", "unknown") in by
            shares = load["component_handler_share"]
            assert 0.0 < shares["syncer"] <= 1.0
            assert abs(sum(shares.values()) - 1.0) < 1e-6

            # Every handler exceeds the sub-microsecond budget; the
            # audit captures method, caller, and an args digest.
            rpc_mod.set_caller_identity("nodeA" + "0" * 11, "syncer")
            await tagged.call("KV", "put", namespace="t", key=b"slow",
                              value=b"x" * 128, timeout=10)
            slow = (await tagged.call(
                "Metrics", "gcs_load", timeout=10))["load"]["slow_handlers"]
            assert slow["total"] >= 1
            rec = slow["recent"][-1]
            assert rec["service"] == "KV" and rec["method"] == "put"
            assert rec["caller"][1] == "syncer"
            assert "bytes[128]" in rec["args"]
            # ... and the event log carries the warning for dashboards.
            ev = await tagged.call("EventLog", "list_events",
                                   source="gcs", timeout=10)
            assert any(e["severity"] == "WARNING" for e in ev)
        finally:
            rpc_mod._caller_identity = None
            await tagged.close()
            await gcs.stop()

    try:
        asyncio.run(run())
    finally:
        cfg.gcs_slow_handler_ms = saved_slow
        rpc_mod._caller_identity = None


def test_attribution_disabled_skips_injection():
    """RAY_TPU_GCS_ATTRIBUTION_ENABLED=0: clients stop injecting the
    reserved _caller kwarg, so every request buckets as 'unknown' —
    the off switch for the overhead-sensitive."""
    import asyncio

    from ray_tpu.core.config import get_config
    from ray_tpu.core.distributed import rpc as rpc_mod
    from ray_tpu.core.distributed.gcs_server import GcsServer
    from ray_tpu.core.distributed.rpc import AsyncRpcClient

    cfg = get_config()
    saved = cfg.gcs_attribution_enabled

    async def run():
        gcs = GcsServer()
        port = await gcs.start()
        client = AsyncRpcClient(f"127.0.0.1:{port}")
        try:
            cfg.gcs_attribution_enabled = False
            rpc_mod.set_caller_identity("nodeB" + "0" * 11, "syncer")
            await client.call("KV", "put", namespace="t", key=b"k",
                              value=b"v", timeout=10)
            rows = (await client.call(
                "Metrics", "gcs_load", timeout=10))["load"]["rows"]
            comps = {r["component"] for r in rows if r["service"] == "KV"}
            assert comps == {"unknown"}
        finally:
            rpc_mod._caller_identity = None
            await client.close()
            await gcs.stop()

    try:
        asyncio.run(run())
    finally:
        cfg.gcs_attribution_enabled = saved


def test_daemon_metrics_endpoint(obs_cluster):
    from ray_tpu.api import _global_worker
    from ray_tpu.core.distributed.rpc import SyncRpcClient

    w = _global_worker()
    node = [n for n in ray_tpu.nodes() if n["Alive"]][0]
    text = SyncRpcClient(node["Address"], w.loop_thread).call(
        "NodeDaemon", "get_metrics", timeout=15)
    assert "raytpu_leases_granted_total" in text
    assert "raytpu_workers" in text
    assert "raytpu_object_store_used_bytes" in text
    assert "# TYPE raytpu_leases_granted_total counter" in text


def test_cli_status_and_lists(obs_cluster):
    from ray_tpu.api import _global_worker
    from ray_tpu.scripts import cli

    addr = _global_worker().gcs_address

    def run(*argv):
        buf = io.StringIO()
        with redirect_stdout(buf):
            cli.main(["--address", addr, *argv])
        return buf.getvalue()

    out = run("status")
    assert "nodes: 1 alive" in out
    assert "CPU:" in out
    out = run("list", "nodes")
    assert "ALIVE" in out
    out = run("list", "tasks")
    assert "traced" in out
    out = run("list", "jobs")
    assert "RUNNING" in out
    out = run("metrics")
    assert "raytpu_workers" in out


def test_cli_lists_tasks_beside_an_event_without_a_task_id(obs_cluster):
    """`list_events` fills the room its limit leaves with spans and profile
    events, which carry no task id: `list tasks` prints them too."""
    from ray_tpu.api import _global_worker
    from ray_tpu.scripts import cli

    worker = _global_worker()
    worker.gcs.call("TaskEvents", "add_task_events", profile=[
        {"kind": "profile", "category": "cpu_profile", "name": "no_task_id",
         "start_ts": 1.0, "end_ts": 2.0}])
    buf = io.StringIO()
    with redirect_stdout(buf):
        cli.main(["--address", worker.gcs_address, "list", "tasks"])
    assert "no_task_id" in buf.getvalue() and "traced" in buf.getvalue()


# ---------------------------------------------------------------------------
# Grafana dashboard generation (ref: dashboard/modules/metrics/
# grafana_dashboard_factory.py) + usage stats (ref: _private/usage/)
# ---------------------------------------------------------------------------

def test_grafana_dashboard_generation(tmp_path):
    import json

    from ray_tpu.dashboard.grafana import (
        generate_dashboard,
        write_dashboards,
    )

    metrics = [
        {"name": "raytpu_tasks_submitted", "description": "t",
         "kind": "counter"},
        {"name": "raytpu_store_used_bytes", "description": "b",
         "kind": "gauge"},
        {"name": "raytpu_rpc_latency", "description": "l",
         "kind": "histogram"},
    ]
    dash = generate_dashboard("test board", metrics=metrics)
    assert len(dash["panels"]) == 3
    kinds = {p["title"]: p for p in dash["panels"]}
    assert "rate(raytpu_tasks_submitted[1m])" in \
        kinds["raytpu_tasks_submitted"]["targets"][0]["expr"]
    hist = kinds["raytpu_rpc_latency"]["targets"]
    assert any("histogram_quantile(0.95" in t["expr"] for t in hist)

    from ray_tpu.dashboard.grafana import KNOWN_METRICS

    files = write_dashboards(str(tmp_path), metrics=KNOWN_METRICS)
    names = {f.rsplit("/", 1)[-1] for f in files}
    assert "provisioning.yaml" in names
    core = json.load(open(str(tmp_path / "raytpu_core.json")))
    assert core["uid"] == "raytpu-core"
    # Real daemon metrics land on the curated boards (prefixes must
    # track node_daemon.py's registrations).
    core_titles = {p["title"] for p in core["panels"]}
    assert "raytpu_workers" in core_titles
    assert "raytpu_lease_grant_seconds" in core_titles
    store = json.load(open(str(tmp_path / "raytpu_store.json")))
    assert any(p["title"].startswith("raytpu_object_store")
               for p in store["panels"])

    # Prometheus-text metadata path (what the CLI pulls from a live
    # daemon) parses HELP/TYPE into the same shape.
    from ray_tpu.dashboard.grafana import metrics_from_prometheus_text

    text = ("# HELP raytpu_workers live workers\n"
            "# TYPE raytpu_workers gauge\n"
            "raytpu_workers 3\n"
            "# HELP raytpu_lease_grant_seconds latency\n"
            "# TYPE raytpu_lease_grant_seconds histogram\n")
    parsed = metrics_from_prometheus_text(text)
    assert {"name": "raytpu_workers", "description": "live workers",
            "kind": "gauge"} in parsed


def test_usage_stats_local_and_optin(tmp_path, monkeypatch):
    import json
    import urllib.request

    from ray_tpu.util import usage_stats as us

    us.record_library_usage("data")
    us.record_extra_usage_tag("experiment", "r4")
    snap = us.collect_usage_snapshot()
    assert "data" in snap["libraries_used"]
    assert snap["extra_tags"]["experiment"] == "r4"
    assert snap["ray_tpu_version"]

    p = us.write_usage_snapshot(str(tmp_path / "usage.json"))
    assert json.load(open(p))["schema_version"] == 1

    # Reporting is OPT-IN: disabled by default even with a URL set.
    # The knobs flow through the config registry, so the frozen config
    # singleton is reset around each env change.
    from ray_tpu.core.config import reset_config

    monkeypatch.setenv("RAY_TPU_USAGE_STATS_URL", "http://example/x")
    monkeypatch.delenv("RAY_TPU_USAGE_STATS_ENABLED", raising=False)
    reset_config()
    posted = []
    monkeypatch.setattr(
        urllib.request, "urlopen",
        lambda req, timeout=None: posted.append(req) or _FakeResp())
    try:
        assert us.report_usage() is False
        assert not posted
        # Explicit opt-in sends exactly the inspectable snapshot.
        monkeypatch.setenv("RAY_TPU_USAGE_STATS_ENABLED", "1")
        reset_config()
        assert us.report_usage() is True
        assert json.loads(posted[0].data.decode())["schema_version"] == 1
    finally:
        reset_config()


class _FakeResp:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
