"""Train-plane goodput observability (ISSUE 20).

Three layers under test:
  - the worker-side StepPhaseRecorder (phase math, implicit steps
    delimited by report(), the checkpoint-persist fold, the
    RAY_TPU_TRAIN_OBS_ENABLED kill switch),
  - the GCS TrainRunState aggregator (goodput split incl. restart
    gaps, cross-rank skew with stale-rank blame) against synthetic
    gauges,
  - the whole federation end-to-end on a live cluster: a clean run, a
    chaos run (kill one rank — lost_restart charged, step counters
    monotonic, the failover leg traces under the SAME run id), a
    SIGSTOPped straggler and an injected input stall both named by
    `doctor`.
"""
import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (Checkpoint, DataParallelTrainer, FailureConfig,
                           RunConfig, ScalingConfig)
from ray_tpu.train import observability as obs
from ray_tpu.util import chaos


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


def _recorder(**kw):
    base = dict(run="t", run_id="t#0", rank=0, world_size=1, enabled=True)
    base.update(kw)
    rec = obs.StepPhaseRecorder(**base)
    rec._trace_steps = 0          # unit tests: math only, no span minting
    return rec


# ---------------------------------------------------------------------------
# StepPhaseRecorder unit layer
# ---------------------------------------------------------------------------

class _SteppedTime:
    """`time` as ray_tpu.train.observability reads it, with a
    perf_counter that moves only when the test moves it: the phase
    arithmetic is then exact, whatever the machine is doing."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self) -> float:
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def test_recorder_phase_math(monkeypatch):
    clock = _SteppedTime()
    monkeypatch.setattr(obs, "time", clock)
    rec = _recorder()
    for _ in range(3):
        with obs.step(rec):
            clock.now += 0.001            # attributed to no phase
            with rec.phase("compute"):
                clock.now += 0.02
            with rec.phase("sync"):
                clock.now += 0.005
            clock.now += 0.002
    snap = rec.snapshot()
    assert snap["steps"] == 3
    assert snap["compute_s"] == pytest.approx(3 * 0.02, abs=1e-6)
    assert snap["sync_s"] == pytest.approx(3 * 0.005, abs=1e-6)
    # The unattributed remainder goes to `other`, never negative, and
    # the phase sum never exceeds the step wall.
    assert snap["other_s"] == pytest.approx(3 * 0.003, abs=1e-6)
    assert snap["step_s"] == pytest.approx(3 * 0.028, abs=1e-6)
    assert (snap["compute_s"] + snap["sync_s"] + snap["other_s"]
            <= snap["step_s"] + 1e-6)
    # other counts as productive: a stall you did not measure cannot
    # be blamed on the input pipeline.
    assert snap["busy_fraction"] == pytest.approx(
        (0.02 + 0.003) / 0.028, abs=1e-4)
    assert snap["window_steps"] == 3
    # Phases charged beyond the step's wall leave `other` at zero.
    with obs.step(rec):
        clock.now += 0.01
        rec.add_phase("compute", 0.05)
    assert rec.snapshot()["other_s"] == snap["other_s"] >= 0.0


def test_recorder_implicit_step_closed_by_report():
    rec = _recorder()
    with rec.phase("compute"):
        time.sleep(0.01)
    assert rec.steps_total == 0           # still open
    rec.on_report()
    assert rec.steps_total == 1           # report() delimits implicit steps
    # Explicit steps are NOT cut short by a mid-step report.
    rec.step_start(explicit=True)
    with rec.phase("compute"):
        time.sleep(0.005)
    rec.on_report()
    assert rec.steps_total == 1
    rec.step_end()
    assert rec.steps_total == 2


def test_recorder_persist_folds_into_checkpoint_phase():
    rec = _recorder()
    with obs.step(rec):
        with rec.phase("compute"):
            time.sleep(0.005)
        rec.observe_persist(0.25)
    snap = rec.snapshot()
    assert snap["checkpoint_s"] >= 0.25
    # Outside any step, a persist opens an implicit step backdated by
    # the charged time, so its wall covers the phase.
    rec2 = _recorder()
    rec2.observe_persist(0.1)
    rec2.on_report()
    snap2 = rec2.snapshot()
    assert snap2["steps"] == 1
    assert snap2["checkpoint_s"] >= 0.1
    assert snap2["step_s"] >= 0.1


def test_recorder_kill_switch(monkeypatch):
    from ray_tpu.core.config import reset_config

    monkeypatch.setenv("RAY_TPU_TRAIN_OBS_ENABLED", "0")
    reset_config()
    try:
        rec = obs.StepPhaseRecorder(run="t", run_id="t#0", rank=0,
                                    world_size=1)
        assert not rec.enabled
        with obs.step(rec):
            with rec.phase("compute"):
                pass
        rec.on_report()
        rec.observe_persist(1.0)
        assert rec.steps_total == 0
        assert rec.gauges()["steps"] == 0
        # PhasedIterator degrades to a plain passthrough.
        it = obs.PhasedIterator(iter([1, 2]), rec)
        assert list(it) == [1, 2]
        assert rec.phase_s.get("data_wait", 0.0) == 0.0
    finally:
        monkeypatch.delenv("RAY_TPU_TRAIN_OBS_ENABLED")
        reset_config()


def test_phased_iterator_charges_data_wait():
    rec = _recorder()

    def slow():
        for i in range(3):
            time.sleep(0.01)
            yield i

    assert list(obs.PhasedIterator(slow(), rec)) == [0, 1, 2]
    rec.step_end()
    assert rec.snapshot()["data_wait_s"] >= 3 * 0.01


# ---------------------------------------------------------------------------
# TrainRunState aggregation (synthetic gauges, no cluster)
# ---------------------------------------------------------------------------

def _stub_train_state(events):
    from ray_tpu.core.distributed.gcs_server import TrainRunState

    gcs = SimpleNamespace(
        event_log=SimpleNamespace(list_events=lambda **kw: events),
        nodes=SimpleNamespace(view=SimpleNamespace(alive_nodes=lambda: [])))
    return TrainRunState(gcs)


def _gauge(rank, attempt, *, steps, compute, data_wait=0.0, sync=0.0,
           checkpoint=0.0, other=0.0, window=None):
    g = {"rank": rank, "world": 2, "attempt": attempt, "run_id": "exp#0",
         "steps": steps, "compute_s": compute, "data_wait_s": data_wait,
         "sync_s": sync, "checkpoint_s": checkpoint, "other_s": other,
         "step_s": compute + data_wait + sync + checkpoint + other}
    if window:
        g["window_steps"], g["window_step_s"] = window
    return g


def test_goodput_split_joins_restart_gaps():
    trs = _stub_train_state(
        [{"run": "exp", "gap_s": 2.5, "world": 2},
         {"run": "exp", "gap_s": 0.0, "world": 2},   # first gang start
         {"run": "other", "gap_s": 9.0, "world": 8}])
    now = time.time()
    trs._runs["exp"] = {
        "first_seen": now, "last_seen": now,
        "ranks": {
            "0@0": {"seen_ts": now, "g": _gauge(
                0, 0, steps=10, compute=6.0, data_wait=2.0, sync=1.0,
                checkpoint=1.0, window=(10, 1.0))},
            "1@0": {"seen_ts": now, "g": _gauge(
                1, 0, steps=10, compute=6.0, data_wait=2.0, sync=1.0,
                checkpoint=1.0, window=(10, 2.0))},
        }}
    s = trs._summarize("exp", trs._runs["exp"])
    # attributed = 2 ranks * 10s of phases; lost = 2.5s gap * world 2.
    assert s["restarts"] == 1
    assert s["lost_restart_s"] == pytest.approx(5.0)
    assert s["split"]["compute"] == pytest.approx(12.0 / 25.0)
    assert s["split"]["data_wait"] == pytest.approx(4.0 / 25.0)
    assert s["split"]["lost_restart"] == pytest.approx(5.0 / 25.0)
    assert s["goodput"] == pytest.approx(12.0 / 25.0)
    # Lockstep run rate = min across ranks; the slow window takes blame.
    assert s["step_rate"] == pytest.approx(5.0)
    assert s["skew"]["blame_rank"] == 1
    assert s["skew"]["ratio"] >= 1.5
    assert s["active"] and s["world"] == 2 and s["steps"] == 10


def test_dead_attempt_retained_and_stale_rank_blamed():
    trs = _stub_train_state([])
    now = time.time()
    trs._runs["exp"] = {
        "first_seen": now, "last_seen": now,
        "ranks": {
            # Attempt 0 died long ago; its attribution must survive in
            # the cumulative split.
            "0@0": {"seen_ts": now - 120, "g": _gauge(
                0, 0, steps=5, compute=5.0)},
            # Attempt 1: rank 0 healthy, rank 1 went quiet (SIGSTOP).
            "0@1": {"seen_ts": now, "g": _gauge(
                0, 1, steps=8, compute=8.0, window=(8, 1.0))},
            "1@1": {"seen_ts": now - 30, "g": _gauge(
                1, 1, steps=3, compute=3.0, window=(3, 0.4))},
        }}
    s = trs._summarize("exp", trs._runs["exp"])
    assert s["attempt"] == 1
    assert s["attributed_s"]["compute_s"] == pytest.approx(16.0)
    assert s["skew"]["stale_ranks"] == [1]
    assert s["skew"]["blame_rank"] == 1


# ---------------------------------------------------------------------------
# End-to-end federation on a live cluster
# ---------------------------------------------------------------------------

def _instrumented_loop(total_steps, sleep=0.1, dataset=None):
    def loop(config):
        import tempfile

        ctx = train.get_context()
        ckpt = train.get_checkpoint()
        start = 0
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "state.json")) as f:
                start = json.load(f)["step"] + 1
        shard = train.get_dataset_shard("train") if dataset else None
        for step in range(start, total_steps):
            with train.step_phases():
                if shard is not None:
                    next(shard)
                with train.phase("compute"):
                    time.sleep(sleep)
            ck = None
            if ctx.get_world_rank() == 0:
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "state.json"), "w") as f:
                    json.dump({"step": step}, f)
                ck = Checkpoint(d)
            train.report({"step": step, "world": ctx.get_world_size()},
                         checkpoint=ck)
            if config.get("dir"):
                with open(os.path.join(
                        config["dir"],
                        f"pid_rank{ctx.get_world_rank()}"), "w") as f:
                    f.write(str(os.getpid()))
    return loop


def _wait_pid(path, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with open(path) as f:
                return int(f.read())
        except (OSError, ValueError):
            time.sleep(0.05)
    raise TimeoutError(f"no pid beacon at {path}")


def _poll(fn, timeout=30.0, period=0.25):
    """Poll `fn` until it returns a truthy value (returned) or timeout."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            last = fn()
        except Exception:  # noqa: BLE001 — GCS mid-refresh
            last = None
        if last:
            return last
        time.sleep(period)
    raise TimeoutError(f"condition never met (last={last!r})")


def _elastic_fc(**overrides):
    base = dict(elastic=True, max_failures=3, replace_timeout_s=20,
                backoff_initial_s=0.1, backoff_max_s=0.5,
                backoff_jitter=0.0, hang_timeout_s=60, grow_check_s=3600)
    base.update(overrides)
    return FailureConfig(**base)


def test_train_run_federated_to_gcs(ray_cluster, tmp_path_factory):
    """Clean 2-rank run: per-rank gauges ride the daemon->syncer->GCS
    path into state.train_runs(), cluster_status()["observability"]
    ["train"], and the run's step spans become a perfetto trace."""
    from ray_tpu.util import state, timeline

    tmp = str(tmp_path_factory.mktemp("tobs"))
    trainer = DataParallelTrainer(
        _instrumented_loop(6, sleep=0.1), train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1},
                                     flops_per_step=1e9),
        run_config=RunConfig(name="tclean", storage_path=tmp),
        backend=None)
    result = trainer.fit()
    assert result.error is None, result.error

    def both_ranks_synced():
        s = state.train_runs().get("tclean")
        # 2 ranks x 6 steps x 100ms of compute phase; wait until both
        # ranks' terminal gauge flush has folded in.
        if s and s["attributed_s"]["compute_s"] >= 2 * 5 * 0.1 * 0.8:
            return s
        return None

    s = _poll(both_ranks_synced)
    assert s["run_id"] == "tclean#0"
    assert s["world"] == 2
    assert s["steps"] >= 5
    assert s["restarts"] == 0
    # compute dominates: the loop sleeps 100ms/step inside phase().
    assert s["goodput"] is not None and s["goodput"] >= 0.5
    assert s["split"]["lost_restart"] == 0.0
    assert s["achieved_flops"] > 0          # flops_per_step hint flowed

    cs = state.cluster_status()["observability"]["train"]["runs"]
    assert "tclean" in cs

    # Per-rank step spans federated under trace_id == run_id.
    spans = _poll(lambda: timeline.fetch_spans(trace_id="tclean#0"))
    names = {sp["name"] for sp in spans}
    assert "train.step" in names and "phase.compute" in names
    ranks = {sp["attrs"].get("rank") for sp in spans
             if sp["name"] == "train.step"}
    assert ranks == {0, 1}
    out = timeline.train_trace("tclean", filename=os.path.join(
        tmp, "trace.json"))
    with open(out) as f:
        trace = json.load(f)
    assert any(ev["pid"] == "run:tclean#0" for ev in trace)


def test_goodput_under_chaos_kill_rank(ray_cluster, tmp_path_factory):
    """Satellite: kill a rank mid-run under the elastic supervisor.
    The restart gap lands in lost_restart, sampled step counters stay
    monotonic per attempt across the gang restart, and the failover
    leg's spans carry the SAME run id as attempt 0."""
    from ray_tpu.api import _global_worker
    from ray_tpu.util import state, timeline

    tmp = str(tmp_path_factory.mktemp("tchaos"))
    run = RunConfig(name="tchaos", storage_path=tmp,
                    failure_config=_elastic_fc())
    trainer = DataParallelTrainer(
        _instrumented_loop(10, sleep=0.3), train_loop_config={"dir": tmp},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}),
        run_config=run, backend=None)

    samples = []
    stop_sampling = threading.Event()

    def sample():
        w = _global_worker()
        while not stop_sampling.is_set():
            try:
                s = w.gcs.call("Train", "summary",
                               timeout=5)["runs"].get("tchaos")
                if s:
                    samples.append((s["attempt"], s["steps"]))
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.2)

    def inject():
        pid = _wait_pid(os.path.join(tmp, "pid_rank1"))
        time.sleep(1.5)       # let attempt 0 flush some spans/gauges
        assert chaos.kill_rank(SimpleNamespace(pids=[pid]), 0)

    sampler = threading.Thread(target=sample, daemon=True)
    injector = threading.Thread(target=inject, daemon=True)
    sampler.start()
    injector.start()
    result = trainer.fit()
    injector.join(timeout=10)
    stop_sampling.set()
    sampler.join(timeout=5)
    assert result.error is None, result.error
    assert result.elastic["restarts"]["death"] >= 1, result.elastic

    def restarted_and_resynced():
        s = state.train_runs().get("tchaos")
        # Wait until both the restart event AND the failover attempt's
        # gauges have reached the GCS.
        if s and s["restarts"] >= 1 and s["attempt"] >= 1:
            return s
        return None

    s = _poll(restarted_and_resynced)
    assert s["attempt"] >= 1
    assert s["lost_restart_s"] > 0.0
    assert s["split"]["lost_restart"] > 0.0
    # Both attempts' attribution is retained in the cumulative split.
    assert s["attributed_s"]["compute_s"] > 0.0

    # Step counters are cumulative per attempt: within an attempt the
    # sampled counter must never decrease.
    per_attempt = {}
    for attempt, steps in samples:
        assert steps >= per_attempt.get(attempt, 0), (
            f"step counter went backwards in attempt {attempt}: {samples}")
        per_attempt[attempt] = steps

    # The failover leg traces under the SAME run id as attempt 0.
    def both_attempts_traced():
        spans = [sp for sp in timeline.fetch_spans(trace_id="tchaos#0")
                 if sp["name"] == "train.step"]
        attempts = {sp["attrs"].get("attempt") for sp in spans}
        return spans if (0 in attempts and max(attempts) >= 1) else None

    spans = _poll(both_attempts_traced)
    assert {sp["trace_id"] for sp in spans} == {"tchaos#0"}


def test_doctor_names_sigstop_straggler(ray_cluster, tmp_path_factory):
    """Acceptance: SIGSTOP one rank mid-run; the skew window goes
    stale for that rank and `doctor` emits a critical train-straggler
    finding naming it."""
    from ray_tpu.util import state

    tmp = str(tmp_path_factory.mktemp("tstrag"))
    trainer = DataParallelTrainer(
        _instrumented_loop(26, sleep=0.2),
        train_loop_config={"dir": tmp},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}),
        run_config=RunConfig(name="tstrag", storage_path=tmp),
        backend=None)

    pid_holder = {}

    def inject():
        pid = _wait_pid(os.path.join(tmp, "pid_rank1"))
        pid_holder["pid"] = pid
        time.sleep(1.0)
        assert chaos.sigstop_rank(SimpleNamespace(pids=[pid]), 0)

    fit_result = {}

    def run_fit():
        fit_result["result"] = trainer.fit()

    injector = threading.Thread(target=inject, daemon=True)
    fitter = threading.Thread(target=run_fit, daemon=True)
    injector.start()
    fitter.start()
    try:
        def straggler_finding():
            # The skew-ratio warning can fire first (rank 1 slows before
            # its gauges go stale); wait for the stale-rank escalation.
            rep = state.doctor()
            for f in rep["findings"]:
                if (f["kind"] == "train-straggler"
                        and f.get("run") == "tstrag"
                        and f["severity"] == "critical"):
                    return f
            return None

        f = _poll(straggler_finding, timeout=40.0, period=0.5)
        assert f["severity"] == "critical"      # stale beats slow-window
        assert f["blame_rank"] == 1
        assert 1 in f["skew"]["stale_ranks"]
        assert "rank 1" in f["message"]
    finally:
        if pid_holder.get("pid"):
            chaos.sigcont_rank(SimpleNamespace(pids=[pid_holder["pid"]]), 0)
    fitter.join(timeout=120)
    assert not fitter.is_alive(), "fit never finished after SIGCONT"
    result = fit_result["result"]
    assert result.error is None, result.error
    assert result.metrics["step"] == 25


def test_doctor_names_input_bound_run(ray_cluster, tmp_path_factory):
    """Acceptance: a slow input shard (each next() sleeps) dominates
    the attribution via the auto data_wait charge and `doctor` emits
    train-input-bound for the run."""
    from ray_tpu.util import state

    class SlowShard:
        def __iter__(self):
            return self

        def __next__(self):
            time.sleep(0.06)
            return {"x": 1}

    class SlowDataset:
        def split(self, world):
            return [SlowShard() for _ in range(world)]

    tmp = str(tmp_path_factory.mktemp("tinput"))
    trainer = DataParallelTrainer(
        _instrumented_loop(8, sleep=0.01, dataset=True),
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}),
        run_config=RunConfig(name="tinput", storage_path=tmp),
        backend=None, datasets={"train": SlowDataset()})
    result = trainer.fit()
    assert result.error is None, result.error

    s = _poll(lambda: state.train_runs().get("tinput"))
    assert s["split"]["data_wait"] >= 0.25, s

    def input_finding():
        rep = state.doctor()
        for f in rep["findings"]:
            if f["kind"] == "train-input-bound" and f.get("run") == "tinput":
                return f
        return None

    f = _poll(input_finding, timeout=20.0, period=0.5)
    assert f["severity"] == "warning"
    assert f["data_wait_share"] >= 0.25
    assert "input-bound" in f["message"]
