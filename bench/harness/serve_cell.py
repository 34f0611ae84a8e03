"""A served cell: serve.run(BenchLLMDeployment) on one chip, driven over
HTTP streaming from the client's side (proxy -> handle -> replica ->
PagedLLMEngine)."""
from __future__ import annotations

import asyncio
import collections
import itertools
import time
from typing import Any, Dict

from bench.harness import client, report, runtime, schedule, spec

APP, ROUTE = "bench", "/bench"
DRAIN_S = 150.0          # > the program's serve_request_deadline_s (120 s)
TRACE_AT, TRACE_S = 0.4, 3.0     # profiler window: from 40% of the window


def _call(method: str, payload: dict, timeout: float = 120.0) -> dict:
    from ray_tpu import serve

    return serve.get_app_handle(APP).options(method_name=method).remote(
        payload).result(timeout=timeout)


def _requests(cell: spec.Cell, seed: int, seconds: float):
    """(the requests, those to check against the engine's limits)."""
    t, vocab = cell.traffic, cell.config["vocab_size"]
    if t["loop"] == "open":
        reqs = schedule.open_schedule(t, float(cell.load["rate_rps"]),
                                      seconds, seed, vocab)
        return reqs, reqs
    one_block = itertools.islice(schedule.closed_schedule(t, seed, vocab),
                                 int(t["block"]))
    return schedule.closed_schedule(t, seed, vocab), list(one_block)


class Served:
    """The cell's configuration deployed and warm: `with Served(...) as s`
    gives `s.window(...)` (one measured window, drained) and `s.report()`.
    Leaving the block stops the runtime and waits for the replica."""

    def __init__(self, cell: spec.Cell, seed: int, concurrency: int,
                 traced: bool, rehearse: bool):
        self.cell, self.seed, self.traced = cell, seed, traced
        self._concurrency, self._rehearse = concurrency, rehearse
        self._pids = []

    def __enter__(self) -> "Served":
        from ray_tpu import serve

        runtime.start(self.cell.chips, self._rehearse)
        try:
            serve.run(
                serve.deployment(
                    BenchDeployment(),
                    ray_actor_options={"num_tpus": self.cell.chips}).bind(
                    self.cell.config, self.seed, self._concurrency,
                    self.traced),
                name=APP, route_prefix=ROUTE, _http=True)
            self.url = (f"http://127.0.0.1:{serve.http_port()}{ROUTE}"
                        f"?stream=1&method=stream")
            self.t_ready = time.time()
            # One small request through the whole front, so that routes,
            # rails and the sub-second programs (sampler, block copy: an
            # odd prompt length makes the engine copy its tail block)
            # have run once.
            warm = schedule.Request(-1, 0.0, 41, 9, list(range(1, 42)))
            w = self.window([warm], loop_kind="open", seconds=0.0,
                            id_prefix="bench-warm")["outcomes"][0]
            if w.cause:
                print(w.failure_line(), flush=True)
                raise RuntimeError("the warm-up request failed")
        except BaseException:
            runtime.stop()
            raise
        return self

    def __exit__(self, *exc) -> None:
        runtime.stop(*self._pids)

    def window(self, requests, *, loop_kind: str, seconds: float,
               id_prefix: str, clients: int = 0, temperature: float = 0.0,
               stagger_s: float = 0.0, at=None) -> Dict[str, Any]:
        marks: Dict[str, Any] = {}
        out = asyncio.run(client.drive(
            self.url, requests, loop_kind=loop_kind, seconds=seconds,
            clients=clients, temperature=temperature, drain_s=DRAIN_S,
            id_prefix=id_prefix, stagger_s=stagger_s,
            on_open=lambda: marks.update(
                open=_call("bench_mark", {"mark": "open"}),
                t_open=time.time()),
            at=at))
        _call("bench_mark", {"mark": "close"})
        out["t_open"] = marks["t_open"]
        return out

    def report(self, keep_trace=None) -> Dict[str, Any]:
        replica = _call("bench_report",
                        {"reduce": self.traced,
                         "programs": self.cell.programs(),
                         "keep_trace": keep_trace}, timeout=600.0)
        self._pids.append(replica["pid"])
        return replica


def run(cell: spec.Cell, *, seed: int, seconds: float, traced: bool,
        rehearse: bool, t_start: float, requests=None,
        keep_trace=None) -> int:
    """`requests`, `keep_trace`: for bench/tools only (a hand-made
    schedule that skips the limit check; a copy of the trace to look at)."""
    traffic, engine = cell.traffic, cell.config["engine"]
    open_loop = traffic["loop"] == "open"
    if requests is None:
        requests, to_check = _requests(cell, seed, seconds)
        spec.check_requests(to_check, engine)
        report.note("schedule", loop=traffic["loop"],
                    planned=len(to_check) if open_loop else None,
                    rate_rps=cell.load.get("rate_rps"),
                    clients=traffic.get("clients"),
                    prompt_len_sum=sum(r.prompt_len for r in to_check),
                    max_tokens_sum=sum(r.max_tokens for r in to_check))
    concurrency = engine["num_slots"] if open_loop else traffic["clients"]
    with Served(cell, seed, concurrency, traced, rehearse) as served:
        side = []
        if traced:
            side.append((TRACE_AT * seconds, lambda: _call(
                "bench_profile", {"seconds": min(TRACE_S, 0.3 * seconds)})))
        run_ = served.window(
            requests, loop_kind=traffic["loop"], seconds=seconds,
            clients=traffic.get("clients", 0),
            temperature=traffic.get("temperature", 0.0),
            stagger_s=traffic.get("start_stagger_s", 0.0),
            id_prefix=f"bench-{seed}", at=side)
        # Every failure prints its cause, before anything else can fail.
        outcomes = run_["outcomes"]
        failed = [o for o in outcomes if o.cause]
        for o in failed:
            print(o.failure_line(), flush=True)
        report.note("window", attempted=len(outcomes), failed=len(failed),
                    failed_by_cause=dict(collections.Counter(
                        o.cause for o in failed)),
                    window_s=run_["window_s"], drain_s=run_["drain_s"])
        replica = served.report(keep_trace)
        t_ready = served.t_ready

    run_["setup_s"] = run_["t_open"] - t_start
    opened, closed = (replica["compile_marks"][k] for k in ("open", "close"))
    window_compiles = {k: closed[k] - opened[k] for k in opened}
    times = replica["times"]
    report.note(
        "phases", attempted=len(outcomes), failed=len(failed),
        failed_by_cause=dict(collections.Counter(o.cause for o in failed)),
        window_s=run_["window_s"], drain_s=run_["drain_s"],
        window_compiles=window_compiles, compile_total=replica["compile_now"],
        check=replica["check"], warmed_widths=replica["warmed_widths"],
        setup={"to_replica_start": times["init_start"] - t_start,
               "params": times["params_ready"] - times["init_start"],
               "engine": times["engine_built"] - times["params_ready"],
               "warmup": times["warmed"] - times["engine_built"],
               "logits_check": times["checked"] - times["warmed"],
               "ready_to_client": t_ready - times["checked"],
               "front_warmup": run_["t_open"] - t_ready},
        late_ms_max=1000 * max((o.sent - o.due for o in outcomes
                                if o.sent is not None), default=0.0),
        engine_stats={k: replica["stats"].get(k) for k in (
            "requests", "completed", "tokens_generated", "prefix_hits",
            "preemptions", "queue_waits", "prefill_chunks")})
    ctx = {"cell": cell, "run": run_, "replica": replica,
           "device": dict(replica["device"]), "trace": replica.get("trace")}
    return report.finish(
        cell, traced, ctx, attempted=len(outcomes), failed=len(failed),
        correct=replica["check"]["ok"] and not any(window_compiles.values()))


def BenchDeployment():
    """Imported late: the class pulls in the program's serving stack."""
    from bench.harness.deployment import BenchLLMDeployment

    return BenchLLMDeployment
