"""Serve benchmark: five probes over the serving plane.

  http_stream   legacy end-to-end probe: continuous-batching deployment
                behind the async HTTP proxy with chunked token streaming
                (req/s + TTFT percentiles).
  engine_fixed  fixed-slot LLMEngine driven directly by N concurrent
                streaming clients (tokens/s, p50/p99 TTFT + ITL), plus
                the engine-side per-phase latency attribution
                (queue_wait / prefill / decode_step means from the
                raytpu_serve_phase_seconds histogram).
  engine_paged  paged KV-cache PagedLLMEngine at EQUAL HBM (same
                KV-token budget as engine_fixed: num_slots*max_len
                tokens carved into blocks) under the same N streams —
                the apples-to-apples claim for the paged engine — with
                the same phase attribution plus KV hit-rate fields
                (block reuse and whole-prefix hit rates, COW copies,
                evictions, preemptions).
  overhead      paired on/off probe for request tracing: the SAME paged
                engine driven with RAY_TPU_SERVE_TRACE_ENABLED toggled
                per run (best-of-N pairs); records the tokens/s cost of
                span recording, expected < 5%.
  chaos         fault-tolerance probe: N concurrent handle-level token
                streams across 2 replicas, one replica SIGKILLed
                mid-run; records the fraction of in-flight streams that
                complete (via resumable-stream failover + recompute)
                and the p99 ITL degradation vs an identical kill-free
                baseline phase.

At stream counts far above the fixed engine's slot count, TTFT is
admission-LIMITED (queueing behind slot admission dominates prefill);
the artifact labels the regime explicitly so percentiles aren't
misread.

A chip belongs to one process at a time.  The engine probes (fixed,
paged, overhead, spec) run the model in THIS process; the cluster probes
(http, chaos, disagg) run it in replica processes, which could not get a
chip this process holds — so on a host with a chip an `--only` set that
mixes the two kinds is refused.  Every probe records the device its
model ran on, asked of the process that ran it.

Usage: python bench_serve.py [--only http,fixed,paged,overhead,chaos]
       [--round 15] [--streams 1024] [--out serve_bench.json]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import threading
import time
import urllib.request


def emit(metric: str, value: float, unit: str) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 4),
                      "unit": unit, "vs_baseline": None}), flush=True)


def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(len(sorted_vals) * q))]


# Which process runs the model: this one, or the replicas a cluster
# probe starts.  disagg does both (a local twin engine checks the
# migrated streams byte for byte).
IN_PROCESS_PROBES = {"fixed", "paged", "overhead", "spec", "disagg"}
CLUSTER_PROBES = {"http", "chaos", "disagg"}


def _replica_device(app: str) -> dict:
    """The device a cluster probe's replica ran on, as the replica
    reports it (this process must not initialise a backend to ask)."""
    from ray_tpu import serve

    return serve.get_app_handle(app).options(
        method_name="runtime_report").remote({}).result(
            timeout=60)["device"]


# ---------------------------------------------------------------------------
# probe: http_stream (legacy end-to-end path)
# ---------------------------------------------------------------------------
def probe_http(args) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    concurrency = args.concurrency or args.num_slots
    ray_tpu.init(num_cpus=4)
    serve.run(
        serve.deployment(LLMDeployment).bind(
            args.model, engine="fixed", num_slots=args.num_slots,
            max_len=args.max_len,
            prefix_cache_size=args.prefix_cache_size),
        name="llm", _http=True, route_prefix="/llm")
    port = serve.http_port()
    url = f"http://127.0.0.1:{port}/llm?stream=1&method=stream"

    # Replica readiness: the LLM replica compiles prefill/decode in its
    # constructor, which can exceed the router's replica-wait budget on a
    # loaded host — poll the controller before timing anything.
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        st = serve.status().get("llm", {})
        if st.get("ready", 0) >= 1:
            break
        time.sleep(1.0)
    else:
        raise RuntimeError(f"llm replicas never became ready: "
                           f"{serve.status()}")

    def one_request(prompt_len: int = 16):
        body = json.dumps({"tokens": list(range(1, prompt_len + 1)),
                           "max_tokens": args.max_tokens}).encode()
        t0 = time.perf_counter()
        resp = urllib.request.urlopen(
            urllib.request.Request(url, data=body), timeout=600)
        resp.readline()
        ttft = time.perf_counter() - t0
        ntok = 1 + sum(1 for _ in resp)
        total = time.perf_counter() - t0
        return ttft, total, ntok

    one_request()   # warmup: trigger prefill/decode compiles
    one_request(64)

    ttfts: list = []
    totals: list = []
    tokens = [0]
    lock = threading.Lock()
    errors = [0]

    def worker(n):
        for _ in range(n):
            try:
                ttft, total, ntok = one_request()
            except Exception:  # noqa: BLE001
                with lock:
                    errors[0] += 1
                continue
            with lock:
                ttfts.append(ttft)
                totals.append(total)
                tokens[0] += ntok

    per = max(1, args.requests // concurrency)
    threads = [threading.Thread(target=worker, args=(per,))
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    device = _replica_device("llm")
    serve.shutdown()
    ray_tpu.shutdown()

    n = len(ttfts)
    if n == 0:
        raise SystemExit("http probe: all requests failed")
    ttfts.sort()
    return {
        "device": device,
        "requests_per_second": {"value": round(n / wall, 2),
                                "unit": "req/s"},
        "ttft_p50_ms": {"value": round(1000 * ttfts[n // 2], 1),
                        "unit": "ms"},
        "ttft_p95_ms": {"value": round(1000 * _pct(ttfts, 0.95), 1),
                        "unit": "ms"},
        "latency_mean_ms": {"value": round(1000 * statistics.mean(totals),
                                           1), "unit": "ms"},
        "tokens_per_second": {"value": round(tokens[0] / wall, 1),
                              "unit": "tokens/s"},
        "errors": errors[0],
        "config": {
            "num_slots": args.num_slots, "max_len": args.max_len,
            "requests": args.requests, "concurrency": concurrency,
            "prefix_cache_size": args.prefix_cache_size,
            "ttft_regime": (
                "admission-free (concurrency <= num_slots): TTFT "
                "measures prefill" if concurrency <= args.num_slots
                else "saturated (concurrency > num_slots): TTFT "
                     "includes slot-admission queueing"),
        },
    }


# ---------------------------------------------------------------------------
# probes: engine_fixed / engine_paged (direct engine, 1k+ streams)
# ---------------------------------------------------------------------------
def _drive_streams(engine, n_streams: int, prompt_len: int,
                   max_tokens: int) -> dict:
    """N concurrent streaming clients against one engine: per-stream
    TTFT + inter-token gaps, zero-drop accounting."""
    lock = threading.Lock()
    ttfts: list = []
    itls: list = []
    tokens = [0]
    errors = [0]
    dropped = [0]

    def client(i: int):
        # Unique prompts (vary by stream) so throughput measures real
        # prefill+decode, not the prefix cache.
        prompt = [(i * 7 + j) % 251 + 1 for j in range(prompt_len)]
        t0 = time.perf_counter()
        last = t0
        got = 0
        gaps = []
        try:
            for _ in engine.generate_stream(prompt,
                                            max_tokens=max_tokens,
                                            timeout=900):
                now = time.perf_counter()
                if got == 0:
                    first = now - t0
                else:
                    gaps.append(now - last)
                last = now
                got += 1
        except Exception as e:  # noqa: BLE001
            from ray_tpu.serve.llm import StreamQueueFullError

            with lock:
                if isinstance(e, StreamQueueFullError):
                    dropped[0] += 1
                else:
                    errors[0] += 1
            return
        with lock:
            tokens[0] += got
            if got:
                ttfts.append(first)
            itls.extend(gaps)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_streams)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    ttfts.sort()
    itls.sort()
    return {
        "streams": n_streams,
        "completed": len(ttfts),
        "errors": errors[0],
        "dropped_streams": dropped[0],
        "wall_s": round(wall, 2),
        "tokens_per_second": {"value": round(tokens[0] / wall, 1),
                              "unit": "tokens/s"},
        "ttft_p50_ms": {"value": round(1000 * (_pct(ttfts, 0.50) or 0), 1),
                        "unit": "ms"},
        "ttft_p99_ms": {"value": round(1000 * (_pct(ttfts, 0.99) or 0), 1),
                        "unit": "ms"},
        "itl_p50_ms": {"value": round(1000 * (_pct(itls, 0.50) or 0), 1),
                       "unit": "ms"},
        "itl_p99_ms": {"value": round(1000 * (_pct(itls, 0.99) or 0), 1),
                       "unit": "ms"},
    }


def _build_params(args):
    import jax

    from ray_tpu.models import configs, init_params

    cfg = configs.get(args.model)
    return cfg, init_params(jax.random.key(0), cfg)


def _serve_hist_snapshot() -> dict:
    """(sum, count) per labelset for the in-process serve latency
    histograms — engines observe TTFT/ITL/phase locally, so diffing two
    snapshots isolates one probe's attribution from the shared
    registry."""
    from ray_tpu.serve import observability

    m = observability.metrics()
    out = {}
    for name in ("phase", "ttft", "itl"):
        _counts, sums, totals = m[name].snapshot()
        out[name] = {key: (sums[key], totals[key]) for key in sums}
    return out


def _latency_attribution(before: dict, after: dict) -> dict:
    """Engine-side per-phase breakdown between two snapshots: mean ms +
    sample count for each phase, plus histogram-level TTFT/ITL means
    (the same series `ray-tpu serve status` reads cluster-wide)."""
    def delta(name):
        rows = {}
        for key, (s1, c1) in after.get(name, {}).items():
            s0, c0 = before.get(name, {}).get(key, (0.0, 0))
            ds, dc = s1 - s0, c1 - c0
            if dc > 0:
                rows[key] = (ds, dc)
        return rows

    phases = {}
    for key, (ds, dc) in delta("phase").items():
        phase = dict(key).get("phase", "?")
        s, c = phases.get(phase, (0.0, 0))
        phases[phase] = (s + ds, c + dc)
    out = {"phase": {p: {"mean_ms": round(1000 * s / c, 3), "count": c}
                     for p, (s, c) in sorted(phases.items())}}
    for name, label in (("ttft", "ttft_mean_ms"), ("itl", "itl_mean_ms")):
        s = sum(ds for ds, _ in delta(name).values())
        c = sum(dc for _, dc in delta(name).values())
        if c:
            out[label] = round(1000 * s / c, 3)
    return out


def _kv_hit_rates(stats: dict) -> dict:
    """KV-cache effectiveness fields from a paged engine's cumulative
    stats: block-level reuse (allocator lookups) and whole-prefix hits
    (engine-level prefill skips)."""
    out = {}
    for hits_k, miss_k, rate_k in (
            ("reuse_hits", "reuse_misses", "block_reuse_hit_rate"),
            ("prefix_hits", "prefix_misses", "prefix_hit_rate")):
        h, ms = stats.get(hits_k, 0), stats.get(miss_k, 0)
        out[hits_k] = h
        out[miss_k] = ms
        out[rate_k] = round(h / (h + ms), 4) if h + ms else None
    for k in ("cow_copies", "evictions", "preemptions",
              "alloc_failures"):
        out[k] = stats.get(k, 0)
    return out


def probe_engine_fixed(args) -> dict:
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = _build_params(args)
    eng = LLMEngine(cfg, params, num_slots=args.num_slots,
                    max_len=args.max_len, prefix_cache_size=0)
    eng.generate([1, 2, 3], max_tokens=2, timeout=300)  # warmup/compile
    before = _serve_hist_snapshot()
    out = _drive_streams(eng, args.streams, args.prompt_len,
                         args.max_tokens)
    out["latency_attribution"] = _latency_attribution(
        before, _serve_hist_snapshot())
    stats = eng.engine_stats()
    eng.shutdown()
    out["config"] = {
        "engine": "fixed", "num_slots": args.num_slots,
        "max_len": args.max_len,
        "kv_hbm_tokens": args.num_slots * args.max_len,
        "ttft_regime": "admission-limited (streams >> num_slots): TTFT "
                       "is dominated by slot-admission queueing",
    }
    out["engine_stats"] = {k: stats[k] for k in
                           ("requests", "completed", "tokens_generated")}
    return out


def probe_engine_paged(args) -> dict:
    from ray_tpu.core.config import get_config
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params = _build_params(args)
    bs = args.block_size or get_config().kv_block_size
    # EQUAL HBM: same KV-token budget as the fixed probe, carved into
    # blocks (+1 for the reserved null block).
    num_blocks = (args.num_slots * args.max_len) // bs + 1
    eng = PagedLLMEngine(cfg, params, num_slots=args.paged_width,
                         max_len=args.max_len, block_size=bs,
                         num_blocks=num_blocks,
                         prefill_chunk=args.prefill_chunk)
    eng.warmup()   # compile all width/chunk tiers outside the timing
    eng.generate([1, 2, 3], max_tokens=2, timeout=300)
    before = _serve_hist_snapshot()
    out = _drive_streams(eng, args.streams, args.prompt_len,
                         args.max_tokens)
    out["latency_attribution"] = _latency_attribution(
        before, _serve_hist_snapshot())
    stats = eng.engine_stats()
    out["kv_cache"] = _kv_hit_rates(stats)
    eng.shutdown()
    out["config"] = {
        "engine": "paged", "decode_width": args.paged_width,
        "max_len": args.max_len, "block_size": bs,
        "num_blocks": num_blocks,
        "kv_hbm_tokens": (num_blocks - 1) * bs,
        "prefill_chunk": args.prefill_chunk,
        "ttft_regime": "admission-limited (streams >> decode width): "
                       "TTFT is dominated by block-pool admission "
                       "queueing",
    }
    out["engine_stats"] = {
        k: stats[k] for k in
        ("requests", "completed", "tokens_generated", "reuse_hits",
         "cow_copies", "prefill_chunks", "queue_waits", "blocks_total")}
    return out


# ---------------------------------------------------------------------------
# probe: trace overhead (paired on/off runs of the SAME engine)
# ---------------------------------------------------------------------------
def probe_trace_overhead(args) -> dict:
    """Tokens/s cost of request tracing: the same warmed paged engine is
    driven with RAY_TPU_SERVE_TRACE_ENABLED toggled per run (the kill
    switch zeroes every span while phase/TTFT metrics record in both
    modes, so the pair isolates span recording).  Best-of-N pairs damp
    scheduler noise; the serve-trace acceptance bar is < 5%."""
    import os

    from ray_tpu.core import config as cfg_mod
    from ray_tpu.core.config import get_config
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params = _build_params(args)
    bs = args.block_size or get_config().kv_block_size
    num_blocks = (args.num_slots * args.max_len) // bs + 1
    eng = PagedLLMEngine(cfg, params, num_slots=args.paged_width,
                         max_len=args.max_len, block_size=bs,
                         num_blocks=num_blocks,
                         prefill_chunk=args.prefill_chunk)
    eng.warmup()
    eng.generate([1, 2, 3], max_tokens=2, timeout=300)
    saved = os.environ.get("RAY_TPU_SERVE_TRACE_ENABLED")

    def run_once(enabled: bool) -> float:
        os.environ["RAY_TPU_SERVE_TRACE_ENABLED"] = \
            "1" if enabled else "0"
        cfg_mod.reset_config()
        r = _drive_streams(eng, args.overhead_streams, args.prompt_len,
                           args.max_tokens)
        if r["errors"]:
            raise SystemExit(f"overhead probe: {r['errors']} errors")
        return r["tokens_per_second"]["value"]

    pairs = []
    try:
        for i in range(args.overhead_pairs):
            # Alternate order inside the pair so warm-cache drift never
            # systematically favors one mode.
            if i % 2 == 0:
                on = run_once(True)
                off = run_once(False)
            else:
                off = run_once(False)
                on = run_once(True)
            pairs.append({"traced_tokens_per_s": on,
                          "untraced_tokens_per_s": off})
    finally:
        if saved is None:
            os.environ.pop("RAY_TPU_SERVE_TRACE_ENABLED", None)
        else:
            os.environ["RAY_TPU_SERVE_TRACE_ENABLED"] = saved
        cfg_mod.reset_config()
        eng.shutdown()
    best_on = max(p["traced_tokens_per_s"] for p in pairs)
    best_off = max(p["untraced_tokens_per_s"] for p in pairs)
    overhead_pct = round(100.0 * (best_off - best_on) / best_off, 2) \
        if best_off else None
    return {
        "pairs": pairs,
        "traced_tokens_per_second": best_on,
        "untraced_tokens_per_second": best_off,
        "overhead_pct": overhead_pct,
        "within_5pct": (overhead_pct is not None
                        and overhead_pct < 5.0),
        "config": {
            "engine": "paged", "decode_width": args.paged_width,
            "streams": args.overhead_streams,
            "max_tokens": args.max_tokens,
            "pairs": args.overhead_pairs,
            "method": "best-of-N paired runs on one warmed engine, "
                      "RAY_TPU_SERVE_TRACE_ENABLED toggled per run "
                      "(spans off; phase/TTFT metrics record in both "
                      "modes)",
        },
    }


# ---------------------------------------------------------------------------
# probe: rails (per-decode-step dispatch overhead, compiled vs RPC loop)
# ---------------------------------------------------------------------------
def probe_rails(args) -> dict:
    """Per-decode-step dispatch overhead of the serve pull plane, two
    regimes over the identical stamping deployment:

    *flood* (``per_step_us``, the headline — same flat-out per-iter
    methodology as BENCH_CORE's actor_calls/compiled-DAG numbers): the
    producer yields back-to-back, so the number is the steady-state
    transport work the plane adds per emitted step with no idle-wait
    mixed in.

    *paced* (``delivery_*_us``): one stamped item per
    ``--rails-step-ms`` (a decode-tick stand-in); producer-yield ->
    consumer-receipt latency per item.  Stamps are ``perf_counter``
    (CLOCK_MONOTONIC, system-wide on Linux, so comparable across the
    replica/handle processes on one host); this regime is dominated by
    wakeup/poll granularity (a 1us time.sleep really costs ~60us), not
    per-step work, and is reported for ITL context.

    Arms: *compiled* (rails on — frames ride the shm channel ring
    written by the replica's pinned pump) and *rpc_loop*
    (RAY_TPU_SERVE_RAILS_ENABLED kill switch thrown handle-side, so
    every pull is a stream_next actor round trip).  Best-of-N damps
    scheduler noise; the rails acceptance bar is compiled
    ``per_step_us`` < 50us."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import get_config

    n_paced = args.rails_steps
    n_flood = max(10 * args.rails_steps, 1000)
    step_s = args.rails_step_ms / 1e3
    ray_tpu.init(num_cpus=4)

    @serve.deployment(num_replicas=1)
    def metronome(request):
        import time as _t
        step = float(request["step_s"])
        for _ in range(int(request["n"])):
            if step:
                _t.sleep(step)
            yield {"t": _t.perf_counter()}

    handle = serve.run(metronome.bind(), name="rails_bench")
    cfg = get_config()
    saved = cfg.serve_rails_enabled
    arms: dict = {}
    try:
        for mode, enabled in (("compiled", True), ("rpc_loop", False)):
            cfg.serve_rails_enabled = enabled
            # warm the admission path (and the ring setup when enabled)
            list(handle.remote_streaming({"n": 4, "step_s": 0.0}))
            best = None
            for _ in range(args.rails_pairs):
                # flood: steady-state per-step transport work
                resp = handle.remote_streaming(
                    {"n": n_flood, "step_s": 0.0})
                t_first = got = None
                for got, _item in enumerate(resp):
                    if t_first is None:
                        t_first = time.perf_counter()
                per_step = (time.perf_counter() - t_first) / got
                assert got == n_flood - 1, f"{mode}: short flood"
                assert resp.rails_used == enabled, \
                    f"{mode}: rails_used={resp.rails_used}"
                # paced: per-item delivery latency at decode-tick pace
                lats = []
                resp = handle.remote_streaming(
                    {"n": n_paced, "step_s": step_s})
                for item in resp:
                    lats.append(time.perf_counter() - item["t"])
                assert len(lats) == n_paced, f"{mode}: short stream"
                lats.sort()
                run = {
                    "per_step_us": round(1e6 * per_step, 2),
                    "delivery_p50_us": round(
                        1e6 * (_pct(lats, 0.50) or 0), 1),
                    "delivery_p99_us": round(
                        1e6 * (_pct(lats, 0.99) or 0), 1),
                    "delivery_mean_us": round(
                        1e6 * sum(lats) / len(lats), 1),
                }
                if best is None or run["per_step_us"] < best["per_step_us"]:
                    best = run
            best["rails_attached"] = enabled
            arms[mode] = best
    finally:
        cfg.serve_rails_enabled = saved
        serve.shutdown()
        ray_tpu.shutdown()

    comp = arms["compiled"]["per_step_us"]
    rpc = arms["rpc_loop"]["per_step_us"]
    return {
        "compiled": arms["compiled"],
        "rpc_loop": arms["rpc_loop"],
        "per_step_dispatch_speedup_x": round(rpc / comp, 1) if comp
        else None,
        "pass_50us": comp < 50.0,
        "config": {
            "flood_steps": n_flood, "paced_steps": n_paced,
            "step_ms": args.rails_step_ms, "pairs": args.rails_pairs,
            "method": "flood = back-to-back production, wall between "
                      "first and last receipt / steps (steady-state "
                      "per-step transport work, BENCH_CORE per-iter "
                      "methodology); paced = stamped yield->receipt "
                      "latency at decode-tick pace; compiled = shm "
                      "ring frames from the replica's pinned rails "
                      "pump, rpc_loop = per-pull stream_next actor "
                      "round trips (single-call RPC dispatch on this "
                      "plane is the ~5.7ms/iter BENCH_CORE "
                      "actor_calls baseline)",
        },
    }


# ---------------------------------------------------------------------------
# probe: spec (paired speculation on/off tokens/s on the paged engine)
# ---------------------------------------------------------------------------
def probe_spec(args) -> dict:
    """Paired spec-decode on/off tokens/s on the paged engine at the
    same KV/HBM shape (only ``speculation_k`` differs): prompt-lookup
    n-gram drafting + width-K paged verify vs plain burst decode, on a
    repetitive greedy workload the drafter can mine.  Speculation is
    exact, so the two arms' outputs must be bit-identical — the probe
    asserts it.  Acceptance: >= 1.5x tokens/s on the draftable
    workload."""
    from ray_tpu.core.config import get_config
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params = _build_params(args)
    bs = args.block_size or get_config().kv_block_size
    num_slots = 4
    num_blocks = (num_slots * args.max_len) // bs + 1
    # A prompt whose greedy continuation stays n-gram-minable (verified:
    # the tiny model's continuation of this one is piecewise-periodic
    # almost immediately, so the drafter keeps proposing).  max_burst=1
    # in BOTH arms is the autoregressive serving baseline — one token
    # per engine tick — that speculative decoding is defined against.
    prompt = [100, 200, 100, 200, 100, 200, 100, 200]

    def run_arm(spec_k: int):
        eng = PagedLLMEngine(cfg, params, num_slots=num_slots,
                             max_len=args.max_len, block_size=bs,
                             num_blocks=num_blocks, max_burst=1,
                             prefix_sharing=False, speculation_k=spec_k,
                             speculation_ngram=args.spec_ngram)
        eng.warmup()   # compiles decode AND verify tiers outside timing
        eng.generate(prompt, max_tokens=8, timeout=300)
        best_tps, toks = 0.0, None
        for _ in range(args.spec_pairs):
            t0 = time.perf_counter()
            toks = eng.generate(prompt, max_tokens=args.spec_tokens,
                                timeout=600)
            best_tps = max(best_tps,
                           len(toks) / (time.perf_counter() - t0))
        stats = eng.engine_stats()
        eng.shutdown()
        return round(best_tps, 1), toks, stats

    plain_tps, plain_toks, _ = run_arm(0)
    spec_tps, spec_toks, st = run_arm(args.spec_k)
    assert spec_toks == plain_toks, \
        "speculative output diverged from plain greedy"
    proposed = st.get("spec_proposed", 0)
    accepted = st.get("spec_accepted", 0)
    speedup = round(spec_tps / plain_tps, 2) if plain_tps else None
    return {
        "plain_tokens_per_second": plain_tps,
        "spec_tokens_per_second": spec_tps,
        "speedup": speedup,
        "pass_1_5x": speedup is not None and speedup >= 1.5,
        "outputs_identical": True,
        "spec_proposed": proposed,
        "spec_accepted": accepted,
        "spec_accept_rate": round(accepted / proposed, 4) if proposed
        else None,
        "config": {
            "engine": "paged", "num_slots": num_slots,
            "max_len": args.max_len, "block_size": bs,
            "num_blocks": num_blocks, "max_burst": 1,
            "speculation_k": args.spec_k,
            "speculation_ngram": args.spec_ngram,
            "max_tokens": args.spec_tokens, "pairs": args.spec_pairs,
            "workload": "repetitive greedy continuation (draftable by "
                        "prompt-lookup); arms differ ONLY in the "
                        "speculation knobs, both decode one tick per "
                        "token otherwise, outputs asserted "
                        "bit-identical",
        },
    }


# ---------------------------------------------------------------------------
# probe: chaos (mid-run replica kill under concurrent streams)
# ---------------------------------------------------------------------------
def probe_chaos(args) -> dict:
    """Two identical phases of N concurrent handle-level token streams
    over a 2-replica LLM deployment; phase two SIGKILLs one replica once
    the run is underway. Streams on the dead replica fail over via the
    handle's resume protocol (prompt + emitted tokens recomputed on the
    survivor), so the headline numbers are the recovered-stream fraction
    and how much the failover + recompute stretches tail ITL."""
    import os
    import signal

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import LLMDeployment

    n_streams = args.chaos_streams
    max_tokens = args.max_tokens
    ray_tpu.init(num_cpus=4)
    app = "llm_chaos"
    serve.run(
        serve.deployment(LLMDeployment, num_replicas=2).bind(
            args.model, engine="fixed", num_slots=args.num_slots,
            max_len=args.max_len),
        name=app)
    controller = get_or_create_controller()
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        st = serve.status().get(app, {})
        if st.get("ready", 0) >= 2:
            break
        time.sleep(1.0)
    else:
        raise RuntimeError(f"chaos replicas never ready: {serve.status()}")

    handle = serve.get_app_handle(app).options(method_name="stream")
    # warmup: compile prefill/decode on both replicas
    for _ in range(2):
        list(handle.remote_streaming(
            {"tokens": [1, 2, 3], "max_tokens": 2}))

    def drive(phase_kill: bool) -> dict:
        lock = threading.Lock()
        itls: list = []
        completed = [0]
        resumed = [0]
        errors = [0]
        tokens_seen = [0]
        underway = threading.Event()

        def client(i: int):
            prompt = [(i * 7 + j) % 251 + 1 for j in range(16)]
            resp = handle.remote_streaming(
                {"tokens": prompt, "max_tokens": max_tokens})
            last = None
            got = 0
            gaps = []
            try:
                for _ in resp:
                    now = time.perf_counter()
                    if last is not None:
                        gaps.append(now - last)
                    last = now
                    got += 1
                    with lock:
                        tokens_seen[0] += 1
                        if tokens_seen[0] >= n_streams:
                            underway.set()
            except Exception:  # noqa: BLE001
                with lock:
                    errors[0] += 1
                return
            with lock:
                itls.extend(gaps)
                if got == max_tokens:
                    completed[0] += 1
                if getattr(resp, "resumes", 0):
                    resumed[0] += 1

        def killer():
            # Wait until ~one token per stream has flowed, then SIGKILL
            # one replica process (crash, not graceful drain).
            if not underway.wait(timeout=120):
                return
            routing = ray_tpu.get(
                controller.get_routing.remote(app), timeout=30)
            victim = sorted(routing["replicas"])[0]
            try:
                h = ray_tpu.get_actor(victim)
                pid = ray_tpu.get(h.getpid.remote(), timeout=10)
                os.kill(pid, signal.SIGKILL)
            except Exception:  # noqa: BLE001  fallback: actor-level kill
                ray_tpu.kill(ray_tpu.get_actor(victim))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_streams)]
        kt = (threading.Thread(target=killer, daemon=True)
              if phase_kill else None)
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        if kt:
            kt.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        itls.sort()
        return {
            "streams": n_streams,
            "completed": completed[0],
            "completed_fraction": round(completed[0] / n_streams, 4),
            "resumed_streams": resumed[0],
            "errors": errors[0],
            "wall_s": round(wall, 2),
            "itl_p50_ms": {"value": round(
                1000 * (_pct(itls, 0.50) or 0), 1), "unit": "ms"},
            "itl_p99_ms": {"value": round(
                1000 * (_pct(itls, 0.99) or 0), 1), "unit": "ms"},
        }

    baseline = drive(phase_kill=False)
    chaos = drive(phase_kill=True)
    device = _replica_device(app)
    serve.shutdown()
    ray_tpu.shutdown()

    base_p99 = baseline["itl_p99_ms"]["value"] or 1e-9
    return {
        "device": device,
        "baseline": baseline,
        "replica_kill": chaos,
        "recovered_fraction": chaos["completed_fraction"],
        "itl_p99_degradation_x": round(
            chaos["itl_p99_ms"]["value"] / base_p99, 2),
        "config": {
            "num_replicas": 2, "engine": "fixed",
            "num_slots": args.num_slots, "max_len": args.max_len,
            "max_tokens": max_tokens, "chaos_streams": n_streams,
            "kill": "SIGKILL one of 2 replicas once >= 1 token/stream "
                    "has flowed; streams resume on the survivor via "
                    "prompt+emitted recompute (exactly-once)",
        },
    }


# ---------------------------------------------------------------------------
# probe: disagg (prefix-registry reuse, prefill/decode split, live KV
# migration on drain)
# ---------------------------------------------------------------------------
def probe_disagg(args) -> dict:
    """Three phases over the disaggregated serving plane:

    (a) prefix reuse — K shared long system prefixes over 2 paged
        replicas; the cluster prefix registry routes repeats to the
        replica already holding the blocks, so aggregate tokens/s beats
        a prefix-sharing-off baseline (target: >= 30%);
    (b) prefill/decode split — a mixed long+short workload on one
        replica with dedicated prefill actors vs unified: long-prompt
        p99 TTFT improves while short-stream p99 ITL holds (<= 10%
        regression);
    (c) live migration — drain a replica mid-run; its streams resume
        warm on the survivor (migrate counters, not recompute) with
        byte-identical output vs a local reference engine."""
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import LLMDeployment, PagedLLMEngine

    BS = 4
    # Two prefill actors: the split-phase long prompts hash across the
    # pool instead of serializing behind a single actor.  Env knobs
    # inherit into the worker processes spawned under this init.
    os.environ["RAY_TPU_SERVE_DISAGG_PREFILL_ACTORS"] = "2"
    ray_tpu.init(num_cpus=4)
    controller = get_or_create_controller()

    def wait_ready(app, n, timeout=300.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if serve.status().get(app, {}).get("ready", 0) >= n:
                return
            time.sleep(1.0)
        raise RuntimeError(f"{app} replicas never ready: {serve.status()}")

    def stream_all(handle, jobs, on_token=None, workers=0):
        """Run every (key, request) job; returns per-key dicts of
        tokens, ttft, itl gaps, resumes.  workers=0: one thread per job
        (full concurrency); workers=N: a bounded pool so per-thread
        overhead doesn't drown the engine-side effect under test."""
        out = {}
        lock = threading.Lock()
        queue = list(jobs)

        def client(key, req):
            t0 = time.perf_counter()
            resp = handle.remote_streaming(req)
            toks, gaps, last, ttft = [], [], None, None
            for item in resp:
                now = time.perf_counter()
                if ttft is None:
                    ttft = now - t0
                if last is not None:
                    gaps.append(now - last)
                last = now
                toks.append(item["token"])
                if on_token:
                    on_token(key)
            with lock:
                out[key] = {"tokens": toks, "ttft": ttft, "itls": gaps,
                            "resumes": getattr(resp, "resumes", 0)}

        def pool_worker():
            while True:
                with lock:
                    if not queue:
                        return
                    key, req = queue.pop(0)
                client(key, req)

        if workers:
            threads = [threading.Thread(target=pool_worker)
                       for _ in range(workers)]
        else:
            threads = [threading.Thread(target=client, args=(k, r))
                       for k, r in jobs]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out, time.perf_counter() - t0

    # -- phase A: cross-replica prefix reuse vs sharing-off baseline ----
    # Long shared system prompts + tiny decode make the chunked prefill
    # the dominant per-request cost — exactly the work a registry hit
    # skips.  A bounded worker pool keeps client-thread overhead from
    # drowning the engine-side difference.
    n_prefixes = 4
    prefix_len = 96          # aligned shared system prompt
    reps = args.disagg_reps  # measured requests per prefix
    a_max_tokens = 4

    def reuse_run(app, sharing: bool) -> dict:
        serve.run(
            serve.deployment(LLMDeployment, num_replicas=2).bind(
                args.model, engine="paged", num_slots=8, max_len=128,
                block_size=BS, prefill_chunk=8,
                prefix_sharing=sharing),
            name=app)
        wait_ready(app, 2)
        handle = serve.get_app_handle(app).options(method_name="stream")

        def prompt(p, r):
            sysp = [(p * 37 + j) % 251 + 1 for j in range(prefix_len)]
            return sysp + [(r * 13 + j) % 251 + 1 for j in range(4)]

        # Warm: requests per prefix register + publish each chain and
        # flush compiles on BOTH replicas (pow-2 routing spreads the
        # rounds); then give the gauge->syncer->controller pipeline a
        # beat to materialize the registry.
        for round_ in range(3):
            for p in range(n_prefixes):
                list(handle.remote_streaming(
                    {"tokens": prompt(p, 900 + round_),
                     "max_tokens": a_max_tokens}))
        time.sleep(3.0 if sharing else 0.5)
        jobs = [((p, r), {"tokens": prompt(p, r),
                          "max_tokens": a_max_tokens})
                for p in range(n_prefixes) for r in range(reps)]
        res, wall = stream_all(handle, jobs, workers=8)
        total_tokens = sum(len(v["tokens"]) for v in res.values())
        prefix_hits = 0
        routing = ray_tpu.get(controller.get_routing.remote(app),
                              timeout=30)
        for name in routing["replicas"]:
            try:
                st = ray_tpu.get(
                    ray_tpu.get_actor(name).handle_request.remote(
                        "stats", (), {}), timeout=30)
                prefix_hits += st.get("prefix_hits", 0)
            except Exception:  # noqa: BLE001
                pass
        serve.delete(app)
        return {"tokens_per_second": round(total_tokens / wall, 1),
                "wall_s": round(wall, 2), "streams": len(jobs),
                "total_tokens": total_tokens,
                "engine_prefix_hits": prefix_hits}

    baseline_a = reuse_run("disagg_reuse_off", sharing=False)
    registry_a = reuse_run("disagg_reuse_on", sharing=True)
    base_tps = baseline_a["tokens_per_second"] or 1e-9
    gain_pct = round(100.0 * (registry_a["tokens_per_second"] - base_tps)
                     / base_tps, 1)

    # -- phase B: prefill/decode split vs unified under mixed load -----
    n_short = 8
    n_long = 4
    long_len = 96            # >= serve_disagg_prompt_threshold (64)

    def split_run(app, disagg: bool) -> dict:
        serve.run(
            serve.deployment(LLMDeployment).bind(
                args.model, engine="paged", num_slots=16, max_len=128,
                block_size=BS, prefill_chunk=16, disagg=disagg),
            name=app)
        wait_ready(app, 1)
        handle = serve.get_app_handle(app).options(method_name="stream")
        # Warmup compiles the replica's decode/prefill tiers and, for
        # disagg, spawns the prefill pool.  Several distinct long
        # prompts so the first-block-digest routing touches (and
        # compiles) every actor in the pool; identical warmup on the
        # unified run keeps the comparison fair.
        for w in range(6):
            list(handle.remote_streaming(
                {"tokens": [(w * 29 + j) % 251 + 1
                            for j in range(long_len)],
                 "max_tokens": 2}))
        list(handle.remote_streaming(
            {"tokens": [1, 2, 3, 4], "max_tokens": 2}))
        jobs = [(("short", i),
                 {"tokens": [(i * 7 + j) % 251 + 1 for j in range(8)],
                  "max_tokens": 24}) for i in range(n_short)]
        jobs += [(("long", i),
                  {"tokens": [(i * 11 + j) % 251 + 1
                              for j in range(long_len)],
                   "max_tokens": 4}) for i in range(n_long)]
        res, wall = stream_all(handle, jobs)
        short_itls = sorted(g for k, v in res.items()
                            for g in v["itls"] if k[0] == "short")
        long_ttfts = sorted(v["ttft"] for k, v in res.items()
                            if k[0] == "long" and v["ttft"] is not None)
        serve.delete(app)
        return {
            "short_itl_p99_ms": round(
                1000 * (_pct(short_itls, 0.99) or 0), 1),
            "long_ttft_p99_ms": round(
                1000 * (_pct(long_ttfts, 0.99) or 0), 1),
            "wall_s": round(wall, 2),
        }

    def split_pass(u, d):
        impr = (u["long_ttft_p99_ms"] or 1e-9) \
            / (d["long_ttft_p99_ms"] or 1e-9)
        reg = 100.0 * (d["short_itl_p99_ms"]
                       - u["short_itl_p99_ms"]) \
            / (u["short_itl_p99_ms"] or 1e-9)
        return impr > 1.0 and reg <= 10.0

    unified_b = split_run("disagg_split_off", disagg=False)
    disagg_b = split_run("disagg_split_on", disagg=True)
    if not split_pass(unified_b, disagg_b):
        # Scheduling jitter (a compile or GC landing inside the short
        # measured window) can sink one attempt; a single rerun with
        # the now-warm detached prefill pool keeps the probe honest.
        unified_b = split_run("disagg_split_off2", disagg=False)
        disagg_b = split_run("disagg_split_on2", disagg=True)
    ttft_impr = round(
        (unified_b["long_ttft_p99_ms"] or 1e-9)
        / (disagg_b["long_ttft_p99_ms"] or 1e-9), 2)
    itl_reg_pct = round(
        100.0 * (disagg_b["short_itl_p99_ms"]
                 - unified_b["short_itl_p99_ms"])
        / (unified_b["short_itl_p99_ms"] or 1e-9), 1)

    # -- phase C: live KV migration on drain ---------------------------
    # The drain must land while streams still hold live decode slots
    # (a finished slot has nothing to export), so it fires as soon as
    # every stream has produced a couple of tokens and the token budget
    # is large enough that the engine can't have finished.
    app = "disagg_drain"
    n_streams = 6
    drain_max_tokens = 96

    def c_prompt(i):
        return [(i * 17 + j) % 251 + 1 for j in range(24)]

    def migration_run() -> dict:
        serve.run(
            serve.deployment(LLMDeployment, num_replicas=2).bind(
                args.model, engine="paged", num_slots=8, max_len=128,
                block_size=BS, prefill_chunk=8),
            name=app)
        wait_ready(app, 2)
        handle = serve.get_app_handle(app).options(method_name="stream")
        list(handle.remote_streaming(
            {"tokens": [1, 2, 3], "max_tokens": 2}))

        seen = {i: 0 for i in range(n_streams)}
        fired = threading.Event()
        lock = threading.Lock()

        def on_token(key):
            with lock:
                seen[key] += 1
                if all(v >= 2 for v in seen.values()):
                    fired.set()

        drained = []
        tickets = [0]

        def drainer():
            if not fired.wait(timeout=120):
                return
            routing = ray_tpu.get(controller.get_routing.remote(app),
                                  timeout=30)
            for name in sorted(routing["replicas"]):
                try:
                    st = ray_tpu.get(
                        ray_tpu.get_actor(name).stats.remote(),
                        timeout=10)
                    if st["streams"] > 0:
                        r = ray_tpu.get(
                            ray_tpu.get_actor(name).drain.remote(
                                timeout_s=10), timeout=15)
                        tickets[0] = r.get("migrated_tickets", 0)
                        drained.append(name)
                        return
                except Exception:  # noqa: BLE001
                    continue

        dt = threading.Thread(target=drainer, daemon=True)
        dt.start()
        jobs = [(i, {"tokens": c_prompt(i),
                     "max_tokens": drain_max_tokens})
                for i in range(n_streams)]
        res, _wall = stream_all(handle, jobs, on_token=on_token)
        dt.join(timeout=15)

        resumed = sum(1 for v in res.values() if v["resumes"])
        migrated_blocks = 0
        routing = ray_tpu.get(controller.get_routing.remote(app),
                              timeout=30)
        for name in routing["replicas"]:
            if name in drained:
                continue
            try:
                st = ray_tpu.get(
                    ray_tpu.get_actor(name).handle_request.remote(
                        "stats", (), {}), timeout=30)
                migrated_blocks += st.get("migrated_blocks", 0)
            except Exception:  # noqa: BLE001
                pass
        serve.delete(app)
        return {"res": res, "drained": drained, "resumed": resumed,
                "tickets": tickets[0],
                "migrated_blocks": migrated_blocks}

    mig = migration_run()
    if mig["migrated_blocks"] == 0:
        # The drain/decode race can finish a stream before export; one
        # retry keeps the probe honest without hiding a real failure.
        mig = migration_run()

    # Byte-identity: greedy decode is deterministic, so every stream
    # must match a local reference engine with the same cfg/seed.
    cfg = configs.get(args.model)
    ref_eng = PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg),
                             num_slots=4, max_len=128, block_size=BS,
                             prefill_chunk=8)
    identical = True
    for i in range(n_streams):
        ref = ref_eng.generate(c_prompt(i), max_tokens=drain_max_tokens,
                               timeout=300)
        if mig["res"][i]["tokens"] != ref:
            identical = False
    ref_eng.shutdown()
    device = _replica_device(app)
    serve.shutdown()
    ray_tpu.shutdown()

    return {
        "device": device,
        "prefix_reuse": {
            "baseline_sharing_off": baseline_a,
            "registry_on": registry_a,
            "gain_pct": gain_pct,
            "pass_30pct": gain_pct >= 30.0,
        },
        "split": {
            "unified": unified_b,
            "disagg": disagg_b,
            "long_ttft_p99_improvement_x": ttft_impr,
            "short_itl_p99_regression_pct": itl_reg_pct,
            "pass": ttft_impr > 1.0 and itl_reg_pct <= 10.0,
        },
        "drain_migration": {
            "drained_replica": mig["drained"],
            "resumed_streams": mig["resumed"],
            "migrated_tickets": mig["tickets"],
            "migrated_blocks": mig["migrated_blocks"],
            "byte_identical": identical,
            "pass": (mig["resumed"] >= 1 and mig["migrated_blocks"] > 0
                     and identical),
        },
        "config": {
            "model": args.model, "block_size": BS,
            "prefix_reuse": {
                "num_replicas": 2, "prefixes": n_prefixes,
                "prefix_len": prefix_len, "reps_per_prefix": reps,
                "max_tokens": a_max_tokens},
            "split": {"num_replicas": 1, "short_streams": n_short,
                      "long_streams": n_long, "long_len": long_len},
            "drain": {"num_replicas": 2, "streams": n_streams,
                      "max_tokens": drain_max_tokens,
                      "drain": "graceful drain of the serving replica "
                               "once every stream has >= 2 tokens; "
                               "streams resume warm from migrated KV "
                               "blocks on the survivor"},
        },
    }


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--only", default="http,fixed,paged,overhead,chaos",
                    help="comma-set of probes: http,fixed,paged,"
                         "overhead,chaos,disagg,rails,spec")
    ap.add_argument("--round", type=int, default=15,
                    help="bench round number recorded in the artifact")
    ap.add_argument("--out", default=None,
                    help="write the artifact JSON here")
    # http probe knobs (legacy)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=None,
                    help="http probe: default num-slots "
                         "(admission-free TTFT)")
    ap.add_argument("--prefix-cache-size", type=int, default=0)
    # shared engine shape (the equal-HBM budget)
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-tokens", type=int, default=16)
    # engine probe knobs
    ap.add_argument("--streams", type=int, default=1024)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--paged-width", type=int, default=64,
                    help="paged engine decode width (slots)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="0: RAY_TPU_KV_BLOCK_SIZE / config default")
    ap.add_argument("--prefill-chunk", type=int, default=128)
    # trace-overhead probe knobs
    ap.add_argument("--overhead-streams", type=int, default=256,
                    help="streams per run in the trace on/off probe")
    ap.add_argument("--overhead-pairs", type=int, default=3,
                    help="paired on/off runs (best-of damping)")
    # chaos probe knobs
    ap.add_argument("--chaos-streams", type=int, default=256,
                    help="concurrent streams in the replica-kill probe")
    # rails probe knobs
    ap.add_argument("--rails-steps", type=int, default=300,
                    help="metronome items per run in the rails probe")
    ap.add_argument("--rails-step-ms", type=float, default=2.0,
                    help="metronome production interval (a decode "
                         "tick stand-in)")
    ap.add_argument("--rails-pairs", type=int, default=3,
                    help="runs per arm (best-of damping)")
    # spec probe knobs
    ap.add_argument("--spec-k", type=int, default=6,
                    help="draft length for the spec-decode probe")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup n-gram for the spec-decode "
                         "probe")
    ap.add_argument("--spec-tokens", type=int, default=192,
                    help="greedy continuation length per spec run")
    ap.add_argument("--spec-pairs", type=int, default=3,
                    help="runs per arm (best-of damping)")
    # disagg probe knobs
    ap.add_argument("--disagg-reps", type=int, default=12,
                    help="measured requests per shared prefix in the "
                         "disagg prefix-reuse phase")
    args = ap.parse_args()

    only = {p.strip() for p in args.only.split(",") if p.strip()}
    from ray_tpu.core.distributed.resources import probe_tpu_count
    from ray_tpu.util import compile_cache

    if (probe_tpu_count() and only & IN_PROCESS_PROBES
            and only & CLUSTER_PROBES):
        ap.error(
            f"this host has a chip, and a chip belongs to one process: "
            f"--only {args.only} mixes probes that run the model in this "
            f"process ({sorted(only & IN_PROCESS_PROBES)}) with probes "
            f"whose replicas need the same chip "
            f"({sorted(only & CLUSTER_PROBES)}); run them in separate "
            f"invocations")
    compile_cache.configure()
    probes: dict = {}
    if "fixed" in only:
        probes["engine_fixed"] = probe_engine_fixed(args)
        emit("serve_fixed_tokens_per_second",
             probes["engine_fixed"]["tokens_per_second"]["value"],
             "tokens/s")
    if "paged" in only:
        probes["engine_paged"] = probe_engine_paged(args)
        emit("serve_paged_tokens_per_second",
             probes["engine_paged"]["tokens_per_second"]["value"],
             "tokens/s")
    if "overhead" in only:
        probes["trace_overhead"] = probe_trace_overhead(args)
        emit("serve_trace_overhead_pct",
             probes["trace_overhead"]["overhead_pct"], "%")
    if "spec" in only:
        probes["spec_decode"] = probe_spec(args)
        emit("serve_spec_speedup",
             probes["spec_decode"]["speedup"], "x")
        emit("serve_spec_accept_rate",
             probes["spec_decode"]["spec_accept_rate"], "fraction")
    if "rails" in only:
        probes["rails"] = probe_rails(args)
        emit("serve_rails_dispatch_us",
             probes["rails"]["compiled"]["per_step_us"], "us")
        emit("serve_rails_rpc_dispatch_us",
             probes["rails"]["rpc_loop"]["per_step_us"], "us")
        emit("serve_rails_dispatch_speedup",
             probes["rails"]["per_step_dispatch_speedup_x"], "x")
    if "chaos" in only:
        probes["chaos"] = probe_chaos(args)
        emit("serve_chaos_recovered_fraction",
             probes["chaos"]["recovered_fraction"], "fraction")
        emit("serve_chaos_itl_p99_degradation",
             probes["chaos"]["itl_p99_degradation_x"], "x")
    if "disagg" in only:
        probes["disagg"] = probe_disagg(args)
        emit("serve_disagg_prefix_reuse_gain_pct",
             probes["disagg"]["prefix_reuse"]["gain_pct"], "%")
        emit("serve_disagg_long_ttft_p99_improvement",
             probes["disagg"]["split"]["long_ttft_p99_improvement_x"],
             "x")
        emit("serve_disagg_migrated_blocks",
             probes["disagg"]["drain_migration"]["migrated_blocks"],
             "blocks")
    if "http" in only:
        probes["http_stream"] = probe_http(args)
        emit("serve_requests_per_second",
             probes["http_stream"]["requests_per_second"]["value"],
             "req/s")
        emit("serve_ttft_p50_ms",
             probes["http_stream"]["ttft_p50_ms"]["value"], "ms")
        emit("serve_tokens_per_second",
             probes["http_stream"]["tokens_per_second"]["value"],
             "tokens/s")

    comparison: dict = {}
    if "engine_fixed" in probes and "engine_paged" in probes:
        f = probes["engine_fixed"]["tokens_per_second"]["value"]
        p = probes["engine_paged"]["tokens_per_second"]["value"]
        comparison["paged_vs_fixed_equal_hbm"] = {
            "fixed_tokens_per_second": f,
            "paged_tokens_per_second": p,
            "speedup": round(p / f, 2) if f else None,
            "note": (f"both engines hold "
                     f"{args.num_slots * args.max_len} KV tokens of "
                     f"HBM; the paged engine decodes "
                     f"{args.paged_width} streams wide vs "
                     f"{args.num_slots} fixed slots"),
        }
    # Each probe's device, asked of the process that ran its model: this
    # one for the engine probes (it holds the backend by then), the
    # replica for the cluster probes.
    devices = {name: p["device"] for name, p in probes.items()
               if "device" in p}
    if only & IN_PROCESS_PROBES:
        from ray_tpu.util.tpu import device_report

        devices["in_process"] = device_report()

    if args.out:
        import datetime

        artifact = {
            "round": args.round,
            "recorded_at_utc": datetime.datetime.now(
                datetime.timezone.utc).isoformat(),
            "backend": ",".join(sorted(
                {d["platform"] for d in devices.values()})),
            "devices": devices,
            "host": {"nproc": len(os.sched_getaffinity(0))},
            "model": args.model,
            "probes": probes,
            "comparison": comparison,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
