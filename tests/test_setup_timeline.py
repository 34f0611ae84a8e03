"""Set-up's timeline: the compile log (`ray_tpu/util/compile_cache.py`:
one entry per program from JAX's own trace / lowering / backend events,
the cache's hit or miss attributed by thread) and a replica's start as
spans (`serve/llm.py:_SetupSpans`, read through `engine_stats()["setup"]`
and `LLMDeployment.runtime_report()`), on one clock.  Everything here is
CPU, the `tiny` preset, no cluster."""
import contextlib
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.util import compile_cache, tracing

PHASES = ("trace_s", "lower_s", "backend_s")


def _jitted(name, k=1.0):
    """A fresh jitted function called `name`: nothing of it is cached in
    memory, and `k` makes its program (and its cache key) its own."""
    def f(x):
        return jnp.tanh(x * k).sum()

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _mine(since, prefix):
    return [e for e in compile_cache.log(since=since)
            if e["program"].startswith(prefix)]


@contextlib.contextmanager
def _listeners_counted():
    """Counts every call JAX's monitoring makes to a listener."""
    calls = []

    def on_event(event, **kw):
        calls.append(event)

    def on_number(event, _n, **kw):
        calls.append(event)

    def on_span(event, _a, _b, **kw):
        calls.append(event)

    m = jax.monitoring
    m.register_event_listener(on_event)
    m.register_event_duration_secs_listener(on_number)
    m.register_event_time_span_listener(on_span)
    m.register_scalar_listener(on_number)
    try:
        yield calls
    finally:
        m.unregister_event_listener(on_event)
        m.unregister_event_duration_listener(on_number)
        m.unregister_event_time_span_listener(on_span)
        m.unregister_scalar_listener(on_number)


@contextlib.contextmanager
def _persistent_cache(path):
    """JAX's persistent cache at `path` (None: no directory) with its
    floors at zero, so that a one-operation program is written too; the
    process's settings put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        for n, v in zip(names, (path, 0.0, -1)):
            jax.config.update(n, v)
        cc.reset_cache()
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


# ---------------------------------------------------------------------------
# the compile log
# ---------------------------------------------------------------------------
def test_one_entry_a_program_and_none_from_a_warm_call():
    compile_cache.counts()                  # listening, before the compile
    t0 = time.time()
    f = _jitted("setup_timeline_first")
    f(jnp.ones(4))
    (e,) = _mine(t0, "setup_timeline_first")
    assert e["program"] == "setup_timeline_first"      # no `jit(...)`
    assert all(e[p] is not None and e[p] >= 0 for p in PHASES)
    assert t0 <= e["start_ts"] <= e["end_ts"] <= time.time()
    assert e["end_ts"] - e["start_ts"] >= sum(e[p] for p in PHASES) - 1e-3
    assert e["thread"] == threading.get_ident()
    # What `f` calls while it is traced (`tanh`, `_reduce_sum`) is part
    # of its trace: no entry of their own.
    assert all(x["backend_s"] is not None
               for x in compile_cache.log(since=e["start_ts"]))

    f(jnp.ones(8))                          # a new shape: a second entry
    assert len(_mine(t0, "setup_timeline_first")) == 2

    # The warm path: JAX raises no event, so no listener runs at all and
    # the log stands still.
    before = compile_cache.log()
    with _listeners_counted() as calls:
        for _ in range(3):
            f(jnp.ones(4)).block_until_ready()
            f(jnp.ones(8)).block_until_ready()
    assert calls == []
    assert compile_cache.log() == before


def test_miss_then_hit_with_a_directory_and_off_without(tmp_path):
    compile_cache.counts()
    c0, t0 = compile_cache.counts(), time.time()
    with _persistent_cache(str(tmp_path)):
        _jitted("setup_timeline_cached", 2.0)(jnp.ones(4))
        jax.clear_caches()                  # the in-memory executables
        _jitted("setup_timeline_cached", 2.0)(jnp.ones(4))
    with _persistent_cache(None):
        _jitted("setup_timeline_cached", 3.0)(jnp.ones(4))
    miss, hit, off = _mine(t0, "setup_timeline_cached")
    assert (miss["cache"], hit["cache"], off["cache"]) == (
        "miss", "hit", "off")
    assert miss["retrieval_s"] is None and off["retrieval_s"] is None
    assert 0 <= hit["retrieval_s"] <= hit["backend_s"]

    # `counts()` keeps its keys and is the log's running total.
    c1, since = compile_cache.counts(), compile_cache.log(since=t0)
    assert {"dir", "hits", "written"} <= set(c1)
    made = [e for e in since if e["backend_s"] is not None]
    assert c1["programs"] - c0["programs"] == len(made)
    for key, kind in (("hits", "hit"), ("misses", "miss"), ("off", "off")):
        assert c1[key] - c0[key] == sum(e["cache"] == kind for e in made)
    assert c1["written"] - c0["written"] == c1["misses"] - c0["misses"]
    for p in PHASES:
        assert c1[p] - c0[p] == pytest.approx(
            sum(e[p] or 0.0 for e in since), abs=1e-6)


def test_two_threads_compiling_at_once_keep_their_own_flags(tmp_path):
    compile_cache.counts()
    n = 6
    with _persistent_cache(str(tmp_path)):
        for i in range(n):                  # written, then forgotten
            _jitted(f"setup_timeline_read_{i}", 10.0 + i)(jnp.ones(4))
        jax.clear_caches()
        t0, gate, failed = time.time(), threading.Barrier(2), []

        def compile_all(prefix, k):
            try:
                for i in range(n):
                    gate.wait(timeout=30)
                    _jitted(f"{prefix}_{i}", k + i)(jnp.ones(4))
            except BaseException as e:      # noqa: BLE001 shown below
                failed.append(e)
                gate.abort()

        threads = [threading.Thread(target=compile_all, args=a) for a in (
            ("setup_timeline_read", 10.0), ("setup_timeline_new", 50.0))]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
    assert not failed and not any(t.is_alive() for t in threads)
    read = _mine(t0, "setup_timeline_read")
    new = _mine(t0, "setup_timeline_new")
    assert len(read) == len(new) == n
    assert {e["cache"] for e in read} == {"hit"}
    assert {e["cache"] for e in new} == {"miss"}
    assert all(e["retrieval_s"] is not None for e in read)
    assert all(e["retrieval_s"] is None for e in new)
    assert len({e["thread"] for e in read}) == 1
    assert {e["thread"] for e in read}.isdisjoint(e["thread"] for e in new)


# ---------------------------------------------------------------------------
# a replica's start as spans
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def warmed():
    """A tiny engine with every kind of tier (one width, its verify
    program, two chunk tiers), warmed once; (engine, time before)."""
    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg = configs.get("tiny")
    t0 = time.time()
    eng = PagedLLMEngine(cfg, init_params(jax.random.key(0), cfg),
                         num_slots=4, max_len=64, prefill_chunk=32,
                         speculation_k=2)
    with eng._tick_lock:
        eng.warmup()
    yield eng, t0
    eng.shutdown()


def test_warmup_yields_a_span_a_launch_inside_its_own(warmed):
    eng, t0 = warmed
    setup = eng.engine_stats()["setup"]
    by_name = {}
    for s in setup["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    (build,), (whole,) = (by_name["serve.setup.engine_build"],
                          by_name["serve.setup.warmup"])
    assert build["attrs"]["num_blocks"] == eng.num_blocks
    assert build["attrs"]["kv_bytes"] == sum(eng._state_bytes.values())
    assert t0 <= build["start_ts"] <= build["end_ts"] <= whole["start_ts"]
    assert whole["attrs"] == {"width_tiers": [4], "chunk_tiers": [32, 64]}
    tiers = by_name["serve.setup.warmup.tier"]
    assert [(s["attrs"]["kind"], s["attrs"]["program"],
             s["attrs"].get("width"), s["attrs"].get("rows"))
            for s in tiers] == [
        ("burst", "paged_decode_burst", 4, None),
        ("verify", "paged_verify_step", 4, None),
        ("chunk", "paged_prefill_chunk", None, 32),
        ("chunk", "paged_prefill_chunk", None, 64)]
    assert len({s["trace_id"] for s in setup["spans"]}) == 1
    at = whole["start_ts"]
    for s in tiers:                 # one after the other, inside the whole
        assert s["parent_id"] == whole["span_id"]
        assert at <= s["start_ts"] <= s["end_ts"] <= whole["end_ts"]
        at = s["end_ts"]
        # The launch's program is in the compile log, inside the span,
        # with its three phases and what the cache said.
        (e,) = [e for e in setup["compile_log"]
                if e["program"] == s["attrs"]["program"]
                and s["start_ts"] <= e["start_ts"]
                and e["end_ts"] <= s["end_ts"]]
        assert all(e[p] is not None for p in PHASES)
        assert e["cache"] in ("hit", "miss", "off")


def test_the_gauge_loop_s_call_copies_nothing(warmed):
    eng, _ = warmed
    assert "setup" not in eng.engine_stats(records=False)
    assert set(eng.engine_stats()["setup"]) == {"spans", "compile_log"}


def test_serving_a_warm_engine_adds_nothing_to_the_log(warmed):
    eng, _ = warmed
    prompt = list(range(1, 20))
    eng.generate(prompt, max_tokens=6)      # the sampler, the block copy
    before = compile_cache.log()
    with _listeners_counted() as calls:
        eng.generate(prompt[:-2] + [7, 9], max_tokens=6)
    assert calls == []
    assert compile_cache.log() == before


@pytest.mark.parametrize("traced", [True, False],
                         ids=["serve-tracing-on", "kill-switch"])
def test_a_deployment_s_start_is_one_trace(warmed, traced):
    from ray_tpu.core.config import get_config
    from ray_tpu.serve.llm import LLMDeployment

    eng, _ = warmed
    cfg = get_config()
    saved = cfg.serve_trace_enabled
    cfg.serve_trace_enabled = traced
    tracing.drain()
    try:
        d = LLMDeployment(eng.cfg, num_slots=4, max_len=64,
                          prefill_chunk=32, speculation_k=0, disagg=False,
                          params_loader=lambda: eng.params)
        try:
            with d.engine._tick_lock:
                d.engine.warmup()           # as the benchmark does: after
            report = d.runtime_report()
        finally:
            d.engine.shutdown()
        sunk = [s for s in tracing.drain()
                if s["name"].startswith("serve.setup")]
    finally:
        cfg.serve_trace_enabled = saved
    assert {"device", "compile_cache", "setup"} <= set(report)
    assert {"dir", "hits", "written"} <= set(report["compile_cache"])
    spans = report["setup"]["spans"]
    names = [s["name"] for s in spans]
    assert names[:4] == ["serve.setup.device_init", "serve.setup.params",
                         "serve.setup.engine_build", "serve.setup"]
    assert names[4:] == ["serve.setup.warmup.tier"] * 3 + [
        "serve.setup.warmup"]
    root = spans[3]
    assert root["parent_id"] is None
    assert root["attrs"] == {"cfg": "tiny", "num_slots": 4, "max_len": 64}
    at = root["start_ts"]
    for s in spans[:3]:             # in turn, inside the constructor
        assert s["parent_id"] == root["span_id"]
        assert at <= s["start_ts"] <= s["end_ts"] <= root["end_ts"]
        at = s["end_ts"]
    assert spans[0]["attrs"] == {"platform": "cpu",
                                 "count": len(jax.devices())}
    assert spans[1]["attrs"] == {"loader": True, "bytes": sum(
        x.nbytes for x in jax.tree_util.tree_leaves(eng.params))}
    # The warm-up the caller asked for later hangs under the same root.
    assert spans[-1]["parent_id"] == root["span_id"]
    assert spans[-1]["start_ts"] >= root["end_ts"]
    assert len({s["trace_id"] for s in spans}) == 1
    # The tracing buffer is a second sink, and the only thing the kill
    # switch silences.
    assert sunk == (spans if traced else [])
