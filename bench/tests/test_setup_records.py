"""The `setup_*` per-layer metrics (bench/metrics/setup_*.py) on a
hand-made `ctx`, through the files and the arguments BENCHMARK.json gives
them: only what ended before `t_open` counts, `setup_seen_share` is a
union and not a sum, and a program without the records (the parent of
PR 55) gives nothing."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import report, spec  # noqa: E402

T0 = 1_790_000_000.0          # run.py's T_START
T_OPEN, SETUP_S = T0 + 40.0, 40.0
SERVED = ("mistral7b-chat", "mistral7b-longprompt", "mixtral-chat",
          "phi4flash-reasoning", "mellum2-codeassist",
          "granite4h-longprompt", "glm47flash-agent", "lagunaxs2-agent",
          "dots3note-longdoc", "sdar30b-gen512")
NAMES = ("setup_span_s.device_init", "setup_span_s.params",
         "setup_span_s.engine_build", "setup_span_s.warmup",
         "setup_compile_s.trace_lower", "setup_compile_s.load",
         "setup_programs", "setup_cache_misses", "setup_seen_share")


def _span(name, a, b, parent="root"):
    return {"kind": "span", "name": name, "trace_id": "setup-1",
            "span_id": name, "parent_id": parent, "start_ts": T0 + a,
            "end_ts": T0 + b, "attrs": {}}


def _entry(program, a, trace, lower, backend, cache="hit"):
    """An entry whose phases follow each other from T0 + a."""
    took = (trace or 0) + (lower or 0) + (backend or 0)
    return {"program": program, "start_ts": T0 + a, "end_ts": T0 + a + took,
            "trace_s": trace, "lower_s": lower, "backend_s": backend,
            "cache": cache if backend is not None else None,
            "retrieval_s": 0.001 if cache == "hit" else None, "thread": 1}


def _ctx():
    """The replica's constructor from 10 s to 24 s after the start (the
    chip taken for 2 s, parameters for 5 s, the engine for 1 s), its
    warm-up from 24 s to 30 s over two tiers, the harness's logits check
    after it (a program compiled at 31 s, outside every span), the window
    open at 40 s; a tier that compiled inside the window, and the span of
    a second `warmup()` after it."""
    spans = [
        _span("serve.setup.device_init", 11.0, 13.0),
        _span("serve.setup.params", 13.0, 18.0),
        _span("serve.setup.engine_build", 18.0, 19.0),
        _span("serve.setup", 10.0, 24.0, parent=None),
        _span("serve.setup.warmup.tier", 24.0, 27.0, "serve.setup.warmup"),
        _span("serve.setup.warmup.tier", 27.0, 29.5, "serve.setup.warmup"),
        _span("serve.setup.warmup", 24.0, 30.0),
        _span("serve.setup.warmup", 50.0, 52.0)]
    log = [
        _entry("init_params", 13.5, 0.5, 0.25, 2.0),
        _entry("eval_shape_only", 18.5, 0.125, None, None),
        _entry("paged_decode_burst", 24.0, 0.5, 0.5, 1.5),
        _entry("paged_prefill_chunk", 27.0, 0.25, 0.25, 1.0, cache="miss"),
        _entry("forward", 31.0, 0.5, 0.5, 3.0),
        _entry("paged_decode_burst", 45.0, 0.5, 0.5, 9.0, cache="miss")]
    return {"run": {"t_open": T_OPEN, "setup_s": SETUP_S},
            "replica": {"stats": {"setup": {"spans": spans,
                                            "compile_log": log}}}}


def _read(ctx, name, cell="mistral7b-chat"):
    m = next(m for m in spec.load_cell(cell).per_layer if m["name"] == name)
    assert m["moves"] == "setup_s" and m["layer"] == "start-up"
    return report._reader(m)(ctx, **m.get("args", {}))


@pytest.mark.parametrize("cell", SERVED)
def test_every_served_cell_reports_the_nine(cell):
    names = [m["name"] for m in spec.load_cell(cell).per_layer]
    assert [n for n in names if n.startswith("setup_")] == list(NAMES)
    for name in NAMES:
        assert math.isfinite(_read(_ctx(), name, cell))


def test_the_train_cell_is_left_out():
    names = [m["name"] for m in
             spec.load_cell("mistral7b-sft-fsdp4").per_layer]
    assert not [n for n in names if n.startswith("setup_")]


@pytest.mark.parametrize("name,want", [
    ("setup_span_s.device_init", 2.0), ("setup_span_s.params", 5.0),
    ("setup_span_s.engine_build", 1.0),
    ("setup_span_s.warmup", 6.0),           # not the one after t_open
    ("setup_compile_s.trace_lower",
     0.75 + 0.125 + 1.0 + 0.5 + 1.0),       # not the tier inside the window
    ("setup_compile_s.load", 2.0 + 1.5 + 1.0 + 3.0),
    ("setup_programs", 4),                  # a trace alone is no program
    ("setup_cache_misses", 1)])
def test_what_ended_before_the_window_opened(name, want):
    assert math.isclose(_read(_ctx(), name), want, rel_tol=1e-9)


def test_an_entry_that_straddles_t_open_is_not_set_up_s():
    ctx = _ctx()
    ctx["replica"]["stats"]["setup"]["compile_log"].append(
        _entry("late", 39.0, 0.5, 0.25, 2.0))
    assert _read(ctx, "setup_programs") == 4


def test_seen_share_is_the_union_over_setup_s():
    # [10, 24] the root with its children and their programs inside it,
    # [24, 30] the warm-up with its tiers, [31, 35] the check's program.
    want = 100.0 * (14.0 + 6.0 + 4.0) / SETUP_S
    assert math.isclose(_read(_ctx(), "setup_seen_share"), want,
                        rel_tol=1e-9)


def test_seen_share_is_cut_to_the_metric_s_own_interval():
    ctx = _ctx()
    setup = ctx["replica"]["stats"]["setup"]
    setup["compile_log"].insert(0, _entry("before_run_py", -3.0, 1.0, 1.0,
                                          2.0))          # [-3, 1]
    setup["spans"].append(_span("serve.setup.late", 38.0, 44.0))
    # the span ended after t_open: not counted at all
    want = 100.0 * (1.0 + 14.0 + 6.0 + 4.0) / SETUP_S
    assert math.isclose(_read(ctx, "setup_seen_share"), want, rel_tol=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_from_a_program_without_the_records(name):
    ctx = _ctx()
    del ctx["replica"]["stats"]["setup"]
    assert _read(ctx, name) is None


def test_nothing_for_a_span_the_replica_never_recorded():
    ctx = _ctx()
    setup = ctx["replica"]["stats"]["setup"]
    setup["spans"] = [s for s in setup["spans"]
                      if s["name"] != "serve.setup.params"]
    assert _read(ctx, "setup_span_s.params") is None
    assert _read(ctx, "setup_span_s.warmup") == 6.0
