"""The readers of the engine's own records (bench/harness/engine_records.py
behind the nine `bench/metrics/*.py` of the engine scheduler) on a
hand-made `ctx`: each metric's value by hand, through the files and the
arguments that BENCHMARK.json gives it."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import client, report, spec  # noqa: E402

T0 = 1_790_000_000.0
FIELDS = ("start", "tick_s", "decode_s", "prefill_s", "sample_s", "lanes",
          "width", "prefill_tokens")


def _phases(rid, submitted, queue, wait, span):
    return {"id": rid, "submitted": submitted, "queue_wait_s": queue,
            "prefill_wait_s": wait, "prefill_span_s": span,
            "ttft_s": queue + wait + span}


def _ctx():
    """A warm-up request and its two ticks, then a window of three
    requests whose prefill spans are [10.1, 11.0], [11.0, 13.0] and
    [13.0, 16.5] after T0: the window's ticks are those that start in
    [10, 16.5], the last of them at the wider tier."""
    phases = (
        _phases("bench-warm--1", T0 + 1.0, 0.001, 0.001, 0.5),
        _phases("bench-7-0", T0 + 10.0, 0.002, 0.098, 0.9),
        _phases("bench-7-1", T0 + 10.5, 0.004, 0.496, 2.0),
        _phases("bench-7-2", T0 + 11.0, 0.006, 1.994, 3.5))
    ticks = (
        (T0 + 1.0, 0.50, 0.00, 0.40, 0.09, 0, 0, 41),       # the warm-up's
        (T0 + 1.5, 0.30, 0.29, 0.00, 0.00, 1, 4, 0),
        (T0 + 10.0, 0.030, 0.000, 0.027, 0.000, 0, 0, 128),
        (T0 + 10.1, 0.300, 0.270, 0.027, 0.000, 1, 4, 128),
        (T0 + 12.0, 0.310, 0.275, 0.008, 0.020, 2, 4, 128),
        (T0 + 16.4, 0.400, 0.394, 0.000, 0.000, 5, 8, 0),
        (T0 + 17.0, 0.270, 0.268, 0.000, 0.000, 3, 4, 0))   # the drain's
    outcomes = [client.Outcome(i, p, 32, due=0.5 * i, sent=0.5 * i,
                               first=0.5 * i + 1.0, last=9.0, tokens=32,
                               status=200, request_id=f"bench-7-{i}")
                for i, p in enumerate((1000, 2000, 3000))]
    return {"run": {"outcomes": outcomes},
            "replica": {"stats": {"request_phases": phases,
                                  "tick_log": ticks,
                                  "tick_fields": FIELDS}}}


def _read(cell_name, metric, ctx):
    cell = spec.load_cell(cell_name)
    m = next(m for m in cell.per_layer if m["name"] == metric)
    return report._reader(m)(ctx, **m.get("args", {}))


BY_HAND = [
    ("mistral7b-longprompt", "engine_ttft_p50_ms", 2500.0),
    ("mistral7b-longprompt", "prefill_lane_wait_ms", 496.0),
    ("mistral7b-longprompt", "prefill_span_ms", 2000.0),
    # the ticks that decoded lie 0.3, 0.31 and 0.1 s inside the spans
    # of 0.9 + 2.0 + 3.5 s (the last one ends 0.3 s after its span)
    ("mistral7b-longprompt", "prefill_interleave_share",
     100.0 * 0.71 / 6.4),
    # the window's four ticks: 30, 300, 310, 400 ms with 0, 1, 2, 5 lanes
    ("mistral7b-longprompt", "engine_tick_ms.long", 260.0),
    ("mistral7b-chat", "engine_tick_ms.chat", (300 + 2 * 310 + 5 * 400) / 8),
    ("mixtral-chat", "engine_tick_ms.chat", 365.0),
    # one of them at width 8, with 5 of the 8 lanes
    ("mistral7b-chat", "tick_wide_ms", 400.0),
    ("mixtral-chat", "tick_wide_share", 62.5),
    # host shares: 3, 3, 7, 6 ms
    ("mistral7b-longprompt", "tick_host_ms.long", 6.0),
    ("mistral7b-chat", "tick_host_ms.chat", 6.0),
    ("mistral7b-longprompt", "prefill_tok_per_tick.long", 96.0),
    ("mixtral-chat", "prefill_tok_per_tick.chat", 96.0),
]


@pytest.mark.parametrize("cell,metric,want", BY_HAND,
                         ids=[f"{c}:{m}" for c, m, _ in BY_HAND])
def test_metric_by_hand(cell, metric, want):
    # stamps near 1.8e9 s resolve 2e-7 s
    assert math.isclose(_read(cell, metric, _ctx()), want, rel_tol=1e-5)


def test_each_cell_takes_the_new_metrics_that_are_its_own():
    new = {m for _, m, _ in BY_HAND}
    tick = {"engine_tick_ms", "tick_host_ms", "prefill_tok_per_tick"}
    wide = {"tick_wide_ms", "tick_wide_share"}

    def got(cell):
        return new & {m["name"] for m in spec.load_cell(cell).per_layer}

    assert got("mistral7b-longprompt") == {
        "engine_ttft_p50_ms", "prefill_lane_wait_ms", "prefill_span_ms",
        "prefill_interleave_share"} | {t + ".long" for t in tick}
    for chat in ("mistral7b-chat", "mixtral-chat"):
        assert got(chat) == {t + ".chat" for t in tick} | wide
    assert got("mistral7b-sft-fsdp4") == set()


@pytest.mark.parametrize("breakage", [
    "program_without_records", "window_request_lost", "tracing_off",
    "no_tick_log"])
def test_a_metric_is_left_out_never_computed_from_a_part(breakage):
    ctx = _ctx()
    stats = ctx["replica"]["stats"]
    if breakage == "program_without_records":     # the parent commit
        ctx["replica"]["stats"] = {"requests": 4, "completed": 4}
    elif breakage == "window_request_lost":
        stats["request_phases"] = stats["request_phases"][:-1]
    elif breakage == "tracing_off":               # ids are None
        stats["request_phases"] = tuple(
            dict(r, id=None) for r in stats["request_phases"])
    elif breakage == "no_tick_log":
        del stats["tick_log"]
    for cell, metric, _ in BY_HAND:
        got = _read(cell, metric, ctx)
        if breakage == "no_tick_log" and "tick" not in metric \
                and metric != "prefill_interleave_share":
            assert got is not None
        else:
            assert got is None, metric


def test_a_failed_request_is_not_of_the_window():
    ctx = _ctx()
    ctx["run"]["outcomes"][2].cause = "tokens"
    # requests 0 and 1: the upper median of 1000 and 2500 ms
    assert math.isclose(
        _read("mistral7b-longprompt", "engine_ttft_p50_ms", ctx), 2500.0)
    # and the window's ticks end at the first token of request 1
    assert math.isclose(
        _read("mistral7b-longprompt", "engine_tick_ms.long", ctx), 640 / 3)
    assert math.isclose(
        _read("mistral7b-longprompt", "prefill_tok_per_tick.long", ctx),
        128.0)
