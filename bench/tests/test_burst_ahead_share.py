"""`burst_ahead_share` (bench/metrics/burst_ahead_share.*) on a hand-made
`ctx`: 100 x the lanes-weighted mean of the tick log's `ahead` through
the file and the arguments BENCHMARK.json gives it, and nothing where the
program's tick log has no such field (the parent of PR 35)."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import client, report, spec  # noqa: E402

T0 = 1_790_000_000.0
FIELDS = ("start", "tick_s", "decode_s", "prefill_s", "sample_s", "lanes",
          "width", "prefill_tokens", "kv_read_tokens", "reset_s",
          "experts_read", "ahead")
SERVED = ("mistral7b-chat", "mixtral-chat", "phi4flash-reasoning",
          "mellum2-codeassist")


def _ctx(fields=FIELDS):
    """One request whose prefill span is [10, 12] after T0, and the ticks
    of its window: a chunk alone, a busy period's first burst of one
    lane (nothing before it), two bursts of 2 and 5 lanes launched ahead
    of the read, and a tick that only read the last; a warm-up's tick
    before and the drain's after."""
    ticks = (
        (T0 + 1.0, 0.30, 0.29, 0.0, 0.0, 1, 4, 0, 900, 0.0, 0.0, 1),
        (T0 + 10.0, 0.03, 0.00, 0.027, 0.0, 0, 0, 128, 0, 0.0, 0.0, 0),
        (T0 + 10.1, 0.30, 0.27, 0.027, 0.0, 1, 4, 128, 900, 0.0, 0.0, 0),
        (T0 + 11.0, 0.31, 0.275, 0.008, 0.02, 2, 4, 128, 2400, 0.0, 0.0, 1),
        (T0 + 11.5, 0.40, 0.394, 0.0, 0.0, 5, 8, 0, 9000, 0.0, 0.0, 1),
        (T0 + 11.9, 0.05, 0.049, 0.0, 0.0, 0, 0, 0, 0, 0.0, 0.0, 0),
        (T0 + 13.0, 0.27, 0.268, 0.0, 0.0, 3, 4, 0, 5000, 0.0, 0.0, 0))
    phases = ({"id": "bench-7-0", "submitted": T0 + 10.0,
               "queue_wait_s": 0.0, "prefill_wait_s": 0.0,
               "prefill_span_s": 2.0, "ttft_s": 2.0},)
    outcomes = [client.Outcome(0, 1000, 32, due=0.0, sent=0.0, first=2.0,
                               last=9.0, tokens=32, status=200,
                               request_id="bench-7-0")]
    return {"run": {"outcomes": outcomes},
            "replica": {"stats": {
                "request_phases": phases, "tick_fields": fields,
                "tick_log": tuple(t[:len(fields)] for t in ticks)}}}


def _read(ctx, cell="mistral7b-chat"):
    m = next(m for m in spec.load_cell(cell).per_layer
             if m["name"] == "burst_ahead_share")
    return report._reader(m)(ctx, **m.get("args", {}))


@pytest.mark.parametrize("cell", SERVED)
def test_the_lanes_weighted_share_of_the_window_s_bursts(cell):
    want = 100.0 * (1 * 0 + 2 * 1 + 5 * 1) / 8
    assert math.isclose(_read(_ctx(), cell), want, rel_tol=1e-9)


def test_a_tick_log_without_the_field_leaves_the_metric_out():
    assert _read(_ctx(FIELDS[:-1])) is None
    # and only the cells that decode from an open loop ask for it
    for name in ("mistral7b-longprompt", "mistral7b-sft-fsdp4"):
        assert "burst_ahead_share" not in {
            m["name"] for m in spec.load_cell(name).per_layer}
