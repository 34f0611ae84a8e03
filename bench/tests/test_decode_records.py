"""The metrics of PR 38 (bench/harness/decode_records.py behind
`engine_tpot_p50_ms`, `front_tpot_ms`, the three `tpot_*_share`,
`decode_lanes_seen`, `device_starved_share.*`) on a hand-made `ctx`,
through the files and the arguments BENCHMARK.json gives them; and
nothing where the program's records have no such fields (the parent of
PR 38) or a request's record never finished."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import client, report, spec  # noqa: E402

T0 = 1_790_000_000.0
FIELDS = ("start", "tick_s", "decode_s", "prefill_s", "sample_s", "lanes",
          "width", "prefill_tokens", "routed_here", "kv_read_tokens",
          "reset_s", "experts_read", "ahead", "starved_s")
CHAT = ("mistral7b-chat", "mixtral-chat", "phi4flash-reasoning",
        "mellum2-codeassist")
LONG = ("mistral7b-longprompt", "granite4h-longprompt")
DECODE = ("engine_tpot_p50_ms", "front_tpot_ms", "tpot_device_wait_share",
          "tpot_first_read_share", "tpot_host_share", "decode_lanes_seen")
_OLD = ("id", "submitted", "queue_wait_s", "prefill_wait_s",
        "prefill_span_s", "ttft_s")


def _rec(i, submitted, ttft, decode_s, n_out, burst, first, lanes):
    return {"id": f"bench-7-{i}", "submitted": T0 + submitted,
            "queue_wait_s": 0.0, "prefill_wait_s": 0.0,
            "prefill_span_s": ttft, "ttft_s": ttft, "decode_s": decode_s,
            "n_out": n_out, "burst_read_s": burst, "first_read_s": first,
            "host_s": decode_s - burst - first, "lanes_seen": lanes}


def _out(i, first, last, tokens, cause=None):
    return client.Outcome(i, 100, tokens, due=0.0, sent=0.0, first=first,
                          last=last, tokens=tokens, status=200, cause=cause,
                          request_id=f"bench-7-{i}")


def _ctx(fields=FIELDS, strip=False):
    """Three requests that decoded (6.0, 5.0 and 8.0 ms a token inside the
    engine; the client saw 6.5, 5.2 and 8.1), one of a single token, one
    that failed at the client, and a warm-up's; ticks of 10 ms, three of
    them inside the window's span (10-12 s after T0), 7 ms starved."""
    phases = [
        {**_rec("warm", 1.0, 0.1, 0.2, 9, 0.1, 0.0, 1.0), "id": "bench-warm-0"},
        _rec(0, 10.0, 1.0, 0.006 * 31, 32, 0.150, 0.006, 1.0),
        _rec(1, 10.5, 1.0, 0.005 * 15, 16, 0.060, 0.000, 2.0),
        _rec(2, 11.0, 1.0, 0.008 * 63, 64, 0.400, 0.024, 3.0),
        _rec(3, 11.2, 0.5, 0.0, 1, 0.0, 0.0, 0.0),
        {**_rec(4, 11.4, 0.5, 0.0, 0, 0.0, 0.0, 0.0),
         **dict.fromkeys(("decode_s", "n_out", "burst_read_s",
                          "first_read_s", "host_s", "lanes_seen"))}]
    outcomes = [_out(0, 1.0, 1.0 + 0.0065 * 31, 32),
                _out(1, 1.5, 1.5 + 0.0052 * 15, 16),
                _out(2, 2.0, 2.0 + 0.0081 * 63, 64),
                _out(3, 1.7, 1.7, 1),
                _out(4, 1.9, 2.5, 5, cause="tokens")]
    row = (0.010, 0.009, 0.0, 0.0, 1, 4, 0, 0, 900, 0.0, 0.0, 1)
    ticks = [(T0 + at,) + row + (starved,) for at, starved in (
        (1.0, 0.009), (10.2, 0.0), (10.9, 0.004), (11.9, 0.003),
        (13.0, 0.008))]
    if strip:
        phases = [{k: r[k] for k in _OLD} for r in phases]
    return {"run": {"outcomes": outcomes},
            "replica": {"stats": {
                "request_phases": tuple(phases), "tick_fields": fields,
                "tick_log": tuple(t[:len(fields)] for t in ticks)}}}


def _read(ctx, name, cell):
    m = next(m for m in spec.load_cell(cell).per_layer if m["name"] == name)
    return report._reader(m)(ctx, **m.get("args", {}))


@pytest.mark.parametrize("cell", CHAT)
def test_the_decode_metrics_of_a_window(cell):
    ctx = _ctx()
    got = {name: _read(ctx, name, cell) for name in DECODE}
    assert math.isclose(got["engine_tpot_p50_ms"], 6.0, rel_tol=1e-9)
    # the medians of the differences by request: 0.5, 0.2, 0.1
    assert math.isclose(got["front_tpot_ms"], 0.2, rel_tol=1e-6)
    total = 0.006 * 31 + 0.005 * 15 + 0.008 * 63
    assert math.isclose(got["tpot_device_wait_share"],
                        100 * 0.610 / total, rel_tol=1e-9)
    assert math.isclose(got["tpot_first_read_share"],
                        100 * 0.030 / total, rel_tol=1e-9)
    assert math.isclose(sum(got[k] for k in (
        "tpot_device_wait_share", "tpot_first_read_share",
        "tpot_host_share")), 100.0, abs_tol=1e-9)
    assert got["decode_lanes_seen"] == 2.0


@pytest.mark.parametrize("cell", CHAT + LONG)
def test_the_starved_share_of_the_windows_ticks(cell):
    name = "device_starved_share." + ("chat" if cell in CHAT else "long")
    assert math.isclose(_read(_ctx(), name, cell),
                        100 * 0.007 / 0.030, rel_tol=1e-9)
    # a tick log without the field (the parent of PR 38): left out
    assert _read(_ctx(FIELDS[:-1]), name, cell) is None


@pytest.mark.parametrize("name", DECODE)
def test_records_without_the_decode_half_leave_the_metric_out(name):
    assert _read(_ctx(strip=True), name, "mistral7b-chat") is None
    # and so does a request that streamed to its end at the client while
    # its record never finished
    ctx = _ctx()
    for k in ("decode_s", "n_out"):
        ctx["replica"]["stats"]["request_phases"][2][k] = None
    assert _read(ctx, name, "mistral7b-chat") is None


def test_each_metric_is_asked_in_the_cells_of_its_end_to_end_metric():
    asked = {cell: {m["name"] for m in spec.load_cell(cell).per_layer}
             for cell in CHAT + LONG + ("mistral7b-sft-fsdp4",)}
    new = set(DECODE) | {"device_starved_share.chat",
                         "device_starved_share.long"}
    for cell in CHAT:
        assert asked[cell] & new == new - {"device_starved_share.long"}
    for cell in LONG:
        assert asked[cell] & new == {"device_starved_share.long"}
    assert not asked["mistral7b-sft-fsdp4"] & new
