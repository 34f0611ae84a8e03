"""The trace reduction on a small trace recorded on a TPU v5 lite by
bench/tools/record_small_trace.py (PR 23): 3 executions each of
`small_matmul` and `small_scan` (a `while` of 4 fusions), host
annotations and counters around them.  The numbers below were read off
the trace's dump by hand."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import xplane  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_file(TRACE, programs=["small_matmul", "small_scan"])


def test_programs_busy_and_idle(reduced):
    progs = reduced["programs"]
    assert progs["small_matmul"]["count"] == 3
    assert progs["small_scan"]["count"] == 3
    # module events: 15771 + 15719 + 15980 ns and 7817 + 7809 + 7817 ns
    assert math.isclose(progs["small_matmul"]["seconds"], 47.470e-6,
                        rel_tol=1e-3)
    assert math.isclose(progs["small_scan"]["seconds"], 23.443e-6,
                        rel_tol=1e-3)
    # busy is the union of the op intervals: a little under the modules'
    busy = reduced["busy_s"]
    assert 0.98 * 70.9e-6 < busy <= 70.92e-6
    assert reduced["busy_s_per_device"] == [busy]
    assert 0.02 < reduced["window_s"] < 0.04
    assert 1 - busy / reduced["window_s"] > 0.99


def test_nested_ops_are_not_counted_twice(reduced):
    ops = reduced["ops"]
    scan_ops = {k: v for k, v in ops.items() if v["program"] == "small_scan"}
    loop = next(v for k, v in scan_ops.items() if "/while" in k)
    body = next(v for k, v in scan_ops.items() if "sine_add_fusion" in k)
    assert body["count"] == 12                       # 3 runs x 4 trips
    assert math.isclose(body["seconds"], 12 * 1.627e-6, rel_tol=5e-3)
    assert loop["seconds"] < 0.1e-6 * 3 + 1e-9       # self time only
    total = sum(v["seconds"] for v in ops.values())
    assert total <= reduced["busy_s"] * 1.001
    label = next(k for k in ops if "convolution_tanh_fusion" in k)
    assert label == "small_matmul/convolution_tanh_fusion_bf16_1024_1024_"


def test_annotations_counters_and_gaps(reduced):
    assert reduced["counters"]["bench.count.decode"] == {
        "count": 3, "lanes": 15, "kv_tokens": 600,
        "each": [{"lanes": 4, "kv_tokens": 100},
                 {"lanes": 5, "kv_tokens": 200},
                 {"lanes": 6, "kv_tokens": 300}]}
    ann = reduced["annotations"]
    assert ann["bench.engine.decode_tick"]["count"] == 3
    assert ann["bench.engine.prefill_tick"]["count"] == 3
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert xplane.NO_SPAN in gaps and "bench.engine.decode_tick" in gaps
    assert math.isclose(sum(gaps.values()),
                        reduced["window_s"] - reduced["busy_s"],
                        rel_tol=1e-6)
    assert len(reduced["breakdown"]["device_ops"]) <= 10


def test_a_program_that_did_not_run_is_named():
    with pytest.raises(LookupError, match="paged_decode_burst"):
        xplane.reduce_file(TRACE, programs=["paged_decode_burst"])


def test_interval_arithmetic():
    assert xplane.self_times([(0, 10, "a", {}), (1, 4, "b", {}),
                              (5, 9, "c", {}), (6, 7, "d", {})]) \
        == [3, 3, 3, 1]
    assert list(xplane._gaps([(1, 2), (3, 5)], 0, 6)) \
        == [(0, 1), (2, 3), (5, 6)]
    assert xplane.op_label("%copy.97 = bf16[8,4097,16,8,128]{4,3,2,1,0} "
                           "copy(%p)") == "copy.97_bf16_8_4097_16_8_128_"
