"""`prefill_lane_wait_ms`: bench/harness/engine_records.py `request_stat` with the
arguments of prefill_lane_wait_ms.json."""
from bench.harness.engine_records import request_stat as read  # noqa: F401
