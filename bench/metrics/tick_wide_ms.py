"""`tick_wide_ms`: bench/harness/engine_records.py `tick_stat` with the
arguments of tick_wide_ms.json."""
from bench.harness.engine_records import tick_stat as read  # noqa: F401
