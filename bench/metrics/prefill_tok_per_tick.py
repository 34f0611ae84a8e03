"""`prefill_tok_per_tick`: bench/harness/engine_records.py `tick_stat` with the
arguments of prefill_tok_per_tick.json."""
from bench.harness.engine_records import tick_stat as read  # noqa: F401
