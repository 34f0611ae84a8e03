"""`engine_tick_ms.chat` / `.long`: bench/harness/engine_records.py
`tick_stat` with the arguments of engine_tick_ms.chat.json / .long.json."""
from bench.harness.engine_records import tick_stat as read  # noqa: F401
