"""`engine_tpot_p50_ms`: bench/harness/decode_records.py `engine_tpot` with the
arguments of engine_tpot_p50_ms.json."""
from bench.harness.decode_records import engine_tpot as read  # noqa: F401
