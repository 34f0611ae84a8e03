"""`front_tpot_ms`: bench/harness/decode_records.py `front_tpot` with the
arguments of front_tpot_ms.json."""
from bench.harness.decode_records import front_tpot as read  # noqa: F401
