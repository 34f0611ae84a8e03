"""`tpot_device_wait_share`: bench/harness/decode_records.py `decode_share` with the
arguments of tpot_device_wait_share.json."""
from bench.harness.decode_records import decode_share as read  # noqa: F401
