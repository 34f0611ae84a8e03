"""`device_starved_share`: bench/harness/decode_records.py `starved_share` with the
arguments of device_starved_share.json."""
from bench.harness.decode_records import starved_share as read  # noqa: F401
