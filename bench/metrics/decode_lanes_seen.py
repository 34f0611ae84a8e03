"""`decode_lanes_seen`: bench/harness/decode_records.py `lanes_seen` with the
arguments of decode_lanes_seen.json."""
from bench.harness.decode_records import lanes_seen as read  # noqa: F401
