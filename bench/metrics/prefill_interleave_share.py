"""`prefill_interleave_share`: bench/harness/engine_records.py
`prefill_interleave`."""
from bench.harness.engine_records import prefill_interleave as read  # noqa: F401
