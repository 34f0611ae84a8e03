"""`tick_wide_share`: bench/harness/engine_records.py `tick_share_widest`
with the arguments of tick_wide_share.json."""
from bench.harness.engine_records import tick_share_widest as read  # noqa: F401
