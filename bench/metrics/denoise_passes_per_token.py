"""`denoise_passes_per_token`: the forward passes of the window's bursts,
each counted once a lane (`passes` x `lanes` of the tick log), over the
tokens counted for those lanes (`block_tokens`): what a token costs in
passes, from the program's own records.  None where the tick log has no
such fields (a program that fills no blocks) or the window's ticks counted
no token."""
from bench.harness.engine_records import window_ticks


def read(ctx):
    ticks = window_ticks(ctx)
    if not ticks or "block_tokens" not in ticks[0]:
        return None
    tokens = sum(t["block_tokens"] for t in ticks)
    if not tokens:
        return None
    return sum(t["passes"] * t["lanes"] for t in ticks) / tokens
