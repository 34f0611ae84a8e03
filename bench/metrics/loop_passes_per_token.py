"""`loop_passes_per_token`: the layers a token of the window's bursts went
through (`loop_passes` of the tick log: the passes of the stack x its
layers), weighted by the bursts' lanes: what a token costs in streams of a
layer's weights, from the program's own records.  None where the tick log
has no such field (a program whose stack runs once) or the window launched
no burst."""
from bench.harness.engine_records import window_ticks


def read(ctx):
    ticks = window_ticks(ctx)
    if not ticks or "loop_passes" not in ticks[0]:
        return None
    lanes = sum(t["lanes"] for t in ticks)
    if not lanes:
        return None
    return sum(t["loop_passes"] * t["lanes"] for t in ticks) / lanes
