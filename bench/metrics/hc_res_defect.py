"""`hc_res_defect`: the largest `hc_res_defect` of the window's ticks (the
tick log's own: the largest |row sum - 1| and |column sum - 1| of the
projected stream-to-stream matrices over the valid rows and mixes of a
tick's chunks and of its burst's steps, reduced on the device): how far the
Sinkhorn-Knopp projection of the timed run itself stopped from the doubly
stochastic matrices.  None where the program's tick log has no such field
(a model with one residual stream, a parent's program) or the window no
ticks."""
from bench.harness.engine_records import window_ticks


def read(ctx):
    ticks = window_ticks(ctx)
    if not ticks or "hc_res_defect" not in ticks[0]:
        return None
    return max(t["hc_res_defect"] for t in ticks)
