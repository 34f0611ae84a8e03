"""`hc_chunk_share`: 100 x the device time of the ops of `program` whose
HLO text reads or writes an array of streams (the family's `hc_operand`:
`hc_roofline`'s ops) over the program's whole device time: what several
residual streams cost beside what they carry.  A family without the
function, a program without such ops and a trace without the program give
None."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx, program: str):
    ops = load_file(metric_file(BENCH_DIR, "hc_roofline", ".py"),
                    "bench_metric_")
    seconds, p = ops.stream_seconds(ctx, program)
    if seconds is None or not p.get("seconds"):
        return None
    return 100.0 * seconds / p["seconds"]
