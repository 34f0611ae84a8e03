"""`setup_seen_share`: 100 x the part of `setup_s` that the program's own
records lie over: the union of the `serve.setup*` spans and of the
compile log's entries (those outside the spans add theirs: the harness's
parameter program, its logits check), cut to [t_open - setup_s, t_open],
over `setup_s`.  The rest is what the program cannot see of its start:
the runtime's start before the replica's constructor, the reference side
of the logits check, the warm-up request.  None where the program keeps
no records."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx):
    got = load_file(metric_file(BENCH_DIR, "setup_span_s", ".py"),
                    "bench_metric_").records(ctx)
    setup_s = ctx["run"].get("setup_s")
    if not got or not setup_s:
        return None
    hi = ctx["run"]["t_open"]
    lo = hi - setup_s
    seen, reach = 0.0, lo
    for a, b in sorted((max(r["start_ts"], lo), min(r["end_ts"], hi))
                       for r in got[0] + got[1]):
        if b > reach:
            seen += b - max(a, reach)
            reach = b
    return 100.0 * seen / setup_s
