"""`setup_compile_s.trace_lower` / `.load`: the compile log's seconds of
set-up by phase (`fields` of setup_compile_s.*.json), summed over every
program that went through JAX's compile path before the window opened.
`trace_s + lower_s` is Python's share, which no cache saves; `backend_s`
is reading executables back where the cache hit and compiling them where
it missed.  None where the program keeps no log."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def entries(ctx):
    """The compile log's entries that ended by `t_open`, or None."""
    got = load_file(metric_file(BENCH_DIR, "setup_span_s", ".py"),
                    "bench_metric_").records(ctx)
    return got[1] if got else None


def programs(ctx):
    """Those that loaded or compiled an executable."""
    log = entries(ctx)
    return None if log is None else [
        e for e in log if e["backend_s"] is not None]


def read(ctx, fields):
    log = entries(ctx)
    if log is None:
        return None
    return sum(e[f] or 0.0 for e in log for f in fields)
