"""`setup_programs`: the compile log's entries with a backend phase that
ended before the window opened: the executables set-up loaded or
compiled, as a count.  None where the program keeps no log."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx):
    got = load_file(metric_file(BENCH_DIR, "setup_compile_s", ".py"),
                    "bench_metric_").programs(ctx)
    return None if got is None else len(got)
