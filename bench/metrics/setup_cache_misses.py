"""`setup_cache_misses`: of `setup_programs`, those the persistent cache
did not hand back (`cache` "miss" or "off"): 0 on a warm machine; above
0 the run paid for compiling, so its `setup_s` is not a warm one, or a
cache key moved.  None where the program keeps no log."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx):
    got = load_file(metric_file(BENCH_DIR, "setup_compile_s", ".py"),
                    "bench_metric_").programs(ctx)
    return None if got is None else sum(e["cache"] != "hit" for e in got)
