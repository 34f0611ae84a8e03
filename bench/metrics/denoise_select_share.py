"""`denoise_select_share`: 100 x the device time of the ops of `program`
whose HLO text shows an array over the vocabulary (the family's
`select_operand`: the head and the selection of a denoising pass) over the
program's whole device time: `dsa_select_share`'s reader (bench/metrics/
dsa_select_share.py), which asks the family for that pattern and leaves a
`while`'s own time out (it carries the head's weight through its tuple and
computes nothing with it).  A family without the function, a program
without such ops and a trace without the program give None."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx, program: str):
    return load_file(metric_file(BENCH_DIR, "dsa_select_share", ".py"),
                     "bench_metric_").read(ctx, program)
