"""`conv_state_rows`: bench/harness/engine_records.py `tick_stat` with
the arguments of conv_state_rows.json; None where the program's tick
log has no such field."""
from bench.harness.engine_records import tick_stat


def read(ctx, **args):
    try:
        return tick_stat(ctx, **args)
    except KeyError:
        return None
