"""`ring_slots_read`: bench/harness/engine_records.py `tick_stat` with
the arguments of ring_slots_read.json; None where the program's tick
log has no such field."""
from bench.harness.engine_records import tick_stat


def read(ctx, **args):
    try:
        return tick_stat(ctx, **args)
    except KeyError:
        return None
