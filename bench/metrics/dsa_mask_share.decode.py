"""`dsa_mask_share.decode`: bench/harness/engine_records.py `tick_stat`
with the arguments of dsa_mask_share.decode.json; None where the
program's tick log has no such field (a parent commit)."""
from bench.harness.engine_records import tick_stat


def read(ctx, **args):
    try:
        return tick_stat(ctx, **args)
    except KeyError:
        return None
