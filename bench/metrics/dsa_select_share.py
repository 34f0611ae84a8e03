"""`dsa_select_share`: 100 x the device time of the ops of `program` that
choose and fetch the selected rows (the family's `select_operand`: the
exact top-k over a row's index scores and the gather of the rows it
selects) over the program's whole device time.  A family without the
function, a program without such ops (one that selects nothing) and a
trace without the program give None."""
import re

from bench.harness.spec import family

_LOOP = re.compile(r"\bwhile\(")


def read(ctx, program: str):
    cfg = ctx["cell"].config
    shaped = getattr(family(cfg), "select_operand", None)
    p = ctx["trace"]["programs"].get(program)
    if shaped is None or not p or not p.get("seconds"):
        return None
    pattern = shaped(cfg)
    seconds = sum(o["seconds"] for o in ctx["trace"]["ops"].values()
                  if o["program"] == program and pattern.search(o["text"])
                  and not _LOOP.search(o["text"]))
    return 100.0 * seconds / p["seconds"] if seconds else None
