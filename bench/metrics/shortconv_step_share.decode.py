"""`shortconv_step_share.decode`: the share of `program`'s device time
spent in its ops whose HLO text shows an operand of a conv layer's mixer
(the family's `mixer_operand`: the kept rows of the slots or of the lanes,
a lane's three chunks, the conv layers' projections): 100 x their seconds
over the program's, `gdn_step_share.decode`'s shape with one difference:
**a `while` is left out**.  A loop carries the slots' rows through its
tuple and touches nothing of them itself, and its own time (what is left
of it less its body: bench/harness/xplane.py) is the gaps between the
hundreds of ops of a step, 8% of this cell's burst, which are nobody's
mixer.  Read beside `moe_visit_share.decode` it says whether the mixers or
the experts set a decode step's pace.  `mixer_seconds` is shared with
`shortconv_roofline.decode`.  A family that gives no such function, a
program without such ops and a run without a trace of the program give
None."""
import re

from bench.harness.spec import family

# By its name too: a loop's tuple type can fill the text the reduction keeps
# (600 characters) before its `while(` is reached.
_LOOP = re.compile(r"^%?while\b|\bwhile\(")


def mixer_seconds(ctx, program: str):
    """(device seconds of `program`'s ops that show a conv mixer's operand,
    loops left out; the program's record of the trace), or None."""
    cfg = ctx["cell"].config
    shaped = getattr(family(cfg), "mixer_operand", None)
    trace = ctx.get("trace")
    if shaped is None or not trace:
        return None
    pattern = shaped(cfg)
    p = trace["programs"].get(program)
    if pattern is None or not p or not p.get("seconds"):
        return None
    seconds = sum(o["seconds"] for o in trace["ops"].values()
                  if o["program"] == program and pattern.search(o["text"])
                  and not _LOOP.search(o["text"]))
    return (seconds, p) if seconds else None


def read(ctx, program: str):
    found = mixer_seconds(ctx, program)
    return None if found is None else 100.0 * found[0] / found[1]["seconds"]
