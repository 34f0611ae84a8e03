"""`dsa_index_roofline`: the least time of one prefill launch's indexer
(the family's `index_flops` at the chip's peak bf16 FLOP/s or
`index_bytes` at its peak HBM bandwidth, whichever is larger, for the
rows and the context a launch of the window carried) over the device
time, per execution of `program`, of its ops whose HLO text shows an array
of stored index keys (the family's `index_operand`).  A `while` carries
the pool through its tuple and reads nothing of it itself, so its own time
is left out.  `launch_roofline` is the shape of it: `chunk_roofline` of
bench/metrics/ssd_scan_roofline.py for work that depends on a launch's
context as well as its rows; `dsa_attn_roofline` and `latent_swa_roofline`
take it with their own three functions.  A family that lacks them, a
program without such ops and a trace without the counter give None."""
import re

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean

_LOOP = re.compile(r"\bwhile\(")


def launch_roofline(ctx, program: str, counter: str, operand: str,
                    flops: str, nbytes: str):
    cfg = ctx["cell"].config
    fam = family(cfg)
    fns = [getattr(fam, n, None) for n in (operand, flops, nbytes)]
    if None in fns:
        return None
    shaped, flops_of, bytes_of = fns
    pattern = shaped(cfg)
    trace = ctx["trace"]
    p = trace["programs"].get(program)
    c = trace["counters"].get(counter)
    if pattern is None or not p or not p.get("count") or not c \
            or not c.get("each"):
        return None
    seconds = sum(o["seconds"] for o in trace["ops"].values()
                  if o["program"] == program and pattern.search(o["text"])
                  and not _LOOP.search(o["text"]))
    if not seconds:
        return None
    peak = peaks(ctx["device"]["kind"])

    def least(ev):
        n = max(ev.get("chunks", 1), 1)
        tokens, context = ev["tokens"] / n, ev["context"] / n
        return max(flops_of(cfg, tokens, context) / peak["bf16_flops"],
                   bytes_of(cfg, tokens, context) / peak["hbm_bytes_per_s"])

    return 100.0 * mean([least(ev) for ev in c["each"]]) \
        / (seconds / p["count"])


def read(ctx, program: str, counter: str):
    return launch_roofline(ctx, program, counter, "index_operand",
                           "index_flops", "index_bytes")
