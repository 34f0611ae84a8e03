"""`hc_roofline`: the least time of a launch's stream mixing over the
device time, per execution of `program`, of its ops whose HLO text reads or
writes an array of streams (the family's `hc_operand`).  The least time is
the larger of two floors, for the rows a launch of the window carried:

  bytes   what must cross HBM at the chip's peak bandwidth: Phi, once a mix
          (the family's `hc_phi_bytes`), and the streams themselves, each
          touched once a phase (`hc_bytes_per_row` x rows), **only where a
          launch's streams (`hc_stream_bytes`) exceed `on_chip_bytes`**:
          under it the compiler keeps them on the chip from one mix to the
          next (a v5e's 128 MiB of VMEM hold the 14.7 MB of a 512-row
          launch nine times), and counting them read 160%: the kernels
          take 0.54 ms where those bytes at 819 GB/s would take 0.88 (my
          chip runs, PR 57; PERF.md section 7);
  flops   the coefficients' product at the chip's peak bf16 FLOP/s
          (`hc_flops_per_row` x rows: exact in float32 is three bfloat16
          passes).

A `while` carries the streams through its tuple and touches nothing of them
itself, so its own time is left out (by its name: the text of a loop with a
long tuple is cut before its `while(`).  A family without the functions (a
model with one residual stream), a program without such ops (a parent's)
and a trace without the counter give None."""
import re

from bench.harness.peaks import peaks
from bench.harness.spec import family
from bench.harness.stats import mean

_LOOP = re.compile(r"^%?while[.\s]|\bwhile\(")
_NEEDS = ("hc_operand", "hc_bytes_per_row", "hc_stream_bytes",
          "hc_phi_bytes", "hc_flops_per_row")


def stream_seconds(ctx, program: str):
    """(device seconds of `program`'s ops that touch streams, the
    program's record), or (None, None) where there is nothing to read."""
    cfg = ctx["cell"].config
    shaped = getattr(family(cfg), "hc_operand", None)
    p = ctx["trace"]["programs"].get(program)
    if shaped is None or not p or not p.get("count"):
        return None, None
    pattern = shaped(cfg)
    seconds = sum(o["seconds"] for o in ctx["trace"]["ops"].values()
                  if o["program"] == program and pattern.search(o["text"])
                  and not _LOOP.search(o["text"]))
    return (seconds, p) if seconds else (None, None)


def read(ctx, program: str, counter: str, on_chip_bytes: int):
    cfg = ctx["cell"].config
    fam = family(cfg)
    c = ctx["trace"]["counters"].get(counter)
    seconds, p = stream_seconds(ctx, program)
    if any(not hasattr(fam, name) for name in _NEEDS) or seconds is None \
            or not c or not c.get("each"):
        return None
    peak = peaks(ctx["device"]["kind"])

    def least(ev):
        rows = ev["tokens"] / max(ev.get("chunks", 1), 1)
        nbytes = fam.hc_phi_bytes(cfg)
        if fam.hc_stream_bytes(cfg, rows) > on_chip_bytes:
            nbytes += fam.hc_bytes_per_row(cfg) * rows
        return max(nbytes / peak["hbm_bytes_per_s"],
                   fam.hc_flops_per_row(cfg) * rows / peak["bf16_flops"])

    return 100.0 * mean([least(ev) for ev in c["each"]]) \
        / (seconds / p["count"])
