"""`gdn_chunk_roofline`: the least time the chunked delta rule of one
prefill launch can take (every linear layer: the family's
`scan_flops_per_chunk` at the chip's peak bf16 FLOP/s or
`scan_bytes_per_chunk` at its peak HBM bandwidth, whichever is larger, for
the tokens a launch of the window carried) over the device time, per
execution of `program`, of its ops whose HLO text shows a state or a chunk's
C x C matrix a head (the family's `scan_operand`): `chunk_roofline` of
bench/metrics/ssd_scan_roofline.py, whose `read` it is under another name
(in this cell the launches run beside decode bursts and move
`tpot_p50_ms`).  A family that lacks the three functions, a program
without such ops and a trace without the counter give None."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx, program: str, counter: str):
    shape = load_file(metric_file(BENCH_DIR, "ssd_scan_roofline", ".py"),
                      "bench_metric_")
    return shape.read(ctx, program, counter)
