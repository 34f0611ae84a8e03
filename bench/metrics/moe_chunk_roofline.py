"""`moe_chunk_roofline`: the least time one prefill chunk's visit of the
held experts can take (every layer: the family's `expert_bytes_per_chunk`
at the chip's peak HBM bandwidth or `expert_flops_per_chunk` at its peak
bf16 FLOP/s, whichever is larger, for the tokens a launch of the window
carried) over the device time, per execution of `program`, of its ops
that read an operand shaped like a layer's held experts (the family's
`expert_operand`): `chunk_roofline` of bench/metrics/ssd_scan_roofline.py
with the experts' functions.  A family that lacks them, a program without
such ops and a trace without the counter give None."""
from bench.harness.spec import BENCH_DIR, load_file, metric_file


def read(ctx, program: str, counter: str):
    shape = load_file(metric_file(BENCH_DIR, "ssd_scan_roofline", ".py"),
                      "bench_metric_")
    return shape.chunk_roofline(
        ctx, program, counter, "expert_operand", "expert_flops_per_chunk",
        "expert_bytes_per_chunk", skip_loops=False)
