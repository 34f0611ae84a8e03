"""The readings behind a family's `TOLERANCES`: the comparison that
decides `correct` (bench/harness/deployment.py `logits_check`, through the
engine's own programs at the configuration's sizes) of the program as it
is (`sound`) and of the program with one fault at a time, each on a fresh
engine.  What a fault is is the family's to say (`control(fault, cfg)` of
bench/families/<family>.py -> (the program configuration, a function that
undoes what it patched)); families/xing4.py has:

    chiprun -- python bench/tools/controls.py --seeds 5700000301 ... \
        [--faults sound bf16_coefficients iters_5 no_clip pool_fp8]

  bf16_coefficients  the coefficients' arithmetic in the nearest precision
                     below the stated float32: Phi, the normed projection,
                     the sigmoid's and the exponential's inputs and the
                     projected matrix rounded to bfloat16
  iters_5 / iters_0  `hc_sinkhorn_iters` rounds cut to 5 / to none
  no_clip            the clip of the stream-to-stream logits at +-1 (what a
                     clip that binds does to the function; at +-30 it never
                     binds on seeded weights)
  no_dynamic         Phi zeroed: the coefficients from b alone
  pool_fp8           a stored latent row rounded to float8_e4m3fn: the
                     nearest precision below the stated `cache_dtype`

Prints one line a (fault, seed): the verdict without its positions, the
median and the worst stray, the defects the reference read of its own
mixing and was handed of the program's (`LAST`), and the peak of memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="xing4.0-29b-a4b-serve-1chip")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=["sound"])
    args = ap.parse_args()
    c = spec._read(os.path.join(spec.BENCH_DIR, "configs",
                                args.config + ".json"))
    fam, eng = spec.family(c), c["engine"]
    for fault in args.faults:
        for seed in args.seeds:
            cfg, undo = fam.control(fault, fam.program_config(c))
            e = PagedLLMEngine(
                cfg, device.seeded_params(fam, cfg, seed),
                num_slots=eng["num_slots"], max_len=eng["max_len"],
                block_size=eng["block_size"],
                prefill_chunk=eng["prefill_chunk"],
                max_burst=eng["max_burst"])
            try:
                v = logits_check(e, c, seed)
            finally:
                e.shutdown()
                undo()
            strays = sorted(1.0 - m for m, _ in v.pop("each", [])
                            if m is not None)
            print(json.dumps({
                "fault": fault, "seed": seed, **v,
                "stray_median": strays[len(strays) // 2] if strays else None,
                "stray_worst": strays[-1] if strays else None,
                "reference": dict(fam.LAST),
                "peak_GB": (device.device_facts()["memory_peak_bytes"]
                            or 0) / 1e9}), flush=True)
            del e
    return 0


if __name__ == "__main__":
    sys.exit(main())
