"""Finds the knee of an open-loop cell once, on the chip: one process,
one set-up, steps of `--step` seconds at rising rates with a drain
between.  The knee is the highest rate whose due requests all complete
and whose backlog does not grow through the step: the engine's queue is
seen empty and its slots are never all taken in the step's last quarter
(the engine admits a waiting request into a free slot at once and queues
its prompt for the one prefill lane, so a backlog shows as full slots
before it shows as a queue), and the requests due in the step's second
half wait no more than twice as long (and a second) for their first token
as those of the first half.  With some tens of requests a step the halves
are noisy: read the steps' TTFT levels and drain times beside the verdict.  The cell's rate is 0.7 of the knee, written as a
number into cells/<cell>.json.

A later `benchmark` PR finds the knee again when nearly every request of
the cell meets its tail (the system got faster: 0.7 of the old knee no
longer loads it).

    python bench/tools/sweep_rate.py --workload mistral7b-chat \
        --rates 1.0 1.5 2.0 2.5 3.0 --step 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from bench.harness import e2e, schedule, serve_cell, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--step", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=spec.ROOT)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload, args.root)
    traffic, cfg = cell.traffic, cell.config
    steps = []
    with serve_cell.Served(cell, args.seed, cfg["engine"]["num_slots"],
                           False, args.rehearse) as served:
        for i, rate in enumerate(args.rates):
            reqs = schedule.open_schedule(traffic, rate, args.step,
                                          args.seed + i, cfg["vocab_size"])
            spec.check_requests(reqs, cfg["engine"])
            depths = []

            def watch_queue(out=depths):
                """The engine's queue, sampled through the step's last
                quarter."""
                end = time.monotonic() + args.step / 4
                while time.monotonic() < end:
                    s = serve_cell._call("stats", {})
                    out.append((s["queue_depth"], s["active"]))
                    time.sleep(0.25)

            run = served.window(
                reqs, loop_kind="open", seconds=args.step,
                id_prefix=f"sweep-{i}",
                temperature=traffic.get("temperature", 0.0),
                at=[(0.75 * args.step, watch_queue)])
            outs = run["outcomes"]
            failed = [o for o in outs if o.cause]
            for o in failed:
                print(o.failure_line(), flush=True)
            step = {"rate_rps": rate, "attempted": len(outs),
                    "failed": len(failed),
                    "queue_min_last_quarter": min(d for d, _ in depths),
                    "queue_max_last_quarter": max(d for d, _ in depths),
                    "active_max_last_quarter": max(a for _, a in depths),
                    "drain_s": run["drain_s"],
                    **{n: e2e.value(n, run) for n in (
                        "ttft_p50_ms", "ttft_p90_ms", "tpot_p50_ms")}}
            halves = [[o.first - o.due for o in outs
                       if o.first is not None
                       and (o.due >= args.step / 2) == late]
                      for late in (False, True)]
            step["ttft_p50_ms_by_half"] = [
                1000 * sorted(h)[len(h) // 2] if h else None for h in halves]
            first, second = step["ttft_p50_ms_by_half"]
            step["sustained"] = bool(
                not failed and step["queue_min_last_quarter"] == 0
                and step["active_max_last_quarter"]
                < cfg["engine"]["num_slots"]
                and first and second and second <= 2.0 * first + 1000.0)
            print(json.dumps({"sweep_step": step}), flush=True)
            steps.append(step)
        replica = served.report()
    ok = [s["rate_rps"] for s in steps if s["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({"sweep": args.workload, "device": replica["device"],
                      "knee_rps": knee,
                      "rate_at_0.7": round(0.7 * knee, 3) if knee else None,
                      "steps": steps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
