"""From a cell's measurements to the last line."""
from __future__ import annotations

import json
import math
import sys
from typing import Any, Dict

from bench.harness import e2e, readers
from bench.harness.spec import BENCH_DIR, Cell, load_file, metric_file


def note(kind: str, **fields) -> None:
    """An earlier line of stdout: causes, schedules, phase reports."""
    print(json.dumps({"bench": kind, **fields}, default=str), flush=True)


def _reader(metric: Dict[str, Any]):
    own = metric_file(BENCH_DIR, metric["name"], ".py")
    if own:
        return load_file(own, "bench_metric_").read
    return getattr(readers, metric["reader"])


def metrics_for(cell: Cell, traced: bool, ctx: Dict[str, Any]
                ) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    if not traced:
        for m in cell.end_to_end:
            v = e2e.value(m["name"], ctx["run"])
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        v = _reader(m)(ctx, **m.get("args", {}))
        if v is None or not math.isfinite(v):
            note("metric_left_out", metric=m["name"],
                 why="its reader found nothing to read")
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def finish(cell: Cell, traced: bool, ctx: Dict[str, Any], *, correct: bool,
           attempted: int, failed: int) -> int:
    """The device check, the metrics and the last line; the exit code.
    `ctx["device"]` is what the process that owns the chips reported."""
    from bench.harness import runtime

    device = ctx["device"]
    if device["platform"] != "tpu" or device["count"] != cell.chips:
        print(f"bench: device check: the worker ran on {device['count']} x "
              f"{device['platform']!r} ({device['kind']!r}), the cell needs "
              f"{cell.chips} x 'tpu': no result", flush=True)
        return 3
    if not runtime.parent_is_off_jax():
        print("bench: this process initialised a JAX backend", flush=True)
        return 3
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": metrics_for(cell, traced, ctx), "device": device}
    if traced:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        line["breakdown"] = ctx["trace"]["breakdown"]
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0
