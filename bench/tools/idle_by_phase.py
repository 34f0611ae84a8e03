"""The device's idle time by what the engine's loop thread was doing,
on the profiler's clock: one traced run of a served cell whose trace is
kept, reduced twice by `xplane.reduce_planes`: with the harness's own
prefix (`bench.`: the three spans `BenchLLMDeployment` wraps around
`_admit_one`, `_decode_tick`, `_prefill_tick`) and with the engine's
(`serve.engine.phase.`: the leaves of `serve/llm.py:_PhaseClock`, flat
and covering the loop, so idle under no leaf should be near nothing and
`wait` is "no work").

    chiprun -- python bench/tools/idle_by_phase.py --workload mistral7b-longprompt

Prints, and writes to `chiprun_out/idle_by_phase/<workload>.json`: idle
seconds by the old span and by leaf, each leaf's seconds and count, the
idle under each old span split by leaf (`cross`), the tick log's
`starved_s` summed over the traced seconds (the engine's epoch clock is
laid onto the trace's by matching the ticks' starts to the `admit`
leaves, which open at the same clock read), the run's own client
medians and the largest distance of a finished request's three phase
sums from its `decode_s`.  Until a `benchmark` PR lets
the harness's reduction take a cell's prefix (ROADMAP D13a) this is a
tool and its table goes into PERF.md by hand.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

PHASE = "serve.engine.phase."
OLD = "bench.engine."


def _both(a, b):
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def cross(planes):
    """({old span or 'none': {leaf or 'none': idle seconds}} over the gaps
    of the first device's op timeline, the trace's bounds, the starts of
    the `admit` leaves)."""
    from bench.harness import xplane

    busy, host = None, []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if xplane._DEVICE.match(plane.name) and xplane.OPS_LINE in lines \
                and busy is None:
            busy = [(s, e) for s, e, _, _ in xplane._events(
                lines[xplane.OPS_LINE])]
        elif plane.name == xplane.HOST_PLANE:
            host = [ev for ln in plane.lines for ev in xplane._events(ln)]
    lo = min([s for s, *_ in host] + [s for s, _ in busy])
    hi = max([e for _, e, *_ in host] + [e for _, e in busy])
    leaves, old = {}, {}
    for s, e, n, _ in sorted(host):
        if n.startswith(PHASE):
            leaves.setdefault(n[len(PHASE):], []).append((s, e))
        elif n.startswith(OLD):
            old.setdefault(n[len(OLD):], []).append((s, e))
    no_leaf = list(xplane._gaps(
        [i for v in leaves.values() for i in v], lo, hi))
    no_old = list(xplane._gaps([i for v in old.values() for i in v], lo, hi))
    cells = {}
    for span, a in list(old.items()) + [("none", no_old)]:
        for leaf, b in list(leaves.items()) + [("none", no_leaf)]:
            both = _both(a, b)
            if both:
                cells[(span, leaf)] = (both, [s for s, _ in both])
    out = {}
    for g_lo, g_hi in xplane._gaps(busy, lo, hi):
        for (span, leaf), (ints, starts) in cells.items():
            got = xplane._overlap(ints, starts, g_lo, g_hi)
            if got:
                row = out.setdefault(span, {})
                row[leaf] = row.get(leaf, 0.0) + got
    return out, (lo, hi), [s for s, _ in leaves.get("admit", [])]


def clock_offset(admits, tick_starts, guess, slack=3.0, step=1e-4):
    """The engine's epoch clock less the trace's: a tick of the log
    starts where an `admit` leaf opens (one clock read apart), so of all
    differences tick - leaf within `slack` of `guess` the true offset is
    the one most pairs share.  Returns (offset, pairs that share it)."""
    import collections

    votes = collections.Counter()
    for t in tick_starts:
        for a in admits:
            d = t - a
            if abs(d - guess) < slack:
                votes[round(d / step)] += 1
    if not votes:
        return None, 0
    k, _ = votes.most_common(1)[0]
    n = votes[k - 1] + votes[k] + votes[k + 1]
    near = [t - a for t in tick_starts for a in admits
            if abs((t - a) / step - k) <= 1.5]
    return sum(near) / len(near), n


def main() -> int:
    from bench.harness import e2e, report, serve_cell, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3_800_000_501)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    keep = os.path.join(spec.ROOT, "chiprun_out", "idle_by_phase",
                        args.workload)
    seen = {}
    finish = report.finish

    def keeping(cell_, traced, ctx, **kw):
        seen.update(ctx)
        return finish(cell_, traced, ctx, **kw)

    report.finish = keeping
    code = serve_cell.run(cell, seed=args.seed, seconds=args.seconds,
                          traced=True, rehearse=False, t_start=T_START,
                          keep_trace=keep)
    if code:
        return code
    # The runtime has stopped and the chip is free; reading a file asks
    # no backend of JAX, and this keeps it from looking for one.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from bench.harness import xplane

    path = os.path.join(keep, "trace.xplane.pb")
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    by_old = xplane.reduce_planes(planes, programs=cell.programs())
    by_leaf = xplane.reduce_planes(planes, span_prefix=PHASE)
    table, (lo, hi), admits = cross(planes)
    stats = seen["replica"]["stats"]
    ticks = [dict(zip(stats["tick_fields"], t)) for t in stats["tick_log"]]
    guess = seen["run"]["t_open"] + serve_cell.TRACE_AT * args.seconds - lo
    offset, matched = clock_offset(
        admits, [t["start"] for t in ticks
                 if abs(t["start"] - lo - guess) < 10.0], guess)
    traced_ticks = [t for t in ticks if offset is not None
                    and lo <= t["start"] - offset <= hi]
    idle_s = by_leaf["window_s"] - by_leaf["busy_s"]
    done = [r for r in stats["request_phases"]
            if r.get("decode_s") is not None]
    gaps = dict(by_leaf["breakdown"]["idle_gaps"])
    out = {
        "workload": args.workload, "seed": args.seed,
        "window_s": by_leaf["window_s"], "busy_s": by_leaf["busy_s"],
        "idle_s": idle_s,
        "idle_by_old_span": dict(by_old["breakdown"]["idle_gaps"]),
        "idle_by_leaf": {k[len(PHASE):] if k.startswith(PHASE) else k: v
                         for k, v in gaps.items()},
        "idle_under_no_leaf_share":
            100.0 * gaps.get(xplane.NO_SPAN, 0.0) / idle_s if idle_s else 0.0,
        "leaves": {k[len(PHASE):]: v
                   for k, v in by_leaf["annotations"].items()},
        "cross": table,
        "ticks_matched": matched, "ticks_traced": len(traced_ticks),
        "starved_s_traced": sum(t.get("starved_s", 0.0)
                                for t in traced_ticks),
        "sample_s_traced": sum(t["sample_s"] for t in traced_ticks),
        "tick_s_traced": sum(t["tick_s"] for t in traced_ticks),
        # the run's own client medians, and how far any finished
        # request's three sums lie from its decode_s
        "client": {m["name"]: e2e.value(m["name"], seen["run"])
                   for m in cell.end_to_end if m["name"] != "setup_s"},
        "requests_closed": len(done),
        "closure_max_s": max((abs(
            r["burst_read_s"] + r["first_read_s"] + r["host_s"]
            - r["decode_s"]) for r in done), default=None)}
    os.makedirs(os.path.dirname(keep), exist_ok=True)
    with open(keep + ".json", "w") as f:
        json.dump(out, f, indent=1)
    os.remove(path)          # 20-60 MB a cell, and the numbers are out
    print(json.dumps({"idle_by_phase": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
