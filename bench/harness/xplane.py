"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, time per jitted program, the ops that took most of it, and the idle
gaps by what the host was doing.  Read with `jax.profiler.ProfileData`
alone.  Checked against a small recorded trace in bench/tests.

What a v5e trace looks like (looked at by hand, PR 23):

A TPU device plane is named `/device:TPU:<n>`.  Its line `XLA Modules`
has one event per execution of a jitted program, named
`jit_<function>(<fingerprint>)` with the stat `run_id`; a function jitted
through `functools.partial` (the engine's) has no name of its own and is
`jit__unknown(<fingerprint>)`.  Its line `XLA Ops` is the core's
sequential timeline: one event per HLO op, named by the op's whole HLO
text (`%fusion.3 = bf16[4,4096]{...} fusion(...)`), nested where a
`while` contains its body's ops.  `Async XLA Ops` holds the spans of
asynchronous copies and collectives, which overlap the core's ops and
are not busy time.  The device's clock runs about a millisecond off the
host's.

The host knows every launch by name: a `PjitFunction(<name>)` event on a
Python thread.  Flow stats (`_pt`/`_p` on the producer, `_ct`/`_c` on
the consumer) lead from it through the runtime's threads to
`DoEnqueueProgram`, which carries the `run_id` of the device's module
event.  One launch resolved names its fingerprint, and so every
execution of that program in the trace, also those launched before the
trace began.

Host threads are lines of the plane `/host:CPU`; a
`jax.profiler.TraceAnnotation` is an event there under its own name, its
keyword arguments the event's stats: spans `bench.<layer>.*` say what the
host was doing, zero-length `bench.count.*` carry counts (tokens, lanes)
on the trace's own clock; a counter is reduced to the sums of its
arguments, `count` events, and `each` event's own arguments.  All times are nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from bench.harness.stats import union_seconds

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\((\d+)\))?$")
_PJIT = re.compile(r"^PjitFunction\((.*)\)$")
_OP = re.compile(r"^%?([^\s=]+)\s*=")
_RESULT = re.compile(r"=\s*\(?\s*([a-z]+\d*)\[([\d,]*)\]")
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
MODULES_LINE, OPS_LINE, ASYNC_LINE = "XLA Modules", "XLA Ops", "Async XLA Ops"
HOST_PLANE = "/host:CPU"
NO_SPAN = "host:no_bench_span"

Event = Tuple[float, float, str, Dict[str, Any]]   # start_s, end_s, name, stats


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        out.append((start, start + ev.duration_ns * 1e-9, ev.name,
                    dict(ev.stats)))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def program_of(module_event_name: str) -> str:
    return _MODULE.match(module_event_name).group(1)


def launch_names(host_lines: List[List[Event]]) -> Dict[int, str]:
    """run_id -> the name of the jitted function the host launched, by
    following the flow stats from each `PjitFunction(<name>)` event."""
    consumers = {}
    for li, evs in enumerate(host_lines):
        for ei, (_, _, _, st) in enumerate(evs):
            if "_c" in st:
                consumers[(st.get("_ct"), st["_c"])] = (li, ei)

    def family(li: int, ei: int):
        evs = host_lines[li]
        yield evs[ei][3]
        j = ei + 1
        while j < len(evs) and evs[j][0] < evs[ei][1]:
            yield evs[j][3]
            j += 1

    def run_id(li: int, ei: int, depth: int = 0) -> Optional[int]:
        if depth > 6:
            return None
        stats = list(family(li, ei))
        for st in stats:
            if "run_id" in st and "_p" in st:
                return st["run_id"]
        for st in stats:
            nxt = consumers.get((st.get("_pt"), st.get("_p")))
            if nxt and nxt != (li, ei):
                r = run_id(*nxt, depth + 1)
                if r is not None:
                    return r
        return None

    names: Dict[int, str] = {}
    for li, evs in enumerate(host_lines):
        outer_end = float("-inf")
        for ei, (start, end, name, _) in enumerate(evs):
            m = _PJIT.match(name)
            if m and start >= outer_end:       # the outer of a nested pair
                outer_end = end
                r = run_id(li, ei)
                if r is not None:
                    names[r] = m.group(1)
    return names


def op_label(name: str) -> str:
    """`<op>_<dtype>_<dims>_` from an op event's name, which is the op's
    HLO text; the name alone where it is not."""
    m = _OP.match(name)
    if not m:
        return name
    r = _RESULT.search(name)
    if not r:
        return m.group(1)
    return f"{m.group(1)}_{r.group(1)}_" + "".join(
        d + "_" for d in r.group(2).split(",") if d)


def op_name(name: str) -> str:
    m = _OP.match(name)
    return m.group(1) if m else name


def self_times(events: List[Event]) -> List[float]:
    """Each event's duration less its direct children's (events nested in
    it on the same line), so that nested ops are not counted twice."""
    selfs = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i, (start, end, _, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= end - start
        stack.append(i)
    return [max(s, 0.0) for s in selfs]


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    cur = lo
    for s, e in sorted(busy):
        if s > cur:
            yield cur, min(s, hi)
        cur = max(cur, e)
        if cur >= hi:
            return
    if cur < hi:
        yield cur, hi


def _overlap(intervals: List[Tuple[float, float]], starts: List[float],
             lo: float, hi: float) -> float:
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0.0
    while i < len(intervals) and intervals[i][0] < hi:
        total += max(0.0, min(hi, intervals[i][1]) - max(lo, intervals[i][0]))
        i += 1
    return total


def reduce_planes(planes: Iterable, programs: Optional[List[str]] = None,
                  span_prefix: str = "bench.",
                  counter_prefix: str = "bench.count.") -> Dict[str, Any]:
    """See the module docstring.  `programs`: jitted functions the caller
    will read by name; one that ran no module event in the trace is an
    error that says which."""
    device_planes, host_lines = [], []
    for plane in planes:
        if _DEVICE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                device_planes.append((plane.name, lines))
        elif plane.name == HOST_PLANE:
            host_lines = list(plane.lines)
    if not device_planes:
        raise LookupError("the trace has no TPU device plane with an "
                          f"{OPS_LINE!r} line: no operation ran on a device")

    annotations: Dict[str, List[Tuple[float, float]]] = {}
    counters: Dict[str, Dict[str, float]] = {}
    t_lo, t_hi = float("inf"), float("-inf")
    host_events = [_events(ln) for ln in host_lines]
    for evs in host_events:
        for start, end, name, stats in evs:
            t_lo, t_hi = min(t_lo, start), max(t_hi, end)
            if name.startswith(counter_prefix):
                c = counters.setdefault(name, {"count": 0, "each": []})
                c["count"] += 1
                own = {k: v for k, v in stats.items()
                       if isinstance(v, (int, float))}
                c["each"].append(own)
                for k, v in own.items():
                    c[k] = c.get(k, 0) + v
            elif name.startswith(span_prefix):
                annotations.setdefault(name, []).append((start, end))
    launched = launch_names(host_events)

    per_device = []
    progs: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, Dict[str, Any]] = {}
    first_busy: List[Tuple[float, float]] = []
    for n, (_, lines) in enumerate(device_planes):
        op_events = _events(lines[OPS_LINE])
        mod_events = _events(lines[MODULES_LINE]) \
            if MODULES_LINE in lines else []
        for start, end, *_ in op_events + mod_events:
            t_lo, t_hi = min(t_lo, start), max(t_hi, end)
        busy = [(s, e) for s, e, _, _ in op_events]
        per_device.append(union_seconds(busy))
        if n:
            continue            # programs and ops from the first device:
        first_busy = busy       # the others run the same SPMD program
        selfs = self_times(op_events)
        leaves = [(e[0], e[1], op_name(e[2]))
                  for e, own in zip(op_events, selfs)
                  if own >= (e[1] - e[0]) * 0.999]
        exposed = sum(e - s for s, e, n in leaves if _COLLECTIVE.match(n))
        spans = [(s, e) for s, e, n in leaves if _COLLECTIVE.match(n)]
        if ASYNC_LINE in lines:
            spans += [(s, e) for s, e, n, _ in _events(lines[ASYNC_LINE])
                      if _COLLECTIVE.match(op_name(n))]
        collectives = {"seconds": union_seconds(spans),
                       "exposed_seconds": exposed}
        # A fingerprint is named by any of its launches the host saw.
        by_print: Dict[str, str] = {}
        for _, _, name, stats in mod_events:
            fn = launched.get(stats.get("run_id"))
            if fn:
                by_print[name] = fn
        mod_names = [by_print.get(m[2], program_of(m[2]))
                     for m in mod_events]
        mod_starts = [m[0] for m in mod_events]
        for (start, end, _, _), prog in zip(mod_events, mod_names):
            p = progs.setdefault(prog, {"seconds": 0.0, "count": 0})
            p["seconds"] += end - start
            p["count"] += 1
        for (start, end, name, _), own in zip(op_events, selfs):
            i = bisect.bisect_right(mod_starts, start) - 1
            prog = mod_names[i] \
                if i >= 0 and start < mod_events[i][1] else "?"
            o = ops.setdefault(f"{prog}/{op_label(name)}",
                               {"program": prog, "seconds": 0.0,
                                "count": 0, "text": name[:600]})
            o["seconds"] += own
            o["count"] += 1
    for name in programs or []:
        if name not in progs:
            raise LookupError(
                f"traced run: no execution of the jitted program {name!r} "
                f"in the trace; it has {sorted(progs)}")

    gaps: Dict[str, float] = {}
    sorted_ann = {k: sorted(v) for k, v in annotations.items()}
    ann_starts = {k: [s for s, _ in v] for k, v in sorted_ann.items()}
    for lo, hi in _gaps(first_busy, t_lo, t_hi):
        left = hi - lo
        for name, spans in sorted_ann.items():
            got = _overlap(spans, ann_starts[name], lo, hi)
            if got:
                gaps[name] = gaps.get(name, 0.0) + got
                left -= got
        if left > 1e-9:
            gaps[NO_SPAN] = gaps.get(NO_SPAN, 0.0) + left
    top = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])
    return {"window_s": t_hi - t_lo,
            "busy_s": sum(per_device) / len(per_device),
            "busy_s_per_device": per_device,
            "programs": progs,
            "ops": {k: v for k, v in top[:400]},
            "counters": counters,
            "collectives": collectives,
            "annotations": {k: {"seconds": union_seconds(v),
                                "count": len(v)}
                            for k, v in annotations.items()},
            "breakdown": {
                "device_ops": [[k, v["seconds"]] for k, v in top[:10]],
                "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                    key=lambda kv: -kv[1])[:10]}}


def reduce_file(path: str, programs: Optional[List[str]] = None
                ) -> Dict[str, Any]:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         programs=programs)


def dump(path: str, per_line: int = 6) -> None:
    """Look at a trace by hand: planes, lines, a few events each."""
    import jax

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for ln in plane.lines:
            evs = list(ln.events)
            print("  LINE", ln.name, len(evs))
            for ev in evs[:per_line]:
                print("     ", ev.name, ev.start_ns, ev.duration_ns,
                      {k: (v if not isinstance(v, str) else v[:160])
                       for k, v in dict(ev.stats).items()})


if __name__ == "__main__":
    import sys

    dump(sys.argv[1])      # python -m bench.harness.xplane <file>
