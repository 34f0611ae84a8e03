"""Device time by kernel and by op: one traced run of a served cell whose
trace is kept, every op of the first device's timeline kept (the harness's
own reduction keeps the 400 largest) and given to the one of `--scopes`
that its name starts with, by program.  An op event of a v5e trace carries
its HLO text and no name stack (my chip run, PR 57: its stats are the
device's offsets alone), so a `jax.named_scope` cannot be read back from a
fusion; a Pallas kernel named for its scope can.

    chiprun -- python bench/tools/scope_times.py --workload xing4-longprompt \
        --scopes hc_pre hc_sinkhorn hc_post

Prints the run's own result line, then for each jitted program its
executions, its device seconds and its seconds by scope (`other`: none of
them); writes to `chiprun_out/scope_times/<workload>.json` every op under
each scope with its seconds, count and the head of its HLO text.  A tool:
its table goes into PERF.md by hand.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import bisect       # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _scope_of(name: str, scopes) -> str:
    """The one of `scopes` that the op's name starts with, else `other`."""
    op = name.lstrip("%")
    return next((s for s in scopes if op.startswith(s + ".")), "other")


def by_scope(planes, scopes):
    from bench.harness import xplane

    host = []
    lines = None
    for plane in planes:
        if xplane._DEVICE.match(plane.name) and lines is None:
            got = {ln.name: ln for ln in plane.lines}
            if xplane.OPS_LINE in got:
                lines = got
        elif plane.name == xplane.HOST_PLANE:
            host = [xplane._events(ln) for ln in plane.lines]
    ops = xplane._events(lines[xplane.OPS_LINE])
    mods = xplane._events(lines[xplane.MODULES_LINE])
    launched = xplane.launch_names(host)
    by_print = {}
    for _, _, name, stats in mods:
        fn = launched.get(stats.get("run_id"))
        if fn:
            by_print[name] = fn
    names = [by_print.get(m[2], xplane.program_of(m[2])) for m in mods]
    starts = [m[0] for m in mods]
    out = {}
    for (s, e, _, _), prog in zip(mods, names):
        p = out.setdefault(prog, {"count": 0, "seconds": 0.0, "scopes": {},
                                  "ops": {}})
        p["count"] += 1
        p["seconds"] += e - s
    for (s, e, name, _), own in zip(ops, xplane.self_times(ops)):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1] or not own:
            continue
        p = out[names[i]]
        scope = _scope_of(name, scopes)
        p["scopes"][scope] = p["scopes"].get(scope, 0.0) + own
        o = p["ops"].setdefault(scope, {})
        label = xplane.op_label(name)
        rec = o.setdefault(label, {"seconds": 0.0, "count": 0,
                                   "text": name[:240]})
        rec["seconds"] += own
        rec["count"] += 1
    for p in out.values():
        p["ops"] = {scope: dict(sorted(o.items(),
                                       key=lambda kv: -kv[1]["seconds"]))
                    for scope, o in p["ops"].items()}
    return out


def main() -> int:
    from bench.harness import report, serve_cell, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5_700_000_101)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--scopes", nargs="+", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    keep = os.path.join(spec.ROOT, "chiprun_out", "scope_times",
                        args.workload)
    code = serve_cell.run(cell, seed=args.seed, seconds=args.seconds,
                          traced=True, rehearse=False, t_start=T_START,
                          keep_trace=keep)
    if code:
        return code
    # The runtime has stopped and the chip is free; reading a file asks
    # no backend of JAX, and this keeps it from looking for one.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    path = os.path.join(keep, "trace.xplane.pb")
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    out = {"workload": args.workload, "seed": args.seed,
           "programs": by_scope(planes, set(args.scopes))}
    with open(keep + ".json", "w") as f:
        json.dump(out, f, indent=1)
    os.remove(path)
    brief = {prog: {"count": p["count"], "seconds": p["seconds"],
                    "scopes": p["scopes"]}
             for prog, p in out["programs"].items()}
    print(json.dumps({"scope_times": brief}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
