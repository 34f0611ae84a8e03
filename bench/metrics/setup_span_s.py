"""`setup_span_s.<part>`: the seconds of the replica's `serve.setup.<part>`
span (`engine_stats()["setup"]["spans"]`, which `bench_report` hands over
in `ctx["replica"]["stats"]`): `end_ts - start_ts`, on `time.time()`, the
clock of the harness's `t_open`.  `records`, for the other `setup_*`
readers too: the replica's spans and compile-log entries that ended
before the window opened, since only those are part of `setup_s`.  A
program without the records (a parent commit) gives None."""


def records(ctx):
    """(spans, compile-log entries) that ended by `t_open`, or None."""
    setup = ctx["replica"].get("stats", {}).get("setup")
    if not setup:
        return None
    t_open = ctx["run"]["t_open"]
    return ([s for s in setup["spans"] if s["end_ts"] <= t_open],
            [e for e in setup["compile_log"] if e["end_ts"] <= t_open])


def read(ctx, span: str):
    got = records(ctx)
    spans = [s for s in got[0] if s["name"] == span] if got else []
    if not spans:
        return None
    return sum(s["end_ts"] - s["start_ts"] for s in spans)
