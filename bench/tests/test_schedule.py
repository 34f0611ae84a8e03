"""Run by hand and in the rehearsal (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider
"""
import itertools
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import schedule, spec  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
SERVED = [w["name"] for w in BENCH["workloads"]
          if spec.load_cell(w["name"]).config["kind"] == "serve"]
SEEDS = (0, 1, 7, 2**31 + 12345)


def _generated(cell, seed, seconds):
    t, vocab = cell.traffic, cell.config["vocab_size"]
    if t["loop"] == "open":
        return schedule.open_schedule(t, cell.load["rate_rps"], seconds,
                                      seed, vocab)
    return list(itertools.islice(
        schedule.closed_schedule(t, seed, vocab), 3 * t["block"]))


def _shape(reqs):
    return [(q.index, q.due_s, q.prompt_len, q.max_tokens) for q in reqs]


@pytest.mark.parametrize("name", SERVED)
def test_schedule_is_a_pure_function_of_its_arguments(name):
    cell = spec.load_cell(name)
    a = _generated(cell, 5, BENCH["run_seconds"])
    assert a == _generated(cell, 5, BENCH["run_seconds"])
    b = _generated(cell, 6, BENCH["run_seconds"])
    assert _shape(a) == _shape(b)                 # the seed moves no length,
    assert [q.tokens for q in a] != [q.tokens for q in b]  # only the tokens


@pytest.mark.parametrize("name", SERVED)
def test_attempted_and_lengths_do_not_depend_on_the_seed(name):
    cell = spec.load_cell(name)
    runs = [_generated(cell, s, BENCH["run_seconds"]) for s in SEEDS]
    assert all(_shape(r) == _shape(runs[0]) for r in runs[1:])
    if cell.traffic["loop"] == "open":
        n = round(cell.load["rate_rps"] * BENCH["run_seconds"])
        assert len(runs[0]) == n
        due = [q.due_s for q in runs[0]]
        assert due == sorted(due)
        assert 0 <= due[0] and due[-1] <= BENCH["run_seconds"]
        want = sorted(zip(
            schedule.quantile_grid(cell.traffic["prompt_len"], n),
            sorted(schedule.quantile_grid(cell.traffic["max_tokens"], n))))
        got = sorted(q.prompt_len for q in runs[0])
        assert got == [p for p, _ in want]


@pytest.mark.parametrize("name", SERVED)
def test_every_request_of_every_mix_fits_its_engine(name):
    cell = spec.load_cell(name)
    for seed in SEEDS:
        reqs = _generated(cell, seed, BENCH["run_seconds"])
        spec.check_requests(reqs, cell.config["engine"])
        assert all(len(q.tokens) == q.prompt_len for q in reqs)
        assert all(1 <= t < cell.config["vocab_size"]
                   for q in reqs for t in q.tokens[:8])


def test_a_request_the_engine_would_cut_short_is_a_fault_in_a_file():
    cell = spec.load_cell(SERVED[0])
    engine = cell.config["engine"]
    limit = spec.request_limit(engine)
    assert limit == engine["max_len"] - 2 - 8
    ok = schedule.Request(0, 0.0, limit - 32, 32, [])
    spec.check_requests([ok], engine)
    for bad in (schedule.Request(0, 0.0, limit - 31, 32, []),
                schedule.Request(0, 0.0, 0, 8, [])):
        with pytest.raises(spec.SpecError):
            spec.check_requests([bad], engine)


def test_quantile_grid():
    grid = schedule.quantile_grid(
        {"kind": "lognormal", "median": 384, "sigma": 0.8, "min": 32,
         "max": 2048}, 101)
    assert grid == sorted(grid) and grid[50] == 384
    assert grid[0] >= 32 and grid[-1] <= 2048
    assert schedule.quantile_grid({"kind": "uniform", "min": 0, "max": 8},
                                  4) == [1, 3, 5, 7]
    assert schedule.quantile_grid({"kind": "const", "value": 5}, 3) == [5] * 3


def test_benchmark_names_resolve_to_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            assert "reader" in m or spec.metric_file(
                spec.BENCH_DIR, m["name"], ".py")
