"""The command end to end on the CPU, with the tiny configuration files
kept for this test only (bench/tests/data/tinyroot: configurations,
mixes, and a BENCHMARK.json of their cells alone): the same control flow
as on the chip, which must END NON-ZERO at the device check and print no
result line.  The metrics are the real ones: every tiny cell stands in
for the real cells of its kind and loop, and takes their entries of the
real BENCHMARK.json and the real bench/metrics.  Slow (a runtime starts
for each case)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "bench", "tests", "data", "tinyroot")
sys.path.insert(0, ROOT)
NOISE = ("[gcs]", "[raylet]", "(worker=", "(actor=")


def _run(*args, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines()
             if ln and not ln.startswith(NOISE)]
    notes = {}
    for ln in lines:
        if ln.startswith('{"bench"'):
            d = json.loads(ln)
            notes[d["bench"]] = d
    return p.returncode, lines, notes


def _kind(root, workload):
    from bench.harness import spec

    cell = spec.load_cell(workload["name"], root)
    return cell.config["kind"], cell.traffic["loop"]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny") / "root")
    shutil.copytree(TINY, os.path.join(root, "bench"))
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"),
              os.path.join(root, "BENCHMARK.json"))
    os.symlink(os.path.join(ROOT, "bench", "metrics"),
               os.path.join(root, "bench", "metrics"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    tiny["end_to_end"], tiny["per_layer"] = [], []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(tiny, f)
    stand_in = {_kind(root, w): w["name"] for w in tiny["workloads"]}
    real_kind = {w["name"]: _kind(ROOT, w) for w in real["workloads"]}
    for key in ("end_to_end", "per_layer"):
        for m in real[key]:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = sorted({stand_in[real_kind[w]]
                                         for w in m["workloads"]})
            tiny[key].append(m)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(tiny, f)
    return root


def _no_result(lines):
    return not any(ln.startswith('{"correct"') for ln in lines)


@pytest.mark.parametrize("cell,devices", [
    ("tiny-chat", 1), ("tiny-closed", 1), ("tiny-train", 4)])
def test_rehearsal_runs_the_cell_and_fails_the_device_check(
        tiny_root, cell, devices):
    rc, lines, notes = _run("--root", tiny_root, "--workload", cell, "--seed",
                            str(2**31 + 5), "--seconds", "2", "--trace", "0",
                            "--rehearse", devices=devices)
    assert rc == 3 and _no_result(lines)
    assert any("device check" in ln for ln in lines)
    phases = notes["phases"]
    assert phases["failed"] == 0 and phases["attempted"] > 0
    assert phases["check"]["ok"]
    assert not any(phases["window_compiles"].values())
    if cell == "tiny-chat":
        assert phases["attempted"] == notes["schedule"]["planned"] == 8


def test_without_the_chips_there_is_no_result():
    rc, lines, _ = _run("--workload", "mistral7b-chat", "--seed", "1",
                        "--seconds", "2", "--trace", "0", timeout=120)
    assert rc != 0 and _no_result(lines)


def test_a_request_over_the_engine_limit_stops_the_run_before_set_up(
        tiny_root, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root, symlinks=True)
    mix = root / "bench" / "traffic" / "tiny-closed.json"
    t = json.loads(mix.read_text())
    t["prompt_len"] = {"kind": "uniform", "min": 100, "max": 300}
    mix.write_text(json.dumps(t))
    rc, lines, notes = _run("--root", str(root), "--workload", "tiny-closed",
                            "--seed", "1", "--seconds", "2", "--trace", "0",
                            "--rehearse", timeout=60)
    assert rc == 2 and _no_result(lines) and "window" not in notes
