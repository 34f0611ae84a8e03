"""The phi4flash family (bench/families/phi4flash.py) enters a copy of
the tiny benchmark as files and entries alone, as
bench/tests/test_families.py shows for `tinyalt`: no file that was there
is edited, its cell finds the family, the comparison that decides
`correct` passes the program as it is through the engine's own scoring
entry, and the command itself serves the cell on the CPU (proxy ->
handle -> replica -> PagedLLMEngine with ring and recurrent state) up to
the device check."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(ROOT, "bench", "tests", "data")
SEED = 2**31 + 11


def _digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("phi4flash") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))

    added = os.path.join(DATA, "phi4flashfamily")
    shutil.copytree(os.path.join(added, "configs"),
                    os.path.join(root, "bench", "configs"),
                    dirs_exist_ok=True)
    with open(os.path.join(added, "entries.json")) as f:
        entries = json.load(f)
    grown = {k: v + entries.get(k, []) if isinstance(v, list) else v
             for k, v in tiny.items()}
    with open(manifest, "w") as f:
        json.dump(grown, f)
    after = _digests(os.path.join(root, "bench"))
    assert all(after[f] == h for f, h in before.items()), \
        "a file of bench/ that was there was edited"
    assert sorted(set(after) - set(before)) == [
        os.path.join("configs", "tinyphi-serve.json")]
    assert all(grown[k][:len(v)] == v for k, v in tiny.items()
               if isinstance(v, list)), "an entry that was there was edited"
    return root


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinyphi-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "phi4flash.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert cfg.recurrent and cfg.n_layers == 8 and cfg.window == 24
    assert fam.expert_operand(cell.config) is None
    assert fam.state_operand(cell.config).search(
        "%fusion.1 = f32[4,4,128]{2,1,0} fusion(f32[3,5,4,128]{3,2,1,0} %h)")


def test_what_a_step_needs_at_the_published_sizes():
    """`decode_step_bytes` by ISSUE 30's arithmetic: every weight once,
    8 readers of the full layer's KV at 5,120 B a position, 8 windows of
    at most 512, and 2 x 9 x (5120 x 16 x 4 + 3 x 5120 x 2) B of state a
    lane."""
    from bench.harness import spec

    with open(os.path.join(ROOT, "bench", "configs",
                           "phi4-mini-flash-serve-1chip.json")) as f:
        c = json.load(f)
    fam = spec.family(c)
    assert round(fam.matrix_params(c)["total"] / 1e6) == 3851
    state = fam.state_bytes_per_step(c, 22)
    assert state == 2 * 9 * (5120 * 16 * 4 + 3 * 5120 * 2) * 22
    weights = fam.matrix_params(c)["total"] * 2
    assert fam.decode_step_bytes(c, 22 * 2000, 22) == \
        weights + 8 * 5120 * 22 * 2000 + 8 * 5120 * 22 * 512 + state
    assert fam.decode_step_bytes(c, 22 * 100, 22) == \
        weights + 8 * 5120 * 22 * 100 + 8 * 5120 * 22 * 100 + state
    assert fam.prefill_flops(c, 128, 128 * 129 / 2) > \
        2 * (fam.matrix_params(c)["total"] - 200064 * 2560) * 128


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinyphi-closed", grown_root)
    c = cell.config
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg, device.seeded_params(fam, cfg, SEED),
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"])
    try:
        v = logits_check(e, c, SEED)
    finally:
        e.shutdown()
    assert v["positions"] == 27 == v["decided"]       # 3 x (1 + 8)
    assert v["ok"] and v["worst"] < 1e-4, v           # float32 throughout


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinyphi-closed", "--seed", str(SEED),
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    notes = {}
    for ln in p.stdout.splitlines():
        if ln.startswith('{"bench"'):
            d = json.loads(ln)
            notes[d["bench"]] = d
    assert p.returncode == 3 and "device check" in p.stdout, p.stderr[-2000:]
    phases = notes["phases"]
    assert phases["failed"] == 0 and phases["attempted"] > 0
    assert phases["check"]["ok"] and phases["check"]["positions"] == 27
    assert not any(phases["window_compiles"].values())
    assert phases["engine_stats"]["prefix_hits"] == 0
