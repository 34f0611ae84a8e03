"""The seam between the harness and a model (bench/harness/spec.py
`family`, bench/families/): a second family enters a copy of the tiny
benchmark as files and entries alone and is checked through the engine's
own programs; outside bench/families/ the harness names no key of a
model's `config.json`; a configuration without a family stops the run
before anything loads."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(ROOT, "bench", "tests", "data")
SEED = 2**31 + 9


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
    """A copy of the tiny benchmark, then the second family's files
    copied in beside it and its entries appended to BENCHMARK.json:
    what a later PR does to the real one."""
    root = str(tmp_path_factory.mktemp("families") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    with open(manifest, "w") as f:
        json.dump(tiny, f)
    before = _digests(root)

    added = os.path.join(DATA, "secondfamily")
    for d in ("families", "configs"):
        shutil.copytree(os.path.join(added, d),
                        os.path.join(root, "bench", d), dirs_exist_ok=True)
    with open(os.path.join(added, "entries.json")) as f:
        entries = json.load(f)
    grown = {k: v + entries.get(k, []) if isinstance(v, list) else v
             for k, v in tiny.items()}
    with open(manifest, "w") as f:
        json.dump(grown, f)

    after = _digests(root)
    del before["BENCHMARK.json"], after["BENCHMARK.json"]
    assert all(after[f] == h for f, h in before.items()), \
        "a file that was there was edited"
    assert all(grown[k][:len(v)] == v for k, v in tiny.items()
               if isinstance(v, list)), "an entry that was there was edited"
    return root


@pytest.fixture(scope="module")
def built(grown_root):
    from bench.harness import device, spec
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinyalt-closed", grown_root)
    c = dict(cell.config, param_dtype="bfloat16", compute_dtype="bfloat16",
             cache_dtype="bfloat16")
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg, device.seeded_params(fam, cfg, SEED),
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"])
    return e, c, fam


def test_the_cell_finds_its_family_in_its_own_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinyalt-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        grown_root, "bench", "families", "tinyalt.py")
    assert spec.family(cell.config).__name__ == "bench_family_tinyalt"
    # a cell that was there finds the harness's own first family
    old = spec.load_cell("tiny-closed", grown_root)
    assert old.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "mistral.py")
    assert spec.family(old.config) is spec.family({"family": "mistral"})


@pytest.mark.parametrize("fault", [False, True],
                         ids=["as_it_is", "layer_dropped"])
def test_logits_check_of_the_second_family(built, fault, monkeypatch):
    """Through the engine's own programs, against the second family's
    own reference, at the sizes of its `check` block: 3 x (1 + 12)."""
    from bench.harness import reference
    from bench.harness.deployment import logits_check

    e, c, fam = built
    true_params, forward = e.params, fam.forward
    monkeypatch.setattr(e, "params", true_params)
    monkeypatch.setattr(
        fam, "forward",
        lambda params, *a, **kw: forward(true_params, *a, **kw))
    if fault:
        blocks = dict(true_params["blocks"])
        for name in ("wo", "w_down"):
            blocks[name] = blocks[name].at[-1].set(0)
        e.params = dict(true_params, blocks=blocks)
    v = logits_check(e, c, SEED)
    assert v["positions"] == 39 and v["bound"] == reference.LOGITS_REL_EXPERTS
    assert v["decided"] >= reference.MIN_DECIDED
    if fault:
        assert not v["ok"] and v["worst_decided"] > v["bound"], v
    else:
        assert v["ok"], v


def _run(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    notes = {}
    for ln in p.stdout.splitlines():
        if ln.startswith('{"bench"'):
            d = json.loads(ln)
            notes[d["bench"]] = d
    return p, notes


def test_rehearsal_of_the_second_family_s_cell(grown_root):
    """The command itself, on the CPU: the replica's worker loads the
    family by the file the cell found, serves the window, and the run
    ends non-zero at the device check."""
    p, notes = _run("--root", grown_root, "--workload", "tinyalt-closed",
                    "--seed", str(SEED), "--seconds", "2", "--trace", "0",
                    "--rehearse")
    assert p.returncode == 3 and "device check" in p.stdout
    phases = notes["phases"]
    assert phases["failed"] == 0 and phases["attempted"] > 0
    assert phases["check"]["ok"] and phases["check"]["positions"] == 39
    assert not any(phases["window_compiles"].values())


@pytest.mark.parametrize("family,says", [
    (None, 'names no "family"'), ("nosuch", "no families/nosuch.py")],
    ids=["no_key", "no_file"])
def test_a_configuration_without_a_family_stops_the_run(
        grown_root, tmp_path, family, says):
    from bench.harness import spec

    root = tmp_path / "root"
    shutil.copytree(grown_root, root)
    path = root / "bench" / "configs" / "tiny-serve.json"
    c = json.loads(path.read_text())
    del c["family"]
    if family:
        c["family"] = family
    path.write_text(json.dumps(c))
    with pytest.raises(spec.SpecError, match=re.escape(says)):
        spec.load_cell("tiny-closed", str(root))
    p, notes = _run("--root", str(root), "--workload", "tiny-closed",
                    "--seed", "1", "--seconds", "2", "--trace", "0",
                    "--rehearse", timeout=60)
    assert p.returncode == 2 and not notes
    (line,) = [ln for ln in p.stderr.splitlines() if ln.startswith("bench:")]
    assert says in line and "tiny-serve.json" in line


# ---------------------------------------------------------------------------
# Outside bench/families/ the harness knows no model
# ---------------------------------------------------------------------------
# Keys of a configuration file that are the deployment's or the
# harness's own, not the model's `config.json`.
_OWN_KEYS = {"name", "kind", "family", "source", "architectures",
             "published", "reduced", "assumed", "deployment", "memory",
             "check", "engine", "mesh", "optimizer", "param_dtype",
             "compute_dtype", "cache_dtype", "vocab_size"}


def _harness_files():
    files = [os.path.join(ROOT, "bench", "run.py")]
    for d in ("harness", "tools"):
        top = os.path.join(ROOT, "bench", d)
        files += [os.path.join(top, f) for f in sorted(os.listdir(top))
                  if f.endswith(".py")]
    return files


def _model_keys():
    keys = set()
    for top in (os.path.join(ROOT, "bench", "configs"),
                os.path.join(DATA, "tinyroot", "configs"),
                os.path.join(DATA, "secondfamily", "configs")):
        for f in os.listdir(top):
            with open(os.path.join(top, f)) as fh:
                keys |= set(json.load(fh))
    return keys - _OWN_KEYS


def test_outside_the_families_the_harness_names_no_model_key():
    """As code names one: a string that is the key (a subscript, a
    `.get`, a membership test) or a keyword argument of that name."""
    import ast

    keys = _model_keys()
    assert {"hidden_size", "num_hidden_layers", "num_local_experts",
            "rope_theta", "sliding_window", "depth", "experts"} <= keys
    named = {}
    for path in _harness_files():
        with open(path) as f:
            tree = ast.parse(f.read())
        hits = sorted(
            {(n.lineno, n.value) for n in ast.walk(tree)
             if isinstance(n, ast.Constant) and n.value in keys}
            | {(n.value.lineno, n.arg) for n in ast.walk(tree)
               if isinstance(n, ast.keyword) and n.arg in keys})
        if hits:
            named[os.path.relpath(path, ROOT)] = hits
    assert not named, named


def test_only_the_train_entry_imports_the_program_s_models():
    imports = {}
    for path in _harness_files():
        with open(path) as f:
            hits = [ln.strip() for ln in f if "ray_tpu.models" in ln
                    and ("import" in ln)]
        if hits:
            imports[os.path.relpath(path, ROOT)] = hits
    assert imports == {os.path.join("bench", "harness", "train_cell.py"): [
        "from ray_tpu.models.training import make_train_step"]}, imports
