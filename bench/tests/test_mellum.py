"""The mellum family (bench/families/mellum.py) enters a copy of the tiny
benchmark as files and entries alone, as bench/tests/test_phi4flash.py
shows for `phi4flash`: no file that was there is edited, its cell finds
the family, the comparison that decides `correct` passes the program as it
is through the engine's own scoring entry with every position decided by
handed-over routing, what a step needs is counted from the published
sizes, and the command itself serves the cell on the CPU (proxy -> handle
-> replica -> PagedLLMEngine with window rings) up to the device check."""
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
    root = str(tmp_path_factory.mktemp("mellum") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))

    added = os.path.join(DATA, "mellumfamily")
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
        os.path.join("configs", "tinymellum-serve.json")]
    assert all(grown[k][:len(v)] == v for k, v in tiny.items()
               if isinstance(v, list)), "an entry that was there was edited"
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "mellum2-12b-serve-1chip.json")) as f:
        return json.load(f)


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinymellum-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "mellum.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert cfg.layer_pattern == ("window", "window", "window", "full")
    assert cfg.n_layers == 8 and cfg.window == 12 and cfg.head_dim == 16
    assert cfg.yarn.factor == 4.0 and cfg.qk_norm and cfg.d_expert == 24
    c = _published()
    assert fam.expert_operand(c).search(
        "fusion(bf16[8,64,2304,896]{3,2,1,0} %w_gate, s32[] %ex)")
    assert fam.ring_operand(c).search(
        "%fusion.7 = f32[33,1,4,8,1152]{4,3,2,1,0} fusion("
        "bf16[6,33,1152,4,128]{4,3,2,1,0} %wk, s32[] %layer)")
    assert not fam.ring_operand(c).search("bf16[2,16385,16,4,128]{4,3,2,1,0}")


def test_the_published_configuration_is_the_catalog_s_but_for_depth():
    """Every key of the source under the source's name; the one cut is
    `num_hidden_layers`, to two whole periods."""
    c = _published()
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 28}
    assert (c["num_hidden_layers"], len(c["layer_types"])) == (8, 28)
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"]) == (2304, 128, 32, 4)
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["intermediate_size"]) == (
                64, 8, 896, 7168)
    assert c["vocab_size"] == 98304 and c["sliding_window"] == 1024
    full = c["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["attention_factor"]) == (
        "yarn", 16, 1.2772588722239782)
    from bench.harness import spec

    fam = spec.family(c)
    assert fam.layer_kinds(c) == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 2
    assert round(fam.total_params(dict(c, num_hidden_layers=28)) / 1e7) \
        == 1215
    # the ramp's ends, by ISSUE 34's arithmetic: pairs 18 .. 35 blend
    inv, factor = fam.inv_frequencies(c, "full_attention")
    plain, one = fam.inv_frequencies(c, "sliding_attention")
    assert one == 1.0 and factor == full["attention_factor"]
    ratio = [float(x) for x in inv / plain]
    assert ratio[18] == 1.0 and ratio[17] == 1.0 and ratio[19] < 1.0
    assert abs(ratio[35] - 1 / 16) < 1e-6 and ratio[34] > 1 / 16
    assert abs(ratio[63] - 1 / 16) < 1e-6


def test_what_a_step_needs_at_the_published_sizes():
    """`decode_step_bytes` by ISSUE 34's arithmetic: every weight outside
    the experts once and the head, 64 (1 - (7/8)^lanes) experts of 12.4 MB
    a layer, two full layers' KV at 2 KB a position, six windows of at
    most 1024 rows a lane."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    one_expert = 3 * 2304 * 896 * 2
    assert fam.expected_routed_experts(c, 1) == 8
    assert round(fam.expected_routed_experts(c, 6), 1) == 35.3
    assert fam.expert_bytes_per_step(c, 6) == \
        8 * fam.expected_routed_experts(c, 6) * one_expert
    dense = 8 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64) * 2 \
        + 2304 * 98304 * 2
    assert fam.decode_step_bytes(c, 6 * 2000, 6) == \
        dense + fam.expert_bytes_per_step(c, 6) + 2 * 2048 * 6 * 2000 \
        + 6 * 6 * 1024 * 2048
    assert fam.ring_bytes_per_step(c, 6 * 2000, 6) == 6 * 6 * 1024 * 2048
    assert fam.ring_bytes_per_step(c, 6 * 100, 6) == 6 * 6 * 100 * 2048
    # a 128-token chunk at position 0: 8 experts a token, not 64
    flops = fam.prefill_flops(c, 128, 128 * 129 / 2)
    per_token = 2 * 8 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64
                         + 8 * 3 * 2304 * 896)
    assert flops == per_token * 128 + 4 * 4096 * 8 * (128 * 129 / 2)


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinymellum-closed", grown_root)
    c = cell.config
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg, device.seeded_params(fam, cfg, SEED),
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"])
    try:
        v = logits_check(e, c, SEED)
        assert len(fam._HANDED) == 3                  # a routing a lane
        tokens = next(iter(fam._HANDED))
        fam._HANDED.clear()                 # nothing handed over: its own
        import jax.numpy as jnp             # top-k and its true margin
        import numpy as np

        _, own = fam.forward(
            e.params, jnp.asarray(np.frombuffer(tokens, np.int32)), c)
    finally:
        e.shutdown()
    assert v["positions"] == 27 == v["decided"]       # 3 x (1 + 8)
    assert v["ok"] and v["worst"] < 1e-4, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]
    # the trap ISSUE 34 found: over 8 layers of top-4 of 8 not one of the
    # lane's 108 positions keeps its own margin over ROUTER_MARGIN
    assert 0.0 < float(own.max()) < 0.2


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinymellum-closed", "--seed", str(SEED),
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
    assert phases["check"]["decided"] == 27
    assert not any(phases["window_compiles"].values())
    assert phases["engine_stats"]["prefix_hits"] == 0


def test_window_attn_roofline_reads_the_ring_ops_and_nothing_else():
    """The reader over a hand-made reduction: the ops of the burst whose
    text shows a ring-shaped array count, a `while` that only carries the
    rings does not, and a trace without such ops (a program that keeps no
    ring, as the parent's) gives None rather than raising."""
    from bench.harness import spec

    c = _published()
    cell = type("Cell", (), {"config": c})()
    read = spec.load_file(os.path.join(
        ROOT, "bench", "metrics", "window_attn_roofline.py"),
        "bench_metric_").read
    ring = "bf16[6,33,1152,4,128]{4,3,2,1,0}"
    ops = {
        "a": {"program": "paged_decode_burst", "seconds": 0.010,
              "text": f"%fusion.1 = f32[33,1,4,8,1152] fusion({ring} %wk)"},
        "b": {"program": "paged_decode_burst", "seconds": 0.500,
              "text": f"%while.2 = (s32[], {ring}) while((s32[], {ring}) %t)"},
        "c": {"program": "paged_prefill_chunk", "seconds": 0.300,
              "text": f"%fusion.3 = f32[1,128,4,8,1152] fusion({ring} %wk)"},
        "d": {"program": "paged_decode_burst", "seconds": 0.200,
              "text": "%fusion.4 = bf16[4,98304] fusion(bf16[2304,98304] %h)"},
    }
    ctx = {"cell": cell, "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"paged_decode_burst": {"count": 5}},
                     "counters": {"bench.count.decode": {"each": [
                         {"lanes": 2, "kv_tokens": 2 * 3000}]}},
                     "ops": ops}}
    args = {"program": "paged_decode_burst", "counter": "bench.count.decode"}
    least = 6 * 2 * 1024 * 2048 / 819e9           # two lanes past the window
    assert abs(read(ctx, **args) - 100 * least / (0.010 / 40)) < 1e-9
    ctx["trace"]["ops"] = {"d": ops["d"]}
    assert read(ctx, **args) is None
    ctx["trace"]["counters"] = {}
    assert read(ctx, **args) is None
