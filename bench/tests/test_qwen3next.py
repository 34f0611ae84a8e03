"""The qwen3next family (bench/families/qwen3next.py) enters a copy of the
tiny benchmark as files and entries alone, as bench/tests/test_laguna.py
shows for `laguna`: no file that was there is edited, its cell finds the
family, the published configuration is the catalog's but for its three
cuts, what a step and a launch need is counted from the published sizes,
the two new device readers read the mixer's ops and nothing else, and the
command itself serves the cell on the CPU (proxy -> handle -> replica ->
PagedLLMEngine with recurrent state by slot and a held share) up to the
device check."""
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
SEED = 2**31 + 17
CELL = "qwen3next-agent"


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
    root = str(tmp_path_factory.mktemp("qwen3next") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))
    added = os.path.join(DATA, "qwen3nextfamily")
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
        os.path.join("configs", "tinyqwen3next-serve.json")]
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen3-next-80b-a3b-serve-1chip.json")) as f:
        return json.load(f)


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinyqwen3next-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "qwen3next.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert cfg.layer_pattern == ("linear", "linear", "linear", "full")
    assert cfg.n_layers == 8 and cfg.recurrent and cfg.launch_spans_chunks
    assert cfg.experts_held == (4, 4) and cfg.n_experts == 8
    assert (cfg.attn_gate, cfg.rotary_dim, cfg.linear_chunk) == (16, 4, 8)
    c = _published()
    assert fam.expert_operand(c).search(
        "fusion(bf16[8,128,2048,512]{3,2,1,0} %w_gate, s32[] %ex)")
    assert not fam.expert_operand(c).search("bf16[8,2048,512]{2,1,0}")
    assert fam.state_operand(c).search("f32[8,32,128,128]{3,2,1,0} %gather")
    assert not fam.state_operand(c).search("f32[6,9,32,128,128]{4,3,2,1,0}")
    assert not fam.state_operand(c).search("bf16[8,32,128,128]{3,2,1,0}")
    for text in ("f32[6,9,32,128,128]{4,3,2,1,0} %lstate",
                 "bf16[8,32,128,128]", "f32[1,8,32,64,64]{4,3,2,1,0}"):
        assert fam.scan_operand(c).search(text), text
    assert not fam.scan_operand(c).search("bf16[2,8193,16,2,256]")
    for text in ("bf16[6,9,3,8192]{3,2,1,0} %lconv", "bf16[8,4,8192]",
                 "bf16[6,2048,12288]{2,1,0} %in_qkvz",
                 "bf16[6,4096,2048]{2,1,0} %out_proj",
                 "f32[8,32,128,128]{3,2,1,0}"):
        assert fam.mixer_operand(c).search(text), text
    for text in ("bf16[2,4096,2048]{2,1,0} %wo", "bf16[8,128,2048,512]",
                 "bf16[2,8193,16,2,256]", "bf16[2048,37984]"):
        assert not fam.mixer_operand(c).search(text), text


def test_the_published_configuration_is_the_catalog_s_but_for_its_cuts():
    """Every number of the catalog's row under its key; the cuts are depth
    (two whole periods), the experts held (128 of 512) and the vocabulary
    (a quarter); no width differs."""
    c = _published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert c["source"] == row["source_url"]
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert {k for k, v in row["config"].items() if c[k] != v} \
        == set(c["reduced"])
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        8, 128, 37984)
    assert c["vocab_size"] * 4 == c["published"]["vocab_size"]
    from bench.harness import spec

    fam = spec.family(c)
    assert fam.kinds(c) == ["linear", "linear", "linear", "full"] * 2
    assert fam.held_range(c) == (0, 128)
    whole = dict(c, num_hidden_layers=48, num_experts=512,
                 vocab_size=151936)
    assert round(fam.matrix_params(whole)["total"] / 1e7) == 7967
    assert round(fam.matrix_params(c)["total"] / 1e6) == 3667
    cfg = fam.program_config(whole)
    assert cfg.experts_held is None and cfg.n_of("linear") == 36
    assert abs(cfg.num_params / fam.matrix_params(whole)["total"] - 1) < 1e-4
    assert (cfg.attn_gate, cfg.rotary_dim, cfg.linear_conv_dim) == (
        256, 64, 8192)
    assert c["engine"] == {"num_slots": 8, "max_len": 16384,
                           "block_size": 16, "prefill_chunk": 512,
                           "max_burst": 8, "speculation_k": 0}
    assert c["check"] == {"lanes": 2, "prompt_len": 6144,
                          "decode_steps": 16}


def test_what_a_step_and_a_launch_need_at_the_published_sizes():
    """By ISSUE 64's arithmetic: 128 (1 - (502/512)^lanes) held experts of
    6.3 MB a layer in 8 layers, every weight outside them once and the
    quarter head, two full layers' KV at 2 KB a position and layer, six
    linear layers' 2 MB state and conv rows a lane, in and out."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    one_expert = 3 * 2048 * 512 * 2
    assert round(fam.expected_held_experts(c, 1), 2) == 2.5
    assert round(fam.expected_held_experts(c, 7), 1) == 16.5
    assert fam.routed_choices_per_row(c) == 80
    assert fam.expert_bytes_per_step(c, 8) == \
        8 * fam.expected_held_experts(c, 8) * one_expert
    state = 32 * 128 * 128 * 4 + 3 * 8192 * 2
    assert fam.state_bytes_per_step(c, 7) == 2 * 6 * 7 * state
    assert fam.state_rows_per_step(c, 7) == 42
    linear = 2048 * (12288 + 64) + 4096 * 2048 + 4 * 8192
    full = 3 * 2048 * 4096 + 2 * 2048 * 512
    dense = (6 * linear + 2 * full + 8 * (3 * 2048 * 512 + 2048
                                          + 2048 * 512) + 2048 * 37984) * 2
    assert fam.decode_step_bytes(c, 7 * 9000, 7) == \
        dense + fam.expert_bytes_per_step(c, 7) + 2 * 2048 * 7 * 9000 \
        + fam.state_bytes_per_step(c, 7)
    # a 512-row launch: eight chunks of 64 a head, 32 heads, six layers
    per_chunk = 2 * (2 * 64 * 64 * 128 + 64 * 64 * 256 + 3 * 64 * 128 * 128
                     + 64 * 64 * 128)
    assert fam.scan_flops_per_chunk(c, 512) == 6 * 32 * 8 * per_chunk
    assert fam.scan_bytes_per_chunk(c, 512) == 6 * (
        2 * 32 * 128 * 128 * 4 + 512 * (8192 * 2 + 2 * 4 * 32 + 4 * 4096))
    flops = fam.prefill_flops(c, 512, 512 * 513 / 2)
    per_token = 2 * (6 * linear + 2 * full + 8 * (
        3 * 2048 * 512 + 2048 + 2048 * 512 + 2.5 * 3 * 2048 * 512))
    assert flops == pytest.approx(
        per_token * 512 + fam.scan_flops_per_chunk(c, 512)
        + 4 * 2 * 4096 * (512 * 513 / 2))


def test_the_two_device_readers_read_the_mixer_s_ops_and_nothing_else():
    """`gdn_step_share.decode` and `gdn_chunk_roofline` over a hand-made
    reduction; a trace without such ops, a run without a trace and a
    family without the functions give None rather than raising."""
    from bench.harness import spec

    c = _published()
    cell = type("Cell", (), {"config": c})()

    def reader(name):
        return spec.load_file(os.path.join(
            ROOT, "bench", "metrics", name + ".py"), "bench_metric_").read

    share, roofline = reader("gdn_step_share.decode"), \
        reader("gdn_chunk_roofline")
    ops = {
        "a": {"program": "paged_decode_burst", "seconds": 0.030,
              "text": "%fusion.1 = f32[8,32,128,128]{3,2,1,0} fusion(...)"},
        "b": {"program": "paged_decode_burst", "seconds": 0.010,
              "text": "%f.2 = bf16[8,1,12288] fusion(bf16[6,2048,12288] %w)"},
        "c": {"program": "paged_decode_burst", "seconds": 0.040,
              "text": "%f.3 = bf16[8,512] fusion(bf16[8,128,2048,512] %w_up)"},
        "d": {"program": "paged_prefill_chunk", "seconds": 0.004,
              "text": "%f.4 = f32[1,8,32,64,64]{4,3,2,1,0} fusion(...)"},
        "e": {"program": "paged_prefill_chunk", "seconds": 0.004,
              "text": "%while.5 = (f32[1,32,128,128]) while(...)"},
        "f": {"program": "paged_prefill_chunk", "seconds": 0.100,
              "text": "%f.6 = bf16[512,512] fusion(bf16[8,128,2048,512] %w)"},
    }
    trace = {"programs": {
        "paged_decode_burst": {"count": 5, "seconds": 0.100},
        "paged_prefill_chunk": {"count": 2, "seconds": 0.2}},
        "ops": ops, "counters": {"bench.count.prefill": {
            "each": [{"tokens": 512, "chunks": 1}]}}}
    ctx = {"cell": cell, "trace": trace, "device": {"kind": "TPU v5 lite"}}
    assert share(ctx, program="paged_decode_burst") == pytest.approx(40.0)
    fam = spec.family(c)
    from bench.harness.peaks import peaks

    peak = peaks("TPU v5 lite")
    least = max(fam.scan_flops_per_chunk(c, 512) / peak["bf16_flops"],
                fam.scan_bytes_per_chunk(c, 512) / peak["hbm_bytes_per_s"])
    assert roofline(ctx, program="paged_prefill_chunk",
                    counter="bench.count.prefill") == pytest.approx(
        100.0 * least / (0.004 / 2))           # the loop's own time left out
    trace["ops"] = {"c": ops["c"], "f": ops["f"]}
    assert share(ctx, program="paged_decode_burst") is None
    assert roofline(ctx, program="paged_prefill_chunk",
                    counter="bench.count.prefill") is None
    assert share({"cell": cell, "trace": None},
                 program="paged_decode_burst") is None
    with open(os.path.join(ROOT, "bench", "configs",
                           "laguna-xs.2-serve-1chip.json")) as f:
        other = type("Cell", (), {"config": json.load(f)})()
    assert share({"cell": other, "trace": trace},
                 program="paged_decode_burst") is None
    assert roofline({"cell": other, "trace": trace,
                     "device": {"kind": "TPU v5 lite"}},
                    program="paged_prefill_chunk",
                    counter="bench.count.prefill") is None


def test_the_entries_of_the_cell():
    """BENCHMARK.json: the configuration, the cell, the three metrics this
    PR added and the cell's name in the lists ISSUE 64 names, found by name
    (a later PR puts its own entries behind them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert "qwen3-next-80b-a3b-serve-1chip" in [c["name"]
                                                for c in b["configs"]]
    (entry,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert entry == dict(entry, chips=1, traffic="agent-closed8",
                         config="qwen3-next-80b-a3b-serve-1chip")
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in ("gdn_chunk_roofline", "gdn_step_share.decode",
                 "linear_state_rows"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p50_ms"
    has_cell = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                if CELL in m.get("workloads", [])}
    all_three = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                 if {"glm47flash-agent", "lagunaxs2-agent", "dsv32-agent"}
                 <= set(m.get("workloads", []))}
    assert all_three - {"moe_ffn_roofline"} <= has_cell
    assert {"ssm_state_roofline", "state_reset_ms",
            "moe_visit_share.decode"} <= has_cell
    assert "moe_ffn_roofline" not in has_cell
    from bench.harness import spec

    cell = spec.load_cell(CELL)
    assert cell.programs() == ["paged_decode_burst", "paged_prefill_chunk"]
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p50_ms", "setup_s"]


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinyqwen3next-closed", grown_root)
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
    finally:
        e.shutdown()
    assert v["positions"] == 27 == v["decided"]       # 3 x (1 + 8)
    assert v["ok"] and v["worst"] < 5e-4, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinyqwen3next-closed", "--seed",
         str(SEED), "--seconds", "2", "--trace", "0", "--rehearse"],
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
