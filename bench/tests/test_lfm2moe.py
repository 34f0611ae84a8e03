"""The lfm2moe family (bench/families/lfm2moe.py) enters a copy of the tiny
benchmark as files and entries alone, as bench/tests/test_laguna.py shows
for `laguna`: no file that was there is edited, its cell finds the family,
the published configuration is the catalog's but for its depth, what a step
needs is counted from the published sizes, the two new device readers read
the conv mixers' ops and nothing else, and the command itself serves the
cell on the CPU (proxy -> handle -> replica -> PagedLLMEngine with conv
rows by slot) up to the device check."""
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
SEED = 2**31 + 19
CELL = "lfm2a1b-bulk32"


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
    root = str(tmp_path_factory.mktemp("lfm2moe") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))
    added = os.path.join(DATA, "lfm2moefamily")
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
        os.path.join("configs", "tinylfm2moe-serve.json")]
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "lfm2-8b-a1b-serve-1chip.json")) as f:
        return json.load(f)


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinylfm2moe-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "lfm2moe.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert cfg.lead_pattern == ("conv", "conv")
    assert cfg.layer_pattern == ("full", "conv", "conv", "conv")
    assert cfg.layer_tail == ("full", "conv", "full") and cfg.n_layers == 13
    assert cfg.recurrent and cfg.launch_spans_chunks and cfg.tie_embeddings
    assert cfg.experts_held is None and cfg.router_bias
    c = _published()
    assert fam.expert_operand(c).search(
        "fusion(bf16[12,32,2048,1792]{3,2,1,0} %w_gate, s32[] %ex)")
    assert not fam.expert_operand(c).search("bf16[12,2048,32]{2,1,0}")
    for text in ("bf16[11,33,2,2048]{3,2,1,0} %lconv", "bf16[32,3,2048]",
                 "bf16[32,2,2048]", "bf16[9,2048,6144]{2,1,0} %in_proj",
                 "bf16[2048,6144]{1,0} %lead_in_proj", "bf16[32,1,6144]",
                 "bf16[9,2048,2048]{2,1,0} %out_proj"):
        assert fam.mixer_operand(c).search(text), text
    for text in ("bf16[3,2048,2048]{2,1,0} %wq", "bf16[12,32,2048,1792]",
                 "bf16[3,8193,64,128]", "bf16[65536,2048]", "bf16[32,1,2048]",
                 "bf16[3,2048,512]", "bf16[2048,7168]", "f32[32,1,32,64]"):
        assert not fam.mixer_operand(c).search(text), text


def test_the_layers_split_into_periods_and_a_tail():
    from bench.harness import spec

    fam = spec.family(_published())
    f, c = "full", "conv"
    assert fam.split_layers([f, c, c, c] * 3) == ([f, c, c, c], [])
    assert fam.split_layers([f, c, c, c] * 4 + [f, c, c, f, c, c]) == (
        [f, c, c, c], [f, c, c, f, c, c])
    assert fam.split_layers([f, c, c, c] * 2 + [f, c]) == ([f, c, c, c], [])
    assert fam.split_layers([f] * 5) == ([f], [])
    assert fam.split_layers([c, f, c]) == ([c, f], [])


def test_the_published_configuration_is_the_catalog_s_but_for_its_depth():
    """Every number of the catalog's row under its key, `layer_types` whole;
    the one cut is depth (14 of 24: the two leading layers and three whole
    periods); no width differs, every expert and the vocabulary are held."""
    c = _published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert c["source"] == row["source_url"]
    assert c["reduced"] == ["num_hidden_layers"]
    assert {k for k, v in row["config"].items() if c[k] != v} \
        == set(c["reduced"])
    assert c["published"] == {"num_hidden_layers": 24}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        14, 32, 65536)
    for key in ("assumed", "deployment", "memory", "layers_run",
                "parameter_count"):
        assert c[key], key
    from bench.harness import spec

    fam = spec.family(c)
    kinds = [fam._KINDS[k] for k in fam.layer_types(c)]
    assert kinds == ["conv"] * 2 + ["full", "conv", "conv", "conv"] * 3
    whole = dict(c, num_hidden_layers=24)
    assert round(fam.matrix_params(whole)["total"] / 1e7) == 834
    assert round(fam.matrix_params(c)["total"] / 1e6) == 4667
    cfg = fam.program_config(whole)
    assert cfg.layer_tail == ("full", "conv", "conv", "full", "conv", "conv")
    assert list(cfg.kinds) == [fam._KINDS[k] for k in c["layer_types"]]
    assert abs(cfg.num_params / fam.matrix_params(whole)["total"] - 1) < 1e-4
    cut = fam.program_config(c)
    assert cut.layer_tail == () and cut.n_periods == 3
    assert (cut.head_dim, cut.conv_kernel, cut.n_experts) == (64, 3, 32)
    assert c["engine"] == {"num_slots": 32, "max_len": 4096,
                           "block_size": 16, "prefill_chunk": 512,
                           "max_burst": 8, "speculation_k": 0}
    assert c["check"] == {"lanes": 4, "prompt_len": 1300,
                          "decode_steps": 16}


def test_what_a_step_needs_at_the_published_sizes():
    """By ISSUE 67's arithmetic: 32 (1 - 0.875^lanes) experts of 22 MB a
    layer in 12 expert layers, every weight outside them once with the tied
    head, three full layers' KV at 2 KB a position and layer, eleven conv
    layers' two rows of 2048 a lane, in and out."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    one_expert = 3 * 2048 * 1792 * 2
    assert round(fam.expected_held_experts(c, 1), 2) == 4.0
    assert round(fam.expected_held_experts(c, 32), 1) == 31.6
    assert fam.expert_bytes_per_step(c, 32) == \
        12 * fam.expected_held_experts(c, 32) * one_expert
    conv = 4 * 2048 * 2048 + 3 * 2048
    rows = 2 * 2 * 2048 * 32 * 2
    assert fam.conv_bytes_per_step(c, 32) == 11 * (conv * 2 + rows) \
        - 2 * 2048 * 2048 * 2            # the leading layers' W_out unseen
    full = 2 * 2048 * 2048 + 2 * 2048 * 512
    dense = (11 * conv + 3 * full + 2 * 3 * 2048 * 7168 + 12 * 2048 * 32
             + 65536 * 2048) * 2
    assert fam.decode_step_bytes(c, 32 * 800, 32) == \
        dense + fam.expert_bytes_per_step(c, 32) + 3 * 2048 * 32 * 800 \
        + 11 * rows
    # a step of 32 lanes reads 9.3 GB less the embedding's gather... the
    # head is the embedding, so all of it: 11.4 ms at 819 GB/s
    assert 9.2e9 < fam.decode_step_bytes(c, 32 * 800, 32) < 9.5e9
    flops = fam.prefill_flops(c, 512, 512 * 513 / 2)
    per_token = 2 * (11 * conv + 3 * full + 2 * 3 * 2048 * 7168
                     + 12 * (2048 * 32 + 4 * 3 * 2048 * 1792))
    assert flops == pytest.approx(
        per_token * 512 + 2 * 11 * 5 * 2048 * 512
        + 4 * 3 * 2048 * (512 * 513 / 2))


def test_the_two_device_readers_read_the_mixer_s_ops_and_nothing_else():
    """`shortconv_step_share.decode` and `shortconv_roofline.decode` over a
    hand-made reduction; a trace without such ops, a run without a trace
    and a family without the functions give None rather than raising."""
    from bench.harness import spec
    from bench.harness.peaks import peaks

    c = _published()
    cell = type("Cell", (), {"config": c})()

    def reader(name):
        return spec.load_file(os.path.join(
            ROOT, "bench", "metrics", name + ".py"), "bench_metric_").read

    share, roofline = reader("shortconv_step_share.decode"), \
        reader("shortconv_roofline.decode")
    ops = {
        "a": {"program": "paged_decode_burst", "seconds": 0.020,
              "text": "%f.1 = bf16[32,1,6144] fusion(bf16[9,2048,6144] %w)"},
        "b": {"program": "paged_decode_burst", "seconds": 0.010,
              "text": "%f.2 = bf16[11,33,2,2048] fusion(bf16[32,3,2048] %c)"},
        "c": {"program": "paged_decode_burst", "seconds": 0.500,
              "text": "%f.3 = f32[32,2048] fusion(bf16[12,32,2048,1792] %w)"},
        "d": {"program": "paged_prefill_chunk", "seconds": 0.100,
              "text": "%f.4 = bf16[512,6144] fusion(bf16[9,2048,6144] %w)"},
        # a loop carries the slots' rows and touches none: left out
        "w": {"program": "paged_decode_burst", "seconds": 0.050,
              "text": "%while.233 = (s32[], bf16[11,33,2,2048]) "
                      "while((s32[], bf16[11,33,2,2048]) %tuple.1)"},
        # its text cut before the keyword (the reduction keeps 600 chars)
        "x": {"program": "paged_decode_burst", "seconds": 0.050,
              "text": "%while.234 = (s32[], bf16[32,1,2048], "
                      "bf16[11,33,2,2048], s32[32]"},
    }
    trace = {"programs": {
        "paged_decode_burst": {"count": 5, "seconds": 0.600},
        "paged_prefill_chunk": {"count": 2, "seconds": 0.2}},
        "ops": ops, "counters": {"bench.count.decode": {
            "each": [{"lanes": 32, "kv_tokens": 1000},
                     {"lanes": 30, "kv_tokens": 900}]}}}
    ctx = {"cell": cell, "trace": trace, "device": {"kind": "TPU v5 lite"}}
    assert share(ctx, program="paged_decode_burst") == pytest.approx(5.0)
    fam = spec.family(c)
    least = (fam.conv_bytes_per_step(c, 32) + fam.conv_bytes_per_step(c, 30)) \
        / 2 / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    got = roofline(ctx, program="paged_decode_burst",
                   counter="bench.count.decode")
    assert got == pytest.approx(100.0 * least / (0.030 / 40))
    trace["ops"] = {k: ops[k] for k in "cdwx"}
    assert share(ctx, program="paged_decode_burst") is None
    assert roofline(ctx, program="paged_decode_burst",
                    counter="bench.count.decode") is None
    assert share({"cell": cell, "trace": None},
                 program="paged_decode_burst") is None
    assert roofline({"cell": cell, "trace": None},
                    program="paged_decode_burst",
                    counter="bench.count.decode") is None
    with open(os.path.join(ROOT, "bench", "configs",
                           "laguna-xs.2-serve-1chip.json")) as f:
        other = type("Cell", (), {"config": json.load(f)})()
    trace["ops"] = ops
    assert share({"cell": other, "trace": trace},
                 program="paged_decode_burst") is None
    assert roofline({"cell": other, "trace": trace,
                     "device": {"kind": "TPU v5 lite"}},
                    program="paged_decode_burst",
                    counter="bench.count.decode") is None


def test_the_entries_of_the_cell():
    """BENCHMARK.json: the configuration, the cell, the three metrics this
    PR added and the cell's name in the lists ISSUE 67 names, found by name
    (a later PR puts its own entries behind them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(b["workloads"]) >= 15
    (cfg,) = [c for c in b["configs"]
              if c["name"] == "lfm2-8b-a1b-serve-1chip"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    (entry,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert entry == dict(entry, chips=1, traffic="bulk-closed32",
                         config="lfm2-8b-a1b-serve-1chip")
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in ("shortconv_step_share.decode", "shortconv_roofline.decode",
                 "conv_state_rows"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p50_ms"
    has_cell = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                if CELL in m.get("workloads", [])}
    with_mellum = {m["name"] for m in b["per_layer"]
                   if {"mistral7b-chat", "mellum2-codeassist",
                       "qwen3next-agent"} <= set(m.get("workloads", []))}
    assert with_mellum <= has_cell
    assert {"tpot_p50_ms", "state_reset_ms", "moe_visit_share.decode",
            "moe_experts_read", "decode_kv_read_tok",
            "decode_roofline"} <= has_cell
    # off `moe_ffn_roofline`, as `dsv32-agent` and `qwen3next-agent` are:
    # its numerator is the uniform expectation (31.4 of 32 at 30 lanes) and
    # the program reads 30.0 under the selection bias (PERF.md section 7)
    assert not {"ssm_state_roofline", "ring_slots_read", "moe_ffn_roofline",
                "moe_routed_here_share.decode"} & has_cell
    from bench.harness import spec

    cell = spec.load_cell(CELL)
    assert cell.programs() == ["paged_decode_burst", "paged_prefill_chunk"]
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p50_ms", "setup_s"]
    assert cell.traffic["clients"] == 32 == cell.traffic["block"]


def test_the_traffic_fits_the_engine():
    """Every pair of a block of 32 fits the engine's 4,096 positions, and
    a block's prompts bring about as many tokens as its streams emit."""
    from bench.harness import schedule, spec

    cell = spec.load_cell(CELL)
    gen = schedule.closed_schedule(cell.traffic, SEED,
                                   cell.config["vocab_size"])
    block = [next(gen) for _ in range(32)]
    spec.check_requests(block, cell.config["engine"])
    assert 32 <= min(r.prompt_len for r in block)
    assert max(r.prompt_len for r in block) <= 2048
    assert 64 <= min(r.max_tokens for r in block)
    assert max(r.max_tokens for r in block) <= 1024
    assert max(r.tokens[0] for r in block) < 65536 and SEED > 2**31


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinylfm2moe-closed", grown_root)
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
    assert v["ok"] and v["worst"] < 5e-5, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]


def test_a_lower_precision_than_stated_fails_a_tolerance(grown_root,
                                                         monkeypatch):
    """The tiny configuration states float32 throughout; held to a float32
    program's limits (ten times its own error), the conv rows kept in
    bfloat16, and the pool and the rows in 8-bit floats (`control`'s
    `cache_fp8`), each fail the logits check."""
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.ops import gated_delta, short_conv
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinylfm2moe-closed", grown_root)
    c = cell.config
    fam = spec.family(c)
    monkeypatch.setitem(fam.TOLERANCES, "LOGITS_REL_EXPERTS", 5e-5)
    cfg, eng = fam.program_config(c), c["engine"]

    def verdict():
        e = PagedLLMEngine(
            cfg, device.seeded_params(fam, cfg, SEED),
            num_slots=eng["num_slots"], max_len=eng["max_len"],
            block_size=eng["block_size"],
            prefill_chunk=eng["prefill_chunk"])
        try:
            return logits_check(e, c, SEED)
        finally:
            e.shutdown()

    _, undo = fam.control("cache_fp8", cfg)
    try:
        v = verdict()
    finally:
        undo()
    assert not v["ok"] and v["worst"] > 100 * 5e-5, v
    import jax.numpy as jnp

    inner = gated_delta.causal_conv

    def rounded(rows, x, w, n):
        out, kept = inner(rows, x, w, n)
        return out, kept.astype(jnp.bfloat16).astype(kept.dtype)

    monkeypatch.setattr(short_conv, "causal_conv", rounded)
    v = verdict()
    assert not v["ok"] and v["worst"] > 5e-5, v


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinylfm2moe-closed", "--seed",
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
