"""The laguna family (bench/families/laguna.py) enters a copy of the tiny
benchmark as files and entries alone, as bench/tests/test_mellum.py shows
for `mellum`: no file that was there is edited, its cell finds the family,
the published configuration is the catalog's but for its three cuts, what
a step needs is counted from the published sizes, `moe_visit_share.decode`
reads the expert ops and nothing else, and the command itself serves the
cell on the CPU (proxy -> handle -> replica -> PagedLLMEngine with window
rings and a held share) up to the device check."""
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
SEED = 2**31 + 13


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
    root = str(tmp_path_factory.mktemp("laguna") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))
    added = os.path.join(DATA, "lagunafamily")
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
        os.path.join("configs", "tinylaguna-serve.json")]
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "laguna-xs.2-serve-1chip.json")) as f:
        return json.load(f)


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinylaguna-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "laguna.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert cfg.lead_pattern == ("full",) and cfg.n_layers == 9
    assert cfg.layer_pattern == ("window", "window", "window", "full")
    assert (cfg.n_heads, cfg.n_heads_window, cfg.n_kv_heads) == (6, 8, 2)
    assert cfg.experts_held == (0, 4) and cfg.n_experts == 8
    c = _published()
    assert fam.expert_operand(c).search(
        "fusion(bf16[8,128,2048,512]{3,2,1,0} %w_gate, s32[] %ex)")
    assert fam.expert_operand(c).search("bf16[128,512,2048]{2,1,0} %w_down")
    assert not fam.expert_operand(c).search("bf16[8,2048,512]{2,1,0}")
    rows = 512 + c["engine"]["prefill_chunk"]
    assert fam.ring_operand(c).search(
        f"bf16[6,9,{rows},8,128]{{4,3,2,1,0}} %wk, s32[] %layer)")
    assert not fam.ring_operand(c).search("bf16[3,8193,16,8,128]{4,3,2,1,0}")


def test_the_published_configuration_is_the_catalog_s_but_for_its_cuts():
    """Every number of the source under the source's key; the cuts are
    depth (layer 0 and two whole periods), the experts held (128 of 256)
    and the vocabulary (half); no width differs."""
    c = _published()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                              "vocab_size": 100352}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        9, 128, 50176)
    assert len(c["layer_types"]) == len(c["mlp_layer_types"]) \
        == len(c["num_attention_heads_per_layer"]) == 40
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"]) == (
                2048, 128, 48, 8, 8192)
    assert (c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["shared_expert_intermediate_size"],
            c["moe_routed_scaling_factor"], c["sliding_window"]) == (
                8, 512, 512, 2.5, 512)
    from bench.harness import spec

    fam = spec.family(c)
    assert fam.layers(c) == [("full_attention", 48, "dense")] + (
        [("sliding_attention", 64, "sparse")] * 3
        + [("full_attention", 48, "sparse")]) * 2
    assert fam.n_lead(c) == 1 and fam.held_range(c) == (0, 128)
    whole = dict(c, num_hidden_layers=40, num_experts=256,
                 vocab_size=100352)
    assert round(fam.matrix_params(whole)["total"] / 1e7) == 3344
    assert round(fam.matrix_params(c)["total"] / 1e6) == 3822
    cfg = fam.program_config(whole)
    assert cfg.tail_pattern == ("window",) * 3 and cfg.experts_held is None
    assert abs(cfg.num_params / 33.44e9 - 1) < 1e-3
    # the full layers' rope turns 64 of 128 dimensions: 32 pairs, the
    # ramp reckoned on 64; the window layers' all 128, unscaled
    inv, r, factor = fam.inv_frequencies(c, "full_attention")
    plain, rw, one = fam.inv_frequencies(c, "sliding_attention")
    assert (r, rw, one) == (64, 128, 1.0) and inv.shape == (32,)
    assert factor == 1.4158883083359672
    assert abs(float(inv[0]) - 1.0) < 1e-6
    assert abs(float(inv[31]) * 64 - 500000.0 ** (-62 / 64)) < 1e-9
    assert float(plain[1]) == pytest.approx(10000.0 ** (-2 / 128))


def test_what_a_step_needs_at_the_published_sizes():
    """By ISSUE 45's arithmetic: 128 (1 - (31/32)^lanes) held experts of
    6.3 MB a layer in 8 expert layers, every weight outside them once and
    the head, three full layers' KV at 4 KB a position, six windows of at
    most 512 rows a lane."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    one_expert = 3 * 2048 * 512 * 2
    assert one_expert == 6291456
    assert fam.expected_held_experts(c, 1) == 4
    assert round(fam.expected_held_experts(c, 8), 1) == 28.7
    assert fam.routed_choices_per_row(c) == 64
    assert fam.expert_bytes_per_step(c, 8) == \
        8 * fam.expected_held_experts(c, 8) * one_expert
    attn = 3 * (2 * 2048 * 6144 + 2048 * 48) \
        + 6 * (2 * 2048 * 8192 + 2048 * 64) + 9 * 2 * 2048 * 1024
    dense = (attn + 3 * 2048 * 8192 + 8 * (3 * 2048 * 512 + 2048 * 256)
             + 2048 * 50176) * 2
    assert fam.decode_step_bytes(c, 8 * 9000, 8) == \
        dense + fam.expert_bytes_per_step(c, 8) + 3 * 4096 * 8 * 9000 \
        + 6 * 8 * 512 * 4096
    assert fam.ring_bytes_per_step(c, 8 * 100, 8) == 6 * 8 * 100 * 4096
    # a 128-token chunk at position 0: 4 held experts a token, not 128
    flops = fam.prefill_flops(c, 128, 128 * 129 / 2)
    per_token = 2 * (attn + 3 * 2048 * 8192 + 8 * (
        3 * 2048 * 512 + 2048 * 256 + 4 * 3 * 2048 * 512))
    assert flops == per_token * 128 + 4 * 3 * 6144 * (128 * 129 / 2) \
        + 4 * 6 * 8192 * 128 * (129 / 2)


def test_moe_visit_share_reads_the_expert_ops_and_nothing_else():
    """The reader over a hand-made reduction: the ops of the burst whose
    text shows an operand shaped like the held experts' stacks count, the
    chunk's and the other ops do not, and a trace without such ops, a run
    without a trace and a family without `expert_operand` give None
    rather than raising."""
    from bench.harness import spec

    c = _published()
    cell = type("Cell", (), {"config": c})()
    read = spec.load_file(os.path.join(
        ROOT, "bench", "metrics", "moe_visit_share.decode.py"),
        "bench_metric_").read
    stack = "bf16[8,128,2048,512]{3,2,1,0}"
    ops = {
        "a": {"program": "paged_decode_burst", "seconds": 0.030,
              "text": f"%fusion.1 = bf16[8,512] fusion({stack} %w_gate)"},
        "b": {"program": "paged_decode_burst", "seconds": 0.010,
              "text": "%fusion.2 = f32[8,2048] fusion(bf16[8,128,512,2048]"
                      "{3,2,1,0} %w_down)"},
        "c": {"program": "paged_prefill_chunk", "seconds": 0.300,
              "text": f"%fusion.3 = bf16[128,512] fusion({stack} %w_up)"},
        "d": {"program": "paged_decode_burst", "seconds": 0.020,
              "text": "%fusion.4 = bf16[8,50176] fusion(bf16[2048,50176] %h)"},
    }
    ctx = {"cell": cell, "trace": {
        "programs": {"paged_decode_burst": {"count": 5, "seconds": 0.080}},
        "ops": ops}}
    assert read(ctx, program="paged_decode_burst") == pytest.approx(50.0)
    ctx["trace"]["ops"] = {"d": ops["d"]}
    assert read(ctx, program="paged_decode_burst") is None
    ctx["trace"]["programs"] = {}
    assert read(ctx, program="paged_decode_burst") is None
    assert read({"cell": cell, "trace": None},
                program="paged_decode_burst") is None
    with open(os.path.join(ROOT, "bench", "configs",
                           "phi4-mini-flash-serve-1chip.json")) as f:
        other = type("Cell", (), {"config": json.load(f)})()
    assert read({"cell": other, "trace": {"programs": {}, "ops": {}}},
                program="paged_decode_burst") is None


def test_the_new_entries_only_add_to_the_benchmark():
    """BENCHMARK.json against the parent's lists: one configuration, one
    cell and one metric at the ends, and the cell's name at the end of
    the lists ISSUE 45 names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert b["configs"][-1]["name"] == "laguna-xs.2-serve-1chip"
    assert b["workloads"][-1] == dict(
        b["workloads"][-1], name="lagunaxs2-agent", chips=1,
        config="laguna-xs.2-serve-1chip", traffic="agent-closed8")
    assert b["per_layer"][-1]["name"] == "moe_visit_share.decode"
    has_new = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if m.get("workloads", [])[-1:] == ["lagunaxs2-agent"]}
    has_glm = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if "glm47flash-agent" in m.get("workloads", [])}
    assert has_new == (has_glm - {"mla_attn_roofline"}) | {
        "window_attn_roofline", "moe_visit_share.decode"}
    from bench.harness import spec

    cell = spec.load_cell("lagunaxs2-agent")
    assert cell.programs() == ["paged_decode_burst", "paged_prefill_chunk"]
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p50_ms", "setup_s"]


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinylaguna-closed", grown_root)
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
    assert v["ok"] and v["worst"] < 1e-4, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinylaguna-closed", "--seed", str(SEED),
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
