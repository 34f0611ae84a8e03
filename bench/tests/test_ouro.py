"""The ouro family (bench/families/ouro.py) enters a copy of the tiny
benchmark as files and entries alone, as bench/tests/test_lfm2moe.py shows
for `lfm2moe`: no file that was there is edited, its cell finds the family,
the published configuration is the catalog's to the key (nothing cut), what
a step needs is counted from the published sizes (the layers' weights and
the KV four times, the head once), the three new readers read the decode
kernel's ops, the counter and the tick log's field and nothing else, each of
the family's faults fails the comparison, and the command itself serves the
cell on the CPU (proxy -> handle -> replica -> PagedLLMEngine over a pool of
a plane a pass and layer) up to the device check."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(ROOT, "bench", "tests", "data")
SEED = 2**31 + 19
CELL = "ouro26b-mathword8"


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
    root = str(tmp_path_factory.mktemp("ouro") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))
    added = os.path.join(DATA, "ourofamily")
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
        os.path.join("configs", "tinyouro-serve.json")]
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "ouro-2.6b-serve-1chip.json")) as f:
        return json.load(f)


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinyouro-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "ouro.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_layers, cfg.loop_passes, cfg.kv_planes) == (3, 3, 9)
    assert cfg.post_norm and cfg.exit_threshold == 0.6
    assert not cfg.state_by_slot and not cfg.tie_embeddings
    assert fam.expert_operand(cell.config) is None
    with pytest.raises(spec.SpecError, match="full attention"):
        fam.program_config(dict(cell.config, sliding_window=64))


def test_the_published_configuration_is_the_catalog_s_whole():
    """Every key of the catalog's row under its key with its value; nothing
    is cut: all 48 layers, 4 passes, the whole vocabulary."""
    c = _published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert c["source"] == row["source_url"]
    assert c["reduced"] == [] and c["published"] == {}
    assert {k for k, v in row["config"].items() if c[k] != v} == set()
    assert (c["num_hidden_layers"], c["total_ut_steps"], c["vocab_size"],
            c["early_exit_threshold"]) == (48, 4, 49152, 1)
    for key in ("assumed", "deployment", "memory", "parameter_count"):
        assert c[key], key
    assert set(c["assumed"]) >= {"sandwich_norms", "norm_between_passes",
                                 "cache_per_pass", "exit_gate",
                                 "attention_bias", "rope_layout"}
    from bench.harness import spec

    fam = spec.family(c)
    assert round(fam.layer_params(c) / 1e4) == 5138          # 51.38 M
    assert round(fam.total_params(c) / 1e6) == 2668
    assert fam.layer_passes(c) == 192
    assert 192 * fam.kv_row_bytes(c) == 1572864              # 1.5 MiB
    cfg = fam.program_config(c)
    assert round(cfg.num_params / 1e6) == 2668
    assert c["engine"] == {"num_slots": 8, "max_len": 512, "block_size": 16,
                           "prefill_chunk": 128, "max_burst": 8,
                           "speculation_k": 0}
    assert c["check"] == {"lanes": 4, "prompt_len": 250, "decode_steps": 16}
    # the pool: 8 x 512 positions and the null block, 1.5 MiB a position
    blocks = 8 * 512 // 16 + 1
    assert blocks * 16 * 1572864 == 6467616768
    assert abs(c["memory"]["kv_pool_GB"] - 6.47) < 0.01
    assert abs(c["memory"]["parameters_GB"] - 5.34) < 0.01


def test_what_a_step_needs_at_the_published_sizes():
    """By ISSUE 69's arithmetic: a step streams the 48 layers' 51.38 M
    matrix parameters four times (19.7 GB) and the head once (0.2 GB), and
    reads the live positions' K and V in 192 planes at 8,192 B each."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    layer = (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2
    assert fam.decode_step_bytes(c, 0, 8) == 192 * layer + 2048 * 49152 * 2
    assert 19.9e9 < fam.decode_step_bytes(c, 0, 8) < 20.0e9
    live = 8 * 288
    assert fam.attn_kv_bytes_per_launch(c, live) == 8192 * live
    assert fam.attn_kv_bytes_per_step(c, live) == 192 * 8192 * live
    assert fam.decode_step_bytes(c, live, 8) \
        == fam.decode_step_bytes(c, 0, 8) + 192 * 8192 * live
    assert 23.5e9 < fam.decode_step_bytes(c, live, 8) < 23.7e9
    assert fam.expert_bytes_per_step(c, 8) == 0
    flops = fam.prefill_flops(c, 128, 128 * 129 / 2)
    assert flops == pytest.approx(192 * (
        2 * (layer / 2) * 128 + 4 * 2048 * (128 * 129 / 2)))


def test_the_three_readers_read_what_they_name_and_nothing_else():
    """`loop_attn_share.decode` and `loop_attn_roofline.decode` over a
    hand-made reduction, `loop_passes_per_token` over a hand-made tick log;
    a trace without the kernel's ops, a run without a trace, a family
    without the function and a log without the field give None."""
    from bench.harness import spec
    from bench.harness.peaks import peaks

    c = _published()
    cell = types.SimpleNamespace(config=c)

    def reader(name):
        return spec.load_file(os.path.join(
            ROOT, "bench", "metrics", name + ".py"), "bench_metric_").read

    share = reader("loop_attn_share.decode")
    roofline = reader("loop_attn_roofline.decode")
    burst = "paged_decode_burst"
    ops = {
        burst + "/paged_decode_attention.13_bf16_8_16_128_": {
            "program": burst, "seconds": 0.060, "count": 3000,
            "text": "%paged_decode_attention.13 = bf16[8,16,128] "
                    "custom-call(...)"},
        burst + "/fusion.7_bf16_8_1_5632_": {
            "program": burst, "seconds": 0.500, "count": 3000,
            "text": "%fusion.7 = bf16[8,1,5632] fusion(bf16[48,2048,5632])"},
        # a chunk's reads are the block loop's, and another program's
        "paged_prefill_chunk/paged_decode_attention.2_bf16_": {
            "program": "paged_prefill_chunk", "seconds": 0.100, "count": 9,
            "text": "%paged_decode_attention.2 = ..."},
        # a name that only starts alike is another kernel
        burst + "/paged_decode_attention_rows.1_bf16_": {
            "program": burst, "seconds": 0.040, "count": 7,
            "text": "%p = ..."},
    }
    trace = {"programs": {burst: {"count": 5, "seconds": 0.600},
                          "paged_prefill_chunk": {"count": 2,
                                                  "seconds": 0.2}},
             "ops": ops, "counters": {"bench.count.decode": {
                 "each": [{"lanes": 8, "kv_tokens": 2000},
                          {"lanes": 6, "kv_tokens": 1500}]}}}
    ctx = {"cell": cell, "trace": trace, "device": {"kind": "TPU v5 lite"}}
    args = dict(program=burst, kernel="paged_decode_attention")
    assert share(ctx, **args) == pytest.approx(10.0)
    fam = spec.family(c)
    least = (fam.attn_kv_bytes_per_launch(c, 2000 + 8 * 3.5)
             + fam.attn_kv_bytes_per_launch(c, 1500 + 6 * 3.5)) / 2 \
        / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    got = roofline(ctx, counter="bench.count.decode", **args)
    # by the kernel's own launches, not by the bursts the window holds
    assert got == pytest.approx(100.0 * least / (0.060 / 3000))
    trace["ops"] = {k: v for k, v in ops.items() if ".13" not in k}
    assert share(ctx, **args) is None
    assert roofline(ctx, counter="bench.count.decode", **args) is None
    assert share({"cell": cell, "trace": None}, **args) is None
    assert roofline({"cell": cell, "trace": None},
                    counter="bench.count.decode", **args) is None
    trace["ops"] = ops
    with open(os.path.join(ROOT, "bench", "configs",
                           "mistral-7b-serve-1chip.json")) as f:
        other = types.SimpleNamespace(config=json.load(f))
    assert share({"cell": other, "trace": trace}, **args) \
        == pytest.approx(10.0)                  # the kernel's share is any
    assert roofline({"cell": other, "trace": trace,          # model's; the
                     "device": {"kind": "TPU v5 lite"}},     # bytes are not
                    counter="bench.count.decode", **args) is None
    trace["counters"] = {}
    assert roofline(ctx, counter="bench.count.decode", **args) is None

    passes = reader("loop_passes_per_token")
    fields = ("start", "tick_s", "lanes", "loop_passes")
    ticks = ((9.0, 0.1, 8, 192),                # before the window
             (10.0, 0.1, 6, 192), (10.1, 0.1, 0, 0), (10.2, 0.1, 2, 192))

    def log(fields, ticks):
        return {"run": {"outcomes": [types.SimpleNamespace(
                    cause=None, first=1.0, request_id="r")]},
                "replica": {"stats": {
                    "request_phases": [{"id": "r", "submitted": 9.95,
                                        "ttft_s": 0.4}],
                    "tick_fields": fields, "tick_log": ticks}}}

    assert passes(log(fields, ticks)) == pytest.approx(192.0)
    assert passes(log(fields[:3], [t[:3] for t in ticks])) is None
    assert passes(log(fields, ticks[2:3])) is None      # no burst


def test_the_entries_of_the_cell():
    """BENCHMARK.json: the configuration, the cell, the three metrics this
    PR added and the cell's name in the lists ISSUE 69 names, found by name
    (a later PR puts its own entries behind them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(b["workloads"]) >= 16
    (cfg,) = [c for c in b["configs"] if c["name"] == "ouro-2.6b-serve-1chip"]
    assert cfg["reduced"] == [] and len(cfg["why"]) <= 200
    (entry,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert entry == dict(entry, chips=1, traffic="mathword-closed8",
                         config="ouro-2.6b-serve-1chip")
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in ("loop_attn_share.decode", "loop_attn_roofline.decode",
                 "loop_passes_per_token"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p50_ms"
    assert by_name["loop_attn_roofline.decode"]["unit"] == "%"
    has_cell = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                if CELL in m.get("workloads", [])}
    with_chat = {m["name"] for m in b["per_layer"]
                 if "mistral7b-chat" in m.get("workloads", [])}
    assert with_chat <= has_cell and len(with_chat) == 30
    assert {"tpot_p50_ms", "decode_kv_read_tok", "decode_roofline",
            "decode_step_dev_ms"} <= has_cell
    assert not {"moe_experts_read", "state_reset_ms", "moe_ffn_roofline",
                "ring_slots_read", "ttft_p50_ms"} & has_cell
    from bench.harness import spec

    cell = spec.load_cell(CELL)
    assert cell.programs() == ["paged_decode_burst", "paged_prefill_chunk"]
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p50_ms", "setup_s"]
    assert cell.traffic["clients"] == 8 == cell.traffic["block"]
    assert cell.traffic["start_stagger_s"] == 0.5
    assert cell.traffic["temperature"] == 0.0 and cell.traffic["stream"]


def test_the_traffic_fits_the_engine():
    """Every request of three blocks of 8 fits the engine's 512 positions:
    a prompt of 64-256 and 256 new tokens."""
    from bench.harness import schedule, spec

    cell = spec.load_cell(CELL)
    gen = schedule.closed_schedule(cell.traffic, SEED,
                                   cell.config["vocab_size"])
    block = [next(gen) for _ in range(24)]
    spec.check_requests(block, cell.config["engine"])
    assert 64 <= min(r.prompt_len for r in block)
    assert max(r.prompt_len for r in block) <= 256
    assert {r.max_tokens for r in block} == {256}
    assert max(max(r.tokens) for r in block) < 49152 and SEED > 2**31


def _checked(c, cfg=None, seed=SEED):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    fam = spec.family(c)
    true, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg or true, device.seeded_params(fam, true, seed),
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"])
    try:
        return logits_check(e, c, seed)
    finally:
        e.shutdown()


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    """The tiny cell's check: 3 lanes x (the last of 100 prompt positions,
    prefilled in launches of 32, 32, 32 and 4 rows, + 8 decode steps) at
    threshold 0.6, float32 throughout."""
    from bench.harness import spec

    cell = spec.load_cell("tinyouro-closed", grown_root)
    v = _checked(cell.config)
    fam = spec.family(cell.config)
    assert v["positions"] == 27 == v["decided"]       # 3 x (1 + 8)
    assert v["ok"] and v["worst"] < 2e-5, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL"]


@pytest.mark.parametrize("fault", ["pass_left_out", "plane_of_another_pass",
                                   "no_post_norm", "final_norm_twice",
                                   "cache_fp8"])
def test_each_fault_fails_the_family_s_own_tolerance(grown_root, fault):
    """At the published threshold (every row reads the last pass), float32
    throughout, against the family's limit as it stands (the published
    widths' bfloat16 reading with room): a pass left out, another pass's
    plane read, a second norm left out, the final norm applied twice and
    the pool in 8-bit floats (the nearest precision below the stated one)
    each come out not correct."""
    from bench.harness import spec

    cell = spec.load_cell("tinyouro-closed", grown_root)
    c = dict(cell.config, early_exit_threshold=1)
    fam = spec.family(c)
    assert fault in fam.FAULTS
    cfg, undo = fam.control(fault, fam.program_config(c))
    try:
        v = _checked(c, cfg)
    finally:
        undo()
    assert v["positions"] == 27 and not v["ok"], v
    assert v["worst"] > fam.TOLERANCES["LOGITS_REL"], v


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinyouro-closed", "--seed",
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
    assert not any(phases["window_compiles"].values())
    assert phases["engine_stats"]["prefix_hits"] == 0
