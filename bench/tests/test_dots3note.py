"""The dots3note family (bench/families/dots3note.py) enters a copy of the
tiny benchmark as files and entries alone, as bench/tests/test_laguna.py
shows for `laguna`: no file that was there is edited, its cell finds the
family, the published configuration is the catalog's but for its three
cuts, what a launch needs is counted from the published sizes, the five
new readers read their ops and counts and nothing else (and nothing,
without raising, from a program that lacks them), and the command itself
serves the cell on the CPU (proxy -> handle -> replica -> PagedLLMEngine
with an index-key leaf, latent rings and a held share) up to the device
check."""
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
CELL = "dots3note-longdoc"


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
    root = str(tmp_path_factory.mktemp("dots") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))
    added = os.path.join(DATA, "dotsfamily")
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
        os.path.join("configs", "tinydots-serve.json")]
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "dots3-note-prev-serve-1chip.json")) as f:
        return json.load(f)


def _reader(name):
    from bench.harness import spec

    return spec.load_file(os.path.join(ROOT, "bench", "metrics",
                                       name + ".py"), "bench_metric_").read


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinydots-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "dots3note.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert cfg.lead_pattern == ("full",) and cfg.n_layers == 7
    assert cfg.layer_pattern == ("full", "window", "window", "window")
    assert cfg.kinds == ("full", "full", "window", "window", "window",
                         "full", "window")
    assert (cfg.n_heads, cfg.n_heads_window) == (4, 2)
    assert (cfg.kv_rank, cfg.kv_rank_window, cfg.window) == (24, 40, 12)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_top_k) == (4, 16, 16)
    assert cfg.attn_gate and cfg.latent_rescale and cfg.state_by_slot
    assert cfg.experts_held == (0, 4) and cfg.n_experts == 8


def test_the_published_configuration_is_the_catalog_s_but_for_its_cuts():
    """Every number of the source under the source's key; the cuts are
    depth (layer 0 and one whole period), the experts held (32 of 256)
    and the vocabulary (an eighth); no width differs."""
    c = _published()
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 46,
                              "n_routed_experts": 256, "vocab_size": 152064}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (5, 32, 19008)
    assert len(c["layer_types"]) == 46
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"]) == (
                5120, 128, 1024, 512, 128, 64, 128, 13824)
    assert (c["swa_num_attention_heads"], c["swa_q_lora_rank"],
            c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
            c["swa_v_head_dim"], c["sliding_window_size"]) == (
                64, 1024, 1024, 192, 128, 513)
    assert (c["index_n_heads"], c["index_head_dim"], c["index_topk"]) == (
        64, 128, 2048)
    assert (c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["n_shared_experts"], c["routed_scaling_factor"]) == (
                8, 1536, 1, 1)
    assert (c["rope_theta"], c["swa_rope_theta"]) == (80000000, 50000)
    for item in ("lora_rescale", "indexer", "window_edge", "expert_groups",
                 "router_bias", "rope_layout", "gate", "left_out",
                 "parameter_count", "index_norm_eps"):
        assert item in c["assumed"]
    assert "eight chips share every layer" in c["deployment"]
    from bench.harness import spec

    fam = spec.family(c)
    assert fam.layer_kinds(c) == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3
    assert fam.held_range(c) == (0, 32) and fam.published_experts(c) == 256
    whole = dict(c, num_hidden_layers=46, n_routed_experts=256,
                 vocab_size=152064)
    assert round(fam.matrix_params(whole)["total"] / 1e8) == 2796
    assert round(fam.matrix_params(c)["total"] / 1e6) == 4087
    cfg = fam.program_config(whole)
    assert cfg.n_of("full") == 13 and cfg.n_of("window") == 33
    assert cfg.experts_held is None
    assert abs(cfg.num_params / 279.55e9 - 1) < 1e-3
    cut = fam.program_config(c)
    assert cut.kinds == ("full", "full", "window", "window", "window")
    assert cut.num_params == fam.matrix_params(c)["total"]
    assert (cut.row_width, cut.kind("window").row_width) == (640, 1152)
    assert cut.attention_scale == 192 ** -0.5
    assert cut.kind("window").scale == 1 / 16
    assert cut.kind("full").rescale_kv == 10 ** 0.5
    assert cut.ring_rows(512) == 1040 == fam.ring_rows(c)


def test_what_a_launch_needs_at_the_published_sizes():
    """By ISSUE 49's arithmetic: 1.93 GFLOP of matrices a token, 2 x 64 x
    128 FLOP a row and position scored and 278.5 kFLOP a row and position
    attended in each of two full layers, the read capped at 2,048
    positions and at 513 in the three window layers."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    assert fam.routed_choices_per_row(c) == 32
    assert fam.expected_held_experts(c, 1) == 1.0
    one_expert = 3 * 5120 * 1536
    assert fam.expert_flops_per_chunk(c, 512) == 2 * 4 * 512 * one_expert
    rows, first = 512, 24064                    # a launch deep in a prompt
    context = sum(p + 1 for p in range(first, first + rows))
    assert fam.index_flops(c, rows, context) == 2 * 2 * 64 * 128 * context
    assert fam.index_bytes(c, rows, context) == 2 * (first + 2 * rows) * 256
    per_position = 2 * 128 * (2 * 512 + 64)
    assert per_position == 278528
    assert fam.attn_flops(c, rows, context) == 2 * per_position * rows * 2048
    assert fam.attn_bytes(c, rows, context) == 2 * (2048 + rows) * 1280
    swa = 2 * 64 * (2 * 1024 + 64)
    assert fam.ring_flops(c, rows, context) == 3 * swa * rows * 513
    assert fam.ring_bytes(c, rows, context) == 3 * (513 + 2 * rows) * 2304
    matrices = fam.prefill_flops(c, 1, 1) - fam.index_flops(c, 1, 1) \
        - fam.attn_flops(c, 1, 1) - fam.ring_flops(c, 1, 1)
    assert round(matrices / 1e7) == 193
    assert fam.prefill_flops(c, rows, context) == matrices * rows \
        + fam.index_flops(c, rows, context) \
        + fam.attn_flops(c, rows, context) + fam.ring_flops(c, rows, context)
    # a launch that straddles the selection's size counts each row's own
    early = sum(min(p + 1, 2048) for p in range(1800, 2312))
    assert fam.attn_flops(c, 512, sum(range(1801, 2313))) \
        == 2 * per_position * early
    # under it the read is the causal one
    assert fam.attn_flops(c, 512, sum(range(1, 513))) \
        == 2 * per_position * sum(range(1, 513))


def test_the_operands_are_those_of_the_program_s_arrays():
    """The families' patterns against op texts of the cell's traced run on
    the chip (my chip run, PR 49, call E): each finds its own ops and none
    of another's."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    texts = {
        "score": "%fusion.984 = f32[512,1024]{1,0:T(8,128)S(1)} fusion(bf16["
                 "1024,128,1]{1,0,2:T(8,128)(2,1)S(1)} %bitcast.1740, f32[512,"
                 "64]{0,1:T(8,128)S(1)} %bitcast.1765, bf16[1,512,64,128]{3,1,"
                 "2,0:T(8,128)(2,1)S(1)} %get-tuple-element.2144), kind=kOutput",
        "keys": "%fusion.968 = bf16[64,16,128]{2,1,0:T(8,128)(2,1)S(1)} fusion("
                "bf16[2,16385,16,128]{3,2,1,0:T(8,128)(2,1)} %gte, s32[64]{0} "
                "%or_bitcast_fusion.4), kind=kCustom",
        "fetch": "%fusion.990 = bf16[262144,640]{1,0:T(8,128)(2,1)} fusion(bf16"
                 "[2,262160,640]{2,1,0:T(8,128)(2,1)} %get-tuple-element.2181, "
                 "s32[262144]{0:T(1024)S(1)} %bitcast.1710), kind=kCustom",
        "product": "%fusion.991 = f32[128,128,2048]{2,1,0:T(8,128)} fusion(bf16"
                   "[128,2048,640]{2,1,0:T(8,128)(2,1)} %bitcast.1577, pred[128"
                   ",2048]{1,0} %copy-done.16, bf16[4,1,128,128,640]{4,3,2,1,0}"
                   " %get-tuple-element.2)",
        "softmax": "%fusion.992 = (f32[128,128]{1,0:T(8,128)S(1)}, f32[128,128,"
                   "2048]{2,1,0:T(8,128)}) fusion(f32[128,128,2048]{2,1,0:T(8,"
                   "128)} %fusion.991), kind=kOutput",
        "sort": "%sort.73 = (f32[1,512,32768]{1,2,0:T(8,128)}, s32[1,512,32768]"
                "{1,2,0:T(8,128)}) sort(f32[1,512,32768]{1,2,0:T(8,128)S(1)} "
                "%copy.461, s32[1,512,32768]{1,2,0:T(8,128)} %broadcast)",
        "merge": "%sort.80 = (f32[1,512,4096]{1,2,0:T(8,128)}, s32[1,512,4096]"
                 "{1,2,0}) sort(f32[1,512,4096]{1,2,0} %concatenate.1, s32[1,"
                 "512,4096]{1,2,0} %concatenate.2)",
        "switch": "%cond.29 = (s32[1,512,2048]{2,1,0:T(8,128)}, f32[1,512,2048]"
                  "{2,1,0:T(8,128)}) conditional(s32[]{:T(128)} %clamp.3, (f32["
                  "1,512,32768]{2,1,0:T(8,128)S(1)}, s32[1,32768]{1,0}) %cond)",
        "ring": "%fusion.887 = (f32[512,64]{0,1:T(8,128)S(1)}, f32[512,64,1040]"
                "{0,2,1:T(8,128)}) fusion(bf16[1,512,64,32]{1,3,2,0} %gte.965, "
                "bf16[1,1040,1152]{2,1,0:T(8,128)(2,1)} %dynamic-slice)",
        "ring_write": "%fusion.23 = bf16[3,9,1040,1152]{3,2,1,0:T(8,128)(2,1)} "
                      "fusion(bf16[3,9,1040,1152]{3,2,1,0} %ring, s32[512,3] "
                      "%at, bf16[512,1152]{1,0} %rows), kind=kCustom",
        "sampler": "%sort.9 = (f32[8,19008]{1,0}, s32[8,19008]{1,0}) sort(f32["
                   "8,19008]{1,0} %logits, s32[8,19008]{1,0} %iota)",
        "pool_write": "%fusion.17 = bf16[2,16385,16,640]{3,2,1,0:T(8,128)(2,1)}"
                      " fusion(bf16[2,16385,16,640]{3,2,1,0} %kv, s32[512,3] "
                      "%at, bf16[512,640]{1,0} %rows), kind=kCustom",
        "matrix": "%fusion.118 = bf16[512,13824]{1,0:T(8,128)(2,1)S(1)} fusion("
                  "bf16[1,5120,13824]{2,1,0:T(8,128)(2,1)} %w_gate, bf16[512,"
                  "5120]{1,0:T(8,128)(2,1)S(1)} %x)",
    }
    finds = {name: {k for k, t in texts.items() if getattr(fam, name)(c)
                    .search(t)}
             for name in ("index_operand", "attn_operand", "ring_operand",
                          "select_operand")}
    assert finds == {
        "index_operand": {"score", "keys"},
        "attn_operand": {"fetch", "product", "softmax"},
        "ring_operand": {"ring", "ring_write"},
        "select_operand": {"fetch", "sort", "merge", "switch"}}


def _ctx(ops, programs, counters=None, kind="TPU v5 lite"):
    cell = type("Cell", (), {"config": _published()})()
    return {"cell": cell, "device": {"kind": kind},
            "trace": {"programs": programs, "ops": ops,
                      "counters": counters or {}}}


def test_the_rooflines_read_their_ops_and_nothing_else():
    """Each reader over a hand-made reduction: the ops of the chunk whose
    text shows its family's operand count, loops and the burst's do not;
    a trace without such ops or without the counter, and a family without
    the functions, give None rather than raising."""
    from bench.harness import spec
    from bench.harness.peaks import peaks

    c = _published()
    fam = spec.family(c)
    rows, first = 512, 24064
    context = sum(p + 1 for p in range(first, first + rows))
    counters = {"bench.count.prefill": {"each": [
        {"tokens": rows, "context": context, "chunks": 1}]}}
    programs = {"paged_prefill_chunk": {"count": 4, "seconds": 0.4}}
    keys = "bf16[2,16385,16,128]{3,2,1,0} %idx"
    flat = "bf16[2,262160,640]{2,1,0} %pool"
    ops = {
        "a": {"program": "paged_prefill_chunk", "seconds": 0.040,
              "text": f"%fusion.1 = f32[1,512,1024] fusion({keys})"},
        "loop": {"program": "paged_prefill_chunk", "seconds": 0.400,
                 "text": f"%while.1 = (s32[], {keys}) while(%tuple)"},
        "burst": {"program": "paged_decode_burst", "seconds": 0.100,
                  "text": f"%fusion.2 = f32[8,1,1024] fusion({keys})"},
        "read": {"program": "paged_prefill_chunk", "seconds": 0.080,
                 "text": "%fusion.3 = f32[1,128,128,2048] fusion(bf16[1,128,"
                         "2048,640]{3,2,1,0} %rows)"},
        "ring": {"program": "paged_prefill_chunk", "seconds": 0.020,
                 "text": "%fusion.4 = f32[1,512,64,1040] fusion(bf16[1,1040,"
                         "1152]{2,1,0} %ring)"},
        "topk": {"program": "paged_prefill_chunk", "seconds": 0.050,
                 "text": "%sort.1 = (f32[1,512,16384]{2,1,0}, s32[1,512,16384]"
                         "{2,1,0}) sort(f32[1,512,16384]{2,1,0} %scores)"},
        "fetch": {"program": "paged_prefill_chunk", "seconds": 0.010,
                  "text": f"%fusion.7 = bf16[262144,640]{{1,0}} fusion({flat}, "
                          "s32[262144]{0} %at), kind=kCustom"},
        "carry": {"program": "paged_prefill_chunk", "seconds": 0.300,
                  "text": f"%while.9 = (s32[], {flat}) while(%tuple.2)"},
    }
    peak = peaks("TPU v5 lite")
    ctx = _ctx(ops, programs, counters)
    args = dict(program="paged_prefill_chunk", counter="bench.count.prefill")
    for name, flops, nbytes, seconds in (
            ("dsa_index_roofline", fam.index_flops, fam.index_bytes, 0.040),
            ("dsa_attn_roofline", fam.attn_flops, fam.attn_bytes, 0.090),
            ("latent_swa_roofline", fam.ring_flops, fam.ring_bytes, 0.020)):
        least = max(flops(c, rows, context) / peak["bf16_flops"],
                    nbytes(c, rows, context) / peak["hbm_bytes_per_s"])
        assert _reader(name)(ctx, **args) == pytest.approx(
            100 * least / (seconds / 4)), name
        assert _reader(name)(_ctx({"burst": ops["burst"]}, programs,
                                  counters), **args) is None
        assert _reader(name)(_ctx(ops, programs), **args) is None
        assert _reader(name)(_ctx(ops, {}, counters), **args) is None
    assert _reader("dsa_select_share")(
        ctx, program="paged_prefill_chunk") == pytest.approx(100 * 0.06 / 0.4)
    assert _reader("dsa_select_share")(
        _ctx({"a": ops["a"]}, programs), program="paged_prefill_chunk") is None
    with open(os.path.join(ROOT, "bench", "configs",
                           "glm-4.7-flash-serve-1chip.json")) as f:
        other = type("Cell", (), {"config": json.load(f)})()
    for name in ("dsa_index_roofline", "dsa_attn_roofline",
                 "latent_swa_roofline"):
        assert _reader(name)(dict(ctx, cell=other), **args) is None
    assert _reader("dsa_select_share")(
        dict(ctx, cell=other), program="paged_prefill_chunk") is None


def test_the_selected_share_is_the_tick_log_s_counts():
    """`dsa_selected_share` from a hand-made tick log: the window's ticks'
    selected over scored; None from a log without the fields (the parent's)
    and from a window that scored nothing."""
    read = _reader("dsa_selected_share")
    fields = ("start", "tick_s", "lanes", "index_scored_tokens",
              "kv_selected_tokens")
    phases = [{"id": "r0", "submitted": 10.0, "ttft_s": 2.0}]
    outcome = type("O", (), {"cause": None, "first": 12.0,
                             "request_id": "r0"})()

    def ctx(fields, log):
        return {"run": {"outcomes": [outcome]},
                "replica": {"stats": {"request_phases": phases,
                                      "tick_fields": fields,
                                      "tick_log": log}}}

    log = [(9.0, 0.1, 0, 10 ** 6, 10 ** 6),          # before the window
           (10.5, 0.1, 0, 2 * 10 ** 7, 2 * 10 ** 6),
           (11.0, 0.1, 4, 10 ** 7, 10 ** 6)]
    assert read(ctx(fields, log)) == pytest.approx(10.0)
    assert read(ctx(fields[:3], [t[:3] for t in log])) is None
    assert read(ctx(fields, [t[:3] + (0, 0) for t in log])) is None


def test_the_new_entries_only_add_to_the_benchmark():
    """BENCHMARK.json against the parent's lists: one configuration, one
    cell and five metrics at the ends, and the cell's name at the end of
    the lists ISSUE 49 names: those of `granite4h-longprompt`, the other
    closed-loop cell with a held share, but for its state-space
    metrics."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert b["configs"][-1]["name"] == "dots3-note-prev-serve-1chip"
    assert b["configs"][-1]["reduced"] == _published()["reduced"]
    assert b["workloads"][-1] == dict(
        b["workloads"][-1], name=CELL, chips=1,
        config="dots3-note-prev-serve-1chip", traffic="longdoc-closed4")
    new = ["dsa_index_roofline", "dsa_attn_roofline", "latent_swa_roofline",
           "dsa_select_share", "dsa_selected_share"]
    assert [m["name"] for m in b["per_layer"][-5:]] == new
    assert all(m["workloads"] == [CELL] and m["moves"] == "ttft_p50_ms"
               for m in b["per_layer"][-5:])
    has_new = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if m.get("workloads", [])[-1:] == [CELL]}
    has_granite = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                   if "granite4h-longprompt" in m.get("workloads", [])}
    assert has_new == (has_granite - {
        "ssd_scan_roofline", "state_reset_ms.long",
        "ssm_state_roofline.long"}) | set(new)
    from bench.harness import spec

    cell = spec.load_cell(CELL)
    assert cell.programs() == ["paged_prefill_chunk"]
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "setup_s"]
    assert cell.traffic["prompt_len"] == {"kind": "uniform", "min": 16384,
                                          "max": 24576}
    assert cell.traffic["clients"] == 4
    assert cell.traffic["max_tokens"] == {"kind": "const", "value": 32}
    spec.check_requests(
        [type("R", (), {"index": 0, "prompt_len": 24576, "max_tokens": 32})()],
        cell.config["engine"])


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinydots-closed", grown_root)
    c = cell.config
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg, device.seeded_params(fam, cfg, SEED),
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"])
    try:
        v = logits_check(e, c, SEED)
        assert len(fam._HANDED) == 3        # experts and a selection a lane
        left = next(iter(fam._HANDED.values()))
        assert left["experts"].shape == (108, 6, 3)
        assert left["selected"].shape == (108, 3, 16) and left["first"] == 0
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
         grown_root, "--workload", "tinydots-closed", "--seed", str(SEED),
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
    assert phases["engine_stats"]["prefill_chunks"] > 0
