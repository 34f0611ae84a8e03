"""The deepseek_v32 family (bench/families/deepseek_v32.py) enters a copy of
the tiny benchmark as files and entries alone, as bench/tests/
test_dots3note.py shows for `dots3note`: no file that was there is edited,
its cell finds the family, the published configuration is the catalog's
but for its four cuts, what a launch and a decode step need is counted
from the published sizes, the five new readers read their ops and counts
and nothing else (and nothing, without raising, from a program that lacks
them), the cell's schedule fits its engine, and the command itself serves
the cell on the CPU (proxy -> handle -> replica -> PagedLLMEngine with an
index-key leaf and a held share of experts chosen by groups) up to the
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
CELL = "dsv32-agent"
NEW = ["dsa_index_roofline.decode", "dsa_attn_roofline.decode",
       "dsa_select_share.decode", "dsa_selected_share.decode",
       "moe_group_open_share.decode"]


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
    root = str(tmp_path_factory.mktemp("dsv32") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))
    added = os.path.join(DATA, "deepseekv32family")
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
        os.path.join("configs", "tinydsv32-serve.json")]
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v3.2-exp-serve-1chip.json")) as f:
        return json.load(f)


def _reader(name):
    from bench.harness import spec

    return spec.load_file(spec.metric_file(
        os.path.join(ROOT, "bench"), name, ".py"), "bench_metric_").read


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinydsv32-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "deepseek_v32.py")
    cfg = spec.family(cell.config).program_config(cell.config)
    assert cfg.kinds == ("full",) * 5 and not cfg.state_by_slot
    assert (cfg.index_heads, cfg.index_dim, cfg.index_top_k) == (4, 16, 16)
    assert (cfg.expert_groups, cfg.expert_groups_kept) == (4, 2)
    assert cfg.experts_held == (0, 2) and cfg.n_experts == 16
    assert cfg.yarn.factor == 8.0 and cfg.yarn_mscale_all_dim == 1.0


def test_the_published_configuration_is_the_catalog_s_but_for_its_cuts():
    """Every number of the source under the source's key; the cuts are
    depth (one dense layer and four expert layers), the experts held (8 of
    256) and the vocabulary (an eighth); no width differs."""
    c = _published()
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 61,
                              "first_k_dense_replace": 3,
                              "n_routed_experts": 256, "vocab_size": 129280}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 8, 16160)
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"]) == (
                7168, 128, 1536, 512, 128, 64, 128, 18432, 2048)
    assert (c["index_n_heads"], c["index_head_dim"], c["index_topk"]) == (
        64, 128, 2048)
    assert (c["n_group"], c["topk_group"], c["num_experts_per_tok"],
            c["n_shared_experts"], c["routed_scaling_factor"]) == (
                8, 4, 8, 1, 2.5)
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (c["rope_theta"], c["rms_norm_eps"], c["model_type"],
            c["max_position_embeddings"], c["num_nextn_predict_layers"]) == (
                10000, 1e-06, "deepseek_v32", 163840, 1)
    for item in ("router_bias", "group_score", "outside_the_kept_groups",
                 "gates", "rope_pairing", "indexer", "index_norm_eps",
                 "num_nextn_predict_layers", "parameter_count"):
        assert item in c["assumed"]
    assert "32 chips share every layer" in c["deployment"]
    assert c["engine"] == {"num_slots": 8, "max_len": 16384,
                           "block_size": 16, "prefill_chunk": 128,
                           "max_burst": 8, "speculation_k": 0}
    assert c["check"] == {"lanes": 2, "prompt_len": 8192, "decode_steps": 16}
    for item in ("parameters_GB", "resident_GB", "measured_peak_GB",
                 "largest_program"):
        assert item in c["memory"]
    from bench.harness import spec

    fam = spec.family(c)
    assert fam.held_range(c) == (0, 8) and fam.published_experts(c) == 256
    assert round(fam.matrix_params(dict(c, **c["published"]))["total"]
                 / 1e8) == 6719
    assert round(fam.matrix_params(c)["total"] / 1e6) == 3226
    cut = fam.program_config(c)
    assert cut.row_width == 640 and cut.n_of("full") == 5
    assert abs(cut.attention_scale - fam.softmax_scale(c)) < 1e-9


def test_what_a_launch_and_a_step_need_at_the_published_sizes():
    """By hand at one shape each: 2 x 64 x 128 FLOP a row and position
    scored and 278.5 kFLOP a row and position attended in each of five
    layers, an index key of 256 B and a latent row of 1,280 B."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    assert fam.routed_choices_per_row(c) == 32
    assert fam.expected_held_experts(c, 1) == 0.25
    per_position = 2 * 128 * (2 * 512 + 64)
    assert per_position == 278528
    # a launch of 512 rows deep in a prompt
    rows, first = 512, 8192
    context = sum(p + 1 for p in range(first, first + rows))
    assert fam.index_flops(c, rows, context) == 5 * 2 * 64 * 128 * context
    assert fam.index_bytes(c, rows, context) == 5 * (first + 2 * rows) * 256
    assert fam.attn_flops(c, rows, context) == 5 * per_position * rows * 2048
    assert fam.attn_bytes(c, rows, context) == 5 * (2048 + rows) * 1280
    early = sum(min(p + 1, 2048) for p in range(1800, 2312))
    assert fam.attn_flops(c, 512, sum(range(1801, 2313))) \
        == 5 * per_position * early
    # a decode step of 7 lanes that hold 60,000 positions between them
    lanes, live = 7, 60000
    assert fam.index_flops_per_step(c, live, lanes) \
        == 5 * 2 * 64 * 128 * live
    assert fam.index_bytes_per_step(c, live, lanes) \
        == 5 * (live + lanes) * 256
    assert fam.attn_flops_per_step(c, live, lanes) \
        == 5 * per_position * lanes * 2048
    assert fam.attn_bytes_per_step(c, live, lanes) \
        == 5 * (lanes * 2048 + lanes) * 1280
    # lanes under the selection's size attend all they hold
    assert fam.attn_bytes_per_step(c, 3000, 2) == 5 * (3000 + 2) * 1280
    assert fam.latent_bytes_per_step(c, live, lanes) \
        == fam.index_bytes_per_step(c, live, lanes) \
        + fam.attn_bytes_per_step(c, live, lanes)
    weights = 2 * (fam.matrix_params(c)["dense"])
    assert round(weights / 1e7) == 340          # 3.40 GB outside the experts
    assert fam.decode_step_bytes(c, live, lanes) == weights \
        + fam.expert_bytes_per_step(c, lanes) \
        + fam.latent_bytes_per_step(c, live, lanes)
    one_expert = 3 * 7168 * 2048 * 2
    assert fam.expert_bytes_per_step(c, lanes) == pytest.approx(
        4 * 8 * (1 - (31 / 32) ** 7) * one_expert)


def test_the_operands_are_those_of_the_program_s_arrays():
    """The family's patterns against op texts of the cell's traced run on
    the chip (my chip run, PR 59, call 1: `paged_decode_burst`): each finds
    its own ops and none of another's."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    texts = {
        "scan": "%fusion.682 = f32[8,1024]{1,0:T(8,128)S(1)} fusion(bf16[8,1024,"
                "128]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.810, f32[8,64]{1,0:T(8,"
                "128)S(1)} %bitcast.824, bf16[8,1,64,128]{3,2,0,1:T(8,128)(2,1)"
                "S(1)} %get-tuple-element.4568), kind=kOutput",
        "keys": "%fusion.681 = bf16[512,16,128]{2,1,0:T(8,128)(2,1)S(1)} fusion("
                "bf16[5,8193,16,128]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element."
                "4567, s32[512]{0:T(512)S(1)} %reshape.1924), kind=kCustom",
        "key_write": "%fusion.655 = bf16[5,8193,16,128]{3,2,1,0:T(8,128)(2,1)} "
                     "fusion(bf16[5,8193,16,128]{3,2,1,0:T(8,128)(2,1)} %gte, "
                     "s32[8]{0:T(128)S(1)} %fusion.642, bf16[8,128]{1,0:T(8,128)"
                     "(2,1)S(1)} %maximum_bitcast_fusion.5), kind=kCustom",
        "fetch": "%fusion.659 = bf16[16384,640]{1,0:T(8,128)(2,1)S(1)} fusion("
                 "bf16[5,131088,640]{2,1,0:T(8,128)(2,1)} %bitcast.785, s32["
                 "16384]{0:T(1024)S(1)} %bitcast.808), kind=kCustom",
        "product": "%fusion.663 = f32[8,128,2048]{2,1,0:T(8,128)S(1)} fusion("
                   "bf16[8,2048,640]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.811, "
                   "pred[8,2048]{1,0:T(8,128)(4,1)S(1)} %compare_bitcast_fusion"
                   ".5, bf16[8,128,640]{2,0,1:T(8,128)(2,1)S(1)} %fusion.662)",
        "values": "%fusion.665 = bf16[8,1,128,640]{3,2,0,1:T(8,128)(2,1)S(1)} "
                  "fusion(bf16[8,2048,640]{2,1,0:T(8,128)(2,1)S(1)} %bitcast."
                  "812, f32[8,128,2048]{2,1,0:T(8,128)S(1)} %gte.4506, f32[8,"
                  "128]{1,0:T(8,128)S(1)} %gte.4507), kind=kOutput",
        "sort": "%sort.11 = (f32[8,1,16384]{2,1,0:T(1,128)S(1)}, s32[8,1,16384]"
                "{2,1,0:T(1,128)S(1)}) sort(f32[8,1,16384]{2,1,0:T(1,128)S(1)} "
                "%neg.17, s32[8,1,16384]{2,1,0:T(1,128)S(1)} %broadcast_in_dim"
                ".131), dimensions={2}, to_apply=%region_45.60",
        "switch": "%conditional.26 = (s32[8,1,2048]{2,1,0:T(1,128)}, f32[8,1,"
                  "2048]{2,0,1:T(8,128)}) conditional(s32[]{:T(128)} %clamp.209"
                  ", (f32[8,1,16384]{2,1,0:T(1,128)S(1)}, s32[8,16384]{1,0:T(8,"
                  "128)S(1)}) %tuple.413, (f32[8,1,16384]{2,1,0:T(1,128)S(1)}",
        "sampler": "%sort.9 = (f32[8,16160]{1,0}, s32[8,16160]{1,0}) sort(f32["
                   "8,16160]{1,0} %logits, s32[8,16160]{1,0} %iota)",
        "pool_write": "%fusion.654 = bf16[5,8193,16,640]{3,2,1,0:T(8,128)(2,1)}"
                      " fusion(bf16[5,8193,16,640]{3,2,1,0:T(8,128)(2,1)} %gte,"
                      " s32[8]{0:T(128)S(1)} %fusion.642, bf16[8,640]{1,0:T(8,"
                      "128)(2,1)S(1)} %fusion.653), kind=kCustom",
        "query": "%fusion.662 = bf16[8,128,640]{2,0,1:T(8,128)(2,1)S(1)} fusion"
                 "(bf16[8,1,128,32]{3,0,2,1} %copy.198, bf16[8,1,128,32]{3,0,2,"
                 "1} %copy.199, bf16[8,1,128,512]{3,0,2,1} %fusion.661)",
        "experts": "%fusion.691 = bf16[8,2048]{1,0:T(8,128)(2,1)S(1)} fusion("
                   "bf16[8,7168]{1,0:T(8,128)(2,1)S(1)} %gte.4615, bf16[4,8,"
                   "7168,2048]{3,2,1,0:T(8,128)(2,1)} %gte.4614, s32[]{:T(128)}"
                   " %gte.4619, s32[]{:T(128)S(6)} %select_n.756), kind=kOutput",
        "out_proj": "%fusion.667 = (f32[8]{0:T(128)S(1)}, bf16[8,1,7168]{2,0,1:"
                    "T(8,128)(2,1)S(1)}) fusion(bf16[8,1,7168]{2,0,1} %gte.4725"
                    ", bf16[5,16384,7168]{2,1,0:T(8,128)(2,1)} %gte.4798, s32[]"
                    " %select_n.750, bf16[128,128,8]{1,2,0} %fusion.666)",
        "matrix": "%fusion.608 = bf16[8,18432]{1,0:T(8,128)(2,1)S(1)} fusion("
                  "bf16[1,7168,18432]{2,1,0:T(8,128)(2,1)} %gte.5019, bf16[8,"
                  "18432]{1,0} %fusion.607, bf16[8,7168]{1,0} %gte.4428)",
    }
    finds = {name: {k for k, t in texts.items()
                    if getattr(fam, name)(c).search(t)}
             for name in ("index_operand", "attn_operand", "select_operand",
                          "expert_operand")}
    assert finds == {
        "index_operand": {"scan", "keys", "key_write"},
        "attn_operand": {"fetch", "product", "values"},
        "select_operand": {"fetch", "sort", "switch"},
        "expert_operand": {"experts"}}


def _ctx(ops, programs, counters=None, kind="TPU v5 lite", config=None):
    cell = type("Cell", (), {"config": config or _published()})()
    return {"cell": cell, "device": {"kind": kind},
            "trace": {"programs": programs, "ops": ops,
                      "counters": counters or {}}}


def test_the_decode_rooflines_read_their_ops_and_nothing_else():
    """Each reader over a hand-made reduction: the ops of the burst whose
    text shows its family's operand count, loops and the chunk's do not;
    a trace without such ops or without the counter, and a family without
    the functions, give None rather than raising."""
    from bench.harness import spec
    from bench.harness.peaks import peaks

    c = _published()
    fam = spec.family(c)
    lanes, kv = 7, 60000
    counters = {"bench.count.decode": {"each": [
        {"lanes": lanes, "kv_tokens": kv}]}}
    programs = {"paged_decode_burst": {"count": 5, "seconds": 0.4}}
    keys = "bf16[5,8193,16,128]{3,2,1,0} %idx"
    flat = "bf16[5,131088,640]{2,1,0} %pool"
    ops = {
        "scan": {"program": "paged_decode_burst", "seconds": 0.040,
                 "text": f"%fusion.1 = f32[8,1,1024] fusion({keys})"},
        "loop": {"program": "paged_decode_burst", "seconds": 0.400,
                 "text": f"%while.1 = (s32[], {keys}) while(%tuple)"},
        "chunk": {"program": "paged_prefill_chunk", "seconds": 0.100,
                  "text": f"%fusion.2 = f32[1,512,1024] fusion({keys})"},
        "read": {"program": "paged_decode_burst", "seconds": 0.080,
                 "text": "%fusion.3 = f32[8,128,2048] fusion(bf16[8,2048,640]"
                         "{2,1,0} %rows)"},
        "topk": {"program": "paged_decode_burst", "seconds": 0.050,
                 "text": "%sort.1 = (f32[8,1,16384]{2,1,0}, s32[8,1,16384]"
                         "{2,1,0}) sort(f32[8,1,16384]{2,1,0} %scores)"},
        "fetch": {"program": "paged_decode_burst", "seconds": 0.010,
                  "text": f"%fusion.7 = bf16[8,2048,640]{{2,1,0}} fusion({flat}"
                          ", s32[8,2048]{1,0} %at), kind=kCustom"},
        "carry": {"program": "paged_decode_burst", "seconds": 0.300,
                  "text": f"%while.9 = (s32[], {flat}) while(%tuple.2)"},
        "cut": {"program": "paged_decode_burst", "seconds": 0.140,
                "text": f"%while.160 = (s32[], bf16[5,8193,16,640], {keys}, "},
    }
    peak = peaks("TPU v5 lite")
    ctx = _ctx(ops, programs, counters)
    args = dict(program="paged_decode_burst", counter="bench.count.decode")
    seen = kv + lanes * 3.5                   # the burst's mean step
    for name, flops, nbytes, seconds in (
            ("dsa_index_roofline.decode", fam.index_flops_per_step,
             fam.index_bytes_per_step, 0.040),
            ("dsa_attn_roofline.decode", fam.attn_flops_per_step,
             fam.attn_bytes_per_step, 0.090)):
        least = max(flops(c, seen, lanes) / peak["bf16_flops"],
                    nbytes(c, seen, lanes) / peak["hbm_bytes_per_s"])
        assert _reader(name)(ctx, **args) == pytest.approx(
            100 * least / (seconds / (5 * 8))), name
        assert _reader(name)(_ctx({"chunk": ops["chunk"]}, programs,
                                  counters), **args) is None
        assert _reader(name)(_ctx(ops, programs), **args) is None
        assert _reader(name)(_ctx(ops, {}, counters), **args) is None
    assert _reader("dsa_select_share.decode")(
        ctx, program="paged_decode_burst") == pytest.approx(100 * 0.06 / 0.4)
    assert _reader("dsa_select_share.decode")(
        _ctx({"scan": ops["scan"]}, programs),
        program="paged_decode_burst") is None
    with open(os.path.join(ROOT, "bench", "configs",
                           "glm-4.7-flash-serve-1chip.json")) as f:
        other = json.load(f)
    for name in NEW[:2]:
        assert _reader(name)(_ctx(ops, programs, counters, config=other),
                             **args) is None


def test_the_tick_log_s_counts_are_read_and_a_log_without_them_is_not():
    """`moe_group_open_share.decode` and `dsa_selected_share.decode` from a
    hand-made tick log: the window's ticks' counts over their rows; None
    from a log without the fields (the parent's) and from a window without
    rows."""
    fields = ("start", "tick_s", "lanes", "prefill_tokens",
              "index_scored_tokens", "kv_selected_tokens", "group_open_rows")
    phases = [{"id": "r0", "submitted": 10.0, "ttft_s": 2.0}]
    outcome = type("O", (), {"cause": None, "first": 12.0,
                             "request_id": "r0"})()
    cell = type("Cell", (), {"config": _published()})()

    def ctx(fields, log):
        return {"cell": cell, "run": {"outcomes": [outcome]},
                "replica": {"stats": {"request_phases": phases,
                                      "tick_fields": fields,
                                      "tick_log": log}}}

    log = [(9.0, 0.1, 8, 512, 10 ** 6, 10 ** 6, 4000),    # before the window
           (10.5, 0.1, 7, 512, 2 * 10 ** 7, 2 * 10 ** 6, 1100),
           (11.0, 0.1, 8, 0, 10 ** 7, 10 ** 6, 140)]
    rows = 512 + 7 * 8 + 8 * 8                  # each once an expert layer
    assert _reader(NEW[4])(ctx(fields, log)) == pytest.approx(
        100 * 1240 / (4 * rows))
    assert _reader(NEW[3])(ctx(fields, log)) == pytest.approx(10.0)
    for name in NEW[3:]:
        assert _reader(name)(ctx(fields[:4], [t[:4] for t in log])) is None
    assert _reader(NEW[4])(ctx(fields, [
        t[:2] + (0, 0) + t[4:] for t in log])) is None


def test_the_new_entries_only_add_to_the_benchmark():
    """BENCHMARK.json: one configuration, one cell and five metrics at the
    ends, and the cell's name at the end of the lists `glm47flash-agent`
    is in but for `mla_attn_roofline` and `moe_ffn_roofline` (PERF.md
    section 3 says why); the cell's schedule fits its engine."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    # by name, not by place: a later PR appends behind these
    (config,) = [c for c in b["configs"]
                 if c["name"] == "deepseek-v3.2-exp-serve-1chip"]
    assert config["reduced"] == _published()["reduced"]
    assert config["source"] == _published()["source"]
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, chips=1, traffic="agent-closed8",
                        config="deepseek-v3.2-exp-serve-1chip")
    assert len(b["workloads"]) >= 13
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    names = [m["name"] for m in b["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + 5] == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
               for m in b["per_layer"][first:first + 5])
    has_new = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if CELL in m.get("workloads", [])}
    has_glm = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if "glm47flash-agent" in m.get("workloads", [])}
    assert has_new == (has_glm - {"mla_attn_roofline", "moe_ffn_roofline"}) \
        | set(NEW)
    from bench.harness import schedule, spec

    cell = spec.load_cell(CELL)
    assert cell.programs() == ["paged_decode_burst", "paged_prefill_chunk"]
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p50_ms", "setup_s"]
    one_block = [r for r, _ in zip(schedule.closed_schedule(
        cell.traffic, SEED, cell.config["vocab_size"]), range(8))]
    spec.check_requests(one_block, cell.config["engine"])
    assert max(r.prompt_len for r in one_block) <= 12288
    assert all(max(r.tokens) < 16160 for r in one_block)


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinydsv32-closed", grown_root)
    c = cell.config
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg, device.seeded_params(fam, cfg, SEED),
        num_slots=eng["num_slots"], max_len=eng["max_len"],
        block_size=eng["block_size"], prefill_chunk=eng["prefill_chunk"])
    try:
        v = logits_check(e, c, SEED)
        assert len(fam._HANDED) == 3    # experts, groups, a selection a lane
        left = next(iter(fam._HANDED.values()))
        assert left["experts"].shape == (108, 4, 3)
        assert left["groups"].shape == (108, 4, 2)
        assert left["selected"].shape == (108, 5, 16) and left["first"] == 0
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
         grown_root, "--workload", "tinydsv32-closed", "--seed", str(SEED),
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
    assert phases["engine_stats"]["prefill_chunks"] > 0
