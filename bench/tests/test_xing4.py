"""The xing4 family (bench/families/xing4.py) enters a copy of the tiny
benchmark as files and entries alone, as bench/tests/test_sdar.py shows for
`sdar`: no file that was there is edited, its cell finds the family, the
comparison that decides `correct` passes the program as it is through the
engine's own scoring entry (routing and each position's defect handed
over) and refuses a projection cut short, what a launch's mixing needs is
counted by hand from the published sizes, each new metric's reader returns
a number from a synthetic context and None where its field is missing, and
the command itself serves the cell on the CPU (proxy -> handle -> replica
-> PagedLLMEngine mixing four streams) up to the device check."""
import dataclasses
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
CELL = "xing4-longprompt"


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
    root = str(tmp_path_factory.mktemp("xing4") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))

    added = os.path.join(DATA, "xing4family")
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
        os.path.join("configs", "tinyxing4-serve.json")]
    assert all(grown[k][:len(v)] == v for k, v in tiny.items()
               if isinstance(v, list)), "an entry that was there was edited"
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "xing4.0-29b-a4b-serve-1chip.json")) as f:
        return json.load(f)


def _metric(name):
    from bench.harness import spec

    return spec.load_file(os.path.join(ROOT, "bench", "metrics",
                                       name + ".py"), "bench_metric_")


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinyxing4-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "xing4.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_res_clamp) == (
        4, 20, (-30.0, 30.0))
    assert cfg.n_dense_layers == 2 and cfg.n_expert_layers == 3
    assert cfg.experts_held is None and cfg.n_experts == 8
    assert cfg.yarn.factor == 8.0 and cfg.yarn.cos_sin_factor == 1.0
    c = _published()
    streams = fam.hc_operand(c)
    assert streams.search("%hc_post.29 = bf16[512,14336]{1,0:T(8,128)(2,1)} "
                          "custom-call(bf16[512,14336]{1,0} %hc_post.28)")
    assert not streams.search("fusion(bf16[1,512,4,3584]{3,2,1,0} %x)")
    assert not streams.search("fusion(bf16[4,3584]{1,0} %four_lanes)")
    assert not streams.search("fusion(bf16[14336,128]{1,0} %phi, "
                              "f32[512,14336]{1,0} %y, bf16[512,3584] %u)")
    assert fam.expert_operand(c).search(
        "custom-call(bf16[6,64,3584,1024]{3,2,1,0} %w_gate)")
    with pytest.raises(spec.SpecError, match="YaRN"):
        fam.program_config(dict(cell.config, rope_scaling={"type": "linear"}))
    with pytest.raises(spec.SpecError, match="ep_size"):
        fam.program_config(dict(cell.config, ep_size=8))


def test_a_program_with_one_stream_is_refused_at_once(monkeypatch):
    """What the parent commit does with the cell: its `MLAMoEConfig` has no
    `hc_mult`, the family says so as a fault in a data file, and the run
    ends before anything loads."""
    from bench.harness import spec
    from ray_tpu.models import mla_moe

    fields = dataclasses.fields
    monkeypatch.setattr(
        dataclasses, "fields", lambda cls: [
            f for f in fields(cls) if cls is not mla_moe.MLAMoEConfig
            or not f.name.startswith("hc_")])
    fam = spec.family(_published())
    monkeypatch.setattr(fam, "_withdraw_app", lambda: None)
    with pytest.raises(spec.SpecError, match="one residual stream"):
        fam.program_config(_published())


def test_the_published_configuration_is_the_catalog_s_but_for_depth():
    """Every key of the source under the source's name; the cut is depth
    alone (`num_hidden_layers` 7 of 40, of which `first_k_dense_replace` 1
    of 2: leading dense layers count once); all 64 experts, the whole
    vocabulary, every width; what the source does not give is under
    `assumed`, each with its ground."""
    c = _published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Xing4.0-29B-A4B"]
    assert c["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if c.get(k, "") != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "first_k_dense_replace", "num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 40,
                              "first_k_dense_replace": 2}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (7, 1)
    assert (c["hc_mult"], c["hc_sinkhorn_iters"], c["n_routed_experts"],
            c["vocab_size"]) == (4, 20, 64, 131072)
    assert {"hc_norm", "hc_sinkhorn_order", "hc_expansion_contraction",
            "hc_seeding", "attention_scale", "num_nextn_predict_layers"} \
        <= set(c["assumed"])
    assert "pipeline" in c["deployment"]
    from bench.harness import spec

    fam = spec.family(c)
    whole = dict(c, num_hidden_layers=40, first_k_dense_replace=2)
    assert round(fam.matrix_params(whole)["total"] / 1e7) == 2951   # "29B"
    assert round(fam.matrix_params(c)["total"] * 2 / 1e7) == 1108  # 11.08 GB
    assert round(fam.softmax_scale(c), 5) == 0.14468
    mem = c["memory"]
    assert 0.25 * 16 < mem["resident_GB"] < 15 and "measured_peak_GB" in mem
    eng = c["engine"]
    assert eng["max_len"] // 2 >= c["check"]["prompt_len"] >= 2048


def test_what_a_launch_s_mixing_needs_at_the_published_sizes():
    """`hc_bytes_per_row` by ISSUE 57's arithmetic: 28,672 B read for the
    coefficients and the mix-down and 7,168 written, 35,840 read and
    28,672 written for the mix-up: 100,352 B a mix, 14 mixes, 719 MB a
    512-row launch; the mixing's products and mixes in `prefill_flops`."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    a_mix = 28_672 + 7_168 + 35_840 + 28_672
    assert a_mix == 100_352 and fam.hc_bytes_per_row(c) == 14 * a_mix
    assert round(fam.hc_bytes_per_row(c) * 512 / 1e6) == 719
    one_expert = 3 * 3584 * 1024
    assert fam.expert_flops_per_chunk(c, 512) == 2 * 6 * 512 * 4 * one_expert
    assert fam.expected_held_experts(c, 1) == 4
    assert round(fam.expected_held_experts(c, 8), 1) == 25.8
    assert fam.expert_bytes_per_chunk(c, 512) == pytest.approx(
        6 * 64 * one_expert * 2, rel=1e-9)                  # every expert
    attn = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 3584
    assert round(attn / 1e5) == 284                         # 28.4 M
    per_token = 2 * (7 * (attn + 2 * 14336 * 24) + 3 * 3584 * 9216
                     + 6 * (3 * 3584 * 1024 + 3584 * 64 + 4 * one_expert)
                     + 2 * 7 * (16 + 8) * 3584)
    assert fam.prefill_flops(c, 128, 128 * 129 / 2) == per_token * 128 \
        + 2 * 7 * 32 * (128 + 64 + 128) * (128 * 129 / 2)


def _ctx(ops=None, counter=True, ticks=True):
    c = _published()
    cell = type("Cell", (), {"config": c})()
    fields = ("start", "tick_s", "lanes", "hc_res_defect")
    log = [(10.0, 0.05, 0, 0.004), (10.1, 0.05, 4, 0.031),
           (10.2, 0.05, 4, 0.0), (99.0, 0.05, 4, 0.5)]
    phases = [{"id": "r1", "submitted": 9.9, "ttft_s": 0.5}]
    outcome = type("O", (), {"cause": None, "first": 1.0,
                             "request_id": "r1"})()
    return {
        "cell": cell, "device": {"kind": "TPU v5 lite"},
        "run": {"outcomes": [outcome]},
        "replica": {"stats": {
            "request_phases": phases,
            "tick_fields": fields if ticks else fields[:3],
            "tick_log": log if ticks else [t[:3] for t in log]}},
        "trace": {
            "programs": {"paged_prefill_chunk": {"count": 10,
                                                 "seconds": 0.2}},
            "counters": {"bench.count.prefill": {"each": [
                {"tokens": 512, "chunks": 1}, {"tokens": 768, "chunks": 2}
            ]}} if counter else {},
            "ops": ops or {}}}


def test_each_new_metric_reads_a_number_and_none_without_its_field():
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    streams = "bf16[512,14336]{1,0:T(8,128)(2,1)}"
    ops = {
        "a": {"program": "paged_prefill_chunk", "seconds": 0.006,
              "text": f"%hc_post.2 = {streams} custom-call({streams} %x)"},
        "b": {"program": "paged_prefill_chunk", "seconds": 0.004,
              "text": f"%hc_pre.1 = (f32[512,128], bf16[512,3584]) "
                      f"custom-call({streams} %x, bf16[14336,128] %w)"},
        "c": {"program": "paged_prefill_chunk", "seconds": 0.050,
              "text": f"%while.3 = (s32[], {streams}) while((s32[], "
                      f"{streams}) %t)"},
        "d": {"program": "paged_prefill_chunk", "seconds": 0.100,
              "text": "%grouped_expert_ffn.12 = f32[512,3584] custom-call("
                      "bf16[6,64,3584,1024]{3,2,1,0} %w_gate)"},
        "e": {"program": "paged_decode_burst", "seconds": 0.300,
              "text": f"%hc_post.9 = bf16[8,14336] custom-call({streams})"},
    }
    chunk = {"program": "paged_prefill_chunk"}
    counted = dict(chunk, counter="bench.count.prefill",
                   on_chip_bytes=128 * 2**20)
    ctx = _ctx(ops)
    rows = (512 + 384) / 2
    # a launch's streams stay on the chip: the product's three bfloat16
    # passes bound it (14 x 2 x 14,336 x 72 a row), not Phi's 19.3 MB
    assert fam.hc_stream_bytes(c, 512) == 512 * 28_672 < 128 * 2**20
    assert fam.hc_phi_bytes(c) == 14 * 24 * 14_336 * 4
    flops = 14 * 2 * 14_336 * 72 * rows
    assert flops / 197e12 > fam.hc_phi_bytes(c) / 819e9
    assert _metric("hc_roofline").read(ctx, **counted) == pytest.approx(
        100 * flops / 197e12 / (0.010 / 10))
    # streams that do not fit are counted at the bandwidth
    tight = dict(counted, on_chip_bytes=2**20)
    assert _metric("hc_roofline").read(ctx, **tight) == pytest.approx(
        100 * (fam.hc_phi_bytes(c) + fam.hc_bytes_per_row(c) * rows) / 819e9
        / (0.010 / 10))
    # a loop whose text is cut before its `while(` is left out by its name
    cut = dict(ops, f={"program": "paged_prefill_chunk", "seconds": 9.0,
                       "text": f"%while.118 = (s32[], {streams}, bf16[7,"})
    assert _metric("hc_roofline").read(_ctx(cut), **counted) == \
        _metric("hc_roofline").read(ctx, **counted)
    assert _metric("hc_chunk_share").read(ctx, **chunk) == pytest.approx(
        100 * 0.010 / 0.2)
    # the window's ticks: 10.0 .. 10.4; the one at 99.0 is outside it
    assert _metric("hc_res_defect").read(ctx) == 0.031
    # a trace without the counter or the ops, a tick log without the
    # field (a parent's program): nothing, and no raise
    bare = _ctx(ops, counter=False, ticks=False)
    assert _metric("hc_roofline").read(bare, **counted) is None
    assert _metric("hc_res_defect").read(bare) is None
    none = _ctx({"d": ops["d"], "c": ops["c"]})
    assert _metric("hc_roofline").read(none, **counted) is None
    assert _metric("hc_chunk_share").read(none, **chunk) is None
    bare["trace"]["programs"] = {}
    assert _metric("hc_chunk_share").read(bare, **chunk) is None
    # a family with one residual stream gives none of them
    with open(os.path.join(ROOT, "bench", "configs",
                           "glm-4.7-flash-serve-1chip.json")) as f:
        ctx["cell"].config = json.load(f)
    assert _metric("hc_roofline").read(ctx, **counted) is None
    assert _metric("hc_chunk_share").read(ctx, **chunk) is None


def test_the_benchmark_lists_the_cell_where_its_readers_find_something():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    # (by name, not by place: a later cell stands behind this one, and the
    # tests of three earlier cells that pinned "the last" fail since)
    assert entry["chips"] == 1 and entry["traffic"] == "longprompt-closed4"
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    (config,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    new = [m for m in mine if m["name"].startswith("hc_")]
    assert [m["name"] for m in new] == [
        "hc_roofline", "hc_chunk_share", "hc_res_defect"]
    assert all(m["workloads"][0] == CELL for m in new)
    assert {m["moves"] for m in mine} == {"ttft_p50_ms", "setup_s"}
    names = {m["name"] for m in mine}
    assert {"prefill_roofline", "moe_chunk_roofline", "moe_experts_read.long",
            "device_idle_share.prefill", "setup_programs"} <= names
    assert "moe_routed_here_share" not in names     # every expert is held
    (ttft,) = [m for m in bench["end_to_end"] if m["name"] == "ttft_p50_ms"]
    assert CELL in ttft["workloads"]
    with open(os.path.join(ROOT, "bench", "traffic",
                           "longprompt-closed4.json"), "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest()[:16] == \
            "48611671190f917f"


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinyxing4-closed", grown_root)
    c = cell.config
    fam = spec.family(c)

    def verdict(cfg):
        eng = c["engine"]
        e = PagedLLMEngine(
            cfg, device.seeded_params(fam, cfg, SEED),
            num_slots=eng["num_slots"], max_len=eng["max_len"],
            block_size=eng["block_size"],
            prefill_chunk=eng["prefill_chunk"])
        try:
            return logits_check(e, c, SEED)
        finally:
            e.shutdown()

    cfg = fam.program_config(c)
    v = verdict(cfg)
    assert len(fam._HANDED) == 3                      # a routing a lane
    assert set(next(iter(fam._HANDED.values()))) == {"experts", "hc_defect"}
    assert v["positions"] == 51 == v["decided"]       # 3 x (1 + 16)
    assert v["ok"] and v["worst"] < 1e-4, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]
    assert fam.LAST["hc_defect_median_program"] == pytest.approx(
        fam.LAST["hc_defect_median"], rel=0.2)
    assert 0 < fam.LAST["hc_defect_median"] < fam.LAST["hc_res_defect"] < 0.05
    # ten rounds of twenty move the logits by less than the bound, and the
    # defects say so: every position is refused
    cut = verdict(dataclasses.replace(cfg, hc_sinkhorn_iters=10))
    assert not cut["ok"] and not cut["finite"], cut
    assert fam.LAST["hc_defect_median_program"] > \
        fam.TOLERANCES["HC_DEFECT_RATIO"] * fam.LAST["hc_defect_median"]


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinyxing4-closed", "--seed", str(SEED),
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
    assert phases["check"]["ok"] and phases["check"]["positions"] == 51
    assert not any(phases["window_compiles"].values())
    stats = phases["engine_stats"]
    assert stats["completed"] == phases["attempted"] + 1      # the warm-up's
