"""The sdar family (bench/families/sdar.py) enters a copy of the tiny
benchmark as files and entries alone, as bench/tests/test_mellum.py shows
for `mellum`: no file that was there is edited, its cell finds the family,
the comparison that decides `correct` passes the program as it is through
the engine's own scoring entry (eight whole blocks a lane, seeded open
rows, routing handed over), what a pass needs is counted by hand from the
published sizes, the mix's schedule holds every residue of a prompt mod 4,
each new metric's reader returns a number from a synthetic context and
None where its field is missing, and the command itself serves the cell on
the CPU (proxy -> handle -> replica -> PagedLLMEngine filling blocks) up
to the device check."""
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
    root = str(tmp_path_factory.mktemp("sdar") / "root")
    shutil.copytree(os.path.join(DATA, "tinyroot"),
                    os.path.join(root, "bench"))
    manifest = os.path.join(root, "BENCHMARK.json")
    os.rename(os.path.join(root, "bench", "BENCHMARK.json"), manifest)
    with open(manifest) as f:
        tiny = json.load(f)
    tiny.update(end_to_end=[], per_layer=[])     # the tiny tree has none
    before = _digests(os.path.join(root, "bench"))

    added = os.path.join(DATA, "sdarfamily")
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
        os.path.join("configs", "tinysdar-serve.json")]
    assert all(grown[k][:len(v)] == v for k, v in tiny.items()
               if isinstance(v, list)), "an entry that was there was edited"
    return root


def _published():
    with open(os.path.join(ROOT, "bench", "configs",
                           "sdar-30b-a3b-serve-1chip.json")) as f:
        return json.load(f)


def _metric(name):
    from bench.harness import spec

    return spec.load_file(os.path.join(ROOT, "bench", "metrics",
                                       name + ".py"), "bench_metric_")


def test_the_cell_finds_the_family_in_the_harness_s_tree(grown_root):
    from bench.harness import spec

    cell = spec.load_cell("tinysdar-closed", grown_root)
    assert cell.config["family_file"] == os.path.join(
        ROOT, "bench", "families", "sdar.py")
    fam = spec.family(cell.config)
    cfg = fam.program_config(cell.config)
    assert (cfg.diffusion_block, cfg.denoise_steps, cfg.mask_token_id) == (
        4, 2, 500)
    assert cfg.n_layers == 3 and cfg.head_dim == 16 and cfg.qk_norm
    assert cfg.n_experts == 8 and cfg.d_expert == 24
    c = _published()
    assert fam.expert_operand(c).search(
        "fusion(bf16[6,128,2048,768]{3,2,1,0} %w_gate, s32[] %ex)")
    assert fam.select_operand(c).search(
        "%fusion.9 = f32[8,4,151936]{2,1,0} fusion(bf16[2048,151936] %head)")
    assert not fam.select_operand(c).search(
        "%gather = bf16[8,4,2048] gather(bf16[151936,2048]{1,0} %embed)")
    with pytest.raises(spec.SpecError, match="diffusion_block"):
        fam.program_config(dict(cell.config, assumed={"qk_norm": True}))


def test_the_published_configuration_is_the_catalog_s_but_for_depth():
    """Every key of the source under the source's name; the one cut is
    `num_hidden_layers`, 6 of 48; what the source does not give is under
    `assumed`, each with its ground."""
    c = _published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "SDAR-30B-A3B-Chat"]
    assert c["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if c.get(k, "") != v]
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 48}
    assert c["num_hidden_layers"] == 6
    a = c["assumed"]
    assert (a["diffusion_block"], a["denoise_steps"], a["remasking"],
            a["mask_token_id"], a["qk_norm"]) == (
                4, 2, "low_confidence_static", 151669, True)
    assert {"logits_shift", "rope_layout", "open_rows"} <= set(a)
    assert "eight stages" in c["deployment"]
    from bench.harness import spec

    fam = spec.family(c)
    assert round(fam.total_params(dict(c, num_hidden_layers=48)) / 1e7) \
        == 3053
    assert round(fam.total_params(c) * 2 / 1e7) == 872        # 8.72 GB
    assert fam.fills(c) == [2, 2] and fam.passes_per_block(c) == 3
    assert fam.fills(dict(c, assumed=dict(a, denoise_steps=3))) == [2, 1, 1]
    eng = c["engine"]
    assert eng["block_size"] % a["diffusion_block"] == 0
    assert (c["check"]["prompt_len"] - 1) % 4 == 0
    assert (c["check"]["decode_steps"] + 1) % 4 == 0


def test_what_a_pass_needs_at_the_published_sizes():
    """`denoise_pass_bytes` and `expert_bytes_per_step` by ISSUE 52's
    arithmetic: attention 18.87 M and router 0.26 M a layer, 128 experts of
    4.719 M of which 8 lanes x 4 rows hit 111.8 expected (ISSUE 52 wrote
    110.8), K / V at 12,288 B a position over six layers, the head in two
    passes of three."""
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    one_expert = 3 * 2048 * 768 * 2
    assert fam.expected_routed_experts(c, 1) == 8
    assert round(fam.expected_routed_experts(c, 32), 1) == 111.8
    assert round(fam.expected_routed_experts(c, 4), 1) == 29.1
    assert fam.expert_bytes_per_step(c, 8) == \
        6 * fam.expected_routed_experts(c, 32) * one_expert
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert attn == 18_874_368 and 2048 * 128 == 262_144
    dense = 6 * (attn + 2048 * 128) * 2
    head = 2048 * 151936 * 2 * 2 / 3
    kv = 6 * 2 * 4 * 128 * 2
    assert kv == 12288
    assert fam.denoise_pass_bytes(c, 8 * 1500, 8) == pytest.approx(
        dense + head + fam.expert_bytes_per_step(c, 8) + kv * 8 * 1500)
    # ~7.2 GB a pass of 8 lanes, of which the experts 6.3
    assert round(fam.denoise_pass_bytes(c, 8 * 1500, 8) / 1e9, 1) == 7.1
    assert round(fam.expert_bytes_per_step(c, 8) / 1e9, 1) == 6.3
    # a 128-row chunk at position 0: a row sees to the end of its block
    flops = fam.prefill_flops(c, 128, 128 * 129 / 2)
    per_token = 2 * 6 * (attn + 2048 * 128 + 8 * 3 * 2048 * 768)
    assert flops == per_token * 128 \
        + 4 * 4096 * 6 * (128 * 129 / 2 + 128 * 1.5)


def test_the_schedule_holds_every_residue_of_a_prompt_mod_four():
    from bench.harness import schedule, spec

    with open(os.path.join(ROOT, "bench", "traffic",
                           "gen512-closed8.json")) as f:
        mix = json.load(f)
    reqs = []
    gen = schedule.closed_schedule(mix, SEED, 151936)
    for _ in range(16):
        reqs.append(next(gen))
    first, second = reqs[:8], reqs[8:]
    assert sorted(r.prompt_len for r in first) == [
        372, 595, 818, 1041, 1264, 1487, 1710, 1933]
    assert sorted(r.prompt_len % 4 for r in first) == [0, 0, 1, 1, 2, 2, 3, 3]
    assert sorted(r.prompt_len for r in second) == sorted(
        r.prompt_len for r in first)
    assert {r.max_tokens for r in reqs} == {512}
    spec.check_requests(first, _published()["engine"])


def _ctx(ops=None, counter=True, ticks=True):
    c = _published()
    cell = type("Cell", (), {"config": c})()
    fields = ("start", "tick_s", "lanes", "experts_read", "passes",
              "block_tokens")
    log = [(10.0, 0.05, 8, 66.0, 6, 61), (10.1, 0.05, 8, 64.0, 6, 64),
           (10.2, 0.05, 0, 0.0, 0, 0)]
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
            "programs": {"paged_denoise_burst": {"count": 5,
                                                 "seconds": 0.3}},
            "counters": {"bench.count.decode": {"each": [
                {"lanes": 8, "kv_tokens": 8 * 1500}]}} if counter else {},
            "ops": ops or {}}}


def test_each_new_metric_reads_a_number_and_none_without_its_field():
    from bench.harness import spec

    c = _published()
    fam = spec.family(c)
    experts = "bf16[6,128,2048,768]{3,2,1,0}"
    head = "bf16[2048,151936]{1,0}"
    ops = {
        "a": {"program": "paged_denoise_burst", "seconds": 0.200,
              "text": f"%fusion.1 = bf16[8,4,768] fusion({experts} %w_gate)"},
        "b": {"program": "paged_denoise_burst", "seconds": 0.030,
              "text": f"%fusion.2 = f32[8,4,151936] fusion({head} %lm_head)"},
        "c": {"program": "paged_denoise_burst", "seconds": 0.500,
              "text": f"%while.3 = (s32[], {head}) while((s32[], {head}) %t)"},
        "d": {"program": "paged_prefill_chunk", "seconds": 0.300,
              "text": f"%fusion.4 = bf16[512,768] fusion({experts} %w_gate)"},
    }
    burst = {"program": "paged_denoise_burst"}
    counted = dict(burst, counter="bench.count.decode")
    passes = 5 * 2 * 3                        # calls x blocks x (T + 1)
    ctx = _ctx(ops)
    assert _metric("denoise_pass_dev_ms").read(
        ctx, scale=1000.0, **burst) == pytest.approx(1000 * 0.3 / passes)
    live = 8 * 1500 + 8 * 4 * 1.5
    # the experts a pass read are the program's count (65 a layer), not
    # the 111.8 that independent routing of 32 rows would expect
    assert _metric("denoise_roofline").read(ctx, **counted) == \
        pytest.approx(100 * fam.denoise_pass_bytes(c, live, 8, 65.0) / 819e9
                      / (0.3 / passes))
    assert _metric("denoise_moe_roofline").read(ctx, **counted) == \
        pytest.approx(100 * 6 * 65.0 * 3 * 2048 * 768 * 2 / 819e9
                      / (0.2 / passes))
    assert fam.expert_bytes_per_step(c, 8, 65.0) \
        < fam.expert_bytes_per_step(c, 8)
    assert _metric("denoise_select_share").read(ctx, **burst) == \
        pytest.approx(100 * 0.03 / 0.3)
    assert _metric("denoise_passes_per_token").read(ctx) == \
        pytest.approx(2 * 6 * 8 / 125)
    # a trace without the program (a parent's), without the ops, without
    # the counter; a tick log without the fields: nothing, and no raise
    bare = _ctx(ops, counter=False, ticks=False)
    assert _metric("denoise_roofline").read(bare, **counted) is None
    assert _metric("denoise_moe_roofline").read(bare, **counted) is None
    assert _metric("denoise_passes_per_token").read(bare) is None
    bare["trace"]["programs"] = {}
    assert _metric("denoise_pass_dev_ms").read(bare, **burst) is None
    assert _metric("denoise_select_share").read(bare, **burst) is None
    none = _ctx({"d": ops["d"]})
    assert _metric("denoise_moe_roofline").read(none, **counted) is None
    assert _metric("denoise_select_share").read(none, **burst) is None
    # a family that fills no blocks gives none of them
    with open(os.path.join(ROOT, "bench", "configs",
                           "mellum2-12b-serve-1chip.json")) as f:
        ctx["cell"].config = json.load(f)
    ctx["trace"]["programs"]["paged_decode_burst"] = {"count": 5,
                                                      "seconds": 0.3}
    assert _metric("denoise_pass_dev_ms").read(
        ctx, program="paged_decode_burst") is None
    assert _metric("denoise_roofline").read(
        ctx, program="paged_decode_burst",
        counter="bench.count.decode") is None


def test_the_benchmark_lists_the_cell_where_its_readers_find_something():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "sdar30b-gen512"
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert entry == bench["workloads"][-1] and entry["chips"] == 1
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if cell in m.get("workloads", [])]
    assert [m["name"] for m in mine][-5:] == [
        "denoise_pass_dev_ms", "denoise_roofline", "denoise_moe_roofline",
        "denoise_select_share", "denoise_passes_per_token"]
    assert all(m["moves"] == "tpot_p50_ms" for m in mine)
    # none of the metrics that divide paged_decode_burst by its steps
    for m in mine:
        with open(os.path.join(ROOT, "bench", "metrics", (
                m["name"] if os.path.exists(os.path.join(
                    ROOT, "bench", "metrics", m["name"] + ".json"))
                else m["name"].rpartition(".")[0]) + ".json")) as f:
            how = json.load(f)
        assert how.get("args", {}).get("program") != "paged_decode_burst", m
    assert {"decode_step_dev_ms", "decode_roofline", "moe_ffn_roofline"} \
        .isdisjoint(m["name"] for m in mine)


def test_logits_check_through_the_engine_s_scoring_entry(grown_root):
    from bench.harness import device, spec
    from bench.harness.deployment import logits_check
    from ray_tpu.serve.llm import PagedLLMEngine

    cell = spec.load_cell("tinysdar-closed", grown_root)
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
    assert v["positions"] == 96 == v["decided"]       # 3 x 8 blocks x 4
    assert v["ok"] and v["worst"] < 1e-4, v           # float32 throughout
    assert v["bound"] == fam.TOLERANCES["LOGITS_REL_EXPERTS"]
    # left to its own top-4 of 8 over 3 layers, few positions are decided
    assert 0.0 < float(own.min()) < 0.2


def test_rehearsal_of_the_cell(grown_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--root",
         grown_root, "--workload", "tinysdar-closed", "--seed", str(SEED),
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
    assert phases["check"]["ok"] and phases["check"]["positions"] == 96
    assert phases["check"]["decided"] == 96
    assert not any(phases["window_compiles"].values())
    stats = phases["engine_stats"]
    # every request streamed its max_tokens: the warm-up's 9, then 8 each
    assert stats["tokens_generated"] == 9 + 8 * (stats["completed"] - 1)
