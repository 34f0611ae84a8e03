import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import client, e2e, spec, stats  # noqa: E402

costs = spec.family({"family": "mistral"})       # what a step needs


def test_pct_and_union():
    assert stats.pct([3, 1, 2], 0.5) == 2
    assert stats.pct(list(range(100)), 0.9) == 90
    assert stats.pct([], 0.5) is None
    assert stats.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3.0


def _out(i, due, first, last, tokens, cause=None):
    return client.Outcome(i, 10, tokens if not cause else 8, due, sent=due,
                          first=first, last=last, tokens=tokens, cause=cause)


def test_a_failed_request_misses_every_latency():
    outs = [_out(i, 0.0, 0.1 * (i + 1), 0.1 * (i + 1) + 0.7, 8)
            for i in range(9)]
    run = {"outcomes": outs, "gave_up_s": 100.0, "setup_s": 1.0}
    assert math.isclose(e2e.value("ttft_p50_ms", run), 500.0)
    assert math.isclose(e2e.value("tpot_p50_ms", run), 100.0)
    outs.append(_out(9, 0.0, 0.05, None, 1, cause="tokens"))
    assert math.isclose(e2e.value("ttft_p90_ms", run), 100000.0)
    assert e2e.value("setup_s", run) == 1.0
    assert e2e.value("train_tok_s", run) is None


def test_costs_at_published_widths():
    c = {"hidden_size": 4096, "intermediate_size": 14336,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "num_hidden_layers": 32, "vocab_size": 32768,
         "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
    assert costs.layer_params(c) == 218_103_808
    assert round(costs.total_params(c) / 1e9, 2) == 7.25     # Mistral-7B
    moe = dict(c, vocab_size=32000, num_local_experts=8,
               num_experts_per_tok=2)
    assert round(costs.total_params(moe) / 1e9, 1) == 46.7   # Mixtral-8x7B
    assert costs.layer_params(moe, active_only=True) \
        < costs.layer_params(moe) / 3
    # a dense decode step moves at least every weight once
    assert costs.decode_step_bytes(c, 0, 1) == 2 * (
        32 * 218_103_808 + 4096 * 32768)
    # top-2 of 8: one lane needs 2 experts a layer, many lanes all 8
    assert costs.expected_routed_experts(moe, 1) == 2.0
    assert math.isclose(costs.expected_routed_experts(moe, 2), 3.5)
    assert 7.9 < costs.expected_routed_experts(moe, 16) < 8.0
    one = costs.expert_bytes_per_step(moe, 1)
    assert one == 32 * 2 * 3 * 4096 * 14336 * 2
    attn_router_head = costs.decode_step_bytes(moe, 0, 1) - one
    assert attn_router_head == 2 * (32 * (
        costs.layer_params(moe) - costs.expert_params_per_layer(moe))
        + 4096 * 32000)


def test_logits_verdict_dense_and_experts():
    from bench.harness import reference as R

    inf = float("inf")
    far, near = 10 * R.ROUTER_MARGIN, R.ROUTER_MARGIN / 10
    # no router anywhere (every margin infinite): every position, and
    # the dense bound
    dense = R.logits_verdict([0.01] * 18, [inf] * 18)
    assert dense["ok"] and dense["bound"] == R.LOGITS_REL
    assert not R.logits_verdict([0.01] * 17 + [0.04], [inf] * 18)["ok"]
    assert not R.logits_verdict([0.01] * 17 + [float("nan")],
                                [inf] * 18)["ok"]
    # experts: a position the reference's own margin marks as undecided
    # may be routed otherwise; every decided position is held to the bound
    flips = R.logits_verdict([0.02] * 8 + [0.5] * 10,
                             [far] * 8 + [near] * 10)
    assert flips["ok"] and flips["decided"] == 8
    assert flips["bound"] == R.LOGITS_REL_EXPERTS
    assert flips["each"][0] == [near, 0.5]
    assert not R.logits_verdict([0.02] * 8 + [0.5] * 10,
                                [far] * 9 + [near] * 9)["ok"]
    assert not R.logits_verdict(
        [0.02] * 18, [far] * 5 + [near] * 13)["ok"]   # too few decided
    assert not R.logits_verdict([0.3] * 18, [far] * 18)["ok"]
    # between the two bounds: sound with a router, not without
    assert R.logits_verdict([0.04] * 18, [far] * 18)["ok"]
    assert not R.logits_verdict([0.04] * 18, [inf] * 18)["ok"]
    # a family's own tolerances hold in place of the constants
    import types

    own = types.SimpleNamespace(TOLERANCES={"LOGITS_REL": 0.05})
    assert R.logits_verdict([0.04] * 18, [inf] * 18, own)["ok"]


def test_decode_rooflines_count_the_routed_experts_only():
    """A fake trace: 10 bursts of 8 steps at 24 ms a step, of which the
    expert FFN ops take 12 ms, alternately 1 and 2 lanes wide."""
    import types

    from bench.harness import readers
    from bench.harness.peaks import peaks

    cfg = {"family": "mistral",
           "hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "num_hidden_layers": 3, "vocab_size": 32000,
           "num_local_experts": 8, "num_experts_per_tok": 2,
           "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
           "engine": {"max_burst": 8}}
    each = [{"lanes": 1 + i % 2, "kv_tokens": 0} for i in range(10)]
    ctx = {"cell": types.SimpleNamespace(config=cfg),
           "device": {"kind": "TPU v5 lite"},
           "trace": {
               "programs": {"burst": {"seconds": 10 * 8 * 0.024, "count": 10}},
               "counters": {"c": {"count": 10, "each": each}},
               "ops": {"burst/fusion.1": {
                   "program": "burst", "seconds": 10 * 8 * 0.012,
                   "text": "%fusion.1 = bf16[4,4096] fusion(bf16[4,8,14336] "
                           "%a, bf16[8,14336,4096] %w_down)"},
                   "burst/copy.2": {"program": "burst", "seconds": 1.0,
                                    "text": "%copy.2 = bf16[3,4097] copy()"}}}}
    bw = peaks("TPU v5 lite")["hbm_bytes_per_s"]
    expert = 3 * 4096 * 14336 * 2                   # one expert, one layer
    need = 3 * (2.0 + 3.5) / 2 * expert             # layers x E[distinct]
    got = readers.moe_ffn_roofline(ctx, program="burst", counter="c")
    assert math.isclose(got, 100 * need / bw / 0.012)
    assert got < 100 * 3 * 8 * expert / bw / 0.012 / 2.5   # not all 8 experts
    whole = readers.decode_roofline(ctx, program="burst", counter="c")
    dense = 2 * (3 * (costs.layer_params(cfg)
                      - costs.expert_params_per_layer(cfg)) + 4096 * 32000)
    kv = 2 * 3 * 8 * 128 * 2 * (1.5 * 3.5)   # lanes x half a burst of tokens
    assert math.isclose(whole, 100 * (need + dense + kv) / bw / 0.024)
