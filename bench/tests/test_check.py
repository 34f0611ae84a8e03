"""The comparison that decides `correct` (bench/harness/reference.py,
deployment.logits_check, the family's `score` and `forward`), held to
what it claims: through the engine's
own programs at a tiny size, in bfloat16, it passes the program as it is
and fails it with a layer's output dropped, an expert's output dropped,
or the KV cache kept in 8-bit floats.  The reference always sees the
true parameters; the faults are put into what the engine runs."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TINY = os.path.join(ROOT, "bench", "tests", "data", "tinyroot", "configs",
                    "tiny-serve.json")
SEED = 3


def _config(experts):
    with open(TINY) as f:
        c = json.load(f)
    return dict(c, num_local_experts=experts, param_dtype="bfloat16",
                compute_dtype="bfloat16", cache_dtype="bfloat16")


@pytest.fixture(scope="module", params=[4, 0], ids=["experts", "dense"])
def built(request):
    from bench.harness import device, spec
    from ray_tpu.serve.llm import PagedLLMEngine

    c = _config(request.param)
    fam = spec.family(c)
    cfg, eng = fam.program_config(c), c["engine"]
    e = PagedLLMEngine(
        cfg, device.seeded_params(fam, cfg, SEED), num_slots=eng["num_slots"],
        max_len=eng["max_len"], block_size=eng["block_size"],
        prefill_chunk=eng["prefill_chunk"])
    return e, c


def _zeroed(params, name, index):
    blocks = dict(params["blocks"])
    blocks[name] = blocks[name].at[index].set(0)
    return dict(params, blocks=blocks)


def _layer_dropped(e, c):
    e.params = _zeroed(_zeroed(e.params, "wo", -1), "w_down", -1)


def _expert_dropped(e, c):
    if not c["num_local_experts"]:
        pytest.skip("no experts")
    e.params = _zeroed(e.params, "w_down", (slice(None), 0))


def _cache_in_8_bits(e, c):
    import jax
    import jax.numpy as jnp

    chunk = e._prefill_chunk_fn

    def rounded(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def prefill(*args):
        cache, last = chunk(*args)
        return jax.tree_util.tree_map(rounded, cache), last

    e._prefill_chunk_fn = prefill


@pytest.mark.parametrize("fault", [None, _layer_dropped, _expert_dropped,
                                   _cache_in_8_bits],
                         ids=["as_it_is", "layer_dropped", "expert_dropped",
                              "cache_in_8_bits"])
def test_logits_check(built, fault, monkeypatch):
    from bench.harness import reference, spec
    from bench.harness.deployment import logits_check

    e, c = built
    fam = spec.family(c)
    true_params, forward = e.params, fam.forward
    monkeypatch.setattr(e, "params", true_params)
    monkeypatch.setattr(e, "_prefill_chunk_fn", e._prefill_chunk_fn)
    monkeypatch.setattr(
        fam, "forward",
        lambda params, *a, **kw: forward(true_params, *a, **kw))
    if fault:
        fault(e, c)
    v = logits_check(e, c, SEED)
    assert v["positions"] == len(v["each"]) == 68
    assert v["decided"] >= reference.MIN_DECIDED
    if fault is None:
        assert v["ok"], v
        if c["num_local_experts"]:
            assert v["decided"] < 68       # the margins do tell positions apart
    else:
        assert not v["ok"] and v["worst_decided"] > v["bound"], v
