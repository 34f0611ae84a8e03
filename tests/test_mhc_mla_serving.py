"""A residual of four streams mixed by manifold-constrained
hyper-connections (`ops.hyper_connections`) in `MLAMoEConfig`'s own stack,
on the served path, held to the xing4 family's plain float32 reference
(`bench/families/xing4.py`, which imports nothing of `ray_tpu/models/` or
`ray_tpu/ops/`, keeps the streams as a dimension of their own and runs the
Sinkhorn rounds as sums over axes); two leading dense layers and three
expert layers (the scan runs three periods), 8 experts top-3 all held; the
rope under YaRN with its factor on the soft-max scale.  The engine computes
the reference's function to 1e-5, and a program with bfloat16 coefficients,
without the clip, or with fewer than 20 rounds does not.  The served
contract's cases are `tests/served_contract.py`'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_contract as contract
from ray_tpu.models import configs, decoding, mla_moe
from ray_tpu.ops import hyper_connections as hc
from ray_tpu.serve.llm import TICK_FIELDS, PagedLLMEngine
from served_contract import Family, seqs

FAM = Family(
    tiny="xing4family/configs/tinyxing4-serve.json",
    registry="tiny-mhc-mla-moe",
    as_registry=dict(norm_eps=1e-6, compute_dtype=contract.FLOAT32),
    published=("xing4.0-29b-a4b", 1e7, 2951),           # "29B" published
    # the norms' gains and the routers' biases on top
    leaves=("xing4.0-29b-a4b", 1e-4), seed=7,
    # the experts the program took and each position's defect
    handed=lambda taken: {"routing": jax.tree.map(np.asarray, taken)},
    deployment=dict(contract.SMALL, engine="paged"))
SEED, EXACT = FAM.seed, FAM.exact
engines, served = contract.fixtures(FAM)


def test_the_tiny_configuration_is_the_registry_s():
    _, cfg = contract.tiny_configuration_is_the_registry_s(FAM)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.n_dense_layers == 2 and cfg.n_expert_layers == 3
    assert cfg.held == (0, 8) and not decoding.counts_routed(cfg)
    assert decoding.counts_defect(cfg) and not cfg.state_by_slot
    assert not decoding.counts_defect(configs.get("tiny-mla-moe"))
    with pytest.raises(ValueError, match="hc_mult"):
        dataclasses.replace(cfg, hc_mult=1)


def test_the_yarn_scale_is_built_in_one_place():
    """(d_nope + d_rope)^-0.5 x (0.1 mscale_all_dim ln factor + 1)^2, in
    `kind()` alone, and `attention_scale` returns it: 0.14468 at the
    published sizes, as the family's own arithmetic; cos and sin x 1."""
    c = FAM.config()
    fam = FAM.reference(c)
    cfg = FAM.program_config(c)
    assert cfg.attention_scale == cfg.kind("full").scale
    assert cfg.attention_scale == pytest.approx(
        20 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    assert cfg.attention_scale == pytest.approx(fam.softmax_scale(c))
    assert cfg.yarn.cos_sin_factor == 1.0
    xing = configs.get("xing4.0-29b-a4b")
    assert round(xing.attention_scale, 5) == 0.14468
    assert xing.kind("full").row_width == 640
    plain = dataclasses.replace(cfg, yarn=None)
    assert plain.attention_scale == 20 ** -0.5
    no_factor = dataclasses.replace(cfg, yarn_mscale_all_dim=0.0)
    assert no_factor.attention_scale == 20 ** -0.5


def test_published_sizes_give_the_published_parameter_count():
    cfg, shapes = contract.published_parameter_count(FAM)
    assert shapes["hc_attn"]["phi"].shape == (40, 24, 4 * 3584)
    assert shapes["hc_ffn"]["b"].dtype == jnp.float32
    one = dataclasses.replace(cfg, hc_mult=0)
    assert cfg.num_params - one.num_params == 2 * 40 * (14336 * 24 + 27)


def test_chunks_of_unequal_size_then_decode(served, engines):
    """100 prompt tokens as one launch of the 128-row tier and, on a narrow
    engine, as chunks of 32, 32, 32 and a tail of 4 padded, each reading
    the lane's earlier blocks; then 10 decode steps; against the plain
    reference's full forward, on logits."""
    e, c = served
    contract.prefill_then_decode_equals_the_reference(FAM, e, c, 2, 100, 10)
    fam = FAM.reference(c)
    assert fam.LAST["hc_defect_median_program"] == pytest.approx(
        fam.LAST["hc_defect_median"], rel=0.2)
    with engines.private(prefill_chunk=32) as (narrow, _):   # its tiers cut
        narrow._chunk_tiers = [t for t in narrow._chunk_tiers if t <= 32]
        errs = FAM.errors(narrow, c, seqs(2, 100 + 10)[:1, :70], 68)
        assert errs.max() < EXACT, errs


def test_idle_lanes_change_nothing(served):
    """A burst of width 4 with one live lane gives that lane what a burst
    of width 1 gives it: idle lanes write the null block, mix their own
    rows and enter nobody's defect."""
    e, c = served
    cfg = e.cfg
    cache = decoding.init_sequence_state(cfg, 17, 8, num_slots=4,
                                         prefill_chunk=32)
    chunk, burst, _ = decoding.make_paged_engine_fns(cfg)
    toks = jnp.asarray(seqs(1, 32)[0], jnp.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    cache, _, defect = chunk(e.params, cache, toks, table, jnp.int32(0),
                             jnp.int32(20))
    assert 0 < float(defect) < 0.05
    key = jax.random.key(0)
    wide = burst(e.params, cache, jnp.array([5, 0, 0, 0], jnp.int32),
                 jnp.zeros((4, 8), jnp.int32).at[0].set(table),
                 jnp.array([20, 0, 0, 0], jnp.int32),
                 jnp.array([True, False, False, False]),
                 jnp.zeros((4,), jnp.float32), key, n_steps=4)
    cache2 = decoding.init_sequence_state(cfg, 17, 8, num_slots=4,
                                          prefill_chunk=32)
    cache2, _, _ = chunk(e.params, cache2, toks, table, jnp.int32(0),
                         jnp.int32(20))
    one = burst(e.params, cache2, jnp.array([5], jnp.int32), table[None],
                jnp.array([20], jnp.int32), jnp.array([True]),
                jnp.zeros((1,), jnp.float32), key, n_steps=4)
    np.testing.assert_array_equal(np.asarray(wide[1])[:, 0],
                                  np.asarray(one[1])[:, 0])
    assert float(wide[-1]) == pytest.approx(float(one[-1]), rel=1e-3)
    assert len(wide) == len(one) == 5        # ..., visited, the defect


def test_expansion_and_contraction(monkeypatch):
    """With a mixing that passes stream 0 through (h_pre = e_0, H_res = I,
    h_post = e_0) the stack is the one-stream model on stream 0 plus the
    untouched copies of the embedding in the other three, which the
    contraction sums: logits of (x_L + 3 E[token])."""
    cfg = dataclasses.replace(configs.get("tiny-mhc-mla-moe"),
                              compute_dtype=jnp.dtype("float32"))
    params = cfg.init_params(jax.random.key(SEED))
    n, d = cfg.hc_mult, cfg.d_model
    eye = jnp.broadcast_to(jnp.eye(n), (1, 24, n, n))
    first = jnp.zeros((1, 24, n)).at[..., 0].set(1.0)

    def through(x, phi, alpha, b, **kw):
        return x[..., :d], first, eye, jnp.zeros(x.shape[:-1])

    monkeypatch.setattr(mla_moe, "hc_coefficients", through)
    state = cfg.init_state(9, 8, 0, 0)
    toks = jnp.asarray(seqs(1, 24), jnp.int32)
    args = (toks, jnp.arange(1, 9, dtype=jnp.int32)[None],
            jnp.arange(24, dtype=jnp.int32)[None], jnp.array([24], jnp.int32))
    _, x, *_ = mla_moe._served_step(params, state, *args, cfg)
    one = dataclasses.replace(cfg, hc_mult=0)
    _, x1, *_ = mla_moe._served_step(params, cfg.init_state(9, 8, 0, 0),
                                     *args, one)
    assert x.shape == x1.shape == (1, 24, d)
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(x1 + 3 * params["embed"][toks]),
        rtol=1e-5, atol=1e-5)


def test_the_defect_is_in_the_tick_log(served):
    """`hc_res_defect`: the largest of a tick's launches, reduced on the
    device, read with the records; near what the reference's own 20 rounds
    leave on the same rows; 0 from a tick that launched nothing, and from a
    model with one stream."""
    e, c = served
    fields = e.engine_stats()["tick_fields"]
    assert fields == TICK_FIELDS + ("hc_res_defect",)
    at = len(fields) - 1
    prompt = [int(t) for t in seqs(1, 90, seed=3)[0]]
    before = len(e.engine_stats()["tick_log"])
    out = e.generate(prompt, max_tokens=9)
    log = e.engine_stats()["tick_log"][before:]
    seen = [t[at] for t in log]
    assert all(isinstance(v, float) and 0 <= v < 0.05 for v in seen), seen
    assert max(seen) > 1e-6
    fam = FAM.reference(c)
    fam.forward(e.params, jnp.asarray(prompt + out, jnp.int32), c,
                jit=contract.jit, routing=None)
    # the program's rows are the reference's but for the last token's,
    # whose mixes no launch ran
    assert max(seen) == pytest.approx(fam.LAST["hc_res_defect"], rel=0.05)
    assert e.engine_stats(records=False).get("tick_log") is None
    glm = configs.get("tiny-mla-moe")
    other = PagedLLMEngine(glm, glm.init_params(jax.random.key(0)),
                           num_slots=2, max_len=64, block_size=8,
                           prefill_chunk=16)
    try:
        other.generate([1, 2, 3, 4, 5], max_tokens=3)
        stats = other.engine_stats()
        assert stats["tick_fields"] == TICK_FIELDS
        assert {len(t) for t in stats["tick_log"]} == {len(TICK_FIELDS)}
    finally:
        other.shutdown()


def test_served_by_the_deployment():
    with contract.deployed(FAM, configs.get(FAM.registry)) as dep:
        assert dep.stats()["state"]["kv_paged"] \
            == dep.engine.cache.kv.size * 2          # bfloat16


# -- the comparison has teeth -------------------------------------------------
def _bfloat16_coefficients(monkeypatch, cfg):
    inner = hc.hc_coefficients

    def rounded(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)

    def patched(x, phi, alpha, b, **kw):
        u, h_post, h_res, _ = inner(x, rounded(phi), alpha, rounded(b), **kw)
        h_res = rounded(h_res)
        return u, rounded(h_post), h_res, hc.res_defect(h_res)

    monkeypatch.setattr(mla_moe, "hc_coefficients", patched)
    return cfg


def _without_the_clip(monkeypatch, cfg):
    """The clip is there to bind: at +-1 it does on seeded weights, and a
    program that drops it (here: keeps +-30) is another function."""
    return cfg, {"mhc_h_res_clamp_min": -1, "mhc_h_res_clamp_max": 1}


def _five_rounds(monkeypatch, cfg):
    return dataclasses.replace(cfg, hc_sinkhorn_iters=5)


def _mix_up_without_h_post(monkeypatch, cfg):
    inner = hc.hc_mix_up
    monkeypatch.setattr(
        mla_moe, "hc_mix_up", lambda x, out, h_res, h_post, **kw: inner(
            x, out, h_res, jnp.ones_like(h_post), **kw))
    return cfg


@pytest.mark.parametrize("fault", [
    _bfloat16_coefficients, _without_the_clip, _five_rounds,
    _mix_up_without_h_post], ids=lambda f: f.__name__.strip("_"))
def test_the_reference_tells_a_fault_of_the_mixing(engines, fault,
                                                   monkeypatch):
    """(`test_chunks_of_unequal_size_then_decode` holds the program as it
    is to the same 2e-5.)"""
    c = FAM.config()
    cfg = fault(monkeypatch, FAM.program_config(c))
    if isinstance(cfg, tuple):               # the reference's side changes
        cfg, over = cfg
        c = dict(c, **over)
    contract.a_fault_is_seen(FAM, engines, cfg, c, n_prompt=64, seed=2,
                             times=5)
