"""Attention / ring attention / norm / rope correctness vs references."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import (
    flash_attention, mha_reference, ring_attention, rms_norm, apply_rope)
from ray_tpu.parallel import MeshConfig, build_mesh


def _qkv(rng, b=2, t=64, h=4, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, t, h, d), dtype)
    k = jax.random.normal(kk, (b, t, h, d), dtype)
    v = jax.random.normal(kv, (b, t, h, d), dtype)
    return q, k, v


def test_flash_matches_reference_causal():
    q, k, v = _qkv(jax.random.key(0))
    out = flash_attention(q, k, v, True, None)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_grads_finite():
    q, k, v = _qkv(jax.random.key(1), t=32)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None) ** 2)

    gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert np.isfinite(np.asarray(g)).all()
    # grad of flash == grad of reference
    gq_ref = jax.grad(lambda q_: jnp.sum(mha_reference(q_, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gq_ref), atol=1e-4)


@pytest.mark.parametrize("t", [640, 768])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
def test_pallas_kernels_match_reference_in_interpret_mode(monkeypatch, heads,
                                                          causal, t):
    """The three Pallas kernels themselves (forward, dq, dk/dv), run by the
    Pallas interpreter on the CPU, MHA and a group of four query heads a KV
    head, against the reference on explicitly repeated K/V; dK/dV at the KV
    heads' own shape.  The tile caps are cut so that both lengths span
    several tiles: 640 only 128 divides (5 x 5 tiles), 768 takes 384 x 256
    (forward, dq) and 256 x 384 (dk/dv) for MHA, so the diagonal crosses
    tiles off their corners; the causal skip and clamp, the online-softmax
    carry and both backward accumulations are live.  Steered from here (no
    option in the program): `_pallas_eligible` is false off the TPU and
    `pallas_call` compiles for Mosaic."""
    from jax.experimental import pallas as pl

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_pallas_eligible", lambda q, k: True)
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(attention, "_TILE_CAPS", {
        "fwd": (512, 256), "dq": (512, 256), "dkv": (256, 384)})
    h, h_kv = heads
    q, k, v = _qkv(jax.random.key(7), b=1, t=t, h=h, d=64)
    k, v = k[:, :, :h_kv], v[:, :, :h_kv]

    def grads(attn):
        return jax.grad(lambda q_, k_, v_: jnp.sum(attn(q_, k_, v_) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal, None)

    def ref(q_, k_, v_):
        rep = h // h_kv
        return mha_reference(q_, jnp.repeat(k_, rep, axis=2),
                             jnp.repeat(v_, rep, axis=2), causal=causal)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=1e-5)
    # The reference's own grouped form is the same contract.
    np.testing.assert_allclose(
        np.asarray(mha_reference(q, k, v, causal=causal)),
        np.asarray(ref(q, k, v)), atol=1e-5)
    for g, g_ref, like in zip(grads(flash), grads(ref), (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-4)


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [128, 384, 640, 2048, 4096])
def test_flash_blocks_divide_the_lengths_and_fit_vmem(t, d, g):
    """`_blocks_for` only ever names tiles that divide the lengths (the grid
    floors: a partial tile would be skipped in silence), in multiples of the
    128 lanes the row statistics are laid along, and whose working set, by
    the code's own count, is under the VMEM figure the code states."""
    from ray_tpu.ops import attention

    for tkv in (t, 2 * t):
        blocks = attention._blocks_for(t, tkv, d, g)
        assert len(blocks) == 3
        for kernel, (block_q, block_kv) in zip(("fwd", "dq", "dkv"), blocks):
            assert t % block_q == 0 and block_q % 128 == 0
            assert tkv % block_kv == 0 and block_kv % 128 == 0
            assert attention._working_set(
                kernel, block_q, block_kv, d, g,
                2) <= attention._VMEM_WORKING_SET < attention._VMEM_LIMIT


@pytest.mark.parametrize("tp,h_kv", [(2, 2), (4, 2), (8, 2)])
def test_sharded_flash_attention_shards_kv_heads_over_tp(tp, h_kv):
    """Under `tp` the KV heads shard with their query heads; where their
    count does not divide, K/V are repeated just enough that it does (2 KV
    heads over tp=4 become 4, over tp=8 one a device), so every device
    holds the KV heads of its own query heads."""
    from ray_tpu.ops.ring_attention import make_sharded_attention

    mesh = build_mesh(MeshConfig(fsdp=1, tp=tp), devices=jax.devices()[:tp])
    q, k, v = _qkv(jax.random.key(11), b=2, t=32, h=8, d=16)
    k, v = k[:, :, :h_kv], v[:, :, :h_kv]
    attn = make_sharded_attention(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, True, None), mesh,
        axis=None)
    with mesh:
        out = jax.jit(attn)(q, k, v)
    ref = mha_reference(q, jnp.repeat(k, 8 // h_kv, axis=2),
                        jnp.repeat(v, 8 // h_kv, axis=2), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_matches_full():
    mesh = build_mesh(MeshConfig(fsdp=1, sp=8))
    b, t, h, d = 2, 128, 4, 16
    q, k, v = _qkv(jax.random.key(2), b=b, t=t, h=h, d=d)
    spec = P(None, "sp", None, None)

    ring = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh:
        out = ring(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_noncausal():
    mesh = build_mesh(MeshConfig(fsdp=1, sp=4), devices=jax.devices()[:4])
    q, k, v = _qkv(jax.random.key(3), b=1, t=64, h=2, d=16)
    spec = P(None, "sp", None, None)
    ring = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp", causal=False),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    with mesh:
        out = ring(q, k, v)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_rms_norm():
    x = jax.random.normal(jax.random.key(0), (4, 8, 16))
    w = jnp.ones((16,)) * 2.0
    y = rms_norm(x, w)
    norm = np.asarray(jnp.sqrt(jnp.mean(np.asarray(y / 2.0) ** 2, axis=-1)))
    np.testing.assert_allclose(norm, 1.0, atol=1e-3)


def test_rope_rotation_preserves_norm_and_relativity():
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16))
    pos = jnp.arange(8)
    y = apply_rope(x, pos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # dot products depend only on relative offsets: shift positions by 5
    y2 = apply_rope(x, pos + 5)
    d1 = np.einsum("bthd,bshd->bths", np.asarray(y), np.asarray(y))
    d2 = np.einsum("bthd,bshd->bths", np.asarray(y2), np.asarray(y2))
    np.testing.assert_allclose(d1, d2, atol=1e-4)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------

def _ulysses_sharded(mesh, spec, causal=True):
    from ray_tpu.ops import ulysses_attention

    return jax.shard_map(
        lambda q_, k_, v_: ulysses_attention(q_, k_, v_, axis="sp",
                                             causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)


def test_ulysses_attention_matches_full():
    mesh = build_mesh(MeshConfig(fsdp=1, sp=4), devices=jax.devices()[:4])
    q, k, v = _qkv(jax.random.key(4), b=2, t=128, h=8, d=16)
    spec = P(None, "sp", None, None)
    out = _ulysses_sharded(mesh, spec)(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_attention_noncausal():
    mesh = build_mesh(MeshConfig(fsdp=1, sp=2), devices=jax.devices()[:2])
    q, k, v = _qkv(jax.random.key(5), b=1, t=64, h=2, d=16)
    spec = P(None, "sp", None, None)
    out = _ulysses_sharded(mesh, spec, causal=False)(q, k, v)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_matches_ring():
    """The two context-parallel schemes are both exact: same numbers."""
    mesh = build_mesh(MeshConfig(fsdp=1, sp=4), devices=jax.devices()[:4])
    q, k, v = _qkv(jax.random.key(6), b=1, t=128, h=4, d=16)
    spec = P(None, "sp", None, None)
    ring = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp",
                                          causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    np.testing.assert_allclose(
        np.asarray(_ulysses_sharded(mesh, spec)(q, k, v)),
        np.asarray(ring(q, k, v)), atol=2e-5)


def test_ulysses_grads_match_reference():
    mesh = build_mesh(MeshConfig(fsdp=1, sp=2), devices=jax.devices()[:2])
    q, k, v = _qkv(jax.random.key(7), b=1, t=64, h=4, d=16)
    spec = P(None, "sp", None, None)
    uly = _ulysses_sharded(mesh, spec)

    # under `jit`, as a step runs them (op by op: a compile an op)
    gq = jax.jit(jax.grad(lambda q_: jnp.sum(uly(q_, k, v) ** 2)))(q)
    gq_ref = jax.jit(jax.grad(
        lambda q_: jnp.sum(mha_reference(q_, k, v, causal=True) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gq_ref),
                               atol=1e-4)


def test_ulysses_rejects_indivisible_heads():
    import pytest

    mesh = build_mesh(MeshConfig(fsdp=1, sp=4), devices=jax.devices()[:4])
    q, k, v = _qkv(jax.random.key(8), b=1, t=64, h=2, d=16)  # 2 heads, sp=4
    spec = P(None, "sp", None, None)
    with pytest.raises(ValueError, match="divisible"):
        _ulysses_sharded(mesh, spec)(q, k, v)


def test_transformer_forward_ulysses_matches_ring():
    """End-to-end: forward() under sp sharding, both attention modes."""
    import dataclasses

    from ray_tpu.models import configs
    from ray_tpu.models.transformer import forward, init_params

    mesh = build_mesh(MeshConfig(fsdp=1, sp=4), devices=jax.devices()[:4])
    # f32 compute: both schemes are EXACT, so they must agree to fp
    # noise (bf16 would only measure accumulation rounding).
    base = dataclasses.replace(configs.TINY, remat=False,
                               compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), base)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                              base.vocab_size, dtype=jnp.int32)
    outs = {}
    for mode in ("ring", "ulysses"):
        cfg = dataclasses.replace(base, sp_attention=mode)
        outs[mode] = forward(params, toks, cfg, mesh=mesh, seq_shards=4)
    np.testing.assert_allclose(np.asarray(outs["ring"]),
                               np.asarray(outs["ulysses"]), atol=1e-4)
