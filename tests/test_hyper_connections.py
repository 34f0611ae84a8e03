"""`ray_tpu.ops.hyper_connections` against a dozen lines of `jax.numpy`
written from the equations at the head of that file: the coefficients,
the Sinkhorn-Knopp projection, the mix-down and the mix-up; what the
projection leaves (rows summing to 1, columns to what 20 plain rounds
leave); a clip that binds; 0, 5 and 20 rounds told apart; bfloat16
streams under float32 coefficients; the three Pallas kernels held to the
plain ops in interpret mode; and, with `hc_mult` 0, the latent stack's other
models lowering to the text and drawing the parameters they had at this
PR's parent (commit 749ca8b)."""
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ray_tpu.models import configs, decoding  # noqa: E402
from ray_tpu.ops import hyper_connections as hc  # noqa: E402

N, D, EPS = 4, 128, 1e-6
F32 = jnp.float32


def _inputs(rows, seed=0, dtype=jnp.bfloat16, b_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 4)
    c = hc.n_coefficients(N)
    x = jax.random.normal(k[0], (2, rows, N * D), F32).astype(dtype)
    phi = jax.random.normal(k[1], (c, N * D), F32) * (N * D) ** -0.5
    b = b_scale * jax.random.normal(k[2], (c,), F32)
    out = jax.random.normal(k[3], (2, rows, D), F32).astype(dtype)
    return x, phi, jnp.array([0.7, 1.3, 1.1], F32), b, out


def _plain(x, phi, alpha, b, out, iters=20, clamp=(-30.0, 30.0)):
    """The equations, streams a dimension of their own, float64."""
    x, out = np.asarray(x, np.float64), np.asarray(out, np.float64)
    phi, alpha, b = (np.asarray(a, np.float64) for a in (phi, alpha, b))
    xs = x.reshape(*x.shape[:-1], N, D)
    r = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + EPS)
    p, q, res = np.split(r @ phi.T, [N, 2 * N], axis=-1)
    h_pre = 1 / (1 + np.exp(-(alpha[0] * p + b[:N])))
    h_post = 2 / (1 + np.exp(-(alpha[1] * q + b[N:2 * N])))
    m = np.exp(np.clip(alpha[2] * res + b[2 * N:], *clamp)).reshape(
        *x.shape[:-1], N, N)
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + EPS)        # each column
        m = m / (m.sum(-1, keepdims=True) + EPS)        # each row
    u = np.einsum("...n,...nd->...d", h_pre, xs)
    new = np.einsum("...nm,...md->...nd", m, xs) \
        + h_post[..., None] * out[..., None, :]
    return u, h_post, m, new.reshape(x.shape)


def _op(x, phi, alpha, b, out, iters=20, clamp=(-30.0, 30.0)):
    u, h_post, h_res, defect = hc.hc_coefficients(
        x, phi, alpha, b, n=N, iters=iters, eps=EPS, clamp=clamp)
    return u, h_post, h_res, hc.hc_mix_up(x, out, h_res, h_post, n=N), defect


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


def test_the_op_is_the_equations_in_float32():
    args = _inputs(24, dtype=F32)
    u, h_post, h_res, new, defect = _op(*args)
    wu, wpost, wres, wnew = _plain(*args)
    _close(u, wu, 1e-5)
    _close(h_post, wpost, 1e-5)
    _close(h_res, wres, 1e-5)
    _close(new, wnew, 1e-5)
    # rows sum to 1 up to eps; columns to what 20 plain rounds leave
    _close(np.asarray(h_res).sum(-1), np.ones(wres.shape[:-1]), 3e-6)
    _close(np.asarray(h_res).sum(-2), wres.sum(-2), 1e-5)
    want = np.maximum(np.abs(wres.sum(-1) - 1),
                      np.abs(wres.sum(-2) - 1)).max(-1)
    assert np.abs(np.asarray(defect) - want).max() < 2e-6
    assert defect.shape == (2, 24) and float(defect.max()) < 0.05


def test_bfloat16_streams_under_float32_coefficients():
    """The streams are bfloat16; the projection is exact in float32 (three
    bfloat16 parts of Phi in one product), so the coefficients agree with
    float64 arithmetic on the same streams to float32 rounding, and the
    new streams to one bfloat16 rounding.  Phi rounded to bfloat16 (what a
    float32 product at a TPU's default precision does) is 100 times
    further off."""
    x, phi, alpha, b, out = _inputs(40)
    u, h_post, h_res, new, _ = _op(x, phi, alpha, b, out)
    assert u.dtype == new.dtype == jnp.bfloat16
    assert h_post.dtype == h_res.dtype == F32
    _, wpost, wres, wnew = _plain(x, phi, alpha, b, out)
    _close(h_post, wpost, 3e-6)
    _close(h_res, wres, 3e-6)
    _close(new, wnew, 2 ** -8)
    rounded = phi.astype(jnp.bfloat16).astype(F32)
    _, rpost, rres, _, _ = _op(x, rounded, alpha, b, out)
    assert np.abs(np.asarray(rres) - wres).max() > 3e-4
    assert np.abs(np.asarray(rpost) - wpost).max() > 3e-4


@pytest.mark.parametrize("iters", [0, 5, 20])
def test_the_rounds_are_told_apart(iters):
    args = _inputs(64, seed=3, dtype=F32)
    _, _, h_res, new, defect = _op(*args, iters=iters)
    _, _, wres, wnew = _plain(*args, iters=iters)
    _close(h_res, wres, 1e-5)
    _close(new, wnew, 1e-5)
    _, _, full, _ = _plain(*args)
    gap = np.abs(wres - full).max()
    median = float(np.median(np.asarray(defect)))
    if iters == 20:
        assert gap == 0 and median < 1e-5
    else:
        assert gap > (1e-3 if iters else 0.5)
        assert median > (1e-4 if iters else 0.5)


def test_a_clip_that_binds():
    """With b four times as large the logits pass +-3: clipped there, the
    projected matrix is another, and the op follows the plain version."""
    args = _inputs(32, seed=5, dtype=F32, b_scale=4.0)
    _, _, h_res, new, _ = _op(*args, clamp=(-3.0, 3.0))
    _, _, wres, wnew = _plain(*args, clamp=(-3.0, 3.0))
    _close(h_res, wres, 1e-5)
    _close(new, wnew, 1e-5)
    _, _, free, _ = _plain(*args)
    assert np.abs(wres - free).max() > 0.05


def test_sinkhorn_alone_and_the_mix_down():
    m = jnp.exp(jax.random.normal(jax.random.key(1), (7, N, N), F32))
    got = hc.sinkhorn(m, 20, EPS)
    want = np.asarray(m, np.float64)
    for _ in range(20):
        want = want / (want.sum(-2, keepdims=True) + EPS)
        want = want / (want.sum(-1, keepdims=True) + EPS)
    _close(got, want, 1e-5)
    x = jax.random.normal(jax.random.key(2), (7, N * D), F32)
    h = jax.random.uniform(jax.random.key(3), (7, N), F32)
    _close(hc.hc_mix_down(x, h, N),
           np.einsum("tn,tnd->td", np.asarray(h, np.float64),
                     np.asarray(x, np.float64).reshape(7, N, D)), 1e-5)


@pytest.mark.parametrize("rows", [8, 128])
def test_the_kernels_are_the_plain_ops_in_interpret_mode(rows, monkeypatch):
    """`hc_pre`, `hc_sinkhorn` and `hc_post` as a TPU runs them (one block
    of 8 rows, two of 64), in Pallas's interpreter."""
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    x, phi, alpha, b, out = _inputs(rows // 2, seed=rows)
    x, out = x.reshape(rows, -1), out.reshape(rows, -1)
    assert hc._kernel_takes(x, N)
    proj, u = hc._pre_plain(x, phi, alpha, b, N, EPS)
    kproj, ku = hc._pre_kernel(x, phi, alpha, b, N, EPS)
    _close(kproj, np.asarray(proj, np.float64), 3e-6)
    _close(ku, np.asarray(u, np.float64), 2 ** -7)
    h_res = hc.sinkhorn(jnp.exp(proj[:, 2 * N:]).reshape(rows, N, N), 20, EPS)
    h_post = 2 * jax.nn.sigmoid(proj[:, N:2 * N])
    m0 = jnp.exp(proj[:, 2 * N:]).reshape(rows, N, N)
    kres, kdefect = hc._sinkhorn_kernel(m0, 20, EPS)
    _close(kres, np.asarray(h_res, np.float64), 3e-6)
    assert np.abs(np.asarray(kdefect)
                  - np.asarray(hc.res_defect(h_res))).max() < 2e-6
    got = np.asarray(hc._post_kernel(x, out, h_res, h_post, N), np.float64)
    want = np.asarray(hc._post_plain(x, out, h_res, h_post, N), np.float64)
    # the same sums in the same order: a rounding of a bfloat16 apart, rarely
    assert np.all(np.abs(got - want) <= 2 ** -7 * np.abs(want))
    assert np.mean(got != want) < 1e-3
    # float32 streams, rows that are neither one block nor whole blocks,
    # a stream that is not whole lane tiles: the plain op
    assert not hc._kernel_takes(x.astype(F32), N)
    assert not hc._kernel_takes(jnp.zeros((100, N * D), jnp.bfloat16), N)
    assert not hc._kernel_takes(jnp.zeros((8, N * 64), jnp.bfloat16), N)


# -- with `hc_mult` 0 the stack's other models are what they were -----------
# sha256 of the StableHLO text (`lowered.as_text()`: no locations) of the
# served programs and of the block copy, and of the seeded parameters' bytes,
# taken on this PR's parent (commit 749ca8b) with the shapes below, at the
# tiny presets and at the published sizes: the streams' fields, the scale
# built in `kind()`, the rope's `yarn` and the sixth value of
# `_served_forward` leave GLM-4.7-Flash's and dots3-note-prev's programs as
# they were, to the letter, so that their compiled programs come from the
# cache as before.  (The two bursts of dots3's configurations: taken on
# PR 60's tree, whose burst of a configuration that selects positions hands
# out one count more, its reads by the mask; nothing else was re-taken.)
_AT_THE_PARENT = {
    ("tiny-mla-moe", "chunk"): "d7d54907d46b5ad4",
    ("tiny-mla-moe", "burst"): "99cd8866034ddb13",
    ("tiny-mla-moe", "copy_block"): "de83fbd14fd07de6",
    ("tiny-mla-moe", "verify"): "c783a012baeae859",
    ("tiny-mla-moe", "params"): "e107670e45833d8b",
    ("tiny-dsa-moe", "chunk"): "a6e75b7f8777bca9",
    ("tiny-dsa-moe", "burst"): "75cee36e4489b129",
    ("tiny-dsa-moe", "copy_block"): "0ccb71cf37b52b1b",
    ("tiny-dsa-moe", "params"): "549a413f804597d0",
    ("glm-4.7-flash", "chunk"): "bc2dbb1e926e58bb",
    ("glm-4.7-flash", "burst"): "c59bbdae09aff190",
    ("glm-4.7-flash", "copy_block"): "e5d7f20966d55b2c",
    ("glm-4.7-flash", "verify"): "22ae8d51da6f2f5d",
    ("dots3-note-prev", "chunk"): "ade7a66f83d4f06b",
    ("dots3-note-prev", "burst"): "02ce6c796823b994",
    ("dots3-note-prev", "copy_block"): "0643cc5026860d21",
}


@pytest.mark.filterwarnings("ignore:paged_latent_attention")
@pytest.mark.parametrize("name,program", list(_AT_THE_PARENT),
                         ids=lambda v: str(v))
def test_one_stream_lowers_and_draws_as_at_the_parent(name, program):
    cfg = configs.get(name)
    assert cfg.hc_mult == 0 and cfg.yarn is None
    if program == "params":
        h = hashlib.sha256()
        leaves = jax.tree_util.tree_leaves_with_path(
            cfg.init_params(jax.random.key(0)))
        for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
            h.update(str(path).encode())
            h.update(np.asarray(leaf).tobytes())
        assert h.hexdigest()[:16] == _AT_THE_PARENT[(name, program)]
        return
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: decoding.init_sequence_state(
        cfg, 17, 8, num_slots=4, prefill_chunk=32))
    chunk, burst, _ = decoding.make_paged_engine_fns(cfg)
    by_slot = cfg.state_by_slot
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    lanes = (arr(4, 8), arr(4), arr(4, dtype=jnp.bool_),
             arr(4, dtype=jnp.float32), key)
    if program == "chunk":
        lowered = chunk.lower(params, cache, arr(32), arr(8), arr(), arr(),
                              **({"slot": arr()} if by_slot else {}))
    elif program == "burst":
        lowered = burst.lower(params, cache, arr(4), *lanes, n_steps=4,
                              **({"slots": arr(4)} if by_slot else {}))
    elif program == "copy_block":
        lowered = jax.jit(decoding.copy_block).lower(cache, arr(), arr())
    else:
        lowered = decoding.make_paged_spec_fns(cfg).lower(
            params, cache, arr(4, 3), *lanes)
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == _AT_THE_PARENT[(name, program)]
