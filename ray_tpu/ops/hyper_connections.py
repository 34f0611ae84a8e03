"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606): a residual of `n` streams a
position, mixed around every sub-block by coefficients made from the
streams themselves, the stream-to-stream part projected onto the doubly
stochastic matrices by Sinkhorn-Knopp.

A position carries `X` in R^{n x d}.  For a sub-block `F` (an attention
or an FFN with its own pre-norm) with parameters `Phi` (n d, n^2 + 2n;
**stored transposed**, `phi` (n^2 + 2n, n d): 24 columns would stand in a
lane tile of 128 and every pass over `Phi` would move five times its
bytes), `alpha` = (alpha_pre, alpha_post, alpha_res) and `b` (n^2 + 2n),
all float32:

    r        = RMSNorm(vec(X))                 n d values, eps, no weight
    [p|q|R]  = r Phi                           n | n | n^2
    h_pre    = sigmoid(alpha_pre p + b_pre)    (n)
    h_post   = 2 sigmoid(alpha_post q + b_post)     (n)
    M_0      = exp(clip(alpha_res mat(R) + b_res, lo, hi))      (n x n)
    M_t      = rows(cols(M_{t-1})), t = 1 .. iters
               cols: each column / (its sum + eps); rows: each row /
               (its sum + eps)
    H_res    = M_iters
    u        = h_pre X                         (d)   the sub-block's input
    X'       = H_res X + h_post^T F(u)         (n x d)

**The streams are laid flat**, `(..., n d)`, stream `i` the columns
`[i d, (i + 1) d)`: with `n` as a dimension of its own, four rows would
stand in a sublane tile of sixteen and every pass over the streams would
move four times their bytes.  Coefficients, projection and both mixes
are float32 whatever the streams' dtype; the streams are read and
written in their own (`compute_dtype`: bfloat16).

`r Phi` is computed as `(vec(X) Phi) * rsqrt(mean(vec(X)^2) + eps)`, the
norm a scalar a row.  For bfloat16 streams the product is exact in
float32 without float32 passes over the streams: `Phi` is split into
three bfloat16 parts (its leading, middle and trailing eight bits of
mantissa), the streams multiply all three in one product of 3 (n^2 + 2n)
columns with float32 accumulation, and the three results are summed.  A
float32 product at the default precision of a TPU would round `Phi` and
the normed streams to bfloat16 (bfloat16 coefficients, which the
benchmark's check refuses); at the highest it is six passes.

Scopes in a profile: `hc_pre` (norm, projection, sigmoid and the
mix-down), `hc_sinkhorn` (the clip, the exponential, the projection onto
the manifold and its defect), `hc_post` (the mix-up).

**Lowered for a TPU, each of the three is one Pallas kernel**
(`jax.lax.platform_dependent`; everything else and the CPU's tests take
the plain ops below, which the kernels are held to in interpret mode):
`hc_pre` reads a block of rows once for the sum of squares, the product
with `Phi`, the sigmoid and the mix-down (XLA's own fusions pass over the
streams three times: a reduction, a product and the mix); `hc_post`
reads the streams and the sub-block's output once and writes the new
streams (XLA's fusion of the concatenated mixes reads each stream once an
output stream); **`hc_sinkhorn` runs all `iters` rounds in one launch**,
the launch's rows along the lanes and each of the n^2 entries a row of
its own, every sum an add of `n` of them, so that a decode step pays
neither a loop's trip nor a fusion an iteration (the rounds unrolled in
XLA came out as one fusion a round, ~24 device ops a mix and 340 a
launch, and a minute of compiling on a CPU for a test-size program; the
plain op below is a `fori_loop` over sums).  PERF.md section 7, PR 57,
has the device times.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
BF16 = jnp.bfloat16
_LANES = 128
# Rows a step of either kernel holds: 64 rows of 4 x 3584 bfloat16 are
# 1.8 MB, so a step of `hc_post` moves 4 MB against ~0.35 us of its own.
_KERNEL_ROWS = 64
_VMEM_LIMIT = 64 * 2**20


def n_coefficients(n: int) -> int:
    """Columns of `Phi`, and entries of `b`: n | n | n^2."""
    return n * n + 2 * n


def _split3(phi):
    """`phi` (c, n d) float32 as three bfloat16 parts that sum to it, one
    under the other: (3 c, n d)."""
    hi = phi.astype(BF16)
    mid = (phi - hi.astype(F32)).astype(BF16)
    lo = (phi - hi.astype(F32) - mid.astype(F32)).astype(BF16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _times_transposed(x, w, **kw):
    """x (T, n d) w^T for w (rows, n d): (T, rows)."""
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())), **kw)


def _project(x, phi):
    """vec(X) Phi, (T, c) float32: exact products of bfloat16 streams
    with a float32 `phi` in one bfloat16 pass (the file's head), or a
    float32 product at the highest precision for streams in float32."""
    c = phi.shape[0]
    if x.dtype == BF16:
        y = _times_transposed(x, _split3(phi), preferred_element_type=F32)
        return y[..., :c] + y[..., c:2 * c] + y[..., 2 * c:]
    return _times_transposed(x.astype(F32), phi,
                             precision=jax.lax.Precision.HIGHEST)


def _streams(x, n: int):
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d] for i in range(n)]


def hc_mix_down(x, h_pre, n: int):
    """u = h_pre X: x (..., n d), h_pre (..., n) float32 -> (..., d) in
    x's dtype."""
    u = sum(h_pre[..., m, None] * xm.astype(F32)
            for m, xm in enumerate(_streams(x, n)))
    return u.astype(x.dtype)


def _pre_plain(x, phi, alpha, b, n: int, eps: float):
    """(r Phi of the file's head (T, n^2 + 2n) float32, u (T, d))."""
    ss = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    proj = _project(x, phi) * jax.lax.rsqrt(ss + eps)
    h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + b[:n])
    return proj, hc_mix_down(x, h_pre, n)


def sinkhorn(m, iters: int, eps: float):
    """`iters` rounds of (columns, then rows) over m (..., n, n), positive:
    each column over (its sum + eps), then each row over (its sum + eps)."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, one, m)


def res_defect(h_res):
    """The largest |row sum - 1| and |column sum - 1| of each h_res
    (..., n, n): how far the projection stopped from the manifold."""
    n = h_res.shape[-1]
    rows = sum(h_res[..., j] for j in range(n))             # (..., n)
    cols = sum(h_res[..., i, :] for i in range(n))
    return jnp.max(jnp.maximum(jnp.abs(rows - 1.0), jnp.abs(cols - 1.0)),
                   axis=-1)


def hc_coefficients(x, phi, alpha, b, *, n: int, iters: int, eps: float,
                    clamp: Tuple[float, float]):
    """The mixing coefficients of streams x (..., n d) and what the
    sub-block reads: (u (..., d) in x's dtype, h_post (..., n), h_res
    (..., n, n), the projection's defect (...)), the coefficients float32.
    `phi` (n^2 + 2n, n d), `alpha` (3,) and `b` (n^2 + 2n,) are float32.
    The mix-down comes with the coefficients because both read the
    streams: on a TPU they are one pass (`_pre_kernel`)."""
    lead = x.shape[:-1]
    with jax.named_scope("hc_pre"):
        proj, u = _pre(x.reshape(-1, x.shape[-1]), phi.astype(F32),
                       alpha.astype(F32), b.astype(F32), n, eps)
        proj, u = proj.reshape(*lead, -1), u.reshape(*lead, -1)
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                      + b[n:2 * n])
    with jax.named_scope("hc_sinkhorn"):
        m0 = jnp.exp(jnp.clip(alpha[2] * proj[..., 2 * n:] + b[2 * n:],
                              *clamp)).reshape(-1, n, n)
        h_res, defect = _project_onto_manifold(m0, iters, eps)
    return u, h_post, h_res.reshape(*lead, n, n), defect.reshape(lead)


def _post_plain(x, out, h_res, h_post, n: int):
    xs = [xm.astype(F32) for xm in _streams(x, n)]
    f = out.astype(F32)
    return jnp.concatenate([
        (sum(h_res[..., i, m, None] * xs[m] for m in range(n))
         + h_post[..., i, None] * f).astype(x.dtype)
        for i in range(n)], axis=-1)


def hc_mix_up(x, out, h_res, h_post, *, n: int):
    """X' = H_res X + h_post^T F(u): x (..., n d), out (..., d), h_res
    (..., n, n), h_post (..., n) -> (..., n d) in x's dtype, float32
    arithmetic."""
    lead = x.shape[:-1]
    with jax.named_scope("hc_post"):
        new = _post(x.reshape(-1, x.shape[-1]),
                    out.reshape(-1, out.shape[-1]).astype(x.dtype),
                    h_res.reshape(-1, n, n), h_post.reshape(-1, n), n)
    return new.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _kernel_takes(x, n: int) -> bool:
    """Whether the kernels can run these streams on a TPU: bfloat16, each
    stream whole lane tiles, at most a tile of coefficients, rows one
    block or whole blocks."""
    t, nd = x.shape
    return (x.dtype == BF16 and nd % (n * _LANES) == 0
            and 3 * n_coefficients(n) <= _LANES
            and (t <= _KERNEL_ROWS or t % _KERNEL_ROWS == 0))


def _pre_body(x_ref, w_ref, ab_ref, proj_ref, u_ref, *, n: int, c: int,
              eps: float):
    x = x_ref[...]                                           # (R, n d)
    nd = x.shape[1]
    d = nd // n
    y = _times_transposed(x, w_ref[...],
                          preferred_element_type=F32)        # (R, 128)
    # The three parts of the product stand c columns apart: bring the
    # middle and the trailing one under the leading one.
    y = y + pltpu.roll(y, _LANES - c, 1) + pltpu.roll(y, _LANES - 2 * c, 1)
    ss = jnp.zeros((x.shape[0], 1), F32)
    for m in range(n):
        xm = x[:, m * d:(m + 1) * d].astype(F32)
        ss = ss + jnp.sum(xm * xm, axis=1, keepdims=True)
    proj = y * jax.lax.rsqrt(ss / nd + eps)
    proj_ref[...] = proj
    h = jax.nn.sigmoid(ab_ref[0:1, :] * proj + ab_ref[1:2, :])
    u = jnp.zeros((x.shape[0], d), F32)
    for m in range(n):
        u = u + h[:, m:m + 1] * x[:, m * d:(m + 1) * d].astype(F32)
    u_ref[...] = u.astype(u_ref.dtype)


def _pre_kernel(x, phi, alpha, b, n: int, eps: float):
    """`_pre_plain` in one pass over a block of rows: the streams are read
    once for the sum of squares, the product, the sigmoid and the
    mix-down."""
    t, nd = x.shape
    c = phi.shape[0]
    rows = min(t, _KERNEL_ROWS)
    w = jnp.pad(_split3(phi), ((0, _LANES - 3 * c), (0, 0)))
    ab = jnp.zeros((8, _LANES), F32).at[0, :n].set(alpha[0]) \
        .at[1, :n].set(b[:n])
    proj, u = pl.pallas_call(
        functools.partial(_pre_body, n=n, c=c, eps=eps),
        grid=(t // rows,),
        in_specs=[pl.BlockSpec((rows, nd), lambda i: (i, 0)),
                  pl.BlockSpec((_LANES, nd), lambda i: (0, 0)),
                  pl.BlockSpec((8, _LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
                   pl.BlockSpec((rows, nd // n), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, _LANES), F32),
                   jax.ShapeDtypeStruct((t, nd // n), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="hc_pre",
    )(x, w, ab)
    return proj[:, :c], u


# Rows a step of `_sinkhorn_kernel` holds along its lanes: a launch's, up
# to this many in one block (16 entries x 1,024 float32 are 64 KB).
_SINKHORN_ROWS = 1024


def _sinkhorn_body(m_ref, out_ref, *, n: int, iters: int, eps: float):
    e = [[m_ref[n * i + j:n * i + j + 1, :] for j in range(n)]
         for i in range(n)]
    for _ in range(iters):
        cols = [sum(e[i][j] for i in range(n)) + eps for j in range(n)]
        e = [[e[i][j] / cols[j] for j in range(n)] for i in range(n)]
        rows = [sum(e[i]) + eps for i in range(n)]
        e = [[e[i][j] / rows[i] for j in range(n)] for i in range(n)]
    defect = jnp.zeros_like(e[0][0])
    for i in range(n):
        defect = jnp.maximum(defect, jnp.abs(sum(e[i]) - 1.0))
        defect = jnp.maximum(
            defect, jnp.abs(sum(e[k][i] for k in range(n)) - 1.0))
        for j in range(n):
            out_ref[n * i + j:n * i + j + 1, :] = e[i][j]
    out_ref[n * n:n * n + 1, :] = defect


def _sinkhorn_kernel(m, iters: int, eps: float):
    """`sinkhorn` and `res_defect` of m (T, n, n) in one launch: the rows
    of the launch along the lanes, each entry a row of its own, every
    round's sums adds of `n` of them."""
    t, n, _ = m.shape
    cols = t if t <= _SINKHORN_ROWS else _SINKHORN_ROWS
    height = -(-(n * n + 1) // 8) * 8
    out = pl.pallas_call(
        functools.partial(_sinkhorn_body, n=n, iters=iters, eps=eps),
        grid=(t // cols,),
        in_specs=[pl.BlockSpec((n * n, cols), lambda i: (0, i))],
        out_specs=pl.BlockSpec((height, cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((height, t), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="hc_sinkhorn",
    )(m.reshape(t, n * n).T)
    return out[:n * n].T.reshape(t, n, n), out[n * n]


def _project_onto_manifold(m, iters: int, eps: float):
    """(H_res (T, n, n), its defect (T,)) of M_0 = m (T, n, n)."""
    def plain(m):
        h_res = sinkhorn(m, iters, eps)
        return h_res, res_defect(h_res)

    t = m.shape[0]
    if m.dtype != F32 or (t > _SINKHORN_ROWS and t % _SINKHORN_ROWS):
        return plain(m)
    return jax.lax.platform_dependent(
        m, tpu=functools.partial(_sinkhorn_kernel, iters=iters, eps=eps),
        default=plain)


def _pre(x, phi, alpha, b, n: int, eps: float):
    plain = functools.partial(_pre_plain, n=n, eps=eps)
    if not _kernel_takes(x, n):
        return plain(x, phi, alpha, b)
    return jax.lax.platform_dependent(
        x, phi, alpha, b, tpu=functools.partial(_pre_kernel, n=n, eps=eps),
        default=plain)


# Columns of a stream a step of `_post_body` mixes at a time: four
# float32 pieces of (64, 512) are 32 vector registers each.
_POST_COLUMNS = 512


def _post_body(x_ref, f_ref, h_ref, out_ref, *, n: int):
    d = f_ref.shape[1]
    h = h_ref[...]                                  # (R, 128): H_res | h_post
    step = _POST_COLUMNS if d % _POST_COLUMNS == 0 else _LANES
    for lo in range(0, d, step):
        f = f_ref[:, lo:lo + step].astype(F32)
        xs = [x_ref[:, m * d + lo:m * d + lo + step].astype(F32)
              for m in range(n)]
        for i in range(n):
            acc = h[:, n * i:n * i + 1] * xs[0]
            for m in range(1, n):
                acc = acc + h[:, n * i + m:n * i + m + 1] * xs[m]
            acc = acc + h[:, n * n + i:n * n + i + 1] * f
            out_ref[:, i * d + lo:i * d + lo + step] = \
                acc.astype(out_ref.dtype)


def _post_kernel(x, out, h_res, h_post, n: int):
    """`_post_plain` with every stream read once: a block of rows of the
    streams and of the sub-block's output in, the new streams out."""
    t, nd = x.shape
    rows = min(t, _KERNEL_ROWS)
    h = jnp.concatenate([h_res.reshape(t, n * n), h_post], axis=1)
    h = jnp.pad(h.astype(F32), ((0, 0), (0, _LANES - h.shape[1])))
    return pl.pallas_call(
        functools.partial(_post_body, n=n),
        grid=(t // rows,),
        in_specs=[pl.BlockSpec((rows, nd), lambda i: (i, 0)),
                  pl.BlockSpec((rows, nd // n), lambda i: (i, 0)),
                  pl.BlockSpec((rows, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, nd), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, nd), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="hc_post",
    )(x, out, h)


def _post(x, out, h_res, h_post, n: int):
    plain = functools.partial(_post_plain, n=n)
    if not _kernel_takes(x, n):
        return plain(x, out, h_res, h_post)
    return jax.lax.platform_dependent(
        x, out, h_res, h_post, tpu=functools.partial(_post_kernel, n=n),
        default=plain)
