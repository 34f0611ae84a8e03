"""Flash attention: Pallas TPU kernels + XLA reference.

Online-softmax blockwise attention (Dao et al.) laid out for the MXU, with
one KV head as the unit of work: K and V come at their own head count, and
a query tile holds the rows of all `g = H // Hkv` query heads of the group
for one range of positions, so a fetched K/V tile serves `g` times the
rows and no repeated K/V exists in HBM.  Key/value tiles are swept in the
innermost grid dimension with the running max / denominator / accumulator
in VMEM scratch.  Causal sweeps neither compute nor fetch a tile above the
diagonal (its index map names the tile already resident), and only tiles
the diagonal crosses pay for the mask.

Autodiff: the forward kernel also emits per-row logsumexp, one float32 a
row; the backward is two more kernels (Dao-style): dq accumulates over kv
tiles, dk/dv hold one KV head's tile and sweep the q tiles of its `g`
heads, so dK/dV come out at the KV heads' own shape.  delta = rowsum(do*o)
is one float32 a row too.  Off the TPU both directions use the XLA
reference; on it, a shape the kernels cannot take does too, and says so
once (`_pallas_eligible`).

Reference framework has no attention op (compute is torch's problem there);
this is greenfield per SURVEY.md §2.4.
"""
from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # TPU lane width; scratch stats are replicated across lanes.
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def mha_reference(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                  kv_offset: int = 0, window: int | None = None):
    """Plain-XLA multi-head attention, numerically stable softmax.

    Shapes: q (B, Tq, H, D), k/v (B, Tkv, Hkv, D) with H a multiple of Hkv:
    query head h reads KV head h // (H // Hkv), as `flash_attention` does.
    `kv_offset` shifts kv global positions for causal masking (used by ring
    attention where the local kv block starts at a nonzero global index; q
    is assumed to start at global index `kv_offset=0` frame of its caller).
    `window` (with `causal`): a query at position t sees only the kv
    positions p with t - window < p <= t, itself counted.
    """
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, tq, hkv, h // hkv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        q_pos = jnp.arange(tq)[:, None]
        k_pos = jnp.arange(tk)[None, :] + kv_offset
        seen = q_pos >= k_pos
        if window is not None:
            seen = seen & (k_pos > q_pos - window)
        s = jnp.where(seen, s, _NEG_INF)
    elif window is not None:
        raise ValueError("a window is a causal mask's lower edge")
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(b, tq, h, d)


def _on_live_tiles(causal, qi, ki, block_q, block_kv, compute):
    """Run `compute(masked)` once if the tile at q tile `qi` x kv tile `ki`
    has an entry with q position >= kv position, with the mask only where
    it has one without: where the diagonal crosses the tile."""
    if not causal:
        compute(False)
        return
    live = (qi + 1) * block_q > ki * block_kv
    full = qi * block_q >= (ki + 1) * block_kv - 1
    pl.when(full)(lambda: compute(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(
        lambda: compute(True))


def _seen(qi, ki, block_q, block_kv, q_axis):
    """The causal mask of a tile, q positions along `q_axis` of its two."""
    shape = (block_q, block_kv) if q_axis == 0 else (block_kv, block_q)
    return (qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            >= ki * block_kv
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


def _group_scores(q, k, qi, ki, block_q, block_kv, sm_scale, masked):
    """Scaled scores (g * block_q, block_kv) float32 of a group's q tile
    (g, block_q, d) against one K tile; one position mask for all g heads."""
    g, _, d = q.shape
    s = jax.lax.dot_general(q.reshape(g * block_q, d), k, _NT,
                            preferred_element_type=jnp.float32) * sm_scale
    if masked:
        seen = _seen(qi, ki, block_q, block_kv, 0)
        s = jnp.where(seen[None], s.reshape(g, block_q, block_kv),
                      _NEG_INF).reshape(g * block_q, block_kv)
    return s


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               sm_scale: float, causal: bool, block_q: int, block_kv: int,
               num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    g, _, d = q_ref.shape[1:]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(masked):
        v = v_ref[0]                             # native dtype -> MXU
        s = _group_scores(q_ref[0], k_ref[0], qi, ki, block_q, block_kv,
                          sm_scale, masked)
        m_prev = m_scr[...]                      # (rows, LANES)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)          # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)                     # (rows, LANES)
        p = jnp.exp(s - m_new[:, :1])                       # (rows, block_kv)
        l_new = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    _on_live_tiles(causal, qi, ki, block_q, block_kv, compute)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, ...] = (acc_scr[...] / l_scr[:, :1]).reshape(
            g, block_q, d).astype(o_ref.dtype)
        # logsumexp, one float32 a row with the rows along the lanes: the
        # lane-replicated column turned over, a head a sublane.
        lse = (m_scr[...] + jnp.log(l_scr[...])).T          # (LANES, rows)
        for j in range(g):
            lse_ref[0, j:j + 1, :] = lse[:1, j * block_q:(j + 1) * block_q]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, lse_scr, delta_scr, *, sm_scale: float,
                   causal: bool, block_q: int, block_kv: int,
                   num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    g, _, d = q_ref.shape[1:]
    rows = g * block_q

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # The row statistics come with the rows along the lanes; this sweep
        # wants them a row a sublane, turned once a q tile.
        for j in range(g):
            at = slice(j * block_q, (j + 1) * block_q)
            lse_scr[at, :] = jnp.expand_dims(lse_ref[0, j], -1)
            delta_scr[at, :] = jnp.expand_dims(delta_ref[0, j], -1)

    def compute(masked):
        k = k_ref[0]
        s = _group_scores(q_ref[0], k, qi, ki, block_q, block_kv, sm_scale,
                          masked)
        p = jnp.exp(s - lse_scr[...])                    # (rows, block_kv)
        dp = jax.lax.dot_general(do_ref[0].reshape(rows, d), v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_scr[...])).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, _NN, preferred_element_type=jnp.float32)

    _on_live_tiles(causal, qi, ki, block_q, block_kv, compute)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, ...] = (dq_scr[...] * sm_scale).reshape(
            g, block_q, d).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                    causal: bool, block_q: int, block_kv: int,
                    num_q_blocks: int):
    """One KV head's tile against the q tiles of its g query heads.  The
    scores are held transposed, (block_kv, block_q): both accumulations are
    then plain products, and the row statistics are used as they are stored,
    along the lanes."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    g = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute(masked):
        k = k_ref[0]
        v = v_ref[0]
        if masked:
            seen = _seen(qi, ki, block_q, block_kv, 1)
        for j in range(g):
            q = q_ref[0, j]                               # (block_q, d)
            do = do_ref[0, j]
            st = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * sm_scale
            if masked:
                st = jnp.where(seen, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, j:j + 1, :])     # (block_kv, block_q)
            dv_scr[...] += jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)       # p^T @ do
            dpt = jax.lax.dot_general(v, do, _NT,
                                      preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta_ref[0, j:j + 1, :])).astype(q.dtype)
            dk_scr[...] += jax.lax.dot_general(
                dst, q, _NN, preferred_element_type=jnp.float32)  # ds^T @ q

    _on_live_tiles(causal, qi, ki, block_q, block_kv, compute)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, sm_scale: float | None = None):
    """Fused attention.  q (B, T, H, D), k/v (B, Tkv, Hkv, D) → (B, T, H, D).

    Uses the Pallas TPU kernels on TPU, XLA reference elsewhere.  GQA/MQA:
    K/V come at their own head count (H a multiple of Hkv; query head h reads
    KV head h // (H // Hkv)) and the gradients of K/V have that shape too.
    """
    return _flash_fwd(q, k, v, causal, sm_scale)[0]


def _flash_fwd(q, k, v, causal, sm_scale):
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"flash_attention: {q.shape[2]} query heads are not "
                         f"a multiple of {k.shape[2]} KV heads")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if _pallas_eligible(q, k):
        out, lse = _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale)
        return out, (q, k, v, out, lse)
    out = mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    return out, (q, k, v, None, None)


def _flash_bwd(causal, sm_scale, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        return _flash_bwd_pallas(q, k, v, out, lse, g, causal=causal,
                                 sm_scale=sm_scale
                                 or 1.0 / math.sqrt(q.shape[-1]))
    _, vjp = jax.vjp(
        lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=causal, sm_scale=sm_scale),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _pallas_eligible(q, k) -> bool:
    if jax.default_backend() != "tpu":
        return False
    t, tkv = q.shape[1], k.shape[1]
    if t % _LANES or tkv % _LANES:   # tiles are multiples of 128 rows
        # Decided at trace time, again for every layer and retrace: the
        # warnings registry shows each distinct message once.
        warnings.warn(
            f"flash_attention: q length {t} / kv length {tkv} is not a "
            f"multiple of 128, so q{q.shape} k{k.shape} takes the XLA "
            f"reference (O(T^2) memory), not the Pallas kernel",
            stacklevel=2)
        return False
    return True


# What a kernel's tiles, scratch and score-sized temporaries may take of
# VMEM by `_working_set`'s count, and what Mosaic is told it may use.  A v5e
# core has 128 MiB; Mosaic's own default is 16 MiB of it.
_VMEM_WORKING_SET = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20
_GRID_PARAMS = pltpu.CompilerParams(    # grids are (bh, outer tile, swept tile)
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)

# Tiles, from a sweep on one v5e (`TPU v5 lite`, 2026-09-28, PR 33): bf16,
# causal, ms a call of forward / dq / dk-dv with the folds (and, for the
# two backward kernels, delta) included, each kernel alone in its jit; the
# parent's 256 x 256 kernels on K/V repeated to the query heads beside them.
# A step's q rows are g * block_q.
#
# (2, 4096, 32/8, 128), g = 4: parent 256 x 256  12.09 / 9.79 / 16.39
#   block_q \ block_kv      256                  512                 1024                 2048
#     128          8.62 / 6.06 / 6.43   5.22 / 4.95 / 5.39   3.66 / 4.78 / 5.11   3.98 / 4.89 / 5.50
#     256          9.21 / 4.89 / 5.15   5.25 / 4.39 / 4.73   3.30 / 4.41 / 4.75   3.87 / 4.71 / 5.31
#     512          7.08 / 4.43 / 4.50   4.32 / 4.13 / 4.28   3.22 / 4.23 / 4.53   3.77 / 4.62 / 5.21
#    1024          7.82 / 4.39 / 4.66   4.32 / 4.23 / 4.54   3.15 / 4.17 / 4.51   6.99 / 7.59 / 8.33
# (8, 2048, 16/16, 64), g = 1: parent 256 x 256  6.61 / 5.56 / 8.93
#     256          6.45 / 5.18 / 5.40   4.21 / 4.13 / 4.32   3.03 / 3.63 / 4.06   3.08 / 3.64 / 4.43
#     512          5.46 / 4.04 / 3.89   3.47 / 3.43 / 3.45   2.55 / 3.29 / 3.61   3.00 / 3.48 / 4.12
#    1024          6.76 / 3.63 / 3.82   3.91 / 3.28 / 3.60   2.40 / 3.13 / 3.48   2.93 / 3.41 / 4.04
#    2048          6.35 / 3.67 / 4.29   3.98 / 3.49 / 4.10   2.89 / 3.39 / 4.03   2.36 / 3.22 / 4.07
# (4, 2048, 16/16, 128), g = 1: parent 256 x 256  3.26 / 2.91 / 4.48
#     256          3.38 / 2.80 / 2.99   2.25 / 2.27 / 2.45   1.66 / 2.03 / 2.32   1.66 / 2.02 / 2.50
#     512          2.87 / 2.23 / 2.25   1.86 / 1.92 / 2.00   1.42 / 1.85 / 2.09   1.64 / 1.93 / 2.35
#    1024          3.53 / 2.01 / 2.21   2.14 / 1.85 / 2.09   1.32 / 1.77 / 2.06   1.62 / 1.90 / 2.33
#    2048          3.33 / 2.03 / 2.43   2.15 / 1.93 / 2.34   1.58 / 1.92 / 2.34   1.27 / 1.76 / 2.26
#
# What a step costs whatever it reads (rescaling the accumulator, the row
# statistics: all by the q rows) is paid once a kv tile, so the forward
# wants 1024 kv rows before anything else; past 2048 q rows or 1024
# positions nothing is won (a tile the diagonal crosses is computed whole:
# wider tiles waste more of it), and 1024 x 2048 at g = 4 no longer fits.
# The dk/dv kernel works a head at a time and is best at 512 x 512
# everywhere.  Chosen: forward and dq 512 x 1024 at g = 4 (3.22 / 4.23) and
# 1024 x 1024 at g = 1 (2.40 / 3.13, 1.32 / 1.77), dk/dv 512 x 512 (4.28,
# 3.45, 2.00): within 3% of the best cell that takes no more VMEM.  Inside
# the train step (2 x 4096 tokens a device, full remat; the same chip) a
# layer's four calls take 2.67 + 2.67 + 3.33 + 3.40 ms where the parent's
# took 11.2 + 11.2 + 8.2 + 14.3.
_TILE_CAPS = {"fwd": (1024, 1024), "dq": (1024, 1024), "dkv": (512, 512)}
_GROUP_ROWS = 2048   # forward, dq: g * block_q, the rows of one score tile


def _working_set(kernel: str, block_q: int, block_kv: int, d: int, g: int,
                 itemsize: int) -> int:
    """Bytes of VMEM a grid step of `kernel` holds: the double-buffered
    tiles, the scratch, and the score-sized temporaries (float32 but for the
    copies handed to the MXU)."""
    rows = g * block_q
    q_tile, kv_tile = rows * d * itemsize, block_kv * d * itemsize
    stat_tile = 8 * block_q * 4              # (g, block_q) f32, 8 sublanes
    column = rows * _LANES * 4               # a (rows, 1) or lane-replicated
    if kernel == "fwd":                      # q, o | k, v | lse
        tiles = 2 * q_tile + 2 * kv_tile + stat_tile
        scratch = 2 * column + rows * d * 4
        scores = rows * block_kv * (2 * 4 + itemsize)
    elif kernel == "dq":                     # q, do, dq | k, v | lse, delta
        tiles = 3 * q_tile + 2 * kv_tile + 2 * stat_tile
        scratch = 2 * column + rows * d * 4
        scores = rows * block_kv * (3 * 4 + itemsize)
    else:                                    # q, do | k, v, dk, dv | lse, delta
        tiles = 2 * q_tile + 4 * kv_tile + 2 * stat_tile
        scratch = 2 * block_kv * d * 4
        scores = block_q * block_kv * (3 * 4 + 2 * itemsize)  # a head a time
    return 2 * tiles + scratch + scores


def _largest_tile(n: int, cap: int) -> int:
    """Largest multiple of 128 that divides `n` and is at most `cap` (the
    grid floors otherwise and a partial tile would be silently skipped);
    callers guarantee n is a multiple of 128."""
    return max(b for b in range(_LANES, max(cap, _LANES) + 1, _LANES)
               if n % b == 0)


def _blocks_for(t: int, tkv: int, d: int, g: int, itemsize: int = 2):
    """(block_q, block_kv) of the forward, dq and dk/dv kernels, from the
    shapes alone: the sweep's best tiles (`_TILE_CAPS`, `_GROUP_ROWS`) cut
    to divisors of the lengths, then stepped down to the next divisor, the
    side with more rows first, while the working set is over
    `_VMEM_WORKING_SET`."""
    blocks = []
    for kernel, (q_cap, kv_cap) in _TILE_CAPS.items():
        if kernel != "dkv":
            q_cap = min(q_cap, _GROUP_ROWS // g)
        block_q = _largest_tile(t, q_cap)
        block_kv = _largest_tile(tkv, kv_cap)
        while (_working_set(kernel, block_q, block_kv, d, g, itemsize)
               > _VMEM_WORKING_SET and max(block_q, block_kv) > _LANES):
            if g * block_q >= block_kv and block_q > _LANES:
                block_q = _largest_tile(t, block_q - _LANES)
            else:
                block_kv = _largest_tile(tkv, block_kv - _LANES)
        blocks.append((block_q, block_kv))
    return tuple(blocks)


def _fold_q(x, hkv):
    """(B, T, H, D) -> (B * Hkv, g, T, D): a KV head's query heads together."""
    b, t, h, d = x.shape
    return x.reshape(b, t, hkv, h // hkv, d).transpose(0, 2, 3, 1, 4).reshape(
        b * hkv, h // hkv, t, d)


def _unfold_q(x, b):
    bh, g, t, d = x.shape
    return x.reshape(b, bh // b, g, t, d).transpose(0, 3, 1, 2, 4).reshape(
        b, t, (bh // b) * g, d)


def _fold_kv(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold_kv(x, b):
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _q_sweep_maps(causal, block_q, block_kv):
    """Index maps of a (bh, q tile, kv tile) grid, the forward's and dq's:
    a group's q-side tile, its row statistics, and the K/V tile."""
    def q_map(bh, qi, ki):
        return (bh, 0, qi, 0)

    def stat_map(bh, qi, ki):
        return (bh, 0, qi)

    def kv_map(bh, qi, ki):
        # Above the diagonal nothing is computed: name the q tile's last
        # live kv tile, already resident, so nothing is fetched either.
        if causal:
            ki = jnp.minimum(ki, ((qi + 1) * block_q - 1) // block_kv)
        return (bh, ki, 0)

    return q_map, stat_map, kv_map


def _flash_pallas(q, k, v, *, causal, sm_scale):
    """-> out (B, T, H, D), lse (B * Hkv, g, T) float32."""
    b, t, h, d = q.shape
    tkv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    (block_q, block_kv), _, _ = _blocks_for(t, tkv, d, g, q.dtype.itemsize)
    num_kv = tkv // block_kv
    q_map, stat_map, kv_map = _q_sweep_maps(causal, block_q, block_kv)
    q_spec = pl.BlockSpec((1, g, block_q, d), q_map)
    kv_spec = pl.BlockSpec((1, block_kv, d), kv_map)
    out, lse = pl.pallas_call(
        functools.partial(
            _fa_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv, num_kv_blocks=num_kv),
        grid=(b * hkv, t // block_q, num_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, pl.BlockSpec((1, g, block_q), stat_map)],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, g, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, g, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g * block_q, _LANES), jnp.float32),
            pltpu.VMEM((g * block_q, _LANES), jnp.float32),
            pltpu.VMEM((g * block_q, d), jnp.float32),
        ],
        compiler_params=_GRID_PARAMS,
    )(_fold_q(q, hkv), _fold_kv(k), _fold_kv(v))
    return _unfold_q(out, b), lse


def _flash_bwd_pallas(q, k, v, out, lse, g_out, *, causal, sm_scale):
    """Dao-style backward: one kernel accumulating dq over kv tiles, one
    accumulating dk/dv over the q tiles of a KV head's whole group.
    delta = rowsum(do * o), one float32 a row like `lse`."""
    b, t, h, d = q.shape
    tkv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    _, dq_blocks, dkv_blocks = _blocks_for(t, tkv, d, g, q.dtype.itemsize)
    delta = jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # (B, T, H)
    folded = (_fold_q(q, hkv), _fold_kv(k), _fold_kv(v), _fold_q(g_out, hkv),
              lse, delta.transpose(0, 2, 1).reshape(b * hkv, g, t))
    dqf = _dq_call(folded, *dq_blocks, causal=causal, sm_scale=sm_scale)
    dkf, dvf = _dkv_call(folded, *dkv_blocks, causal=causal,
                         sm_scale=sm_scale)
    return _unfold_q(dqf, b), _unfold_kv(dkf, b), _unfold_kv(dvf, b)


def _dq_call(folded, block_q, block_kv, *, causal, sm_scale):
    qf, kf = folded[:2]
    bh, g, t, d = qf.shape
    num_kv = kf.shape[1] // block_kv
    q_map, stat_map, kv_map = _q_sweep_maps(causal, block_q, block_kv)
    q_spec = pl.BlockSpec((1, g, block_q, d), q_map)
    kv_spec = pl.BlockSpec((1, block_kv, d), kv_map)
    stat_spec = pl.BlockSpec((1, g, block_q), stat_map)
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv, num_kv_blocks=num_kv),
        grid=(bh, t // block_q, num_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((g * block_q, d), jnp.float32),
                        pltpu.VMEM((g * block_q, 1), jnp.float32),
                        pltpu.VMEM((g * block_q, 1), jnp.float32)],
        compiler_params=_GRID_PARAMS,
    )(*folded)


def _dkv_call(folded, block_q, block_kv, *, causal, sm_scale):
    qf, kf, vf = folded[:3]
    bh, g, t, d = qf.shape
    num_q = t // block_q

    def first_live_q(ki, qi):
        # Below the first q tile that sees this kv tile nothing is computed:
        # name that tile, which the sweep needs next anyway.
        if causal:
            qi = jnp.maximum(qi, jnp.minimum(ki * block_kv // block_q,
                                             num_q - 1))
        return qi

    q_spec = pl.BlockSpec((1, g, block_q, d),
                          lambda bh, ki, qi: (bh, 0, first_live_q(ki, qi), 0))
    stat_spec = pl.BlockSpec((1, g, block_q),
                             lambda bh, ki, qi: (bh, 0, first_live_q(ki, qi)))
    kv_spec = pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0))
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv, num_q_blocks=num_q),
        grid=(bh, kf.shape[1] // block_kv, num_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        compiler_params=_GRID_PARAMS,
    )(*folded)


# Pool blocks a lane reads per trip of `paged_attention`'s loop.  A trip
# costs some ten small device ops whatever it reads, and every lane of the
# call reads a whole group (its own blocks, or the null block past its end).
# Measured on a v5e at Mistral-7B widths (block 16; PR 27, and PR 26 read
# the same to 0.01 ms), groups of 4 / 8 / 16 / 32 blocks: a decode step of
# 16 lanes at 200 live positions takes 5.76 / 5.67 / 5.63 / 7.47 ms, at
# 2500 9.41 / 8.47 / 8.11 / 9.97 and at 4000 11.74 / 10.34 / 9.77 / 11.84;
# 4 lanes differ by under 0.1 ms at 200 and take 7.72 / 7.02 / 6.96 / 6.61
# at 4000; a 128-token chunk at position 3840 8.55 / 7.88 / 7.50 / 7.30.
_PAGED_GROUP_BLOCKS = 16
# A table of more than `_PAGED_LONG_TABLE` entries (an engine whose
# sequences pass 8,192 positions at block 16; no cell before PR 45 has one)
# is read in groups of `_PAGED_GROUP_BLOCKS_LONG`.  Measured on a v5e at
# Laguna-XS.2's widths (three full layers of 48 query heads over 8 KV heads
# among nine, 1,024 table entries; PR 45, the bare served programs), groups
# of 16 / 32 / 64 / 128 blocks: a decode step of 8 lanes at 5,200-12,600
# positions takes 8.53 / 8.03 / 8.36 / 8.61 ms, of 4 lanes 5.96 / 5.51 / 5.37
# / 5.44, of 8 lanes at 300 5.46 / 5.38 / 5.51 / 5.75; a 512-row chunk at
# position 8,192 40.2 / 39.9 / 43.2 / 50.3.  32 is the fastest at 8 lanes
# and for the chunk, by 4% and 8% over 64.  **64 is taken for what a trip
# costs the profiler**: a trip is 14 device ops whatever it holds, 3 s of
# back-to-back bursts are 1.18 M device events at 16 blocks a trip (3,660 a
# step) and 0.76 M at 64 (2,340), and the benchmark's harness waits 120 s
# for a 3 s profile to stop: at 16 (and 128-row chunks) the traced run of
# `lagunaxs2-agent` failed there, at 64 it stops in ~90 s (PERF.md section
# 8, PR 40 (1) and PR 45).  Since PR 47 a decode step does not come here
# (`_paged_decode_kernel`: one device op a layer and lane, no trip); what
# still loops over such a table is a chunk and a verify step, whose 8% at
# 32 are theirs to take back.
_PAGED_LONG_TABLE = 512
_PAGED_GROUP_BLOCKS_LONG = 64


def _paged_running_softmax(k_pool, v_pool, layer, block_tables, positions,
                           kv_len, score, mix, stat, d_out,
                           group_blocks=None, block_size=None):
    """The loop both paged bodies share: groups of `group_blocks`
    table entries (None: `_PAGED_GROUP_BLOCKS`, or
    `_PAGED_GROUP_BLOCKS_LONG` under a table wider than
    `_PAGED_LONG_TABLE`) of one layer of the pool, read at `[layer,
    block]` as stored, under a running soft-max whose trip count follows
    the longest live lane of the call.  `score(kb)` gives a group's
    scaled scores (S, K, *stat, t) float32 from its keys (S, t, ...),
    the pool's trailing dimensions as stored; `mix(p, vb)` applies the
    probabilities to its values, (S, K, *stat, d_out).  `block_size`:
    the positions of a page where it holds several rows a position
    (`pages_as_rows`: a group's keys are then (S, t x rows, ...)); None:
    a page's rows are its positions."""
    s, k_w = positions.shape
    bs, row = block_size or k_pool.shape[2], k_pool.shape[3:]
    if group_blocks is None:
        group_blocks = _PAGED_GROUP_BLOCKS \
            if block_tables.shape[1] <= _PAGED_LONG_TABLE \
            else _PAGED_GROUP_BLOCKS_LONG
    g = min(group_blocks, block_tables.shape[1])
    t = g * bs
    # Whole groups only: dynamic_slice would clamp a ragged last one onto
    # the entries before it.  The padding names the null block.
    tables = jnp.pad(block_tables, ((0, 0), (0, -block_tables.shape[1] % g)))
    over_stat = (slice(None), slice(None)) + (None,) * len(stat)

    def group(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, i * g, g, axis=1)
        kb = k_pool[layer, ids].reshape(s, -1, *row)
        # One pool given as both: its rows carry key and value together
        # (`paged_latent_attention`) and a group is gathered once.
        vb = kb if v_pool is k_pool \
            else v_pool[layer, ids].reshape(s, -1, *row)
        sc = score(kb)
        seen = (i * t + jnp.arange(t)) <= positions[:, :, None]   # (S,K,t)
        sc = jnp.where(seen[over_stat], sc, _NEG_INF)
        # kv position 0 is in group 0 and every query sees it, so from the
        # first trip on `m_new` is a real score and masked entries vanish.
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1)
        acc = alpha[..., None] * acc + mix(p, vb)
        return m_new, l, acc

    stat = (s, k_w) + tuple(stat)
    _, l, acc = jax.lax.fori_loop(
        0, (jnp.max(kv_len) + t - 1) // t, group,
        (jnp.full(stat, _NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
         jnp.zeros(stat + (d_out,), jnp.float32)))
    # l is 0 only where no trip ran: a call whose every lane is idle.
    return acc / jnp.where(l > 0, l, 1.0)[..., None]


# Which pools are kept as the rows the decode kernel reads.  The kernel
# takes a page as rows of (position, KV head) x D.  A pool kept by
# position, (L, N, block_size, Hkv, D), of 4 KV heads or more is stored in
# tiles that make those rows a bitcast; **of fewer, the compiler stores
# bf16[2,8193,16,2,256] in tiles `T(2,128)(2,1)` and the rows in
# `T(8,128)(2,1)`**, so the kernel's view was a copy of a layer's whole
# pool, K and V, a step (Qwen3-Next's full layers, 2 KV heads of 256:
# `reshape.2443` / `.2444`, 0.27 GB each, 1.49 s of a 3 s trace, `tpot_p50_ms`
# 10.48; PR 64, which sent such a pool to the loop: 8.11), and the loop's
# gather of 8 lanes' groups of 64 such pages ran at a tenth of the chip's
# bandwidth (`fusion.845` / `.849 bf16[512,16,2,256]`: 2.46 ms of a 5.9 ms
# step where the live bytes ask 0.3).  Such a pool is allocated as the
# rows themselves, (L, N, block_size x Hkv, D), row t x Hkv + g position t
# of KV head g: whole (16, 128) tiles, the kernel's `stored` view to the
# letter (its body and mask table are PR 47's, untouched), and under
# `T(8,128)(2,1)` a position's two bfloat16 heads are one 32-bit sublane.
# What knows: `models.decoding.init_paged_cache` (the shape),
# `_paged_forward` (the write: rows off x Hkv + arange(Hkv) of the block)
# and `paged_attention`; the block operations index `[:, block]`.
# What was tried (PR 66; AOT for a described v5e, then `TPU v5 lite`,
# 2026-10-04, call A: the bare served programs at Qwen3-Next's cut, 8
# slots x 16,384, parent | this):
#   AOT, the burst: one `paged_decode_attention` call site (the scan's),
#   K and V handed over as `bf16[2,8193,32,256]{T(8,128)(2,1)}`, the
#   scatter in place, nothing else of the pool's shape, temporaries 52 MB;
#   the chunk: groups of bf16[64,32,256] gathered in whole tiles, each
#   regrouped (1 MB, a relayout to `bf16[1024,2,256]{T(2,128)(2,1)}`) for
#   the products the loop had.  The pool is never regrouped.
#   a decode step, ms:  8 lanes at 4,096-12,288       6.67 | 4.25
#                       4 of the 8                     5.63 | 3.38
#                       8 lanes at 240-360             3.83 | 3.77
#   a launch of 512 rows, ms, at position 0 / 4,096 / 8,192 / 11,776
#                       22.50 / 22.78 / 23.40 / 23.85 | 22.78 / 22.90 /
#                       23.72 / 23.95  (+0.4% to +1.3%: the regroup)
# In the cell's traces the kernel's two calls a step take 0.24-0.26 ms
# each for 57k live positions (117 MB = 0.143 ms at 819 GB/s: 55-59% of
# its bytes; a page is 16 KB, so a step of `_PAGED_KERNEL_PAGES` moves
# half the bytes of Laguna's, which read 85%).  More pages a step give
# little: the bare step of 8 lanes reads 4.25 / 4.20 / 4.16 ms at 16 / 32
# / 64 (call B), so the constant stays PR 47's.  Not built: rows of a
# position with its heads side by side, (L, N, block_size, Hkv x D), also
# whole tiles, a kernel that multiplies a KV head's query rows with
# lane-aligned columns (no mask table, no products against the other
# head) and a chunk that needs no regroup: the form to take if the
# launch's 1% is ever wanted back; and a kernel that copies pages of
# (16, 2, 256) as stored and unpacks the heads in VMEM (what Mosaic
# refused of that kind is above `_PAGED_KERNEL_PAGES`).
# The rule reads shapes alone, the same on every platform (a frame
# shipped between engines has one geometry); a pool split over a mesh
# keeps the KV heads' axis it is split on, and takes the loop anyway.
def pages_as_rows(n_kv_heads: int, head_dim: int, block_size: int,
                  dtype) -> bool:
    """Whether a K / V pool of such pages is allocated as rows of whole
    lanes, (L, N, rows, lanes) = `page_rows`: a page that is not whole
    tiles as (block_size, Hkv, D) and is as such rows.  Two kinds of page:
    fewer than 4 KV heads of whole lanes (rows of (position, KV head), what
    the decode kernel reads: above), and **heads of half a lane tile** (D
    = 64: two heads of a position side by side in a row of 128 lanes, a
    position's Hkv / 2 rows one after the other; narrower heads are the
    test presets' alone and stay by position).
    Kept by position, a pool of heads of 64 is stored with the blocks' axis
    innermost (`bf16[3,8193,16,8,64]{1,4,3,2,0}`) and every program that
    reads it copies it whole into tiles of (8, 128) with half of every
    lane row empty, and back: K and V, 0.41 GB each way at LFM2-8B-A1B's
    three full layers, a burst and a launch (AOT for a v5e, PR 67:
    `copy.214` / `.215` / `.220` / `.221`, 1.63 GB of temporaries).  As
    rows it is handed over and aliased as it is, and **a head of 64 gets
    the decode kernel too** (`_paged_decode_side_by_side`: the kernel told
    of Hkv / 2 heads of 128, a query zero in the other head's lanes); a
    chunk's loop regroups each gathered group by head, never the pool.  A
    pool of heads of 64 that is not kept so (an odd count of KV heads, a
    page that is not whole sublanes, a pool split over a mesh) gets the
    loop for a decode step too, and `paged_attention` says so once a call
    site.
    `models.decoding.init_paged_cache` asks, for a pool on one chip."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    rows, lanes = page_rows(n_kv_heads, head_dim, block_size)
    if head_dim % _LANES == 0:
        return n_kv_heads < 4 and rows % sublanes == 0
    return (2 * head_dim == _LANES and n_kv_heads % 2 == 0
            and rows % sublanes == 0)


def page_rows(n_kv_heads: int, head_dim: int, block_size: int):
    """(rows, lanes) of a page kept as rows (`pages_as_rows`): a row is a
    position's KV head of whole lanes, or two of a position's half-lane
    heads side by side."""
    lanes = max(head_dim, _LANES)
    return block_size * n_kv_heads * head_dim // lanes, lanes


def paged_attention(q, k_pool, v_pool, layer, block_tables, positions, kv_len,
                    scale=None, sees=None, *, kv_heads: int):
    """Attention of `q` (S, K, H, D) over one layer of a paged KV pool.
    `scale` multiplies the scores (None: D ** -0.5; a model that publishes
    another, such as 1 / D, hands it over: folded into a bfloat16 `q` it
    would round every query once more unless it is a power of two).

    `k_pool` / `v_pool` are the whole pool, read at `[layer, block]` as
    stored: no slice of it is taken out and no copy in another dtype is
    made.  A page is kept in one of two layouts, and whoever allocates the
    pool decides (`models.decoding.init_paged_cache`, by `pages_as_rows`):
    by position, (L, N, block_size, Hkv, D), or as rows of whole lanes:
    (L, N, block_size x Hkv, D), row t x Hkv + g position t of KV head g
    (the rows the decode kernel reads), or, of heads of half a lane tile,
    (L, N, block_size x Hkv / 2, 128), two of a position's heads side by
    side.  `kv_heads` is Hkv, the caller's (its configuration's): the
    second layout does not show it.  Lane s owns the blocks
    `block_tables[s]` (S, B) in sequence order; its query i stands at
    `positions[s, i]` and sees the kv positions <= that, or <=
    `sees[s, i]` where given (the end of the query's block, K >= 2:
    `models.decoding`), all written by the caller.  `kv_len` (S,): a
    lane's live positions (0: idle, its output garbage).

    Only live blocks are read, under a running soft-max, in one of two
    tilings.  **A decode step (one query row a lane) lowered for a TPU is
    one Pallas kernel a layer, this file's own** (`_paged_decode_kernel`:
    a live page of K and of V copied to VMEM once, a DMA each, where the
    loop gathered a group into a temporary that the products read again;
    operands in the pool's dtype, everything else float32).  A chunk, a
    verify step (K > 1), every other platform, a pool split over a mesh
    and a page that is not whole tiles (it says so) take **the loop**
    over groups of `_PAGED_GROUP_BLOCKS` table entries, whose trip count
    follows the longest live lane of this call (`_paged_running_softmax`):
    the rep = H // Hkv query heads of a KV head are grouped on their own
    axis against the stored head (of pages kept as rows, the gathered
    group is regrouped by head, 1 MB a trip, never the pool), so K and V
    are neither repeated nor kept in another dtype; scores, soft-max,
    the probabilities and both products' accumulation are float32 (the
    compiler folds a group's widening into the second product: on a v5e
    the step takes the same time with the probabilities rounded to the
    cache dtype).  The platform is the one the program is lowered for
    (`jax.lax.platform_dependent`), as `paged_latent_attention`'s.
    Returns (S, K, H, D) float32.
    """
    s, k_w, h, d = q.shape
    hkv = kv_heads
    rows = k_pool.ndim == 4                # `pages_as_rows`
    bs = k_pool.shape[2] * k_pool.shape[3] // (hkv * d) if rows \
        else k_pool.shape[2]
    if scale is None:
        scale = d ** -0.5

    def heads(group):
        """A gathered group's keys or values by (position, KV head)."""
        return group.reshape(s, -1, hkv, d) if rows else group

    def loop(q, k_pool, v_pool, layer, block_tables, positions, kv_len):
        qg = q.reshape(s, k_w, hkv, h // hkv, d)
        out = _paged_running_softmax(
            k_pool, v_pool, layer, block_tables, positions, kv_len,
            lambda kb: jnp.einsum("sqhrd,sthd->sqhrt", qg, heads(kb),
                                  preferred_element_type=jnp.float32) * scale,
            lambda p, vb: jnp.einsum("sqhrt,sthd->sqhrd", p, heads(vb),
                                     preferred_element_type=jnp.float32),
            (hkv, h // hkv), d, block_size=bs)
        return out.reshape(s, k_w, h, d)

    # KV heads a stored row holds side by side: 2 of half-lane heads kept
    # as rows (`pages_as_rows`), else 1.
    pack = k_pool.shape[3] // d if rows else 1

    def kernel(q, k_pool, v_pool, layer, block_tables, positions, kv_len):
        read = _paged_decode_kernel if pack == 1 \
            else _paged_decode_side_by_side
        return read(q, k_pool, v_pool, layer, block_tables, kv_len,
                    scale=scale, kv_heads=hkv)

    positions = positions if sees is None else sees    # the loop's mask
    args = (q, k_pool, v_pool, layer, block_tables, positions, kv_len)
    if k_w != 1 or jax.typeof(k_pool).sharding.mesh.size > 1:
        # A pool split over a mesh (tensor-parallel serving) is a program
        # the compiler partitions, and it cannot partition a kernel.
        return loop(*args)
    sublanes = 32 // k_pool.dtype.itemsize
    if (pack * d) % _LANES or (bs * hkv // pack) % sublanes:
        # Decided at trace time, as `paged_latent_attention`'s.
        what = (f"head_dim {d} against {_LANES} lanes" if d % _LANES else
                f"{bs} positions x {hkv} KV heads against {sublanes} "
                f"sublanes")
        warnings.warn(
            f"paged_attention: a page of {k_pool.shape[2:]} {k_pool.dtype} "
            f"is not whole tiles of {sublanes} x {_LANES} as rows of "
            f"(position, KV head): {what}.  A decode step over "
            f"pool{k_pool.shape} takes the block loop on a TPU too, not "
            f"the Pallas kernel",
            stacklevel=2)
        return loop(*args)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=loop)


# Pool blocks a lane reads per trip over a latent pool.  A latent row is a
# third of the bytes of a position's K and V above, so a trip of the same
# cost holds more blocks.  Measured on a v5e at GLM-4.7-Flash widths (12
# layers, block 16, rows of 640; PR 40, one call), groups of 16 / 32 / 64 /
# 128 blocks: a decode step of 8 lanes at 5,200-12,600 live positions takes
# 11.67 / 10.42 / 11.42 / 11.81 ms, one lane at 9,000 7.10 / 6.22 / 6.71 /
# 7.14, a 512-row chunk at position 8,192 42.9 / 41.6 / 42.4 / 44.6.
_LATENT_GROUP_BLOCKS = 32


def paged_latent_attention(q, pool, layer, block_tables, positions, kv_len,
                           *, d_v: int, scale: float, selected=None):
    """`paged_attention`'s sibling for a latent (MLA, arXiv:2405.04434)
    pool in absorbed form: multi-query attention of `q` (S, K, H, W) over
    one layer of a pool of flat rows (L, N, block_size, W), each row one
    position's key for every head, **whose first `d_v` columns are also
    its value**: a row is (normalised latent | roped key | padding), a
    query (its no-rope part times the key up-projection | its roped part
    | zeros), and what comes back is the probabilities applied to the
    latent, (S, K, H, d_v) float32, for the caller's value up-projection.
    A decode step (one query row a lane) lowered for a TPU is one Pallas
    kernel a layer, this file's own (`_latent_decode_kernel`: a live page
    copied to VMEM once, that buffer both products' operand); a chunk, a
    verify step and every other platform take the same grouped running
    soft-max over the live blocks of `[layer]` as `paged_attention`,
    `_LATENT_GROUP_BLOCKS` a trip, the group gathered once for both
    products: one algorithm in two tilings.  The platform is
    the one the program is lowered for (`jax.lax.platform_dependent`), so
    a program compiled ahead of time for a described chip holds what the
    chip runs.  `scale` multiplies the scores (the published head size's,
    not W's).  W is whole lane tiles: the caller pads a row of 576 to 640,
    or the compiler lays the pool out its own way and copies it whole
    around every step (`models.mla_moe` has the numbers), and the kernel
    is not to be had.

    `selected` = (index scores, each position's row of the pool, k, and
    whether the positions are wanted too), a model that attends to a
    learned selection: each query row's soft-max runs over exactly its k
    best-scored rows, and (out, positions or None, 1 where the call read
    the selection as a mask else 0) comes back.  A chunk and a decode
    step lowered for a TPU read them as a mask over the lanes' live pages
    in a Pallas kernel and sort nothing; every other platform sorts, and
    fetches them (`_attend_selected`)."""
    if selected is not None:
        return _attend_selected(q, pool, layer, block_tables, kv_len,
                                *selected, d_v=d_v, scale=scale)

    def loop(q, pool, layer, block_tables, positions, kv_len):
        return _paged_running_softmax(
            pool, pool, layer, block_tables, positions, kv_len,
            lambda kb: jnp.einsum("sqhe,ste->sqht", q, kb,
                                  preferred_element_type=jnp.float32) * scale,
            lambda p, vb: jnp.einsum("sqht,ste->sqhe", p, vb[..., :d_v],
                                     preferred_element_type=jnp.float32),
            (q.shape[2],), d_v, group_blocks=_LATENT_GROUP_BLOCKS)

    def kernel(q, pool, layer, block_tables, positions, kv_len):
        return _latent_decode_kernel(q, pool, layer, block_tables, kv_len,
                                     d_v=d_v, scale=scale)

    args = (q, pool, layer, block_tables, positions, kv_len)
    if q.shape[1] != 1:
        return loop(*args)
    if pool.shape[-1] % _LANES:
        # Decided at trace time, as `_pallas_eligible`'s: the warnings
        # registry shows each distinct message once.
        warnings.warn(
            f"paged_latent_attention: a pool row of {pool.shape[-1]} is not "
            f"whole tiles of {_LANES} lanes, so a decode step over "
            f"pool{pool.shape} takes the block loop on a TPU too, not the "
            f"Pallas kernel (a step of 8 lanes takes over half as long "
            f"again at GLM-4.7-Flash widths: 10.4 ms against 6.6)",
            stacklevel=2)
        return loop(*args)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=loop)


# Table entries a trip of `paged_index_scores` reads: its score tile is
# (rows, index heads, positions) float32 before the heads are summed, 134
# MB for a 512-row chunk of 64 heads at 64 blocks of 16.
_INDEX_GROUP_BLOCKS = 64
# Query rows `_selected_latent_attention` fetches for at once (since PR 50
# a chunk's fallback off the kernel's reach, since PR 60 a decode step's
# too, `_attend_masked`; a decode step's lanes are under it): 2,048 rows of
# 640 a query row, 128 query rows are a buffer of 0.34 GB and a score tile
# of 0.13 GB; 512 at once 1.3 GB a layer.
_SELECT_QUERY_ROWS = 128


def paged_index_scores(q, w, pool, layer, block_tables, positions, kv_len):
    """A learned index's score of every cached position for every query
    row (the lightning indexer of DeepSeek-V3.2's report): I[t, s] =
    sum_j w[t, j] relu(q[t, j] . k[s]) over the index heads j, against
    one layer of a pool of index keys (L, N, block_size, D), one row a
    position, read through the lanes' tables.  `q` (S, K, Hi, D) in the
    pool's dtype, `w` (S, K, Hi) float32 (the heads' weights, the
    model's constant factors folded in); products accumulate in float32
    and so do relu, weights and the sum.  Returns (S, K, B x block_size)
    float32: `_NEG_INF` at the positions a row does not see (s > its
    own) and at those past the last trip.  Groups of
    `_INDEX_GROUP_BLOCKS` table entries a trip, the trip count following
    the longest live lane of the call, as `_paged_running_softmax`."""
    s, k_w = positions.shape
    bs = pool.shape[2]
    g = min(_INDEX_GROUP_BLOCKS, block_tables.shape[1])
    t = g * bs
    tables = jnp.pad(block_tables, ((0, 0), (0, -block_tables.shape[1] % g)))
    width = tables.shape[1] * bs

    def group(i, out):
        ids = jax.lax.dynamic_slice_in_dim(tables, i * g, g, axis=1)
        kb = pool[layer, ids].reshape(s, t, pool.shape[3])
        sc = jnp.einsum("sqhd,std->sqht", q, kb,
                        preferred_element_type=jnp.float32)
        part = jnp.sum(jax.nn.relu(sc) * w[..., None], axis=2)    # (S,K,t)
        return jax.lax.dynamic_update_slice_in_dim(out, part, i * t, axis=2)

    out = jax.lax.fori_loop(
        0, (jnp.max(kv_len) + t - 1) // t, group,
        jnp.full((s, k_w, width), _NEG_INF, jnp.float32))
    seen = jnp.arange(width) <= positions[:, :, None]
    return jnp.where(seen, out, _NEG_INF)[..., :block_tables.shape[1] * bs]


# What an exact top-k costs on a v5e.  `jax.lax.top_k` is there a stable
# sort of (score, position) pairs over all the candidates; measured (`TPU v5
# lite`, 2026-10-01, PR 49, calls A to E; 512 rows, k = 2,048, float32
# scores), ms by candidates a row: 4,096 0.98 | 8,192 2.12 | 12,288 4.26 |
# 16,384 4.79 | 20,480 12.28 | 24,576 14.18 | 32,768 17.63 (`argsort`:
# 18.85): past 16,384 it leaves a fast path.  So (i) a row's candidates are
# cut to the tier, k x 2^j, that holds the call's longest lane; (ii) a tier
# past `_SELECT_SPAN` is sorted span by span and the spans' bests by one
# more sort of 2 k pairs a span; (iii) what rides with a score is what the
# fetch needs, the position's row of the pool, so that nothing looks table
# entries up afterwards.  Each of these was first built otherwise and
# measured: the rows looked up after a top-k of positions, a gather of
# 2,048 scalars a query row, 7.5 ms a 512-row launch and layer beside a
# sort of 17.6; the rows as a second value of a *stable* sort, to which the
# compiler adds the positions as a third operand, 25.0 ms at 32,768 (call
# E); the spans' bests merged by `take_along_axis`, that gather again, 25.8
# (call C); the spans as one batched top-k over a reshaped (rows, spans,
# span), a change of layout, 24-54 (call B).  Hence one *unstable* sort of
# (score, payload) pairs: which of two equal scores comes first is the
# sorting network's to say, the same for every payload (it compares scores
# alone), so the rows fetched and the positions handed to a check are one
# selection.  The k-th score alone, by 32 rounds of bisection on the
# scores' bit patterns, sorts nothing (0.30 ms at 16,384), and since PR 51
# a chunk's kernel, since PR 60 a decode step's, reads `score >= it` as its
# mask and needs no list: the sort is the fetch's alone, and the fetch is
# every other platform's and, on a TPU, what a call falls back to inside
# the program (`_attend_masked`; the search's table above it).
_SELECT_SPAN = 16384


def _best_pairs(neg, payload, k: int):
    """The k smallest of `neg` (.., T) with their `payload`, in order."""
    neg, payload = jax.lax.sort((neg, payload), dimension=-1, num_keys=1,
                                is_stable=False)
    return neg[..., :k], payload[..., :k]


def _select(scores, payload, k: int, live):
    """(-score, payload) (S, K, k) of each row's k largest `scores` (S, K,
    T), largest first; `payload` (S, T) int32 rides with its position's
    score.  An exact top-k (no approximate one: it would be another
    model); among equal scores the sort's own order.  `live` (a traced
    scalar, or None): positions at or past it hold `_NEG_INF` in every
    row (the call's longest lane), so only the tier of candidates that
    holds it, k x 2^j, is sorted, in spans of `_SELECT_SPAN`."""
    width = scores.shape[-1]
    k = min(k, width)

    def search(sc, payload):
        upto = sc.shape[-1]
        payload = jnp.broadcast_to(payload[:, None, :], sc.shape)
        spans = [_best_pairs(-sc[..., lo:lo + _SELECT_SPAN],
                             payload[..., lo:lo + _SELECT_SPAN], k)
                 for lo in range(0, upto, _SELECT_SPAN)]
        if len(spans) == 1:
            return spans[0]
        return _best_pairs(*(jnp.concatenate(x, axis=-1)
                             for x in zip(*spans)), k)

    tiers = [k]
    while tiers[-1] < width:
        tiers.append(min(2 * tiers[-1], width))
    if live is None or len(tiers) == 1:
        return search(scores, payload)
    tier = sum((live > t).astype(jnp.int32) for t in tiers[:-1])
    return jax.lax.switch(
        tier, [lambda sc, pl, t=t: search(sc[..., :t], pl[..., :t])
               for t in tiers], scores, payload)


def select_positions(scores, k: int, live=None):
    """The `k` positions of largest `scores` (S, K, T) a row, (S, K,
    min(k, T)) int32 (`_select`).  A row that sees fewer than k positions
    gets all of them and, behind them, positions it does not see (their
    score is `_NEG_INF`)."""
    at = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.int32),
                          (scores.shape[0], scores.shape[-1]))
    return _select(scores, at, k, live)[1]


def select_rows(scores, k: int, live, rows):
    """`select_positions` for the fetch (a call off the mask's reach or
    platform: nobody else sorts): the same selection in order, but of
    `rows` (S, T) int32, each position's row in a layer's pool laid flat,
    (S, K, k) bool which the row sees, (S, K) the set's least score."""
    neg, got = _select(scores, rows, k, live)
    return got, neg < -0.5 * _NEG_INF, jnp.maximum(-neg[..., -1],
                                                   0.5 * _NEG_INF)


def _selected_latent_attention(q, pool, layer, rows, seen, *, d_v: int,
                               scale: float):
    """A selection read by fetching it, on every platform (a call on a
    TPU comes here off the masked kernels' reach): `rows` (S, K, k)
    int32, each query row's rows of `[layer]` of the pool laid flat, go
    into a dense (query rows, k, W) buffer, both products' operand; one
    soft-max over the k, masked to those the row sees (`seen`).  A
    chunk's query rows go `_SELECT_QUERY_ROWS` at a time."""
    s, k_w, h, w = q.shape
    n_layers, n_blocks, bs, _ = pool.shape
    flat = pool.reshape(n_layers, n_blocks * bs, w)

    def attend(q, rows, seen):                         # (S, r, ...)
        # Every selected row lies in the pool: no clamp.
        got = flat.at[layer, rows].get(mode="promise_in_bounds")  # (S,r,k,W)
        sc = jnp.einsum("sqhe,sqje->sqhj", q, got,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(seen[:, :, None, :], sc, _NEG_INF)
        # The probabilities in the rows' dtype, as the decode kernels'; the
        # product over the whole row and the value's columns taken of the
        # result: a slice of the fetched buffer would be a copy of it.
        out = jnp.einsum("sqhj,sqje->sqhe",
                         jax.nn.softmax(sc, axis=-1).astype(got.dtype), got,
                         preferred_element_type=jnp.float32)
        return out[..., :d_v]

    r = _SELECT_QUERY_ROWS
    if k_w <= r or k_w % r:
        return attend(q, rows, seen)

    def split(x):                          # (S, K, ..) -> (K / r, S, r, ..)
        return jnp.moveaxis(x.reshape(s, k_w // r, r, *x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda xs: attend(*xs),
                      (split(q), split(rows), split(seen)))
    return jnp.moveaxis(out, 0, 1).reshape(s, k_w, h, d_v)


# Pool blocks a step of the decode kernel copies in and multiplies.
# Measured on a v5e at GLM-4.7-Flash widths (`TPU v5 lite`, 2026-09-30,
# PR 43, two calls; bfloat16 pool of 12 x 8,193 blocks of 16 rows of 640,
# 20 heads), 16 / 32 / 64 / 128 blocks a step.  The kernel alone, ms a
# call (one layer), 8 lanes at 5,200-12,600 live positions (71,200 rows =
# 91 MB = 0.111 ms at 819 GB/s) | one lane of the 8 at 9,000:
#   JAX's paged attention, the pool as keys and again as values (PR 40's)
#                         0.307 0.264 0.284 0.304 | 0.047 0.041 0.042 0.047
#   this kernel, copies issued from a loop
#                         0.293 0.241 0.221 0.212 | 0.041 0.034 0.032 0.032
#   this kernel, a page's copy under its own `pl.when` (one form for whole
#   and part steps)             0.197 0.184 0.180 |       0.029 0.026 0.027
#   this kernel, a whole step's copies written out
#                         0.223 0.163 0.143 0.140 | 0.033 0.025 0.022 0.024
#   the same with `q` standing still in the MXU (scores as rows x q^T,
#   heads padded to a tile, turned back)
#                         0.355 0.298 0.276 0.275 | 0.050 0.042 0.039 0.041
#   the block loop above at its 32          0.422 |             0.304
# The whole decode step of the benchmark's 12 layers (a burst of 8 / 8):
#   PR 40's kernel at its 32                 8.05 |              2.99
#   this kernel, copies written out
#                          7.59  6.87  6.63  6.60 |  2.89  2.79  2.77  2.79
# As committed (a third call: the fetch-ahead one site, the per-page
# code one add and one table read) the kernel alone reads 0.137 | 0.022
# and the step 6.57 | 2.77 at 64.
# One copy a page is worth a tenth by itself: the copies' issue is the
# rest.  A start or a wait issued from a loop (or behind a branch) costs
# the kernel's one instruction stream ~9 ns, 128 of them a step of 64
# pages 1.1 us where the step's bytes take 1.6, and the products wait
# behind them; written out they overlap.  64 pages are within 2% of 128
# at half the VMEM and better on a single lane.  The rows stand still in
# the MXU for both products (20 query rows stream past each 128 x 128
# tile of rows): with `q` still the scores come out turned and cost a
# transposition a step.  Against the loop the kernel's output differs by
# under 0.1% of its rms (PR 40's kernel, float32 products on a
# bfloat16-rounded `q * scale`: 0.4%).
_LATENT_KERNEL_PAGES = 64


def _paged_decode_body(layer_ref, tables_ref, len_ref, q_ref, o_ref,
                       slot_ref, pools, bufs, sems, *, pages, bs, scale,
                       d_out, seen, values, prepare=None, flat=False,
                       sparse=False):
    """One lane of a decode kernel's grid (`_latent_decode_kernel`,
    `_paged_decode_kernel`, `_masked_decode_kernel`): a loop over the
    lane's steps of `pages` pool blocks of `bs` positions under a running
    soft-max.  A step's live blocks `[layer, table[lane, j]]` of every
    pool of `pools` are copied,
    a DMA a block and pool, into one of the two halves of that pool's
    buffer of `bufs` (2, pages * rows of a page, W) while the other half
    is multiplied; the copy of a lane's first step is started by the
    live lane before it (by lane 0 for the first), so the pipeline runs
    through the lanes of the call.  `slot_ref` carries the half in turn
    from lane to lane.  The first buffer's rows are the keys;
    `values(keys, slot)` gives the rows the probabilities are applied to
    (`d_out` columns) and `seen(i, length, shape)` which scores of step
    `i` stand; `prepare()` runs once a call, before the first copy.
    `flat`: a layer of a pool is its blocks' rows laid flat, (N x rows of
    a page, W), and a page the rows from `block x rows` on.  `sparse`:
    `seen` may leave a step, a lane's first included, without a score
    that stands (a selection).  Both are read at trace time: a kernel
    that gives neither traces as it did before they were."""
    lane, n_lanes = pl.program_id(0), pl.num_programs(0)
    n_entries = tables_ref.shape[0] // n_lanes
    rows = bufs[0].shape[1] // pages       # of a page in a buffer
    t = pages * bs                         # positions a step
    length = len_ref[lane]

    def next_live(start):
        """The first live lane at or after `start`; `n_lanes`: none."""
        return jax.lax.fori_loop(
            start, n_lanes,
            lambda s, found: jnp.where(
                (found == n_lanes) & (len_ref[s] > 0), s, found), n_lanes)

    def copies(of_lane, step, slot, go, written_out=True):
        """Start (`go`) or await the copies of step `step` of `of_lane`:
        its blocks that hold a live position, no others.  A whole step's
        are written out one by one: issued from a loop they cost a call
        two thirds of what its bytes do (the table above)."""
        first = step * pages
        live = jnp.minimum(pages, pl.cdiv(len_ref[of_lane], bs) - first)
        entry = of_lane * n_entries + first
        layer = layer_ref[0]
        ways = [(pool.at[layer], buf.at[slot], sem.at[slot])
                for pool, buf, sem in zip(pools, bufs, sems)]

        def one(j, _=None):
            at = j * rows
            if not isinstance(j, int):
                at = pl.multiple_of(at, rows)
            block = tables_ref[entry + j]
            if flat:
                block = pl.ds(pl.multiple_of(block * rows, rows), rows)
            for page, half, sem in ways:
                dma = pltpu.make_async_copy(
                    page.at[block], half.at[pl.ds(at, rows)], sem)
                if go:
                    dma.start()
                else:
                    dma.wait()

        def from_a_loop():
            jax.lax.fori_loop(0, live, one, None)

        if not written_out:
            return from_a_loop()
        pl.when(live < pages)(from_a_loop)

        @pl.when(live == pages)
        def _whole():
            for j in range(pages):
                one(j)

    @pl.when(lane == 0)
    def _open():
        # Rows past a lane's length are masked out of the scores and
        # meet a probability of 0 in the value product: they have to be
        # numbers.  What a half holds there from then on is an earlier
        # step's rows.
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        if prepare is not None:
            prepare()
        slot_ref[0] = 0
        first = next_live(0)

        @pl.when(first < n_lanes)
        def _first_copy():              # once a call: from the loop
            copies(first, 0, 0, True, written_out=False)

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _live():
        after = next_live(lane + 1)
        q = q_ref[0]                                       # (H, W)
        n_steps = pl.cdiv(length, t)

        def step(i, carry):
            m, l, acc, slot = carry

            more = i + 1 < n_steps        # else the next live lane's first

            @pl.when(more | (after < n_lanes))
            def _fetch_ahead():
                copies(jnp.where(more, lane, after),
                       jnp.where(more, i + 1, 0), 1 - slot, True)

            copies(lane, i, slot, False)
            keys = bufs[0][slot]                           # (rows a step, W)
            s = jax.lax.dot_general(
                q, keys, _NT, preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen(i, length, s.shape), s, _NEG_INF)
            # Position 0 is in step 0 and seen, so `m_new` is a real
            # score from the first step on and masked entries vanish
            # (`sparse`: `m` starts above the mask and under every score,
            # so a step without a score leaves exp(s - m_new) 0, not 1).
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(keys.dtype), values(keys, slot), _NN,
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - slot

        h = q.shape[0]
        _, l, acc, slot = jax.lax.fori_loop(
            0, n_steps, step,
            (jnp.full((h, 1), 0.5 * _NEG_INF if sparse else _NEG_INF,
                      jnp.float32),
             jnp.zeros((h, 1), jnp.float32),
             jnp.zeros((h, d_out), jnp.float32), slot_ref[0]))
        slot_ref[0] = slot
        # `sparse`: l is 0 where a live lane's row selects nothing.
        o_ref[0] = acc / (jnp.where(l > 0, l, 1.0) if sparse else l)


def _latent_decode_body(layer_ref, tables_ref, len_ref, q_ref, pool_ref,
                        o_ref, buf, sems, slot_ref, *, pages, d_v, scale):
    """`_paged_decode_body` over one pool of flat rows that are key and,
    in their first `d_v` columns, value: one buffer, both products'
    operand."""
    t = buf.shape[1]                       # positions a step

    def seen(i, length, shape):
        return i * t + jax.lax.broadcasted_iota(jnp.int32, shape, 1) < length

    _paged_decode_body(
        layer_ref, tables_ref, len_ref, q_ref, o_ref, slot_ref, (pool_ref,),
        (buf,), (sems,), pages=pages, bs=t // pages, scale=scale, d_out=d_v,
        seen=seen, values=lambda rows, slot: rows[:, :d_v])


@functools.partial(jax.jit, static_argnames=("d_v", "scale"))
def _latent_decode_kernel(q, pool, layer, block_tables, kv_len, *, d_v,
                          scale):
    """A decode step's attention over the latent pool as one kernel a
    layer, of this file's own: `q` (S, 1, H, W) against the pool where it
    lies, (L, N, block_size, W) in HBM, with `layer`, the tables and the
    lengths as scalars the kernel reads.  A page is copied to VMEM once
    and that one buffer gives both products: the scores `q` against its
    rows and the probabilities against the rows' first `d_v` columns,
    operands in the pool's dtype, accumulation, statistics and rescaling
    float32, the scale applied to the float32 scores (`_fa_kernel`'s
    convention, and what the block loop's einsums are on a TPU at
    default precision).  The grid is the lanes; `_latent_decode_body` has
    the pipeline.  Only a lane's live pages are read; an idle lane
    (`kv_len` 0) reads none and gets 0.  The two halves of the buffer
    are 2.6 MB at GLM-4.7-Flash widths, so Mosaic's default share of
    VMEM holds them (asked for `_VMEM_LIMIT`, the compiler counts 95 MB
    more among the burst's temporaries).  Jitted, so that a program's
    call sites (layer 0's and the scan's) trace the written-out copies
    once a shape: traced at each, they added 13 s to a replica's
    start.  Returns (S, 1, H, d_v) float32."""
    s, _, h, w = q.shape
    bs = pool.shape[2]
    pages = min(_LATENT_KERNEL_PAGES, block_tables.shape[1])
    out = pl.pallas_call(
        functools.partial(_latent_decode_body, pages=pages, d_v=d_v,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[
                pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, d_v), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, d_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.astype(jnp.int32).reshape(-1), kv_len.astype(jnp.int32),
      q[:, 0].astype(pool.dtype), pool)
    return out[:, None]


# Pool blocks a step of `_paged_decode_kernel` copies in and multiplies,
# and the rows of (position, KV head) a step may hold at most: 16 blocks
# of 16 positions are 2,048 rows at 8 KV heads, 1,024 at Mellum2's 4; at
# an MHA model's 32 KV heads the cap leaves 8 blocks.  The cap is VMEM's,
# reckoned first: a page of (16, 8, 128) bfloat16 is 32 KB of K and 32 KB
# of V and lies in VMEM as it does in HBM, 128 rows of 128 in whole
# (16, 128) tiles, nothing padded, so the two halves of the two buffers
# are 1 MB for every 1,024 rows of a step; beside them the mask table
# (H x rows int32) and a step's scores and probabilities (H x rows
# float32 twice, once more in the rows' dtype): ~7 MB at 4,096 rows and
# Laguna-XS.2's 48 heads, inside the 16 MB Mosaic takes by default (asked
# for 64 MiB, the compiler counts 67 MB more among a Mistral burst's
# temporaries).  A step of 64 blocks overran it at 16 KV heads (bench-1b4:
# 17 MB of 16).
# Measured on a v5e (`TPU v5 lite`, 2026-10-01, PR 47, calls A and B;
# bfloat16 pools, block 16, D 128, the loop at the group it has: 64 under
# Laguna's table of 1,024 entries, 16 elsewhere), 16 / 32 / 64 / 128 blocks
# a step (at Mellum2's 4 KV heads half the rows).  The kernel alone, ms a
# call (one layer) at Laguna-XS.2's widths (48 query heads over 8):
#   8 lanes at 5,200-12,600 live positions (71,200 x 4 KB = 292 MB =
#   0.356 ms at 819 GB/s)      loop 1.066 | 0.421  0.423  0.432  0.477
#   4 of the 8 lanes (0.178)   loop 1.074 | 0.231  0.229  0.230  0.251
# (85 / 84 / 82 / 75% and 77 / 78 / 77 / 71% of their bytes; a call
# under ~0.19 ms is bound by its launch from the host in that timing, loop
# and kernel alike: 8 lanes at 300 and Mistral's 4 lanes read 0.19-0.20.)
# The whole decode step of the bare served burst, ms:
#   Laguna-XS.2 (3 full layers of 9; 8 lanes at 5,400-12,600)
#                              loop  8.36 |  6.53   6.54   6.55   6.67
#     4 lanes of the 8               7.05 |  4.64   4.63   4.65   4.69
#     8 lanes at 240-360             5.53 |  5.39   5.37   5.40   5.45
#   Mistral-7B (8 layers, 32 heads over 8; 4 of 16 lanes at 200-700)
#                                    6.29 |  5.67   5.68   5.70   5.74
#     4 lanes at 3,000-4,000         9.84 |  6.19   6.21   6.22   6.26
#     16 lanes at 1,750-3,250        9.06 |  7.37   7.36   7.39   7.54
#   Mellum2 (2 full layers of 8, 32 heads over 4; 2 of 8 lanes at
#   1,000-3,000)                     5.64 |  5.10   5.09   5.10
#     8 of 32 lanes at 1,500-4,500  13.23 | 10.19  10.17  10.15
#   Mixtral-8x7B (3 layers; 2 of 16 lanes at 200-700)
#                                    6.55 |  6.35   6.33   6.35
#     4 lanes at 3,000-4,000        10.64 |  9.26   9.27   9.27
#   granite-4.0-h-small (1 attention layer of 10; 4 of 16 lanes at
#   2,100-3,900)                    14.60 | 14.14  14.14  14.15
# 16, 32 and 64 blocks are level (within 0.3% of a step everywhere, 128
# behind by 1-2%): the copy bounds the kernel, not the step's size.  **16
# are taken for what a block costs a replica's start**: a whole step's
# copies are written out, and a written-out block is ~6 ms of tracing and
# lowering a program (0.054 s a call site with the loop, 0.19 / 0.26 /
# 0.37 / 0.65 at 4 / 16 / 32 / 64 blocks; a replica warms five burst
# tiers).  At 32 the warm `setup_s` of `mistral7b-chat` read 24.6 -> 26.6
# s, `warmup` 3.6 -> 4.85 (call C2b), half the room its bound of 10%
# leaves (unrolled at lowering instead, `fori_loop(unroll=True)`, a block
# costs as much: 0.32 at 32).  The kernel is never slower than the loop,
# short lanes included (Mistral at 200-700: -10%), so the rule reads no
# length.  Against the loop its output differs by 0.4-0.8% of the loop's
# rms where the two rescale at different positions and by 1e-6 where a
# step is the loop's group (both round the probabilities to bfloat16 for
# the second product).
# The form.  A stored page has one view that is its bytes: rows of
# (position, KV head) x D (under the pool's `T(8,128)(2,1)` tiles the
# reshape (L, N, 16, Hkv, 128) -> (L, N, 16 Hkv, 128) compiles to a
# bitcast; rows of Hkv x D, each query head spread over its KV head's
# columns, are another order of the bytes: a copy of the pool).  On it one
# product of all H query rows with a step's rows computes every query head
# against every KV head, Hkv times the multiply-adds and Hkv times the
# scores masked and exponentiated, behind a copy that still bounds the
# call at 82-85% of its bytes.  A product a KV head on a strided view of
# those rows is refused by Mosaic (`Strided load with non 32-bit data`: a
# bfloat16 row is half a sublane); two heads at a time through a 32-bit
# view compiles and was not run (PERF.md section 8).
_PAGED_KERNEL_PAGES = 16
_PAGED_KERNEL_ROWS = 4096
_MASKED = 1 << 30     # no position reaches it


def _kv_decode_body(layer_ref, tables_ref, len_ref, q_ref, k_ref, v_ref,
                    o_ref, k_buf, v_buf, k_sems, v_sems, slot_ref, at_ref,
                    *, pages, bs, scale):
    """`_paged_decode_body` over a K and a V pool of grouped-query heads.
    A page is taken as it is stored, (block_size x Hkv, D): row t * Hkv + g
    is position t of KV head g, so one product of all H query rows with a
    step's rows holds every head's scores, each query head's own in the
    columns of its KV head and the other heads' beside them.  `at_ref`
    (H, rows a step) holds, once a call, the position a column stands at
    where its KV head is the row's own and `_MASKED` elsewhere: one
    comparison a step leaves the scores that count."""
    h, d = q_ref.shape[1:]
    hkv = k_buf.shape[1] // (pages * bs)
    t = pages * bs

    def prepare():
        row = jax.lax.broadcasted_iota(jnp.int32, at_ref.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, at_ref.shape, 1)
        own = jax.lax.rem(col, hkv) == jax.lax.div(row, h // hkv)
        at_ref[...] = jnp.where(own, jax.lax.div(col, hkv), _MASKED)

    _paged_decode_body(
        layer_ref, tables_ref, len_ref, q_ref, o_ref, slot_ref,
        (k_ref, v_ref), (k_buf, v_buf), (k_sems, v_sems), pages=pages, bs=bs,
        scale=scale, d_out=d, prepare=prepare,
        seen=lambda i, length, shape: at_ref[...] < length - i * t,
        values=lambda rows, slot: v_buf[slot])


@functools.partial(jax.jit, static_argnames=("scale", "kv_heads"))
def _paged_decode_kernel(q, k_pool, v_pool, layer, block_tables, kv_len, *,
                         scale, kv_heads):
    """A decode step's attention over a K / V pool as one kernel a layer,
    of this file's own: `q` (S, 1, H, D) against the pools where they lie,
    (L, N, block_size, Hkv, D) or those pages kept as rows (L, N,
    block_size x Hkv, D) in HBM (`kv_heads` = Hkv), with `layer`, the
    tables and the lengths as scalars the kernel reads.  A live page of K
    and the same page of V are one DMA each into their halves of two VMEM
    buffers; operands in the pool's dtype, accumulation, statistics and
    rescaling float32, the scale applied to the float32 scores
    (`_latent_decode_kernel`'s convention and pipeline:
    `_paged_decode_body`).  Only a lane's live pages are read; an idle
    lane (`kv_len` 0) reads none and gets 0.  Jitted, as the latent
    kernel and for its reason.  Returns (S, 1, H, D) float32."""
    s, _, h, d = q.shape
    # Rows of (position, KV head): the bytes of a page as they are stored,
    # and the pool's own shape where `pages_as_rows` allocated it.
    stored = (*k_pool.shape[:2], math.prod(k_pool.shape[2:-1]), d)
    bs = stored[2] // kv_heads
    pages = max(1, min(_PAGED_KERNEL_PAGES, block_tables.shape[1],
                       _PAGED_KERNEL_ROWS // stored[2]))
    rows = pages * stored[2]
    out = pl.pallas_call(
        functools.partial(_kv_decode_body, pages=pages, bs=bs, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, d), k_pool.dtype),
                pltpu.VMEM((2, rows, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, rows), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.astype(jnp.int32).reshape(-1), kv_len.astype(jnp.int32),
      q[:, 0].astype(k_pool.dtype), k_pool.reshape(stored),
      v_pool.reshape(stored))
    return out[:, None]


def _paged_decode_side_by_side(q, k_pool, v_pool, layer, block_tables,
                               kv_len, *, scale, kv_heads):
    """`_paged_decode_kernel` over a pool of half-lane heads kept two a row
    (`pages_as_rows`: (L, N, block_size x Hkv / 2, 128), a row KV heads 2r
    and 2r + 1 of a position side by side): the kernel as it is, told that
    the pool has Hkv / 2 KV heads of 128.  A query head goes in with its
    own KV head's half of the lanes and zeros in the other's, so its
    product with a row is its product with its own head's key; what comes
    back for it is the probabilities applied to both heads' values, of
    which its own half is taken.  The products are twice the needed ones
    and the bytes exactly the live pages', which is what a decode step
    pays for.  `q` (S, 1, H, D), D half a lane tile; returns (S, 1, H, D)
    float32."""
    s, _, h, d = q.shape
    pack = k_pool.shape[-1] // d
    own = (jnp.arange(h) // (h // kv_heads)) % pack        # a head's half
    place = jax.nn.one_hot(own, pack, dtype=jnp.float32)   # (H, pack)
    wide = (q[..., None, :] * place.astype(q.dtype)[:, :, None]).reshape(
        s, 1, h, pack * d)
    out = _paged_decode_kernel(wide, k_pool, v_pool, layer, block_tables,
                               kv_len, scale=scale,
                               kv_heads=kv_heads // pack)
    return jnp.einsum("sqhpd,hp->sqhd", out.reshape(s, 1, h, pack, d), place)


# How a chunk reads a selection on a TPU, and why not by its rows (PR 50;
# `TPU v5 lite`, 2026-10-01, calls 1 to 3; dots3-note-prev's widths: one
# lane of 512 query rows x 128 heads over rows of 640 bfloat16, 512 of them
# the value, k = 2,048, a pool of 2 x 16,385 blocks of 16).
# **A DMA a selected row cannot be written.**  ISSUE 50 asked for a kernel
# that copies each query row's 2,048 rows from the pool laid flat, a DMA a
# row (a 32-bit word row a pair, the half taken in VMEM), and multiplies
# them there.  Mosaic refuses every slice of a tiled dimension that is not
# whole tiles, in HBM as in VMEM and at any width of element, compiled for
# a described v5e: `pool.at[layer, pl.ds(row, 1)]` on float32 "Slice shape
# along dimension 1 must be aligned to tiling (8), but is 1"; two bfloat16
# rows, or one row of the pool's 32-bit view (`ref.bitcast`, tiling (4,
# 128)): "(4), but is 1"; a whole tile at a row that is no multiple of 8:
# "Failed to prove that a tile index in dimension 1 is divisible by the
# tiling".  (Pallas's interpreter ran the float32 form and has no bitcast
# of a DMA's source.)  What can be copied is a tile: 8 stored rows, 10 KB,
# eight times a row's bytes, 21 MB a query row = 26 us at 819 GB/s = 13 ms
# a layer and launch before a product, against the fetch's 17.7; with the
# 16,384 columns a query row then has to mask, not built.
# **Built: the lanes' live pages read whole, the selection a mask.**  A
# launch's 512 sets together are nearly every live position, so each page
# is copied once a group of query rows (a step's pages one DMA each, the
# next step's under this step's products) and multiplied with all the
# group's rows x heads at once; what the selection saves is then no
# product, only which scores count.  ms a call (one layer), the fetch
# (`_selected_latent_attention`: XLA's gather of 128 x 2,048 rows, 4.4 ms,
# and the read of the 0.34 GB it wrote, 1.9, four times) beside the kernel
# at (query rows a group, pages a step):
#   context     fetch |  (8, 32)  (16, 32)  (16, 64)  (16, 128)  (8, 16)
#        0      25.51 |    1.18     1.16      1.59      2.75      1.24
#    8,192      25.65 |    9.87     9.94      9.48     11.2      11.21
#   16,384      25.68 |   18.61    18.68     17.43     19.65     21.21
#   24,064      25.70 |   26.78    26.9      24.38     26.07     30.51
#   32,256      25.66 |   37.51    35.66     33.32       -       40.5
# ((24, 64) and (32, 32) overrun VMEM's 64 MiB; (16, 32), (8, 16) and the
# last row are call 1's, before the mask was taken a query row and not a
# score row, which gave 3-5%.)  (16, 64): 2 x 512 x 128 x 1,152 FLOPs a
# live position in 1.0 us, 70% of the MXU's 197 TFLOP/s, level from 8k to
# 32k.  The fetch costs the same at every context and the kernel a
# millisecond a thousand live positions: they cross at ~25,500, and
# `_MASKED_LIVE_MAX` is the longest lane measured under it.  With the
# counts and the branch around it (`_attend_masked`) the kernel reads 1.68 /
# 9.57 / 17.55 / 24.48.  The bare 512-row launch of the benchmark's five
# layers (two of them full), parent | this: 73.6 | 26.8 at context 0, 75.0
# | 31.7 at 2k, 83.4 | 51.9 at 8k, 99.3 | 83.4 at 16k, 100.2 | 98.5 at
# 24,064, 100.8 | 101.4 at 31,744 (the fetch, past the kernel's reach); a
# decode step of 8 lanes at 24k 7.69 | 7.66 (the fetch, both).  Against
# the fetch the kernel differs by 1% of its rms (the running soft-max
# rescales and rounds exp(s - m), the fetch the normalised probabilities,
# both to bfloat16).
# **The selection is a threshold, and nothing is sorted for it** (PR 51;
# `TPU v5 lite`, 2026-10-01, call 1; bare, 512 rows, k = 2,048, float32
# normal scores, a row seeing up to its own position).  The mask needs the
# set's least score and no list, and PR 50 still sorted in front of the
# kernel.  The k-th largest score is the largest float32 that k scores
# reach, found a bit a round from the top of its place in the order of
# all float32 (`_score_at`): 32 counts of `score >= candidate` over the
# row, exact whatever the scores are.  ms by candidates a row, the sort
# (`select_rows`) | the search | the search with the three counts at the
# edge (`_edge_of_best`, what a launch pays):
#    4,096   0.62 | 0.25 | 0.47        16,384   4.59 | 0.30 | 0.52
#    8,192   1.32 | 0.21 | 0.50        24,576   6.39 | 0.42 | 0.60
#   32,768  12.08 | 2.96 | 3.12   (a call under ~0.2 ms is bound by its
# launch from the host).  32 rounds over 32 MB in 0.30 ms are 3.4 TB/s:
# up to 24,576 candidates (48 MB) the compiler keeps the scores on the
# chip between the rounds, at 32,768 it reads HBM every round.  Hence the
# search reads no candidate past `_MASKED_LIVE_MAX`: the kernel reaches no
# further.  In a launch's profile (call 3, context 24,064) the 32 rounds
# are 0.29 ms a layer.  It has two tiers, 4 k and all: with the sort's
# five, traced at both call sites of each of a replica's five chunk
# tiers, a warm replica of dots3-note-prev read `setup_s` +2.2% / +7.8% /
# +9.2% in three pairs (calls A, B; bound 10%; its warm-up 22.5-23.2 s for
# 17.8-19.5); with two, jitted, +2.5% on the medians of five and three
# runs (call F: 19.3-22.4 s), and a bare launch the same to 0.2 ms at
# every context.  Tried beside it, call 1: two bits a round
# (three counts in 16 rounds) 0.21 / 0.24 / 0.34 / 0.48, 1.53 at 32,768; four
# bits (15 counts in 8 rounds) 0.26 / 0.44 / 0.80 / 1.18 / 1.51; a Pallas
# kernel with a group's (16, T) scores in VMEM for all 32 rounds 0.24 /
# 0.26 / 0.39 / 0.51 / 0.53 (32 rows a group: 0.20 / 0.26 / 0.34 / 0.48 /
# 0.51; 8: 0.32-0.96): level with the plain loop where the kernel reaches,
# so the plain loop stands.
# **Equal scores.**  The mask is `score >= the set's least`, and of the
# positions that tie with it the set may hold some only.  With float32
# scores a row's 2,049th best equals its 2,048th once in ~2,000 rows: 0 / 1
# / 0 / 3 rows of a launch's 512 in PR 50's four calls (normal scores of 23
# bits), every one a set that holds one of the tied positions: the kernel
# keeps the lowest of them, the reference's own rule, found by its row of
# the pool (`last`).  A row that keeps two or more and leaves one out sends
# its launch to the fetch, where the sort settles it; none was seen.
_MASKED_QUERY_ROWS = 16
_MASKED_KERNEL_PAGES = 64
_MASKED_LIVE_MAX = 24576


def _kept(scores, least, last, at):
    """Which positions a threshold keeps: those whose score is above the
    row's `least`, and of those that equal it all (`last` negative) or
    the one whose row of the pool, `at`, is `last`.  One rule for both
    masked kernels and for the positions handed to a check."""
    return (scores > least) | ((scores == least) & (
        (last < 0) | (at == last)))


def _masked_latent_body(layer_ref, tables_ref, len_ref, pool_ref, q_ref,
                        sc_ref, least_ref, last_ref, at_ref, o_ref, buf,
                        sems, *, pages, bs, d_v, scale):
    """One group of a lane's query rows of `_masked_latent_kernel`'s grid:
    a loop over the lane's steps of `pages` pool blocks under a running
    soft-max.  A step's live blocks `[layer, table[lane, j]]` of the pool
    laid flat are copied, a DMA a block, into one half of `buf` (2, pages
    x bs, W) while the other half is multiplied with every head of every
    query row of the group at once.  A position counts for a query row
    where its index score `sc_ref` (G, T) is above the row's `least_ref`
    (G, 1), or equals it and either the row keeps every such position
    (`last_ref` (G, 1) negative) or the position's row of the pool,
    `at_ref` (1, T), is `last_ref`'s; nowhere else: `_NEG_INF` in the
    scores, 0 among the probabilities."""
    lane = pl.program_id(0)
    g, h, w = q_ref.shape[1:]
    t = pages * bs                         # positions a step
    layer, length = layer_ref[0], len_ref[lane]
    entry = lane * (tables_ref.shape[0] // pl.num_programs(0))
    n_steps = pl.cdiv(length, t)

    def copies(step, slot, go):
        """Start (`go`) or await the copies of step `step`: its blocks
        that hold a live position, no others."""
        first = step * pages
        live = jnp.minimum(pages, pl.cdiv(length, bs) - first)

        def one(j, _):
            at = pl.multiple_of(tables_ref[entry + first + j] * bs, bs)
            dma = pltpu.make_async_copy(
                pool_ref.at[layer, pl.ds(at, bs)],
                buf.at[slot, pl.ds(pl.multiple_of(j * bs, bs), bs)],
                sems.at[slot])
            if go:
                dma.start()
            else:
                dma.wait()

        jax.lax.fori_loop(0, live, one, None)

    @pl.when((lane == 0) & (pl.program_id(1) == 0))
    def _open():
        # Rows past a lane's length are masked out and meet a probability
        # of 0 in the value product: they have to be numbers.
        buf[...] = jnp.zeros_like(buf)

    pl.when(n_steps > 0)(lambda: copies(0, 0, True))
    q = q_ref[0].reshape(g * h, w)
    least, last = least_ref[0], last_ref[0]                # (G, 1)

    def step(i, carry):
        m, l, acc = carry
        slot = i % 2
        pl.when(i + 1 < n_steps)(lambda: copies(i + 1, 1 - slot, True))
        copies(i, slot, False)
        keys = buf[slot]                                   # (t, W)
        s = jax.lax.dot_general(
            q, keys, _NT, preferred_element_type=jnp.float32) * scale
        here = pl.ds(pl.multiple_of(i * t, t), t)
        sc = sc_ref[0, :, here]                            # (G, t)
        keep = _kept(sc, least, last, at_ref[0, :, here])
        # a query row's mask for each of its heads: (G, t) -> (G x H, t)
        keep = jnp.broadcast_to(
            jnp.where(keep, 1.0, 0.0)[:, None, :], (g, h, t)
        ).reshape(g * h, t) > 0.5
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(keys.dtype), keys[:, :d_v], _NN,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # A row may select nothing of a step, its first included: `m` starts
    # above the mask's `_NEG_INF` and under every score, so that such a
    # step's exp(s - m_new) are 0 and not 1.
    _, l, acc = jax.lax.fori_loop(
        0, n_steps, step,
        (jnp.full((g * h, 1), 0.5 * _NEG_INF, jnp.float32),
         jnp.zeros((g * h, 1), jnp.float32),
         jnp.zeros((g * h, d_v), jnp.float32)))
    # l is 0 where a row selects nothing: an idle lane, a padded row.
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).reshape(g, h, d_v)


@functools.partial(jax.jit, static_argnames=("d_v", "scale"))
def _masked_latent_kernel(q, pool, layer, block_tables, kv_len, scores,
                          least, last, *, d_v, scale):
    """A chunk's attention over a selection as one kernel a layer, of
    this file's own, **reading the lanes' live pages whole and the
    selection as a mask**: `q` (S, K, H, W) against the pool where it
    lies, laid flat (L, N x block_size, W) in HBM (a bitcast of it), with
    `layer`, the tables and the lengths as scalars the kernel reads.
    Query row (s, i) attends the positions p of its lane whose index
    score `scores[s, i, p]` (S, K, T) float32 is above `least[s, i]` (S,
    K), and of those that equal it all (`last[s, i]` (S, K) int32
    negative) or the one whose row of the pool is `last[s, i]`
    (`_attend_masked` finds both, and whether that is an exact top-k).
    The grid is (lanes, groups of `_MASKED_QUERY_ROWS` query rows); a
    group's rows times the heads are the rows of one score tile against
    a step of `_MASKED_KERNEL_PAGES` pages, copied to VMEM once for both
    products (`_masked_latent_body`).  Operands in the pool's dtype,
    accumulation, statistics and rescaling float32, the scale applied to
    the float32 scores (`_latent_decode_kernel`'s conventions).  Jitted,
    as the decode kernels and for their reason.  Returns (S, K, H, d_v)
    float32."""
    s, k_w, h, w = q.shape
    n_layers, n_blocks, bs, _ = pool.shape
    g = _MASKED_QUERY_ROWS
    pages = min(_MASKED_KERNEL_PAGES, block_tables.shape[1])
    t = pages * bs
    # Whole groups of query rows and whole steps of positions: a padded
    # row selects nothing, a padded position is selected by nobody.
    rows = -k_w % g
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, -block_tables.shape[1] % pages)))
    width = tables.shape[1] * bs
    q = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, rows), (0, 0), (0, 0)))
    scores = jnp.pad(
        scores, ((0, 0), (0, rows), (0, width - scores.shape[-1])),
        constant_values=_NEG_INF)
    least = jnp.pad(least.astype(jnp.float32), ((0, 0), (0, rows)),
                    constant_values=-_NEG_INF)
    last = jnp.pad(last.astype(jnp.int32), ((0, 0), (0, rows)))
    # each position's row of a layer's pool laid flat
    at = jnp.repeat(tables, bs, axis=1) * bs + jnp.arange(width) % bs
    out = pl.pallas_call(
        functools.partial(_masked_latent_body, pages=pages, bs=bs, d_v=d_v,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s, (k_w + rows) // g),
            in_specs=[
                # the pool first: a profile keeps the start of an op's text
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, g, h, w), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, g, width), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, g, 1), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, g, 1), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, 1, width), lambda i, j, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, g, h, d_v),
                                   lambda i, j, *_: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, t, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((s, k_w + rows, h, d_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="masked_latent_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.reshape(-1),
      kv_len.astype(jnp.int32), pool.reshape(n_layers, n_blocks * bs, w),
      q, scores, least[..., None], last[..., None], at[:, None, :])
    return out[:, :k_w]


# A decode step reads its selection the same way (PR 60; `TPU v5 lite`,
# 2026-10-03, call A2): one query row a lane, its H = 128 heads the rows of
# the score tile (the chunk's kernel at K = 1 would pad the row to a group
# of 16 and multiply sixteen times as much), on `_paged_decode_body`'s
# pipeline through the lanes.  Bare, one layer, k = 2,048 of float32 normal
# scores, rows of 640 bfloat16 of which 512 are the value, every lane at
# the same length; ms a call, each timed inside one program (a scan over
# 16 queries: no launch from the host in it).  The fetch (`_fetch_best`:
# the sort of the tier that holds the longest lane, the gather of 2,048
# rows a lane, both products) | this kernel behind the search and the
# branch (`_attend_masked`) | of that the search (`_threshold`):
#   live a lane   8 lanes                     4 lanes
#     4,096     0.437 | 0.212 | 0.075      0.258 | 0.143 | 0.064
#     8,192     0.555 | 0.293 | 0.073      0.306 | 0.193 | 0.063
#    13,312     0.830 | 0.449 | 0.085      0.442 | 0.275 | 0.069
#    16,384     0.829 | 0.513 | 0.086      0.445 | 0.311 | 0.068
#    24,576     1.435 | 0.691 | 0.130      0.752 | 0.385 | 0.095
#    32,768     1.430 | 0.916 | 0.132      0.754 | 0.493 | 0.089
# (to 16,384 under DeepSeek-V3.2-Exp's pool and table of 1,024 entries,
# past it under dots3-note-prev's of 2,048, which reads the same to 0.02 ms
# where both were measured; 32,768: the kernel and the search alone, the
# branch still stood at 24,576.)  The kernel wins at every length a cell
# can hold, by 1.6-2.1 times, and its lead grows with the tier the sort is
# on; it takes 3.2 us a step of 64 pages (1,024 positions: 1.3 MB, 1.6 us
# of copy at 819 GB/s, 0.30 GFLOP, 1.5 us at 197 TFLOP/s): 128 query rows
# stream past each 128 x 128 tile of stored rows once, so the MXU loads a
# tile for every 128 rows it multiplies, and the products with the
# soft-max's vector work over a (128, 1,024) tile bound the step, not the
# copy (410 GB/s).  Past 32,768 nobody has measured (the sort would go to
# four spans, the kernel on by 0.028 ms a thousand positions at 8 lanes):
# `_MASKED_DECODE_LIVE_MAX` is the longest lane measured.  Against the same
# set attended in float32 the kernel's output differs by 0.07% of its rms
# (at most 1.1% of the rms in one value), the fetch's by 0.15% (3.1%): a
# running soft-max rounds exp(s - m) to bfloat16, the fetch the normalised
# probabilities.
# **Pages a step: the table's are 64, 32 are taken for what a written-out
# page costs a replica's start** (`_PAGED_KERNEL_PAGES` has the account: a
# step's copies are written out, and the kernel is traced once a burst
# tier and lowered once a program).  128 pages read the same as 64 to
# 0.01 ms; 32 cost 0.02-0.03 ms a call (call A: 0.204 / 0.292 / 0.428 for
# 0.181 / 0.271 / 0.402 at 8 lanes of 4k / 8k / 13k).  In the cell
# (`dsv32-agent`, five layers a step; calls B and C, one seed a row,
# `tpot_p50_ms`): the parent 15.94 / 16.01 | 64 pages 13.47 / 13.59 | 32
# pages 13.55 / 13.70 | 16 pages 13.76 / 13.80 | 64 issued from a loop
# 13.86 / 14.00; the cell's warm `setup_s` read 62.2 -> 65.5 and 61.3 ->
# 66.4 at 64 pages (+5.4%, +8.2% under a bound of 10%; tracing and
# lowering the kernel for a described v5e, this sandbox's CPU: 0.81 s a
# tier of 8 lanes at 64 pages, 0.38 at 32, 0.38 at 16).
_MASKED_DECODE_PAGES = 32
_MASKED_DECODE_LIVE_MAX = 32768


def _masked_decode_body(layer_ref, tables_ref, len_ref, pool_ref, q_ref,
                        sc_ref, least_ref, last_ref, at_ref, o_ref, buf,
                        sems, slot_ref, *, pages, d_v, scale):
    """`_paged_decode_body` over one pool of latent rows, as
    `_latent_decode_body` but laid flat, **under a selection as a mask**:
    a position counts where it is under the lane's length and `_kept`
    keeps it by its index score `sc_ref` (1, T) against the lane's
    `least_ref` (1, 1) and, at a tie, by its row of the pool `at_ref`
    (1, T) against `last_ref` (1, 1): `_masked_latent_body`'s rule."""
    t = buf.shape[1]                       # positions a step

    def seen(i, length, shape):
        here = pl.ds(pl.multiple_of(i * t, t), t)
        keep = _kept(sc_ref[0, :, here], least_ref[0], last_ref[0],
                     at_ref[0, :, here])
        # One comparison over the score tile: a kept position's column
        # stands under what is left of the lane's length, any other
        # under 0.
        return jax.lax.broadcasted_iota(jnp.int32, shape, 1) < jnp.where(
            keep, length - i * t, 0)

    _paged_decode_body(
        layer_ref, tables_ref, len_ref, q_ref, o_ref, slot_ref, (pool_ref,),
        (buf,), (sems,), pages=pages, bs=t // pages, scale=scale, d_out=d_v,
        seen=seen, values=lambda rows, slot: rows[:, :d_v], flat=True,
        sparse=True)


@functools.partial(jax.jit, static_argnames=("d_v", "scale"))
def _masked_decode_kernel(q, pool, layer, block_tables, kv_len, scores,
                          least, last, *, d_v, scale):
    """A decode step's attention over a selection as one kernel a layer,
    of this file's own: `_masked_latent_kernel` for one query row a lane,
    `q` (S, 1, H, W), on `_latent_decode_kernel`'s grid (the lanes) and
    pipeline (`_paged_decode_body`: the next live lane's first step
    fetched ahead), its conventions and its operands, the pool laid flat
    (L, N x block_size, W) first.  Lane s attends the positions p under
    `kv_len[s]` whose index score `scores[s, 0, p]` (S, 1, T) float32 is
    above `least[s, 0]`, and of those that equal it all (`last[s, 0]`
    negative) or the one whose row of the pool is `last[s, 0]`
    (`_threshold`).  A lane's H heads are the rows of the score tile
    against a step of `_MASKED_DECODE_PAGES` pages: the chunk's kernel at
    K = 1 would pad the one query row to a group of
    `_MASKED_QUERY_ROWS`.  Only a lane's live pages are read; an idle
    lane reads none and gets 0.  Returns (S, 1, H, d_v) float32."""
    s, _, h, w = q.shape
    n_layers, n_blocks, bs, _ = pool.shape
    pages = min(_MASKED_DECODE_PAGES, block_tables.shape[1])
    # Whole steps of positions: a padded position is selected by nobody.
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, -block_tables.shape[1] % pages)))
    width = tables.shape[1] * bs
    scores = jnp.pad(scores, ((0, 0), (0, 0), (0, width - scores.shape[-1])),
                     constant_values=_NEG_INF)
    # each position's row of a layer's pool laid flat
    at = jnp.repeat(tables, bs, axis=1) * bs + jnp.arange(width) % bs
    out = pl.pallas_call(
        functools.partial(_masked_decode_body, pages=pages, d_v=d_v,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[
                # the pool first: a profile keeps the start of an op's text
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, 1, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, 1, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, h, d_v), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, d_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="masked_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.reshape(-1),
      kv_len.astype(jnp.int32), pool.reshape(n_layers, n_blocks * bs, w),
      q[:, 0].astype(pool.dtype), scores, least.astype(jnp.float32)[..., None],
      last.astype(jnp.int32)[..., None], at[:, None, :])
    return out[:, None]


def _masked_takes(q_shape, pool_shape, dtype, d_v: int):
    """None where `_masked_latent_kernel` can run these shapes on a TPU,
    else what stands in its way: rows, values and pages in whole tiles,
    the heads whole sublane tiles of a query row's score rows."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize not in (2, 4):
        return f"a pool of {dtype.name} has no tile of its own"
    sublanes = 32 // dtype.itemsize
    if pool_shape[-1] % _LANES or d_v % _LANES:
        return (f"a row of {pool_shape[-1]} with a value of {d_v} is not "
                f"whole tiles of {_LANES} lanes")
    if q_shape[2] % sublanes or pool_shape[2] % sublanes:
        return (f"{q_shape[2]} heads or a page of {pool_shape[2]} rows are "
                f"not whole tiles of {sublanes} sublanes")
    return None


def _score_at(key):
    """The float32 that stands at place `key` (uint32) in the order of all
    float32: the negatives' bit patterns turned over, the others above
    them."""
    top = jnp.uint32(1 << 31)
    return jax.lax.bitcast_convert_type(
        jnp.where(key >= top, key ^ top, ~key), jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "reach"))
def _edge_of_best(scores, k: int, live, reach: int = 1 << 30):
    """Where each row's k largest `scores` (S, K, T) end, **by a search
    that sorts nothing**: (least, above, equal, first), each (S, K).
    `least` is the k-th largest score to the bit (`select_rows`' own:
    halfway to `_NEG_INF` for a row that sees under k), the largest
    float32 that k of the row's scores reach, found a bit a round from the
    top of its place in the order (`_score_at`); `above` and `equal` count
    the scores over it and at it, and `first` is the lowest position at
    it.  `live` as `_select`'s: only the candidates that can hold the
    call's longest lane are read, the first 4 k or all up to `reach` (two
    tiers, not the sort's five, because a tier costs a replica's start
    more than it saves a launch; what comes back for a lane longer than
    `reach` nobody reads).  Jitted, as the kernels: a program's call sites
    trace it once."""
    k = min(k, scores.shape[-1])
    top = max(k, min(scores.shape[-1], reach))

    def edge(sc):
        def round_(i, key):
            higher = key | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
            count = jnp.sum(sc >= _score_at(higher)[..., None], axis=-1,
                            dtype=jnp.int32)
            return jnp.where(count >= k, higher, key)

        key = jax.lax.fori_loop(0, 32, round_,
                                jnp.zeros(sc.shape[:-1], jnp.uint32))
        least = jnp.maximum(_score_at(key), 0.5 * _NEG_INF)[..., None]
        tie = sc == least
        return (least[..., 0], jnp.sum(sc > least, axis=-1, dtype=jnp.int32),
                jnp.sum(tie, axis=-1, dtype=jnp.int32),
                jnp.argmax(tie, axis=-1).astype(jnp.int32))

    if 4 * k >= top:
        return edge(scores[..., :top])
    return jax.lax.cond(live > 4 * k, lambda: edge(scores[..., :top]),
                        lambda: edge(scores[..., :4 * k]))


@functools.partial(jax.jit,
                   static_argnames=("k", "handed", "d_v", "scale"))
def _fetch_best(q, pool, layer, block_tables, kv_len, scores, at, *, k: int,
                handed: bool, d_v: int, scale: float):
    """The k best by the sort (`select_rows`: here and nowhere else), their
    rows fetched (`_selected_latent_attention`); with `handed` also the
    positions, in the sort's order.  Jitted: a chunk holds it on both
    sides of the platform's branch and at two call sites, one trace of
    its five tiers of sorts for all of them."""
    with jax.named_scope("dsa_select"):
        live = jnp.max(kv_len)
        rows, seen, _ = select_rows(scores, k, live, at)
        positions = select_positions(scores, k, live) if handed else None
    return _selected_latent_attention(q, pool, layer, rows, seen, d_v=d_v,
                                      scale=scale), positions


def _threshold(scores, at, k: int, live, reach: int):
    """The selection as a threshold (the choosing, a chunk's and a decode
    step's alike, and **no sort**): `_edge_of_best` finds each row's k-th
    best score, `least`; every position above it is in the set, and of
    the `equal` that tie with it the set keeps `kept`, enough to make k.
    So the mask `score >= least` is the set where a row keeps every such
    position, or one alone: the lowest, which a kernel finds among them
    by its row of the pool, `last` (negative: every one).  Returns (least
    (S, K) float32, last (S, K) int32, whether that is an exact top-k in
    every row: false where some row keeps two or more and leaves one
    out, and where the counts do not bear the search out, a score that
    is no number)."""
    with jax.named_scope("dsa_select"):
        least, above, equal, first = _edge_of_best(scores, k, live,
                                                   reach=reach)
        # a row that sees under k keeps all it sees: none at its `least`
        kept = jnp.where(least > 0.5 * _NEG_INF, k - above, 0)
        every = equal == kept
        last = jnp.where(every, -1, jnp.take_along_axis(at, first, axis=1))
        exact = jnp.all(every | ((kept == 1) & (equal > 0)))
    return least, last, exact


def _attend_masked(q, pool, layer, block_tables, kv_len, scores, at, *,
                   k: int, handed: bool, d_v: int, scale: float):
    """The selection read as a mask over the lanes' live pages where the
    threshold is an exact top-k (`_threshold`) and the call's longest
    lane is within the kernel's reach, else the fetch: a chunk by
    `_masked_latent_kernel` up to `_MASKED_LIVE_MAX`, a decode step (one
    query row a lane) by `_masked_decode_kernel` up to
    `_MASKED_DECODE_LIVE_MAX`.  **A call that reads the mask executes no
    sort**; one whose ties the mask cannot express, or whose longest lane
    is past the reach, takes the fetch, sort and all inside that branch
    (`_fetch_best`).  Returns (out, with `handed` the positions: those
    the mask kept, or the fetch's; else None, whether the call read the
    mask: the branch's own predicate, int32)."""
    decode = q.shape[1] == 1
    kernel = _masked_decode_kernel if decode else _masked_latent_kernel
    reach = _MASKED_DECODE_LIVE_MAX if decode else _MASKED_LIVE_MAX
    live, k = jnp.max(kv_len), min(k, scores.shape[-1])
    least, last, exact = _threshold(scores, at, k, live, reach)

    def mask():
        out = kernel(q, pool, layer, block_tables, kv_len, scores, least,
                     last, d_v=d_v, scale=scale)
        if not handed:
            return out, None
        keep = _kept(scores, least[..., None], last[..., None],
                     at[:, None, :])
        # the kept positions (a set: the order is the sort's), behind them
        # for a row that sees under k positions it does not see
        return out, select_positions(jnp.where(keep, 0.0, _NEG_INF), k, live)

    masked = (live <= reach) & exact
    return (*jax.lax.cond(
        masked, mask,
        lambda: _fetch_best(q, pool, layer, block_tables, kv_len, scores, at,
                            k=k, handed=handed, d_v=d_v, scale=scale)),
        masked.astype(jnp.int32))


def _attend_selected(q, pool, layer, block_tables, kv_len, scores, at,
                     k: int, handed: bool = False, *, d_v: int,
                     scale: float):
    """`paged_latent_attention` over a learned selection: each query row
    attends the `k` positions of its lane with the largest index `scores`
    (S, K, T) float32 (an exact top-k: `_select` says why no other), `at`
    (S, T) int32 each position's row in a layer's pool laid flat.  One
    of two forms that attend the same function.  **Lowered for a TPU, a
    chunk and a decode step alike read their lanes' live pages whole in
    one Pallas kernel a layer, the selection a threshold on the score
    tile and nothing sorted** (`_attend_masked`: a chunk once a group of
    query rows, a decode step a lane's heads at once; it falls back
    inside the program where the threshold is not the set or a lane is
    past the kernel's reach).  Every other platform, a pool split over a
    mesh and shapes that are not whole tiles (a chunk says so) **sort,
    and fetch** the selected rows into a dense buffer (`_fetch_best`).
    The platform is the one the program is lowered for
    (`jax.lax.platform_dependent`), as `paged_latent_attention`'s.
    Returns (out (S, K, H, d_v) float32, the positions attended (S, K, k)
    int32 if `handed` else None: a set, in the order of the form that
    read it, 1 where the call read the mask else 0: int32, the program's
    to sum)."""
    how = dict(k=k, handed=handed, d_v=d_v, scale=scale)
    args = (q, pool, layer, block_tables, kv_len, scores, at)

    def fetch(*args):
        return (*_fetch_best(*args, **how), jnp.int32(0))

    if jax.typeof(pool).sharding.mesh.size > 1:
        return fetch(*args)
    why = _masked_takes(q.shape, pool.shape, pool.dtype, d_v)
    if why is not None:
        # Decided at trace time, as `paged_latent_attention`'s; a decode
        # step says nothing more than its model's chunk has.
        if q.shape[1] != 1:
            warnings.warn(
                f"paged_latent_attention: {why}, so a chunk over pool"
                f"{pool.shape} fetches its selected rows on a TPU too, not "
                f"the Pallas kernel (a 512-row launch at dots3-note-prev's "
                f"widths takes 25 ms a layer whatever its context)",
                stacklevel=3)
        return fetch(*args)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(_attend_masked, **how), default=fetch)


# Differential attention (arXiv:2410.05258): softmax(q1 k1^T / sqrt(D)) v
# - lambda softmax(q2 k2^T / sqrt(D)) v, with v twice as wide as a key.
# Stored, a position's keys and values are flat rows of E = G * 2D: KV
# group g is the D-wide key heads (2g, 2g+1) = (k1, k2) side by side at
# columns [g * 2D, (g + 1) * 2D), and its value the value heads (2g, 2g+1)
# side by side there.  The rows stay flat from the pool to the products:
# a trailing dimension of D = 64, half a lane tile, makes the compiler lay
# the pool out otherwise and copy all of it into and out of every step,
# and a gathered group reshaped to (t, G, 2D) is a copy of the group.  So
# a query is spread over a row of zeros instead: map c of differential
# head (g, r) is a row of E with q_c at columns g * 2D + c * D, and its dot
# with a stored key row is q_c k_c alone.  Scores and values are then one
# batched matmul a lane over whole rows, X = G * rep * 2 query rows against
# t stored ones; the value product gives (X, E), of which row (g, r, c)
# keeps its own group's 2D columns.  Ten times the multiply-adds the
# mathematics needs, on a step that waits for memory.
# A query comes as (S, K, G, rep, 2, D).  Both bodies return the two maps
# applied to the group's value, (S, K, G, rep, 2, 2D) float32; the caller
# subtracts them.  A group's K and V are read once for both maps and all
# rep heads.
def _diff_products(q6):
    s, k_w, g, rep, _, d = q6.shape
    scale = d ** -0.5
    zero = jnp.zeros_like(q6[..., 0, :])
    halves = jnp.stack([jnp.concatenate([q6[..., 0, :], zero], -1),
                        jnp.concatenate([zero, q6[..., 1, :]], -1)],
                       axis=-2)                       # (S,K,G,rep,2,2D)
    own = jnp.eye(g, dtype=q6.dtype)
    qx = jnp.einsum("sqgrce,gh->sqgrche", halves, own).reshape(
        s, k_w, g * rep * 2, g * 2 * d)

    def score(kb):                     # (S, t, E) -> (S, K, X, t)
        return jnp.einsum("sqxe,ste->sqxt", qx, kb,
                          preferred_element_type=jnp.float32) * scale

    def mix(p, vb):                    # -> (S, K, X, E)
        return jnp.einsum("sqxt,ste->sqxe", p, vb,
                          preferred_element_type=jnp.float32)

    def own_columns(out):              # (S, K, X, E) -> (S,K,G,rep,2,2D)
        out = out.reshape(s, k_w, g, rep * 2, g, 2 * d)
        return jnp.einsum("sqgxhe,gh->sqgxe", out, own.astype(out.dtype)) \
            .reshape(s, k_w, g, rep, 2, 2 * d)

    return score, mix, own_columns


def paged_diff_attention(q6, k_pool, v_pool, layer, block_tables, positions,
                         kv_len):
    """`paged_attention`'s sibling for differential heads over a pool of
    flat rows (L, N, block_size, E): the same grouped running soft-max
    over the live blocks of `[layer]`."""
    score, mix, own_columns = _diff_products(q6)
    g, rep, d = q6.shape[2], q6.shape[3], q6.shape[5]
    return own_columns(_paged_running_softmax(
        k_pool, v_pool, layer, block_tables, positions, kv_len, score, mix,
        (g * rep * 2,), g * 2 * d))


# A sliding-window layer keeps a ring a slot instead of pool blocks: rings
# (L_window, S + 1, R, ...) hold position p of the sequence in engine slot s
# at [layer, s, p % R], R = window + prefill_chunk (a chunk is written
# before it is read, and its first query still sees a whole window); the
# last slot is the null slot, where idle lanes point.  Rows are written for
# the positions below a lane's length and no others, so row j holds the
# largest position <= length - 1 that is j modulo R (negative: never written
# by this sequence, whatever an earlier owner of the slot left there), and a
# row is seen by the position it holds: nothing is zeroed when a slot
# changes hands.  `models.hybrid` (differential heads, flat rows) and
# `models.decoding` (plain GQA, rows of (Hkv, D)) share the three functions
# below and differ in the `attend` they hand to `slot_ring_reader`.
def ring_rows(positions, kv_len, ring: int):
    """The ring row each of `positions` (S, K) is written to: p % ring,
    and `ring` itself (out of bounds: a `.at[].set(mode="drop")` drops it)
    for a position at or past the lane's `kv_len` (S,), which is the
    zero-padded tail of a chunk or an idle lane."""
    return jnp.where(positions < kv_len[:, None], positions % ring, ring)


def ring_seen(positions, kv_len, ring: int, window: int):
    """(S, K, ring) bool: which rows of its lane's ring a query at
    `positions` (S, K) sees, the lane holding `kv_len` (S,) positions:
    those whose held position p has t - window < p <= t."""
    top = (kv_len - 1)[:, None]
    held = top - jnp.mod(top - jnp.arange(ring)[None, :], ring)    # (S, R)
    held, pos = held[:, None, :], positions[:, :, None]
    return (held <= pos) & (held > pos - window) & (held >= 0)


# Which rings a burst's window layer reads, by the bare burst's time (ms a
# burst of 8 steps, every lane live at 2,048 positions, median of 12; my
# chip runs, PR 58, call A, one TPU v5 lite).  "in place": every slot's
# rings where they lie, the lanes' queries put at their slots; "joined":
# the lanes' rings alone, a `dynamic_slice` a lane; "a lane at a time":
# those slices, `attend` called once a lane.
#
#   lanes of slots      in place   joined   a lane at a time
#   Mellum2   4 of 33     32.59     25.66     25.75
#             8 of 33     32.95     27.38     27.75
#            16 of 33     34.69     31.26     31.18
#            32 of 33     37.50     39.09       -
#   phi4flash 4 of 33    111.46    102.15    102.49
#             8 of 33    118.10    111.32    111.42
#            16 of 33    132.32    129.47    130.26
#            32 of 33    167.30    171.76       -
#   Laguna    4 of 9      25.76     23.61     23.12
#             8 of 9      26.59     26.42     26.69
#   dots3     4 of 9      36.94     35.47     36.07
#             8 of 9      41.87     40.78     41.58
#
# (Rings a slot: Mellum2 6 of 1,152 x 4 x 128, phi4flash 8 of 640 x 1,280
# for differential heads, Laguna 6 of 1,024 x 8 x 128, dots3 3 of 1,040 x
# 1,152 latent rows.)  Joined wins wherever the lanes are at most half the
# slots and loses where they are all of them but one on 33 (it copies what
# the other reads where it lies); at 8 of 9 it is a draw on Laguna and
# 2.6% on dots3, whose traffic never fills that tier, so the rule stays
# the one line below.  A lane at a time is no faster than the join and is
# not kept.
def _lanes_rings(lanes: int, n_slots: int) -> bool:
    """Whether a call of `lanes` lanes reads its lanes' rings alone, of
    `n_slots` (the null slot counted): the table above."""
    return 2 * lanes <= n_slots


def ring_slots_read(lanes: int, n_slots: int) -> int:
    """The slots whose rings one window layer of a call of `lanes` lanes
    reads through `slot_ring_reader`."""
    return lanes if _lanes_rings(lanes, n_slots) else n_slots


def slot_ring_reader(attend, slots, positions, kv_len, window: int,
                     n_slots: int):
    """`read(q, k_rings, v_rings, layer)`: `attend(q, k_ring, v_ring,
    positions, kv_len, window)` of the lanes' queries over their slots'
    rings of `[layer]`.  A call narrow against the slots (`_lanes_rings`;
    one lane: a prefill chunk): each lane's ring where it lies, a
    `dynamic_slice` a lane, joined along the lane axis; an idle lane
    carries the null slot and length 0, reads that ring and sees nothing.
    A call as wide as most of the slots: every slot's ring in place, in
    slot order, with the lanes' queries put at their `slots` (S,) and the
    answers taken back; slots that are no lane of the call have length 0
    and see nothing.  What the compiler makes of each (the compiled text
    for a described v5e, Mellum2's burst at width 4 of 33): of
    `rings[layer, slots]`, a gather, slices of the whole ring array
    `(6,33,384,4,128)` and more bytes than in place (XLA's count 2.28 ->
    7.49 GB), as when PR 30 first tried it; of the per-lane slices,
    `(1,1,1152,4,128)` slices and `(4,1152,4,128)` arrays, no array of 33
    but the rings' write (2.28 -> 1.58 GB).  `n_slots` counts the null
    slot."""
    lanes = positions.shape[0]
    if _lanes_rings(lanes, n_slots):
        def read(q, k_rings, v_rings, layer):
            ats = [(layer, slots[j]) + (0,) * (k_rings.ndim - 2)
                   for j in range(lanes)]
            size = (1, 1) + k_rings.shape[2:]

            def joined(rings):
                return jnp.concatenate([jax.lax.dynamic_slice(
                    rings, at, size) for at in ats], axis=1)[0]
            return attend(q, joined(k_rings), joined(v_rings), positions,
                          kv_len, window)
        return read
    pos_all = jnp.zeros((n_slots,) + positions.shape[1:],
                        positions.dtype).at[slots].set(positions)
    len_all = jnp.zeros((n_slots,), kv_len.dtype).at[slots].set(kv_len)

    def read(q, k_rings, v_rings, layer):
        q_all = jnp.zeros((n_slots,) + q.shape[1:], q.dtype).at[slots].set(q)
        return attend(q_all, k_rings[layer], v_rings[layer], pos_all,
                      len_all, window)[slots]
    return read


def window_attention(q, k_ring, v_ring, positions, kv_len, window):
    """Grouped-query attention of `q` (S, K, H, D) over a ring a lane:
    `k_ring` / `v_ring` (S, R, Hkv, D), rows as `ring_seen` reads them.
    The whole ring is read, in one soft-max: R is a window and a chunk,
    whatever the lane's length.  As `paged_attention`: the rep = H // Hkv
    query heads of a KV head on their own axis against the stored head,
    scores and both products' accumulation float32.  An idle lane
    (kv_len 0) sees nothing and returns garbage nobody reads.  Returns
    (S, K, H, D) float32."""
    s, k_w, h, d = q.shape
    hkv = k_ring.shape[2]
    qg = q.reshape(s, k_w, hkv, h // hkv, d)
    seen = ring_seen(positions, kv_len, k_ring.shape[1], window)
    sc = jnp.einsum("sqhrd,sthd->sqhrt", qg, k_ring,
                    preferred_element_type=jnp.float32) * d ** -0.5
    sc = jnp.where(seen[:, :, None, None, :], sc, _NEG_INF)
    out = jnp.einsum("sqhrt,sthd->sqhrd", jax.nn.softmax(sc, axis=-1),
                     v_ring, preferred_element_type=jnp.float32)
    return out.reshape(s, k_w, h, d)


def window_diff_attention(q6, k_ring, v_ring, positions, kv_len, window):
    """`window_attention`'s sibling for differential heads over rings of
    flat rows (S, R, E): both maps in one soft-max over the whole ring."""
    seen = ring_seen(positions, kv_len, k_ring.shape[1], window)
    score, mix, own_columns = _diff_products(q6)
    sc = jnp.where(seen[:, :, None, :], score(k_ring), _NEG_INF)
    return own_columns(mix(jax.nn.softmax(sc, axis=-1), v_ring))


def latent_window_attention(q, ring, _, positions, kv_len, window, *,
                            d_v: int, scale: float):
    """`window_attention`'s sibling for rings of latent rows (S, R, W),
    in absorbed form: multi-query attention of `q` (S, K, H, W) over a
    ring a lane whose rows are (normalised latent | roped key | padding),
    the first `d_v` columns also the value, as a row of
    `paged_latent_attention`'s pool; rows seen as `ring_seen` reads them,
    the whole ring in one soft-max.  `slot_ring_reader` hands it the ring
    as keys and again as values; it reads the one.  Returns (S, K, H,
    d_v) float32."""
    seen = ring_seen(positions, kv_len, ring.shape[1], window)
    sc = jnp.einsum("sqhe,ste->sqht", q, ring,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(seen[:, :, None, :], sc, _NEG_INF)
    return jnp.einsum("sqht,ste->sqhe", jax.nn.softmax(sc, axis=-1),
                      ring[..., :d_v], preferred_element_type=jnp.float32)
