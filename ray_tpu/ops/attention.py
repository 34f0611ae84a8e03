"""Flash attention: Pallas TPU kernel + XLA reference.

Online-softmax blockwise attention (Dao et al.) laid out for the MXU:
queries stream through VMEM in `block_q` rows while key/value blocks of
`block_kv` rows are swept in the innermost grid dimension, with the running
max/denominator/accumulator held in VMEM scratch across the sweep.  Causal
sweeps skip fully-masked kv blocks.

Autodiff: the forward kernel also emits per-row logsumexp; the backward is
two more Pallas kernels (Dao-style): dq accumulates over kv blocks, dk/dv
accumulate over q blocks, with delta = rowsum(do*o) precomputed.  Off the
TPU both directions use the XLA reference; on it, a shape the kernel
cannot take does too, and says so once (`_pallas_eligible`).

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


def mha_reference(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                  kv_offset: int = 0):
    """Plain-XLA multi-head attention, numerically stable softmax.

    Shapes: q (B, Tq, H, D), k/v (B, Tkv, H, D).  `kv_offset` shifts kv
    global positions for causal masking (used by ring attention where the
    local kv block starts at a nonzero global index; q is assumed to start
    at global index `kv_offset=0` frame of its caller).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_pos = jnp.arange(tq)[:, None]
        k_pos = jnp.arange(tk)[None, :] + kv_offset
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               sm_scale: float, causal: bool, block_q: int, block_kv: int,
               num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: kv block is live iff its first row index <= q block's last row.
    live = (qi + 1) * block_q > ki * block_kv if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]                             # native dtype -> MXU
        k = k_ref[0]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                         # (block_q, block_kv)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[...]                      # (block_q, LANES)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)          # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)                     # (block_q, LANES)
        p = jnp.exp(s - m_new[:, :1])                       # (block_q, block_kv)
        l_new = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, ...] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)
        # logsumexp per row, lane-replicated (TPU tiling wants a 128 lane
        # dim — same layout as the in-tree pallas flash attention)
        lse_ref[0, ...] = m_scr[...] + jnp.log(l_scr[...])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale: float, causal: bool,
                   block_q: int, block_kv: int, num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (qi + 1) * block_q > ki * block_kv if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                          # (block_q, 1)
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, ...] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                    causal: bool, block_q: int, block_kv: int,
                    num_q_blocks: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (qi + 1) * block_q > ki * block_kv if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bkv)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # p^T @ do
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # ds^T @ q

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, sm_scale: float | None = None):
    """Fused attention.  q,k,v: (B, T, H, D) → (B, T, H, D).

    Uses the Pallas TPU kernel on TPU, XLA reference elsewhere.  GQA/MQA:
    callers repeat kv heads before the call (XLA folds the broadcast).
    """
    return _flash_fwd(q, k, v, causal, sm_scale)[0]


def _flash_fwd(q, k, v, causal, sm_scale):
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
    if t % 128 or tkv % 128:   # blocks are 128 or 256 rows (_blocks_for)
        # Decided at trace time, again for every layer and retrace: the
        # warnings registry shows each distinct message once.
        warnings.warn(
            f"flash_attention: q length {t} / kv length {tkv} is not a "
            f"multiple of 128, so q{q.shape} k{k.shape} takes the XLA "
            f"reference (O(T^2) memory), not the Pallas kernel",
            stacklevel=2)
        return False
    return True


def _blocks_for(t: int, tkv: int) -> tuple[int, int]:
    # Block sizes must divide the sequence lengths exactly (the grid floors
    # otherwise and partial blocks would be silently skipped); callers
    # guarantee t, tkv are multiples of 128.
    return (256 if t % 256 == 0 else 128), (256 if tkv % 256 == 0 else 128)


def _fold(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _flash_pallas(q, k, v, *, causal, sm_scale):
    b, t, h, d = q.shape
    tkv = k.shape[1]
    block_q, block_kv = _blocks_for(t, tkv)
    num_q = t // block_q
    num_kv = tkv // block_kv

    qf, kf, vf = _fold(q), _fold(k), _fold(v)

    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=num_kv)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)

    def unfold(x):
        return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    return unfold(out), lse


def _flash_bwd_pallas(q, k, v, out, lse, g, *, causal, sm_scale):
    """Dao-style backward: one kernel accumulating dq over kv blocks, one
    accumulating dk/dv over q blocks.  delta = rowsum(do * o)."""
    b, t, h, d = q.shape
    tkv = k.shape[1]
    block_q, block_kv = _blocks_for(t, tkv)
    num_q, num_kv = t // block_q, tkv // block_kv
    bh = b * h

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    dof, of = _fold(g), _fold(out)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)                               # (BH, T)
    delta = jnp.broadcast_to(delta[..., None], (bh, t, _LANES))

    common_in = [qf, kf, vf, dof, lse, delta]

    dq_kernel = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=num_kv)
    dqf = pl.pallas_call(
        dq_kernel,
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*common_in)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_kv=block_kv, num_q_blocks=num_q)
    dkf, dvf = pl.pallas_call(
        dkv_kernel,
        grid=(bh, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tkv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tkv, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*common_in)

    def unfold(x, tt):
        return x.reshape(b, h, tt, d).transpose(0, 2, 1, 3)

    return unfold(dqf, t), unfold(dkf, tkv), unfold(dvf, tkv)


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


def _paged_running_softmax(k_pool, v_pool, layer, block_tables, positions,
                           kv_len, score, mix, stat, d_out):
    """The loop both paged bodies share: groups of `_PAGED_GROUP_BLOCKS`
    table entries of one layer of the pool, read at `[layer, block]` as
    stored, under a running soft-max whose trip count follows the longest
    live lane of the call.  `score(kb)` gives a group's scaled scores
    (S, K, *stat, t) float32 from its keys (S, t, ...), the pool's
    trailing dimensions as stored; `mix(p, vb)` applies the probabilities
    to its values, (S, K, *stat, d_out)."""
    s, k_w = positions.shape
    bs, row = k_pool.shape[2], k_pool.shape[3:]
    g = min(_PAGED_GROUP_BLOCKS, block_tables.shape[1])
    t = g * bs
    # Whole groups only: dynamic_slice would clamp a ragged last one onto
    # the entries before it.  The padding names the null block.
    tables = jnp.pad(block_tables, ((0, 0), (0, -block_tables.shape[1] % g)))
    over_stat = (slice(None), slice(None)) + (None,) * len(stat)

    def group(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, i * g, g, axis=1)
        kb = k_pool[layer, ids].reshape(s, t, *row)
        vb = v_pool[layer, ids].reshape(s, t, *row)
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


def paged_attention(q, k_pool, v_pool, layer, block_tables, positions, kv_len):
    """Attention of `q` (S, K, H, D) over one layer of a paged KV pool.

    `k_pool` / `v_pool` are the whole pool (L, N, block_size, Hkv, D), read
    at `[layer, block]` as stored: no slice of it is taken out and no copy in
    another dtype is made.  Lane s owns the blocks `block_tables[s]` (S, B)
    in sequence order; its query i stands at absolute position
    `positions[s, i]` and sees the kv positions <= that, which the caller
    has already written.  `kv_len` (S,) is how many positions of each lane
    are live (0: the lane is idle and its output is garbage nobody reads).

    Only live blocks are read: a loop over groups of `_PAGED_GROUP_BLOCKS`
    table entries with a running soft-max, whose trip count follows the
    longest live lane of this call (`_paged_running_softmax`).  The rep =
    H // Hkv query heads of a KV
    head are grouped on their own axis against the stored head, so K and V
    are neither repeated nor kept in another dtype; scores, soft-max, the
    probabilities and both products' accumulation are float32 (the compiler
    folds a group's widening into the second product: on a v5e the step
    takes the same time with the probabilities rounded to the cache dtype).
    Returns (S, K, H, D) float32.
    """
    s, k_w, h, d = q.shape
    hkv = k_pool.shape[3]
    qg = q.reshape(s, k_w, hkv, h // hkv, d)
    scale = d ** -0.5
    out = _paged_running_softmax(
        k_pool, v_pool, layer, block_tables, positions, kv_len,
        lambda kb: jnp.einsum("sqhrd,sthd->sqhrt", qg, kb,
                              preferred_element_type=jnp.float32) * scale,
        lambda p, vb: jnp.einsum("sqhrt,sthd->sqhrd", p, vb,
                                 preferred_element_type=jnp.float32),
        (hkv, h // hkv), d)
    return out.reshape(s, k_w, h, d)


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


def window_diff_attention(q6, k_ring, v_ring, positions, kv_len, window):
    """Differential attention over a ring a lane: `k_ring` / `v_ring`
    (S, R, E) hold position p at row p % R, rows written for the
    positions below `kv_len` (S,) and no others.  Row j therefore holds
    the largest position <= kv_len - 1 that is j modulo R (negative:
    never written), and a query at position t sees the rows whose
    position p has t - window < p <= t.  The whole ring is read, in one
    soft-max: R is a window and a chunk, whatever the lane's length.  An
    idle lane (kv_len 0) sees nothing and returns garbage nobody reads."""
    r = k_ring.shape[1]
    top = (kv_len - 1)[:, None]
    held = top - jnp.mod(top - jnp.arange(r)[None, :], r)          # (S, R)
    held, pos = held[:, None, :], positions[:, :, None]
    seen = (held <= pos) & (held > pos - window) & (held >= 0)     # (S,K,R)
    score, mix, own_columns = _diff_products(q6)
    sc = jnp.where(seen[:, :, None, :], score(k_ring), _NEG_INF)
    return own_columns(mix(jax.nn.softmax(sc, axis=-1), v_ring))
