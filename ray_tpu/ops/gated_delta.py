"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear attention
whose state a position first corrects and then writes.

A head keeps a matrix `S` (d_k, d_v), float32, zero at a sequence's start.
Position t, with key k_t and query q_t (d_k; the caller normalises and
scales them), value v_t (d_v), a write strength beta_t in (0, 1) and a log
decay g_t <= 0:

    S <- exp(g_t) S
    d  = beta_t (v_t - S^T k_t)        what S does not yet answer for k_t
    S <- S + k_t d^T
    o_t = S^T q_t

`gated_delta_step` is that, one position a lane, in plain array ops on the
lanes' states handed over as an array of their own.  **A decode step of
the served path is `gated_delta_step_rows`: the same position on the
states where they live**, rows of any array of state rows (the cache's
`lstate`, every linear layer's slots, viewed as rows) named by an index a
lane.  Lowered for a TPU that is one Pallas kernel a layer, this file's
own (`_step_kernel`): the grid runs over (lane, block of value heads), a
grid step's DMA is the lane's block of heads straight out of the rows'
array and straight back (the array is the call's result too, aliased to
its operand: never copied, rows no lane names never touched), and the
four lines above run head by head in VMEM and registers, float32, in that
order; the state is read once and written once, where the plain form
between a gather and a scatter passed over the lanes' 17 MB five or six
times a layer.  On every other platform, and for a state that is not
float32 in whole (8, 128) tiles (`_step_kernel_takes`), it is the plain
form between the gather and the scatter: the CPU's path, the chunk form's
companion and the kernel's oracle.  `jax.lax.platform_dependent` chooses,
so a program compiled ahead of time for a described chip holds what the
chip runs.  `gated_delta_chunks` is the same recurrence over
T = m x `chunk` positions as matrix products, in the WY form of the paper
(section 3.3): inside a chunk of C positions, with G_t the running sum of g
and D[t, r] = exp(G_t - G_r) for r <= t,

    L = strict_lower((beta o K) K^T o D)         (C, C)
    [W | U] = (I + L)^-1 [beta o exp(G) o K | beta o V]
    V' = U - W S_0                               the corrected values
    O  = (exp(G) o Q) S_0 + lower(Q K^T o D) V'
    S_C = exp(G_C) S_0 + (exp(G_C - G) o K)^T V'

`(I + L)^-1` is a unit lower-triangular solve a chunk and head, in float32
(forward substitution: the recurrence's own order of subtractions); it and
everything else that does not read `S` is computed for all m chunks at
once, and only the last three lines run chunk after chunk, the float32
state handed on inside the program (an unrolled scan, as
`models.mamba2_moe._mamba2`'s and for its reason).  Products take operands
in the compute dtype and accumulate in float32; decays, the triangular
system and the state are float32.

A position with beta = 0 and g = 0 (the caller's padded tail, an idle lane)
writes nothing and decays nothing: `S` passes it unchanged to the bit.

`causal_conv` is the short depth-wise convolution in front of the rule,
over the rows a slot kept and the launch's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES, _SUBLANES = 128, 8


def causal_conv(rows, x, w, n_valid):
    """Depth-wise causal convolution of width J = w.shape[0] over x
    (S, K, c), continued from `rows` (S, J - 1, c), the inputs of the J - 1
    positions before x[:, 0]: out[t] = sum_j w[j] cat[t + j] in float32,
    cat = rows ++ x.  Returns (out (S, K, c) float32, the rows to keep: the
    inputs of the last J - 1 of each lane's `n_valid` (S,) positions)."""
    width, k_w = w.shape[0], x.shape[1]
    cat = jnp.concatenate([rows.astype(x.dtype), x], axis=1)
    out = sum(w[j].astype(F32) * cat[:, j:j + k_w].astype(F32)
              for j in range(width))
    keep = jax.vmap(lambda c, n: jax.lax.dynamic_slice_in_dim(
        c, n, width - 1, axis=0))(cat, n_valid)
    return out, keep.astype(rows.dtype)


def gated_delta_step(q, k, v, g, beta, state):
    """One position a lane.  q, k (S, H, d_k), v (S, H, d_v), g, beta
    (S, H), state (S, H, d_k, d_v); float32 throughout.  Returns (o
    (S, H, d_v), the state after the position)."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    state = jnp.exp(g.astype(F32))[..., None, None] * state.astype(F32)
    answered = jnp.einsum("shkv,shk->shv", state, k)
    delta = beta.astype(F32)[..., None] * (v - answered)
    state = state + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("shkv,shk->shv", state, q), state


# Value heads a grid step of `_step_kernel` takes.  Measured on a v5e at
# Qwen3-Next's widths (PR 68, call AB: six linear layers x 8 steps on the
# slots' states f32[6,9,32,128,128] as 54 rows, a burst of 8 lanes, 2.1 MB a
# lane and layer; us a layer and step, the default scoped VMEM, no margin
# asked for):
#
#                                        8 lanes live   6 live, 2 idle
#   gather, plain step, scatter (XLA)       133.3           133.4
#   the kernel, 8 heads a block (0.5 MB)     59.0            59.0
#   16 heads (1 MB)                          58.2            58.7
#   32 heads (2 MB)                          56.5            52.6
#   32 heads, idle lanes skipped             58.1            45.2
#
# A live lane's 2 x 2.1 MB pass in 7.1-7.4 us: 570-595 GB/s, what a plain
# elementwise pass over an array reaches on this chip (590), so the kernel
# is bound by its copies at every block size and the largest block has the
# fewest grid steps (of two idle lanes side by side at 32 heads a block the
# second repeats the first's block, which the pipeline does not copy
# again).  **Skipping idle lanes was built and taken out**: a skipped lane
# repeated the block of the step before it under `pl.when`, 7.4 us a layer
# and idle lane bare, but in `qwen3next-agent` (0.9-1.9 idle lanes of 8)
# `decode_step_dev_ms` read 3.15 against 3.17 and the replica's start took
# 4.4 s longer, every run, all of it tracing and lowering
# (`setup_compile_s.trace_lower` 16.9 against 12.5, the parent's 11.4; call
# D, three rounds): `setup_s` +10.7% where its bound is 10%.  AOT for a
# described v5e: the burst holds one call a linear layer of the period's
# body, `(f32[54,32,128,128], f32[8,32,128]) custom-call` with the state
# aliased, between bitcasts of f32[6,9,32,128,128]; nothing copies, gathers
# or scatters an array of either shape (tests/test_tpu_compile.py holds it).
_STEP_HEAD_BLOCK = 32
# The state's blocks the pipeline keeps in VMEM: one coming in, one being
# computed from, one computed and one going out.
_STEP_STATE_VMEM = 8 * 2**20


def _step_head_block(hv: int, dk: int, dv: int) -> int:
    """Value heads a grid step of `_step_kernel` takes: the most whole
    sublanes of them, `_STEP_HEAD_BLOCK` at most, that divide `hv` and whose
    states fit in `_STEP_STATE_VMEM` four times; 0 where there is none."""
    fit = [hb for hb in range(_SUBLANES, _STEP_HEAD_BLOCK + 1, _SUBLANES)
           if hv % hb == 0 and 4 * hb * dk * dv * 4 <= _STEP_STATE_VMEM]
    return max(fit, default=0)


def _step_kernel_takes(states) -> bool:
    """Whether a step over state rows takes `_step_kernel` where it is
    lowered for a TPU: a float32 state whose (d_k, d_v) is whole (8, 128)
    tiles, a block of heads that fits (`_step_head_block`), on one device
    (an array split over a mesh is the plain form's)."""
    hv, dk, dv = states.shape[1:]
    return (states.dtype == F32 and dv % _LANES == 0 and dk % _SUBLANES == 0
            and _step_head_block(hv, dk, dv) > 0
            and jax.typeof(states).sharding.mesh.size <= 1)


def _step_body(rows_ref, decay_ref, beta_ref, q_ref, k_ref, v_ref, s_ref,
               s_out, o_ref, *, hb: int, hv: int):
    """A grid step of `_step_kernel`: one lane's `hb` heads, head after
    head in `gated_delta_step`'s order, a head's (d_k, d_v) state whole in
    registers.  Keys and queries come as rows and are wanted as columns (a
    state's d_k lies along the sublanes): both blocks are turned at once."""
    del rows_ref                               # the index maps read it
    base = pl.program_id(0) * hv + pl.program_id(1) * hb
    dk = q_ref.shape[-1]
    kq = jnp.concatenate(
        [k_ref[...], q_ref[...], jnp.zeros((_LANES - 2 * hb, dk), F32)],
        axis=0).T              # column j: head j's key, hb + j: its query
    for j in range(hb):
        k, q = kq[:, j:j + 1], kq[:, hb + j:hb + j + 1]
        state = decay_ref[base + j] * s_ref[j]
        answered = jnp.sum(state * k, axis=0, keepdims=True)
        delta = beta_ref[base + j] * (v_ref[j:j + 1, :] - answered)
        state = state + k * delta
        s_out[j] = state
        o_ref[j:j + 1, :] = jnp.sum(state * q, axis=0, keepdims=True)


def _step_kernel(q, k, v, g, beta, states, rows, *, head_block=None):
    """`gated_delta_step` on the rows `rows` of `states`, in place: one
    kernel whose grid is (lane, block of `head_block` value heads).  `rows`
    is prefetched and the state's index map reads it, so a grid step copies
    a lane's (head_block, d_k, d_v) block out of `states` where it lies and
    back to where it lay (`states` is the call's first result, aliased to
    its operand: rows no lane names are never touched); decay and beta are
    scalars a head, read from SMEM.  Everything is float32 on the VPU: a
    multiply and a sum over d_k for each of the two products.  An idle
    lane is computed like any other: exp(0) S + k 0 leaves its row to the
    bit, however many idle lanes name it.  It asks for no VMEM beyond the
    default: four blocks of the state, 8 MiB at 32 heads of 128 x 128."""
    s_w, hv, dk = q.shape
    dv = v.shape[-1]
    hb = head_block or _step_head_block(hv, dk, dv)

    def per_lane(width):
        return pl.BlockSpec((None, hb, width), lambda s, h, *_: (s, h, 0))

    state = pl.BlockSpec((None, hb, dk, dv),
                         lambda s, h, rows, *_: (rows[s], h, 0, 0))
    states, o = pl.pallas_call(
        functools.partial(_step_body, hb=hb, hv=hv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(s_w, hv // hb),
            in_specs=[per_lane(dk), per_lane(dk), per_lane(dv), state],
            out_specs=[state, per_lane(dv)]),
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct(v.shape, F32)],
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="gated_delta_step_rows",
    )(rows.astype(jnp.int32), jnp.exp(g.astype(F32)).reshape(-1),
      beta.astype(F32).reshape(-1), q.astype(F32), k.astype(F32),
      v.astype(F32), states)
    return o, states


def gated_delta_step_rows(q, k, v, g, beta, states, rows):
    """`gated_delta_step` where the lanes' states live: `states`
    (R, H, d_k, d_v), any array of state rows, and `rows` (S,) int32, the
    row each lane's state is.  Returns (o (S, H, d_v) float32, `states`
    with the rows `rows` replaced).  Live lanes name rows of their own;
    lanes with beta = 0 and g = 0 (idle: their `o` is nobody's to read)
    may share one, which stays as it was to the bit, as does every row no
    lane names.

    Lowered for a TPU it is one kernel (`_step_kernel`: a lane's state read
    out of `states` once and written back once) where `_step_kernel_takes`;
    everywhere else the plain form between a gather and a scatter of the
    lanes' rows: one algorithm, what differs is where the state's bytes
    are taken from."""
    def plain(q, k, v, g, beta, states, rows):
        o, state = gated_delta_step(q, k, v, g, beta, states[rows])
        return o, states.at[rows].set(state.astype(states.dtype))

    args = (q, k, v, g, beta, states, rows)
    if not _step_kernel_takes(states):
        return plain(*args)
    return jax.lax.platform_dependent(*args, tpu=_step_kernel, default=plain)


def gated_delta_chunks(q, k, v, g, beta, state, *, chunk: int, cd):
    """T = m x `chunk` positions a lane.  q, k (S, T, H, d_k), v
    (S, T, H, d_v), g, beta (S, T, H) float32, state (S, H, d_k, d_v)
    float32; `cd` the dtype of the products' operands.  T <= `chunk` is
    one chunk of T positions.  Returns (o (S, T, H, d_v) float32, the state
    after position T)."""
    s_w, t_w, h = g.shape
    c = min(chunk, t_w)
    if t_w % c:
        raise ValueError(f"a launch of {t_w} rows is not whole chunks of "
                         f"{chunk}")
    m = t_w // c

    def parts(a):                 # (S, m C, H, ..) -> (S, m, H, C, ..)
        a = a.reshape(s_w, m, c, *a.shape[2:])
        return jnp.moveaxis(a, 2, 3)

    q, k, v = parts(q.astype(cd)), parts(k.astype(cd)), parts(v.astype(F32))
    qf, kf = q.astype(F32), k.astype(F32)
    g, beta = parts(g.astype(F32)), parts(beta.astype(F32))   # (S, m, H, C)
    run = jnp.cumsum(g, axis=-1)                              # G_t <= 0
    lower = jnp.tril(jnp.ones((c, c), bool))
    # Masked before the exponential: above the diagonal the difference is
    # positive.
    decay = jnp.exp(jnp.where(lower, run[..., :, None] - run[..., None, :],
                              -jnp.inf))                      # (S,m,H,C,C)
    kk = jnp.einsum("smhtd,smhrd->smhtr", k, k, preferred_element_type=F32)
    qk = jnp.einsum("smhtd,smhrd->smhtr", q, k, preferred_element_type=F32)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    system = jnp.eye(c, dtype=F32) + jnp.where(
        strict, beta[..., None] * kk * decay, 0.0)
    rhs = jnp.concatenate([
        (beta * jnp.exp(run))[..., None] * kf,
        beta[..., None] * v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    d_k = k.shape[-1]
    w, u = solved[..., :d_k], solved[..., d_k:]
    q_in = jnp.exp(run)[..., None] * qf                       # exp(G) o Q
    last = run[..., -1]                                       # G_C (S,m,H)
    k_out = jnp.exp(last[..., None] - run)[..., None] * kf
    inside = (qk * decay).astype(cd)

    def one(s0, part):
        w, u, q_in, inside, k_out, last = part
        s0c = s0.astype(cd)
        fresh = u - jnp.einsum("shtk,shkv->shtv", w.astype(cd), s0c,
                               preferred_element_type=F32)
        o = jnp.einsum("shtk,shkv->shtv", q_in.astype(cd), s0c,
                       preferred_element_type=F32) \
            + jnp.einsum("shtr,shrv->shtv", inside, fresh.astype(cd),
                         preferred_element_type=F32)
        s1 = jnp.exp(last)[..., None, None] * s0 + jnp.einsum(
            "shtk,shtv->shkv", k_out.astype(cd), fresh.astype(cd),
            preferred_element_type=F32)
        return s1, o

    state, o = jax.lax.scan(
        one, state.astype(F32),
        tuple(jnp.moveaxis(a, 1, 0)
              for a in (w, u, q_in, inside, k_out, last)),
        unroll=True)
    # (m, S, H, C, d_v) -> (S, m C, H, d_v)
    o = jnp.moveaxis(o, 0, 1)
    o = jnp.moveaxis(o, 2, 3).reshape(s_w, t_w, h, o.shape[-1])
    return o, state
