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

`gated_delta_step` is that, one position a lane (a decode step: the state
read and written once).  `gated_delta_chunks` is the same recurrence over
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

import jax
import jax.numpy as jnp

F32 = jnp.float32


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
