"""Ring attention: exact attention over sequence shards via ICI neighbor exchange.

Context parallelism for long sequences (Liu et al. ring attention /
blockwise attention).  The sequence axis is sharded over a mesh axis; each
device holds a local q/k/v shard and, over `n` ring steps, rotates the k/v
shard to its ICI neighbor with `lax.ppermute` while merging blockwise
online-softmax partial results.  XLA overlaps the permute with the attention
compute of the previous block (async collective-permute).

The reference framework has no sequence/context parallelism at all
(SURVEY.md §2.4 — verified absent); this is greenfield TPU design.

`ring_attention` is written against per-device local shards and must run
inside `shard_map` (or pmap); `make_ring_attention` wraps it for use inside a
pjit/global-view program.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.mesh import AXIS_SEQ, mesh_axis_sizes

_NEG_INF = -1e30


def _block_attn(q, k, v, q_off, kv_off, *, causal, sm_scale):
    """Unnormalized blockwise attention with global-position causal mask.

    q: (B, Tq, H, D) local; k/v: (B, Tk, H, D) currently-held shard.
    Returns (m, l, acc): rowwise max (B,Tq,H,1), sum of exp (B,Tq,H,1),
    unnormalized weighted values (B,Tq,H,D), all float32.
    """
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_pos = q_off + jnp.arange(tq)[:, None]
        k_pos = kv_off + jnp.arange(tk)[None, :]
        mask = (q_pos >= k_pos)[None, :, None, :]
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, _NEG_INF)  # keep finite for fully-masked rows
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return m, l, acc


def ring_attention(q, k, v, *, axis: str = AXIS_SEQ, causal: bool = True,
                   sm_scale: float | None = None):
    """Exact attention over a sequence-sharded axis.  Call inside shard_map.

    q, k, v: local shards (B, T_local, H, D).  Global sequence length is
    T_local * axis_size(axis); device i owns positions [i*T_local, (i+1)*T_local).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    t_local = q.shape[1]
    qf = q.astype(jnp.float32)

    def step(carry, t):
        m, l, acc, kc, vc = carry
        # After t forward rotations, device i holds kv shard (i - t) mod n.
        j = (i - t) % n
        # Rotate kv to the next device first so XLA overlaps permute+compute.
        perm = [(src, (src + 1) % n) for src in range(n)]
        k_next = lax.ppermute(kc, axis, perm)
        v_next = lax.ppermute(vc, axis, perm)
        bm, bl, bacc = _block_attn(qf, kc, vc, i * t_local, j * t_local,
                                   causal=causal, sm_scale=sm_scale)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(bm - m_new)
        l_new = alpha * l + beta * bl
        acc_new = alpha * acc + beta * bacc
        return (m_new, l_new, acc_new, k_next, v_next), None

    b, _, h, d = q.shape
    m0 = jnp.full((b, t_local, h, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, t_local, h, 1), jnp.float32)
    acc0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, acc0, k, v), jnp.arange(n))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def make_sharded_attention(local_fn, mesh: Mesh, *,
                           axis: str | None = AXIS_SEQ,
                           batch_axes: Sequence[str] = ("dp", "fsdp"),
                           head_axis: str | None = "tp"):
    """Shared shard_map wrapper for attention under a mesh — the
    context-parallel schemes (`ring_attention`, `ulysses_attention`) and
    the plain flash kernel, which XLA cannot partition on its own: one
    place owns the layout contract so they cannot drift apart.

    Layout: (B, T, H, D) with B over `batch_axes`, T over `axis`, H over
    `head_axis`.  Only axes present in `mesh` are used.  `local_fn`
    takes per-device (q, k, v) shards.  `axis=None` keeps T whole on
    every device: required for a `local_fn` that does not exchange
    blocks over the axis itself (the plain flash kernel).  K/V may come
    at fewer heads than q (GQA): where their count does not divide over
    `head_axis` they are repeated just enough that it does, so a device
    holds the KV heads of its own query heads.
    """
    sizes = mesh_axis_sizes(mesh)
    bspec = tuple(a for a in batch_axes if a in sizes) or None
    hspec = head_axis if head_axis in sizes else None
    data_shards = math.prod(sizes[a] for a in bspec or ())

    def sharded(q, k, v):
        b = bspec
        if q.shape[0] % data_shards:
            # Same outcome as a dropped sharding constraint: every device
            # of the data axes computes the whole batch.
            warnings.warn(
                f"attention batch {q.shape[0]} does not divide over mesh "
                f"axes {bspec} (x{data_shards}): computed unsharded on "
                f"each device", stacklevel=2)
            b = None
        hkv = math.lcm(k.shape[2], sizes[hspec]) if hspec else k.shape[2]
        if hkv != k.shape[2] and q.shape[2] % hkv == 0:
            k = jnp.repeat(k, hkv // k.shape[2], axis=2)
            v = jnp.repeat(v, hkv // v.shape[2], axis=2)
        spec = P(b, axis if axis in sizes else None, hspec, None)
        return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)

    return sharded


def make_ring_attention(mesh: Mesh, *, axis: str = AXIS_SEQ, causal: bool = True,
                        sm_scale: float | None = None,
                        batch_axes: Sequence[str] = ("dp", "fsdp"),
                        head_axis: str | None = "tp"):
    """Wrap `ring_attention` in shard_map for use inside a pjit program."""
    fn = functools.partial(ring_attention, axis=axis, causal=causal,
                           sm_scale=sm_scale)
    return make_sharded_attention(fn, mesh, axis=axis,
                                  batch_axes=batch_axes,
                                  head_axis=head_axis)
