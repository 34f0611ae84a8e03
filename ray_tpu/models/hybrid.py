"""Decoder-hybrid-decoder language model (SambaY, arXiv:2507.06607) on
the served path: layers that differ, and a sequence whose state is more
than a block table.

The stack, for `n_layers = n` (0-based layer `l`), in three runs of
paired layers.  Every layer is `x += mixer(LN(x)); x += MLP(LN'(x))`,
LayerNorm with gain and bias, a fused gate/up SwiGLU, tied embeddings,
no positional encoding (the recurrence carries order):

    n/4 pairs      (Mamba, window differential attention)   l = 0 .. n/2-1
    1 pair         (Mamba, full differential attention)     l = n/2, n/2+1
    n/4 - 1 pairs  (gated memory unit, cross-attention)     l = n/2+2 ..

The last Mamba hands on its un-gated scan output `m`; every gated memory
unit reads `m` of the same positions, and every cross-attention layer
reads the full layer's K and V (YOCO's one KV cache, arXiv:2405.05254).
Attention is differential (arXiv:2410.05258): two soft-max maps over the
same value, subtracted.  Mamba is the selective scan of arXiv:2312.00752.

What a sequence keeps (`HybridState`), and who indexes it:

    k, v     (1, N, bs, Hkv * D)   the full layer's KV, paged: the
                                   engine's block tables, as for every
                                   other model; a position's heads flat
                                   in one row (ops.attention says why)
    wk, wv   (n/4, S+1, R, Hkv * D)  the window layers' KV, a ring a
                                   slot: position p lives at row p % R,
                                   R = window + prefill_chunk
    conv     (n/4+1, S+1, d_conv-1, d_in)   Mamba's last conv inputs
    h        (n/4+1, S+1, d_state, d_in)    and its state, float32

Ring and recurrent state are indexed by the engine's slot (`slots`, one
per lane); row S is the null slot, where idle lanes point, as block 0 is
the null block.  A position that is not valid (the zero-padded tail of a
prefill chunk, an idle lane of a burst) writes no ring row and leaves
conv rows and `h` exactly as they were: a recurrence, unlike KV, cannot
be overwritten by the next chunk.

`HybridConfig` carries the served step as methods (`init_params`,
`served_step`, `final_logits`, `init_state`, `reset_slot`,
`kv_read_tokens`), which is
how `models.decoding` and `serve.llm.PagedLLMEngine` tell a model that
brings its own sequence state from `TransformerConfig`, whose state is
the paged pool alone.  Training this model is not here (the backward of
the scan is a later PR).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (
    paged_diff_attention, ring_rows, slot_ring_reader, window_diff_attention)
from ray_tpu.ops.norms import layer_norm, rms_norm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    d_ff: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0            # 0: ceil(d_model / 16)
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    name: str = "hybrid"

    # A sequence of this model keeps state by the engine's slot (rings and
    # recurrent state: the engine turns prefix sharing off and refuses KV
    # shipping and a mesh), and some of it is recurrent (zeroed when a
    # slot changes hands; speculation refused).
    state_by_slot: ClassVar[bool] = True
    recurrent: ClassVar[bool] = True

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers must be a multiple of 4, at least 8: "
                             "n/4 window pairs, one full pair, n/4 - 1 "
                             "cross pairs")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2 \
                or self.n_heads % 2:
            raise ValueError("differential attention pairs adjacent heads: "
                             "n_heads and n_kv_heads even, one a multiple "
                             "of the other")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def rank(self) -> int:
        return self.dt_rank or math.ceil(self.d_model / 16)

    @property
    def n_window(self) -> int:          # pairs (Mamba, window attention)
        return self.n_layers // 4

    @property
    def n_cross(self) -> int:           # pairs (GMU, cross-attention)
        return self.n_layers // 4 - 1

    @property
    def n_mamba(self) -> int:
        return self.n_window + 1

    @property
    def num_params(self) -> int:
        d, f, di = self.d_model, self.d_ff, self.d_inner
        kv = self.n_kv_heads * self.head_dim
        mamba = d * 2 * di + di * (self.rank + 2 * self.d_state) \
            + self.rank * di + di * d
        return (self.vocab_size * d + self.n_layers * 3 * d * f
                + self.n_mamba * (mamba + 2 * d * d + 2 * d * kv)
                + self.n_cross * (2 * d * di + 2 * d * d))

    # -- the sequence state ---------------------------------------------
    def ring_len(self, prefill_chunk: int) -> int:
        """Rows of a slot's ring: a chunk of C positions is written
        before it is read, and its first query still sees the `window`
        positions up to its own."""
        return self.window + prefill_chunk

    def init_state(self, num_blocks: int, block_size: int, num_slots: int,
                   prefill_chunk: int) -> "HybridState":
        dtype = self.compute_dtype
        kv = self.n_kv_heads * self.head_dim
        pool = (1, num_blocks, block_size, kv)
        ring = (self.n_window, num_slots + 1, self.ring_len(prefill_chunk),
                kv)
        rows = (self.n_mamba, num_slots + 1)
        return HybridState(
            k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
            wk=jnp.zeros(ring, dtype), wv=jnp.zeros(ring, dtype),
            conv=jnp.zeros(rows + (self.d_conv - 1, self.d_inner), dtype),
            h=jnp.zeros(rows + (self.d_state, self.d_inner),
                        self.state_dtype))

    @staticmethod
    def reset_slot(state: "HybridState", slot) -> "HybridState":
        """Zero one slot's recurrent state (a request is admitted to it,
        or a preempted stream will re-prefill).  The ring needs none: a
        row is seen only at the position it was last written for."""
        return dataclasses.replace(
            state, conv=state.conv.at[:, slot].set(0),
            h=state.h.at[:, slot].set(0))

    def kv_read_tokens(self, lengths) -> int:
        """KV positions one decode step sees, over lanes of `lengths`
        and the layers that read: the full layer's KV by itself and the
        cross layers, each window layer's window."""
        return int((1 + self.n_cross) * sum(lengths) + self.n_window
                   * sum(min(int(n), self.window) for n in lengths))

    def init_params(self, rng: jax.Array):
        return init_params(rng, self)

    def lambda_init(self, layer):
        return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))

    # -- the served step --------------------------------------------------
    def final_logits(self, params, x):
        x = layer_norm(x, params["final_norm_g"], params["final_norm_b"],
                       eps=self.norm_eps)
        return jnp.einsum("btd,vd->btv", x,
                          params["embed"].astype(self.compute_dtype))

    def served_step(self, params, state: "HybridState", tokens, block_tables,
                    positions, kv_len, slots):
        return _served_step(params, state, tokens, block_tables, positions,
                            kv_len, slots, self)


@dataclasses.dataclass
class HybridState:
    k: jax.Array          # (1, N_blocks, block_size, Hkv * D)
    v: jax.Array
    wk: jax.Array         # (n_window, S+1, R, Hkv * D)
    wv: jax.Array
    conv: jax.Array       # (n_mamba, S+1, d_conv-1, d_in)
    h: jax.Array          # (n_mamba, S+1, d_state, d_in) float32

    def resident_bytes(self) -> dict:
        def nbytes(*arrays):
            return int(sum(a.size * a.dtype.itemsize for a in arrays))

        return {"kv_paged": nbytes(self.k, self.v),
                "kv_window": nbytes(self.wk, self.wv),
                "recurrent": nbytes(self.conv, self.h)}


jax.tree_util.register_dataclass(
    HybridState, ["k", "v", "wk", "wv", "conv", "h"], [])


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: HybridConfig):
    """Seeded parameters, the layers of a run stacked on a leading axis:
    `win` (n/4 pairs), `full` (1 pair), `cross` (n/4 - 1 pairs), each
    with a mixer and an MLP for either layer of the pair.  Norm gains and
    biases, the conv bias and the lambda vectors are drawn away from
    their neutral values, so that a comparison notices when one is left
    out.  `A_log`, `D` and `dt_b` are float32 whatever `param_dtype` is,
    as Mamba keeps them."""
    d, f, di = cfg.d_model, cfg.d_ff, cfg.d_inner
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ds, dc, rank = cfg.d_state, cfg.d_conv, cfg.rank
    dt = cfg.param_dtype
    count = iter(range(1 << 20))

    def draw(shape, scale, dtype=dt, shift=0.0):
        key = jax.random.fold_in(rng, next(count))
        return (shift + scale * jax.random.normal(key, shape, F32)) \
            .astype(dtype)

    def norm(n):
        return {"norm_g": draw((n, d), 0.1, shift=1.0),
                "norm_b": draw((n, d), 0.1)}

    def mlp(n):
        return {**norm(n), "w_gate_up": draw((n, d, 2 * f), d ** -0.5),
                "w_down": draw((n, f, d), f ** -0.5)}

    def mamba(n):
        key = jax.random.fold_in(rng, next(count))
        step = jnp.exp(jax.random.uniform(
            key, (n, di), F32, math.log(1e-3), math.log(1e-1)))
        return {**norm(n),
                "in_proj": draw((n, d, 2 * di), d ** -0.5),
                "conv_w": draw((n, dc, di), dc ** -0.5),
                "conv_b": draw((n, di), 0.1),
                "x_proj": draw((n, di, rank + 2 * ds), di ** -0.5),
                "dt_w": draw((n, rank, di), rank ** -0.5),
                "dt_b": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, ds + 1, dtype=F32))[None, :, None], (n, ds, di)),
                "D": jnp.ones((n, di), F32),
                "out_proj": draw((n, di, d), di ** -0.5)}

    def attn(n, own_kv=True):
        out = {**norm(n), "wq": draw((n, d, nh * hd), d ** -0.5),
               "wo": draw((n, nh * hd, d), (nh * hd) ** -0.5),
               "lambda_q1": draw((n, hd), 0.1), "lambda_k1": draw((n, hd), 0.1),
               "lambda_q2": draw((n, hd), 0.1), "lambda_k2": draw((n, hd), 0.1),
               "subln": draw((n, 2 * hd), 0.1, shift=1.0)}
        if own_kv:
            out["wk"] = draw((n, d, nkv * hd), d ** -0.5)
            out["wv"] = draw((n, d, nkv * hd), d ** -0.5)
        return out

    def gmu(n):
        return {**norm(n), "w1": draw((n, d, di), d ** -0.5),
                "w2": draw((n, di, d), di ** -0.5)}

    def pairs(n, first, second):
        return {"mixer_a": first(n), "mlp_a": mlp(n),
                "mixer_b": second(n), "mlp_b": mlp(n)}

    return {
        "embed": draw((cfg.vocab_size, d), d ** -0.5),
        "win": pairs(cfg.n_window, mamba, attn),
        "full": pairs(1, mamba, attn),
        "cross": pairs(cfg.n_cross, gmu, lambda n: attn(n, own_kv=False)),
        "final_norm_g": draw((d,), 0.1, shift=1.0),
        "final_norm_b": draw((d,), 0.1),
    }


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def _ln(bp, x, cfg):
    return layer_norm(x, bp["norm_g"], bp["norm_b"], eps=cfg.norm_eps)


def _mlp(bp, x, cfg):
    cd = cfg.compute_dtype
    with jax.named_scope("mlp"):
        gu = jnp.einsum("skd,df->skf", _ln(bp, x, cfg),
                        bp["w_gate_up"].astype(cd))
        gate, up = jnp.split(gu, 2, axis=-1)
        return jnp.einsum("skf,fd->skd", jax.nn.silu(gate) * up,
                          bp["w_down"].astype(cd))


def _mamba(bp, x, conv_s, h_s, valid, cfg):
    """The selective scan over the K positions of each lane, from the
    lane's state: conv_s (S, d_conv-1, d_in), h_s (S, d_state, d_in)
    float32.  Returns (the mixer's output (S, K, d), the un-gated scan
    output (S, K, d_in), conv_s', h_s').  A position that is not `valid`
    (S, K; the valid ones are a prefix) takes delta = 0, so `h` passes
    it unchanged to the bit, and the conv rows kept are those of the
    last valid positions."""
    cd = cfg.compute_dtype
    di, ds = cfg.d_inner, cfg.d_state
    k_w = x.shape[1]
    az = jnp.einsum("skd,de->ske", _ln(bp, x, cfg), bp["in_proj"].astype(cd))
    a, z = az[..., :di], az[..., di:]
    cat = jnp.concatenate([conv_s.astype(cd), a], axis=1)  # (S, K+dc-1, di)
    conv = bp["conv_b"].astype(F32) + sum(
        bp["conv_w"][j].astype(F32) * cat[:, j:j + k_w].astype(F32)
        for j in range(cfg.d_conv))
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)     # (S,)
    conv_s = jax.vmap(lambda c, n: jax.lax.dynamic_slice_in_dim(
        c, n, cfg.d_conv - 1, axis=0))(cat, n_valid).astype(conv_s.dtype)
    a = jax.nn.silu(conv).astype(cd)                       # (S, K, di)
    proj = jnp.einsum("ske,er->skr", a, bp["x_proj"].astype(cd),
                      preferred_element_type=F32)
    r = proj[..., :cfg.rank].astype(cd)
    b_in = proj[..., cfg.rank:cfg.rank + ds]               # (S, K, ds)
    c_out = proj[..., cfg.rank + ds:]
    delta = jax.nn.softplus(jnp.einsum(
        "skr,re->ske", r, bp["dt_w"].astype(cd),
        preferred_element_type=F32) + bp["dt_b"].astype(F32))
    delta = jnp.where(valid[..., None], delta, 0.0)        # (S, K, di)
    a_neg = -jnp.exp(bp["A_log"].astype(F32))              # (ds, di)
    a32 = a.astype(F32)

    def step(h, at):
        dlt, xt, bt, ct = at                # (S,di) (S,di) (S,ds) (S,ds)
        h = jnp.exp(dlt[:, None, :] * a_neg) * h \
            + (dlt * xt)[:, None, :] * bt[:, :, None]
        return h, jnp.sum(h * ct[:, :, None], axis=1)

    h_s = h_s.astype(F32)
    if k_w == 1:
        h_s, y = step(h_s, (delta[:, 0], a32[:, 0], b_in[:, 0], c_out[:, 0]))
        y = y[:, None]
    else:
        h_s, y = jax.lax.scan(
            step, h_s, tuple(jnp.swapaxes(t, 0, 1)
                             for t in (delta, a32, b_in, c_out)),
            unroll=8)
        y = jnp.swapaxes(y, 0, 1)
    y = y + bp["D"].astype(F32) * a32                      # (S, K, di)
    out = jnp.einsum("ske,ed->skd",
                     (y * jax.nn.silu(z.astype(F32))).astype(cd),
                     bp["out_proj"].astype(cd))
    return out, y.astype(cd), conv_s, h_s


def _gmu(bp, x, m, cfg):
    cd = cfg.compute_dtype
    gate = jnp.einsum("skd,de->ske", _ln(bp, x, cfg), bp["w1"].astype(cd))
    return jnp.einsum("ske,ed->skd", m * jax.nn.silu(gate),
                      bp["w2"].astype(cd))


def _queries(bp, h, cfg):
    """(S, K, G, rep, 2, D): query head 2j + c is map c of differential
    head j, and differential head j = g * rep + r reads KV group g."""
    s, k_w = h.shape[:2]
    g = cfg.n_kv_heads // 2
    q = jnp.einsum("skd,dh->skh", h, bp["wq"].astype(cfg.compute_dtype))
    return q.reshape(s, k_w, g, cfg.n_heads // cfg.n_kv_heads, 2,
                     cfg.head_dim)


def _keys_values(bp, h, cfg):
    """A position's K and V as they are stored: the heads flat."""
    cd = cfg.compute_dtype
    return (jnp.einsum("skd,dh->skh", h, bp["wk"].astype(cd)),
            jnp.einsum("skd,dh->skh", h, bp["wv"].astype(cd)))


def _diff_out(bp, maps, layer, cfg):
    """maps (S, K, G, rep, 2, 2D) float32, the two soft-max maps applied
    to the group's value: subtract, normalise each head, project."""
    lam0 = cfg.lambda_init(layer)
    lam = jnp.exp(jnp.sum(bp["lambda_q1"].astype(F32)
                          * bp["lambda_k1"].astype(F32))) \
        - jnp.exp(jnp.sum(bp["lambda_q2"].astype(F32)
                          * bp["lambda_k2"].astype(F32))) + lam0
    o = maps[..., 0, :] - lam * maps[..., 1, :]            # (S,K,G,rep,2D)
    o = rms_norm(o, bp["subln"], eps=cfg.norm_eps) * (1.0 - lam0)
    o = o.reshape(*o.shape[:2], cfg.n_heads * cfg.head_dim)
    return jnp.einsum("skh,hd->skd", o.astype(cfg.compute_dtype),
                      bp["wo"].astype(cfg.compute_dtype))


def _take(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _served_step(params, state: HybridState, tokens, block_tables, positions,
                 kv_len, slots, cfg: HybridConfig):
    """`tokens` (S, K) at absolute `positions` (S, K) through every
    layer; `kv_len` (S,) is each lane's length once its valid tokens are
    in (0: an idle lane) and `slots` (S,) the engine slot whose ring and
    recurrent state the lane reads and writes (the null slot for an idle
    lane).  Returns (state, hidden (S, K, d)).  Write-then-read, as the
    paged step: a layer puts the tokens' K and V in place first and the
    attention that follows finds them there."""
    cd = cfg.compute_dtype
    bs = state.k.shape[2]
    ring = state.wk.shape[2]
    valid = positions < kv_len[:, None]                    # (S, K)
    live = (kv_len > 0)[:, None]
    wb = jnp.where(live, jnp.take_along_axis(
        block_tables, positions // bs, axis=1), 0)
    off = jnp.where(live, positions % bs, 0)
    row = ring_rows(positions, kv_len, ring)
    lane = slots[:, None]
    x = params["embed"].astype(cd)[tokens]
    n_half = cfg.n_layers // 2
    # A window layer reads its lanes' rings, or every slot's where they lie
    # (ops.attention.slot_ring_reader).
    ring_attention = slot_ring_reader(
        window_diff_attention, slots, positions, kv_len, cfg.window,
        state.wk.shape[1])

    def mamba_layer(x, bp, li, conv, h):
        with jax.named_scope("mamba"):
            out, m, conv_s, h_s = _mamba(bp, x, conv[li, slots],
                                         h[li, slots], valid, cfg)
            conv = conv.at[li, slots].set(conv_s)
            h = h.at[li, slots].set(h_s.astype(h.dtype))
        return x + out, m, conv, h

    def window_pair(carry, layer_in):
        x, conv, h, wk, wv = carry
        bp, li = layer_in
        x, _, conv, h = mamba_layer(x, bp["mixer_a"], li, conv, h)
        x = x + _mlp(bp["mlp_a"], x, cfg)
        with jax.named_scope("swa"):
            ap = bp["mixer_b"]
            hn = _ln(ap, x, cfg)
            k, v = _keys_values(ap, hn, cfg)
            wk = wk.at[li, lane, row].set(k.astype(wk.dtype), mode="drop")
            wv = wv.at[li, lane, row].set(v.astype(wv.dtype), mode="drop")
            maps = ring_attention(_queries(ap, hn, cfg), wk, wv, li)
            x = x + _diff_out(ap, maps, 2 * li + 1, cfg)
        x = x + _mlp(bp["mlp_b"], x, cfg)
        return (x, conv, h, wk, wv), None

    (x, conv, h, wk, wv), _ = jax.lax.scan(
        window_pair, (x, state.conv, state.h, state.wk, state.wv),
        (params["win"], jnp.arange(cfg.n_window)))

    bp = _take(params["full"], 0)
    x, m, conv, h = mamba_layer(x, bp["mixer_a"], cfg.n_window, conv, h)
    x = x + _mlp(bp["mlp_a"], x, cfg)
    with jax.named_scope("full_attn"):
        ap = bp["mixer_b"]
        hn = _ln(ap, x, cfg)
        k, v = _keys_values(ap, hn, cfg)
        k_pool = state.k.at[0, wb, off].set(k.astype(state.k.dtype))
        v_pool = state.v.at[0, wb, off].set(v.astype(state.v.dtype))
        maps = paged_diff_attention(_queries(ap, hn, cfg), k_pool, v_pool,
                                    0, block_tables, positions, kv_len)
        x = x + _diff_out(ap, maps, n_half + 1, cfg)
    x = x + _mlp(bp["mlp_b"], x, cfg)

    def cross_pair(x, layer_in):
        bp, j = layer_in
        with jax.named_scope("gmu"):
            x = x + _gmu(bp["mixer_a"], x, m, cfg)
        x = x + _mlp(bp["mlp_a"], x, cfg)
        with jax.named_scope("cross_attn"):
            ap = bp["mixer_b"]
            maps = paged_diff_attention(
                _queries(ap, _ln(ap, x, cfg), cfg), k_pool, v_pool, 0,
                block_tables, positions, kv_len)
            x = x + _diff_out(ap, maps, n_half + 3 + 2 * j, cfg)
        x = x + _mlp(bp["mlp_b"], x, cfg)
        return x, None

    x, _ = jax.lax.scan(cross_pair, x,
                        (params["cross"], jnp.arange(cfg.n_cross)))
    return HybridState(k=k_pool, v=v_pool, wk=wk, wv=wv, conv=conv, h=h), x
