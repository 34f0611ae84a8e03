"""A state-space / attention hybrid with routed experts on the served
path: Mamba-2 mixers (arXiv:2405.21060) whose prefill chunk is one chunk
of the state-space-duality form, a few attention layers without
positional encoding over the paged pool, and in every layer one rank's
share of an expert-parallel FFN beside a shared expert (the layout of
the `granitemoehybrid` config family).  `models.hybrid` is its sibling
under the same protocol; what differs is below.

The stack, for `n_layers` layers whose kinds repeat with the period
`layer_pattern` ("mamba" | "attention"):

    x = embedding_multiplier * E[token]
    x += residual_multiplier * mixer_l(RMSNorm(x))
    h  = RMSNorm'(x)
    x += residual_multiplier * (experts_l(h) + shared_l(h))
    logits = RMSNorm_f(x) E^T / logits_scaling          (E tied)

Mamba-2 mixer, H heads of P channels, one group of state size N, conv
width `d_conv` with bias: `[z | xBC | dt] = u W_in` (widths HP | HP + 2N
| H); `xBC = silu(conv1d_causal(xBC))` split into x (H, P), B (N), C (N)
shared by the heads; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)` a
scalar a head; state `S` (H, P, N) float32:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z)) W_out                (the gate, then the
                                                     norm over all HP)

A decode step is that recurrence for one position on the lanes' state.
A chunk of Q positions from the slot's incoming state S_0 is the same
recurrence as three matrix products a head and no loop over positions
(`_ssd_chunk`): with s_t = sum_{r<=t} dt_r A and L[t, r] = exp(s_t - s_r)
for r <= t, else 0,

    Y   = (L o (C B^T)) (dt o X) + exp(s) o (C S_0^T)
    S_Q = exp(s_Q) S_0 + sum_r exp(s_Q - s_r) dt_r x_r B_r^T

decay terms and the state in float32, the products in the compute dtype
with float32 accumulation.  A prefill launch of up to Q = the engine's
`prefill_chunk` rows is one such chunk; a wider one, of m x Q rows, is m
of them, the float32 state handed from one to the next inside the
program (`_mamba2`: one `lax.scan`, so `_ssd_chunk` is traced once, and
unrolled where it is lowered: as a loop the carried state took a layout
of its own and the slots' whole state array was copied into it, 0.69 GB
of temporaries a 512-row launch where the unrolled program holds 0.05;
AOT for a v5e, PR 56), while everything that keeps no
state between positions (projections, convolution, attention, experts)
runs over all the rows at once: a launch streams the weights once
whatever its rows.  Nothing a sequence keeps is laid out by a launch's
rows, which the class states (`launch_spans_chunks`) and the engine
asks.  A position that is not valid (the padded tail of a launch,
wherever it begins, or an idle lane) takes dt = 0: it decays nothing,
adds nothing, and the state passes it unchanged to the bit, as in
`models.hybrid._mamba`.

Attention: grouped-query heads over the engine's paged pool
(`ops.attention.paged_attention`), no positional encoding, the soft-max
scale `attention_scale` handed to the op as its `scale` (it is 1 / D
here, not D ** -0.5, and folding it into bfloat16 queries would round
them once more).

Experts: `ops.moe.moe_mlp_dropless` with `MoEConfig.held`: the router is
`n_experts` wide and takes `expert_top_k`; the stacks hold the
`experts_held` range alone and the layer computes its own part of the
sum.  The shared expert is a dense SwiGLU that every token takes, added
to the routed sum here, once, whatever the share.

What a sequence keeps (`Mamba2MoEState`):

    k, v   (n_attention, N_blocks, block_size, Hkv, D)  paged, as ever
    conv   (n_mamba, S+1, d_conv-1, HP + 2N)   the last conv inputs
    h      (n_mamba, S+1, P, H, N) float32     the state, by engine slot

Row S is the null slot; the state also names, as a static field, the
positions of one chunk (`chunk`: the engine's `prefill_chunk`), which
sizes none of the arrays.  A slot's state is stored channels first, (P, H,
N): with P = 64, half a lane tile, as the minor dimension of x the
compiler lays a chunk's x out as (P, H) and the state it updates with
it; stored as (H, P, N) every slot's state was copied into that layout
and back a chunk (0.65 GB each way at 16 slots), and stored as (H * P,
N) the burst's gather of the lanes' state split the whole of it in four
a layer and step (both AOT for a v5e, PR 36).  The configuration carries the served step as
methods, as `HybridConfig` does; it also has `n_experts`, so its
`served_step` takes `routing` and returns the experts visited, the
experts taken if asked and the top-k choices that fell on held experts
(`models.decoding._served_forward`).  Training is not here.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.moe import MoEConfig, moe_mlp_dropless, routed_zero
from ray_tpu.ops.norms import rms_norm

F32 = jnp.float32
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Mamba2MoEConfig:
    vocab_size: int = 100352
    d_model: int = 4096
    n_layers: int = 40
    layer_pattern: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    d_head: int = 128
    attention_scale: float = 1.0 / 128
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    n_experts: int = 72
    expert_top_k: int = 10
    d_expert: int = 768
    d_shared: int = 1536
    # (first, count) of the n_experts held here; None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    name: str = "mamba2-moe"

    # Recurrent state by the engine's slot: the engine turns prefix
    # sharing off, refuses speculation, KV shipping and a mesh, and
    # zeroes a slot's state when it changes hands.
    state_by_slot: ClassVar[bool] = True
    recurrent: ClassVar[bool] = True
    # Nothing of that state is laid out by a launch's rows (no ring of
    # window + prefill_chunk positions): a launch of m x prefill_chunk
    # rows is m chunks, the state carried between them in the program.
    launch_spans_chunks: ClassVar[bool] = True

    def __post_init__(self):
        if set(self.layer_pattern) - {"mamba", "attention"} \
                or self.n_layers % len(self.layer_pattern):
            raise ValueError("layer_pattern names 'mamba' and 'attention' "
                             "layers, and n_layers is whole periods of it")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        self.moe                        # MoEConfig checks the held range

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_xbc(self) -> int:             # x, B and C side by side
        return self.d_inner + 2 * self.d_state

    @property
    def periods(self) -> int:
        return self.n_layers // len(self.layer_pattern)

    def n_of(self, kind: str) -> int:
        return self.periods * self.layer_pattern.count(kind)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(num_experts=self.n_experts, top_k=self.expert_top_k,
                         held=self.experts_held)

    @property
    def num_params(self) -> int:
        d, di = self.d_model, self.d_inner
        kv = self.n_kv_heads * self.d_head
        q = self.n_heads * self.d_head
        mamba = d * (di + self.d_xbc + self.ssm_heads) + di * d
        ffn = d * self.n_experts + 3 * d * self.d_shared \
            + self.held[1] * 3 * d * self.d_expert
        return (self.vocab_size * d + self.n_layers * ffn
                + self.n_of("mamba") * mamba
                + self.n_of("attention") * (2 * d * q + 2 * d * kv))

    # -- the sequence state ---------------------------------------------
    def init_state(self, num_blocks: int, block_size: int, num_slots: int,
                   prefill_chunk: int) -> "Mamba2MoEState":
        dtype = self.compute_dtype
        pool = (self.n_of("attention"), num_blocks, block_size,
                self.n_kv_heads, self.d_head)
        rows = (self.n_of("mamba"), num_slots + 1)
        return Mamba2MoEState(
            k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
            conv=jnp.zeros(rows + (self.d_conv - 1, self.d_xbc), dtype),
            h=jnp.zeros(rows + (self.ssm_head_dim, self.ssm_heads,
                                self.d_state), self.state_dtype),
            chunk=prefill_chunk)

    @staticmethod
    def reset_slot(state: "Mamba2MoEState", slot) -> "Mamba2MoEState":
        """Zero one slot's recurrent state (a request is admitted to it,
        or a preempted stream will re-prefill)."""
        return dataclasses.replace(
            state, conv=state.conv.at[:, slot].set(0),
            h=state.h.at[:, slot].set(0))

    def kv_read_tokens(self, lengths) -> int:
        """KV positions one decode step sees, over lanes of `lengths`:
        the attention layers alone keep any."""
        return int(self.n_of("attention") * sum(lengths))

    def init_params(self, rng: jax.Array):
        return init_params(rng, self)

    # -- the served step --------------------------------------------------
    def final_logits(self, params, x):
        x = rms_norm(x, params["final_norm"], eps=self.norm_eps)
        logits = jnp.einsum("btd,vd->btv", x,
                            params["embed"].astype(self.compute_dtype))
        return logits / self.logits_scaling

    def served_step(self, params, state: "Mamba2MoEState", tokens,
                    block_tables, positions, kv_len, slots,
                    routing: bool = False):
        return _served_step(params, state, tokens, block_tables, positions,
                            kv_len, slots, self, routing)


@dataclasses.dataclass
class Mamba2MoEState:
    k: jax.Array          # (n_attention, N_blocks, block_size, Hkv, D)
    v: jax.Array
    conv: jax.Array       # (n_mamba, S+1, d_conv-1, HP + 2N)
    h: jax.Array          # (n_mamba, S+1, P, H, N) float32
    chunk: int            # positions of one SSD chunk (static)

    def resident_bytes(self) -> dict:
        def nbytes(*arrays):
            return int(sum(a.size * a.dtype.itemsize for a in arrays))

        return {"kv_paged": nbytes(self.k, self.v), "kv_window": 0,
                "recurrent": nbytes(self.conv, self.h)}


jax.tree_util.register_dataclass(
    Mamba2MoEState, ["k", "v", "conv", "h"], ["chunk"])


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: Mamba2MoEConfig):
    """Seeded parameters, the layers of a kind stacked on a leading axis:
    `mamba` (all Mamba layers in order), `attn` (all attention layers),
    `ffn` (every layer's norm, router, shared expert and held experts).
    Norm gains and the conv bias are drawn away from their neutral
    values, so that a comparison notices when one is left out.  `A_log`,
    `D` and `dt_bias` are float32 whatever `param_dtype` is."""
    d, di, hs = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    q, kv = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head
    fe, fs, held = cfg.d_expert, cfg.d_shared, cfg.held[1]
    dt = cfg.param_dtype
    count = iter(range(1 << 20))

    def draw(shape, scale, dtype=dt, shift=0.0):
        key = jax.random.fold_in(rng, next(count))
        return (shift + scale * jax.random.normal(key, shape, F32)) \
            .astype(dtype)

    def mamba(n):
        key = jax.random.fold_in(rng, next(count))
        step = jnp.exp(jax.random.uniform(
            key, (n, hs), F32, math.log(1e-3), math.log(1e-1)))
        return {"norm": draw((n, d), 0.1, shift=1.0),
                "in_proj": draw((n, d, di + cfg.d_xbc + hs), d ** -0.5),
                "conv_w": draw((n, cfg.d_conv, cfg.d_xbc),
                               cfg.d_conv ** -0.5),
                "conv_b": draw((n, cfg.d_xbc), 0.1),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "A_log": jnp.log(jnp.broadcast_to(
                    1.0 + jnp.arange(hs, dtype=F32) % 16, (n, hs))),
                "D": draw((n, hs), 0.1, F32, shift=1.0),
                "gate_norm": draw((n, di), 0.1, shift=1.0),
                "out_proj": draw((n, di, d), di ** -0.5)}

    def attn(n):
        return {"norm": draw((n, d), 0.1, shift=1.0),
                "wq": draw((n, d, q), d ** -0.5),
                "wk": draw((n, d, kv), d ** -0.5),
                "wv": draw((n, d, kv), d ** -0.5),
                "wo": draw((n, q, d), q ** -0.5)}

    def ffn(n):
        return {"norm": draw((n, d), 0.1, shift=1.0),
                "router": draw((n, d, cfg.n_experts), d ** -0.5),
                "shared_gate_up": draw((n, d, 2 * fs), d ** -0.5),
                "shared_down": draw((n, fs, d), fs ** -0.5),
                "w_gate": draw((n, held, d, fe), d ** -0.5),
                "w_up": draw((n, held, d, fe), d ** -0.5),
                "w_down": draw((n, held, fe, d), fe ** -0.5)}

    return {"embed": draw((cfg.vocab_size, d), d ** -0.5),
            "mamba": mamba(cfg.n_of("mamba")),
            "attn": attn(cfg.n_of("attention")),
            "ffn": ffn(cfg.n_layers),
            "final_norm": draw((d,), 0.1, shift=1.0)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def _ssd_chunk(xh, b_in, c_out, dt, a_neg, h0, cd):
    """One chunk of the state-space-duality form.  xh (S, Q, H, P) in the
    compute dtype `cd`, b_in / c_out (S, Q, N), dt (S, Q, H) float32 (0
    at a position that is not valid), a_neg (H,) float32, h0 (S, P, H, N)
    float32.  Returns (y (S, Q, H, P) float32, h_Q float32)."""
    q = xh.shape[1]
    s = jnp.cumsum(dt * a_neg, axis=1)                     # (S, Q, H) <= 0
    st = jnp.swapaxes(s, 1, 2)                             # (S, H, Q)
    seen = jnp.tril(jnp.ones((q, q), bool))
    # The decay matrix L[t, r] = exp(s_t - s_r), r <= t (masked before
    # the exponential: above the diagonal the difference is positive).
    decay = jnp.exp(jnp.where(seen, st[..., :, None] - st[..., None, :],
                              -jnp.inf))                   # (S, H, Q, Q)
    scores = jnp.einsum("stn,srn->str", c_out, b_in,
                        preferred_element_type=F32)        # C B^T, (S, Q, Q)
    dx = dt[..., None] * xh.astype(F32)                    # dt o X
    y = jnp.einsum("shtr,srhp->sthp",
                   (decay * scores[:, None]).astype(cd), dx.astype(cd),
                   preferred_element_type=F32)
    # What the incoming state gives each position, and the state handed
    # on: two plain products over the state as (H * P, N).
    y = y + jnp.exp(s)[..., None] * jnp.einsum(
        "stn,sphn->sthp", c_out, h0.astype(cd), preferred_element_type=F32)
    last = s[:, -1]                                        # s_Q, (S, H)
    tail = jnp.exp(last[:, None] - s)                      # exp(s_Q - s_r)
    h = jnp.exp(last)[:, None, :, None] * h0 + jnp.einsum(
        "srhp,srn->sphn", (tail[..., None] * dx).astype(cd), b_in,
        preferred_element_type=F32)
    return y, h


def _mamba2(bp, x, conv_s, h_s, valid, cfg, chunk):
    """The Mamba-2 mixer over the K positions of each lane, from the
    lane's state: conv_s (S, d_conv-1, HP + 2N), h_s (S, P, H, N)
    float32.  Returns (out (S, K, d), conv_s', h_s').  K = 1 is the
    one-position recurrence; 1 < K <= `chunk` is one chunk of
    `_ssd_chunk`; K = m x `chunk` is m of them in an unrolled
    scan that carries the state: `_ssd_chunk` is traced once whatever m
    is, and no loop is lowered (the module's header says why).
    The projections, the convolution, the gate and the norm run over
    all K rows.  A position that is not `valid` (S, K; the valid ones
    are a prefix, which may end in any of the m chunks) takes dt = 0 and
    the conv rows kept are the last valid ones'."""
    cd = cfg.compute_dtype
    di, n, hs, p = cfg.d_inner, cfg.d_state, cfg.ssm_heads, cfg.ssm_head_dim
    s_w, k_w = x.shape[:2]
    u = rms_norm(x, bp["norm"], eps=cfg.norm_eps)
    zxd = jnp.einsum("skd,de->ske", u, bp["in_proj"].astype(cd))
    z, xbc, dt = (zxd[..., :di], zxd[..., di:di + cfg.d_xbc],
                  zxd[..., di + cfg.d_xbc:])
    cat = jnp.concatenate([conv_s.astype(cd), xbc], axis=1)
    conv = bp["conv_b"].astype(F32) + sum(
        bp["conv_w"][j].astype(F32) * cat[:, j:j + k_w].astype(F32)
        for j in range(cfg.d_conv))
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)     # (S,)
    conv_s = jax.vmap(lambda c, m: jax.lax.dynamic_slice_in_dim(
        c, m, cfg.d_conv - 1, axis=0))(cat, n_valid).astype(conv_s.dtype)
    xbc = jax.nn.silu(conv).astype(cd)
    xh = xbc[..., :di].reshape(s_w, k_w, hs, p)
    b_in, c_out = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt.astype(F32) + bp["dt_bias"].astype(F32))
    dt = jnp.where(valid[..., None], dt, 0.0)              # (S, K, H)
    a_neg = -jnp.exp(bp["A_log"].astype(F32))              # (H,)
    h_s = h_s.astype(F32)
    with jax.named_scope("ssd"):
        if k_w == 1:
            d1, x1 = dt[:, 0], xh[:, 0].astype(F32)        # (S,H) (S,H,P)
            h_s = jnp.exp(d1 * a_neg)[:, None, :, None] * h_s \
                + jnp.swapaxes(d1[..., None] * x1, 1, 2)[..., None] \
                * b_in[:, 0].astype(F32)[:, None, None, :]
            y = jnp.einsum("sphn,sn->shp", h_s,
                           c_out[:, 0].astype(F32))[:, None]
        elif k_w <= chunk:
            y, h_s = _ssd_chunk(xh, b_in, c_out, dt, a_neg, h_s, cd)
        elif k_w % chunk:
            raise ValueError(f"a launch of {k_w} rows is not whole chunks "
                             f"of {chunk}")
        else:
            def parts(a):           # (S, m Q, ..) -> (m, S, Q, ..)
                return jnp.swapaxes(
                    a.reshape(s_w, k_w // chunk, chunk, *a.shape[2:]), 0, 1)

            def one(h0, part):
                y, h_q = _ssd_chunk(*part, a_neg, h0, cd)
                return h_q, y

            h_s, y = jax.lax.scan(
                one, h_s, tuple(map(parts, (xh, b_in, c_out, dt))),
                unroll=True)
            y = jnp.swapaxes(y, 0, 1).reshape(s_w, k_w, hs, p)
    y = y + bp["D"].astype(F32)[:, None] * xh.astype(F32)  # (S, K, H, P)
    gated = (y.reshape(s_w, k_w, di) * jax.nn.silu(z.astype(F32))).astype(cd)
    out = jnp.einsum("ske,ed->skd",
                     rms_norm(gated, bp["gate_norm"], eps=cfg.norm_eps),
                     bp["out_proj"].astype(cd))
    return out, conv_s, h_s


def _ffn(fp, experts, li, x, live, cfg, routing):
    """Routed experts (this rank's share) plus the shared expert over
    x (S, K, d); `live` (S, K): the rows that carry a real token.
    Returns (out, experts visited, routed here, taken)."""
    cd = cfg.compute_dtype
    h = rms_norm(x, fp["norm"], eps=cfg.norm_eps)
    with jax.named_scope("experts"):
        out, visited, *taken, routed = moe_mlp_dropless(
            h, {"router": fp["router"], **experts}, cfg.moe, live=live,
            layer=li, return_routing=routing, return_routed=True)
    with jax.named_scope("shared"):
        gu = jnp.einsum("skd,df->skf", h, fp["shared_gate_up"].astype(cd))
        gate, up = jnp.split(gu, 2, axis=-1)
        out = out + jnp.einsum("skf,fd->skd", jax.nn.silu(gate) * up,
                               fp["shared_down"].astype(cd))
    return out, visited, routed, (taken[0] if routing else None)


def _take(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)


def _runs(pattern):
    """The period as runs of one kind: [(kind, first, count)]."""
    out = []
    for j, kind in enumerate(pattern):
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, j, 1])
    return [tuple(r) for r in out]


def _served_step(params, state: Mamba2MoEState, tokens, block_tables,
                 positions, kv_len, slots, cfg: Mamba2MoEConfig,
                 routing: bool = False):
    """`tokens` (S, K) at absolute `positions` (S, K) through every
    layer; `kv_len` (S,) is each lane's length once its valid tokens are
    in (0: an idle lane) and `slots` (S,) the engine slot whose recurrent
    state the lane reads and writes (the null slot for an idle lane).
    Returns (state, hidden (S, K, d), experts visited summed over the
    layers, the experts every row took (L, S, K, top_k) with `routing`
    else None, the top-k choices of live rows that fell on held experts,
    summed over the layers).  Write-then-read, as the paged step.  The
    layers of a period run as its runs of one kind, each a scan that
    indexes the weight stacks (a scan's own slice of the experts would
    be copied out before the visit could index it: `ops.moe`)."""
    cd = cfg.compute_dtype
    bs = state.k.shape[2]
    valid = positions < kv_len[:, None]                    # (S, K)
    live = (kv_len > 0)[:, None]
    wb = jnp.where(live, jnp.take_along_axis(
        block_tables, positions // bs, axis=1), 0)
    off = jnp.where(live, positions % bs, 0)
    res = cfg.residual_multiplier
    x = (params["embed"].astype(cd)[tokens]
         * cfg.embedding_multiplier).astype(cd)
    ffn = {k: v for k, v in params["ffn"].items()
           if k not in _EXPERT_WEIGHTS}
    experts = {k: params["ffn"][k] for k in _EXPERT_WEIGHTS}
    pattern = cfg.layer_pattern
    per = {kind: pattern.count(kind) for kind in set(pattern)}

    def layer(carry, li, kind, at):
        x, k_pool, v_pool, conv, h, visited, routed = carry
        if kind == "mamba":
            with jax.named_scope("mamba"):
                out, conv_s, h_s = _mamba2(
                    _take(params["mamba"], at), x, conv[at, slots],
                    h[at, slots], valid, cfg, state.chunk)
                conv = conv.at[at, slots].set(conv_s)
                h = h.at[at, slots].set(h_s.astype(h.dtype))
        else:
            with jax.named_scope("attn"):
                ap = _take(params["attn"], at)
                u = rms_norm(x, ap["norm"], eps=cfg.norm_eps)
                shape = (*tokens.shape, -1, cfg.d_head)
                q, k, v = (jnp.einsum("skd,dh->skh", u, ap[w].astype(cd))
                           .reshape(shape) for w in ("wq", "wk", "wv"))
                k_pool = k_pool.at[at, wb, off].set(k.astype(k_pool.dtype))
                v_pool = v_pool.at[at, wb, off].set(v.astype(v_pool.dtype))
                attn = paged_attention(
                    q, k_pool, v_pool, at, block_tables, positions, kv_len,
                    scale=cfg.attention_scale, kv_heads=cfg.n_kv_heads)
                out = jnp.einsum(
                    "skh,hd->skd",
                    attn.reshape(*tokens.shape, -1).astype(cd),
                    ap["wo"].astype(cd))
        x = x + (res * out).astype(cd)
        out, n, r, taken = _ffn(_take(ffn, li), experts, li, x, valid,
                                cfg, routing)
        x = x + (res * out).astype(cd)
        return (x, k_pool, v_pool, conv, h, visited + n, routed + r), taken

    def period(carry, i):
        taken = []
        for kind, first, count in _runs(pattern):
            # Layer first + j of period i: the `at`-th of its kind.
            before = pattern[:first].count(kind)

            def one(carry, j, kind=kind, first=first, before=before):
                return layer(carry, i * len(pattern) + first + j, kind,
                             i * per[kind] + before + j)

            carry, got = jax.lax.scan(one, carry, jnp.arange(count))
            taken.append(got)
        return carry, (jnp.concatenate(taken) if routing else None)

    zero, none = jnp.int32(0), routed_zero(tokens.size, cfg.moe)
    (x, k_pool, v_pool, conv, h, visited, routed), taken = jax.lax.scan(
        period, (x, state.k, state.v, state.conv, state.h, zero, none),
        jnp.arange(cfg.periods))
    if routing:                 # (periods, p, S, K, k) -> (L, S, K, k)
        taken = taken.reshape(cfg.n_layers, *taken.shape[2:])
    return (dataclasses.replace(state, k=k_pool, v=v_pool, conv=conv, h=h),
            x, visited, taken, routed)
