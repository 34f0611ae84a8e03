"""Mixture-of-Experts: top-k routing + expert-parallel FFN.

Greenfield per SURVEY.md §2.4 (reference has no EP implementation).  XLA-
SPMD design: experts live on the "expert" logical axis (mesh axis `ep`);
dispatch/combine are einsums against a capacity-bounded one-hot tensor, so
when the expert axis is sharded XLA lowers the dispatch to `all_to_all`
over ICI — no hand-written routing collectives.

Shapes: tokens (B, T, d) → flat groups (G, S, d) where G spreads over the
batch axes; dispatch (G, S, E, C); expert compute (E, G, C, d).

That is `moe_mlp`, the train-time form.  Serving routes exactly and is
bound by the bytes of expert weights it reads: `moe_mlp_dropless` visits
only the experts that live tokens are routed to, and of those only the
ones held here: `MoEConfig.held` names one rank's contiguous share of the
experts (the router keeps its published width, the stacks hold the share,
the result is the share's partial sum; no exchange and nothing in its
place).  A shared expert that every token takes is not an expert of this
loop: it is the model's own dense FFN, added to the routed sum by the
model (`models/mamba2_moe.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.parallel.sharding import LogicalRules, DEFAULT_RULES, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    # One rank's share of an expert-parallel layer: (first, count), the
    # contiguous range of the `num_experts` that is held here.  The
    # router stays `num_experts` wide; the weight stacks hold the `count`
    # experts alone.  None: all of them (`moe_mlp_dropless`).
    held: Optional[Tuple[int, int]] = None
    # How `moe_mlp_dropless` scores the experts.  "softmax": the top_k
    # largest probabilities, renormalised.  "sigmoid": s = sigmoid(logit)
    # a router output; the top_k largest of s + `router_bias` (a learned
    # (E,) vector beside the router in the parameters, which selects and
    # does not gate; of s alone where the parameters have none) are taken,
    # gated by their own s renormalised and multiplied by `route_scale`.
    scoring: str = "softmax"
    route_scale: float = 1.0

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: 'softmax' or "
                             f"'sigmoid'")
        if self.held is not None:
            first, count = self.held
            if first < 0 or count < 1 or first + count > self.num_experts:
                raise ValueError(f"held={self.held}: not a range of "
                                 f"{self.num_experts} experts")


def init_moe_params(rng, d_model: int, d_ff: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(rng, 4)
    e = cfg.num_experts

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    return {
        "router": dense(ks[0], (d_model, e), d_model),
        "w_gate": dense(ks[1], (e, d_model, d_ff), d_model),
        "w_up": dense(ks[2], (e, d_model, d_ff), d_model),
        "w_down": dense(ks[3], (e, d_ff, d_model), d_ff),
    }


def moe_param_logical_axes():
    return {
        "router": ("embed", "expert"),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def top_k_routing(logits: jnp.ndarray, k: int, capacity: int):
    """logits (G, S, E) → dispatch (G,S,E,C) one-hot, combine (G,S,E,C).

    Switch/GShard-style: per-token top-k experts, capacity-bounded by
    position-in-expert (tokens over capacity are dropped — residual path
    carries them)."""
    g, s, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)         # (G,S,k)
    # Normalize chosen gates to sum 1 (standard for k>1).
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # one-hot per choice: (G, S, k, E)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
    # position of each token within its expert queue, per choice.
    # flatten choices into the token sequence: priority = earlier token,
    # earlier choice.
    flat = onehot.reshape(g, s * k, e)
    pos = jnp.cumsum(flat, axis=1) * flat - 1.0              # (G, S*k, E)
    pos = pos.reshape(g, s, k, e)
    keep = (pos >= 0) & (pos < capacity)
    pos = jnp.where(keep, pos, 0.0)
    cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32)           # (G,S,k,E,C)
    cap_onehot = cap_onehot * keep[..., None].astype(jnp.float32)
    dispatch = jnp.max(cap_onehot, axis=2)                   # (G,S,E,C)
    combine = jnp.einsum("gske,gskec->gsec", onehot * gate_vals[..., None],
                         cap_onehot)
    return dispatch, combine, probs


# Why `moe_mlp_dropless` is a loop over the experts that were hit, and one
# form for every caller.  Measured on a v5e at Mixtral-8x7B's widths (8
# experts of three 4096 x 14336 bf16 matrices, top-2; depth 3; PR 31, the
# bare served programs, ms a decode step / a chunk; "dense" is the product
# over all experts with zero combine weights that stood here before):
#
#   burst, width 4, ~300 positions    1 live   2 live   4 live
#     dense                           12.52    12.52    12.52
#     visit (experts read a layer)     4.08     6.29     9.08   (2.0 / 3.6 / 5.6)
#   at ~2,000 positions: dense 12.74 / 12.76 / 12.74, visit 4.30 / 6.30 / 9.14
#   burst, width 8, 8 live: 12.55 -> 11.61 (7.4 read); width 16, 8 live:
#   12.67 -> 11.60 (7.3); 16 live: 12.67 -> 12.47 (7.9), at 2,000 13.29 -> 13.10
#   prefill chunk of 128 tokens (all 8 read): 12.72 -> 10.44 at position 0,
#   12.94 -> 11.59 at 1,920; of 32 tokens 12.42 -> 9.67
#
# One more expert a layer costs 0.47 ms = 352 MB at 757 GB/s (92% of the
# chip's 819).  The visit is never slower, also where every expert is hit,
# so there is no crossover and no second path; nothing here needed Pallas:
# each product is one fusion whose operand is the stack with the slice
# inside (AOT for a v5e; tests/test_tpu_compile.py holds it).
#
# The same loop at 64 small experts (Mellum2-12B-A2.5B's widths: three
# 2304 x 896 bf16 matrices an expert, 12.4 MB, top-8; depth 8, six window
# layers and two full; PR 34, the bare served programs on a v5e, ms a
# decode step with the experts read a layer beside it / ms a chunk):
#
#   burst at ~2,000 positions   1 live       2 live        all live
#     width 4                   4.06 (8.0)   5.32 (15.2)   7.34 (26.6)
#     width 8 / 16 / 32, all live:  9.99 (40.8) / 12.74 (54.3) / 14.74 (60.8)
#     width 8, 4 live 7.48 (26.6); width 16, 8 live 10.38 (40.8)
#   at ~200 positions: width 4 3.88 / 5.08 / 6.77 (8.0 / 14.8 / 24.5),
#     width 8 / 16 / 32 all live 8.84 (36.1) / 10.87 (47.1) / 12.89 (57.5)
#   prefill chunk, seeded tokens (all 64 read), at position 0 / 1,920 /
#   5,888: 128 tokens 13.77 / 13.56 / 14.43, 64 tokens 11.40 / 11.25 /
#   12.31, 32 tokens 10.24 / 9.91 / 10.40
#
# Experts read follow 64 (1 - (7/8)^lanes) (8 / 15 / 26.5 / 42 / 56 / 63)
# to within 2.5.  One more expert a layer costs 22 us (7.34 - 4.06 ms over
# 18.6 experts x 8 layers; 12.74 - 10.38 over 13.5 x 8) where its 12.4 MB
# take 15.1 us at the chip's 819 GB/s: 563 GB/s, 69%, against Mixtral's
# 92%.  The 7 us a trip beyond its bytes are the loop's own: three small
# fusions, three dynamic slices, a row of the combine weights, at 8 rows
# of work.  A 128-token chunk makes 512 trips in at most 13.8 ms (27 us a
# trip, 20 at 32 tokens): every one of the 64 experts multiplies all 128
# rows though 16 are routed to it, 8 x the products the model needs, and
# still waits for memory more than for the MXU.  Tokens grouped by expert
# (a sort and one product a group) would win the 8 x and most of the 7 us;
# that is a form of its own and a `perf_opt` PR's (ROADMAP R1), not a
# repair of this one: at Mixtral's widths this loop is at the roofline.
#
# The same loop over one rank's share, 36 held of 72 experts, top-10
# (granite-4.0-h-small's widths: three 4096 x 768 bf16 matrices an expert,
# 18.9 MB; one period of ten layers, nine Mamba-2 and one attention, a
# shared expert of width 1536 beside the routed ones; PR 36, the bare
# served programs on a v5e, ms a decode step with the held experts read a
# layer beside it / ms a chunk):
#
#   burst at ~300 / ~3,000 positions, width 4:
#     1 live 6.73 / 6.72 (5.3 / 5.1)   2 live 7.90 / 7.97 (9.2 / 8.8)
#     4 live 10.10 / 10.08 (16.5 / 16.5)
#   width 8, all live 13.92 / 13.87 (24.9 / 24.2); width 16, all live
#     19.28 / 19.86 (32.3 / 33.2)
#   prefill chunk, seeded tokens (all 36 read), at position 0 / 1,920 /
#   3,840: 256 tokens 21.9 / 22.0 / 22.1, 128 tokens 18.1 / 18.1 / 18.1,
#   64 tokens 18.0, 32 tokens 16.4 / 16.2 / 16.5
#
# Held experts read follow 36 (1 - (62/72)^lanes) (5.0 / 9.3 / 16.2 / 25.1
# / 32.7) to within 0.5: half of what the router chooses falls here
# (`routed_here` / the choices made: 49.3% in the served cell).  One more
# held expert a layer costs 30 us (10.10 - 6.73 ms over 11.2 experts x 10
# layers) where its 18.9 MB take 23 us at 819 GB/s: 77%, between Mellum's
# 69% at 12.4 MB an expert and Mixtral's 92% at 352 MB: the trip's own
# ~7 us again.  A 256-token chunk makes 360 trips in some 15 of its 22 ms
# (42 us a trip; `moe_chunk_roofline` 55% in the served cell's trace): at
# 256 rows a trip also reads and writes the float32 accumulator (256 x 4096
# x 4 B each way, 8 MB beside the 18.9 MB of weights) and multiplies all
# 256 rows where 36 are routed to the expert.  Rows grouped by expert
# would win both; still ROADMAP S11's, with the numbers above as its
# baseline.
#
# The same loop over one rank's share, 128 held of 256 experts, top-8,
# scored by a sigmoid (Laguna-XS.2's widths: three 2048 x 512 bf16 matrices
# an expert, 6.3 MB; a dense first layer and two periods of three window
# layers to one full layer, eight expert layers, a shared expert of width
# 512 beside the routed ones; PR 45, the bare served programs on a v5e, ms a
# decode step with the held experts read a layer beside it / ms a chunk):
#
#   burst at ~8,000 positions, width 4:
#     1 live 3.95 (3.9)   2 live 4.63 (8.1)   4 live 5.32 (15.6)
#   width 8: 4 live 6.20 (15.6), all live 7.49 (29.3); all live at ~300
#     positions 5.21 (28.5), at ~12,000 8.70 (28.5); 1 live at ~300 2.79 (3.9)
#   prefill chunk, seeded tokens (all 128 read), at position 0 / 4,096 /
#   8,192: 128 tokens 19.7 / 20.7 / 21.3, 256 tokens 25.4 / 26.4 / 27.3,
#   512 tokens 37.5 / 38.8 / 40.2 (154 / 99 / 73 us a token at position 0)
#
# Held experts read follow 128 (1 - (31/32)^lanes) (4.0 / 7.9 / 15.3 / 28.7)
# to within 0.6.  One more held expert a layer costs 12-15 us (5.32 - 3.95
# ms over 11.7 experts x 8 layers; 7.49 - 6.20 over 13.7 x 8) where its 6.3
# MB take 7.7 us at 819 GB/s: 52-65%, under Mellum's 69% at 12.4 MB and
# granite's 77% at 18.9 MB: the trip's own ~5-7 us again, now as long as
# the trip's bytes.  A 128-token chunk makes 1,024 trips in some 15 of its
# 20 ms (~15 us a trip); a 512-token chunk the same 1,024 trips in ~30 of
# its 37.5 ms (~29 us a trip, where 512 rows x three 2048 x 512 products are
# 3.2 GFLOP = 16 us at the chip's 197 TFLOP/s and the float32 accumulator
# 512 x 2048 x 4 B each way is 8 MB beside the 6.3 MB of weights): every one
# of the 128 held experts multiplies all 512 rows where 16 are routed to it
# (4 of a row's 8 choices fall here), 32 x the products the model needs.
# The baseline ROADMAP S11 is judged on at the smallest expert the
# benchmark holds.
def moe_mlp_dropless(x: jnp.ndarray, params: dict, cfg: MoEConfig, *,
                     live: "jnp.ndarray | None" = None, layer=None,
                     return_routing: bool = False,
                     return_routed: bool = False):
    """Exact (dropless) top-k MoE for INFERENCE: every live token reaches
    all of its top-k experts, so the result is independent of how many
    other tokens share the batch — a cached decode step computes the same
    function as a full prefill (capacity-based `moe_mlp` drops over-
    capacity tokens, which makes its output depend on the token count;
    that's the standard train-time scheme, ref: Switch/GShard, but
    serving engines route exactly, ref: Mixtral inference).

    x (B, T, d); `live` (B,) bool says which lanes carry a real token
    (None: all), or (B, T) which rows do (a chunk's padded tail is then
    routed nowhere).  An idle lane's rows select no expert and come out
    zero.
    Returns (out (B, T, d), visited): `visited` (int32 scalar) is the
    number of distinct experts the live rows are routed to; with
    `return_routing` also the experts each row took, (B, T, top_k) int32
    (a scoring entry hands them to a reference, which then computes the
    same function where two router logits lie closer than the program's
    rounding).  With `layer`
    (a traced index) the three expert weights are the stacks of all
    layers (L, E, ..) and the visit slices [layer, expert]: a caller
    inside a scan over layers hands the stacks whole, because a layer's
    (E, ..) slice taken by the scan is copied out, all E experts of it,
    before the loop below can index it (2.8 GB a layer at Mixtral's
    widths; AOT for a v5e, PR 31).

    A visit of the set, not a product over all experts: at decode the
    cost is the bytes of expert weights read, and 1-4 live tokens are
    routed to 2-5 of 8 experts.  The experts that some live row chose
    come first in an order computed from the routing, and a loop of
    `visited` trips slices one expert's three matrices each into its
    products over all rows, each row weighted by its gate for that
    expert (zero where it was not chosen).  An expert no live row chose
    is never read.  When every expert is hit (a prefill chunk) the loop
    is the dense form, expert by expert.

    **One rank's share** (`cfg.held = (first, count)`): the weight
    stacks hold experts first .. first + count - 1 of `num_experts` and
    no others.  The router keeps all `num_experts` outputs and its
    top-k; a choice that fell on an expert held elsewhere gets combine
    weight zero here, the order and the loop run over the held experts
    alone, and `visited` counts held experts hit.  What the absent
    experts would add is left out: the result is this rank's partial
    sum, and nothing stands in for the other ranks or their exchange.
    With `return_routed` one more output, last: the top-k choices of
    live rows that fell on held experts (int32; every live choice where
    all are held).  `held=None` traces the very operations it traced
    before there was a share.

    **The scoring** is `cfg.scoring`'s (`MoEConfig`): the lines between
    the router's product and `chosen` and nothing after them, so a share,
    `live`, `return_routing` and `return_routed` mean the same under
    either.  "sigmoid" reads `params["router_bias"]` (E,) where the
    parameters have one.  Soft-max scoring with `route_scale` 1 traces
    what it traced before there was a choice.
    """
    b, t, d = x.shape
    dtype = x.dtype
    e = cfg.num_experts

    logits = jnp.einsum("btd,de->bte", x, params["router"].astype(dtype))
    if cfg.scoring == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    else:
        # The bias moves the selection; the gates are the unbiased scores.
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
        bias = params.get("router_bias")
        _, expert_idx = jax.lax.top_k(
            probs if bias is None else probs + bias.astype(jnp.float32),
            cfg.top_k)
        gate_vals = jnp.take_along_axis(probs, expert_idx, axis=-1)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    if cfg.route_scale != 1.0:
        gate_vals = gate_vals * cfg.route_scale
    chosen = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (B,T,k,E)
    if live is not None:
        mask = live.astype(jnp.float32)
        chosen = chosen * (mask[:, None, None, None] if live.ndim == 1
                           else mask[:, :, None, None])
    if cfg.held is not None:
        # This rank's columns: (B,T,k,count), in the stacks' own order.
        first, e = cfg.held
        chosen = chosen[..., first:first + e]
    # (N,E) combine weights: zero for unselected experts and idle rows
    w = jnp.sum(chosen * gate_vals[..., None], axis=2).reshape(b * t, e)
    hit = jnp.any(chosen.reshape(-1, e) > 0, axis=0)           # (E,)
    visited = jnp.sum(hit.astype(jnp.int32))
    order = jnp.argsort(~hit, stable=True)                     # hit first

    rows = x.reshape(b * t, d)

    def expert(name, ex):
        # One dynamic slice (cast after it, never the stack), so that it
        # fuses into the product as an operand and is not hoisted out of
        # the loop as a layer's copy.
        stack = params[name]
        at = (ex,) if layer is None else (layer, ex)
        return jax.lax.dynamic_slice(
            stack, (*at, 0, 0), (1,) * len(at) + stack.shape[-2:]
        ).reshape(stack.shape[-2:]).astype(dtype)

    def visit(i, acc):
        ex = order[i]
        gate = rows @ expert("w_gate", ex)
        up = rows @ expert("w_up", ex)
        out_e = (jax.nn.silu(gate) * up) @ expert("w_down", ex)
        return acc + jax.lax.dynamic_index_in_dim(w, ex, 1) \
            * out_e.astype(jnp.float32)

    out = jax.lax.fori_loop(0, visited, visit,
                            jnp.zeros((b * t, d), jnp.float32))
    out = out.reshape(b, t, d).astype(dtype)
    res = (out, visited, expert_idx) if return_routing else (out, visited)
    if return_routed:
        res += (jnp.sum(chosen).astype(jnp.int32),)
    return res


def moe_mlp(x: jnp.ndarray, params: dict, cfg: MoEConfig, *,
            rules: LogicalRules = DEFAULT_RULES):
    """x (B, T, d) → (B, T, d), plus auxiliary losses dict."""
    b, t, d = x.shape
    dtype = x.dtype
    e = cfg.num_experts
    tokens = b * t
    capacity = max(1, int(cfg.capacity_factor * tokens * cfg.top_k / e))
    xg = x.reshape(1, tokens, d)                              # one group

    logits = jnp.einsum("gsd,de->gse", xg, params["router"].astype(dtype))
    dispatch, combine, probs = top_k_routing(logits, cfg.top_k, capacity)

    # dispatch tokens to expert buffers: (E, G, C, d); expert axis sharded.
    expert_in = jnp.einsum("gsec,gsd->egcd",
                           dispatch.astype(jnp.float32),
                           xg.astype(jnp.float32)).astype(dtype)
    expert_in = with_logical_constraint(
        expert_in, ("expert", None, None, "embed"), rules)
    gate = jnp.einsum("egcd,edf->egcf", expert_in,
                      params["w_gate"].astype(dtype))
    up = jnp.einsum("egcd,edf->egcf", expert_in,
                    params["w_up"].astype(dtype))
    hidden = jax.nn.silu(gate) * up
    hidden = with_logical_constraint(
        hidden, ("expert", None, None, "mlp"), rules)
    expert_out = jnp.einsum("egcf,efd->egcd", hidden,
                            params["w_down"].astype(dtype))
    out = jnp.einsum("gsec,egcd->gsd",
                     combine.astype(jnp.float32),
                     expert_out.astype(jnp.float32))

    # load-balancing loss (Switch eq. 4) + router z-loss
    me = jnp.mean(probs, axis=(0, 1))                         # (E,)
    ce = jnp.mean(jnp.max(dispatch, axis=-1), axis=(0, 1))    # fraction routed
    lb_loss = e * jnp.sum(me * ce)
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    z_loss = jnp.mean(z ** 2) * cfg.router_z_loss
    aux = {"moe_load_balance_loss": lb_loss, "moe_z_loss": z_loss}
    return out.reshape(b, t, d).astype(dtype), aux
