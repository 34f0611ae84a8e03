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
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    # largest probabilities, renormalised.  "sigmoid": s = sigmoid(logit) a
    # router output; the top_k largest of s + `router_bias` (a learned (E,)
    # vector beside the router, which selects and does not gate; of s alone
    # without one) are taken, gated by their own s x `route_scale`.
    scoring: str = "softmax"
    route_scale: float = 1.0
    grouped_from_rows: int = 0   # `grouped_tile_rows`; 0: _GROUPED_FROM_ROWS
    # Group-limited routing (under "sigmoid"): the experts stand in
    # `n_groups` groups of consecutive ones, a group's score is the sum of
    # its two largest selection scores, and a row's top_k come from the
    # `groups_kept` groups of largest score alone.  1 / 1: one group, no
    # expert left out.
    n_groups: int = 1
    groups_kept: int = 1

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: 'softmax' or "
                             f"'sigmoid'")
        if (self.n_groups, self.groups_kept) != (1, 1):
            if self.scoring != "sigmoid":
                raise ValueError("groups of experts are scored by biased "
                                 "sigmoid scores: scoring='sigmoid'")
            if self.n_groups < 1 or self.num_experts % self.n_groups:
                raise ValueError(f"n_groups={self.n_groups} does not divide "
                                 f"{self.num_experts} experts into groups")
            if not 1 <= self.groups_kept <= self.n_groups or self.top_k > \
                    self.groups_kept * (self.num_experts // self.n_groups):
                raise ValueError(
                    f"groups_kept={self.groups_kept} of {self.n_groups} "
                    f"groups must hold top_k={self.top_k} experts")
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


# A launch groups its rows by expert (`_grouped`) from this many rows on,
# if the visit would send them through this many (rows x held experts)
# products' rows or more; any other launch visits (the table above
# `moe_mlp_dropless`).
_GROUPED_FROM_ROWS = 64
_GROUPED_FROM_PRODUCTS = 2048


def grouped_tile_rows(n_rows: int, cfg: MoEConfig) -> int:
    """Rows a trip of the grouped form multiplies in a launch of `n_rows`
    rows, 0 where the launch takes the visit: a choice of static shapes (and
    of `cfg.grouped_from_rows`: below, above `moe_mlp_dropless`).  Half as
    many again as the rows an expert sees if the router spreads them evenly
    (n_rows x top_k / num_experts), rounded up to a power of two from 16 on,
    so that nearly every group is one trip (a second tile reads again)."""
    held = cfg.held[1] if cfg.held else cfg.num_experts
    if n_rows < (cfg.grouped_from_rows or _GROUPED_FROM_ROWS) \
            or n_rows * held < _GROUPED_FROM_PRODUCTS:
        return 0
    mean = -(-n_rows * cfg.top_k // cfg.num_experts)
    return max(16, 1 << (mean + mean // 2 - 1).bit_length())


def routed_zero(n_rows: int, cfg: MoEConfig):
    """What a caller that sums `return_routed` over layers starts from:
    a launch of `n_rows` rows counts (choices routed here, tiles) in the
    grouped form and the choices alone in the visit; under groups of
    experts (choices, tiles, rows whose kept groups hold a held expert)
    in either."""
    if cfg.n_groups > 1:
        return jnp.zeros((3,), jnp.int32)
    return jnp.zeros((2,), jnp.int32) if grouped_tile_rows(n_rows, cfg) \
        else jnp.int32(0)


def _group_scores(by_group):
    """(.., G, size) selection scores -> (.., G): a group's score is the
    sum of its two largest (of its largest where it has one expert)."""
    return jnp.sum(jax.lax.top_k(by_group, min(2, by_group.shape[-1]))[0],
                   axis=-1)


def _kept_groups(pick, cfg: MoEConfig):
    """Group-limited routing's first step.  `pick` (B, T, E) float32
    selection scores -> (`pick` with every expert outside the row's
    `groups_kept` groups of largest `_group_scores` at -inf, those groups
    (B, T, groups_kept) int32, open (B, T) bool: one of them holds an
    expert held here).  Among equal groups the lower index is kept, as
    among equal experts."""
    b, t, e = pick.shape
    size = e // cfg.n_groups
    by_group = pick.reshape(b, t, cfg.n_groups, size)
    _, kept = jax.lax.top_k(_group_scores(by_group), cfg.groups_kept)
    keep = jnp.any(jax.nn.one_hot(kept, cfg.n_groups, dtype=jnp.bool_),
                   axis=2)                                      # (B,T,G)
    first, count = cfg.held or (0, e)
    here = keep[..., first // size:(first + count - 1) // size + 1]
    return jnp.where(keep[..., None], by_group, -jnp.inf).reshape(b, t, e), \
        kept, jnp.any(here, axis=-1)


# What the tile kernel may hold of an expert's three matrices at a time,
# both of its buffers counted, and the most it may ask of a v5e's 128 MiB
# of VMEM.
_KERNEL_WEIGHT_BYTES = 40 * 2**20
_VMEM_LIMIT = 100 * 2**20
_LANES = 128


def _f_block(d: int, f: int, itemsize: int) -> int:
    """Columns of an expert's inner width a step of the tile kernel copies
    in: all `f` where the three matrices fit twice, else the most whole
    lane tiles that divide `f` and do."""
    fits = [fb for fb in range(_LANES, f + 1, _LANES)
            if f % fb == 0 and 6 * d * fb * itemsize <= _KERNEL_WEIGHT_BYTES]
    return f if 6 * d * f * itemsize <= _KERNEL_WEIGHT_BYTES or not fits \
        else fits[-1]


def _kernel_vmem(n: int, d: int, f: int, tile: int, itemsize: int) -> int:
    """Bytes of VMEM `_fused_ffn_kernel` asks for: the weights' blocks
    twice, the launch's rows and their float32 sum twice and the sum once
    more while a tile is added to it, a tile's scratches and values, a
    margin.  The compiler counts what is asked for among a program's
    temporaries, so no more than that."""
    return 2**24 + d * (6 * _f_block(d, f, itemsize) * itemsize
                        + 16 * n + 12 * tile)


def _fused_ffn_body(ex_ref, tiles_ref, layer_ref, col_ref, row_ref, gate_ref,
                    rows_ref, wg_ref, wu_ref, wd_ref, o_ref, x_ref, acc_ref):
    """Step (i, j) of `_fused_ffn_kernel`'s grid.  At a tile's first step
    its rows are picked out of the launch's rows (a one-hot product: row
    r of the tile is row `col_ref[r]` of the launch, none where that is
    -1), at every step they meet columns j of the expert's inner width,
    and at its last the tile's results, rounded as the loop's product
    comes out, are added to their rows of the launch's sum, each times its
    gate: one-hot again, the float32 gate in three bfloat16 pieces so that
    the products stay exact."""
    i, j = pl.program_id(0), pl.program_id(1)
    n, tile = rows_ref.shape[0], x_ref.shape[0]
    dtype = x_ref.dtype

    @pl.when((i == 0) & (j == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < tiles_ref[0])
    def _multiply():
        @pl.when(j == 0)
        def _open():
            pick = jax.lax.broadcasted_iota(jnp.int32, (tile, n), 1) \
                == col_ref[...]
            x_ref[...] = jnp.dot(pick.astype(jnp.float32).astype(dtype),
                                 rows_ref[...],
                                 preferred_element_type=jnp.float32
                                 ).astype(dtype)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]

        def product(w_ref):     # rounded as the loop's product comes out
            return jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32
                           ).astype(dtype).astype(jnp.float32)

        hidden = jax.nn.silu(product(wg_ref)) * product(wu_ref)
        acc_ref[...] += jnp.dot(hidden.astype(dtype), wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _close():
            y = acc_ref[...].astype(dtype)
            back = jax.lax.broadcasted_iota(jnp.int32, (n, tile), 0) \
                == row_ref[...]
            left = gate_ref[...]                               # (1, tile)
            total = jnp.zeros(o_ref.shape, jnp.float32)
            for _ in range(3):
                piece = left.astype(dtype).astype(jnp.float32)
                total += jnp.dot(jnp.where(back, piece, 0.0).astype(dtype),
                                 y, preferred_element_type=jnp.float32)
                left = left - piece
            o_ref[...] += total


@functools.partial(jax.jit, static_argnames=("tile",))
def _fused_ffn_kernel(rows, w_gate, w_up, w_down, layer, ex, tiles, src,
                      gates, *, tile):
    """The grouped form's products and its sum as one kernel: tile i is
    the rows `src[i]` (n_tiles, tile; -1: none) of `rows` (N, d), through
    expert `ex[i]` of layer `layer` of the stacks (L, E, d, f) / (L, E, f,
    d), which stay where they lie, a block of an expert's matrices copied
    to VMEM a step while the step before it is multiplied; each result is
    added to its row of the output (N, d) float32 times its gate
    (`gates`, as `src`).  The rows and the sum stay in VMEM for the whole
    call.  Tiles from `tiles` on are not multiplied and copy nothing new
    (their block indices stand still)."""
    n, d = rows.shape
    n_tiles = src.shape[0]
    f = w_gate.shape[-1]
    fb = _f_block(d, f, w_gate.dtype.itemsize)
    nj = f // fb

    def live(i, tiles):
        return jnp.minimum(i, jnp.maximum(tiles[0] - 1, 0))

    def col(i, j, tiles):
        return jnp.where(i < tiles[0], j, nj - 1)

    def slots(shape):
        return pl.BlockSpec((None, *shape),
                            lambda i, j, ex, t, li: (live(i, t), 0, 0))

    def matrix(shape, at):
        return pl.BlockSpec((None, None, *shape), lambda i, j, ex, t, li: (
            li[0], ex[live(i, t)], *at(col(i, j, t))))

    return pl.pallas_call(
        _fused_ffn_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nj),
            in_specs=[
                slots((tile, 1)), slots((1, tile)), slots((1, tile)),
                pl.BlockSpec((n, d), lambda i, j, *_: (0, 0)),
                matrix((d, fb), lambda c: (0, c)),
                matrix((d, fb), lambda c: (0, c)),
                matrix((fb, d), lambda c: (c, 0))],
            out_specs=pl.BlockSpec((n, d), lambda i, j, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((tile, d), rows.dtype),
                            pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_kernel_vmem(n, d, f, tile,
                                          w_gate.dtype.itemsize)),
        name="grouped_expert_ffn",
    )(ex.astype(jnp.int32), jnp.asarray(tiles, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), src[:, :, None],
      src[:, None, :], gates.astype(jnp.float32)[:, None, :], rows, w_gate,
      w_up, w_down)


def _kernel_takes(rows, w_gate, tile: int) -> bool:
    """Whether `_fused_ffn_kernel` can run these shapes on a TPU: bfloat16
    rows and weights in whole tiles (16 sublanes, 128 lanes), and what it
    keeps in VMEM within the limit."""
    n, d = rows.shape
    f = w_gate.shape[-1]
    return (rows.dtype == w_gate.dtype == jnp.bfloat16 and n % 16 == 0
            and d % _LANES == 0 and f % _LANES == 0
            and _kernel_vmem(n, d, f, tile, 2) <= _VMEM_LIMIT)


def _expert_ffn(h, stacks, layer, ex):
    """Expert `ex`'s FFN over h (rows, d): its three matrices out of
    `stacks` (gate, up, down: (E, ..), or (L, E, ..) with `layer`)."""
    def expert(stack):
        # One dynamic slice (cast after it, never the stack), so that it
        # fuses into the product as an operand and is not hoisted out of
        # the loop as a layer's copy.
        at = (ex,) if layer is None else (layer, ex)
        return jax.lax.dynamic_slice(
            stack, (*at, 0, 0), (1,) * len(at) + stack.shape[-2:]
        ).reshape(stack.shape[-2:]).astype(h.dtype)

    gate = h @ expert(stacks[0])
    up = h @ expert(stacks[1])
    return (jax.nn.silu(gate) * up) @ expert(stacks[2])


@functools.partial(jax.jit, static_argnames=("tile",))
def _grouped(rows, chosen, gate_vals, stacks, layer, *, tile: int):
    """The routed sum of `rows` (N, d) with each row's choices grouped by
    expert: `chosen` (B, T, k, E) one-hot over the experts held here (all
    zero for a choice that fell elsewhere or an idle row), `gate_vals`
    (B, T, k), `stacks` the three weight stacks and `layer` their layer
    (None: they are one layer's).  Returns (out (N, d) float32, tiles
    multiplied: int32).  Jitted, so that a program's call sites (the
    layers of a period, the tail) trace it once a shape: traced at each,
    a chunk tier's lowering took half as long again.

    The N x k choices are sorted by expert (those routed nowhere last), so
    a group is a run of the sorted order, and laid out in tiles of `tile`
    slots, every group from a tile's first slot on: a group's last tile is
    ragged, and its spare slots hold no row.  A trip takes a tile's rows
    through the tile's one expert and adds each result, times its gate, to
    its row's float32 sum.  Lowered for a TPU the trips are the grid of one
    kernel (`_fused_ffn_kernel`) where its tiles allow; elsewhere a loop
    of the visit's own three products, on rows gathered into the layout
    beforehand and summed by a scatter afterwards."""
    n, d = rows.shape
    k, e = chosen.shape[-2:]
    m = n * k
    flat = chosen.reshape(m, e)
    routed = jnp.any(flat > 0, axis=1)                         # (M,)
    key = jnp.where(routed, jnp.argmax(flat, axis=1), e)
    order = jnp.argsort(key, stable=True)           # sorted place -> choice
    count = jnp.sum(flat, axis=0).astype(jnp.int32)            # (E,)
    first = jnp.cumsum(count) - count               # a group's sorted start
    trips = -(-count // tile)
    trips_to = jnp.cumsum(trips)                    # tiles up to and with e
    tiles = trips_to[-1]
    # At most M / tile whole tiles and a ragged one a group.
    n_tiles = m // tile + e
    ex = jnp.minimum(jnp.searchsorted(
        trips_to, jnp.arange(n_tiles, dtype=jnp.int32), side="right"), e - 1)
    # Tile i is the j-th of `ex[i]`: slots j * tile .. of its group.
    in_group = ((jnp.arange(n_tiles, dtype=jnp.int32)
                 - (trips_to - trips)[ex]) * tile)[:, None] \
        + jnp.arange(tile, dtype=jnp.int32)                 # (tiles, tile)
    there = in_group < count[ex][:, None]
    choice = order[jnp.where(there, first[ex][:, None] + in_group, 0)]
    src = jnp.where(there, choice // k, -1)         # a slot's row; -1: none
    gates = jnp.where(there, gate_vals.reshape(m)[choice], 0.0)

    def loop(rows, ex, tiles, src, gates):
        tiled = rows[jnp.maximum(src, 0).reshape(-1)]   # (n_tiles * tile, d)

        def trip(i, ys):
            h = jax.lax.dynamic_slice_in_dim(tiled, i * tile, tile)
            return jax.lax.dynamic_update_slice_in_dim(
                ys, _expert_ffn(h, stacks, layer, ex[i]), i * tile, 0)

        ys = jax.lax.fori_loop(0, tiles, trip, jnp.zeros_like(tiled))
        return jnp.zeros((n, d), jnp.float32).at[
            jnp.where(src < 0, n, src).reshape(-1)].add(
                gates.reshape(-1, 1) * ys.astype(jnp.float32), mode="drop")

    def kernel(rows, ex, tiles, src, gates):
        whole = [w if layer is not None else w[None] for w in stacks]
        return _fused_ffn_kernel(
            rows, *whole, 0 if layer is None else layer, ex, tiles, src,
            gates, tile=tile)

    args = (rows, ex, tiles, src, gates)
    if not _kernel_takes(rows, stacks[0], tile):
        return loop(*args), tiles
    return jax.lax.platform_dependent(*args, tpu=kernel, default=loop), tiles


# Why `moe_mlp_dropless` is a loop over the experts that were hit where a
# launch is narrow, and groups its rows by expert where it is wide (the
# last table below, PR 46).  Measured on a v5e at Mixtral-8x7B's widths (8
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
# chip's 819).  The visit is never slower than the dense product, also
# where every expert is hit, and needed no Pallas: each product is one
# fusion whose operand is the stack with the slice inside (AOT for a v5e;
# tests/test_tpu_compile.py holds it).  At these widths it stays the form
# of every launch under 256 rows (PR 46's table): eight trips a layer at
# 92% of the bandwidth leave nothing to win until the products of all
# rows by every expert outlast the weights' copy (~240 rows).
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
# win the 8 x and the 7 us: since PR 46 a chunk of 64 rows or more here
# takes that form (128 rows: 16.8 -> 12.2 ms at position 2,048), the
# bursts keep this loop.
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
# 256 rows where 36 are routed to the expert.  Rows grouped by expert win
# both (PR 46: the 256-row chunk 22.0 -> 17.1 ms, its expert ops 15.5 ->
# ~9.5 where the bytes take 8.3).
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
# The baseline ROADMAP S11's chunk half was judged on, at the smallest
# expert the benchmark holds (PR 46: the 512-row chunk 39.3 -> 22.8 ms).
#
# **Rows grouped by expert** (PR 46; `_grouped`): the bare chunk programs
# of the five configurations above at position 2,048 on a v5e (`TPU v5
# lite`, 2026-10-01, two calls; ms a chunk; parent = the visit; "loop" =
# the visit's three fusions on one tile of the sorted rows a trip, the
# rows gathered into tiles before and each choice's result gathered back
# after; "kernel" = one Pallas call a layer, its grid the tiles, on the
# same gathered tiles; "fused" = that kernel picking a tile's rows and
# summing its results itself: what stands; tile = `grouped_tile_rows`):
#
#   rows                      32     64     128    256    512   512 at 0
#   Laguna-XS.2   visit      11.7   16.1   20.5    -     39.3    37.8
#     (6.3 MB)    loop       12.3   15.4   17.9    -     28.9    27.9
#                 kernel     10.0   12.3   14.1    -     24.6    23.2
#                 fused       9.9    -     13.7    -     22.8    21.6
#   granite-4.0-h visit      16.1   18.0   18.1   22.0
#     (18.9 MB)   loop       16.9   18.6   19.2   23.1
#                 kernel     14.8   15.9   16.8   20.2
#                 fused       -     15.0    -     17.1         (17.0 at 0)
#   GLM-4.7-Flash visit       -     14.0   17.8    -     32.3    26.3
#     (18.9 MB)   loop        -     14.7   17.6    -     26.2    22.9
#                 kernel      -     12.3   14.2    -     22.4    18.1
#                 fused       -      -     13.8    -     20.4    16.5
#   Mellum2       visit      13.4   14.9   16.8                 (11.1 at 0)
#     (12.4 MB)   loop       14.0   15.0   15.8
#                 kernel     11.4   12.2   12.9
#                 fused      11.3   11.9   12.2                  (9.2 at 0)
#   Mixtral-8x7B  visit       -     13.3   13.3   14.8   26.6    25.2
#     (352 MB)    loop        -     13.7   14.4   14.7   17.2    18.2
#                 kernel      -     13.9   14.6   14.8   16.4    17.4
#                 fused       -     14.3   14.3   14.1   16.7    17.8
#
# Tiles half / twice the rule's (kernel; the loop moves more): Laguna at
# 512 rows 24.8 / 26.1 against 24.6, granite at 256 rows 20.1 / 21.4
# against 20.2, Mellum at 128 rows 13.3 / 13.4 against 12.9, Mixtral at
# 512 rows 21.1 / 27.0 against 16.4 (a second tile of an expert reads its
# 352 MB again; one tile of all 512 rows is the visit's product).
# What the table says.  (1) **The loop is no form to keep**: a trip of
# three fusions on 32-64 rows still costs 11 us at Laguna's 6.3 MB (7.7 of
# bytes) and 33 us at granite's 18.9 MB (23), and the gathers around it
# ~0.3 ms a layer, so at granite's widths it is slower than the visit.
# It is what a platform without the kernel runs (the CPU's tests), and a
# TPU for rows or weights the kernel cannot take (`_kernel_takes`).  (2)
# The kernel's expert ops run at 85-90% of the weights' bytes (a 512-row
# Laguna chunk 8.9 ms where 1,028 tiles x 6.3 MB x 8 layers take 7.9;
# granite 9.8 for 8.3; GLM 9.2 for 8.1): the next expert's copy runs under
# this tile's products.  (3) With the rows gathered and the results
# summed by XLA around it, a layer paid 0.3-0.4 ms more outside the kernel
# than the visit did (Laguna 12.3 -> 15.2 ms a chunk outside the expert
# ops, granite 6.8 -> 10.7): inside the kernel both are a one-hot product
# a tile on an MXU that waits for the copy anyway, and 1.8-3.1 ms a
# chunk came back.  (4) **The crossover**: from 64 rows on wherever the visit
# would send rows x held experts >= 2,048 rows through an expert's
# products.  Under 64 rows lies every burst (Mellum's widest is 32 lanes),
# and a burst's program stays the parent's to the letter; the kernel
# would win a 32-row chunk too (-8 to -15%) and gives it up for that.
# At eight experts (rows x 8 < 2,048: under 256 rows) the visit is 7%
# faster (64 and 128 rows) and from 256 rows on slower (-5%, then -37%).
# What is left over the bytes at the small experts is the sort and the
# tiles' bookkeeping of 4,096-5,120 choices a layer (outside the expert
# ops a fused chunk still spends 1.1-1.3 ms more than the visit's did,
# 0.11-0.17 ms a layer, two gathers of 4,864 scalars 32 us each among
# it; the expert ops themselves 8.7 ms at Laguna's widths, 9.4 at
# granite's) and the grid's steps that do nothing (`m // tile + e` tiles
# are laid out, about half hold rows).
#
# **A model that fills blocks groups from 16 rows on**
# (`MoEConfig.grouped_from_rows`, which `TransformerConfig.moe` sets for a
# `diffusion_block` alone; PR 52): its decode launch is a pass of B
# rows a lane, 32 rows at 8 lanes, which no program before it ran, so there
# is no burst's text to keep; the table above has the kernel 8-15% ahead of
# the visit at 32 rows; and the visit's trip is 12 device ops, of which a
# pass over 128 experts ran 112 a layer: 57,000 device events a burst of
# six passes, 2.7 M in the benchmark's 3 s profile, which then did not stop
# inside the harness's 120 s (my chip run, PR 52, call 1).  Grouped, a
# layer's experts are one kernel call.
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
    rounding; under `cfg.n_groups` > 1 the groups the row kept ride
    behind them, (B, T, top_k + groups_kept)).  With `layer`
    (a traced index) the three expert weights are the stacks of all
    layers (L, E, ..) and the visit slices [layer, expert]: a caller
    inside a scan over layers hands the stacks whole, because a layer's
    (E, ..) slice taken by the scan is copied out, all E experts of it,
    before the loop below can index it (2.8 GB a layer at Mixtral's
    widths; AOT for a v5e, PR 31).

    A visit of the set, not a product over all experts: at decode the
    cost is the bytes of expert weights read, and 1-4 live tokens are
    routed to 2-5 of 8 experts.  **A launch that `grouped_tile_rows`
    gives no tile (every decode burst, a narrow chunk, a chunk of a few
    large experts) visits:** the experts that some live row chose come
    first in an order computed from the routing, and a loop of `visited`
    trips slices one expert's three matrices each into its products over
    all rows, each row weighted by its gate for that expert (zero where
    it was not chosen).  An expert no live row chose is never read.
    **Any other launch (a prefill chunk) groups its rows by expert**
    (`_grouped`): every expert would be hit and the visit be the dense
    form, expert by expert, all rows through each; instead the rows'
    choices are sorted by expert and a trip multiplies one tile of
    `grouped_tile_rows` rows by the one expert they chose.  Both compute
    one function (products in the input's dtype, gates and the sum over
    a row's choices float32), from the same stacks, sliced inside the
    products; which one a launch takes follows from its static shapes
    alone.

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
    all are held), from a launch that groups its rows a pair of int32,
    (those choices, the tiles it multiplied): a caller that sums them
    over layers starts from `routed_zero`.  `held=None` traces the very
    operations it traced before there was a share, and a launch that
    visits the very operations it traced before there was a second form.

    **The scoring** is `cfg.scoring`'s (`MoEConfig`): the lines between
    the router's product and `chosen` and nothing after them, so a share,
    `live`, `return_routing` and `return_routed` mean the same under
    either.  "sigmoid" reads `params["router_bias"]` (E,) where the
    parameters have one.  Soft-max scoring with `route_scale` 1 traces
    what it traced before there was a choice.  **With `cfg.n_groups` > 1
    the selection is limited to groups** (`_kept_groups`, in the scope
    `moe_groups`): the selection scores outside a row's `groups_kept`
    best groups are masked before the top-k, and nothing else changes
    (the gates are renormalised over all top_k taken, held or not);
    `return_routed` then hands out three int32 from either form,
    (choices routed here, tiles multiplied or 0, the live rows whose kept
    groups hold an expert held here: the rows that *can* route here).
    One group traces what it traced before there were groups.
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
        pick = probs if bias is None else probs + bias.astype(jnp.float32)
        if cfg.n_groups > 1:
            with jax.named_scope("moe_groups"):
                pick, kept, open_here = _kept_groups(pick, cfg)
        _, expert_idx = jax.lax.top_k(pick, cfg.top_k)
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

    stacks = [params[k] for k in ("w_gate", "w_up", "w_down")]

    def visit(i, acc):
        ex = order[i]
        out_e = _expert_ffn(rows, stacks, layer, ex)
        return acc + jax.lax.dynamic_index_in_dim(w, ex, 1) \
            * out_e.astype(jnp.float32)

    tile = grouped_tile_rows(b * t, cfg)
    if tile:        # (`w` and `order` are the visit's: unused, and dropped)
        out, tiles = _grouped(rows, chosen, gate_vals, stacks, layer,
                              tile=tile)
    else:
        out = jax.lax.fori_loop(0, visited, visit,
                                jnp.zeros((b * t, d), jnp.float32))
    out = out.reshape(b, t, d).astype(dtype)
    res = (out, visited)
    if return_routing:      # under groups the row's kept groups ride behind
        res += (expert_idx if cfg.n_groups == 1 else jnp.concatenate(
            [expert_idx, kept.astype(expert_idx.dtype)], axis=-1),)
    if return_routed:
        routed = jnp.sum(chosen).astype(jnp.int32)
        if cfg.n_groups > 1:
            if live is not None:
                open_here &= (live[:, None] if live.ndim == 1 else live)
            res += (jnp.stack([routed, tiles if tile else jnp.int32(0),
                               jnp.sum(open_here, dtype=jnp.int32)]),)
        else:
            res += (jnp.stack([routed, tiles]) if tile else routed,)
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
