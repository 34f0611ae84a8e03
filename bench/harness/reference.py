"""The comparison that decides `correct`: what every family is held to.
The plain references themselves are the families' (bench/families/
<family>.py: `forward`, `row_loss`); a family may bring tolerances of
its own (`TOLERANCES`, a dict by the names of the constants below, each
with its measurement and reason beside it as here), and one that brings
none is held to these, which were measured on Mistral-7B and
Mixtral-8x7B.
"""
from __future__ import annotations

import jax.numpy as jnp

F32 = jnp.float32


# ---------------------------------------------------------------------------
# The comparison that decides `correct`.
#
# The served model computes in bfloat16 (8 significant bits: one rounding
# moves a value by up to 2^-8 = 0.4% of itself) and keeps its KV cache in
# bfloat16; the reference computes the same weights in float32.  With
# random weights of variance 1/fan_in the logits have a standard deviation
# near 1.  The measure, per compared position, is the error's root mean
# square over the vocabulary as a share of the reference logits' own.
#
# Dense (measured on the chip, PR 23, Mistral widths, 8 layers, 18
# positions a run: 2 prompts x (the last prefill position + 8 decode
# steps)): 0.0094-0.0100 at every position, every run and every seed.
# Every position must meet LOGITS_REL, 3x that.
#
# With experts a position can differ for a reason that is no error: the
# program computes its router logits in bfloat16, so where the float32
# logits of the last expert taken and the first left out lie closer than
# the program's own rounding, it routes the token to the other expert, and
# that token's logits are then another function's (model-configs guide,
# section 3.3, warns of this for sampled tokens; with random routers it
# reaches the logits).  The reference knows where that can happen without
# asking the program: its own routing margin there (families/mistral.py,
# `_moe_ffn`; infinite where a block has no router) is small
# in some layer.  A position whose margin is at least ROUTER_MARGIN in
# every layer is *decided* and must meet LOGITS_REL_EXPERTS; at least
# MIN_DECIDED positions must be decided; an *undecided* position is held
# to nothing but being finite.
#
# Measured on the chip (PR 23 review round, Mixtral widths, 3 layers, 6
# seeds x 18 positions): six positions were routed otherwise (errors
# 0.16-0.69), at margins 0.001, 0.005, 0.015, 0.021, 0.025 and 0.076;
# every other position read 0.009-0.033, in one run 0.018 in one lane and
# 0.030 in the other.  That is why LOGITS_REL_EXPERTS is wider than
# LOGITS_REL: a position attends over ~260 earlier ones of which some were
# routed otherwise, and their keys and values differ.  It is also why the
# margin is 0.2 and not the 0.02 that rounding alone would give: the same
# 2-3% reaches the router's input, the difference of two logits then
# carries about 0.03-0.045 of their rms, and 0.076 is a two-sigma event of
# that; 0.2 is over four.  About a fifth of the positions are decided.
#
# bench/tests/test_check.py holds this verdict to what it claims, through
# the engine's own programs at a tiny size: it passes the program as it
# is, and fails it with one expert's output dropped, with one layer's
# output dropped, and with the KV cache kept in 8-bit floats.  What it
# cannot catch: a layer computed in bfloat16 where the configuration says
# bfloat16 (the stated dtype is the program's), and a fault that touches
# only undecided positions.  The train check is tighter.
# ---------------------------------------------------------------------------
LOGITS_REL = 0.03
LOGITS_REL_EXPERTS = 0.06
ROUTER_MARGIN = 0.2
MIN_DECIDED = 6
# bf16 compute against f32 reference on the same f32 master weights: the
# loss is a mean over 8 x 4096 positions, so rounding averages out
# (measured on four chips, PR 23: 6e-6).  1e-3 relative is a hundredth of
# what skipping one of eight layers moves it.
LOSS_REL = 1e-3


def tolerances(family=None) -> dict:
    """The constants above by name, with the family's own
    (`TOLERANCES`) over them where it brings any."""
    return {"LOGITS_REL": LOGITS_REL,
            "LOGITS_REL_EXPERTS": LOGITS_REL_EXPERTS,
            "ROUTER_MARGIN": ROUTER_MARGIN, "MIN_DECIDED": MIN_DECIDED,
            "LOSS_REL": LOSS_REL, **getattr(family, "TOLERANCES", {})}


def position_errors(got, want):
    """Per position (row): rms(got - want) / rms(want), float32."""
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return jnp.sqrt(jnp.mean(jnp.square(got - want), axis=-1)
                    / jnp.mean(jnp.square(want), axis=-1))


def logits_verdict(errors, margins, family=None) -> dict:
    """`errors`, `margins`: every compared position's error and routing
    margin (flat lists of equal length).  A model whose reference found
    a router anywhere (a finite margin) is held to the experts' bound,
    one whose margins are all infinite to the dense one.  `family`:
    the module whose tolerances hold (`tolerances`).  `each` lists the
    positions, so that a verdict can be explained from the output
    alone."""
    t = tolerances(family)
    each = sorted((float(m), float(e)) for m, e in zip(margins, errors))
    errs = sorted(e for _, e in each)
    decided = [e for m, e in each if m >= t["ROUTER_MARGIN"]]
    routed = any(m != float("inf") for m, _ in each)
    bound = t["LOGITS_REL_EXPERTS"] if routed else t["LOGITS_REL"]
    finite = all(e == e and e != float("inf") for e in errs)
    return {"positions": len(errs), "decided": len(decided),
            "median": errs[len(errs) // 2], "worst": errs[-1],
            "worst_decided": max(decided, default=None), "bound": bound,
            "finite": finite,
            "ok": bool(finite and len(decided) >= t["MIN_DECIDED"]
                       and max(decided) <= bound),
            "each": [[m if m != float("inf") else None, e]
                     for m, e in each]}
