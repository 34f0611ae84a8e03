"""The gated short convolution (the mixer of LiquidAI's LFM2, `model_type:
lfm2` / `lfm2_moe`): the only mixing of positions in a `conv` layer.

The layer's projection gives three rows of `d` channels a position,
`[B | C | z]`.  With h_t = B_t * z_t, element by element,

    c_t = sum_{j < J} w_j * h_{t - (J - 1) + j}        a channel, causal,
    y_t = C_t * c_t                                     zeros before the
                                                        sequence's start

so a sequence's whole memory of its past in such a layer is the last J - 1
rows of `h`: what the engine keeps by slot where an attention layer keeps
every position's K and V.  No activation, no bias, no state besides.

`gated_short_conv` is that for any number of rows a launch, continued from
the rows a slot kept (`ops.gated_delta.causal_conv`, which a Gated DeltaNet
layer's convolution over `[q | k | v]` is too): a chunk of a prompt and a
decode step (one row a lane) are the same call.  The gate `B * z` is
rounded once to the operands' dtype, which is what the slot keeps; the
convolution's products and sum and the gate by `C` are float32.  A lane
whose rows are not valid (a padded tail, an idle lane: `n_valid` short of
the launch's rows) gets its kept rows back as they were, to the bit.
"""
from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.ops.gated_delta import causal_conv


def gated_short_conv(rows, bcz, w, n_valid):
    """`bcz` (S, K, 3 c) = [B | C | z] of K positions a lane, `rows`
    (S, J - 1, c) the gated inputs of the J - 1 positions before them, `w`
    (J, c) the depth-wise kernel, `n_valid` (S,) how many of a lane's K
    positions are real (a prefix).  Returns (y (S, K, c) float32, the rows
    to keep: the gated inputs of the last J - 1 real positions)."""
    b, c, z = jnp.split(bcz, 3, axis=-1)
    conv, rows = causal_conv(rows, b * z, w, n_valid)
    return c.astype(jnp.float32) * conv, rows
