"""The causal depthwise convolution over the sequence that the hybrid LM's
mixers share, and the gated short convolution built on it.

``y[t] = sum_j w[:, j] * x[t - (width - 1) + j]`` for each channel, zeros
before the sequence: a Gated DeltaNet layer runs it at width 4 over its
``[q | k | v]`` channels (then ``silu``), an LFM2 ``conv`` layer at width 3
between its two gates, with no activation.  Float32 whatever the operands'
dtype: ``width`` shifted multiply-adds that XLA fuses into one pass.
"""

from __future__ import annotations

import jax.numpy as jnp

F32 = jnp.float32


def causal_depthwise_conv(x, w):
    """``x``: ``(B, T, C)``; ``w``: ``(C, width)``, the last tap on the
    current token.  Returns ``(B, T, C)`` float32; any ``T >= 1``."""
    t, width = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t].astype(F32) * w[:, j] for j in range(width))


def gated_short_conv(bcu, w):
    """``C * conv(B * u)`` of ``bcu = [B | C | u]`` ``(B, T, 3 E)``, the
    three parts in this order (LFM2's ``in_proj``); ``w``: ``(E, width)``.
    Returns ``(B, T, E)`` float32, what ``out_proj`` reads."""
    gate_in, gate_out, u = (part.astype(F32) for part in jnp.split(bcu, 3, -1))
    return gate_out * causal_depthwise_conv(gate_in * u, w)
