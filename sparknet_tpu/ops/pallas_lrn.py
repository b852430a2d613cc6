"""Fused cross-channel LRN kernel in Pallas — the AlexNet hot op.

An ablation of the fused AlexNet training step (another machine, before
the ledger) put LRN at ~23% of it: autodiff through ``reduce_window`` + ``pow``
materializes the squared/summed/scale intermediates in HBM both ways.
This kernel keeps the whole channel window resident in VMEM per
(image, spatial-tile) grid cell and writes only ``y`` forward / ``dx``
backward — the minimum HBM traffic — with the backward recomputing the
normalizer from ``x`` instead of storing residuals (reference analytic
gradient: ``caffe/src/caffe/layers/lrn_layer.cpp`` CrossChannelBackward).

  forward:  scale = k + (alpha/n) * S(x^2);  y = x * scale^-beta
  backward: dx = scale^-beta * dy
               - (2*alpha*beta/n) * x * S(dy * x * scale^-beta / scale)

where S is the centered (pre-pad (n-1)//2) windowed sum across channels.
``scale^-beta`` goes through the sqrt/rsqrt chain (`_fast_negpow`) — no
transcendental ``pow`` for the zoo's beta=0.75.

Layout: the NCHW tensor is viewed as (N, C, H*W); grid is
(N, spatial tiles); each cell sees a (C, TILE_L) block.  The channel
window sum is 5 sublane-shifted adds on the VPU.  Ragged final spatial
tiles read garbage lanes that never get written back (scale >= k > 0
keeps them finite).

On non-TPU backends the kernel runs in interpreter mode so CPU tests pin
it against the XLA reference path bit-for-bit semantics.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# canonical implementation lives beside the XLA LRN path (no cycle:
# vision.py imports this module only lazily inside its env-gated branch)
from sparknet_tpu.ops.vision import _fast_negpow  # noqa: E402


def _window_sum(v, n: int):
    """Centered windowed sum over axis 0 (channels) with Caffe's pre-pad
    (n-1)//2 — n static shifted adds."""
    c = v.shape[0]
    pre = (n - 1) // 2
    post = n - 1 - pre
    acc = v
    for d in range(1, min(post, c - 1) + 1):  # channels i+d (post side)
        acc = acc + jnp.pad(v[d:], ((0, d), (0, 0)))
    for d in range(1, min(pre, c - 1) + 1):  # channels i-d (pre side)
        acc = acc + jnp.pad(v[:-d], ((d, 0), (0, 0)))
    return acc


def _fwd_kernel(x_ref, y_ref, *, n, alpha, beta, k):
    x = x_ref[0].astype(jnp.float32)
    scale = k + (alpha / n) * _window_sum(x * x, n)
    y_ref[0] = (x * _fast_negpow(scale, beta)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, dx_ref, *, n, alpha, beta, k):
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    scale = k + (alpha / n) * _window_sum(x * x, n)
    p = _fast_negpow(scale, beta)
    inner = _window_sum(dy * x * p / scale, n)
    dx = p * dy - (2.0 * alpha * beta / n) * x * inner
    dx_ref[0] = dx.astype(dx_ref.dtype)


_TILE_L = 1024  # lanes per grid cell; C*TILE_L*4B fp32 work set stays << VMEM


def _call(kernel, nchw_shape, dtype, args, n, alpha, beta, k, interpret):
    N, C, H, W = nchw_shape
    L = H * W
    tile = min(_TILE_L, pl.cdiv(L, 128) * 128)
    grid = (N, pl.cdiv(L, tile))
    spec = pl.BlockSpec((1, C, tile), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(
            kernel, n=n, alpha=float(alpha), beta=float(beta), k=float(k)
        ),
        out_shape=jax.ShapeDtypeStruct((N, C, L), dtype),
        grid=grid,
        in_specs=[spec] * len(args),
        out_specs=spec,
        interpret=interpret,
    )(*args).reshape(N, C, H, W)


def _use_interpret(interpret):
    if interpret is None:
        # one source of truth for "kernels lower here" — the shared
        # pallas_attention.lowerable() gate, not a local backend check
        from sparknet_tpu.ops.pallas_attention import lowerable

        return not lowerable()
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_across_channels(x, n, alpha, beta, k, interpret=None):
    """Caffe ACROSS_CHANNELS LRN on an NCHW tensor, fused in Pallas."""
    y, _ = _fwd(x, n, alpha, beta, k, interpret)
    return y


def _fwd(x, n, alpha, beta, k, interpret):
    shape = x.shape
    xr = x.reshape(shape[0], shape[1], -1)
    y = _call(
        _fwd_kernel, shape, x.dtype, (xr,), n, alpha, beta, k,
        _use_interpret(interpret),
    )
    return y, x


def _bwd(n, alpha, beta, k, interpret, x, dy):
    shape = x.shape
    xr = x.reshape(shape[0], shape[1], -1)
    dyr = dy.reshape(shape[0], shape[1], -1)
    dx = _call(
        _bwd_kernel, shape, dy.dtype, (xr, dyr), n, alpha, beta, k,
        _use_interpret(interpret),
    )
    return (dx,)


lrn_across_channels.defvjp(_fwd, _bwd)
