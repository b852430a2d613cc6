"""Vision layers: convolution, pooling, LRN, im2col, SPP.

TPU-first design notes: there is no im2col+GEMM lowering here (reference:
``caffe/src/caffe/layers/base_conv_layer.cpp:243-295``) — convs go straight
to ``lax.conv_general_dilated`` so XLA tiles them onto the MXU; pooling is
``lax.reduce_window``.  What *is* preserved is the reference's exact shape
arithmetic and numerics: floor conv shapes, Caffe's ceil-mode pooling with
the boundary-window clip, AVE-pool divisors that count the padded ring, and
both LRN normalization regions (``caffe/src/caffe/layers/pooling_layer.cpp``,
``lrn_layer.cpp``).
"""

from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from sparknet_tpu.config.schema import FillerParameter
from sparknet_tpu.ops.base import BlobDef, Layer, Shape, register


def _pair(lst, h_val, w_val, default):
    """Resolve Caffe's repeated-or-h/w spatial params to an (h, w) pair."""
    if h_val or w_val:
        return int(h_val or default), int(w_val or default)
    if isinstance(lst, int):
        return (int(lst or default),) * 2 if lst or default else (default, default)
    if not lst:
        return default, default
    if len(lst) == 1:
        return int(lst[0]), int(lst[0])
    return int(lst[0]), int(lst[1])


class _ConvBase(Layer):
    def _geometry(self, in_shape: Shape):
        cp = self.lp.convolution_param
        kh, kw = _pair(cp.kernel_size, cp.kernel_h, cp.kernel_w, 0)
        sh, sw = _pair(cp.stride, cp.stride_h, cp.stride_w, 1)
        ph, pw = _pair(cp.pad, cp.pad_h, cp.pad_w, 0)
        dh, dw = _pair(cp.dilation, 0, 0, 1)
        if kh <= 0 or kw <= 0:
            raise ValueError(f"layer {self.name!r}: kernel_size required")
        return (kh, kw), (sh, sw), (ph, pw), (dh, dw)

    def _param_mults(self):
        ps = self.lp.param
        w = ps[0] if len(ps) > 0 else None
        b = ps[1] if len(ps) > 1 else None
        return (
            (w.lr_mult if w else 1.0, w.decay_mult if w else 1.0),
            (b.lr_mult if b else 1.0, b.decay_mult if b else 1.0),
        )

    def _checked_out_hw(self, oh: int, ow: int, h: int, w: int):
        if oh <= 0 or ow <= 0:
            raise ValueError(
                f"layer {self.name!r}: output {oh}x{ow} non-positive for "
                f"input {h}x{w}"
            )
        return oh, ow


@register
class Convolution(_ConvBase):
    """2-D convolution, NCHW activations, OIHW weights.

    Weight blob ``(num_output, in_c/group, kh, kw)``; output spatial size is
    ``floor((in + 2p - ((k-1)*d + 1)) / s) + 1`` (reference:
    ``base_conv_layer.cpp`` compute_output_shape).
    """

    TYPE = "Convolution"

    def blob_defs(self, bottom_shapes):
        cp = self.lp.convolution_param
        (kh, kw), _, _, _ = self._geometry(bottom_shapes[0])
        in_c = bottom_shapes[0][1]
        group = max(1, cp.group)
        if in_c % group or cp.num_output % group:
            raise ValueError(f"layer {self.name!r}: channels not divisible by group")
        (wl, wd), (bl, bd) = self._param_mults()
        defs = [
            BlobDef(
                (cp.num_output, in_c // group, kh, kw),
                cp.weight_filler,
                wl,
                wd,
            )
        ]
        if cp.bias_term:
            defs.append(
                BlobDef(
                    (cp.num_output,),
                    cp.bias_filler or FillerParameter(type="constant"),
                    bl,
                    bd,
                )
            )
        return defs

    def out_shapes(self, bottom_shapes):
        cp = self.lp.convolution_param
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = self._geometry(bottom_shapes[0])
        n, _, h, w = bottom_shapes[0]
        oh = (h + 2 * ph - ((kh - 1) * dh + 1)) // sh + 1
        ow = (w + 2 * pw - ((kw - 1) * dw + 1)) // sw + 1
        oh, ow = self._checked_out_hw(oh, ow, h, w)
        return [(n, cp.num_output, oh, ow)]

    def apply(self, blobs, bottoms, rng, train):
        cp = self.lp.convolution_param
        _, (sh, sw), (ph, pw), (dh, dw) = self._geometry(bottoms[0].shape)
        x, w = bottoms[0], blobs[0]
        group = max(1, cp.group)
        y = lax.conv_general_dilated(
            x,
            w,
            window_strides=(sh, sw),
            padding=[(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=group,
        )
        if cp.bias_term:
            y = y + blobs[1][None, :, None, None]
        return [y], None


@register
class Deconvolution(_ConvBase):
    """Transposed convolution — the exact adjoint of Convolution, so weight
    blob is ``(in_c, num_output/group, kh, kw)`` and output spatial size is
    ``s*(in-1) + (k-1)*d + 1 - 2p`` (reference: ``deconv_layer.cpp``)."""

    TYPE = "Deconvolution"

    def blob_defs(self, bottom_shapes):
        cp = self.lp.convolution_param
        (kh, kw), _, _, _ = self._geometry(bottom_shapes[0])
        in_c = bottom_shapes[0][1]
        group = max(1, cp.group)
        if in_c % group or cp.num_output % group:
            raise ValueError(f"layer {self.name!r}: channels not divisible by group")
        (wl, wd), (bl, bd) = self._param_mults()
        defs = [BlobDef((in_c, cp.num_output // group, kh, kw), cp.weight_filler, wl, wd)]
        if cp.bias_term:
            defs.append(
                BlobDef(
                    (cp.num_output,),
                    cp.bias_filler or FillerParameter(type="constant"),
                    bl,
                    bd,
                )
            )
        return defs

    def out_shapes(self, bottom_shapes):
        cp = self.lp.convolution_param
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = self._geometry(bottom_shapes[0])
        n, _, h, w = bottom_shapes[0]
        oh = sh * (h - 1) + (kh - 1) * dh + 1 - 2 * ph
        ow = sw * (w - 1) + (kw - 1) * dw + 1 - 2 * pw
        oh, ow = self._checked_out_hw(oh, ow, h, w)
        return [(n, cp.num_output, oh, ow)]

    def apply(self, blobs, bottoms, rng, train):
        cp = self.lp.convolution_param
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = self._geometry(bottoms[0].shape)
        group = max(1, cp.group)
        w = blobs[0]  # (in_c, out_c/group, kh, kw)
        in_c = w.shape[0]
        # transpose to OIHW with I/O swapped per group, flip spatial taps:
        # deconv(x, w) == conv(x dilated by s, flip(w^T), pad = (k-1)*d - p)
        if group > 1:
            w = w.reshape(group, in_c // group, cp.num_output // group, kh, kw)
            w = jnp.swapaxes(w, 1, 2).reshape(cp.num_output, in_c // group, kh, kw)
        else:
            w = jnp.swapaxes(w, 0, 1)
        w = w[:, :, ::-1, ::-1]
        y = lax.conv_general_dilated(
            bottoms[0],
            w,
            window_strides=(1, 1),
            padding=[
                ((kh - 1) * dh - ph, (kh - 1) * dh - ph),
                ((kw - 1) * dw - pw, (kw - 1) * dw - pw),
            ],
            lhs_dilation=(sh, sw),
            rhs_dilation=(dh, dw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=group,
        )
        if cp.bias_term:
            y = y + blobs[1][None, :, None, None]
        return [y], None


def _pool_geometry(pp, h, w):
    if pp.global_pooling:
        kh, kw = h, w
        sh = sw = 1
        ph = pw = 0
    else:
        kh, kw = _pair(pp.kernel_size, pp.kernel_h, pp.kernel_w, 0)
        sh, sw = _pair(pp.stride, pp.stride_h, pp.stride_w, 1)
        ph, pw = _pair(pp.pad, pp.pad_h, pp.pad_w, 0)
        if kh <= 0 or kw <= 0:
            raise ValueError("pooling kernel_size required")
    oh = int(math.ceil((h + 2 * ph - kh) / sh)) + 1
    ow = int(math.ceil((w + 2 * pw - kw) / sw)) + 1
    if ph or pw:
        # last window must start strictly inside image+pad
        # (reference: pooling_layer.cpp LayerSetUp clip)
        if (oh - 1) * sh >= h + ph:
            oh -= 1
        if (ow - 1) * sw >= w + pw:
            ow -= 1
    if oh <= 0 or ow <= 0:
        raise ValueError(
            f"pooling kernel {kh}x{kw} stride {sh}x{sw} pad {ph}x{pw} "
            f"yields non-positive output for input {h}x{w}"
        )
    return (kh, kw), (sh, sw), (ph, pw), (oh, ow)


def caffe_max_pool(x, kernel, stride, pad, out_hw):
    """Ceil-mode max pooling over NCHW, Caffe shape semantics."""
    (kh, kw), (sh, sw), (ph, pw), (oh, ow) = kernel, stride, pad, out_hw
    h, w = x.shape[2], x.shape[3]
    hi_h = (oh - 1) * sh + kh - h - ph  # may exceed ph due to ceil mode
    hi_w = (ow - 1) * sw + kw - w - pw
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        (1, 1, kh, kw),
        (1, 1, sh, sw),
        [(0, 0), (0, 0), (ph, max(0, hi_h)), (pw, max(0, hi_w))],
    )


def caffe_avg_pool(x, kernel, stride, pad, out_hw):
    """Ceil-mode average pooling; the divisor counts window positions inside
    the pad-extended image (so border averages include the zero pad ring but
    not the ceil-extension), matching the reference exactly."""
    (kh, kw), (sh, sw), (ph, pw), (oh, ow) = kernel, stride, pad, out_hw
    h, w = x.shape[2], x.shape[3]
    hi_h = max(0, (oh - 1) * sh + kh - h - ph)
    hi_w = max(0, (ow - 1) * sw + kw - w - pw)

    def wsum(a, pl_h, pl_w, ph_h, ph_w):
        return lax.reduce_window(
            a,
            0.0,
            lax.add,
            (1, 1, kh, kw),
            (1, 1, sh, sw),
            [(0, 0), (0, 0), (pl_h, ph_h), (pl_w, ph_w)],
        )

    s = wsum(x, ph, pw, hi_h, hi_w)
    ones = jnp.ones((1, 1, h + 2 * ph, w + 2 * pw), dtype=x.dtype)
    div = wsum(ones, 0, 0, max(0, hi_h - ph), max(0, hi_w - pw))
    return s / div


@register
class Pooling(Layer):
    """MAX / AVE / STOCHASTIC pooling (reference: ``pooling_layer.cpp``)."""

    TYPE = "Pooling"

    def out_shapes(self, bottom_shapes):
        n, c, h, w = bottom_shapes[0]
        _, _, _, (oh, ow) = _pool_geometry(self.lp.pooling_param, h, w)
        return [(n, c, oh, ow)]

    def apply(self, blobs, bottoms, rng, train):
        pp = self.lp.pooling_param
        x = bottoms[0]
        kernel, stride, pad, out_hw = _pool_geometry(pp, x.shape[2], x.shape[3])
        method = pp.pool.upper()
        if method == "MAX":
            y = caffe_max_pool(x, kernel, stride, pad, out_hw)
        elif method == "AVE":
            y = caffe_avg_pool(x, kernel, stride, pad, out_hw)
        elif method == "STOCHASTIC":
            y = self._stochastic(x, kernel, stride, pad, out_hw, rng, train)
        else:
            raise ValueError(f"unknown pool method {pp.pool!r}")
        return [y], None

    @staticmethod
    def _stochastic(x, kernel, stride, pad, out_hw, rng, train):
        # reference: cuda-only StochasticPooling; train samples a window
        # element with probability proportional to its value, test takes the
        # activation-weighted average.
        (kh, kw), (sh, sw), (ph, pw), (oh, ow) = kernel, stride, pad, out_hw
        n, c, h, w = x.shape
        patches = lax.conv_general_dilated_patches(
            x,
            (kh, kw),
            (sh, sw),
            [(ph, max(0, (oh - 1) * sh + kh - h - ph)),
             (pw, max(0, (ow - 1) * sw + kw - w - pw))],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )  # (n, c*kh*kw, oh, ow)
        patches = patches.reshape(n, c, kh * kw, oh, ow)
        patches = jnp.maximum(patches, 0.0)
        total = jnp.sum(patches, axis=2, keepdims=True)
        prob = jnp.where(total > 0, patches / jnp.maximum(total, 1e-12), 0.0)
        if train:
            if rng is None:
                raise ValueError("stochastic pooling needs an rng in train mode")
            g = jax.random.uniform(rng, (n, c, 1, oh, ow), dtype=x.dtype)
            cum = jnp.cumsum(prob, axis=2)
            idx = jnp.sum((cum < g).astype(jnp.int32), axis=2, keepdims=True)
            idx = jnp.clip(idx, 0, kh * kw - 1)
            return jnp.take_along_axis(patches, idx, axis=2)[:, :, 0]
        return jnp.sum(prob * patches, axis=2)


def _fast_negpow(s, beta: float):
    """``s ** -beta`` without the transcendental ``pow`` when 4*beta is a
    small integer (every Caffe model zoo LRN uses beta=0.75): composed from
    sqrt/rsqrt/multiplies, which the TPU VPU executes natively.  LRN is the
    headline AlexNet step's biggest non-matmul cost — pow = exp(log) on a
    ~75M-element tensor dominated the step's ablation."""
    q = round(4 * beta)
    if not math.isclose(4 * beta, q) or not 1 <= q <= 8:
        return jnp.power(s, -beta)
    # s^-(q/4) = prod over set bits of q of s^-(1,2,4)/4 etc.; build from
    # r1 = s^-1/4 = rsqrt(sqrt(s))
    r1 = lax.rsqrt(lax.sqrt(s))
    out = None
    p = r1
    while q:
        if q & 1:
            out = p if out is None else out * p
        q >>= 1
        if q:
            p = p * p
    return out


def _lrn_window_sum(v, n: int):
    """Windowed channel sum, window ``n`` centered with Caffe's pre-pad
    (n-1)//2, on an NCHW tensor.  Lowered as pad + n shifted channel
    slices, not ``reduce_window`` — on v5e the shifted-adds form fuses
    into one streaming pass and measures ~25% faster inside the AlexNet
    step (reduce_window-add also lacks reverse-mode support in jax 0.9,
    which is why LRN carries a custom_vjp at all)."""
    pad = (n - 1) // 2
    vp = jnp.pad(v, [(0, 0), (pad, n - 1 - pad), (0, 0), (0, 0)])
    c = v.shape[1]
    out = None
    for d in range(n):
        s = lax.slice_in_dim(vp, d, d + c, axis=1)
        out = s if out is None else out + s
    return out


def _lrn_fwd_res(x, n, alpha, beta, k):
    scale = k + (alpha / n) * _lrn_window_sum(x * x, n)
    p = _fast_negpow(scale, beta)
    y = x * p
    return y, x


def _lrn_fwd(x, n, alpha, beta, k):
    y, res = _lrn_fwd_res(x, n, alpha, beta, k)
    return y, res


def _lrn_bwd(n, alpha, beta, k, x, dy):
    # Caffe's analytic backward (``lrn_layer.cpp`` CrossChannelBackward):
    #   dx_i = p_i*dy_i - (2*alpha*beta/n) * x_i * sum_{j in win(i)}
    #                                          dy_j * x_j * p_j / scale_j
    # one windowed sum + elementwise.  Only ``x`` is saved from the
    # forward; scale/p are recomputed here — LRN sits on the two largest
    # activation tensors of the headline net, so HBM traffic (not VPU
    # arithmetic) is its cost, and recompute beats storing the scale/p
    # residuals (measured ~8.3ms -> ~3ms of the AlexNet iteration, v5e).
    scale = k + (alpha / n) * _lrn_window_sum(x * x, n)
    p = _fast_negpow(scale, beta)
    inner = _lrn_window_sum(dy * x * p / scale, n)
    dx = p * dy - (2.0 * alpha * beta / n) * x * inner
    return (dx,)


# n/alpha/beta/k are static Python scalars (nondiff)
lrn_across_channels = jax.custom_vjp(
    lambda x, n, alpha, beta, k: _lrn_fwd_res(x, n, alpha, beta, k)[0],
    nondiff_argnums=(1, 2, 3, 4),
)
lrn_across_channels.defvjp(_lrn_fwd, _lrn_bwd)


@register
class LRN(Layer):
    """Local response normalization, both norm regions (reference:
    ``lrn_layer.cpp``).  ACROSS_CHANNELS divides alpha by local_size;
    WITHIN_CHANNEL is 1 + alpha * avgpool(x^2) through the AVE-pool path."""

    TYPE = "LRN"

    def out_shapes(self, bottom_shapes):
        return [bottom_shapes[0]]

    def apply(self, blobs, bottoms, rng, train):
        from sparknet_tpu.config.schema import LRNParameter

        p = self.lp.lrn_param or LRNParameter()
        x = bottoms[0]
        n = p.local_size
        if p.norm_region.upper() == "ACROSS_CHANNELS":
            return [
                lrn_across_channels(
                    x, int(n), float(p.alpha), float(p.beta), float(p.k)
                )
            ], None
        # WITHIN_CHANNEL: average pool of squares over an n x n window,
        # stride 1, Caffe-pad (n-1)/2 — then x * (1 + alpha*avg)^-beta
        pad = (n - 1) // 2
        kernel, stride, pads = (n, n), (1, 1), (pad, pad)
        h, w = x.shape[2], x.shape[3]
        _, _, _, out_hw = _pool_geometry(
            _PoolGeom(n, 1, pad), h, w
        )
        avg = caffe_avg_pool(x * x, kernel, stride, pads, out_hw)
        scale = 1.0 + p.alpha * avg
        return [x * jnp.power(scale, -p.beta)], None


class _PoolGeom:
    """Minimal pooling_param stand-in for reusing _pool_geometry."""

    def __init__(self, k, s, p):
        self.global_pooling = False
        self.kernel_size, self.kernel_h, self.kernel_w = k, 0, 0
        self.stride, self.stride_h, self.stride_w = s, 0, 0
        self.pad, self.pad_h, self.pad_w = p, 0, 0


@register
class Im2col(_ConvBase):
    """Explicit patch extraction (reference: ``im2col_layer.cpp``) — only
    needed for parity; real convs never lower through it on TPU."""

    TYPE = "Im2col"

    def out_shapes(self, bottom_shapes):
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = self._geometry(bottom_shapes[0])
        n, c, h, w = bottom_shapes[0]
        oh = (h + 2 * ph - ((kh - 1) * dh + 1)) // sh + 1
        ow = (w + 2 * pw - ((kw - 1) * dw + 1)) // sw + 1
        oh, ow = self._checked_out_hw(oh, ow, h, w)
        return [(n, c * kh * kw, oh, ow)]

    def apply(self, blobs, bottoms, rng, train):
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = self._geometry(bottoms[0].shape)
        y = lax.conv_general_dilated_patches(
            bottoms[0],
            (kh, kw),
            (sh, sw),
            [(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        return [y], None


@register
class SPP(Layer):
    """Spatial pyramid pooling (reference: ``spp_layer.cpp``): pyramid level
    i pools into a 2^i x 2^i grid; flattened outputs concat along channels."""

    TYPE = "SPP"

    def _levels(self, h, w):
        p = self.lp.spp_param
        levels = []
        for i in range(p.pyramid_height):
            bins = 2**i
            kh, kw = int(math.ceil(h / bins)), int(math.ceil(w / bins))
            ph, pw = (kh * bins - h + 1) // 2, (kw * bins - w + 1) // 2
            levels.append((bins, (kh, kw), (kh, kw), (ph, pw)))
        return levels

    def out_shapes(self, bottom_shapes):
        n, c, h, w = bottom_shapes[0]
        total = sum(b * b * c for b, _, _, _ in self._levels(h, w))
        return [(n, total)]

    def apply(self, blobs, bottoms, rng, train):
        x = bottoms[0]
        n, c, h, w = x.shape
        p = self.lp.spp_param
        outs = []
        for bins, kernel, stride, pad in self._levels(h, w):
            _, _, _, out_hw = _pool_geometry(
                _PoolGeom(kernel[0], stride[0], pad[0]), h, w
            )
            if p.pool.upper() == "AVE":
                y = caffe_avg_pool(x, kernel, stride, pad, out_hw)
            else:
                y = caffe_max_pool(x, kernel, stride, pad, out_hw)
            y = y[:, :, :bins, :bins]
            outs.append(y.reshape(n, -1))
        return [jnp.concatenate(outs, axis=1)], None
