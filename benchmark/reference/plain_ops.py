"""The few operations the plain references share, as the Caffe layers define
them.  No kernels, no fusion, no dtype tricks: float32 throughout, and the
caller runs them under ``jax.default_matmul_precision("highest")`` because a
TPU multiplies float32 in bf16 passes otherwise."""

import jax
import jax.numpy as jnp
from jax import lax


def conv2d(x, w, b=None, stride=1, pad=0, group=1):
    """NCHW input, OIHW weights (O, I/group, kh, kw): convolution_layer.cpp."""
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=group,
    )
    return y if b is None else y + b.reshape(1, -1, 1, 1)


def max_pool(x, k, stride):
    """pooling_layer.cpp without pad: ceil((size - k) / stride) + 1 windows,
    the last of which may hang over the edge."""
    h, w = x.shape[2:]
    oh = -(-(h - k) // stride) + 1
    ow = -(-(w - k) // stride) + 1
    over_h = (oh - 1) * stride + k - h
    over_w = (ow - 1) * stride + k - w
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
        [(0, 0), (0, 0), (0, over_h), (0, over_w)],
    )


def lrn_across_channels(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    """lrn_layer.cpp: x / (k + alpha/size * sum of squares over a window of
    ``size`` channels centred on each channel) ** beta."""
    sq = jnp.pad(x * x, [(0, 0), (size // 2, size // 2), (0, 0), (0, 0)])
    c = x.shape[1]
    window = sum(sq[:, d:d + c] for d in range(size))
    return x / jnp.power(k + (alpha / size) * window, beta)


def inner_product(x, w, b):
    """inner_product_layer.cpp: weights are (num_output, dim)."""
    return x.reshape(x.shape[0], -1) @ w.T + b


def batch_norm_train(x, eps=1e-5):
    """batch_norm_layer.cpp in TRAIN: normalise by the batch's own mean and
    biased variance per channel (the moving sums are not used forward)."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def softmax_loss(logits, labels):
    """softmax_loss_layer.cpp: mean over the batch of -log softmax[label]."""
    logp = jax.nn.log_softmax(logits, axis=1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked)


def step(logits_fn, params, x, labels, probes):
    """Loss, logits and the gradients of the ``probes`` (layer names, first
    blob each) of one forward and backward pass, at the highest precision.
    The batch is an argument and not a constant of the compiled program, so
    that another seed finds the program in the compile cache."""

    def loss_fn(p, x, labels):
        out = logits_fn(p, x)
        return softmax_loss(out, labels), out

    with jax.default_matmul_precision("highest"):
        (loss, out), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(params, x, labels)
    return loss, out, [grads[name][0] for name in probes]
