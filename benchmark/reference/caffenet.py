"""bvlc_reference_caffenet, TRAIN phase, from BVLC Caffe's
``models/bvlc_reference_caffenet/train_val.prototxt``: AlexNet with each
pooling before its normalisation.  Dropout is left out: the check sets the
ratio to 0 in its copy of the program's net description."""

import jax.numpy as jnp

from benchmark.reference import plain_ops as ops

# the layers whose gradients the check compares, and the blob of the logits
FIRST_CONV, LAST_FC = "conv1", "fc8"


def logits(params, x):
    def conv(name, x, **kw):
        w, b = params[name]
        return jnp.maximum(ops.conv2d(x, w, b, **kw), 0.0)

    def fc(name, x):
        w, b = params[name]
        return ops.inner_product(x, w, b)

    x = conv("conv1", x, stride=4)
    x = ops.lrn_across_channels(ops.max_pool(x, 3, 2))
    x = conv("conv2", x, pad=2, group=2)
    x = ops.lrn_across_channels(ops.max_pool(x, 3, 2))
    x = conv("conv3", x, pad=1)
    x = conv("conv4", x, pad=1, group=2)
    x = conv("conv5", x, pad=1, group=2)
    x = ops.max_pool(x, 3, 2)
    x = jnp.maximum(fc("fc6", x), 0.0)
    x = jnp.maximum(fc("fc7", x), 0.0)
    return fc("fc8", x)
