"""ResNet-50, TRAIN phase, from He et al. (arXiv:1512.03385, table 1) and the
authors' Caffe release: every convolution has no bias and is followed by
BatchNorm (batch statistics) and Scale (gamma, beta); bottleneck blocks are
1x1 / 3x3 / 1x1 with the stride on the first 1x1, and the first block of each
stage projects its shortcut with a strided 1x1.  The program names a
convolution's BatchNorm and Scale ``bn_<conv>`` and ``scale_<conv>``."""

import jax.numpy as jnp

from benchmark.reference import plain_ops as ops

FIRST_CONV, LAST_FC = "conv1", "fc1000"

# (stage, blocks, stride of the stage's first block)
STAGES = ((2, 3, 1), (3, 4, 2), (4, 6, 2), (5, 3, 2))


def logits(params, x):
    def conv_bn(name, x, relu=True, **kw):
        (w,) = params[name]
        gamma, beta = params["scale_" + name]
        y = ops.batch_norm_train(ops.conv2d(x, w, **kw))
        y = y * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)
        return jnp.maximum(y, 0.0) if relu else y

    x = conv_bn("conv1", x, stride=2, pad=3)
    x = ops.max_pool(x, 3, 2)
    for stage, blocks, stride in STAGES:
        for i in range(blocks):
            base = f"res{stage}{'abcdef'[i]}"
            s = stride if i == 0 else 1
            shortcut = x
            if i == 0:
                shortcut = conv_bn(base + "_branch1", x, relu=False, stride=s)
            y = conv_bn(base + "_branch2a", x, stride=s)
            y = conv_bn(base + "_branch2b", y, pad=1)
            y = conv_bn(base + "_branch2c", y, relu=False)
            x = jnp.maximum(shortcut + y, 0.0)
    x = jnp.mean(x, axis=(2, 3))  # global average pool
    w, b = params["fc1000"]
    return ops.inner_product(x, w, b)
