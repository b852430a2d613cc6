"""Layer-zoo tests: shape semantics, golden values, finite-difference
gradient checks.

Mirrors the reference's testing backbone (SURVEY §4.2): the
``GradientChecker`` finite-difference harness (``test_gradient_check_util
.hpp``) becomes a jax.grad-vs-numerical comparison; Caffe-specific shape
rules (ceil pooling, AVE divisors, LRN alpha/n) get golden tests.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import enable_x64 as jax_enable_x64

from sparknet_tpu import config
from sparknet_tpu.net import JaxNet
from sparknet_tpu.ops.base import create_layer
from sparknet_tpu.config.schema import LayerParameter


def _layer(text: str, phase="TRAIN"):
    lp = config.parse(f"layer {{ {text} }}", config.NetParameter).layer[0]
    return create_layer(lp, phase)


def _num_grad(f, x, eps=1e-3):
    x = np.asarray(x, np.float64)
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        i = it.multi_index
        xp, xm = x.copy(), x.copy()
        xp[i] += eps
        xm[i] -= eps
        g[i] = (f(xp) - f(xm)) / (2 * eps)
        it.iternext()
    return g


def check_grad(layer, bottoms, blobs=None, train=True, rng=None, atol=5e-4):
    """Finite-difference check of d(sum of tops)/d(bottom0), in float64 like
    the reference's double-typed GradientChecker instantiations."""
    blobs = blobs or []
    with jax_enable_x64(True):

        def scalar_out(bot0):
            tops, _ = layer.apply(
                [jnp.asarray(b, jnp.float64) for b in blobs],
                [jnp.asarray(bot0, jnp.float64)]
                + [jnp.asarray(b, jnp.float64) for b in bottoms[1:]],
                rng,
                train,
            )
            return sum(jnp.sum(t) for t in tops)

        analytic = jax.grad(scalar_out)(jnp.asarray(bottoms[0], jnp.float64))
        numeric = _num_grad(lambda x: float(scalar_out(x)), bottoms[0], eps=1e-5)
        np.testing.assert_allclose(
            np.asarray(analytic), numeric, atol=atol, rtol=1e-4
        )


# ---------------------------------------------------------------------------
# Shape semantics
# ---------------------------------------------------------------------------


def test_conv_floor_shapes():
    l = _layer(
        'name: "c" type: "Convolution" '
        "convolution_param { num_output: 8 kernel_size: 3 stride: 2 pad: 1 }"
    )
    assert l.out_shapes([(2, 3, 11, 11)]) == [(2, 8, 6, 6)]


def test_pool_ceil_shapes():
    # Caffe ceil mode: 6 -> ceil((6-3)/2)+1 = 3 (floor frameworks give 2)
    l = _layer(
        'name: "p" type: "Pooling" pooling_param { pool: MAX kernel_size: 3 stride: 2 }'
    )
    assert l.out_shapes([(1, 1, 6, 6)]) == [(1, 1, 3, 3)]
    # cifar10_full pool1: 32 -> 16
    assert l.out_shapes([(1, 32, 32, 32)]) == [(1, 32, 16, 16)]


def test_pool_pad_clip_rule():
    # with pad, last window must start inside image+pad:
    # h=4,k=2,s=2,p=1: ceil((4+2-2)/2)+1 = 3; (3-1)*2=4 < 4+1 -> stays 3
    l = _layer(
        'name: "p" type: "Pooling" '
        "pooling_param { pool: AVE kernel_size: 2 stride: 2 pad: 1 }"
    )
    assert l.out_shapes([(1, 1, 4, 4)]) == [(1, 1, 3, 3)]


def test_max_pool_golden():
    l = _layer(
        'name: "p" type: "Pooling" pooling_param { pool: MAX kernel_size: 2 stride: 2 }'
    )
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 1, 4, 4)
    tops, _ = l.apply([], [x], None, True)
    np.testing.assert_allclose(
        np.asarray(tops[0][0, 0]), [[5.0, 7.0], [13.0, 15.0]]
    )


def test_avg_pool_pad_divisor_counts_pad_ring():
    # Caffe AVE with pad: corner window divisor counts positions inside the
    # padded image (here 2x2 window fully inside pad+image => /4, with one
    # real pixel of value 4 and three zeros -> 1.0)
    l = _layer(
        'name: "p" type: "Pooling" '
        "pooling_param { pool: AVE kernel_size: 2 stride: 2 pad: 1 }"
    )
    x = 4.0 * jnp.ones((1, 1, 4, 4), jnp.float32)
    tops, _ = l.apply([], [x], None, True)
    out = np.asarray(tops[0][0, 0])
    assert out[0, 0] == pytest.approx(1.0)  # corner: 1 real pixel / 4
    assert out[1, 1] == pytest.approx(4.0)  # interior: 4 real pixels / 4


def test_inner_product_flatten_order():
    l = _layer(
        'name: "ip" type: "InnerProduct" inner_product_param { num_output: 2 }'
    )
    assert l.out_shapes([(3, 4, 5, 5)]) == [(3, 2)]
    defs = l.blob_defs([(3, 4, 5, 5)])
    assert defs[0].shape == (2, 100)
    assert defs[1].shape == (2,)


def test_deconv_shapes():
    l = _layer(
        'name: "d" type: "Deconvolution" '
        "convolution_param { num_output: 4 kernel_size: 4 stride: 2 pad: 1 }"
    )
    assert l.out_shapes([(1, 8, 5, 5)]) == [(1, 4, 10, 10)]
    assert l.blob_defs([(1, 8, 5, 5)])[0].shape == (8, 4, 4, 4)


def test_slice_concat_roundtrip():
    sl = _layer('name: "s" type: "Slice" top: "a" top: "b" slice_param { axis: 1 }')
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 4, 3)
    tops, _ = sl.apply([], [x], None, True)
    assert tops[0].shape == (2, 2, 3)
    cat = _layer('name: "c" type: "Concat" concat_param { axis: 1 }')
    (y,), _ = cat.apply([], tops, None, True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))


def test_reshape_zero_and_infer():
    l = _layer(
        'name: "r" type: "Reshape" '
        "reshape_param { shape { dim: 0 dim: -1 dim: 2 } }"
    )
    assert l.out_shapes([(3, 4, 6)]) == [(3, 12, 2)]


def test_accuracy_topk():
    l = _layer('name: "a" type: "Accuracy" accuracy_param { top_k: 2 }')
    logits = jnp.asarray(
        [[0.1, 0.5, 0.4], [0.9, 0.05, 0.05], [0.2, 0.3, 0.5]], jnp.float32
    )
    labels = jnp.asarray([2, 1, 2], jnp.float32)
    (acc,), _ = l.apply([], [logits, labels], None, False)
    # top2 hits: sample0 (0.4 is 2nd), sample1 misses? top2 of [0.9,.05,.05]
    # is classes {0,1} -> hit; sample2 hit -> 3/3... label1=1 in top2: yes.
    assert float(acc) == pytest.approx(1.0)
    l1 = _layer('name: "a" type: "Accuracy"')
    (acc1,), _ = l1.apply([], [logits, labels], None, False)
    # top-1: argmaxes are [1, 0, 2] vs labels [2, 1, 2] -> 1 hit of 3
    assert float(acc1) == pytest.approx(1.0 / 3.0)


# ---------------------------------------------------------------------------
# Gradient checks (GradientChecker analog)
# ---------------------------------------------------------------------------

RNG = np.random.RandomState(0)


def test_conv_grad():
    l = _layer(
        'name: "c" type: "Convolution" '
        "convolution_param { num_output: 2 kernel_size: 3 stride: 2 pad: 1 }"
    )
    x = RNG.randn(2, 3, 5, 5).astype(np.float32)
    blobs = l.init_blobs(jax.random.PRNGKey(0), [x.shape])
    blobs = [jnp.asarray(RNG.randn(*b.shape), jnp.float32) * 0.1 for b in blobs]
    check_grad(l, [x], blobs)


def _direct_conv(x, w, stride, pad, group):
    """Convolution as a plain sum over kernel taps in float64 numpy, with
    its input and weight gradients for an output gradient ``g``: the
    independent reference for the Convolution layer."""
    x = np.pad(np.asarray(x, np.float64), ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    w = np.asarray(w, np.float64)
    n, c, hp, wp = x.shape
    o, cg, kh, kw = w.shape
    oh, ow = (hp - kh) // stride + 1, (wp - kw) // stride + 1
    og = o // group

    def taps():
        for gi in range(group):
            ci, oi = slice(gi * cg, (gi + 1) * cg), slice(gi * og, (gi + 1) * og)
            for p in range(kh):
                for q in range(kw):
                    rows = slice(p, p + stride * (oh - 1) + 1, stride)
                    cols = slice(q, q + stride * (ow - 1) + 1, stride)
                    yield ci, oi, p, q, rows, cols

    y = np.zeros((n, o, oh, ow))
    for ci, oi, p, q, rows, cols in taps():
        y[:, oi] += np.einsum("nchw,oc->nohw", x[:, ci, rows, cols], w[oi, :, p, q])

    def grads(g):
        dx, dw = np.zeros_like(x), np.zeros_like(w)
        for ci, oi, p, q, rows, cols in taps():
            dx[:, ci, rows, cols] += np.einsum("nohw,oc->nchw", g[:, oi], w[oi, :, p, q])
            dw[oi, :, p, q] += np.einsum("nohw,nchw->oc", g[:, oi], x[:, ci, rows, cols])
        return dx[:, :, pad : hp - pad, pad : wp - pad], dw

    return y, grads


@pytest.mark.parametrize(
    "kernel,stride,pad,group,cin,cout,hw",
    [
        (11, 4, 0, 1, 3, 8, 27),  # CaffeNet conv1: the thin strided stem
        (5, 1, 2, 2, 6, 4, 7),  # conv2
        (3, 1, 1, 1, 4, 6, 6),  # conv3
        (3, 1, 1, 2, 6, 4, 6),  # conv4, conv5
    ],
    ids=["conv1", "conv2", "conv3", "conv4"],
)
def test_convolution_matches_direct_sum(kernel, stride, pad, group, cin, cout, hw):
    """The Convolution layer at CaffeNet's geometries (kernel, stride, pad,
    group; narrow channels) against the direct sum: output, and the input
    and weight gradients of a random output gradient."""
    l = _layer(
        'name: "c" type: "Convolution" convolution_param { '
        f"num_output: {cout} kernel_size: {kernel} stride: {stride} "
        f"pad: {pad} group: {group} }}"
    )
    rng = np.random.RandomState(kernel * 10 + group)
    x = rng.randn(2, cin, hw, hw).astype(np.float32)
    w = rng.randn(cout, cin // group, kernel, kernel).astype(np.float32)
    b = rng.randn(cout).astype(np.float32)

    def f(x, w):
        (y,), _ = l.apply([w, jnp.asarray(b)], [x], None, True)
        return y

    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(f, jnp.asarray(x), jnp.asarray(w))
        want, grads = _direct_conv(x, w, stride, pad, group)
        want = want + b[None, :, None, None]
        assert y.shape == want.shape
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-4)
        g = rng.randn(*want.shape).astype(np.float32)
        dx, dw = vjp(jnp.asarray(g))
        want_dx, want_dw = grads(np.asarray(g, np.float64))
        np.testing.assert_allclose(np.asarray(dx), want_dx, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(dw), want_dw, rtol=1e-5, atol=1e-4)


def test_pool_grads():
    for pool in ("MAX", "AVE"):
        l = _layer(
            f'name: "p" type: "Pooling" '
            f"pooling_param {{ pool: {pool} kernel_size: 3 stride: 2 pad: 1 }}"
        )
        x = RNG.randn(1, 2, 5, 5).astype(np.float32) * 2
        check_grad(l, [x])


def test_lrn_grads():
    for region in ("ACROSS_CHANNELS", "WITHIN_CHANNEL"):
        l = _layer(
            f'name: "n" type: "LRN" '
            f"lrn_param {{ local_size: 3 alpha: 0.5 beta: 0.75 "
            f"norm_region: {region} }}"
        )
        x = RNG.randn(1, 4, 4, 4).astype(np.float32)
        check_grad(l, [x])


def test_softmax_loss_grad_and_value():
    l = _layer('name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "y"')
    x = RNG.randn(4, 5).astype(np.float32)
    labels = np.array([0, 2, 4, 1], np.float32)

    with jax_enable_x64(True):

        def f(logits):
            tops, _ = l.apply(
                [],
                [jnp.asarray(logits, jnp.float64), jnp.asarray(labels)],
                None,
                True,
            )
            return tops[0]

        analytic = jax.grad(lambda z: f(z))(jnp.asarray(x, jnp.float64))
        numeric = _num_grad(lambda z: float(f(z)), x, eps=1e-5)
        np.testing.assert_allclose(np.asarray(analytic), numeric, atol=1e-6)
    # value matches -mean log softmax at labels
    logp = jax.nn.log_softmax(jnp.asarray(x), axis=1)
    expect = -np.mean([logp[i, int(labels[i])] for i in range(4)])
    assert float(f(x)) == pytest.approx(float(expect), rel=1e-5)


def test_softmax_loss_ignore_label():
    l = _layer(
        'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "y" '
        "loss_param { ignore_label: 1 }"
    )
    x = RNG.randn(4, 5).astype(np.float32)
    labels = np.array([0, 1, 4, 1], np.float32)
    tops, _ = l.apply([], [jnp.asarray(x), jnp.asarray(labels)], None, True)
    logp = jax.nn.log_softmax(jnp.asarray(x), axis=1)
    expect = -(logp[0, 0] + logp[2, 4]) / 2.0  # only 2 valid
    assert float(tops[0]) == pytest.approx(float(expect), rel=1e-5)


def test_batchnorm_train_and_global_stats():
    l = _layer('name: "bn" type: "BatchNorm"')
    x = RNG.randn(8, 3, 2, 2).astype(np.float32) * 3 + 1
    blobs = l.init_blobs(jax.random.PRNGKey(0), [x.shape])
    tops, new_blobs = l.apply(blobs, [jnp.asarray(x)], None, True)
    y = np.asarray(tops[0])
    np.testing.assert_allclose(y.mean(axis=(0, 2, 3)), 0, atol=1e-5)
    np.testing.assert_allclose(y.std(axis=(0, 2, 3)), 1, atol=1e-3)
    # global-stats path: after one update the stored stats are batch mean and
    # bias-corrected variance (scale_factor 1), so expect exactly
    # (x - mean) / sqrt(var * m/(m-1) + eps)
    tops2, _ = l.apply(new_blobs, [jnp.asarray(x)], None, False)
    m = x.shape[0] * x.shape[2] * x.shape[3]
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3)) * m / (m - 1)
    expect = (x - mean[None, :, None, None]) / np.sqrt(
        var[None, :, None, None] + 1e-5
    )
    np.testing.assert_allclose(np.asarray(tops2[0]), expect, atol=1e-4)


def test_dropout_train_scale_and_test_identity():
    l = _layer('name: "d" type: "Dropout" dropout_param { dropout_ratio: 0.4 }')
    x = jnp.ones((1000,), jnp.float32)
    (y,), _ = l.apply([], [x], jax.random.PRNGKey(1), True)
    y = np.asarray(y)
    kept = y > 0
    assert 0.5 < kept.mean() < 0.7
    np.testing.assert_allclose(y[kept], 1.0 / 0.6, rtol=1e-6)
    (yt,), _ = l.apply([], [x], None, False)
    np.testing.assert_allclose(np.asarray(yt), 1.0)


def test_eltwise_ops():
    a = jnp.asarray([1.0, 2.0])
    b = jnp.asarray([3.0, 1.0])
    for op, coeffs, expect in [
        ("SUM", "coeff: 1 coeff: -1", [-2.0, 1.0]),
        ("PROD", "", [3.0, 2.0]),
        ("MAX", "", [3.0, 2.0]),
    ]:
        l = _layer(
            f'name: "e" type: "Eltwise" eltwise_param {{ operation: {op} {coeffs} }}'
        )
        (y,), _ = l.apply([], [a, b], None, True)
        np.testing.assert_allclose(np.asarray(y), expect)


def test_lrn_across_formula():
    # single pixel, 1 channel window n=1: scale = k + alpha*x^2
    l = _layer(
        'name: "n" type: "LRN" lrn_param { local_size: 1 alpha: 2.0 beta: 1.0 k: 1.0 }'
    )
    x = jnp.asarray([[[[2.0]]]])
    (y,), _ = l.apply([], [x], None, True)
    assert float(y[0, 0, 0, 0]) == pytest.approx(2.0 / (1.0 + 2.0 * 4.0))


# ---------------------------------------------------------------------------
# Net-level
# ---------------------------------------------------------------------------

TINY_NET = """
name: "tiny"
layer {
  name: "data" type: "HostData" top: "data" top: "label"
  java_data_param { shape { dim: 4 dim: 3 dim: 8 dim: 8 } shape { dim: 4 } }
}
layer {
  name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3 pad: 1
    weight_filler { type: "xavier" } }
}
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer {
  name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 }
}
layer {
  name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 10 weight_filler { type: "xavier" } }
}
layer {
  name: "loss" type: "SoftmaxWithLoss" bottom: "ip1" bottom: "label" top: "loss"
  include { phase: TRAIN }
}
layer {
  name: "acc" type: "Accuracy" bottom: "ip1" bottom: "label" top: "acc"
  include { phase: TEST }
}
"""


def _tiny_batch():
    rng = np.random.RandomState(1)
    return {
        "data": rng.randn(4, 3, 8, 8).astype(np.float32),
        "label": np.array([1, 3, 5, 7], np.float32),
    }


def test_net_build_and_phases():
    net_param = config.parse_net_prototxt(TINY_NET)
    train = JaxNet(net_param, phase="TRAIN")
    test = JaxNet(net_param, phase="TEST")
    assert "loss" in train.layer_names and "acc" not in train.layer_names
    assert "acc" in test.layer_names and "loss" not in test.layer_names
    assert train.blob_shapes["conv1"] == (4, 4, 8, 8)
    assert train.blob_shapes["pool1"] == (4, 4, 4, 4)
    assert train.blob_shapes["ip1"] == (4, 10)


def test_net_forward_loss_grad():
    net_param = config.parse_net_prototxt(TINY_NET)
    net = JaxNet(net_param, phase="TRAIN")
    params, stats = net.init(seed=0)
    batch = _tiny_batch()
    out = net.apply(params, stats, batch, rng=jax.random.PRNGKey(0))
    assert out.blobs["loss"].shape == ()
    assert float(out.loss) == pytest.approx(float(out.blobs["loss"]))
    # ~chance loss at random init
    assert 1.5 < float(out.loss) < 3.5
    grads = jax.grad(lambda p: net.loss_fn(p, stats, batch)[0])(params)
    gnorm = sum(
        float(jnp.sum(jnp.abs(g))) for gs in grads.values() for g in gs
    )
    assert gnorm > 0


def test_net_weight_sharing():
    shared = """
layer { name: "d" type: "HostData" top: "x"
  java_data_param { shape { dim: 2 dim: 6 } } }
layer { name: "a" type: "InnerProduct" bottom: "x" top: "a"
  param { name: "w" } param { name: "bshared" }
  inner_product_param { num_output: 6 } }
layer { name: "b" type: "InnerProduct" bottom: "a" top: "b"
  param { name: "w" } param { name: "bshared" }
  inner_product_param { num_output: 6 } }
"""
    net = JaxNet(config.parse_net_prototxt(shared), phase="TRAIN")
    params, stats = net.init(0)
    assert "a" in params and "b" not in params  # single storage under owner
    x = {"x": np.ones((2, 6), np.float32)}
    out = net.apply(params, stats, x)
    assert out.blobs["b"].shape == (2, 6)


def test_net_jit_and_dummy_data():
    text = """
layer { name: "d" type: "DummyData" top: "x"
  dummy_data_param { shape { dim: 2 dim: 3 }
    data_filler { type: "constant" value: 2.0 } } }
layer { name: "p" type: "Power" bottom: "x" top: "y"
  power_param { power: 2.0 } }
"""
    net = JaxNet(config.parse_net_prototxt(text), phase="TRAIN")
    params, stats = net.init(0)
    fn = jax.jit(lambda p, s: net.apply(p, s, {}).blobs["y"])
    np.testing.assert_allclose(np.asarray(fn(params, stats)), 4.0)


def test_sparse_gaussian_filler_probability():
    """GaussianFiller sparse: non-zero probability = sparse / num_outputs
    where num_outputs = shape[0] (filler.hpp:76-86)."""
    from sparknet_tpu.config.schema import FillerParameter
    from sparknet_tpu.ops import fillers

    p = FillerParameter(type="gaussian", std=1.0, sparse=5)
    x = np.asarray(
        fillers.fill(jax.random.PRNGKey(0), (10, 1000), p)
    )
    frac = (x != 0).mean()  # expect ~ 5/10 = 0.5
    assert 0.45 < frac < 0.55, frac


def test_lrn_fast_negpow_matches_pow():
    """The sqrt/rsqrt chain used by the LRN normalizer equals ``s**-beta``
    for every quarter-integer beta (and falls back to pow otherwise)."""
    from sparknet_tpu.ops.vision import _fast_negpow

    s = jnp.abs(jnp.asarray(RNG.randn(512), jnp.float32)) + 0.3
    for beta in (0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 2.0, 0.6, 3.14):
        np.testing.assert_allclose(
            np.asarray(_fast_negpow(s, beta)),
            np.asarray(jnp.power(s, -beta)),
            rtol=2e-5,
        )


def test_analytic_flops_alexnet():
    """The MFU flop walk lands on the known AlexNet cost (~1.4 GFLOPs/img
    forward, conv+fc only)."""
    from sparknet_tpu import models
    from sparknet_tpu.config import replace_data_layers
    from sparknet_tpu.net import JaxNet
    from sparknet_tpu.utils import flops

    netp = replace_data_layers(
        models.load_model("alexnet"),
        [(1, 3, 227, 227), (1,)],
        [(1, 3, 227, 227), (1,)],
    )
    net = JaxNet(netp, phase="TRAIN")
    fwd = flops.forward_flops(net)
    assert 1.3e9 < fwd < 1.6e9, fwd
    assert flops.train_flops(net) == 3.0 * fwd


class DoubleIt:  # not a Layer subclass: must be rejected
    pass


from sparknet_tpu.ops.base import Layer as _Layer  # noqa: E402


class ScaledIdentity(_Layer):
    """Test fixture for the Python custom-layer dispatch."""

    TYPE = "ScaledIdentity"

    def out_shapes(self, bottom_shapes):
        return [bottom_shapes[0]]

    def apply(self, blobs, bottoms, rng, train):
        scale = float(self.lp.python_param.param_str or "1")
        return [bottoms[0] * scale], None


def test_python_layer_dispatch():
    """type: "Python" resolves python_param.module/layer to a user Layer
    subclass (python_layer.hpp role); param_str reaches the class."""
    l = _layer(
        'name: "py" type: "Python" python_param '
        '{ module: "tests.test_layers" layer: "ScaledIdentity" '
        'param_str: "2.5" }'
    )
    # pytest imports this file as top-level `test_layers`, while the
    # dispatch imports `tests.test_layers` — same class, two module
    # objects, so compare by identity of behavior/name not isinstance
    assert type(l).__name__ == "ScaledIdentity"
    (out,), _ = l.apply([], [jnp.asarray([1.0, 2.0])], None, True)
    np.testing.assert_allclose(np.asarray(out), [2.5, 5.0])

    with pytest.raises(TypeError, match="Layer subclass"):
        _layer(
            'name: "py" type: "Python" python_param '
            '{ module: "tests.test_layers" layer: "DoubleIt" }'
        )
    with pytest.raises(ValueError, match="cannot import"):
        _layer(
            'name: "py" type: "Python" python_param '
            '{ module: "no.such.module" layer: "X" }'
        )
    with pytest.raises(ValueError, match="need python_param"):
        _layer('name: "py" type: "Python"')


def test_python_layer_in_net():
    from sparknet_tpu import config as _config
    from sparknet_tpu.net import JaxNet as _JaxNet

    NET = """
    layer { name: "d" type: "HostData" top: "x"
      java_data_param { shape { dim: 2 dim: 3 } } }
    layer { name: "py" type: "Python" bottom: "x" top: "y"
      python_param { module: "tests.test_layers" layer: "ScaledIdentity"
        param_str: "3" } }
    layer { name: "red" type: "Reduction" bottom: "y" top: "loss"
      loss_weight: 1.0 reduction_param { operation: MEAN axis: 0 } }
    """
    net = _JaxNet(_config.parse_net_prototxt(NET), phase="TRAIN")
    params, stats = net.init(0)
    x = np.ones((2, 3), np.float32)
    out = net.apply(params, stats, {"x": x}, rng=jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out.blobs["y"]), 3.0 * x)
