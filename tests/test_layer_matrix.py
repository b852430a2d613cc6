"""Exhaustive layer matrix: every registered layer type is exercised in
f32 (finite-difference gradient check where differentiable, forward
otherwise) and bf16 (forward finiteness) — the analog of the reference's
``TestDtypesAndDevices`` typed cross-product that instantiates every
layer test over {float,double} x {CPU,GPU}
(``include/caffe/test/test_caffe_main.hpp:31-72``).

Coverage is *enforced*: the spec table below is checked against
``LAYER_REGISTRY`` at collection time, so a newly registered layer type
fails this module until it declares how it is tested (or why not).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import enable_x64 as jax_enable_x64

from sparknet_tpu import config
from sparknet_tpu.ops import base as ops_base
from sparknet_tpu.ops import attention as _attention  # noqa: F401 (registers)
from sparknet_tpu.ops.base import create_layer

R = np.random.RandomState(42)


def _away_from_zero(x, margin=0.15):
    return x + np.sign(x) * margin


def _probs(shape):
    z = np.exp(R.randn(*shape))
    p = z / z.sum(axis=1, keepdims=True)
    return np.clip(p, 0.05, 1.0)


# Every entry: proto body (without name), mode, bottoms builder.
# mode: "grad"       — finite-diff check of d(sum tops)/d(bottom0)
#       "param_grad" — finite-diff check w.r.t. blobs[0] (index-fed layers)
#       "forward"    — non-differentiable forward (argmax/threshold/...)
#       "source"     — data source/sink: no bottoms to feed; covered by
#                      the pipeline/e2e suites (reason documented)
SPECS = {
    "AbsVal": dict(
        proto='type: "AbsVal"', mode="grad",
        bottoms=lambda: [_away_from_zero(R.randn(2, 3, 4, 4))],
    ),
    "Accuracy": dict(
        proto='type: "Accuracy"', mode="forward",
        bottoms=lambda: [R.randn(6, 5), R.randint(0, 5, (6,)).astype(float)],
    ),
    "ArgMax": dict(
        proto='type: "ArgMax" argmax_param { top_k: 2 }', mode="forward",
        bottoms=lambda: [R.randn(4, 7)],
    ),
    "Attention": dict(
        # tiny (B,T,E): the finite-diff check loops 2 forwards per input
        # element, and attention's fori_loop trace dominates wall time
        proto='type: "Attention" attention_param { num_heads: 2 }',
        mode="grad", bottoms=lambda: [R.randn(1, 4, 4) * 0.5],
    ),
    "BNLL": dict(
        proto='type: "BNLL"', mode="grad",
        bottoms=lambda: [R.randn(3, 4)],
    ),
    "BatchNorm": dict(
        proto='type: "BatchNorm"', mode="grad", train=True,
        bottoms=lambda: [R.randn(4, 3, 5, 5)],
    ),
    "BatchReindex": dict(
        proto='type: "BatchReindex"', mode="grad",
        bottoms=lambda: [R.randn(4, 3), R.randint(0, 4, (6,)).astype(float)],
    ),
    "Bias": dict(
        proto='type: "Bias"', mode="grad",
        bottoms=lambda: [R.randn(2, 3, 4, 4)],
    ),
    "Concat": dict(
        proto='type: "Concat" concat_param { axis: 1 }', mode="grad",
        bottoms=lambda: [R.randn(2, 3, 4, 4), R.randn(2, 5, 4, 4)],
    ),
    "ContrastiveLoss": dict(
        proto='type: "ContrastiveLoss"', mode="grad",
        bottoms=lambda: [
            R.randn(4, 2), R.randn(4, 2), R.randint(0, 2, (4,)).astype(float),
        ],
    ),
    "Convolution": dict(
        proto='type: "Convolution" convolution_param '
              "{ num_output: 2 kernel_size: 3 stride: 2 pad: 1 }",
        mode="grad", bottoms=lambda: [R.randn(2, 3, 5, 5)],
    ),
    "Data": dict(mode="source", reason="native DB pipeline; test_db_apps"),
    "Deconvolution": dict(
        proto='type: "Deconvolution" convolution_param '
              "{ num_output: 2 kernel_size: 3 stride: 2 }",
        mode="grad", bottoms=lambda: [R.randn(2, 3, 4, 4)],
    ),
    "Dropout": dict(
        proto='type: "Dropout" dropout_param { dropout_ratio: 0.5 }',
        mode="grad", train=True, rng=True,
        bottoms=lambda: [R.randn(3, 8)],
    ),
    "DummyData": dict(mode="source", reason="filler-generated; test_layers"),
    "ELU": dict(
        proto='type: "ELU" elu_param { alpha: 0.7 }', mode="grad",
        bottoms=lambda: [_away_from_zero(R.randn(3, 4))],
    ),
    "Eltwise": dict(
        proto='type: "Eltwise" eltwise_param { operation: PROD }',
        mode="grad", bottoms=lambda: [R.randn(2, 5), R.randn(2, 5)],
    ),
    "Embed": dict(
        proto='type: "Embed" embed_param '
              "{ input_dim: 7 num_output: 3 bias_term: true }",
        mode="param_grad",
        bottoms=lambda: [R.randint(0, 7, (5,)).astype(float)],
    ),
    "EuclideanLoss": dict(
        proto='type: "EuclideanLoss"', mode="grad",
        bottoms=lambda: [R.randn(4, 3), R.randn(4, 3)],
    ),
    "Exp": dict(
        proto='type: "Exp" exp_param { scale: 0.5 shift: 0.1 }',
        mode="grad", bottoms=lambda: [R.randn(3, 4) * 0.5],
    ),
    "Filter": dict(
        proto='type: "Filter"', mode="grad",
        bottoms=lambda: [R.randn(4, 3), R.randint(0, 2, (4,)).astype(float)],
    ),
    "Flatten": dict(
        proto='type: "Flatten"', mode="grad",
        bottoms=lambda: [R.randn(2, 3, 4)],
    ),
    "HDF5Data": dict(mode="source", reason="file-fed; test_examples hdf5"),
    "HDF5Output": dict(mode="source", reason="sink; host-side writer tap"),
    "HingeLoss": dict(
        proto='type: "HingeLoss"', mode="grad", atol=2e-3,
        bottoms=lambda: [
            _away_from_zero(R.randn(5, 4), 0.2),
            R.randint(0, 4, (5,)).astype(float),
        ],
    ),
    "HostData": dict(mode="source", reason="push-fed; every e2e test"),
    "Im2col": dict(
        proto='type: "Im2col" convolution_param '
              "{ kernel_size: 3 stride: 2 pad: 1 }",
        mode="grad", bottoms=lambda: [R.randn(2, 3, 5, 5)],
    ),
    "ImageData": dict(mode="source", reason="listfile-fed; test_examples"),
    "InfogainLoss": dict(
        proto='type: "InfogainLoss"', mode="grad", atol=2e-3,
        bottoms=lambda: [
            _probs((4, 3)),
            R.randint(0, 3, (4,)).astype(float),
            np.abs(R.randn(3, 3)) + 0.1,
        ],
    ),
    "InnerProduct": dict(
        proto='type: "InnerProduct" inner_product_param { num_output: 4 }',
        mode="grad", bottoms=lambda: [R.randn(3, 5)],
    ),
    "Input": dict(mode="source", reason="deploy feed; test_examples rcnn"),
    "JavaData": dict(mode="source", reason="HostData alias; e2e tests"),
    "LRN": dict(
        proto='type: "LRN" lrn_param { local_size: 3 alpha: 0.5 }',
        mode="grad", bottoms=lambda: [R.randn(2, 4, 3, 3)],
    ),
    "Log": dict(
        proto='type: "Log"', mode="grad",
        bottoms=lambda: [np.abs(R.randn(3, 4)) + 0.5],
    ),
    "MVN": dict(
        proto='type: "MVN"', mode="grad", atol=2e-3,
        bottoms=lambda: [R.randn(2, 3, 4, 4)],
    ),
    "MemoryData": dict(mode="source", reason="in-memory feed; test_layers"),
    "MultinomialLogisticLoss": dict(
        proto='type: "MultinomialLogisticLoss"', mode="grad", atol=2e-3,
        bottoms=lambda: [_probs((4, 3)), R.randint(0, 3, (4,)).astype(float)],
    ),
    "PReLU": dict(
        proto='type: "PReLU"', mode="grad",
        bottoms=lambda: [_away_from_zero(R.randn(2, 3, 4, 4))],
    ),
    "Python": dict(
        proto='type: "Python" python_param '
              '{ module: "tests.test_layers" layer: "ScaledIdentity" '
              'param_str: "1.5" }',
        mode="grad", bottoms=lambda: [R.randn(3, 4)],
    ),
    "Pooling": dict(
        proto='type: "Pooling" pooling_param '
              "{ pool: MAX kernel_size: 3 stride: 2 }",
        mode="grad", bottoms=lambda: [R.randn(1, 2, 5, 5) * 2],
    ),
    "Power": dict(
        proto='type: "Power" power_param { power: 2 scale: 0.5 shift: 1 }',
        mode="grad", bottoms=lambda: [R.randn(3, 4) * 0.3],
    ),
    "ReLU": dict(
        proto='type: "ReLU" relu_param { negative_slope: 0.1 }',
        mode="grad", bottoms=lambda: [_away_from_zero(R.randn(3, 4))],
    ),
    "Reduction": dict(
        proto='type: "Reduction" reduction_param '
              "{ operation: SUMSQ axis: 1 coeff: 0.5 }",
        mode="grad", bottoms=lambda: [R.randn(3, 4)],
    ),
    "Reshape": dict(
        proto='type: "Reshape" reshape_param '
              "{ shape { dim: 0 dim: -1 } }",
        mode="grad", bottoms=lambda: [R.randn(2, 3, 4)],
    ),
    "SPP": dict(
        proto='type: "SPP" spp_param { pyramid_height: 2 }',
        mode="grad", bottoms=lambda: [R.randn(2, 2, 6, 6) * 2],
    ),
    "Scale": dict(
        proto='type: "Scale" scale_param { bias_term: true }',
        mode="grad", bottoms=lambda: [R.randn(2, 3, 4, 4)],
    ),
    "Sigmoid": dict(
        proto='type: "Sigmoid"', mode="grad",
        bottoms=lambda: [R.randn(3, 4)],
    ),
    "SigmoidCrossEntropyLoss": dict(
        proto='type: "SigmoidCrossEntropyLoss"', mode="grad",
        bottoms=lambda: [R.randn(4, 3), R.randint(0, 2, (4, 3)).astype(float)],
    ),
    "Silence": dict(
        proto='type: "Silence"', mode="forward",
        bottoms=lambda: [R.randn(2, 3)],
    ),
    "Slice": dict(
        proto='type: "Slice" slice_param { axis: 1 slice_point: 2 }',
        mode="grad", n_top=2, bottoms=lambda: [R.randn(2, 5, 3)],
    ),
    "Softmax": dict(
        proto='type: "Softmax"', mode="grad",
        bottoms=lambda: [R.randn(3, 5)],
    ),
    "SoftmaxWithLoss": dict(
        proto='type: "SoftmaxWithLoss"', mode="grad",
        bottoms=lambda: [R.randn(4, 5), R.randint(0, 5, (4,)).astype(float)],
    ),
    "Split": dict(
        proto='type: "Split"', mode="grad", n_top=2,
        bottoms=lambda: [R.randn(2, 4)],
    ),
    "TanH": dict(
        proto='type: "TanH"', mode="grad",
        bottoms=lambda: [R.randn(3, 4)],
    ),
    "Threshold": dict(
        proto='type: "Threshold" threshold_param { threshold: 0.3 }',
        mode="forward", bottoms=lambda: [R.randn(3, 4)],
    ),
    "Tile": dict(
        proto='type: "Tile" tile_param { axis: 1 tiles: 3 }',
        mode="grad", bottoms=lambda: [R.randn(2, 3)],
    ),
    "WindowData": dict(mode="source", reason="region sampler; test_windows"),
}


def test_every_registered_type_has_a_spec():
    """New layer registrations must declare their matrix coverage."""
    registered = set(ops_base.LAYER_REGISTRY)
    specced = set(SPECS)
    assert registered - specced == set(), (
        f"layer types missing a matrix spec: {sorted(registered - specced)}"
    )
    assert specced - registered == set(), (
        f"stale specs for unregistered types: {sorted(specced - registered)}"
    )


def _build(type_name, spec):
    tops = " ".join(f'top: "t{i}"' for i in range(spec.get("n_top", 1)))
    lp = config.parse(
        f'layer {{ name: "x" {spec["proto"]} {tops} }}', config.NetParameter
    ).layer[0]
    layer = create_layer(lp, "TRAIN" if spec.get("train") else "TEST")
    bottoms = [np.asarray(b) for b in spec["bottoms"]()]
    blobs = layer.init_blobs(
        jax.random.PRNGKey(3), [b.shape for b in bottoms]
    )
    blobs = [
        jnp.asarray(R.randn(*b.shape) * 0.3 + 0.05, jnp.float32)
        if b.dtype != jnp.int32 else b
        for b in blobs
    ]
    rng = jax.random.PRNGKey(11) if spec.get("rng") else None
    return layer, bottoms, blobs, rng


_RUNNABLE = sorted(k for k, s in SPECS.items() if s["mode"] != "source")


@pytest.mark.parametrize("type_name", _RUNNABLE)
def test_f32_matrix(type_name):
    spec = SPECS[type_name]
    layer, bottoms, blobs, rng = _build(type_name, spec)
    train = bool(spec.get("train"))
    atol = spec.get("atol", 5e-4)

    if spec["mode"] == "forward":
        tops, _ = layer.apply(
            blobs, [jnp.asarray(b, jnp.float32) for b in bottoms], rng, train
        )
        for t in tops:
            assert bool(jnp.all(jnp.isfinite(t)))
        return

    from tests.test_layers import _num_grad

    wrt_param = spec["mode"] == "param_grad"
    with jax_enable_x64(True):

        def scalar_out(v):
            if wrt_param:
                bl = [jnp.asarray(v, jnp.float64)] + [
                    jnp.asarray(b, jnp.float64) for b in blobs[1:]
                ]
                bo = [jnp.asarray(b, jnp.float64) for b in bottoms]
            else:
                bl = [jnp.asarray(b, jnp.float64) for b in blobs]
                bo = [jnp.asarray(v, jnp.float64)] + [
                    jnp.asarray(b, jnp.float64) for b in bottoms[1:]
                ]
            tops, _ = layer.apply(bl, bo, rng, train)
            return sum(jnp.sum(t) for t in tops)

        seed = np.asarray(blobs[0] if wrt_param else bottoms[0], np.float64)
        analytic = jax.grad(scalar_out)(jnp.asarray(seed))
        numeric = _num_grad(lambda x: float(scalar_out(x)), seed, eps=1e-5)
    np.testing.assert_allclose(
        np.asarray(analytic), numeric, atol=atol, rtol=1e-3
    )


@pytest.mark.parametrize("type_name", _RUNNABLE)
def test_bf16_forward_matrix(type_name):
    """bf16 is the TPU compute dtype: every layer's forward must accept
    bf16 bottoms and produce finite outputs."""
    spec = SPECS[type_name]
    layer, bottoms, blobs, rng = _build(type_name, spec)
    tops, _ = layer.apply(
        [jnp.asarray(b, jnp.bfloat16) for b in blobs],
        [jnp.asarray(b, jnp.bfloat16) for b in bottoms],
        rng,
        bool(spec.get("train")),
    )
    for t in tops:
        assert bool(jnp.all(jnp.isfinite(t.astype(jnp.float32))))
