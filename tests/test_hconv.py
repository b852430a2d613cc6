"""Horizontal sibling-conv fusion: the Inception branch convs reading one
bottom run as a single concatenated-output convolution.  Must be
numerically exact vs the unfused path in f32, preserve the full blob map,
and leave gradients identical."""

import numpy as np
import pytest
import jax

from sparknet_tpu import config, models
from sparknet_tpu import net as net_module
from sparknet_tpu.net import JaxNet

# a group minimum above any group's size: the unfused reference
NO_FUSION = 10**6


@pytest.fixture
def hfuse_env(monkeypatch):
    # guard tests use minimal 2-conv fixtures; the production minimum is
    # 3 members (2-way groups measured slower on v5e)
    monkeypatch.setattr(net_module, "HCONV_MIN_MEMBERS", 2)


def _tiny_googlenet():
    return models.load_model("googlenet", batch=2, image=64, classes=7)


def test_plan_finds_inception_groups(hfuse_env):
    net = JaxNet(_tiny_googlenet(), phase="TRAIN")
    assert net._hconv_groups, "no sibling-conv groups found in GoogLeNet"
    fused_members = sum(
        len(g["lis"]) for g in net._hconv_groups.values()
    )
    # every inception block contributes a >=2-member group (1x1 + the
    # 3x3/5x5 reduces read the block input with identical 1x1 geometry)
    assert len(net._hconv_groups) >= 9
    assert fused_members > len(net._hconv_groups)


@pytest.mark.slow
def test_fused_forward_backward_exact(monkeypatch):
    netp = _tiny_googlenet()
    fused = JaxNet(netp, phase="TRAIN")
    monkeypatch.setattr(net_module, "HCONV_MIN_MEMBERS", NO_FUSION)
    base = JaxNet(netp, phase="TRAIN")
    assert not base._hconv_groups and fused._hconv_groups

    params, stats = base.init(0)
    rng = np.random.RandomState(0)
    batch = {
        "data": rng.randn(2, 3, 64, 64).astype(np.float32),
        "label": rng.randint(0, 7, 2).astype(np.float32),
    }

    out_b = base.apply(params, stats, batch, rng=jax.random.PRNGKey(5))
    out_f = fused.apply(params, stats, batch, rng=jax.random.PRNGKey(5))
    np.testing.assert_allclose(
        float(out_b.loss), float(out_f.loss), rtol=1e-5
    )
    # the full named blob map survives fusion (getData parity)
    assert set(out_b.blobs) == set(out_f.blobs)
    for name in out_b.blobs:
        np.testing.assert_allclose(
            np.asarray(out_b.blobs[name]),
            np.asarray(out_f.blobs[name]),
            atol=1e-4,
            rtol=1e-4,
            err_msg=name,
        )

    def loss_fn(net):
        def f(p):
            return net.apply(
                p, stats, batch, rng=jax.random.PRNGKey(5)
            ).loss
        return f

    gb = jax.grad(loss_fn(base))(params)
    gf = jax.grad(loss_fn(fused))(params)
    flat_b, _ = jax.tree_util.tree_flatten(gb)
    flat_f, _ = jax.tree_util.tree_flatten(gf)
    for a, b in zip(flat_b, flat_f):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3
        )


SIBLINGS = """
name: "siblings"
layer { name: "data" type: "HostData" top: "x" top: "label"
  java_data_param { shape { dim: 2 dim: 4 dim: 9 dim: 9 } shape { dim: 2 } } }
layer { name: "ca" type: "Convolution" bottom: "x" top: "a"
  convolution_param { num_output: 3 %(geom)s weight_filler { type: "xavier" }
    bias_filler { type: "gaussian" std: 0.1 } } }
layer { name: "cb" type: "Convolution" bottom: "x" top: "b"
  convolution_param { num_output: 5 %(geom)s weight_filler { type: "xavier" }
    bias_filler { type: "gaussian" std: 0.1 } } }
layer { name: "cc" type: "Convolution" bottom: "x" top: "c"
  convolution_param { num_output: 2 %(geom)s weight_filler { type: "xavier" }
    bias_filler { type: "gaussian" std: 0.1 } } }
layer { name: "cat" type: "Concat" bottom: "a" bottom: "b" bottom: "c" top: "abc" }
layer { name: "relu" type: "ReLU" bottom: "abc" top: "abc" }
layer { name: "fc" type: "InnerProduct" bottom: "abc" top: "logits"
  inner_product_param { num_output: 7 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label"
  top: "loss" }
"""


@pytest.mark.parametrize(
    "geom",
    ["kernel_size: 1", "kernel_size: 3 pad: 1", "kernel_size: 3 stride: 2"],
    ids=["1x1", "3x3-pad1", "3x3-stride2"],
)
def test_three_member_group_matches_unfused(monkeypatch, geom):
    """A group of three siblings fuses at the production minimum and gives
    the unfused net's loss, every named blob and every parameter gradient
    to float32 rounding."""
    netp = config.parse_net_prototxt(SIBLINGS % {"geom": geom})
    fused = JaxNet(netp, phase="TRAIN")
    monkeypatch.setattr(net_module, "HCONV_MIN_MEMBERS", NO_FUSION)
    base = JaxNet(netp, phase="TRAIN")
    assert list(fused._hconv_groups) == [1] and not base._hconv_groups
    assert fused._hconv_groups[1]["sizes"] == [3, 5, 2]

    params, stats = base.init(3)
    rng = np.random.RandomState(4)
    batch = {
        "x": rng.randn(2, 4, 9, 9).astype(np.float32),
        "label": np.array([1, 5], np.float32),
    }

    def run(net):
        def loss(p):
            out = net.apply(p, stats, batch, rng=jax.random.PRNGKey(0))
            return out.loss, out.blobs

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, has_aux=True)(params)

    (loss_b, blobs_b), grads_b = run(base)
    (loss_f, blobs_f), grads_f = run(fused)
    np.testing.assert_allclose(float(loss_f), float(loss_b), rtol=1e-6)
    assert set(blobs_f) == set(blobs_b)
    for name in blobs_b:
        np.testing.assert_allclose(
            np.asarray(blobs_f[name]), np.asarray(blobs_b[name]),
            rtol=1e-6, atol=1e-6, err_msg=name,
        )
    assert set(grads_f) == set(grads_b)
    for layer in grads_b:
        for i, (gf, gb) in enumerate(zip(grads_f[layer], grads_b[layer])):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gb), rtol=1e-5, atol=1e-6,
                err_msg=f"{layer}[{i}]",
            )


def test_member_top_collision_blocks_fusion(hfuse_env):
    """A member's top name legally rebound/read by a layer between the
    leader and the member must block fusion: early production would
    change what that layer sees."""
    NET = """
    name: "m"
    layer { name: "data" type: "HostData" top: "x"
      java_data_param { shape { dim: 2 dim: 3 dim: 8 dim: 8 } } }
    layer { name: "p" type: "Power" bottom: "x" top: "b"
      power_param { scale: 2.0 } }
    layer { name: "ca" type: "Convolution" bottom: "x" top: "a"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    layer { name: "q" type: "Power" bottom: "b" top: "q"
      power_param { shift: 1.0 } }
    layer { name: "cb" type: "Convolution" bottom: "x" top: "b"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    layer { name: "r" type: "Eltwise" bottom: "q" bottom: "q" top: "r" }
    """
    net = JaxNet(config.parse_net_prototxt(NET), phase="TRAIN")
    # cb's top "b" is read by q inside the would-be span -> no fusion
    assert not net._hconv_groups

    params, stats = net.init(0)
    x = np.random.RandomState(2).randn(2, 3, 8, 8).astype(np.float32)
    blobs = net.forward(params, stats, {"x": x})
    # q must see p's "b" (2*x), not cb's conv output
    np.testing.assert_allclose(blobs["q"], 2.0 * x + 1.0, atol=1e-5)
    # and the final "b" is cb's conv output
    w_b, bias_b = [np.asarray(v) for v in params["cb"]]
    manual_b = np.einsum(
        "oc,nchw->nohw", w_b[:, :, 0, 0], x
    ) + bias_b.reshape(1, -1, 1, 1)
    np.testing.assert_allclose(blobs["b"], manual_b, atol=1e-5)


def test_later_rebinding_does_not_corrupt_slice_sizes(hfuse_env):
    """A layer AFTER the fused span that legally rebinds a member's top
    with a different channel count must not change the group's slice
    sizes (sizes come from each member's num_output, not the final
    binding of the name)."""
    NET = """
    name: "m"
    layer { name: "data" type: "HostData" top: "x"
      java_data_param { shape { dim: 2 dim: 3 dim: 8 dim: 8 } } }
    layer { name: "ca" type: "Convolution" bottom: "x" top: "a"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    layer { name: "cb" type: "Convolution" bottom: "x" top: "b"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    layer { name: "cc" type: "Convolution" bottom: "x" top: "c"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    layer { name: "rebind" type: "Convolution" bottom: "b" top: "a"
      convolution_param { num_output: 5 kernel_size: 1
        weight_filler { type: "xavier" } } }
    """
    net = JaxNet(config.parse_net_prototxt(NET), phase="TRAIN")
    assert net._hconv_groups
    (group,) = net._hconv_groups.values()
    assert group["sizes"] == [2, 2, 2]

    params, stats = net.init(0)
    x = np.random.RandomState(3).randn(2, 3, 8, 8).astype(np.float32)
    blobs = net.forward(params, stats, {"x": x})
    assert blobs["a"].shape == (2, 5, 8, 8)  # final binding: rebind's out
    assert blobs["b"].shape == (2, 2, 8, 8)
    w_c, bias_c = [np.asarray(v) for v in params["cc"]]
    manual_c = np.einsum(
        "oc,nchw->nohw", w_c[:, :, 0, 0], x
    ) + bias_c.reshape(1, -1, 1, 1)
    np.testing.assert_allclose(blobs["c"], manual_c, atol=1e-5)


def test_inplace_bottom_rewrite_blocks_fusion(hfuse_env):
    """Two convs reading blob X with an in-place ReLU on X between them
    must NOT fuse (they see different versions of X)."""
    NET = """
    name: "m"
    layer { name: "data" type: "HostData" top: "x"
      java_data_param { shape { dim: 2 dim: 3 dim: 8 dim: 8 } } }
    layer { name: "ca" type: "Convolution" bottom: "x" top: "a"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    layer { name: "rx" type: "ReLU" bottom: "x" top: "x" }
    layer { name: "cb" type: "Convolution" bottom: "x" top: "b"
      convolution_param { num_output: 2 kernel_size: 1
        weight_filler { type: "xavier" } } }
    """
    net = JaxNet(config.parse_net_prototxt(NET), phase="TRAIN")
    assert not net._hconv_groups  # in-place rewrite of x blocks fusion

    params, stats = net.init(0)
    x = np.random.RandomState(1).randn(2, 3, 8, 8).astype(np.float32)
    blobs = net.forward(params, stats, {"x": x})
    # ca saw pre-ReLU x, cb saw post-ReLU x — semantics preserved
    w_a, b_a = [np.asarray(v) for v in params["ca"]]
    manual_a = np.einsum(
        "oc,nchw->nohw", w_a[:, :, 0, 0], x
    ) + b_a.reshape(1, -1, 1, 1)
    np.testing.assert_allclose(blobs["a"], manual_a, atol=1e-5)
    w_b, b_b = [np.asarray(v) for v in params["cb"]]
    manual_b = np.einsum(
        "oc,nchw->nohw", w_b[:, :, 0, 0], np.maximum(x, 0)
    ) + b_b.reshape(1, -1, 1, 1)
    np.testing.assert_allclose(blobs["b"], manual_b, atol=1e-5)
