"""ImageNet data-plane tests: loader, ScaleAndConvert, mean computation,
device-side transforms, and the ImageNetApp end-to-end on the mesh.

Mirrors the reference's (disabled) ``ImageNetLoaderSpec`` counting
semantics plus the behaviors pinned in ``ScaleAndConvert.scala`` (corrupt
drop, ragged-tail drop) and ``ComputeMean.scala`` (distributed reduce ==
global mean), which had no tests upstream.
"""

import io
import tarfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu.data import (
    ImageNetLoader,
    ScaleAndConvert,
    compute_mean,
    reduce_mean_sums,
    transforms,
    write_synthetic_imagenet,
)


@pytest.fixture(scope="module")
def synth_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("imagenet"))
    write_synthetic_imagenet(
        root, num_shards=3, images_per_shard=10, classes=4, seed=0
    )
    return root


def test_loader_lists_shards_by_prefix(synth_root):
    loader = ImageNetLoader(synth_root)
    assert len(loader.list_shards("train.")) == 3
    assert len(loader.list_shards("train.00001")) == 1
    assert loader.list_shards("val.") == []


def test_loader_labels_and_tar_stream(synth_root):
    loader = ImageNetLoader(synth_root)
    labels = loader.load_labels("train.txt")
    assert len(labels) == 30
    assert all(0 <= v < 4 for v in labels.values())
    pairs = list(loader.iter_shard(loader.list_shards()[0], labels))
    assert len(pairs) == 10
    jpeg, label = pairs[0]
    assert jpeg[:2] == b"\xff\xd8"  # JPEG SOI marker
    assert isinstance(label, int)


def test_loader_partitions_cover_everything(synth_root):
    loader = ImageNetLoader(synth_root)
    parts = loader.partitions("train.", "train.txt", num_parts=2)
    counts = [sum(1 for _ in p) for p in parts]
    assert sum(counts) == 30
    assert all(c > 0 for c in counts)


def test_scale_and_convert_force_resize(synth_root):
    loader = ImageNetLoader(synth_root)
    labels = loader.load_labels("train.txt")
    conv = ScaleAndConvert(4, 48, 40)
    for data, _ in loader.iter_shard(loader.list_shards()[0], labels):
        img = conv.convert_image(data)
        assert img.shape == (3, 48, 40) and img.dtype == np.uint8
        break


def test_scale_and_convert_drops_corrupt(tmp_path):
    root = str(tmp_path)
    write_synthetic_imagenet(
        root, num_shards=1, images_per_shard=12, corrupt_every=3, seed=1
    )
    loader = ImageNetLoader(root)
    conv = ScaleAndConvert(2, 32, 32)
    pairs = list(
        loader.iter_shard(loader.list_shards()[0], loader.load_labels("train.txt"))
    )
    assert len(pairs) == 12
    mbs = list(conv.make_minibatches(pairs))
    # 4 corrupt dropped -> 8 good -> 4 batches of 2
    assert len(mbs) == 4
    for imgs, lbls in mbs:
        assert imgs.shape == (2, 3, 32, 32) and lbls.shape == (2,)


def test_minibatch_ragged_tail_dropped(synth_root):
    loader = ImageNetLoader(synth_root)
    conv = ScaleAndConvert(4, 32, 32)
    pairs = list(
        loader.iter_shard(loader.list_shards()[0], loader.load_labels("train.txt"))
    )  # 10 images, batch 4 -> 2 batches, tail of 2 dropped
    mbs = list(conv.make_minibatches(pairs))
    assert len(mbs) == 2


def test_compute_mean_matches_direct_and_distributed():
    rng = np.random.RandomState(0)
    images = rng.randint(0, 256, (24, 3, 8, 8)).astype(np.uint8)
    labels = np.zeros(24, np.int32)
    mbs = [(images[i : i + 4], labels[i : i + 4]) for i in range(0, 24, 4)]
    mean, count = compute_mean(iter(mbs))
    assert count == 24
    np.testing.assert_allclose(
        mean, images.astype(np.float64).mean(axis=0), atol=1e-4
    )
    # partition-wise sums reduce to the same mean (ComputeMean.scala:51-57)
    dist = reduce_mean_sums(
        [
            compute_mean(iter(mbs[:2]), return_sum=True),
            compute_mean(iter(mbs[2:]), return_sum=True),
        ]
    )
    np.testing.assert_allclose(dist, mean, atol=1e-5)


def test_train_transform_crop_mean_window():
    """Mean must be subtracted over the *source crop window*
    (data_transformer.cpp:49-58)."""
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (4, 3, 12, 12)).astype(np.uint8)
    mean = rng.rand(3, 12, 12).astype(np.float32) * 100
    fn = transforms.train_transform(mean, crop=8, mirror=False)
    out = np.asarray(fn({"data": imgs}, jax.random.PRNGKey(0))["data"])
    assert out.shape == (4, 3, 8, 8)
    # every output must equal SOME window of (img - mean): recover offsets
    for i in range(4):
        diffs = imgs[i].astype(np.float32) - mean
        found = False
        for ho in range(5):
            for wo in range(5):
                if np.allclose(out[i], diffs[:, ho : ho + 8, wo : wo + 8]):
                    found = True
        assert found, f"image {i}: output is not a mean-subtracted window"


def test_train_transform_mirror_and_randomness():
    imgs = np.arange(2 * 3 * 6 * 6, dtype=np.uint8).reshape(2, 3, 6, 6)
    fn = transforms.train_transform(None, crop=4, mirror=True)
    a = np.asarray(fn({"data": imgs}, jax.random.PRNGKey(0))["data"])
    b = np.asarray(fn({"data": imgs}, jax.random.PRNGKey(1))["data"])
    assert a.shape == (2, 3, 4, 4)
    assert not np.allclose(a, b)  # offsets/flips differ across rngs


def _crop_reference(imgs, mean, crop, mirror, scale, key):
    """The per-image semantics, plainly: window of the frame, the mean at
    the same window, subtract, scale, mirror — in float32, offsets and
    flips from the transform's own ``jax.random`` calls."""
    n, _, h, w = imgs.shape
    k_h, k_w, k_f = jax.random.split(key, 3)
    h_offs = np.asarray(jax.random.randint(k_h, (n,), 0, h - crop + 1))
    w_offs = np.asarray(jax.random.randint(k_w, (n,), 0, w - crop + 1))
    flips = np.asarray(jax.random.bernoulli(k_f, 0.5, (n,)))
    out = np.empty((n, imgs.shape[1], crop, crop), np.float32)
    for i, (ho, wo) in enumerate(zip(h_offs, w_offs)):
        window = imgs[i, :, ho:ho + crop, wo:wo + crop].astype(np.float32)
        if mean is not None:
            full = mean.shape[-2:] != (1, 1)
            window = window - (
                mean[:, ho:ho + crop, wo:wo + crop] if full else mean
            )
        if scale != 1.0:
            window = window * np.float32(scale)
        out[i] = window[:, :, ::-1] if mirror and flips[i] else window
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "shape,crop,mean_kind,mirror,scale",
    [
        ((5, 3, 12, 12), 8, "image", True, 1.0),
        ((5, 3, 12, 12), 8, "channel", True, 1.0),
        ((5, 3, 12, 12), 8, None, True, 1.0),
        ((5, 3, 12, 12), 8, "image", False, 1.0),
        ((5, 3, 12, 12), 8, "image", True, 0.5),
        ((5, 3, 12, 12), 8, "channel", False, 0.5),
        ((4, 3, 10, 14), 7, "image", True, 1.0),  # non-square stored frames
        ((1, 3, 6, 6), 4, "image", True, 1.0),  # batch 1
        ((3, 3, 9, 9), 9, "image", True, 1.0),  # the crop is the frame
    ],
)
def test_train_transform_equals_per_image_reference_exactly(
    shape, crop, mean_kind, mirror, scale, dtype
):
    """Mean, scale and rounding once on the stored frame, crop and mirror
    as one-hot selections: every element equals the per-image reference's
    bits, in float32 and (reference rounded once) in bfloat16."""
    rng = np.random.RandomState(1)
    imgs = rng.randint(0, 256, shape).astype(np.uint8)
    mean = {
        "image": rng.rand(*shape[1:]).astype(np.float32) * 255,
        "channel": np.float32([104.3, 116.7, 122.9])[:, None, None],
        None: None,
    }[mean_kind]
    fn = transforms.train_transform(mean, crop, mirror=mirror, scale=scale)
    for seed in (0, 7):
        key = jax.random.PRNGKey(seed)
        want = _crop_reference(imgs, mean, crop, mirror, scale, key)
        got = jax.jit(lambda b, k: fn(b, k, dtype))({"data": imgs}, key)
        assert got["data"].dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(
            np.asarray(got["data"].astype(jnp.float32)),
            np.asarray(jnp.asarray(want).astype(dtype).astype(jnp.float32)),
        )


_TINY_NET = """
name: "tiny"
layer { name: "data" type: "HostData" top: "data" top: "label"
  java_data_param { shape { dim: 4 dim: 3 dim: 8 dim: 8 } shape { dim: 4 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "c1"
  convolution_param { num_output: 4 kernel_size: 3
    weight_filler { type: "xavier" } } }
layer { name: "fc" type: "InnerProduct" bottom: "c1" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""


def _tiny_solver(extra="", **kw):
    """A solver over 12x12 stored frames cropped to 8, whose net's
    ``loss_fn`` records the ``data`` it is handed."""
    from sparknet_tpu import config
    from sparknet_tpu.solver import Solver

    mean = np.random.RandomState(2).rand(3, 12, 12).astype(np.float32) * 255
    solver = Solver(
        config.parse_solver_prototxt(f'base_lr: 0.01 lr_policy: "fixed" {extra}'),
        net_param=config.parse_net_prototxt(_TINY_NET),
        train_transform=transforms.train_transform(mean, 8),
        **kw,
    )
    seen, loss_fn = [], solver.net.loss_fn

    def recording(params, stats, batch, *rest):
        seen.append(batch["data"])
        return loss_fn(params, stats, batch, *rest)

    solver.net.loss_fn = recording
    return solver, seen


def _tiny_batch(lead=()):
    rng = np.random.RandomState(3)
    return {
        "data": rng.randint(0, 256, lead + (4, 3, 12, 12)).astype(np.uint8),
        "label": rng.randint(0, 5, lead + (4,)).astype(np.float32),
    }


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_solver_hands_the_transform_its_compute_dtype(compute_dtype):
    """Inside the step the crops arrive in the net's compute dtype (conv1's
    cast has nothing left to do); the factory's closure called with two
    arguments, as the benchmark's check calls it, still gives float32."""
    solver, seen = _tiny_solver(compute_dtype=compute_dtype)
    state, batch, key = solver.init_state(0), _tiny_batch(), jax.random.PRNGKey(4)
    solver._grads(state.params, state.stats, batch, key)
    assert seen[0].dtype == jnp.dtype(compute_dtype or "float32")
    assert seen[0].shape == (4, 3, 8, 8)
    plain = solver.train_transform(batch, jax.random.fold_in(key, 0x7F))
    assert plain["data"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(seen[0].astype(jnp.float32)),
        np.asarray(plain["data"].astype(seen[0].dtype).astype(jnp.float32)),
    )
    # any other (batch, rng) -> batch callable is called as it is
    solver.train_transform = lambda b, rng: {
        **b, "data": b["data"][..., :8, :8].astype(np.float32)
    }
    solver._grads(state.params, state.stats, batch, key)
    assert seen[1].dtype == jnp.float32


def test_solver_iter_size_branch_crops_like_the_plain_branch():
    """Microbatch ``i`` of the ``iter_size > 1`` scan gets the crops the
    plain branch gives for ``fold_in(rng, i)``, in the compute dtype."""
    plain, seen_plain = _tiny_solver(compute_dtype="bfloat16")
    micro, seen_micro = _tiny_solver("iter_size: 2", compute_dtype="bfloat16")
    state, batch, key = plain.init_state(0), _tiny_batch((2,)), jax.random.PRNGKey(5)
    with jax.disable_jit():  # the scan runs as a loop: crops are concrete
        micro._grads(state.params, state.stats, batch, key)
    assert len(seen_micro) == 2
    for i, got in enumerate(seen_micro):
        plain._grads(
            state.params, state.stats,
            {k: v[i] for k, v in batch.items()}, jax.random.fold_in(key, i),
        )
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(seen_plain[i].astype(jnp.float32)),
        )
    assert not np.array_equal(
        np.asarray(seen_micro[0], np.float32), np.asarray(seen_micro[1], np.float32)
    )


def test_test_transform_center_crop_golden():
    imgs = np.zeros((1, 1, 6, 6), np.uint8)
    imgs[0, 0, 2, 2] = 100  # center of the 4x4 center crop at (1,1)
    fn = transforms.test_transform(None, crop=4)
    out = np.asarray(fn({"data": imgs})["data"])
    assert out.shape == (1, 1, 4, 4)
    assert out[0, 0, 1, 1] == 100.0
    # deterministic
    np.testing.assert_array_equal(out, np.asarray(fn({"data": imgs})["data"]))


def test_from_transform_param_paths():
    from sparknet_tpu.config.schema import TransformationParameter

    tp = TransformationParameter(crop_size=4, mirror=True, scale=0.5)
    fn = transforms.from_transform_param(tp, phase="TRAIN")
    imgs = np.full((2, 3, 6, 6), 8, np.uint8)
    out = np.asarray(fn({"data": imgs}, jax.random.PRNGKey(0))["data"])
    assert out.shape == (2, 3, 4, 4)
    np.testing.assert_allclose(out, 4.0)  # scale applied
    # identity config -> None
    assert transforms.from_transform_param(TransformationParameter()) is None
    # mean_value per-channel path, no crop
    tp2 = TransformationParameter(mean_value=[1.0, 2.0, 3.0])
    fn2 = transforms.from_transform_param(tp2, phase="TEST")
    out2 = np.asarray(fn2({"data": imgs})["data"])
    np.testing.assert_allclose(out2[0, 0], 7.0)
    np.testing.assert_allclose(out2[0, 2], 5.0)
    # per-channel mean + crop (the standard Caffe config) broadcasts the
    # (C,1,1) mean instead of windowing it
    tp3 = TransformationParameter(crop_size=4, mean_value=[1.0, 2.0, 3.0])
    for phase in ("TRAIN", "TEST"):
        fn3 = transforms.from_transform_param(tp3, phase=phase)
        args3 = ({"data": imgs}, jax.random.PRNGKey(0))[: 2 if phase == "TRAIN" else 1]
        out3 = np.asarray(fn3(*args3)["data"])
        assert out3.shape == (2, 3, 4, 4)
        np.testing.assert_allclose(out3[:, 0], 7.0)
        np.testing.assert_allclose(out3[:, 2], 5.0)


@pytest.mark.slow
def test_imagenet_app_cached_shuffled_epochs_over_http(tmp_path):
    """ISSUE 8 wire-through for the flagship app: tar shards served
    over a fetch-counting HTTP store, fronted by --cache_dir, with
    --shuffle_epochs re-dealing shard ownership mid-run — every shard
    crosses the network exactly ONCE across both epochs."""
    import http.server
    import threading
    import urllib.parse

    from sparknet_tpu.apps import imagenet_app

    root = str(tmp_path / "shards")
    # enough images that every worker keeps >= tau minibatches under
    # any epoch's assignment: 2 workers x batch 4 x (tau 2 + 1)
    write_synthetic_imagenet(
        root, num_shards=2, images_per_shard=24, classes=3, seed=2
    )
    write_synthetic_imagenet(
        root, num_shards=2, images_per_shard=4, classes=3,
        labels_file="val.txt", shard_prefix="val.", seed=3,
    )
    fetches = {}

    class Handler(http.server.SimpleHTTPRequestHandler):
        def __init__(self, *a, **kw):
            super().__init__(*a, directory=root, **kw)

        def log_message(self, *a):
            pass

        def do_GET(self):
            name = urllib.parse.unquote(self.path.lstrip("/"))
            fetches[name] = fetches.get(name, 0) + 1
            return super().do_GET()

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        rc = imagenet_app.main([
            f"--data={url}",
            "--workers=2", "--rounds=2", "--test_every=5",
            "--train_batch=4", "--test_batch=2", "--tau=2",
            "--full_size=64", "--crop=56", "--classes=3",
            "--model=alexnet",
            f"--cache_dir={tmp_path / 'cache'}",
            "--shuffle_epochs=2",
        ])
        assert rc == 0
        # two epochs (reshuffled assignment at round 1) but every train
        # shard streamed off the network exactly once — I/O-flat
        tar_counts = {
            k: v for k, v in fetches.items()
            if k.startswith("train.") and k.endswith(".tar")
        }
        assert len(tar_counts) == 2
        assert all(v == 1 for v in tar_counts.values()), tar_counts
    finally:
        srv.shutdown()


@pytest.mark.slow
def test_imagenet_app_e2e_synthetic_mesh():
    """The flagship driver end-to-end on the virtual mesh: synthetic JPEG
    shards -> tar streaming -> resize -> mean -> device-side crops ->
    tau-averaging rounds -> distributed eval."""
    from sparknet_tpu.apps import imagenet_app

    rc = imagenet_app.main(
        [
            "--workers=2",
            "--rounds=2",
            "--test_every=1",
            "--train_batch=4",
            "--test_batch=2",
            "--tau=2",
            "--full_size=64",
            "--crop=56",
            "--model=alexnet",
        ]
    )
    assert rc == 0
