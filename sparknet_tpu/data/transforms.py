"""Device-side batch transforms: the train/test preprocessing closures,
jitted onto the TPU.

The reference preprocesses per image on the host — random/center crop +
mean subtraction in Scala closures (``ImageNetApp.scala:128-180``) or in
``DataTransformer`` C++ (``data_transformer.cpp:19-132``). TPU-first, the
same math runs *inside* the jitted train step on uint8 device batches:
the host stays out of the hot path, and host->device transfers shrink 4x
(uint8 vs float32).  It is not free next to the convs: on the v5e the
scope ``transform`` takes 2.84 ms of CaffeNet's 16.16 ms step at batch
256, 17.6% of the device's time (``transform_device_ms``, PERF.md
section 5, PR 24) -- per-image crop windows as two 256-iteration loops,
a layout copy, the mirror's ``rev``.

Factories return closures with the reference's semantics:

- ``train_transform``: per-image random crop offsets, optional per-image
  mirror, mean subtracted *over the crop window* (the reference indexes the
  mean image by source-window coordinates — data_transformer.cpp:49-58),
  optional scale.
- ``test_transform``: deterministic center crop ((H-crop)/2, like
  ``DataTransformer``; note ``ImageNetApp.scala:131`` hardcodes offset 15
  for 256->227 — one pixel off true center), mean subtracted, no mirror.

Wire them into ``Solver(train_transform=..., test_transform=...)``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Batch = Dict[str, jax.Array]

__all__ = [
    "train_transform",
    "test_transform",
    "finish_host_crops",
    "from_transform_param",
]


def _host_mean(mean):
    """Mean image as a HOST (numpy) array.  A device-resident closure
    constant makes jit lowering fetch its value back — a device->host
    transfer; a numpy constant embeds as an HLO literal with no device
    traffic."""
    return None if mean is None else np.asarray(mean, np.float32)


def finish_host_crops(
    mean: Optional[np.ndarray],
    scale: float = 1.0,
    data_key: str = "data",
) -> Callable[[Batch, jax.Array], Batch]:
    """Device-side finish for the native pipeline's ``u8_output`` mode:
    the host shipped uint8 crop *windows* plus their geometry
    (``h_off``/``w_off``/``flip`` batch keys); this subtracts the mean
    over each image's source window (dynamic-sliced from the full mean
    image — data_transformer.cpp:49-58 semantics), scales, and applies
    the mirror, all fused into the training step.  The rng argument is
    ignored (randomness was drawn on the host, deterministically)."""
    mean_arr = _host_mean(mean)

    def fn(batch: Batch, rng=None) -> Batch:
        x = batch[data_key].astype(jnp.float32)
        crop_h, crop_w = x.shape[-2], x.shape[-1]
        if mean_arr is not None:
            if mean_arr.ndim == 1 or mean_arr.shape[-2:] == (1, 1):
                x = x - mean_arr.reshape(-1, 1, 1)
            else:
                mwin = jax.vmap(
                    lambda ho, wo: jax.lax.dynamic_slice(
                        mean_arr,
                        (0, ho, wo),
                        (mean_arr.shape[0], crop_h, crop_w),
                    )
                )(batch["h_off"], batch["w_off"])
                x = x - mwin
        if scale != 1.0:
            x = x * scale
        flips = batch["flip"].astype(bool)
        x = jnp.where(flips[:, None, None, None], x[..., ::-1], x)
        new = {
            k: v for k, v in batch.items()
            if k not in ("h_off", "w_off", "flip")
        }
        new[data_key] = x
        return new

    return fn


def _crop_one(img, mean, h_off, w_off, crop: int, flip, scale: float):
    """Crop one (C, H, W) image + the mean at the same window, subtract,
    optionally mirror (reference mirrors after transform: the output is
    written flipped, data_transformer.cpp:119-130)."""
    c = img.shape[0]
    window = jax.lax.dynamic_slice(
        img, (0, h_off, w_off), (c, crop, crop)
    ).astype(jnp.float32)
    if mean is not None:
        if mean.shape[-2:] == (1, 1):  # per-channel mean: broadcast
            window = window - mean
        else:  # full mean image: indexed by the source window
            mwin = jax.lax.dynamic_slice(
                mean, (0, h_off, w_off), (c, crop, crop)
            )
            window = window - mwin
    if scale != 1.0:
        window = window * scale
    if flip is not None:
        window = jnp.where(flip, window[:, :, ::-1], window)
    return window


def train_transform(
    mean: Optional[np.ndarray],
    crop: int,
    mirror: bool = True,
    scale: float = 1.0,
    data_key: str = "data",
) -> Callable[[Batch, jax.Array], Batch]:
    """Random crop + mirror + mean-sub closure for TRAIN phase
    (``imageNetTrainPreprocessing``, ImageNetApp.scala:166-180; randomness
    per image, like DataTransformer's per-datum Rand())."""
    mean_arr = _host_mean(mean)

    def fn(batch: Batch, rng: jax.Array) -> Batch:
        imgs = batch[data_key]
        n, c, h, w = imgs.shape
        k_h, k_w, k_f = jax.random.split(rng, 3)
        h_offs = jax.random.randint(k_h, (n,), 0, h - crop + 1)
        w_offs = jax.random.randint(k_w, (n,), 0, w - crop + 1)
        flips = (
            jax.random.bernoulli(k_f, 0.5, (n,))
            if mirror
            else jnp.zeros((n,), bool)
        )
        out = jax.vmap(
            lambda im, ho, wo, fl: _crop_one(
                im, mean_arr, ho, wo, crop, fl, scale
            )
        )(imgs, h_offs, w_offs, flips)
        new = dict(batch)
        new[data_key] = out
        return new

    return fn


def test_transform(
    mean: Optional[np.ndarray],
    crop: int,
    scale: float = 1.0,
    data_key: str = "data",
) -> Callable[[Batch], Batch]:
    """Deterministic center-crop + mean-sub closure for TEST phase
    (``imageNetTestPreprocessing``, ImageNetApp.scala:128-142)."""
    mean_arr = _host_mean(mean)

    def fn(batch: Batch) -> Batch:
        imgs = batch[data_key]
        _, c, h, w = imgs.shape
        h_off = (h - crop) // 2
        w_off = (w - crop) // 2
        out = imgs[:, :, h_off : h_off + crop, w_off : w_off + crop].astype(
            jnp.float32
        )
        if mean_arr is not None:
            if mean_arr.shape[-2:] == (1, 1):  # per-channel mean: broadcast
                out = out - mean_arr
            else:
                out = out - mean_arr[
                    :, h_off : h_off + crop, w_off : w_off + crop
                ]
        if scale != 1.0:
            out = out * scale
        new = dict(batch)
        new[data_key] = out
        return new

    return fn


def from_transform_param(
    tp,
    mean: Optional[np.ndarray] = None,
    phase: str = "TRAIN",
    data_key: str = "data",
):
    """Build the phase's transform closure from a layer's
    ``TransformationParameter`` (crop_size / mirror / scale / mean_value
    / mean_file — proto/caffe.proto TransformationParameter), resolving the
    mean exactly like ``DataTransformer`` (mean_file XOR mean_value,
    data_transformer.cpp:19-47). Returns None when the config implies the
    identity.  TRAIN -> (batch, rng)->batch; TEST -> (batch)->batch."""
    if mean is None:
        if tp.mean_file:
            from sparknet_tpu.io.caffemodel import load_mean_image

            mean = load_mean_image(tp.mean_file)
        elif tp.mean_value:
            mean = np.asarray(tp.mean_value, np.float32)[:, None, None]
    crop = int(tp.crop_size)
    if crop <= 0 and mean is None and tp.scale == 1.0 and not tp.mirror:
        return None
    if crop <= 0:
        # no crop: mean-sub/scale only (mirror needs no window either way)
        def no_crop_train(batch: Batch, rng: jax.Array) -> Batch:
            x = batch[data_key].astype(jnp.float32)
            if mean is not None:
                x = x - jnp.asarray(mean, jnp.float32)
            if tp.scale != 1.0:
                x = x * tp.scale
            if tp.mirror:
                flip = jax.random.bernoulli(
                    rng, 0.5, (x.shape[0],) + (1,) * (x.ndim - 1)
                )
                x = jnp.where(flip, x[..., ::-1], x)
            new = dict(batch)
            new[data_key] = x
            return new

        def no_crop_test(batch: Batch) -> Batch:
            x = batch[data_key].astype(jnp.float32)
            if mean is not None:
                x = x - jnp.asarray(mean, jnp.float32)
            if tp.scale != 1.0:
                x = x * tp.scale
            new = dict(batch)
            new[data_key] = x
            return new

        return no_crop_train if phase == "TRAIN" else no_crop_test
    if phase == "TRAIN":
        return train_transform(
            mean, crop, mirror=tp.mirror, scale=tp.scale, data_key=data_key
        )
    return test_transform(mean, crop, scale=tp.scale, data_key=data_key)
