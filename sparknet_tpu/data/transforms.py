"""Device-side batch transforms: the train/test preprocessing closures,
jitted onto the TPU.

The reference preprocesses per image on the host — random/center crop +
mean subtraction in Scala closures (``ImageNetApp.scala:128-180``) or in
``DataTransformer`` C++ (``data_transformer.cpp:19-132``). TPU-first, the
same math runs *inside* the jitted train step on uint8 device batches:
the host stays out of the hot path, and host->device transfers shrink 4x
(uint8 vs float32).  On the v5e the scope ``transform`` takes 0.45 ms of
CaffeNet's 13.14 ms step at batch 256 (``transform_device_ms``, PERF.md
section 5, my chip run, PR 25): XLA fuses the pass over the stored frames
(mean, rounding to the compute dtype) and both one-hot selections into
one convolution fusion of 0.27 ms and copies the bf16 crops once into
conv1's layout, 0.17 ms; nothing walks the batch image by image.  Off
the chip the selections are real arithmetic, 0.17 GFLOP an image: 0.4 s
for a float32 batch of 256 on the sandbox's 8 CPU cores, a few percent of
a CaffeNet step there.

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


def _select_crops(d, h_offs, w_offs, flips, crop: int):
    """Crop (and mirror) every image of ``d`` (N, C, H, W) at its own
    offsets as two one-hot selections on the MXU: ``sel_w[n, w, j]`` is 1
    where source column ``w`` lands in output column ``j`` (the mirror is
    inside it: ``j`` reads ``crop - 1 - j``), ``sel_h[n, i, h]`` where
    source row ``h`` lands in output row ``i``.  Every output element is
    one product ``1 * d`` plus zeros, accumulated in float32, so rounding
    back to ``d.dtype`` returns ``d``'s own bits; float32 needs
    ``precision="highest"`` for that on the TPU (bf16 passes otherwise)."""
    _, _, h, w = d.shape
    j = jnp.arange(crop)
    src_w = w_offs[:, None] + jnp.where(flips[:, None], crop - 1 - j, j)
    src_h = h_offs[:, None] + j
    sel_w = (jnp.arange(w)[None, :, None] == src_w[:, None, :]).astype(d.dtype)
    sel_h = (src_h[:, :, None] == jnp.arange(h)[None, None, :]).astype(d.dtype)
    how = dict(
        preferred_element_type=jnp.float32,
        precision="highest" if d.dtype == jnp.float32 else None,
    )
    cols = jnp.einsum("nchw,nwj->nchj", d, sel_w, **how).astype(d.dtype)
    return jnp.einsum("nih,nchj->ncij", sel_h, cols, **how).astype(d.dtype)


def train_transform(
    mean: Optional[np.ndarray],
    crop: int,
    mirror: bool = True,
    scale: float = 1.0,
    data_key: str = "data",
) -> Callable[..., Batch]:
    """Random crop + mirror + mean-sub closure for TRAIN phase
    (``imageNetTrainPreprocessing``, ImageNetApp.scala:166-180; randomness
    per image, like DataTransformer's per-datum Rand()).

    Mean and scale are elementwise and crop and mirror pure selections, so
    they commute: the mean is subtracted (in float32) and the result
    rounded to ``dtype`` once on the whole stored frame, then
    ``_select_crops`` picks each image's window.  Called as
    ``(batch, rng)`` the closure returns float32 crops; ``Solver`` passes
    its net's compute dtype as ``dtype``, so that the batch is rounded
    here, once, to the very bits the first layer's cast would give."""
    mean_arr = _host_mean(mean)

    def fn(batch: Batch, rng: jax.Array, dtype=jnp.float32) -> Batch:
        imgs = batch[data_key]
        n, _, h, w = imgs.shape
        k_h, k_w, k_f = jax.random.split(rng, 3)
        h_offs = jax.random.randint(k_h, (n,), 0, h - crop + 1)
        w_offs = jax.random.randint(k_w, (n,), 0, w - crop + 1)
        flips = (
            jax.random.bernoulli(k_f, 0.5, (n,))
            if mirror
            else jnp.zeros((n,), bool)
        )
        d = imgs.astype(jnp.float32)
        if mean_arr is not None:
            d = d - mean_arr  # (C, H, W) or (C, 1, 1): broadcasts over N
        if scale != 1.0:
            d = d * scale
        new = dict(batch)
        new[data_key] = _select_crops(
            d.astype(dtype), h_offs, w_offs, flips, crop
        )
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
