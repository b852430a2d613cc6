"""InferenceEngine: a deploy net compiled for a fixed set of batch buckets.

The serving analog of ``cmd_classify``'s load path, hardened for a hot
loop: the net is taken to its deploy view (``models.deploy_variant``)
when handed a train/test config, weights load from a ``.caffemodel`` /
``.caffemodel.h5`` (BVLC or snapshot output — io/checkpoint.py writes
the same format) and live as device-resident pytrees, and the jitted
forward is pre-traced at every bucket batch size during ``warmup()`` so
the steady state never sees an XLA compile.  Bucket shapes are static
(the pad-and-mask idiom of ``apps/imagenet_app.py``): a batch of n
requests runs at the smallest bucket >= n, rows beyond n are zero pad
whose outputs are sliced away by the caller.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEFAULT_BUCKETS = (1, 4, 16, 64)


class InferenceEngine:
    """Loads a deploy net and serves jitted forward passes at fixed
    batch-size buckets.

    Parameters
    ----------
    net_param:
        NetParameter (deploy or train/test — the TEST view is derived),
        or a zoo model name.
    weights:
        Optional ``.caffemodel`` / ``.caffemodel.h5`` path.
    buckets:
        Ascending batch-size buckets to pre-compile; requests larger
        than the top bucket are chunked by the caller
        (``infer`` handles that transparently).
    output_blob:
        Blob to serve; defaults to ``"prob"`` when the net names one
        (the BVLC deploy convention), else the last layer's first top.
    compute_dtype:
        e.g. ``"bfloat16"`` for TPU-native inference compute; None keeps
        reference f32 numerics (byte-equal with ``JaxNet.forward``).
    """

    def __init__(
        self,
        net_param,
        weights: Optional[str] = None,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        output_blob: Optional[str] = None,
        compute_dtype: Optional[str] = None,
        seed: int = 0,
    ):
        import jax

        from sparknet_tpu import models
        from sparknet_tpu.net import JaxNet

        if isinstance(net_param, str):
            net_param = models.load_model(net_param)
        if self._config_feed_count(net_param) > 1:
            # train/test config (data+label feeds): take the deploy view
            # (Input data, losses -> prob) exactly like cmd_classify does
            net_param = models.deploy_variant(net_param)
        net = JaxNet(net_param, phase="TEST", compute_dtype=compute_dtype)
        self.net = net
        self.net_param = net_param
        self.data_blob = net.feed_blobs[0]
        # per-item shape: the bucket batch dim replaces the config's
        self.item_shape: Tuple[int, ...] = tuple(
            net.blob_shapes[self.data_blob][1:]
        )
        self.buckets: List[int] = sorted({int(b) for b in buckets})
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive: {buckets}")

        params, stats = net.init(seed)
        if weights:
            from sparknet_tpu.io import caffemodel, checkpoint

            loaded = checkpoint._load_model_blobs(weights)
            params, stats = caffemodel.apply_blobs(net, params, stats, loaded)
        # weights stay device-resident for the life of the engine
        self.params = jax.device_put(params)
        self.stats = jax.device_put(stats)

        if output_blob is not None and output_blob not in net.blob_shapes:
            raise ValueError(
                f"output blob {output_blob!r} not produced by the net; "
                f"have {sorted(net.blob_shapes)}"
            )
        self.output_blob = output_blob or (
            "prob"
            if "prob" in net.blob_shapes
            else net_param.layer[-1].top[0]
        )

        def _forward(params, stats, x):
            return net.forward(params, stats, {self.data_blob: x})[
                self.output_blob
            ]

        self._fwd = jax.jit(_forward)
        # jit dispatch is thread-safe, but serialize forward calls so
        # concurrent callers (batcher worker + direct infer) don't
        # interleave device work unpredictably under load tests
        self._lock = threading.Lock()

    @staticmethod
    def _config_feed_count(net_param) -> int:
        """Host-fed blob count of the TEST view, straight from the
        config — no throwaway JaxNet build (shape inference on a deep
        net is not free at startup)."""
        from sparknet_tpu.config.schema import NetState
        from sparknet_tpu.graph import filter_net
        from sparknet_tpu.ops.base import LAYER_REGISTRY
        from sparknet_tpu.ops.data_layers import _HostFed

        filtered = filter_net(net_param, NetState(phase="TEST"))
        feeds = list(filtered.input)
        for lp in filtered.layer:
            cls = LAYER_REGISTRY.get(lp.type)
            if cls is not None and issubclass(cls, _HostFed):
                feeds.extend(lp.top)
        return len(set(feeds))

    def to_device(self, device) -> None:
        """Commit the weights to ``device``: every forward then runs
        there (a program follows its committed operands), whatever the
        calling thread's default device.  Call before ``warmup()``."""
        import jax

        self.params = jax.device_put(self.params, device)
        self.stats = jax.device_put(self.stats, device)

    @property
    def device(self):
        """The device the weights live on."""
        import jax

        (dev,) = jax.tree_util.tree_leaves(self.params)[0].devices()
        return dev

    # ------------------------------------------------------------------
    # Compilation control
    # ------------------------------------------------------------------
    def warmup(self) -> int:
        """Trace + compile the forward at every bucket size (one XLA
        program per bucket; nothing compiles after this).  Returns the
        jit cache size (== len(buckets))."""
        import jax

        for b in self.buckets:
            x = np.zeros((b,) + self.item_shape, np.float32)
            jax.block_until_ready(self._fwd(self.params, self.stats, x))
        return self.jit_cache_size()

    def jit_cache_size(self) -> int:
        """Number of compiled programs behind the forward fn — stable
        after ``warmup()`` iff no recompiles happened (the serving
        no-recompile invariant; tests and /metrics read this)."""
        return int(self._fwd._cache_size())

    # ------------------------------------------------------------------
    # Bucketing
    # ------------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n, or the max bucket when n exceeds it
        (caller chunks)."""
        if n < 1:
            raise ValueError(f"need at least one item, got {n}")
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def pad_to_bucket(self, x: np.ndarray) -> Tuple[np.ndarray, int]:
        """(x padded with zero rows to the selected bucket, n_real)."""
        n = x.shape[0]
        b = self.bucket_for(n)
        if n == b:
            return x, n
        pad = np.zeros((b - n,) + tuple(x.shape[1:]), x.dtype)
        return np.concatenate([x, pad], axis=0), n

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------
    def run_padded(self, x: np.ndarray) -> np.ndarray:
        """Forward one already-bucket-shaped batch; returns the full
        (bucket-sized) output — callers slice off pad rows."""
        if x.shape[0] not in self.buckets:
            raise ValueError(
                f"batch dim {x.shape[0]} is not a bucket {self.buckets}"
            )
        if tuple(x.shape[1:]) != self.item_shape:
            raise ValueError(
                f"item shape {tuple(x.shape[1:])} != net input "
                f"{self.item_shape}"
            )
        with self._lock:
            out = self._fwd(
                # sparknet: sync-ok(host request payload coerced before the put — x never holds a device array)
                self.params, self.stats, np.asarray(x, np.float32)
            )
        # sparknet: sync-ok(serving D2H: materializing the response rows IS the product)
        return np.asarray(out)

    def infer(self, x: np.ndarray) -> np.ndarray:
        """Single-shot inference for n items (any n >= 1): chunks by the
        max bucket, pads the tail, returns exactly n output rows."""
        # sparknet: sync-ok(host request payload coerced once at the API edge)
        x = np.asarray(x, np.float32)
        if x.ndim == len(self.item_shape):  # single item without batch dim
            x = x[None]
        outs = []
        for i in range(0, x.shape[0], self.max_bucket):
            chunk = x[i : i + self.max_bucket]
            padded, n = self.pad_to_bucket(chunk)
            outs.append(self.run_padded(padded)[:n])
        return np.concatenate(outs, axis=0)
