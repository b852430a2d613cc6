"""FeaturizerApp — batch feature extraction.

Reference: ``src/main/scala/apps/FeaturizerApp.scala:88-103`` — broadcast
weights once, forward each minibatch, pull a named blob back as an NDArray.
Here ``JaxNet.forward`` returns every blob, so the tap is a dict lookup.

Run:
    python -m sparknet_tpu.apps.featurizer_app --model=NAME --blob=ip1 \
        --data=DIR|DB [--weights=F.caffemodel] [--batches=4] \
        [--out=features.npz]
(real minibatches come from --data or the net's Data-layer source;
--allow_synthetic featurizes random batches for smoke tests only)
"""

from __future__ import annotations

import argparse

import numpy as np


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="cifar10_full")
    parser.add_argument("--blob", default="ip1")
    parser.add_argument("--weights", default=None)
    parser.add_argument("--data", default=None,
                        help="CIFAR binary dir or SNDB path")
    parser.add_argument("--allow_synthetic", action="store_true",
                        help="smoke-test only: featurize random batches")
    parser.add_argument("--batches", type=int, default=4)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    from sparknet_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()

    import jax

    from sparknet_tpu import models
    from sparknet_tpu.data.source import resolve_batches
    from sparknet_tpu.io import caffemodel
    from sparknet_tpu.net import JaxNet

    netp = (
        models.load_model(args.model)
        if not args.model.endswith(".prototxt")
        else __import__("sparknet_tpu.config", fromlist=["load_net_prototxt"])
        .load_net_prototxt(args.model)
    )
    net = JaxNet(netp, phase="TEST")
    params, stats = net.init(0)
    if args.weights:
        loaded = caffemodel.load_weights(args.weights)
        params, stats = caffemodel.apply_blobs(net, params, stats, loaded)

    # real minibatches (FeaturizerApp.scala:88-103 pulls from the RDD)
    stacked = resolve_batches(
        net, netp, args.data, args.batches, phase="TEST",
        allow_synthetic=args.allow_synthetic,
    )
    feats = []
    fwd = jax.jit(net.forward)
    for i in range(args.batches):
        batch = {k: v[i] for k, v in stacked.items()}
        blobs = fwd(params, stats, batch)
        if args.blob not in blobs:
            raise SystemExit(
                f"blob {args.blob!r} not in net; have {sorted(blobs)}"
            )
        feats.append(np.asarray(blobs[args.blob]))
    features = np.stack(feats)
    print(f"extracted {args.blob}: {features.shape}")
    if args.out:
        if args.out.endswith((".h5", ".hdf5")):
            # the HDF5Output layer's role (``hdf5_output_layer.cpp``
            # writes tapped blobs as named datasets): activation taps
            # export in the interchange format
            import h5py

            with h5py.File(args.out, "w") as h:
                h[args.blob] = features
            print(f"wrote {args.out} (HDF5, dataset {args.blob!r})")
        else:
            np.savez(args.out, features=features)
            print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
