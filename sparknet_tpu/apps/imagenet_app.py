"""ImageNetApp — distributed ImageNet training driver (the flagship app).

Reference: ``src/main/scala/apps/ImageNetApp.scala`` — load tar shards from
the bucket, force-resize to 256x256, compute + broadcast the mean image,
then the parameter-averaging loop with tau=50 (``syncInterval``,
``:155``), testing every 10 rounds (``:118``), with per-image random-crop
(train) / center-crop (test) + mean-subtraction preprocessing closures
(``:128-180``).

TPU-native deltas:
- The preprocessing closures run on-device inside the jitted round
  (``sparknet_tpu.data.transforms``); minibatches cross host->device as
  uint8 at full 256x256.
- Broadcast + reduce of weights is the mesh collective inside
  ``ParameterAveragingTrainer.round`` — weights never visit the host.
- The mean image is computed in one streaming pass per partition and
  reduced (``ComputeMean`` semantics), then saved as mean.binaryproto.

Run:
    python -m sparknet_tpu.apps.imagenet_app --data=DIR --workers=4
(DIR holds tar shards + train.txt/val.txt; synthesizes JPEG shards when
--data is omitted)
"""

from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np

TAU = 50  # reference: syncInterval = 50, ImageNetApp.scala:155
FULL_SIZE = 256  # fullHeight/fullWidth, ImageNetApp.scala:23-24
CROP_SIZE = 227  # croppedHeight/croppedWidth, ImageNetApp.scala:25-26


def load_minibatch_partitions(
    loader, prefix: str, labels_file: str, n_workers: int, batch: int,
    height: int, width: int, keep: slice = slice(None),
    epoch=None, shuffle_seed: int = 0,
):
    """Partition shards over workers and pack each partition into uint8
    minibatches (materialized — performance is best if the data fits in
    memory, same caveat as the reference app's .persist()).  ``keep``
    selects which workers' partitions to materialize — a multi-host run
    loads only its own block while every host agrees on the global
    partitioning.  ``epoch`` routes shard ownership through the
    cross-epoch shuffle-by-assignment service (``data/shuffle.py``);
    None keeps the legacy round-robin deal."""
    from sparknet_tpu.data import ScaleAndConvert

    conv = ScaleAndConvert(batch, height, width)
    parts = loader.partitions(
        prefix, labels_file, num_parts=n_workers,
        epoch=epoch, shuffle_seed=shuffle_seed,
    )
    out = []
    for w, part in enumerate(parts):
        if keep != slice(None) and not (keep.start <= w < keep.stop):
            continue
        mbs = list(conv.make_minibatches(part))
        out.append(mbs)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--data", default=None,
                        help="dir with tar shards + train.txt/val.txt")
    parser.add_argument("--train_prefix", default="train.")
    parser.add_argument("--test_prefix", default="val.")
    parser.add_argument("--train_labels", default="train.txt")
    parser.add_argument("--test_labels", default="val.txt")
    parser.add_argument("--model", default="alexnet",
                        help="alexnet | caffenet | googlenet | resnet50")
    parser.add_argument("--workers", type=int, default=0, help="0 = all devices")
    parser.add_argument("--rounds", type=int, default=10)
    parser.add_argument("--tau", type=int, default=0, help="0 = reference (50)")
    parser.add_argument("--test_every", type=int, default=10)
    parser.add_argument("--train_batch", type=int, default=0)
    parser.add_argument("--test_batch", type=int, default=0)
    parser.add_argument("--full_size", type=int, default=0)
    parser.add_argument("--crop", type=int, default=0)
    parser.add_argument("--classes", type=int, default=1000)
    parser.add_argument("--no_mirror", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--cache_dir", default=None,
        help="front the object store with the host-local content-"
        "addressed chunk cache rooted here (data/chunk_cache.py): "
        "epoch 1 fills it, later epochs read local disk — multi-epoch "
        "runs go I/O-flat (only meaningful when --data is a "
        "gs://|s3://|http(s)://|file:// url)",
    )
    parser.add_argument(
        "--cache_bytes", default="0",
        help="chunk-cache LRU byte budget, e.g. 512M / 8G "
        "(0 = unbounded)",
    )
    parser.add_argument(
        "--shuffle_epochs", type=int, default=0,
        help="split --rounds into N epochs and reshuffle shard->worker "
        "ownership between them via the seeded shuffle-by-assignment "
        "service (data/shuffle.py): a global reshuffle moves only the "
        "assignment table, and with --cache_dir the repeat reads hit "
        "the local cache (0/1 = single fixed assignment, the legacy "
        "behavior)",
    )
    from sparknet_tpu import obs
    from sparknet_tpu.io import journal as journal_mod
    from sparknet_tpu.parallel import comm, hierarchy

    obs.add_cli_args(parser)  # --obs / --obs_port / --trace_out
    comm.add_cli_args(parser)  # --compress / --overlap_avg
    hierarchy.add_cli_args(parser)  # --slices / --cross_slice_every / --elastic
    journal_mod.add_cli_args(parser)  # --journal / --no_journal / ...
    args = parser.parse_args(argv)

    from sparknet_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()

    import jax

    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.data import (
        ImageNetLoader,
        MinibatchSampler,
        RoundFeed,
        compute_mean,
        reduce_mean_sums,
        stack_windows,
        transforms,
        write_synthetic_imagenet,
    )
    from sparknet_tpu.apps.scores import primary_accuracy
    from sparknet_tpu.io.caffemodel import save_mean_image
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        local_worker_slice,
        make_mesh,
        shard_leading_global,
    )
    from sparknet_tpu.solver import Solver
    from sparknet_tpu.utils import TrainingLog

    distributed = jax.process_count() > 1
    log = TrainingLog(tag="imagenet", echo=jax.process_index() == 0)
    synthetic = args.data is None
    if synthetic:
        # scaled-down defaults so the offline demo fits one host
        args.train_batch = args.train_batch or 8
        args.test_batch = args.test_batch or 4
        args.tau = args.tau or 4
        args.full_size = args.full_size or 64
        args.crop = args.crop or 56
        # the generator tells classes apart by a bounded channel shift,
        # so it draws at most 4 labels; the net keeps --classes outputs
        label_classes = min(args.classes, 4)
        data_dir = tempfile.mkdtemp(prefix="imagenet_synth_")
        n_shards = max(2, args.workers or jax.local_device_count())
        write_synthetic_imagenet(
            data_dir, num_shards=n_shards,
            images_per_shard=args.train_batch * (args.tau + 1),
            classes=label_classes, seed=args.seed,
        )
        write_synthetic_imagenet(
            data_dir, num_shards=n_shards,
            images_per_shard=args.test_batch * 2, classes=label_classes,
            labels_file="val.txt", shard_prefix="val.", seed=args.seed + 1,
        )
        log.log(f"synthesized JPEG tar shards in {data_dir}")
    else:
        # reference constants (ImageNetApp.scala:20-26)
        args.train_batch = args.train_batch or 256
        args.test_batch = args.test_batch or 50
        args.tau = args.tau or TAU
        args.full_size = args.full_size or FULL_SIZE
        args.crop = args.crop or CROP_SIZE
        data_dir = args.data

    n_workers = args.workers or (
        jax.device_count() if distributed else jax.local_device_count()
    )
    if distributed and n_workers != jax.device_count():
        raise SystemExit("multi-host runs must use --workers == all devices")
    log.log(f"num workers: {n_workers}")

    mesh = make_mesh({"dp": n_workers}, devices=jax.devices()[:n_workers])
    mine = local_worker_slice(mesh) if distributed else slice(0, n_workers)

    from sparknet_tpu.data import chunk_cache

    loader = ImageNetLoader(
        data_dir,
        cache_dir=args.cache_dir,
        cache_bytes=chunk_cache.parse_bytes(args.cache_bytes),
    )
    if loader.cache is not None:
        log.log(
            f"chunk cache at {loader.cache.root} "
            f"(budget {loader.cache.byte_budget or 'unbounded'} bytes)"
        )
    # cross-epoch shuffle-by-assignment: --shuffle_epochs N splits the
    # run into N epochs; each epoch's shard->worker ownership is a
    # seeded permutation pure in (seed, epoch) — the reshuffle moves
    # only the assignment table, and repeat reads hit the chunk cache
    shuffle_on = args.shuffle_epochs > 1
    rounds_per_epoch = (
        -(-args.rounds // args.shuffle_epochs) if shuffle_on else None
    )

    def load_train_parts(epoch):
        return load_minibatch_partitions(
            loader, args.train_prefix, args.train_labels, n_workers,
            args.train_batch, args.full_size, args.full_size, keep=mine,
            epoch=epoch, shuffle_seed=args.seed,
        )

    log.log("loading train data")
    train_parts = load_train_parts(0 if shuffle_on else None)
    log.log("loading test data")
    test_parts = load_minibatch_partitions(
        loader, args.test_prefix, args.test_labels, n_workers,
        args.test_batch, args.full_size, args.full_size, keep=mine,
    )

    def global_sum(n: int) -> int:
        if not distributed:
            return n
        from jax.experimental import multihost_utils

        return int(multihost_utils.process_allgather(np.int64(n)).sum())

    num_train_mbs = global_sum(sum(len(p) for p in train_parts))
    log.log(f"numTrainMinibatches = {num_train_mbs}")
    num_test_mbs = global_sum(sum(len(p) for p in test_parts))
    log.log(f"numTestMinibatches = {num_test_mbs}")
    if min(len(p) for p in train_parts) < args.tau:
        raise SystemExit(
            f"every worker needs >= tau={args.tau} train minibatches; "
            f"partition sizes {[len(p) for p in train_parts]}"
        )
    if min(len(p) for p in test_parts) == 0:
        raise SystemExit(
            f"every worker needs >= 1 test minibatch; partition sizes "
            f"{[len(p) for p in test_parts]} (fewer val shards than "
            f"workers? reduce --workers or add shards)"
        )

    log.log("computing mean image")
    local_sums = [compute_mean(iter(p), return_sum=True) for p in train_parts]
    if distributed:
        # cross-host ComputeMean reduce: allgather every host's (sum,
        # count) partial (one image-sized accumulator per host).  The int64
        # sums ride as hi/lo int32 halves — allgather demotes int64 when
        # x64 is off, and count*255 can exceed int32 on big corpora.
        from jax.experimental import multihost_utils

        total = sum(s for s, _ in local_sums)
        count = sum(c for _, c in local_sums)
        hi = (total >> 20).astype(np.int32)
        lo = (total & ((1 << 20) - 1)).astype(np.int32)
        g_hi, g_lo, g_cnt = multihost_utils.process_allgather(
            (hi, lo, np.int32(count))
        )
        host_totals = (np.asarray(g_hi, np.int64) << 20) + np.asarray(
            g_lo, np.int64
        )
        mean = reduce_mean_sums(
            [(t, int(c)) for t, c in zip(host_totals, np.asarray(g_cnt))]
        )
    else:
        mean = reduce_mean_sums(local_sums)
    # a bucket/HTTP data root is not writable from here: the mean
    # artifact lands next to the cache (or a temp dir) instead
    from sparknet_tpu.data import object_store

    if object_store.is_object_store_url(data_dir):
        mean_dir = (
            loader.cache.root if loader.cache is not None
            else tempfile.mkdtemp(prefix="imagenet_mean_")
        )
    else:
        mean_dir = data_dir
    mean_path = os.path.join(mean_dir, "mean.binaryproto")
    save_mean_image(mean, mean_path)
    log.log(f"mean image -> {mean_path}")

    # per-worker samplers over that worker's partition (contiguous random
    # window of tau per round, MinibatchSampler semantics); seeds keyed by
    # GLOBAL worker index so a multi-host run draws like a 1-host run
    # (and by epoch, so a reshuffled epoch draws fresh windows)
    def build_samplers(parts, epoch=0):
        return [
            MinibatchSampler(
                {
                    "data": np.stack([mb[0] for mb in part]),
                    "label": np.stack(
                        [mb[1].astype(np.float32) for mb in part]
                    ),
                },
                num_sampled_batches=args.tau,
                seed=args.seed + mine.start + i + 7919 * epoch,
            )
            for i, part in enumerate(parts)
        ]

    samplers = build_samplers(train_parts)
    # test batches: heterogeneous per-worker counts, pad-and-mask — every
    # minibatch is scored even when val shards split unevenly
    test_batches, test_counts = ParameterAveragingTrainer.pad_partitions(
        [
            {
                "data": np.stack([mb[0] for mb in p]),
                "label": np.stack([mb[1].astype(np.float32) for mb in p]),
            }
            for p in test_parts
        ]
    )
    if distributed:
        # agree globally on the pad length and counts vector
        from jax.experimental import multihost_utils

        g_counts = multihost_utils.process_allgather(
            np.asarray(test_counts, np.int32)
        ).reshape(-1)
        nb_max = int(g_counts.max())
        if nb_max > test_batches["data"].shape[1]:
            pad = nb_max - test_batches["data"].shape[1]
            test_batches = {
                k: np.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                for k, v in test_batches.items()
            }
        test_counts = g_counts
    num_test_used = int(np.asarray(test_counts).sum())
    del train_parts, test_parts  # samplers/test_batches hold the only copy

    # net: cropped feed shapes (replaceDataLayers, ImageNetApp.scala:103-104)
    from sparknet_tpu.models.builders import BUILDERS

    netp = (
        models.load_model(args.model, classes=args.classes)
        if args.model in BUILDERS  # prototxt-backed models take no kwargs
        else models.load_model(args.model)
    )
    netp = cfg.replace_data_layers(
        netp,
        [(args.train_batch, 3, args.crop, args.crop), (args.train_batch,)],
        [(args.test_batch, 3, args.crop, args.crop), (args.test_batch,)],
    )
    solver_param = models.load_model_solver(args.model).copy()
    solver = Solver(
        solver_param,
        net_param=netp,
        train_transform=transforms.train_transform(
            mean, args.crop, mirror=not args.no_mirror
        ),
        test_transform=transforms.test_transform(mean, args.crop),
    )

    # --health sentry (before the trainer: audit arity bakes into the
    # shard_map output spec); no snapshots here -> rollback = halt
    from sparknet_tpu.obs import health as health_mod

    sentry = health_mod.sentry_from_args(args, solver, echo=log.log)
    if getattr(args, "elastic", False):
        log.log(
            "--elastic: the membership controller is wired in "
            "cifar_app (this app applies the --slices/"
            "--cross_slice_every hierarchy schedule; preemption "
            "masking rides the fleet plane)"
        )
    trainer = hierarchy.averaging_trainer_from_args(
        args, solver, mesh, n_workers
    )
    state = trainer.init_state(seed=args.seed)
    test_on_dev = shard_leading_global(test_batches, mesh)
    log.log("finished setting up nets and weights")

    def evaluate(r=-1):
        scores = trainer.test_and_store_result(
            state, test_on_dev, counts=test_counts
        )
        for name in sorted(scores):  # solver.cpp:397-410 logs every output
            log.log(
                f"test output {name} = {scores[name] / max(1, num_test_used):.4f}",
                i=r,
            )
        return primary_accuracy(scores) / max(1, num_test_used)

    # pipelined round feed: the uint8 windows for round r+1 are stacked
    # into recycled buffers and device_put on a producer thread while
    # round r executes
    run_obs = obs.start_from_args(args, echo=log.log)
    # epoch switching runs on the feed's producer thread (assemble is
    # called once per round, in order): at an epoch boundary the shard
    # assignment re-deals and the partitions reload — through the chunk
    # cache those reloads are local-disk hits, overlapped under the
    # previous round's execute like any other assembly work
    sampler_state = {"epoch": 0, "samplers": samplers}

    def draw_windows(r):
        if shuffle_on:
            e = min(r // rounds_per_epoch, args.shuffle_epochs - 1)
            if e != sampler_state["epoch"]:
                parts = load_train_parts(e)
                if min(len(p) for p in parts) < args.tau:
                    raise RuntimeError(
                        f"epoch {e}: a worker's reshuffled partition has "
                        f"fewer than tau={args.tau} minibatches; sizes "
                        f"{[len(p) for p in parts]}"
                    )
                sampler_state["samplers"] = build_samplers(parts, e)
                sampler_state["epoch"] = e
                log.log(
                    f"epoch {e}: shard ownership reshuffled "
                    "(shuffle-by-assignment; repeat reads served by the "
                    "chunk cache)", i=r,
                )
        return [s.next_window for s in sampler_state["samplers"]]

    # timed_worker_windows: with --profile the per-worker draw times
    # feed the round profiler's straggler attribution
    feed = RoundFeed(
        lambda r, out: stack_windows(
            obs.profile.timed_worker_windows(r, draw_windows(r)),
            out,
        ),
        place=lambda host: shard_leading_global(host, mesh),
        num_rounds=args.rounds,
    )
    # --journal: the round ledger (io/journal.py).  This app keeps no
    # snapshots, so commits mark in-memory round completion only
    # (durable=False); the resume-capable drivers attach snapshot refs.
    jr = journal_mod.journal_from_args(args, "imagenet_run.journal")
    try:
        for r in range(args.rounds):
            if r % args.test_every == 0:  # test-then-train, ImageNetApp.scala:118
                # land any in-flight overlapped average before scoring
                state = trainer.finalize(state)
                log.log(f"{evaluate(r) * 100:.2f}% accuracy", i=r)
            log.log("training", i=r)
            if jr is not None:
                jr.begin_round(r, iter=r * args.tau, cursor=r)
            if sentry is not None:
                state, _ = sentry.guarded_round(
                    trainer, state, feed.next_round(r), round_index=r
                )
            else:
                state, _ = trainer.round(
                    state, feed.next_round(r), round_index=r
                )
            log.log(
                f"trained, smoothed_loss {solver.smoothed_loss:.4f}", i=r
            )
            if jr is not None:
                jr.commit_round(r, iter=(r + 1) * args.tau, durable=False)
        state = trainer.finalize(state)  # last round's average lands
        acc = evaluate()
        log.log(f"final accuracy {acc * 100:.2f}%")
        if jax.process_index() == 0:
            print(f"final accuracy {acc * 100:.2f}%")
        return 0
    except health_mod.SentryHalt as e:
        log.log(f"training halted by the health sentry: {e}")
        return 1
    finally:
        # telemetry closes AFTER the final-accuracy line so the JSONL
        # run log carries the run's headline result too
        if jr is not None:
            jr.close()
        feed.stop()
        run_obs.close()
        log.close()


if __name__ == "__main__":
    raise SystemExit(main())
