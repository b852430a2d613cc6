"""Command-line interface — the ``caffe`` binary's brew commands.

Reference: ``caffe/tools/caffe.cpp:28-55`` registers train/test/time/
device_query; flag semantics preserved where they make sense on TPU:

    python -m sparknet_tpu.tools.cli train --solver=S [--snapshot=F.solverstate.npz]
        [--weights=F.caffemodel] [--data=DIR] [--sigint_effect=stop|snapshot|none]
    python -m sparknet_tpu.tools.cli test --model=N --weights=F --data=DIR|DB
        [--iterations=50] [--allow_synthetic]
    python -m sparknet_tpu.tools.cli time --model=N [--iterations=50]
    python -m sparknet_tpu.tools.cli device_query
    python -m sparknet_tpu.tools.cli serve --net=N [--weights=F] [--port=P]

``--gpu=...`` becomes ``--devices=N`` (first N local TPU devices as the dp
mesh; the P2PSync role is AllReduceTrainer).  ``test`` scores real data:
``--data`` (CIFAR binary dir or SNDB path) or the net's own Data-layer
``data_param.source``; ``--allow_synthetic`` is a smoke-test-only escape.
``train`` falls back to synthetic batches when ``--data`` is omitted.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, Optional

import numpy as np


def _load_net(path):
    from sparknet_tpu import config

    return config.load_net_prototxt(path)


def _synthetic_batches(net, tau: int, seed: int = 0) -> Dict[str, np.ndarray]:
    from sparknet_tpu.data.source import synthetic_batches

    return synthetic_batches(net, tau, seed)


def _declared_feed_shapes(netp, phase):
    """Declared data-layer shapes for one phase view, straight from the
    config (no net build): the first host-fed layer that can state its
    shapes, or None."""
    from sparknet_tpu.config.schema import NetState
    from sparknet_tpu.graph import filter_net
    from sparknet_tpu.ops import data_layers as dl
    from sparknet_tpu.ops.base import create_layer

    filtered = filter_net(netp, NetState(phase=phase))
    for lp in filtered.layer:
        try:
            layer = create_layer(lp, phase)
        except Exception:
            continue
        if isinstance(layer, dl._HostFed):
            shapes = layer.declared_shapes()
            if shapes:
                return [tuple(s) for s in shapes]
    return None


def _stage_cached_dir(url: str, cache_dir, cache_bytes) -> str:
    """Materialize an object-store root as a local directory view whose
    files are chunk-cache entries (verified, refetch-on-corrupt): list
    the store, pull every ``*.bin`` through the cache, symlink the
    verified chunk paths under ``<cache>/views/<key>/`` — the CIFAR
    loader reads ordinary local files, the network is touched once."""
    import tempfile

    from sparknet_tpu.data import chunk_cache, object_store

    store = object_store.open_store(url)
    cache = chunk_cache.ChunkCache(
        cache_dir or tempfile.mkdtemp(prefix="sparknet_cache_"),
        byte_budget=chunk_cache.parse_bytes(cache_bytes),
    )
    view = os.path.join(
        cache.root, "views", chunk_cache.ChunkCache.key_for(store.url, "")
    )
    os.makedirs(view, exist_ok=True)
    names = [n for n in store.list("") if n.endswith(".bin")]
    if not names:
        raise SystemExit(f"train: no *.bin objects under {url!r}")
    for name in names:
        path = cache.local_path(store, name)
        link = os.path.join(view, name)
        # object names may carry path separators (recursive listings)
        os.makedirs(os.path.dirname(link) or view, exist_ok=True)
        if os.path.islink(link) or os.path.exists(link):
            os.unlink(link)
        os.symlink(path, link)
    return view


def cmd_train(args) -> int:
    # pure argument conflicts fail BEFORE any model/device setup
    if args.resume and (args.snapshot or args.weights):
        print(
            "train: --resume scans the solver's snapshot_prefix and "
            "conflicts with --snapshot/--weights — pass one or the other",
            file=sys.stderr,
        )
        return 1
    if getattr(args, "compress", "none") != "none" or getattr(
        args, "overlap_avg", False
    ):
        # cli train's dp mode is per-step gradient allreduce (the
        # P2PSync analog) — there is no tau-step parameter delta to
        # quantize or overlap.  The comm plane lives on the parameter-
        # averaging drivers.
        print(
            "train: --compress/--overlap_avg apply to tau-round "
            "parameter averaging — use the averaging apps "
            "(sparknet_tpu.apps.cifar_app / cifar_db_app / "
            "imagenet_app / imagenet_run_db_app); cli train's "
            "--devices mode is per-step gradient allreduce",
            file=sys.stderr,
        )
        return 1

    # --publish_to implies the health sentry: a publish carries the
    # sentry's verdict, and an unaudited run has no verdict to attach
    if args.publish_to and not args.health and not args.health_policy:
        args.health = "warn"

    # telemetry first, so restore/snapshot spans and the /metrics
    # sidecar cover the whole run (both flags off -> pure no-op)
    from sparknet_tpu import obs

    run_obs = obs.start_from_args(args)
    try:
        return _cmd_train(args)
    finally:
        run_obs.close()


def _cmd_train(args) -> int:
    import jax

    from sparknet_tpu import config
    from sparknet_tpu.data import CifarLoader, MinibatchSampler
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.solver import Solver
    from sparknet_tpu.utils import SignalHandler, SolverAction, TrainingLog

    solver_param = config.load_solver_prototxt(args.solver)
    trainer = None
    if args.devices > 1:
        # the `caffe train --gpu=0,1,...` analog (tools/caffe.cpp:213-216
        # spins P2PSync): synchronous gradient allreduce over a dp mesh.
        # Reference semantics: the config's batch_size is per-device, the
        # effective batch is batch * devices (caffe/docs/multigpu.md).
        from sparknet_tpu.config import replace_data_layers
        from sparknet_tpu.parallel import AllReduceTrainer, make_mesh

        n = args.devices
        if len(jax.devices()) < n:
            print(
                f"train: --devices={n} but jax sees "
                f"{len(jax.devices())} device(s)",
                file=sys.stderr,
            )
            return 1
        netp0 = config.resolve_solver_net(solver_param)
        train_shapes = _declared_feed_shapes(netp0, "TRAIN")
        test_shapes = _declared_feed_shapes(netp0, "TEST") or train_shapes
        if train_shapes is None:
            print(
                "train: --devices needs data layers with declared shapes "
                "(HostData/Input/MemoryData)",
                file=sys.stderr,
            )
            return 1
        # reference semantics: training batch scales by device count,
        # the TEST view keeps the config's own batch (caffe's --gpu
        # multiplies the training batch only, docs/multigpu.md)
        scaled = [(s[0] * n,) + tuple(s[1:]) for s in train_shapes]
        netp = replace_data_layers(netp0, scaled, test_shapes)
        solver = Solver(solver_param, net_param=netp)
        mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
        trainer = AllReduceTrainer(solver, mesh)
        print(f"allreduce data-parallel over {n} devices")
    else:
        solver = Solver(solver_param)
    # one prefix rule for BOTH writing snapshots and --resume's scan
    prefix = solver_param.snapshot_prefix or "snapshot"
    # --journal/--no_journal: the crash-consistency round ledger
    # (io/journal.py) beside the snapshots.  Auto default: a --resume
    # that finds an existing ledger consumes it (journal-guided
    # restore rewinds to the last COMMITTED boundary).
    from sparknet_tpu.io import journal as journal_mod

    jr = journal_mod.journal_from_args(
        args, journal_mod.default_journal_path(prefix),
        resuming=args.resume,
    )
    if jr is not None:
        print(f"run journal: {jr.path} (fsync={jr.fsync})")
    # training-health sentry (--health/--health_policy): flips the
    # solver's in-graph numerics audit on and guards every window;
    # rollback restores the newest verified snapshot under the same
    # prefix the snapshots use (obs/health.py)
    from sparknet_tpu.obs import health as health_mod

    sentry = health_mod.sentry_from_args(args, solver, echo=print)
    if sentry is not None:
        sentry.restore_fn = health_mod.make_restore_fn(
            solver, prefix, trainer=trainer
        )
    if args.resume:
        # fault-tolerant resume: newest CRC-valid snapshot under the
        # solver's snapshot_prefix; corrupt ones are quarantined and the
        # scan falls back (io/checkpoint.restore_newest_valid).  With a
        # run journal the restore is LEDGER-GUIDED: rewind to the last
        # committed round boundary (a snapshot published for a round
        # whose commit never landed is ignored, its round re-executes)
        # and put the journaled driver state (sentry EMA/cooldown) back.
        try:
            if jr is not None and jr.last_committed_round is not None:
                state, used, job_state, jinfo = (
                    checkpoint.restore_newest_valid_journaled(
                        solver, prefix, jr
                    )
                )
                if (
                    job_state
                    and sentry is not None
                    and "sentry" in job_state
                ):
                    sentry.load_state(job_state["sentry"])
                if jinfo["in_flight_round"] is not None:
                    from sparknet_tpu import obs as _obs_mod

                    tm = _obs_mod.training_metrics()
                    if tm is not None:
                        tm.recover_replayed.inc()
                    print(
                        "journal: round %d was in flight at the crash "
                        "— it re-executes (never skipped, never "
                        "double-committed)" % jinfo["in_flight_round"]
                    )
            else:
                state, used = checkpoint.restore_newest_valid(
                    solver, prefix
                )
        except (FileNotFoundError, checkpoint.SnapshotCorrupt) as e:
            print(f"train: --resume: {e}", file=sys.stderr)
            return 1
        if trainer is not None:
            state = trainer.shard_state(state)
        print(f"resumed from {used} at iter {int(state.iter)}")
    elif args.snapshot:
        state = checkpoint.restore(solver, args.snapshot)
        if trainer is not None:
            state = trainer.shard_state(state)
        print(f"resumed from {args.snapshot} at iter {int(state.iter)}")
    else:
        state = (
            trainer.init_state(seed=args.seed)
            if trainer is not None
            else solver.init_state(seed=args.seed)
        )
        if args.weights:
            state = checkpoint.load_weights_into_state(solver, state, args.weights)
            if trainer is not None:
                state = trainer.shard_state(state)
            print(f"warm-started weights from {args.weights}")

    effects = {
        "stop": SolverAction.STOP,
        "snapshot": SolverAction.SNAPSHOT,
        "none": SolverAction.NONE,
    }
    log = TrainingLog(tag="train")

    sampler = None
    if args.data:
        from sparknet_tpu.data import object_store

        data_dir = args.data
        if object_store.is_object_store_url(args.data):
            # stage the CIFAR binaries through the chunk cache: verified
            # local files, CRC-checked on every read, refetched only
            # when missing/evicted/corrupt — a re-run is I/O-free
            data_dir = _stage_cached_dir(
                args.data, args.cache_dir, args.cache_bytes
            )
            print(f"staged {args.data} -> {data_dir} (chunk cache)")
        loader = CifarLoader(data_dir)
        x, y = loader.minibatches(
            solver.net.blob_shapes[solver.net.feed_blobs[0]][0]
        )
        sampler = MinibatchSampler(
            {"data": x, "label": y}, num_sampled_batches=args.tau, seed=args.seed
        )

    max_iter = args.max_iter or solver_param.max_iter or 1000
    snap_every = solver_param.snapshot
    # --async_snapshot: serialization + file writes happen on a worker
    # thread so the train loop keeps stepping (Orbax-style async
    # checkpointing; the snapshot itself still publishes atomically)
    ckpt = checkpoint.AsyncCheckpointer() if args.async_snapshot else None
    # iter tracked host-side: it advances exactly tau per window, and a
    # per-round device_get of state.iter would sync the async dispatch
    # queue
    it = int(jax.device_get(state.iter))
    # pipelined round feed: the next window is assembled and device_put
    # on a producer thread while the current one trains
    from sparknet_tpu.data import RoundFeed

    # --shuffle_epochs: deterministic epoch passes over the partition,
    # re-permuting the minibatch ORDER each epoch (shuffle-by-assignment
    # over indices — the table moves, the resident arrays do not).
    # Keyed by the ABSOLUTE round (start iter // tau + r): a resumed
    # run continues the same schedule mid-epoch.
    epoch_draw = None
    if sampler is not None and args.shuffle_epochs > 1:
        from sparknet_tpu.data import shuffle as shuffle_mod

        windows_per_epoch = max(1, sampler.total // args.tau)
        base_round = it // args.tau
        perm_memo = {}

        def epoch_draw(r):
            abs_r = base_round + r
            e = abs_r // windows_per_epoch
            if e not in perm_memo:
                perm_memo.clear()  # one epoch's table at a time
                perm_memo[e] = shuffle_mod.permutation(
                    sampler.total, args.seed, e
                )
            pos = (abs_r % windows_per_epoch) * args.tau
            idx = perm_memo[e][pos : pos + args.tau]
            return {k: v[idx] for k, v in sampler.batches.items()}

    def assemble(r, out):
        if epoch_draw is not None:
            return epoch_draw(r)
        return (
            sampler.next_window()
            if sampler
            else _synthetic_batches(solver.net, args.tau)
        )

    feed = RoundFeed(
        assemble,
        sharding=trainer.batch_sharding if trainer is not None else None,
        num_rounds=max(0, -(-(max_iter - it) // args.tau)),
    )
    r = 0

    def job_extra():
        # the full-job-state companion of a snapshot: driver-side
        # scalars a plain TrainState restore silently resets
        extra = {"cursor": {"iter": it, "round": it // args.tau}}
        if sentry is not None:
            extra["sentry"] = sentry.export_state()
        return extra

    # a journaled async boundary commits once its publish is CONFIRMED
    # (the next save/wait joins the worker): (round, iter) awaiting ref
    async_pending = None

    def commit_async_published():
        nonlocal async_pending
        if jr is None or async_pending is None or ckpt is None:
            return
        paths_done = ckpt.last_paths
        if paths_done:
            pr, pit = async_pending
            async_pending = None
            jr.commit_round(
                pr, iter=pit,
                snapshot=os.path.basename(paths_done[1]),
            )

    # the context manager guarantees the previous handler chain comes
    # back even when a step raises (no leaked handlers on exceptions)
    with SignalHandler(
        sigint_effect=effects[args.sigint_effect],
        sighup_effect=effects[args.sighup_effect],
    ) as handler:
        try:
            while it < max_iter:
                abs_r = it // args.tau
                if jr is not None:
                    # write-ahead intent: restart knows this round was
                    # in flight whatever happens next
                    jr.begin_round(abs_r, iter=it, cursor=abs_r)
                batches = feed.next_round(r)
                stepper = trainer if trainer is not None else solver
                if sentry is not None:
                    state, _ = sentry.guarded_step(
                        stepper, state, batches, round_index=r
                    )
                else:
                    state, _ = stepper.step(state, batches)
                r += 1
                it += args.tau
                # throttled logging (SolverParameter.display semantics,
                # solver.cpp:237): reading smoothed_loss is the device
                # sync point, so it runs once per display interval, not
                # per window
                disp = solver_param.display or args.tau
                if it % disp < args.tau:
                    log.log(
                        f"iter {it} smoothed_loss {solver.smoothed_loss:.4f}"
                    )
                action = handler.get_action()
                if action == SolverAction.SNAPSHOT or (
                    snap_every
                    and it % snap_every < args.tau
                    and it >= snap_every
                ):
                    if ckpt is not None:
                        # publish the PREVIOUS write and commit it
                        # BEFORE the next save spawns: reading
                        # last_paths after save() could race a fast
                        # new write and attach ITS ref to the old
                        # round's commit record
                        ckpt.wait()
                        commit_async_published()
                        ckpt.save(
                            solver, state, prefix,
                            extra_state=job_extra(),
                        )
                        async_pending = (abs_r, it)
                        log.log(f"async snapshot started at iter {it}")
                    else:
                        paths = checkpoint.snapshot(
                            solver, state, prefix,
                            extra_state=job_extra(),
                        )
                        if jr is not None:
                            # the durable boundary: commit rides the
                            # published snapshot ref
                            jr.commit_round(
                                abs_r, iter=it,
                                snapshot=os.path.basename(paths[1]),
                            )
                        log.log(f"snapshotted to {paths[0]}")
                if action == SolverAction.STOP:
                    log.log("stop requested; snapshotting and exiting")
                    if ckpt is not None:
                        ckpt.wait()  # same ordering rule as above
                        commit_async_published()
                        ckpt.save(
                            solver, state, prefix,
                            extra_state=job_extra(),
                        )
                        async_pending = (abs_r, it)
                    else:
                        paths = checkpoint.snapshot(
                            solver, state, prefix,
                            extra_state=job_extra(),
                        )
                        if jr is not None:
                            jr.commit_round(
                                abs_r, iter=it,
                                snapshot=os.path.basename(paths[1]),
                            )
                    break
        except health_mod.SentryHalt as e:
            # deliberately NO snapshot here: the live weights are the
            # poisoned ones the sentry just condemned.  The flight
            # bundle (if armed) was dumped by the sentry; /healthz
            # reads 503 until the process exits.
            log.log(f"training halted by the health sentry: {e}")
            if ckpt is not None:
                ckpt.wait()  # publish any PRE-anomaly async snapshot
                commit_async_published()
                ckpt.close()  # detach the SIGTERM/atexit drain hooks
            if jr is not None:
                jr.close()
            return 1
        finally:
            # a step/snapshot exception must not leak the producer
            # thread (and its in-flight device batches)
            feed.stop()
        if ckpt is not None:
            paths = ckpt.wait()
            commit_async_published()
            ckpt.close()
            if paths:
                log.log(f"final async snapshot: {paths[0]}")
    if jr is not None:
        jr.close()
    if args.publish_to:
        # train-to-serve delivery (serve/publish.py): the final state
        # publishes ONLY with a passing sentry verdict attached to its
        # CRC manifest — the delivery watcher (cli serve --watch)
        # re-verifies both before any canary sees traffic.  A SentryHalt
        # never reaches here: condemned weights are never published.
        from sparknet_tpu.serve import publish as publish_mod

        verdict = publish_mod.verdict_from_sentry(sentry)
        try:
            paths = publish_mod.publish_snapshot(
                solver, state, args.publish_to, verdict
            )
        except publish_mod.PublishRefused as e:
            print(f"train: {e}", file=sys.stderr)
            return 1
        log.log(
            f"published verified snapshot {paths[0]} -> "
            f"{args.publish_to} (verdict: {verdict['reason']})"
        )
    return 0


def cmd_test(args) -> int:
    from sparknet_tpu.config import parse_solver_prototxt
    from sparknet_tpu.data.source import resolve_batches
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.solver import Solver

    netp = _load_net(args.model)
    solver = Solver(
        parse_solver_prototxt('base_lr: 0.0 lr_policy: "fixed"'), net_param=netp
    )
    state = solver.init_state(0)
    if args.weights:
        state = checkpoint.load_weights_into_state(solver, state, args.weights)
    # real data: --data (CIFAR dir or SNDB path) or the net's own Data
    # layer source; --allow_synthetic is an explicit smoke-test escape
    batches = resolve_batches(
        solver.test_net,
        netp,
        args.data,
        args.iterations,
        phase="TEST",
        allow_synthetic=args.allow_synthetic,
    )
    scores = solver.test_and_store_result(state, batches)
    for name, total in scores.items():
        print(f"{name} = {total / args.iterations:.4f}")
    return 0


def cmd_time(args) -> int:
    import jax

    from sparknet_tpu.config import parse_solver_prototxt
    from sparknet_tpu.net import JaxNet
    from sparknet_tpu.utils.profiler import format_profile, profile_net

    netp = _load_net(args.model)
    net = JaxNet(netp, phase="TRAIN")
    params, stats = net.init(0)
    batch = {k: v[0] for k, v in _synthetic_batches(net, 1).items()}
    result = profile_net(net, params, stats, batch, iterations=args.iterations)
    print(format_profile(result))
    return 0


def cmd_device_query(args) -> int:
    import jax

    for d in jax.devices():
        print(
            f"device {d.id}: platform={d.platform} kind={d.device_kind} "
            f"process={d.process_index}"
        )
    return 0


def cmd_convert_imageset(args) -> int:
    """``convert_imageset [--shuffle] [--resize WxH] [--backend B] ROOT
    LISTFILE DB`` — build a DB of Datum records from an image tree + a
    "<relpath> <label>" listfile (reference:
    ``caffe/tools/convert_imageset.cpp``).  ``--backend sndb`` (default)
    writes the native record format; ``--backend lmdb`` / ``leveldb``
    write the Caffe interchange formats through ``io/lmdb.py`` /
    ``io/leveldb.py``."""
    import os

    from PIL import Image

    entries = []
    with open(args.listfile) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            name, label = line.rsplit(None, 1)
            entries.append((name, int(label)))
    if args.shuffle:  # FLAGS_shuffle
        np.random.RandomState(args.seed).shuffle(entries)

    images, labels = [], []
    for name, label in entries:
        img = Image.open(os.path.join(args.root, name))
        img = img.convert("L" if args.gray else "RGB")
        if args.resize_width and args.resize_height:
            img = img.resize((args.resize_width, args.resize_height))
        arr = np.asarray(img, np.uint8)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        images.append(np.ascontiguousarray(arr.transpose(2, 0, 1)))
        labels.append(label)
    if not images:
        print("convert_imageset: empty listfile", file=sys.stderr)
        return 1
    shapes = {im.shape for im in images}
    if args.check_size and len(shapes) > 1:
        print(f"convert_imageset: sizes differ: {shapes}", file=sys.stderr)
        return 1
    if len(shapes) > 1:
        raise SystemExit(
            "images have differing sizes; pass --resize_width/--resize_height"
        )
    _write_backend_db(args.backend, args.db, np.stack(images), labels)
    print(f"Processed {len(labels)} files.")
    return 0


def _write_backend_db(backend: str, db: str, images, labels) -> None:
    """One Datum-DB writer dispatch for every converter CLI."""
    if backend == "lmdb":
        from sparknet_tpu.io import lmdb

        lmdb.write_datum_lmdb(db, images, labels)
    elif backend == "leveldb":
        from sparknet_tpu.io import leveldb

        leveldb.write_datum_leveldb(db, images, labels)
    else:
        from sparknet_tpu import runtime

        runtime.write_datum_db(db, images, np.asarray(labels))


def cmd_convert_mnist(args) -> int:
    """``convert_mnist IMAGES LABELS DB [--backend B] [--pairs N]`` —
    idx files -> Datum DB (reference: ``examples/mnist/
    convert_mnist_data.cpp``); ``--pairs N`` packs N random 2-channel
    image pairs with same-class labels instead (``examples/siamese/
    convert_mnist_siamese_data.cpp``)."""
    from sparknet_tpu.data import mnist

    images = mnist.read_idx_images(args.images)
    labels = mnist.read_idx_labels(args.labels)
    if len(images) != len(labels):
        print(
            f"convert_mnist: {len(images)} images vs {len(labels)} labels",
            file=sys.stderr,
        )
        return 1
    if args.pairs:
        images, labels = mnist.make_pairs(
            images, labels, args.pairs, seed=args.seed
        )
    _write_backend_db(args.backend, args.db, images, labels)
    print(f"Processed {len(labels)} records.")
    return 0


def cmd_classify(args) -> int:
    """``classify --model D.prototxt --weights W.caffemodel [--mean M]
    [--labels L.txt] [--topk 5] IMAGE...`` — single-image inference with
    top-k class output (reference: ``examples/cpp_classification/
    classification.cpp``).  The deploy net's input size drives the
    resize; mean may be a binaryproto or comma-separated channel
    values."""
    import os

    import jax
    from PIL import Image

    from sparknet_tpu import config, models
    from sparknet_tpu.io import caffemodel
    from sparknet_tpu.net import JaxNet

    netp = (
        config.load_net_prototxt(args.model)
        if args.model.endswith(".prototxt")
        else models.load_model(args.model)
    )
    net = JaxNet(netp, phase="TEST")
    if len(net.feed_blobs) > 1:
        # train/test config: derive the deploy view (Input data, losses
        # -> prob) like the BVLC deploy.prototxts do
        try:
            netp = models.deploy_variant(netp)
        except ValueError as e:
            print(f"classify: {e}", file=sys.stderr)
            return 1
        net = JaxNet(netp, phase="TEST")
        print("classify: derived deploy view from train/test config",
              file=sys.stderr)
    data_blob = net.feed_blobs[0]
    _, c, h, w = net.blob_shapes[data_blob]
    params, stats = net.init(0)
    if args.weights:
        params, stats = caffemodel.apply_blobs(
            net, params, stats, caffemodel.load_weights(args.weights)
        )

    mean = _load_mean_arg(args.mean) if args.mean else None
    if mean is not None:
        if mean.ndim == 1:
            mean = mean.reshape(-1, 1, 1)
        elif mean.shape[1] < h or mean.shape[2] < w:
            print(
                f"classify: mean image {mean.shape[1]}x{mean.shape[2]} "
                f"is smaller than the net input {h}x{w}",
                file=sys.stderr,
            )
            return 1
    labels = None
    if args.labels:
        with open(args.labels) as f:
            labels = [l.strip() for l in f if l.strip()]

    if mean is not None and (mean.shape[1] > h or mean.shape[2] > w):
        # a larger mean image center-crops to the input (the reference
        # resizes; crop keeps exact mean semantics for the standard
        # 256-mean/227-input case); (C,1,1) value means broadcast as-is
        off_h = (mean.shape[1] - h) // 2
        off_w = (mean.shape[2] - w) // 2
        mean = mean[:, off_h:off_h + h, off_w:off_w + w]

    fwd = jax.jit(net.forward)
    for path in args.images:
        img = Image.open(path).convert("L" if c == 1 else "RGB")
        if args.oversample:
            # resize to the oversample source dims, then 10-crop at the
            # net input size and score-average (classifier.py:47-93)
            from sparknet_tpu.data.transformer import oversample_chw

            src = args.resize or max(256, h, w)
            if src < h or src < w:
                print(
                    f"classify: --resize {src} is smaller than the net "
                    f"input {h}x{w}; oversample crops need a larger "
                    "source",
                    file=sys.stderr,
                )
                return 1
            img = img.resize((src, src), Image.BILINEAR)
        else:
            img = img.resize((w, h), Image.BILINEAR)
        arr = np.asarray(img, np.float32)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        chw = arr.transpose(2, 0, 1)
        if args.oversample:
            crops = oversample_chw(chw, h, w)
            if mean is not None:
                crops = crops - mean[None]
            batch = {data_blob: crops}
        else:
            if mean is not None:
                chw = chw - mean
            batch = {data_blob: chw[None]}
        blobs = fwd(params, stats, batch)
        # "prob" if the deploy net names one (the BVLC convention),
        # else the last layer's top; apply softmax if the scores are
        # not already a distribution (deploy nets often end at fc)
        score_blob = (
            "prob"
            if "prob" in net.blob_shapes
            else net.net_param.layer[-1].top[0]
        )
        out = np.asarray(blobs[score_blob])
        # oversample: average the 10 crops' outputs (classifier.py:81-93)
        scores = out.reshape(out.shape[0], -1).mean(axis=0)
        if scores.min() < 0 or scores.sum() > 1.001:
            e = np.exp(scores - scores.max())
            scores = e / e.sum()
        top = np.argsort(scores)[::-1][: args.topk]
        print(f"---------- Prediction for {path} ----------")
        for i in top:
            name = labels[i] if labels and i < len(labels) else f"class {i}"
            print(f'{scores[i]:.4f} - "{name}"')
    return 0


def cmd_serve(args) -> int:
    """``serve --net D.prototxt|zoo-name [--weights W] [--port P]
    [--buckets 1,4,16,64] [--max_wait_ms 2] [--queue 256]
    [--replicas N] [--watch PUBLISH_DIR] [--canary_frac F]`` — run the
    inference serving front-end (``sparknet_tpu/serve/``): jitted
    forward pre-compiled per batch bucket, dynamic micro-batching,
    ``/predict`` + ``/healthz`` + ``/metrics``, SIGTERM graceful drain.
    ``--replicas N`` serves a fleet (``serve/fleet.py``): N
    shared-nothing replicas behind a load-shedding router;
    ``--watch`` adds the delivery controller (``serve/delivery.py``)
    canarying snapshots that ``cli train --publish_to`` publishes
    there, promoting or rolling back with no restart.

    ``--generate`` serves a TransformerLM checkpoint instead
    (``serve/generate.py``): prefill/decode-disaggregated greedy
    decoding over a paged KV arena with continuous batching, token
    streaming on chunked-NDJSON ``POST /generate``; the fleet and
    delivery flags compose unchanged (streams resume on a sibling
    replica after a replica death, promotes drop zero in-flight
    decodes)."""
    from sparknet_tpu import config, models, obs
    from sparknet_tpu.serve import (
        DeliveryController,
        GenerationEngine,
        InferenceEngine,
        ReplicaPool,
        Router,
        ServeServer,
    )

    if args.generate:
        from sparknet_tpu.models.transformer_lm import TransformerLM

        lm = TransformerLM(
            dim=args.lm_dim, depth=args.lm_depth, heads=args.lm_heads,
            seq_len=args.lm_seq_len,
        )
        gen_buckets = [
            int(b) for b in args.prefill_buckets.split(",") if b.strip()
        ]

        def make_engine(weights=None):
            return GenerationEngine(
                lm,
                weights=weights if weights is not None else args.weights,
                prefill_buckets=gen_buckets,
                max_streams=args.max_streams,
                kv_blocks=args.kv_blocks,
                kv_block_size=args.kv_block_size,
            )

    else:
        if not args.net:
            print("serve: --net is required without --generate",
                  file=sys.stderr)
            return 2
        netp = (
            config.load_net_prototxt(args.net)
            if args.net.endswith(".prototxt")
            else models.load_model(args.net)
        )
        buckets = [int(b) for b in args.buckets.split(",") if b.strip()]

        def make_engine(weights=None):
            return InferenceEngine(
                netp,
                weights=weights if weights is not None else args.weights,
                buckets=buckets,
                output_blob=args.output_blob,
                compute_dtype=args.dtype or None,
            )

    # telemetry (--obs/--ship_to/...): the fleet registers its series on
    # the shared training registry so the PR-10 shipper ships the
    # per-replica/fleet autoscaling signals unchanged
    run_obs = obs.start_from_args(args)
    delivery = None
    try:
        if args.replicas > 1 or args.watch:
            tm = obs.training_metrics()
            import jax

            # replica i on local device i (round-robin past the last):
            # one host's chips each serve their own replica
            pool = ReplicaPool(
                make_engine,
                replicas=args.replicas,
                max_queue=args.queue,
                max_wait_ms=args.max_wait_ms,
                registry=tm.registry if tm is not None else None,
                devices=jax.local_devices(),
                stream=args.generate,
            )
            for rep in pool.replicas:
                print(f"serve: replica {rep.index} on {rep.engine.device}")
            router = Router(
                pool, max_inflight=args.queue,
                canary_frac=args.canary_frac,
            )
            if args.generate:
                print(
                    "serve: generation fleet of %d replica(s) warmed "
                    "(prefill buckets %s, %d decode slots, %d x %d "
                    "KV blocks each) — POST /generate streams NDJSON"
                    % (
                        len(pool.replicas), gen_buckets,
                        args.max_streams, args.kv_blocks,
                        args.kv_block_size,
                    )
                )
            else:
                print(
                    "serve: fleet of %d replica(s) warmed (%d bucket "
                    "programs each: %s), input %s"
                    % (
                        len(pool.replicas), len(buckets), buckets,
                        pool.item_shape,
                    )
                )
            if args.watch:
                delivery = DeliveryController(
                    pool, router, args.watch,
                    cache_dir=args.cache_dir,
                    decision_requests=args.decision_requests,
                    divergence_max=args.divergence_max,
                    echo=print,
                ).start()
                print(f"serve: delivery watcher on {args.watch}")
            server = ServeServer(
                router=router,
                delivery=delivery,
                host=args.host,
                port=args.port,
                verbose=args.verbose,
            )
        else:
            engine = make_engine()
            n = engine.warmup()
            if args.generate:
                print(
                    "serve: warmed %d programs (prefill buckets %s + "
                    "decode + score), %d decode slots, %d x %d KV "
                    "blocks — POST /generate streams NDJSON"
                    % (
                        n, engine.buckets, args.max_streams,
                        args.kv_blocks, args.kv_block_size,
                    )
                )
            else:
                print(
                    f"serve: warmed {n} bucket programs "
                    f"{engine.buckets} for input {engine.item_shape}, "
                    f"output blob {engine.output_blob!r}"
                )
            server = ServeServer(
                engine,
                host=args.host,
                port=args.port,
                max_queue=args.queue,
                max_wait_ms=args.max_wait_ms,
                verbose=args.verbose,
            )
        return server.run()
    finally:
        run_obs.close()


def cmd_parse_log(args) -> int:
    """``parse_log LOG [--out PREFIX]`` — training log -> train/test
    CSVs (the ``tools/extra/parse_log.py`` role, for this framework's
    ``training_log_<ts>.txt`` format)."""
    from sparknet_tpu.tools import parse_log as pl

    train, test = pl.parse_log(args.log)
    import os

    prefix = args.out or os.path.splitext(args.log)[0]
    paths = pl.write_csvs(train, test, prefix)
    print(
        f"parsed {len(train)} train rows, {len(test)} test rows -> "
        + ", ".join(paths)
    )
    return 0


def cmd_upgrade_net_proto_text(args) -> int:
    """``upgrade_net_proto_text IN OUT`` — rewrite a legacy (V0/V1)
    net prototxt in the modern format (reference:
    ``caffe/tools/upgrade_net_proto_text.cpp``; the upgrade passes
    themselves live in ``config/prototext.py``)."""
    from sparknet_tpu import config
    from sparknet_tpu.config import prototext

    netp = config.load_net_prototxt(args.input)  # upgrades on load
    with open(args.output, "w") as f:
        f.write(prototext.dumps(netp))
    print(f"Wrote upgraded net to {args.output}")
    return 0


def cmd_upgrade_net_proto_binary(args) -> int:
    """``upgrade_net_proto_binary IN OUT`` — rewrite a legacy (V0/V1)
    *binary* NetParameter in the modern binary format (reference:
    ``caffe/tools/upgrade_net_proto_binary.cpp``; codec:
    ``io/protobin.py``).  Weight-carrying nets upgrade in place — layer
    blobs ride through like upgrade_proto.cpp:21-80 copies them."""
    from sparknet_tpu.io import protobin

    netp = protobin.load_net_binary(args.input)  # upgrades on load
    protobin.save_net_binary(netp, args.output)
    print(f"Wrote upgraded binary net to {args.output}")
    return 0


def cmd_upgrade_solver_proto_text(args) -> int:
    """``upgrade_solver_proto_text IN OUT`` — rewrite a legacy solver
    prototxt (enum ``solver_type`` -> string ``type``) in the modern
    format (reference: ``caffe/tools/upgrade_solver_proto_text.cpp``)."""
    from sparknet_tpu import config
    from sparknet_tpu.config import prototext
    from sparknet_tpu.config.schema import solver_method

    sp = config.load_solver_prototxt(args.input)
    if sp.solver_type is not None:
        sp.type = solver_method(sp)
        sp.solver_type = None
    with open(args.output, "w") as f:
        f.write(prototext.dumps(sp))
    print(f"Wrote upgraded solver to {args.output}")
    return 0


def _load_mean_arg(arg: str):
    """``--mean`` value -> array: a mean.binaryproto path gives the
    (C, H, W) mean image; comma-separated values give per-channel (C,)
    means.  Shared by ``classify`` and ``detect``."""
    import os

    import numpy as np

    from sparknet_tpu.io import caffemodel

    if os.path.isfile(arg):
        mean = np.asarray(caffemodel.load_mean_image(arg))
        return mean[0] if mean.ndim == 4 else mean
    return np.asarray([float(v) for v in arg.split(",")], np.float32)


def cmd_detect(args) -> int:
    """``detect --model M [--weights W] --window_file F`` — R-CNN-style
    windowed detection: score every proposal window listed in an R-CNN
    window file (reference: ``python/caffe/detector.py`` driven over
    ``window_data_layer``-format files).  Prints one line per window:
    ``<image> <x1> <y1> <x2> <y2> <top-class> <score>``."""
    import numpy as np

    from sparknet_tpu import config, models
    from sparknet_tpu.data.windows import parse_window_file
    from sparknet_tpu.tools.detector import Detector

    netp = (
        config.load_net_prototxt(args.model)
        if args.model.endswith(".prototxt")
        else models.load_model(args.model)
    )
    mean = _load_mean_arg(args.mean) if args.mean else None
    # Detector validates a too-small mean image itself
    det = Detector(
        netp,
        weights=args.weights,
        mean=mean,
        context_pad=args.context_pad,
        crop_mode=args.crop_mode,
        batch=args.batch,
    )
    images = parse_window_file(args.window_file, args.root_folder)
    jobs = []
    for im in images:
        # window-file rows are (class, overlap, x1, y1, x2, y2),
        # inclusive; Detector takes (ymin, xmin, ymax, xmax) max-exclusive
        wins = [
            (int(y1), int(x1), int(y2) + 1, int(x2) + 1)
            for (_cls, _ov, x1, y1, x2, y2) in im.windows
        ]
        if wins:
            jobs.append((im.path, wins))
    dets = det.detect_windows(jobs)
    for d in dets:
        ymin, xmin, ymax, xmax = [int(v) for v in d["window"]]
        top = int(np.argmax(d["prediction"]))
        print(
            f"{d['filename']} {xmin} {ymin} {xmax - 1} {ymax - 1} "
            f"{top} {float(d['prediction'][top]):.4f}"
        )
    print(f"scored {len(dets)} windows over {len(jobs)} images",
          file=sys.stderr)
    return 0


def cmd_draw_net(args) -> int:
    """``draw_net NET OUT.dot`` — emit a graphviz visualization of a net
    definition (reference: ``caffe/python/caffe/draw.py`` via
    ``python/draw_net.py``; here dot source is written directly, render
    with ``dot -Tpng OUT.dot -o OUT.png``)."""
    from sparknet_tpu import config
    from sparknet_tpu.tools import draw

    netp = config.load_net_prototxt(args.input)
    draw.draw_net_to_file(
        netp, args.output, rankdir=args.rankdir,
        label_edges=not args.no_edge_labels, phase=args.phase,
    )
    print(f"Drawing net to {args.output}")
    return 0


def cmd_compute_image_mean(args) -> int:
    """``compute_image_mean DB [OUTPUT]`` — streaming mean image of a
    Datum DB, written as mean.binaryproto (reference:
    ``caffe/tools/compute_image_mean.cpp``)."""
    import os

    from sparknet_tpu.io import caffemodel, lmdb

    total = None
    count = 0
    if lmdb.is_lmdb(args.db):
        it = (img for img, _ in lmdb.read_datum_lmdb(args.db))
    elif os.path.isdir(args.db):
        from sparknet_tpu.io import leveldb

        if not leveldb.is_leveldb(args.db):
            print(
                f"compute_image_mean: {args.db} is neither an LMDB, a "
                "LevelDB, nor a record DB",
                file=sys.stderr,
            )
            return 1
        it = (img for img, _ in leveldb.read_datum_leveldb(args.db))
    else:
        from sparknet_tpu import runtime
        from sparknet_tpu.data.source import _record_shape

        c, h, w = _record_shape(args.db, args.channels, 0, 0)

        def _iter_sndb():
            with runtime.RecordDB(args.db) as db:
                for i in range(len(db)):
                    _, value = db.read(i)
                    lw = len(value) - c * h * w  # 1- or 2-byte label
                    yield np.frombuffer(value[lw:], np.uint8).reshape(c, h, w)

        it = _iter_sndb()
    for img in it:
        s = img.astype(np.int64)
        total = s if total is None else total + s
        count += 1
    if total is None:
        print("compute_image_mean: empty db", file=sys.stderr)
        return 1
    mean = (total.astype(np.float64) / count).astype(np.float32)
    caffemodel.save_mean_image(mean, args.output)
    print(f"Number of items: {count}")
    for ch in range(mean.shape[0]):
        print(f"mean_value channel [{ch}]: {mean[ch].mean():.6g}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv[:1] == ["train"] and "--lm" in argv:
        # the transformer-LM workload: ``train --lm`` hands the rest of
        # the line to apps/lm_app.py, whose parser carries the LM's
        # full surface — --sp (sequence-parallel ring width, dp x sp
        # mesh), --corpus/--cache_dir, --seq_len/--dim/--depth/--heads,
        # plus the same --obs/--health/--journal/--elastic/--compress
        # groups every averaging app exposes.  A prototxt --solver does
        # not apply (the LM is builder-backed, models/transformer_lm).
        from sparknet_tpu.apps import lm_app

        return lm_app.main([a for a in argv[1:] if a != "--lm"])
    parser = argparse.ArgumentParser(prog="sparknet_tpu", description=__doc__)
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("train")
    p.add_argument(
        "--lm", action="store_true",
        help="train the transformer LM workload instead of a prototxt "
        "solver: the rest of the line goes to apps/lm_app.py "
        "(--sp RING_WIDTH for sequence parallelism over a dp x sp "
        "mesh, --corpus URL/dir, --seq_len/--dim/--depth/--heads, "
        "full --obs/--health/--journal/--elastic surface; --solver "
        "does not apply)",
    )
    p.add_argument("--solver", required=True)
    p.add_argument("--snapshot", default=None)
    p.add_argument("--resume", action="store_true",
                   help="continue from the newest CRC-valid snapshot "
                   "under the solver's snapshot_prefix (corrupt ones "
                   "are quarantined and skipped)")
    p.add_argument("--weights", default=None)
    p.add_argument("--data", default=None,
                   help="CIFAR binary dir, or a gs://|s3://|http(s)://|"
                   "file:// url staged through the chunk cache")
    p.add_argument("--cache_dir", default=None,
                   help="chunk-cache root for an object-store --data "
                   "(data/chunk_cache.py; default: a temp dir)")
    p.add_argument("--cache_bytes", default="0",
                   help="chunk-cache LRU byte budget, e.g. 512M / 8G "
                   "(0 = unbounded)")
    p.add_argument("--shuffle_epochs", type=int, default=0,
                   help="with a value >= 2, draw training windows as "
                   "deterministic epoch passes whose minibatch ORDER "
                   "re-permutes each epoch (seeded shuffle-by-"
                   "assignment, data/shuffle.py); resume-aware via the "
                   "absolute iteration.  0/1 = the legacy random "
                   "windows (matching the averaging apps' 0/1 = off). "
                   "Unlike the apps, the value does not split the run: "
                   "an epoch here is one data pass (total/tau windows)")
    p.add_argument("--tau", type=int, default=10)
    p.add_argument("--max_iter", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--async_snapshot", action="store_true",
                   help="write snapshots on a background thread")
    p.add_argument("--devices", type=int, default=1,
                   help="N>1: synchronous allreduce DP over the first N "
                   "local devices (the caffe train --gpu=0,..,N-1 analog; "
                   "batch_size is per-device)")
    p.add_argument(
        "--sigint_effect", choices=["stop", "snapshot", "none"], default="stop"
    )
    p.add_argument(
        "--sighup_effect", choices=["stop", "snapshot", "none"], default="snapshot"
    )
    p.add_argument(
        "--publish_to", default=None, metavar="DIR",
        help="publish the final state here for a serving fleet "
        "(serve/publish.py): a CRC-manifested snapshot with the health "
        "sentry's PASSING verdict attached — a diverged run publishes "
        "nothing.  Implies --health warn.  Serve side: "
        "cli serve --watch DIR canaries + promotes it with no restart",
    )
    from sparknet_tpu import obs as _obs
    from sparknet_tpu.io import journal as _journal
    from sparknet_tpu.parallel import comm as _comm

    _obs.add_cli_args(p)  # --obs / --obs_port / --trace_out
    _comm.add_cli_args(p)  # --compress / --overlap_avg
    _journal.add_cli_args(p)  # --journal / --no_journal / --journal_path
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("test")
    p.add_argument("--model", required=True)
    p.add_argument("--weights", default=None)
    p.add_argument("--data", default=None, help="CIFAR binary dir or SNDB path")
    p.add_argument("--allow_synthetic", action="store_true",
                   help="smoke-test only: score random batches")
    p.add_argument("--iterations", type=int, default=50)
    p.set_defaults(fn=cmd_test)

    p = sub.add_parser("time")
    p.add_argument("--model", required=True)
    p.add_argument("--iterations", type=int, default=10)
    p.set_defaults(fn=cmd_time)

    p = sub.add_parser("device_query")
    p.set_defaults(fn=cmd_device_query)

    p = sub.add_parser("convert_imageset")
    p.add_argument("root", help="image tree root")
    p.add_argument("listfile", help='"<relpath> <label>" lines')
    p.add_argument("db", help="output DB path")
    p.add_argument("--gray", action="store_true")
    p.add_argument("--shuffle", action="store_true")
    p.add_argument(
        "--backend", choices=["sndb", "lmdb", "leveldb"], default="sndb"
    )
    p.add_argument("--resize_width", type=int, default=0)
    p.add_argument("--resize_height", type=int, default=0)
    p.add_argument("--check_size", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=cmd_convert_imageset)

    p = sub.add_parser("convert_mnist")
    p.add_argument("images", help="idx3 image file (.gz ok)")
    p.add_argument("labels", help="idx1 label file (.gz ok)")
    p.add_argument("db", help="output DB path")
    p.add_argument(
        "--backend", choices=["sndb", "lmdb", "leveldb"], default="sndb"
    )
    p.add_argument("--pairs", type=int, default=0,
                   help="write N siamese 2-channel pairs instead")
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=cmd_convert_mnist)

    p = sub.add_parser("serve")
    p.add_argument("--net", default=None,
                   help="deploy prototxt or zoo model name (required "
                   "unless --generate)")
    p.add_argument("--weights", default=None,
                   help=".caffemodel / .caffemodel.h5 (snapshot output ok)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8361)
    p.add_argument("--buckets", default="1,4,16,64",
                   help="comma-separated batch-size buckets to pre-compile")
    p.add_argument("--max_wait_ms", type=float, default=2.0,
                   help="micro-batch coalescing deadline")
    p.add_argument("--queue", type=int, default=256,
                   help="admission queue bound (overflow -> 429)")
    p.add_argument("--output_blob", default=None,
                   help="blob to serve (default: prob, else last top)")
    p.add_argument("--dtype", default=None,
                   help="compute dtype, e.g. bfloat16 (default f32)")
    p.add_argument("--verbose", action="store_true",
                   help="log each HTTP request")
    p.add_argument("--replicas", type=int, default=1,
                   help="N>1: a serving FLEET (serve/fleet.py) — N "
                   "shared-nothing engine replicas behind a router "
                   "that load-balances by in-flight depth and sheds "
                   "(429) at a fleet-wide admission bound (--queue)")
    p.add_argument("--watch", default=None, metavar="PUBLISH_DIR",
                   help="watch this publish location (local dir or "
                   "object-store url) for cli train --publish_to "
                   "snapshots: CRC+verdict verify, warm a standby "
                   "off-path, canary live traffic, promote or roll "
                   "back with no restart (serve/delivery.py)")
    p.add_argument("--canary_frac", type=float, default=0.125,
                   help="fraction of live traffic mirrored to a canary "
                   "during a delivery decision window")
    p.add_argument("--decision_requests", type=int, default=32,
                   help="mirrored requests per canary decision window")
    p.add_argument("--divergence_max", type=float, default=0.25,
                   help="max |canary - incumbent| output divergence "
                   "before the canary rolls back")
    p.add_argument("--cache_dir", default=None,
                   help="chunk-cache root for the delivery watcher's "
                   "verified snapshot staging (default: a temp dir)")
    p.add_argument("--generate", action="store_true",
                   help="serve a TransformerLM checkpoint for token "
                   "streaming (serve/generate.py): chunked-NDJSON "
                   "POST /generate, continuous batching over a paged "
                   "KV arena; composes with --replicas/--watch")
    p.add_argument("--lm_dim", type=int, default=256,
                   help="--generate: TransformerLM embedding dim")
    p.add_argument("--lm_depth", type=int, default=4,
                   help="--generate: TransformerLM layers")
    p.add_argument("--lm_heads", type=int, default=4,
                   help="--generate: TransformerLM attention heads")
    p.add_argument("--lm_seq_len", type=int, default=256,
                   help="--generate: model context length")
    p.add_argument("--prefill_buckets", default="16,32,64,128",
                   help="--generate: prompt-length buckets to "
                   "pre-compile (longer prompts -> 400)")
    p.add_argument("--max_streams", type=int, default=8,
                   help="--generate: decode slots (the fixed decode "
                   "batch width)")
    p.add_argument("--kv_blocks", type=int, default=64,
                   help="--generate: paged KV arena blocks (worst-case "
                   "reservation at admission; overflow -> 429)")
    p.add_argument("--kv_block_size", type=int, default=16,
                   help="--generate: positions per KV block")
    _obs.add_cli_args(p)  # --obs/--ship_to/...: fleet series ride the shipper
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("parse_log")
    p.add_argument("log")
    p.add_argument("--out", default=None, help="CSV prefix")
    p.set_defaults(fn=cmd_parse_log)

    p = sub.add_parser("classify")
    p.add_argument("images", nargs="+")
    p.add_argument("--model", required=True)
    p.add_argument("--weights", default=None)
    p.add_argument("--mean", default=None,
                   help="mean.binaryproto path or comma-separated values")
    p.add_argument("--labels", default=None, help="one class name per line")
    p.add_argument("--topk", type=int, default=5)
    p.add_argument(
        "--oversample", action="store_true",
        help="10-crop (corners+center and mirrors) score averaging "
        "(classifier.py predict(oversample=True))",
    )
    p.add_argument(
        "--resize", type=int, default=0,
        help="oversample source size (default max(256, input))",
    )
    p.set_defaults(fn=cmd_classify)

    for name, fn in (
        ("upgrade_net_proto_text", cmd_upgrade_net_proto_text),
        ("upgrade_net_proto_binary", cmd_upgrade_net_proto_binary),
        ("upgrade_solver_proto_text", cmd_upgrade_solver_proto_text),
    ):
        p = sub.add_parser(name)
        p.add_argument("input")
        p.add_argument("output")
        p.set_defaults(fn=fn)

    p = sub.add_parser("detect")
    p.add_argument("--model", required=True,
                   help="deploy prototxt or zoo model name")
    p.add_argument("--weights", default=None)
    p.add_argument("--window_file", required=True,
                   help="R-CNN window_data file of proposal windows")
    p.add_argument("--root_folder", default="")
    p.add_argument("--mean", default=None,
                   help="mean.binaryproto path or comma-separated values")
    p.add_argument("--context_pad", type=int, default=0)
    p.add_argument("--crop_mode", default="warp", choices=["warp", "square"])
    p.add_argument("--batch", type=int, default=32)
    p.set_defaults(fn=cmd_detect)

    p = sub.add_parser("draw_net")
    p.add_argument("input")
    p.add_argument("output")
    p.add_argument("--rankdir", default="LR", choices=["LR", "TB", "BT", "RL"])
    p.add_argument("--phase", default=None, choices=["TRAIN", "TEST"])
    p.add_argument("--no_edge_labels", action="store_true")
    p.set_defaults(fn=cmd_draw_net)

    p = sub.add_parser("compute_image_mean")
    p.add_argument("db")
    p.add_argument("output", nargs="?", default="mean.binaryproto")
    p.add_argument("--channels", type=int, default=3,
                   help="record channels for raw DBs (1 for --gray sets; "
                   "LMDB Datums carry their own shape)")
    p.set_defaults(fn=cmd_compute_image_mean)

    args = parser.parse_args(argv)
    from sparknet_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
