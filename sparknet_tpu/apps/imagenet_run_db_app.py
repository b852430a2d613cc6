"""ImageNetRunDBApp — phase 2 of the two-phase ImageNet DB path.

Reference: ``src/main/scala/apps/ImageNetRunDBApp.scala:40-117`` — read
the infoFile for per-worker test batch counts, build per-worker solvers
whose engine ``DataLayer`` reads the DBs, **warm-start from a
.caffemodel** (``net.loadWeightsFromFile``, ``:72-77``), then the
τ=50 averaging loop testing every 10 rounds.  The reference's periodic
weight save (commented out at ``:95-100``) is wired in here for real:
``--snapshot_every N`` writes model+solver state through
``io/checkpoint.py`` and ``--resume`` continues from the newest one —
kill -> resume -> eval is a tested path (tests/test_db_apps.py).

Run:
    python -m sparknet_tpu.apps.imagenet_run_db_app --db_dir=DB_DIR \
        --rounds=20 --warm_start=weights.caffemodel
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

TAU = 50  # syncInterval, ImageNetRunDBApp.scala:104


def _broadcast_state(trainer, st):
    """Restore semantics: every worker restarts from the snapshot file,
    exactly like the reference restoring the same .solverstate on each
    executor (now shared trainer machinery — the sentry's rollback path
    uses the same re-placement)."""
    return trainer.broadcast_state(st)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--db_dir", required=True)
    parser.add_argument("--model", default="caffenet")
    parser.add_argument("--rounds", type=int, default=10)
    parser.add_argument("--tau", type=int, default=0, help="0 = reference (50)")
    parser.add_argument("--test_every", type=int, default=10)
    parser.add_argument("--crop", type=int, default=0)
    parser.add_argument("--no_mirror", action="store_true")
    parser.add_argument("--warm_start", default=None,
                        help=".caffemodel[.h5] to load weights from")
    parser.add_argument("--snapshot_every", type=int, default=0,
                        help="snapshot every N rounds")
    parser.add_argument("--snapshot_prefix", default=None)
    parser.add_argument("--resume", action="store_true",
                        help="continue from the newest snapshot")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--cache_dir", default=None,
        help="when --db_dir is a gs://|s3://|http(s)://|file:// url, "
        "stage the DB files through the host-local content-addressed "
        "chunk cache rooted here (data/chunk_cache.py) — a restarted "
        "run re-verifies local bytes instead of re-downloading",
    )
    parser.add_argument(
        "--cache_bytes", default="0",
        help="chunk-cache LRU byte budget, e.g. 512M / 8G "
        "(0 = unbounded)",
    )
    parser.add_argument(
        "--shuffle_epochs", type=int, default=0,
        help="split --rounds into N epochs and re-permute which worker "
        "reads which train DB shard between them (seeded shuffle-by-"
        "assignment, data/shuffle.py) — no bytes move, only the "
        "worker->shard table (0/1 = fixed assignment; resumes must "
        "pass the same --rounds/--shuffle_epochs for stable epoch "
        "boundaries)",
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

    from sparknet_tpu import config as cfg, models, runtime
    from sparknet_tpu.apps.scores import primary_accuracy
    from sparknet_tpu.data import RoundFeed, stack_windows
    from sparknet_tpu.io import caffemodel, checkpoint
    from sparknet_tpu.parallel import (
        first_worker,
        make_mesh,
        shard_leading,
    )
    from sparknet_tpu.solver import Solver
    from sparknet_tpu.utils import TrainingLog

    log = TrainingLog(tag="imagenet_run_db")
    # --db_dir may be an object-store url: the DB files stage through
    # the chunk cache to verified local paths (CRC-manifested, atomic,
    # quarantine-on-corruption) — phase 2 runs straight off a bucket,
    # and a restart re-verifies local bytes instead of re-downloading
    from sparknet_tpu.data import object_store

    remote_db = object_store.is_object_store_url(args.db_dir)
    if remote_db:
        import tempfile

        from sparknet_tpu.data import chunk_cache

        if (
            args.cache_dir is None
            and args.snapshot_prefix is None
            and (args.resume or args.snapshot_every)
        ):
            # snapshots would land in a fresh temp cache dir that the
            # NEXT invocation cannot find — --resume would report "no
            # snapshots" while valid ones sit stranded in /tmp
            raise SystemExit(
                "imagenet_run_db: a remote --db_dir with "
                "--snapshot_every/--resume needs a stable --cache_dir "
                "or an explicit --snapshot_prefix (snapshots in a "
                "temp-dir cache would be unfindable on restart)"
            )
        cache_root = args.cache_dir or tempfile.mkdtemp(
            prefix="sparknet_db_cache_"
        )
        _store = object_store.open_store(args.db_dir)
        _cache = chunk_cache.ChunkCache(
            cache_root, byte_budget=chunk_cache.parse_bytes(args.cache_bytes)
        )
        log.log(f"staging {args.db_dir} through chunk cache {cache_root}")

        def db_path(name: str) -> str:
            return _cache.local_path(_store, name)
    else:

        def db_path(name: str) -> str:
            return os.path.join(args.db_dir, name)

    with open(db_path("imagenet_db_info.json")) as f:
        info = json.load(f)
    n_workers = int(info["workers"])
    full = int(info["full_size"])
    args.tau = args.tau or TAU
    crop = args.crop or (227 if full >= 256 else (full * 7) // 8)
    log.log(f"testPartitionSizes = {info['test_batches']}")
    num_test_mbs = int(sum(info["test_batches"]))

    mean = caffemodel.load_mean_image(
        db_path("imagenet_mean.binaryproto")
    )

    # per-worker native pipelines: train crops randomly + mirrors, test
    # center-crops — DataTransformer semantics in the reader thread
    pipes = [
        runtime.DataPipeline(
            db_path(f"ilsvrc12_train_db_{w}.sndb"),
            batch_size=int(info["train_batch"]),
            shape=(3, full, full),
            crop=crop,
            mirror=not args.no_mirror,
            train=True,
            mean=mean,
            seed=args.seed + w,
        )
        for w in range(n_workers)
    ]
    test_pipes = [
        runtime.DataPipeline(
            db_path(f"ilsvrc12_val_db_{w}.sndb"),
            batch_size=int(info["test_batch"]),
            shape=(3, full, full),
            crop=crop,
            train=False,
            mean=mean,
            seed=args.seed,
        )
        for w in range(n_workers)
    ]

    from sparknet_tpu.models.builders import BUILDERS

    netp = (
        models.load_model(args.model, classes=int(info["classes"]))
        if args.model in BUILDERS  # prototxt-backed models take no kwargs
        else models.load_model(args.model)
    )
    netp = cfg.replace_data_layers(
        netp,
        [(int(info["train_batch"]), 3, crop, crop), (int(info["train_batch"]),)],
        [(int(info["test_batch"]), 3, crop, crop), (int(info["test_batch"]),)],
    )
    solver = Solver(models.load_model_solver(args.model), net_param=netp)
    # --health sentry (before the trainer: audit arity bakes into the
    # shard_map output spec); rollback restores through this app's own
    # snapshot prefix below
    from sparknet_tpu.obs import health as health_mod

    sentry = health_mod.sentry_from_args(args, solver, echo=log.log)
    mesh = make_mesh({"dp": n_workers}, devices=jax.devices()[:n_workers])
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

    prefix = args.snapshot_prefix or os.path.join(
        cache_root if remote_db else args.db_dir, "imagenet_db"
    )
    if sentry is not None:
        sentry.restore_fn = health_mod.make_restore_fn(
            solver, prefix, trainer=trainer
        )
    # --journal: the crash-consistency round ledger beside the
    # snapshots; a --resume that finds one consumes it automatically
    # (ledger-guided rewind to the last COMMITTED boundary + the
    # journaled driver state put back)
    jr = journal_mod.journal_from_args(
        args, journal_mod.default_journal_path(prefix),
        resuming=args.resume,
    )
    if jr is not None:
        log.log(f"run journal: {jr.path} (fsync={jr.fsync})")
    start_round = 0
    if args.resume:
        # fault-tolerant resume: CRC-verified, newest-valid-wins — a
        # corrupt/truncated newest snapshot (preemption mid-write) is
        # quarantined and the scan falls back to an older valid one
        job_state = None
        try:
            if jr is not None and jr.last_committed_round is not None:
                st, used, job_state, jinfo = (
                    checkpoint.restore_newest_valid_journaled(
                        solver, prefix, jr
                    )
                )
                if jinfo["in_flight_round"] is not None:
                    tm = obs.training_metrics()
                    if tm is not None:
                        tm.recover_replayed.inc()
                    log.log(
                        "journal: round %d was in flight at the crash "
                        "— re-executing it" % jinfo["in_flight_round"]
                    )
            else:
                st, used = checkpoint.restore_newest_valid(solver, prefix)
        except FileNotFoundError:
            raise SystemExit(f"--resume: no {prefix}_iter_*.solverstate*")
        except checkpoint.SnapshotCorrupt as e:
            raise SystemExit(f"--resume: {e}")
        state = _broadcast_state(trainer, st)
        if job_state:
            # driver-side state the snapshot's TrainState never
            # carried: comm-plane EF residuals + sentry scalars
            if "comm" in job_state:
                trainer.restore_comm_state(job_state["comm"])
            if sentry is not None and "sentry" in job_state:
                sentry.load_state(job_state["sentry"])
        start_round = int(np.asarray(st.iter)) // args.tau
        log.log(f"resumed from {used} at round {start_round}")
    elif args.warm_start:
        # ImageNetRunDBApp.scala:75 loadWeightsFromFile
        st = checkpoint.load_weights_into_state(
            solver, first_worker(jax.device_get(state)), args.warm_start
        )
        state = _broadcast_state(trainer, st)
        log.log(f"warm start from {args.warm_start}")
    log.log("initialize nets on workers")

    # pad-and-mask heterogeneous test partitions from the infoFile
    counts = np.asarray(info["test_batches"], np.int32)
    nb_max = int(counts.max())
    tb = {
        "data": np.zeros(
            (n_workers, nb_max, int(info["test_batch"]), 3, crop, crop),
            np.float32,
        ),
        "label": np.zeros(
            (n_workers, nb_max, int(info["test_batch"])), np.float32
        ),
    }
    for w, pipe in enumerate(test_pipes):
        for b in range(int(counts[w])):
            x, y = pipe.next()
            tb["data"][w, b] = x
            tb["label"][w, b] = y
    test_on_dev = shard_leading(tb, mesh)

    def evaluate():
        scores = trainer.test_and_store_result(
            state, test_on_dev, counts=counts
        )
        return primary_accuracy(scores) / max(1, num_test_mbs)

    # cross-epoch shuffle-by-assignment (--shuffle_epochs): worker w
    # reads train shard perm[w] for the epoch — a seeded permutation
    # pure in (seed, epoch), derived from the ABSOLUTE round index so a
    # resumed run re-derives the same table.  No bytes move; only the
    # worker->shard assignment.
    shuffle_on = args.shuffle_epochs > 1
    rounds_per_epoch = (
        -(-args.rounds // args.shuffle_epochs) if shuffle_on else None
    )

    def pipe_order(r):
        if not shuffle_on:
            return range(n_workers)
        from sparknet_tpu.data import shuffle as shuffle_mod

        e = min(r // rounds_per_epoch, args.shuffle_epochs - 1)
        return shuffle_mod.permutation(n_workers, args.seed, e)

    def assemble(r, out):
        # worker_timer: with --profile each worker's DB pull time feeds
        # the round profiler's straggler attribution (no-op otherwise)
        windows = []
        for w, p in enumerate(pipe_order(r)):
            pipe = pipes[p]
            with obs.profile.worker_timer(r, w, n_workers):
                batches = [pipe.next() for _ in range(args.tau)]
                windows.append(
                    {
                        "data": np.stack([b[0] for b in batches]),
                        "label": np.stack([b[1] for b in batches]),
                    }
                )
        return stack_windows(windows, out)

    # pipelined feed, resume-aware: rounds are absolute, so a resumed
    # run's producer starts at start_round and the reader pipelines pick
    # up where the DB cursors sit
    run_obs = obs.start_from_args(args, echo=log.log)
    feed = RoundFeed(
        assemble,
        mesh=mesh,
        start_round=start_round,
        num_rounds=args.rounds,
    )
    try:
        for r in range(start_round, start_round + args.rounds):
            if r % args.test_every == 0:
                # land any in-flight overlapped average before scoring
                state = trainer.finalize(state)
                log.log(f"{evaluate() * 100:.2f}% accuracy", i=r)
            log.log("training", i=r)
            if jr is not None:
                # write-ahead intent: restart knows round r was in
                # flight whatever happens next
                jr.begin_round(r, iter=r * args.tau, cursor=r)
            if sentry is not None:
                state, _ = sentry.guarded_round(
                    trainer, state, feed.next_round(r), round_index=r
                )
            else:
                state, _ = trainer.round(
                    state, feed.next_round(r), round_index=r
                )
            log.log(f"trained, smoothed_loss {solver.smoothed_loss:.4f}", i=r)
            if args.snapshot_every and (r + 1) % args.snapshot_every == 0:
                # a snapshot must capture the round's AVERAGE, not a
                # mid-flight overlapped state
                state = trainer.finalize(state)
                st = first_worker(jax.device_get(state))
                extra = {"cursor": {"round": r + 1}}
                comm_state = trainer.export_comm_state()
                if comm_state is not None:
                    extra["comm"] = comm_state
                if sentry is not None:
                    extra["sentry"] = sentry.export_state()
                model_path, state_path = checkpoint.snapshot(
                    solver, st, prefix, extra_state=extra
                )
                if jr is not None:
                    # the durable boundary: the commit rides the
                    # published snapshot ref (exactly-once rewind
                    # target for restore_newest_valid_journaled)
                    jr.commit_round(
                        r, iter=(r + 1) * args.tau,
                        snapshot=os.path.basename(state_path),
                    )
                log.log(f"snapshot -> {model_path}", i=r)

        state = trainer.finalize(state)  # last round's average lands
        acc = evaluate()
        log.log(f"final accuracy {acc * 100:.2f}%")
        print(f"final accuracy {acc * 100:.2f}%")
        return 0
    except health_mod.SentryHalt as e:
        # no snapshot of the condemned weights; the newest snapshot on
        # disk predates the anomaly and stays the restore point
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
        for p in pipes + test_pipes:
            p.close()


if __name__ == "__main__":
    raise SystemExit(main())
