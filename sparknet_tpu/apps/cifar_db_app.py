"""CifarDBApp — the DB-path training driver.

Reference: ``src/main/scala/apps/CifarDBApp.scala`` — phase 1 writes
per-worker DB shards + mean.binaryproto through the shim
(``CreateDB``/``ComputeMean``), phase 2 trains with the engine's own
``DataLayer`` reading those DBs (no callback data path).  Here phase 1
writes native record DBs + the binary mean file, phase 2 feeds the same
averaging loop from ``runtime.DataPipeline`` reader threads — the native
data plane end to end.

Run:
    python -m sparknet_tpu.apps.cifar_db_app --workers=2 --rounds=6
"""

from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np


def create_dbs(data_dir: str, out_dir: str, n_workers: int, seed: int = 0):
    """Phase 1: shard train set into per-worker DBs, write test DB + mean
    (CreateDB + ComputeMean parity)."""
    from sparknet_tpu import runtime
    from sparknet_tpu.data import CifarLoader
    from sparknet_tpu.io import caffemodel

    loader = CifarLoader(data_dir, seed=seed)
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for w in range(n_workers):
        path = os.path.join(out_dir, f"train_shard_{w}.sndb")
        runtime.write_datum_db(
            path, loader.train_images[w::n_workers], loader.train_labels[w::n_workers]
        )
        paths.append(path)
    test_path = os.path.join(out_dir, "test.sndb")
    runtime.write_datum_db(test_path, loader.test_images, loader.test_labels)
    mean_path = os.path.join(out_dir, "mean.binaryproto")
    caffemodel.save_mean_image(loader.mean_image, mean_path)
    return paths, test_path, mean_path


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--data", default=None)
    parser.add_argument("--db_dir", default=None)
    parser.add_argument("--workers", type=int, default=2)
    parser.add_argument("--rounds", type=int, default=6)
    parser.add_argument("--tau", type=int, default=10)
    parser.add_argument("--batch", type=int, default=100)
    parser.add_argument("--seed", type=int, default=0)
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

    from sparknet_tpu import models, runtime
    from sparknet_tpu.data import CifarLoader, RoundFeed, stack_windows
    from sparknet_tpu.io import caffemodel
    from sparknet_tpu.parallel import make_mesh, shard_leading
    from sparknet_tpu.solver import Solver
    from sparknet_tpu.utils import TrainingLog

    log = TrainingLog(tag="cifar_db")
    data_dir = args.data
    if data_dir is None:
        data_dir = tempfile.mkdtemp(prefix="cifar_synth_")
        CifarLoader.write_synthetic(data_dir, num_train=4000, num_test=500)
        log.log(f"synthesized CIFAR data in {data_dir}")
    db_dir = args.db_dir or tempfile.mkdtemp(prefix="cifar_dbs_")

    shard_paths, test_path, mean_path = create_dbs(
        data_dir, db_dir, args.workers, args.seed
    )
    log.log(f"created {len(shard_paths)} train DBs + test DB in {db_dir} "
            f"(native={runtime.native_available()})")

    mean = caffemodel.load_mean_image(mean_path)
    pipes = [
        runtime.DataPipeline(
            p,
            batch_size=args.batch,
            shape=(3, 32, 32),
            mean=mean,
            train=True,
            seed=args.seed + w,
        )
        for w, p in enumerate(shard_paths)
    ]
    test_pipe = runtime.DataPipeline(
        test_path, batch_size=args.batch, shape=(3, 32, 32), mean=mean, train=False
    )

    mesh = make_mesh(
        {"dp": args.workers}, devices=jax.devices()[: args.workers]
    )
    solver = Solver(models.load_model_solver("cifar10_full"))
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
        args, solver, mesh, args.workers
    )
    state = trainer.init_state(seed=args.seed)
    log.log("nets ready")

    def assemble(r, out):
        # reader-thread pulls + worker stack, on the RoundFeed producer:
        # round r+1's DB reads and H2D overlap round r's execute.
        # worker_timer: with --profile each worker's DB pull time feeds
        # the round profiler's straggler attribution (no-op otherwise)
        windows = []
        for w, p in enumerate(pipes):
            with obs.profile.worker_timer(r, w, len(pipes)):
                batches = [p.next() for _ in range(args.tau)]
                windows.append(
                    {
                        "data": np.stack([b[0] for b in batches]),
                        "label": np.stack([b[1] for b in batches]),
                    }
                )
        return stack_windows(windows, out)

    run_obs = obs.start_from_args(args, echo=log.log)
    # --journal: the round ledger (io/journal.py).  This app keeps no
    # snapshots, so commits mark in-memory round completion only
    # (durable=False) — a progress/postmortem record, not a resume
    # target; the resume-capable drivers (cli train,
    # imagenet_run_db_app) attach snapshot refs.
    jr = journal_mod.journal_from_args(args, "cifar_db_run.journal")
    feed = RoundFeed(
        assemble,
        mesh=mesh,
        num_rounds=args.rounds,
    )
    try:
        for r in range(args.rounds):
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
                f"round {r} trained, smoothed_loss {solver.smoothed_loss:.4f}"
            )
            if jr is not None:
                jr.commit_round(r, iter=(r + 1) * args.tau, durable=False)

        state = trainer.finalize(state)  # last round's average lands
        # eval from the test DB
        nb = 2
        tb = [test_pipe.next() for _ in range(args.workers * nb)]
        test_batches = {
            "data": np.stack([b[0] for b in tb]).reshape(
                args.workers, nb, args.batch, 3, 32, 32
            ),
            "label": np.stack([b[1] for b in tb]).reshape(
                args.workers, nb, args.batch
            ),
        }
        scores = trainer.test_and_store_result(
            state, shard_leading(test_batches, mesh)
        )
        acc = scores.get("accuracy", 0.0) / (args.workers * nb)
        log.log(f"final accuracy {acc:.4f}")
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
        for p in pipes:
            p.close()
        test_pipe.close()


if __name__ == "__main__":
    raise SystemExit(main())
