"""CifarApp — distributed CIFAR-10 training driver.

Reference: ``src/main/scala/apps/CifarApp.scala`` — the canonical SparkNet
loop: load + partition data across workers, build per-worker nets,
then rounds of broadcast -> tau local steps -> reduce/average, testing
every ``test_every`` rounds, all phase-logged.  Here the broadcast/reduce
plane is the mesh collective inside ``ParameterAveragingTrainer.round``, so
one call does what steps 1-5 of the reference loop did (and the
2x|theta|xN floats never touch the host).

Run:
    python -m sparknet_tpu.apps.cifar_app --data=DIR --workers=4 --rounds=50
(synthesizes CIFAR-format data when --data is omitted)
"""

from __future__ import annotations

import argparse
import tempfile

import numpy as np


TAU = 10  # reference: syncInterval = 10, CifarApp.scala:119


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--data", default=None, help="CIFAR binary dir")
    parser.add_argument("--workers", type=int, default=0, help="0 = all devices")
    parser.add_argument("--rounds", type=int, default=50)
    parser.add_argument("--tau", type=int, default=TAU)
    parser.add_argument("--test_every", type=int, default=10)  # CifarApp.scala:101
    parser.add_argument("--batch", type=int, default=100)
    parser.add_argument("--seed", type=int, default=0)
    from sparknet_tpu import obs
    from sparknet_tpu.io import journal as journal_mod
    from sparknet_tpu.parallel import comm, hierarchy

    obs.add_cli_args(parser)  # --obs / --obs_port / --trace_out
    comm.add_cli_args(parser)  # --compress / --overlap_avg
    hierarchy.add_cli_args(parser)  # --slices/--cross_slice_every/--elastic
    journal_mod.add_cli_args(parser)  # --journal / --no_journal / ...
    args = parser.parse_args(argv)

    from sparknet_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()

    import jax

    from sparknet_tpu import models
    from sparknet_tpu.apps.scores import primary_accuracy
    from sparknet_tpu.data import (
        CifarLoader,
        MinibatchSampler,
        RoundFeed,
        stack_windows,
    )
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        local_worker_slice,
        make_mesh,
        shard_leading_global,
    )
    from sparknet_tpu.solver import Solver
    from sparknet_tpu.utils import TrainingLog

    distributed = jax.process_count() > 1
    log = TrainingLog(tag="cifar", echo=jax.process_index() == 0)
    data_dir = args.data
    if data_dir is None:
        data_dir = tempfile.mkdtemp(prefix="cifar_synth_")
        CifarLoader.write_synthetic(data_dir, num_train=5000, num_test=1000)
        log.log(f"synthesized CIFAR-format data in {data_dir}")

    n_workers = args.workers or (
        jax.device_count() if distributed else jax.local_device_count()
    )
    if distributed and n_workers != jax.device_count():
        raise SystemExit("multi-host runs must use --workers == all devices")
    log.log(f"num workers: {n_workers}")

    loader = CifarLoader(data_dir, seed=args.seed)
    log.log("loaded data")

    mesh = make_mesh({"dp": n_workers}, devices=jax.devices()[:n_workers])
    # this host's contiguous block of workers (every host computes the
    # same global partitioning, then keeps only its own — the Spark
    # partitions-per-executor analog)
    mine = local_worker_slice(mesh) if distributed else slice(0, n_workers)

    x, y = loader.minibatches(args.batch, train=True)
    if len(x) < n_workers * args.tau:
        raise SystemExit(
            f"need >= {n_workers * args.tau} minibatches, have {len(x)}"
        )
    # repartition into contiguous near-equal blocks (RDD repartition
    # analog) — partition sizes may differ by one batch; each worker's
    # window sampler draws tau from its OWN partition size
    samplers = [
        MinibatchSampler(
            {"data": xs, "label": ys},
            num_sampled_batches=args.tau,
            seed=args.seed + w,
        )
        for w, (xs, ys) in enumerate(
            zip(np.array_split(x, n_workers), np.array_split(y, n_workers))
        )
        if mine.start <= w < mine.stop
    ]
    xt, yt = loader.minibatches(args.batch, train=False)
    # heterogeneous test partitions (Spark parallelize gives near-equal
    # splits; ragged tails are scored, not dropped): pad-and-mask
    test_parts = [
        {"data": xs, "label": ys}
        for xs, ys in zip(
            np.array_split(xt, n_workers), np.array_split(yt, n_workers)
        )
    ]
    num_test_batches = len(xt)

    solver = Solver(models.load_model_solver("cifar10_full"))
    # --health: numerics audit + divergence sentry.  Built BEFORE the
    # trainer (the audit arity bakes into the shard_map output spec);
    # this app keeps no snapshots, so rollback degrades to halt.
    from sparknet_tpu.obs import health as health_mod

    sentry = health_mod.sentry_from_args(args, solver, echo=log.log)
    # --compress/--overlap_avg: comm-plane averaging (delta-quantized,
    # chunked, optionally overlapped — parallel/comm.py);
    # --slices/--cross_slice_every: two-tier hierarchical schedule
    spec = hierarchy.spec_from_args(args, n_workers)
    # --stale_bound: swap in the bounded-staleness trainer (same round
    # surface; this app feeds every worker each round, so boundaries
    # see full arrival sets — the flag matters for drivers that model
    # arrivals, runtime/recover.py and the chaos harness)
    trainer = hierarchy.averaging_trainer_from_args(
        args, solver, mesh, n_workers, hierarchy=spec
    )
    # --elastic: the membership controller (runtime/membership.py)
    # maintains epoch-numbered roster views that drive each round's
    # live_mask; a SIGTERM preemption notice marks THIS process's
    # slice ($SPARKNET_SLICE_ID, the launcher sets it; defaults to the
    # last slice) leaving at the next round boundary, and the departed
    # slice rejoins from the survivor consensus (this app keeps no
    # snapshots) --rejoin_after boundaries later — the single-process
    # stand-in for the orchestrator's relaunch notice (AutoRejoin;
    # external drivers use note_join / fleet views instead).
    membership_ctl = None
    auto_rejoin = None
    if args.elastic:
        import os as _os

        from sparknet_tpu.runtime import membership as membership_mod

        membership_ctl = membership_mod.MembershipController(
            spec
            if spec is not None
            else hierarchy.HierarchySpec.flat(n_workers),
            echo=log.log,
        )
        my_slice = int(
            _os.environ.get(
                "SPARKNET_SLICE_ID",
                membership_ctl.spec.num_slices - 1,
            )
        )
        membership_ctl.sigterm_marks(my_slice)
        auto_rejoin = membership_mod.AutoRejoin(
            membership_ctl, args.rejoin_after
        )
        obs.set_membership(membership_ctl)
    state = trainer.init_state(seed=args.seed)
    test_batches, test_counts = ParameterAveragingTrainer.pad_partitions(
        test_parts
    )
    test_on_dev = shard_leading_global(
        {k: v[mine] for k, v in test_batches.items()}
        if distributed
        else test_batches,
        mesh,
    )
    log.log("finished setting up nets and weights")

    def evaluate(r=None):
        scores = trainer.test_and_store_result(
            state, test_on_dev, counts=test_counts
        )
        for name in sorted(scores):
            log.log(f"test output {name} = {scores[name] / num_test_batches:.4f}")
        return primary_accuracy(scores) / num_test_batches

    # pipelined round feed: round r+1's windows are drawn, stacked into
    # recycled buffers and device_put on a producer thread while round r
    # executes (RoundFeed)
    run_obs = obs.start_from_args(args, echo=log.log)
    # --journal: the round ledger (io/journal.py).  This app keeps no
    # snapshots, so commits mark in-memory round completion only
    # (durable=False) — a progress/postmortem record carrying the view
    # epoch; the resume-capable drivers attach snapshot refs.
    jr = journal_mod.journal_from_args(args, "cifar_run.journal")
    # timed_worker_windows: with --profile the per-worker draw times
    # feed the round profiler's straggler attribution (plain list
    # comprehension otherwise)
    feed = RoundFeed(
        lambda r, out: stack_windows(
            obs.profile.timed_worker_windows(
                r, [s.next_window for s in samplers]
            ),
            out,
        ),
        place=lambda host: shard_leading_global(host, mesh),
        num_rounds=args.rounds,
    )
    from sparknet_tpu.utils import SignalHandler, SolverAction

    try:
        # the SIGTERM handler is installed only to deliver preemption
        # notices to the membership hook; SIGINT/SIGHUP keep their
        # default behavior (this app has no snapshot machinery)
        with SignalHandler(
            sigint_effect=SolverAction.NONE,
            sighup_effect=SolverAction.NONE,
            sigterm_hooks=membership_ctl is not None,
        ):
            for r in range(args.rounds):
                if r % args.test_every == 0:  # test before train, CifarApp.scala:101
                    # land any in-flight overlapped average before scoring
                    state = trainer.finalize(state)
                    log.log(f"round {r}, accuracy {evaluate(r):.4f}")
                if jr is not None:
                    jr.begin_round(
                        r, iter=r * args.tau, cursor=r,
                        view_epoch=(
                            membership_ctl.view.epoch
                            if membership_ctl is not None else 0
                        ),
                    )
                mask = None
                if membership_ctl is not None:
                    # roster changes land at the round boundary; a
                    # relaunched slice rejoins from the survivor
                    # consensus (momentum zeroed)
                    membership_ctl.advance(r)
                    auto_rejoin.on_round(r)
                    if membership_ctl.pending_joiners():
                        state, _ = membership_mod.readmit_from_survivors(
                            trainer, state, membership_ctl, r,
                            echo=log.log,
                        )
                    mask = membership_ctl.live_mask()
                    if not mask.any():
                        log.log(
                            f"round {r}: no live workers in the "
                            "membership view; stopping"
                        )
                        break
                if sentry is not None:
                    state, _ = sentry.guarded_round(
                        trainer, state, feed.next_round(r),
                        live_mask=mask, round_index=r,
                    )
                else:
                    state, _ = trainer.round(
                        state, feed.next_round(r),
                        live_mask=mask, round_index=r,
                    )
                log.log(
                    f"round {r} trained, smoothed_loss {solver.smoothed_loss:.4f}"
                )
                if jr is not None:
                    jr.commit_round(
                        r, iter=(r + 1) * args.tau, durable=False
                    )
        state = trainer.finalize(state)  # last round's average lands
        log.log(f"final accuracy {evaluate():.4f}")
        return 0
    except health_mod.SentryHalt as e:
        log.log(f"training halted by the health sentry: {e}")
        return 1
    finally:
        if membership_ctl is not None:
            membership_ctl.detach()
        if jr is not None:
            jr.close()
        feed.stop()
        run_obs.close()
        log.close()


if __name__ == "__main__":
    raise SystemExit(main())
