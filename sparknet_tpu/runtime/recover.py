"""The journaled driver loop: crash-consistent training, provable.

``run_driver`` is a small cifar10_quick parameter-averaging loop wired
the way a crash-consistent production driver must be:

- every round is bracketed by a **write-ahead intent** and a
  **durable commit** in the run journal (``io/journal.RunJournal``),
- every committed boundary snapshots the FULL job state: params +
  history (the classic snapshot) plus the CommPlane error-feedback
  residuals, the sentry EMA/cooldown, the membership view epoch and
  the data cursor (``io/checkpoint.snapshot(extra_state=...)``),
- resume reconciles ledger vs snapshots
  (``checkpoint.restore_newest_valid_journaled``): rewind to the last
  committed boundary, re-execute the one in-flight round, never
  re-execute a committed one.

The loop doubles as the kill-anywhere chaos child: ``--kill_at
PHASE:ROUND`` SIGKILLs the process at a named phase boundary —

    assemble            after the round's host batch is built
    h2d                 after the dp-sharded device placement
    execute             after the fused local-steps+average returns
    average             after the sentry consumed the round's stats
    snapshot_mid_write  mid-write of the solverstate file (the tmp is
                        written, the publish rename never happens)
    journal_mid_append  mid-append of the commit record (half a frame
                        lands durably — the torn tail truncation case)

— and ``runtime/chaos.run_kill_sweep`` drives the full sweep: each
kill-point's resumed trajectory must be BIT-IDENTICAL to an
uninterrupted control (the digest covers params, history, iter, EF
residuals and sentry EMA), with at most one replayed round.  The
``--no_journal`` leg proves the zero is not vacuous: resuming from the
plain newest snapshot resets the EF residuals and measurably diverges.

Subprocess entry::

    python -m sparknet_tpu.runtime.recover --workdir DIR --rounds 4 \
        [--kill_at execute:2] [--resume] [--no_journal]

prints one JSON line (rounds executed, final state digest, per-round
wall times, restore latency).  Importable pieces (``RecoverContext``,
``run_driver`` with ``kill=<raise>``) power the in-process tier-1
tests and the chaos harness's ``driver_kill`` fault.
"""

from __future__ import annotations

import hashlib
import json
import os
import signal as _signal
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

KILL_POINTS = (
    "assemble",
    "h2d",
    "execute",
    "average",
    "snapshot_mid_write",
    "journal_mid_append",
    # bounded-staleness runs only (--stale_bound > 0): fires right
    # after the stale averaging boundary folded its arrival set, before
    # the worker-round vector is committed — resume must replay the
    # boundary from the journaled vector, <= stale_bound rounds
    "stale_boundary",
)


class SimulatedKill(BaseException):
    """The in-process stand-in for SIGKILL (tests / chaos driver_kill):
    raised by a kill hook, caught by the harness — deliberately a
    BaseException so no library ``except Exception`` can absorb it."""


def sigkill_self() -> None:
    os.kill(os.getpid(), _signal.SIGKILL)


def parse_kill_at(value: Optional[str]) -> Tuple[Optional[str], int]:
    if not value:
        return None, -1
    phase, _, r = value.partition(":")
    if phase not in KILL_POINTS:
        raise ValueError(
            f"kill_at phase {phase!r}: expected one of {KILL_POINTS}"
        )
    return phase, int(r or 0)


class RecoverContext:
    """Everything expensive, built once: data, solver (audit on),
    mesh, trainer (int8 delta averaging so real EF-residual state is
    carried).  Reusable across in-process control/crash/resume runs —
    the jitted programs compile once."""

    def __init__(
        self,
        workdir: str,
        workers: int = 2,
        tau: int = 2,
        batch: int = 8,
        seed: int = 7,
        compress: str = "int8",
        stale_bound: int = 0,
    ):
        import jax

        from sparknet_tpu import config as cfg, models
        from sparknet_tpu.data import CifarLoader
        from sparknet_tpu.parallel import (
            BoundedStalenessTrainer,
            ParameterAveragingTrainer,
            make_mesh,
        )
        from sparknet_tpu.solver import Solver

        self.workdir = workdir
        self.workers = workers
        self.tau = tau
        self.batch = batch
        self.seed = seed
        self.stale_bound = int(stale_bound)
        if self.stale_bound > 0:
            # stale boundaries don't compose with the comm plane's
            # EF-residual collectives; the stale recovery leg carries
            # the worker-round ledger + per-worker replicas instead
            compress = "none"
        self.compress = compress
        os.makedirs(workdir, exist_ok=True)
        data_dir = os.path.join(workdir, "data")
        if not os.path.isdir(data_dir):
            CifarLoader.write_synthetic(
                data_dir, num_train=256, num_test=32, seed=seed
            )
        self.xs, self.ys = CifarLoader(data_dir).minibatches(
            batch, train=True
        )
        netp = cfg.replace_data_layers(
            models.load_model("cifar10_quick"),
            [(batch, 3, 32, 32), (batch,)],
            [(batch, 3, 32, 32), (batch,)],
        )
        # audit=True: the sentry's stats ride the jitted round, so the
        # journaled sentry EMA is real state, not a stub
        self.solver = Solver(
            models.load_model_solver("cifar10_quick"), net_param=netp,
            audit=True,
        )
        if jax.device_count() < workers:
            raise RuntimeError(
                f"recover needs >= {workers} devices (virtual CPU mesh)"
            )
        self.mesh = make_mesh(
            {"dp": workers}, devices=jax.devices()[:workers]
        )
        if self.stale_bound > 0:
            self.trainer = BoundedStalenessTrainer(
                self.solver, self.mesh, stale_bound=self.stale_bound
            )
            # the deterministic straggler: the last worker never
            # self-arrives, so every boundary's arrival set is a pure
            # function of the (journaled) worker-round vector — the
            # bound forces it in every stale_bound-th boundary
            self.straggler = workers - 1
        else:
            self.trainer = ParameterAveragingTrainer(
                self.solver, self.mesh, compress=compress
            )
            self.straggler = None
        self.prefix = os.path.join(workdir, "recover_ckpt")

    def batch_for(self, r: int) -> Dict[str, np.ndarray]:
        """Round ``r``'s host batch, a pure function of the absolute
        round index (the shuffle-cursor discipline: resume re-derives
        the same draw from the journaled cursor, no stateful sampler to
        lose)."""
        W, tau, B, n = self.workers, self.tau, self.batch, len(self.xs)
        data = np.empty((W, tau) + self.xs[0].shape, np.float32)
        label = np.empty((W, tau, B), np.float32)
        for w in range(W):
            for t in range(tau):
                i = (r * W * tau + w * tau + t) % n
                data[w, t] = self.xs[i]
                label[w, t] = self.ys[i]
        return {"data": data, "label": label}

    def make_sentry(self):
        from sparknet_tpu.obs.health import HealthSentry

        return HealthSentry(policy="warn")

    def arrival_for(self) -> np.ndarray:
        """The boundary's self-arrival set (pure: same every round —
        the straggler's fold-ins come from the bound forcing it)."""
        arr = np.ones((self.workers,), bool)
        if self.straggler is not None:
            arr[self.straggler] = False
        return arr


def state_digest(
    state, comm_state=None, sentry_state=None, stale_state=None
) -> str:
    """Deterministic digest of the FULL job state: every TrainState
    leaf (params, stats, history, iter), the comm plane's EF residuals,
    the sentry's EMA scalars, and (stale runs) the worker-round
    ledger.  Bit-identity of two runs == equal digests."""
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves(jax.device_get(state))
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    if comm_state is not None:
        resid = comm_state["resid"]
        for i in range(len(resid)):
            h.update(np.asarray(resid[str(i)]).tobytes())
    if sentry_state is not None:
        h.update(
            json.dumps(
                {
                    k: sentry_state.get(k)
                    for k in ("ema", "emvar", "seen", "cooldown")
                },
                sort_keys=True,
            ).encode()
        )
    if stale_state is not None:
        h.update(
            json.dumps(
                {
                    "boundary": int(np.asarray(stale_state["boundary"])),
                    "worker_rounds": [
                        int(v)
                        for v in np.asarray(
                            stale_state["worker_rounds"]
                        ).reshape(-1)
                    ],
                },
                sort_keys=True,
            ).encode()
        )
    return h.hexdigest()


def run_driver(
    ctx: RecoverContext,
    rounds: int,
    *,
    journal: bool = True,
    resume: bool = False,
    kill_at: Optional[Tuple[Optional[str], int]] = None,
    kill: Optional[Callable[[], None]] = None,
    fsync: str = "commit",
    run_dir: Optional[str] = None,
) -> Dict:
    """One driver invocation (fresh or ``resume``); returns the run
    record.  ``kill_at=(phase, round)`` arms the kill at that phase
    boundary; ``kill`` defaults to a real SIGKILL (pass a raiser for
    in-process harnesses).  ``run_dir`` overrides where the snapshots
    + ledger live (in-process harnesses run control/crash/resume legs
    in separate dirs off ONE compiled context)."""
    import jax

    from sparknet_tpu import obs as _obs
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.io.journal import RunJournal, default_journal_path
    from sparknet_tpu.parallel import (
        export_worker_history,
        export_worker_replicas,
        first_worker,
        restore_worker_history,
        restore_worker_replicas,
        shard_leading,
        stale_window,
    )
    from sparknet_tpu.parallel.hierarchy import HierarchySpec
    from sparknet_tpu.runtime import membership as membership_mod

    kill = kill or sigkill_self
    kp, kr = kill_at or (None, -1)
    stale = ctx.stale_bound > 0
    if kp == "stale_boundary" and not stale:
        raise ValueError(
            "kill_at stale_boundary needs a --stale_bound > 0 context"
        )

    def maybe_kill(phase: str, r: int) -> None:
        if kp == phase and r == kr:
            kill()

    trainer = ctx.trainer
    prefix = ctx.prefix
    if run_dir is not None:
        os.makedirs(run_dir, exist_ok=True)
        prefix = os.path.join(run_dir, "recover_ckpt")
    jr = (
        RunJournal(default_journal_path(prefix), fsync=fsync)
        if journal
        else None
    )
    sentry = ctx.make_sentry()
    # a (flat) membership controller rides along so the view epoch is
    # real journaled state: the resumed epoch clock must continue, not
    # rewind (flat spec + all-live mask => no effect on the math)
    membership = membership_mod.MembershipController(
        HierarchySpec.flat(ctx.workers)
    )

    start_round = 0
    restore_s = None
    resumed_from = None
    info = None
    try:
        if resume:
            t0 = time.perf_counter()
            st = js = None
            if jr is not None:
                try:
                    st, used, js, info = (
                        checkpoint.restore_newest_valid_journaled(
                            ctx.solver, prefix, jr
                        )
                    )
                except FileNotFoundError:
                    info = jr.reconcile()  # round 0 never committed
                start_round = info["resume_round"]
                if info["in_flight_round"] is not None:
                    tm = _obs.training_metrics()
                    if tm is not None:
                        tm.recover_replayed.inc()
            else:
                try:
                    st, used = checkpoint.restore_newest_valid(
                        ctx.solver, prefix
                    )
                    start_round = int(np.asarray(st.iter)) // ctx.tau
                except FileNotFoundError:
                    pass
            if st is not None:
                resumed_from = os.path.basename(used)
                state = trainer.broadcast_state(st)  # resets the plane
                if js:
                    if "comm" in js:
                        trainer.restore_comm_state(js["comm"])
                    if "sentry" in js:
                        sentry.load_state(js["sentry"])
                    if "membership" in js:
                        membership.load_state(js["membership"])
                    if "workers" in js:
                        # PER-WORKER momentum history: the consensus
                        # snapshot carries worker 0's only (broadcast
                        # replicated it), but each worker's local-SGD
                        # momentum differs — put the true stacks back
                        state = restore_worker_history(
                            state, js["workers"], ctx.mesh
                        )
                    if stale and "stale" in js:
                        # bounded staleness: worker replicas DIVERGE
                        # between boundaries (absent workers keep their
                        # own params), so the full per-worker stacks
                        # replace the broadcast consensus, and the
                        # worker-round ledger resumes where it was
                        state = restore_worker_replicas(
                            state, js["stale"]["replicas"], ctx.mesh
                        )
                        ctx.trainer.load_stale_state(
                            js["stale"]["ledger"]
                        )
            else:
                trainer.reset_comm_state()
                if stale:
                    trainer.reset_stale_state()
                state = trainer.init_state(seed=ctx.seed)
            restore_s = time.perf_counter() - t0
        else:
            trainer.reset_comm_state()
            if stale:
                trainer.reset_stale_state()
            state = trainer.init_state(seed=ctx.seed)

        rounds_executed: List[int] = []
        round_ms: List[float] = []
        losses = None
        for r in range(start_round, rounds):
            t_r = time.perf_counter()
            view = membership.advance(r)
            meta = {}
            if stale:
                # the journal VERSIONS every worker's round vector: the
                # intent records what each worker was about to fold,
                # the commit (below) what it folded — resume replays
                # <= stale_bound rounds from exactly this vector
                meta = {
                    "worker_rounds": [
                        int(v) for v in trainer.worker_rounds
                    ],
                    "stale_bound": ctx.stale_bound,
                }
            if jr is not None:
                # the WRITE-AHEAD intent: everything restart needs to
                # know what round ``r`` was (the exactly-once bracket)
                jr.begin_round(
                    r,
                    iter=r * ctx.tau,
                    view_epoch=view.epoch,
                    cursor=r,
                    rng="default_train_key(0)",
                    **meta,
                )
            if stale:
                # each worker consumes the window of its OWN next
                # round — a pure function of the journaled ledger
                host = stale_window(ctx.batch_for, trainer.worker_rounds)
            else:
                host = ctx.batch_for(r)
            maybe_kill("assemble", r)
            placed = shard_leading(host, ctx.mesh)
            maybe_kill("h2d", r)
            if stale:
                state, losses, stats = trainer.round(
                    state, placed, arrived=ctx.arrival_for(),
                    round_index=r,
                )
            else:
                state, losses, stats = trainer.round(
                    state, placed, round_index=r
                )
            rounds_executed.append(r)
            maybe_kill("execute", r)
            if stale:
                # the mid-async-boundary preemption: the arrival set
                # folded and the ledger advanced in memory, but neither
                # the snapshot nor the commit record landed
                maybe_kill("stale_boundary", r)
                lb = trainer.last_boundary
                sentry.observe(
                    r, losses, stats,
                    arrived=lb["arrived"],
                    worker_rounds=[
                        lb["boundary"] - l for l in lb["lag"]
                    ],
                )
            else:
                sentry.observe(r, losses, stats)
            maybe_kill("average", r)
            # the durable boundary: full job state beside params, then
            # the commit record referencing it
            host_state = jax.device_get(state)
            consensus = first_worker(host_state)
            extra = {
                "sentry": sentry.export_state(),
                "membership": membership.export_state(),
                "cursor": {"next_round": r + 1},
                # per-worker momentum stacks (the consensus model/state
                # files keep worker 0's view only)
                "workers": export_worker_history(host_state),
            }
            comm_state = trainer.export_comm_state()
            if comm_state is not None:
                extra["comm"] = comm_state
            if stale:
                # full per-worker replicas + the ledger: stale worker
                # states diverge by design, so the consensus snapshot
                # under-determines the fleet
                extra["stale"] = {
                    "ledger": trainer.export_stale_state(),
                    "replicas": export_worker_replicas(host_state),
                }
            if kp == "snapshot_mid_write" and r == kr:
                # the preemption lands while the solverstate tmp is
                # written but unpublished — restore must never see it
                checkpoint.set_crash_hook(
                    lambda path: (
                        kill()
                        if path.endswith(".solverstate.npz")
                        else None
                    )
                )
            try:
                _, state_path = checkpoint.snapshot(
                    ctx.solver, consensus, prefix,
                    fmt="BINARYPROTO", extra_state=extra,
                )
            finally:
                checkpoint.set_crash_hook(None)
            if jr is not None:
                if kp == "journal_mid_append" and r == kr:
                    jr.crash_hook = kill
                commit_meta = dict(meta)
                if stale:
                    # post-fold vector: what the boundary durably owns
                    commit_meta["worker_rounds"] = [
                        int(v) for v in trainer.worker_rounds
                    ]
                jr.commit_round(
                    r,
                    iter=(r + 1) * ctx.tau,
                    snapshot=os.path.basename(state_path),
                    **commit_meta,
                )
            round_ms.append((time.perf_counter() - t_r) * 1e3)

        final_comm = trainer.export_comm_state()
        final_sentry = sentry.export_state()
        final_stale = trainer.export_stale_state() if stale else None
        return {
            "rounds": rounds,
            "start_round": start_round,
            "rounds_executed": rounds_executed,
            "final_iter": int(
                np.asarray(jax.device_get(state.iter)).reshape(-1)[0]
            ),
            "final_digest": state_digest(
                state, final_comm, final_sentry, final_stale
            ),
            "final_loss": (
                float(np.mean(np.asarray(jax.device_get(losses))))
                if losses is not None
                else None
            ),
            "sentry_ema": final_sentry["ema"],
            "view_epoch": membership.view.epoch,
            "journal": journal,
            "journal_truncated_bytes": (
                jr.truncated_bytes if jr is not None else 0
            ),
            "resumed_from": resumed_from,
            "resume_info": info,
            "stale_bound": ctx.stale_bound,
            "worker_rounds": (
                [int(v) for v in trainer.worker_rounds]
                if stale
                else None
            ),
            "restore_s": (
                round(restore_s, 4) if restore_s is not None else None
            ),
            "round_ms": [round(m, 2) for m in round_ms],
        }
    finally:
        if jr is not None:
            jr.close()


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workdir", required=True)
    p.add_argument("--rounds", type=int, default=4)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--tau", type=int, default=2)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--compress", default="int8")
    p.add_argument(
        "--stale_bound", type=int, default=0,
        help="run the bounded-staleness driver leg: the last worker "
        "straggles (never self-arrives; the bound forces it), the "
        "journal versions the worker-round vector, snapshots carry "
        "full per-worker replicas.  0 = the synchronous driver",
    )
    p.add_argument(
        "--kill_at", default=None, metavar="PHASE:ROUND",
        help="SIGKILL self at this phase boundary of this round "
        f"(phases: {', '.join(KILL_POINTS)})",
    )
    p.add_argument("--resume", action="store_true")
    p.add_argument(
        "--no_journal", dest="journal", action="store_false",
        default=True,
        help="run without the ledger (the divergence control: resume "
        "resets EF residuals / sentry state)",
    )
    p.add_argument("--fsync", default="commit")
    args = p.parse_args(argv)

    # the virtual mesh must exist before any backend use
    from sparknet_tpu.utils.devices import (
        enable_compile_cache,
        ensure_devices,
    )

    ensure_devices(max(args.workers, 2))
    enable_compile_cache()

    ctx = RecoverContext(
        args.workdir,
        workers=args.workers,
        tau=args.tau,
        batch=args.batch,
        seed=args.seed,
        compress=args.compress,
        stale_bound=args.stale_bound,
    )
    rec = run_driver(
        ctx,
        args.rounds,
        journal=args.journal,
        resume=args.resume,
        kill_at=parse_kill_at(args.kill_at),
        fsync=args.fsync,
    )
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
