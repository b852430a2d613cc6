"""Crash-consistent recovery (``runtime/recover.py`` + the journaled
resume paths): in-process kill/resume legs proven BIT-IDENTICAL to an
uninterrupted control, the no-journal divergence control, the
AllReduceTrainer resume bit-equivalence, and the async-checkpointer
preemption drain (SIGTERM flush; SIGKILL mid-write never loses the
previous snapshot).

The real-SIGKILL sweep (``chaos.run_kill_sweep``) is the ``@slow``
subprocess smoke below."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest
import jax

from sparknet_tpu import config
from sparknet_tpu.io import checkpoint
from sparknet_tpu.parallel import AllReduceTrainer, make_mesh
from sparknet_tpu.runtime import recover
from sparknet_tpu.solver import Solver
from sparknet_tpu.utils.signals import SignalHandler, SolverAction

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NET = """
name: "rc_net"
layer { name: "data" type: "HostData" top: "x" top: "label"
  java_data_param { shape { dim: 8 dim: 6 } shape { dim: 8 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
  inner_product_param { num_output: 16 weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "logits"
  inner_product_param { num_output: 4 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""


def _tiny_solver():
    sp = config.parse_solver_prototxt(
        'base_lr: 0.05 lr_policy: "fixed" momentum: 0.9'
    )
    return Solver(sp, net_param=config.parse_net_prototxt(NET))


def _window(tau, seed):
    rng = np.random.RandomState(seed)
    return {
        "x": rng.randn(tau, 8, 6).astype(np.float32),
        "label": rng.randint(0, 4, (tau, 8)).astype(np.float32),
    }


def _boom():
    raise recover.SimulatedKill()


# ---------------------------------------------------------------------------
# the journaled driver loop: kill -> resume -> bit-identity


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    """One compiled cifar10_quick recover context shared by every leg
    (int8 delta averaging: real EF-residual state is carried)."""
    return recover.RecoverContext(
        str(tmp_path_factory.mktemp("recover")),
        workers=2, tau=1, batch=8,
    )


@pytest.fixture(scope="module")
def control(ctx):
    return recover.run_driver(
        ctx, 3, run_dir=os.path.join(ctx.workdir, "control")
    )


def _crash_then_resume(ctx, kill_at, name, journal=True):
    d = os.path.join(ctx.workdir, name)
    with pytest.raises(recover.SimulatedKill):
        recover.run_driver(
            ctx, 3, journal=journal, kill_at=kill_at, kill=_boom,
            run_dir=d,
        )
    return recover.run_driver(ctx, 3, journal=journal, resume=True,
                              run_dir=d)


def test_control_run_shape(control):
    assert control["rounds_executed"] == [0, 1, 2]
    assert control["final_iter"] == 3
    assert control["journal"] is True


def test_kill_after_execute_resumes_bit_identical(ctx, control):
    """Crash after the round trained but before its boundary was
    durable: the resume rewinds to the previous committed boundary,
    re-executes exactly that one round, and the full-job-state digest
    (params, per-worker momentum, EF residuals, sentry EMA) matches
    the uninterrupted control bit for bit."""
    rec = _crash_then_resume(ctx, ("execute", 1), "kill_execute")
    assert rec["start_round"] == 1
    assert rec["rounds_executed"] == [1, 2]  # exactly one replay
    assert rec["final_digest"] == control["final_digest"]
    assert rec["resume_info"]["in_flight_round"] == 1


def test_kill_mid_journal_append_truncates_and_recovers(ctx, control):
    """Half a commit frame lands durably: open() must truncate the torn
    tail, the round whose commit tore re-executes, and the snapshot it
    had already published (beyond the committed boundary) is ignored —
    never restored, never double-counted."""
    rec = _crash_then_resume(
        ctx, ("journal_mid_append", 1), "kill_journal"
    )
    assert rec["journal_truncated_bytes"] > 0
    assert rec["start_round"] == 1
    assert rec["final_digest"] == control["final_digest"]


def test_kill_mid_snapshot_write_keeps_previous_boundary(ctx, control):
    """The solverstate tmp is written but never published: the previous
    boundary stays the newest valid restore point and the in-flight
    round re-executes."""
    rec = _crash_then_resume(
        ctx, ("snapshot_mid_write", 1), "kill_snapmid"
    )
    assert rec["start_round"] == 1
    assert rec["resumed_from"].endswith("_iter_1.solverstate.npz")
    assert rec["final_digest"] == control["final_digest"]


def test_kill_before_round_executes_replays_nothing(ctx, control):
    rec = _crash_then_resume(ctx, ("assemble", 1), "kill_assemble")
    assert rec["start_round"] == 1
    assert rec["rounds_executed"] == [1, 2]
    assert rec["final_digest"] == control["final_digest"]


@pytest.mark.parametrize("phase", ["h2d", "average"])
def test_kill_at_the_other_phase_boundaries_resumes_bit_identical(
    ctx, control, phase
):
    """The kill points the named cases above leave: the batch placed but
    the round not run, and the round averaged but its boundary not yet
    durable.  Both replay exactly the round that was in flight."""
    rec = _crash_then_resume(ctx, (phase, 1), "kill_" + phase)
    assert rec["start_round"] == 1
    assert rec["rounds_executed"] == [1, 2]
    assert rec["final_digest"] == control["final_digest"]


def test_kill_at_stale_boundary_replays_within_the_bound(tmp_path):
    """Bounded staleness: the arrival set folded and the ledger advanced
    in memory, but neither snapshot nor commit landed.  The resume
    replays the boundary from the journaled worker-round vector, at most
    ``stale_bound`` rounds, to the uninterrupted run's digest."""
    bound, rounds, kill_round = 2, 4, 2
    sctx = recover.RecoverContext(
        str(tmp_path), workers=2, tau=1, batch=8, stale_bound=bound
    )
    control = recover.run_driver(
        sctx, rounds, run_dir=str(tmp_path / "control")
    )
    d = str(tmp_path / "kill_stale")
    with pytest.raises(recover.SimulatedKill):
        recover.run_driver(
            sctx, rounds, kill_at=("stale_boundary", kill_round), kill=_boom,
            run_dir=d,
        )
    rec = recover.run_driver(sctx, rounds, resume=True, run_dir=d)
    assert rec["final_digest"] == control["final_digest"]
    replayed = [r for r in rec["rounds_executed"] if r <= kill_round]
    assert 1 <= len(replayed) <= bound
    assert rec["rounds_executed"][-1] == rounds - 1


def test_no_journal_resume_diverges(ctx, control):
    """The non-vacuous control: the SAME crash without the ledger
    resumes from the plain newest snapshot — EF residuals and
    per-worker momentum reset — and the trajectory measurably
    diverges.  This is exactly what the journal exists to prevent."""
    rec = _crash_then_resume(
        ctx, ("average", 1), "nojournal", journal=False
    )
    assert rec["final_digest"] != control["final_digest"]


def test_journal_is_bit_neutral_on_uninterrupted_runs(ctx, control):
    """Ledger on vs off changes nothing about the math: an
    uninterrupted journal-off run digests identically."""
    rec = recover.run_driver(
        ctx, 3, journal=False,
        run_dir=os.path.join(ctx.workdir, "nojournal_full"),
    )
    assert rec["final_digest"] == control["final_digest"]


def test_jobstate_carries_comm_sentry_membership(ctx):
    """The full-job-state inventory is really on disk beside the
    params: comm residuals, sentry scalars, membership epoch, cursor,
    per-worker history — all under the CRC manifest."""
    d = os.path.join(ctx.workdir, "control")
    state_path = checkpoint.find_snapshots(
        os.path.join(d, "recover_ckpt")
    )[-1]
    js = checkpoint.load_job_state(state_path)
    assert js["comm"]["compress"] == "int8"
    assert len(js["comm"]["resid"]) > 0
    assert "ema" in js["sentry"] and "cooldown" in js["sentry"]
    assert js["membership"]["states"] == ["live", "live"]
    assert js["cursor"]["next_round"] == 3
    assert len(js["workers"]["history"]) > 0
    checkpoint.verify_snapshot(state_path)


def test_comm_restore_state_rejects_mismatches(ctx):
    plane = ctx.trainer._comm
    exported = plane.export_state()
    assert exported is not None and exported["compress"] == "int8"
    with pytest.raises(ValueError, match="compress"):
        plane.restore_state({"compress": "bf16", "resid": {}})
    bad = {
        "compress": "int8",
        "resid": {str(i): np.zeros((1,), np.float32)
                  for i in range(len(exported["resid"]))},
    }
    with pytest.raises(ValueError, match="shape"):
        plane.restore_state(bad)
    # a faithful roundtrip is accepted
    plane.restore_state(exported)


# ---------------------------------------------------------------------------
# AllReduceTrainer resume bit-equivalence (the existing identity tests
# cover only the parameter-averaging trainer)


def test_allreduce_kill_resume_bit_equivalent(tmp_path):
    """Kill + resume at a round boundary on the allreduce path: the
    resumed TrainState equals the uninterrupted control exactly."""
    tau, rounds, snap_at = 2, 4, 1
    prefix = str(tmp_path / "ar_ck")

    def run(trainer, state, start, stop):
        for r in range(start, stop):
            state, _ = trainer.step(state, _window(tau, seed=r))
        return state

    solver = _tiny_solver()
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    trainer = AllReduceTrainer(solver, mesh)
    state = trainer.init_state(seed=0)
    state = run(trainer, state, 0, snap_at + 1)
    checkpoint.snapshot(solver, jax.device_get(state), prefix)
    control = jax.device_get(run(trainer, state, snap_at + 1, rounds))

    # "kill": the live state is gone; only the snapshot survives
    st, used = checkpoint.restore_newest_valid(solver, prefix)
    resumed = trainer.shard_state(st)
    assert int(np.asarray(st.iter)) == (snap_at + 1) * tau
    resumed = jax.device_get(
        run(trainer, resumed, snap_at + 1, rounds)
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(control),
        jax.tree_util.tree_leaves(resumed),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# async-checkpointer preemption drain (SIGTERM hook + bounded flush)


def test_async_ckpt_sigterm_hook_flushes_inflight_write(tmp_path):
    """A SIGTERM landing mid-async-write used to abandon it (daemon
    worker, tmp left behind, round's snapshot silently skipped).  The
    checkpointer's sigterm hook now drains the in-flight write before
    the handler returns."""
    solver = _tiny_solver()
    state = solver.init_state(seed=0)
    state, _ = solver.step(state, _window(2, seed=0))
    prefix = str(tmp_path / "ck")
    ckpt = checkpoint.AsyncCheckpointer()
    # slow the publish down so the SIGTERM really lands mid-write
    checkpoint.set_crash_hook(lambda path: time.sleep(0.3))
    try:
        with SignalHandler(
            sigint_effect=SolverAction.NONE,
            sighup_effect=SolverAction.NONE,
            sigterm_hooks=True,
        ):
            ckpt.save(solver, state, prefix)
            os.kill(os.getpid(), signal.SIGTERM)
            # the handler ran the drain hook synchronously: by the
            # time the signal returns, the write is published
            assert ckpt._thread is None
    finally:
        checkpoint.set_crash_hook(None)
        ckpt.close()
    snaps = checkpoint.find_snapshots(prefix)
    assert len(snaps) == 1
    checkpoint.verify_snapshot(snaps[0])
    assert not [p for p in os.listdir(str(tmp_path)) if ".tmp-" in p]


def test_async_ckpt_close_detaches_hooks(tmp_path):
    from sparknet_tpu.utils import signals as signals_mod

    ckpt = checkpoint.AsyncCheckpointer()
    assert ckpt._drain in signals_mod._sigterm_hooks
    ckpt.close()
    assert ckpt._drain not in signals_mod._sigterm_hooks
    ckpt.close()  # idempotent


def test_sigkill_mid_async_write_previous_snapshot_survives(tmp_path):
    """A REAL SIGKILL while the async worker is mid-solverstate-write:
    nothing half-written publishes (tmp only), and
    ``restore_newest_valid`` still finds the PREVIOUS snapshot."""
    script = tmp_path / "killer.py"
    script.write_text(
        """
import os, signal, sys
sys.path.insert(0, %r)
import numpy as np
from sparknet_tpu import config
from sparknet_tpu.io import checkpoint
from sparknet_tpu.solver import Solver

NET = %r
sp = config.parse_solver_prototxt(
    'base_lr: 0.05 lr_policy: "fixed" momentum: 0.9'
)
solver = Solver(sp, net_param=config.parse_net_prototxt(NET))
state = solver.init_state(seed=0)
prefix = os.path.join(%r, "ck")
checkpoint.snapshot(solver, state, prefix)  # the previous boundary
print("FIRST_SNAPSHOT_DONE", flush=True)
state = state._replace(iter=np.asarray(2, np.int32))
checkpoint.set_crash_hook(
    lambda p: os.kill(os.getpid(), signal.SIGKILL)
    if p.endswith(".solverstate.npz") else None
)
ckpt = checkpoint.AsyncCheckpointer()
ckpt.save(solver, state, prefix)
ckpt.wait()
print("UNREACHABLE", flush=True)
"""
        % (_REPO, NET, str(tmp_path))
    )
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0  # SIGKILLed
    assert "FIRST_SNAPSHOT_DONE" in proc.stdout
    assert "UNREACHABLE" not in proc.stdout
    solver = _tiny_solver()
    prefix = str(tmp_path / "ck")
    st, used = checkpoint.restore_newest_valid(solver, prefix)
    assert int(np.asarray(st.iter)) == 0  # the previous boundary
    # the torn write never published a solverstate for iter 2
    assert not any(
        "_iter_2.solverstate" in p for p in checkpoint.find_snapshots(prefix)
    )


# ---------------------------------------------------------------------------
# the real-SIGKILL sweep, one point (tier-1 runs the in-process legs
# above)


@pytest.mark.slow
def test_subprocess_kill_sweep_smoke(tmp_path):
    from sparknet_tpu.runtime import chaos

    rep = chaos.run_kill_sweep(
        workdir=str(tmp_path), rounds=3, kill_round=1,
        kill_points=("journal_mid_append",),
    )
    assert rep["killpoints_survived"] == rep["killpoints_total"] == 1
    assert rep["bit_identical_all"] is True
    assert rep["max_replayed_rounds"] <= 1
    assert rep["no_journal_diverged"] is True
    assert rep["journal_bit_neutral"] is True
