"""Elastic membership + two-tier hierarchical averaging (ISSUE 13):
``runtime/membership.py`` + ``parallel/hierarchy.py`` + the trainer's
tier schedule.

Key contracts:
- membership view epochs are MONOTONIC and advance only at round
  boundaries; a late-heartbeat worker demotes to ``leaving`` (never
  straight to dead); a join racing its own leave waits until the leave
  completes (rejoin-before-leave-completes ordering);
- a flat ``HierarchySpec`` (one slice, or K=1) is BIT-IDENTICAL to
  today's single-tier round (the PR-3/PR-5 identity-pin style);
- intra-slice rounds average within each slice only (survivor masking
  and NaN semantics preserved per slice); every K-th round is the
  ordinary global round;
- readmission merges ONLY the rejoining rows (survivors untouched)
  and zeroes the rejoiners' momentum (the PR-5 rejoin contract);
- ``_place_live``'s placed-mask cache is a bounded LRU: churning
  membership masks can't grow it, and hot masks survive the churn;
- the 2-process e2e (PR-10 ``fleet_ship_worker`` pattern): one real
  shipper process killed and relaunched mid-run walks the views
  live -> leaving -> dead -> joining -> live off the fleet collector's
  verdicts.
"""

import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu import obs
from sparknet_tpu.parallel import (
    HierarchySpec,
    ParameterAveragingTrainer,
    hierarchy,
    make_mesh,
    shard_leading,
)
from sparknet_tpu.runtime import membership as membership_mod
from sparknet_tpu.runtime.membership import (
    DEAD,
    JOINING,
    LEAVING,
    LIVE,
    MembershipController,
)
from sparknet_tpu.utils.signals import SignalHandler, SolverAction

from tests.test_parallel import _data, _solver

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_obs():
    yield
    obs._reset_training_metrics_for_tests()


def _mesh(n=4):
    return make_mesh({"dp": n}, devices=jax.devices()[:n])


def _spec(k=2):
    return HierarchySpec.grouped(4, 2, k)


# ----------------------------------------------------------------------
# HierarchySpec


def test_spec_validation_and_grouping():
    s = HierarchySpec.grouped(5, 2, 3)
    assert s.slices == ((0, 1), (2, 3, 4)) or s.slices == ((0, 1, 2), (3, 4))
    assert sorted(w for sl in s.slices for w in sl) == list(range(5))
    assert s.cross_slice_every == 3
    assert s.slice_of(4) == 1
    with pytest.raises(ValueError):
        HierarchySpec(4, ((0, 1), (1, 2, 3)))  # overlap
    with pytest.raises(ValueError):
        HierarchySpec(4, ((0, 1),))  # not a partition
    with pytest.raises(ValueError):
        HierarchySpec(4, ((0, 1), (2, 3)), 0)  # K < 1


def test_spec_flatness_and_schedule():
    assert HierarchySpec.flat(4).is_flat()
    assert HierarchySpec.grouped(4, 2, 1).is_flat()  # K=1: all cross
    two = HierarchySpec.grouped(4, 2, 3)
    assert not two.is_flat()
    # cross every K-th round: r = 2, 5, 8, ...
    assert [two.is_cross_round(r) for r in range(6)] == [
        False, False, True, False, False, True,
    ]
    assert two.slice_ids() == (0, 0, 1, 1)
    # flat specs are cross every round
    assert all(HierarchySpec.flat(4).is_cross_round(r) for r in range(5))


def test_spec_from_args_cli_surface():
    import argparse

    p = argparse.ArgumentParser()
    hierarchy.add_cli_args(p)
    args = p.parse_args([])
    assert hierarchy.spec_from_args(args, 4) is None  # flat default
    args = p.parse_args(["--slices", "2", "--cross_slice_every", "4"])
    s = hierarchy.spec_from_args(args, 4)
    assert s.num_slices == 2 and s.cross_slice_every == 4
    # --elastic alone still builds a (flat) spec for the controller
    args = p.parse_args(["--elastic"])
    assert hierarchy.spec_from_args(args, 4) is not None


# ----------------------------------------------------------------------
# MembershipController


def test_view_epochs_monotonic_and_boundary_applied():
    c = MembershipController(_spec())
    assert c.epoch == 0
    v = c.advance(0)
    assert v.epoch == 0  # nothing changed: no epoch bump
    c.note_preempt(slice_index=0)
    # the event is QUEUED: the live view is unchanged until a boundary
    assert all(s == LIVE for s in c.view.states)
    v = c.advance(1)
    assert v.epoch == 1 and v.states[:2] == (LEAVING, LEAVING)
    assert list(v.live_mask()) == [0.0, 0.0, 1.0, 1.0]
    v = c.advance(2)  # leave grace expires -> dead
    assert v.epoch == 2 and v.states[:2] == (DEAD, DEAD)
    c.note_join([0, 1])
    v = c.advance(3)
    assert v.epoch == 3 and v.states[:2] == (JOINING, JOINING)
    assert c.pending_joiners() == (0, 1)
    v = c.admit(3)
    assert v.epoch == 4 and all(s == LIVE for s in v.states)
    assert c.epochs_monotonic()
    kinds = [k for _, _, k, _ in c.transitions]
    assert kinds == ["leave", "death", "join_request", "rejoin"]


def test_export_load_state_continues_the_epoch_clock():
    """Full-job-state roundtrip (crash consistency, round 17): a
    restarted driver loads the journaled roster and CONTINUES the view
    history — same epoch, same states, same leave-grace bookkeeping —
    instead of rewinding the epoch clock to zero."""
    c = MembershipController(_spec())
    c.note_preempt(slice_index=0)
    c.advance(1)  # epoch 1: slice 0 leaving
    d = c.export_state()
    assert d["epoch"] == 1 and d["round"] == 1
    c2 = MembershipController(_spec())
    c2.load_state(d)
    assert c2.view.epoch == 1 and c2.view.round == 1
    assert c2.view.states == c.view.states
    # the leave completes on schedule in the restarted controller
    v = c2.advance(2)
    assert v.states[:2] == (DEAD, DEAD) and v.epoch == 2
    assert c2.epoch > d["epoch"]  # monotonic across the restart
    # a roster sized for a different spec fails loudly
    import pytest as _pytest

    with _pytest.raises(ValueError, match="workers"):
        MembershipController(
            HierarchySpec.flat(2)
        ).load_state(d)


def test_late_heartbeat_demotes_to_leaving_not_dead():
    c = MembershipController(_spec())
    c.note_late([3])
    v = c.advance(0)
    assert v.states[3] == LEAVING  # late != dead: it may catch up
    # an explicit death completes the departure immediately
    c.note_dead([3])
    v = c.advance(1)
    assert v.states[3] == DEAD


def test_rejoin_before_leave_completes_is_deferred():
    c = MembershipController(_spec())
    c.note_preempt(workers=[2, 3])
    c.advance(0)  # leaving
    # the relaunch races the leave: join requested while still leaving
    c.note_join([2, 3])
    v = c.advance(1)
    # this boundary completes the LEAVE (dead); the join must NOT land
    # in the same boundary — leave finishes first
    assert v.states[2:] == (DEAD, DEAD)
    assert c.pending_joiners() == ()
    v = c.advance(2)
    assert v.states[2:] == (JOINING, JOINING)
    assert c.epochs_monotonic()


def test_join_on_live_worker_is_dropped():
    c = MembershipController(_spec())
    c.note_join([1])
    v = c.advance(0)
    assert v.states[1] == LIVE and v.epoch == 0  # no-op: never left


def test_fleet_view_ingestion_drives_membership():
    c = MembershipController(_spec())
    hw = {"host0": [0, 1], "host1": [2, 3]}

    def view(state, boot):
        return {"hosts": {
            "host0": {"state": "live", "boot_id": "b0"},
            "host1": {"state": state, "boot_id": boot},
        }}

    c.ingest_fleet_view(view("live", "b1"), hw)
    assert c.advance(0).epoch == 0  # healthy fleet: nothing to apply
    c.ingest_fleet_view(view("late", "b1"), hw)
    v = c.advance(1)
    assert v.states[2:] == (LEAVING, LEAVING)  # late -> leaving
    c.ingest_fleet_view(view("dead", "b1"), hw)
    v = c.advance(2)
    assert v.states[2:] == (DEAD, DEAD)
    # the relaunched process comes back LIVE with a NEW boot_id
    c.ingest_fleet_view(view("live", "b1-NEW"), hw)
    v = c.advance(3)
    assert v.states[2:] == (JOINING, JOINING)
    v = c.admit(3)
    assert all(s == LIVE for s in v.states)
    assert c.epochs_monotonic()


def test_event_queue_is_lock_free_for_signal_context():
    """Regression (review): the SIGTERM hook runs in signal-handler
    context ON the driver thread — if the signal lands while the
    driver holds the controller lock (inside advance/admit), a locked
    event queue would deadlock.  note_preempt must complete even with
    the lock held."""
    c = MembershipController(_spec())
    with c._lock:  # simulate: signal delivered mid-advance
        c.note_preempt(slice_index=0)  # must not block
    v = c.advance(0)
    assert v.states[:2] == (LEAVING, LEAVING)


def test_fast_relaunch_boot_id_flip_forces_leave_then_rejoin():
    """Regression (review): a host that crashes and relaunches BETWEEN
    collector polls reports state live with a NEW boot_id while its
    workers are still marked live — the fresh process's reinitialized
    state must walk the full leave -> rejoin path, never be averaged
    in raw under the stale mask."""
    c = MembershipController(_spec())
    hw = {"host0": [0, 1], "host1": [2, 3]}

    def view(boot):
        return {"hosts": {
            "host0": {"state": "live", "boot_id": "b0"},
            "host1": {"state": "live", "boot_id": boot},
        }}

    c.ingest_fleet_view(view("b1"), hw)
    assert c.advance(0).epoch == 0
    # the fast restart: still "live", boot_id flipped
    c.ingest_fleet_view(view("b1-NEW"), hw)
    v = c.advance(1)
    assert v.states[2:] == (DEAD, DEAD)  # old incarnation's state gone
    assert list(v.live_mask()) == [1.0, 1.0, 0.0, 0.0]
    v = c.advance(2)
    assert v.states[2:] == (JOINING, JOINING)  # rejoin requested
    v = c.admit(2)
    assert all(s == LIVE for s in v.states)
    assert c.epochs_monotonic()


def test_auto_rejoin_requests_join_after_grace():
    """AutoRejoin (cifar_app --elastic --rejoin_after): a departed
    worker's rejoin is requested N boundaries after it first left, and
    only once the leave has COMPLETED."""
    c = MembershipController(_spec())
    ar = membership_mod.AutoRejoin(c, after=2)
    c.note_preempt(slice_index=1)
    c.advance(0)
    ar.on_round(0)  # leaving since round 0 — not dead yet: no join
    c.advance(1)  # leave completes -> dead
    ar.on_round(1)  # 1 - 0 < 2: still waiting
    v = c.advance(2)
    assert v.states[2:] == (DEAD, DEAD)
    ar.on_round(2)  # 2 - 0 >= 2 and dead: join requested
    v = c.advance(3)
    assert v.states[2:] == (JOINING, JOINING)
    # disabled policy never requests anything
    c2 = MembershipController(_spec())
    ar2 = membership_mod.AutoRejoin(c2, after=0)
    c2.note_preempt(slice_index=1)
    c2.advance(0)
    c2.advance(1)
    for r in range(2, 10):
        ar2.on_round(r)
        c2.advance(r)
    assert c2.view.states[2:] == (DEAD, DEAD)


def test_sigterm_hook_marks_slice_leaving():
    c = MembershipController(_spec())
    c.sigterm_marks(1)
    try:
        with SignalHandler(
            sigint_effect=SolverAction.NONE,
            sighup_effect=SolverAction.NONE,
            sigterm_hooks=True,
        ):
            os.kill(os.getpid(), signal.SIGTERM)
            v = c.advance(0)
            assert v.states == (LIVE, LIVE, LEAVING, LEAVING)
    finally:
        c.detach()
    # handler restored: a hook-less SignalHandler scope is also clean
    assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL


def test_membership_metrics_and_healthz_block():
    tm = obs.enable_training_metrics()
    c = MembershipController(_spec())
    obs.set_membership(c)
    try:
        c.note_preempt(slice_index=0)
        c.advance(1)
        assert tm.membership_epoch.value == 1
        assert tm.membership_workers.labels("leaving").value == 2
        assert tm.membership_transitions.labels("leave").value == 2
        # /healthz carries the membership block and stays 200 (a
        # degraded-but-training fleet is not unhealthy)
        from sparknet_tpu.obs.exporter import ObsExporter

        ex = ObsExporter(tm.registry, port=0).start()
        try:
            h, p = ex.address
            with urllib.request.urlopen(
                f"http://{h}:{p}/healthz", timeout=5
            ) as rsp:
                import json

                body = json.loads(rsp.read())
            assert rsp.status == 200
        finally:
            ex.close()
        assert body["status"] == "ok"
        m = body["membership"]
        assert m["epoch"] == 1
        assert m["workers"]["leaving"] == 2
        assert m["states"][:2] == ["leaving", "leaving"]
    finally:
        obs.set_membership(None)


# ----------------------------------------------------------------------
# trainer: flat bit-identity + the two-tier schedule


def _run_rounds(mesh, data, hier, rounds=3, masks=None, round_idx=True):
    solver = _solver(momentum=0.9)
    t = ParameterAveragingTrainer(solver, mesh, hierarchy=hier)
    st = t.init_state(seed=0)
    for r in range(rounds):
        m = masks[r] if masks else None
        st, _ = t.round(
            st, shard_leading(dict(data), mesh), live_mask=m,
            round_index=r if round_idx else None,
        )
    return t, jax.device_get(st)


def test_flat_spec_bit_identical_to_single_tier():
    """The ISSUE 13 identity pin: HierarchySpec.flat AND a multi-slice
    K=1 grouping both produce states BITWISE equal to hierarchy=None
    (they run the same jitted program by construction)."""
    mesh = _mesh(4)
    data = _data(4, 2, seed=5)
    _, ref = _run_rounds(mesh, data, None)
    for hier in (HierarchySpec.flat(4), HierarchySpec.grouped(4, 2, 1)):
        t, st = _run_rounds(mesh, data, hier)
        assert t._slice_round is None  # flat: no slice program built
        for a, b in zip(
            jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(st)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_two_tier_schedule_slices_then_synchronizes():
    """Intra rounds average within a slice only (slices diverge);
    the K-th round's global average re-synchronizes everyone."""
    mesh = _mesh(4)
    data = _data(4, 2, seed=3)  # per-worker distinct data
    solver = _solver(momentum=0.9)
    t = ParameterAveragingTrainer(
        solver, mesh, hierarchy=HierarchySpec.grouped(4, 2, 2)
    )
    st = t.init_state(seed=0)
    st, _ = t.round(st, shard_leading(dict(data), mesh), round_index=0)
    leaf = jax.tree_util.tree_leaves(jax.device_get(st).params)[0]
    assert np.array_equal(leaf[0], leaf[1])  # within slice 0
    assert np.array_equal(leaf[2], leaf[3])  # within slice 1
    assert not np.array_equal(leaf[0], leaf[2])  # across slices
    st, _ = t.round(st, shard_leading(dict(data), mesh), round_index=1)
    leaf = jax.tree_util.tree_leaves(jax.device_get(st).params)[0]
    assert np.array_equal(leaf[0], leaf[2])  # cross round: global


def test_two_tier_auto_round_counter_matches_explicit():
    """Without round_index the trainer counts its own calls — same
    schedule for a fresh run."""
    mesh = _mesh(4)
    data = _data(4, 2, seed=9)
    _, a = _run_rounds(
        mesh, data, HierarchySpec.grouped(4, 2, 2), rounds=3
    )
    _, b = _run_rounds(
        mesh, data, HierarchySpec.grouped(4, 2, 2), rounds=3,
        round_idx=False,
    )
    for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_intra_round_dead_slice_does_not_poison_survivors():
    """A fully-departed slice contributes nothing to the live slice's
    intra average — even when its slots hold NaN garbage."""
    mesh = _mesh(4)
    data = _data(4, 2, seed=3)
    solver = _solver(momentum=0.9)
    t = ParameterAveragingTrainer(
        solver, mesh, hierarchy=HierarchySpec.grouped(4, 2, 2)
    )
    st = t.init_state(seed=0)
    # poison the departed slice's slots (a preempted worker's last
    # write can be garbage)
    def poison(x):
        x = np.asarray(x).copy()
        x[0] = np.nan
        return x

    st = type(st)(
        jax.tree_util.tree_map(poison, jax.device_get(st).params),
        st.stats, st.history, st.iter,
    )
    st = shard_leading(jax.device_get(st), mesh)
    mask = np.array([0, 0, 1, 1], np.float32)
    st, losses = t.round(
        st, shard_leading(dict(data), mesh), live_mask=mask,
        round_index=0,  # intra round
    )
    leaf = jax.tree_util.tree_leaves(jax.device_get(st).params)[0]
    assert np.isfinite(leaf[2]).all() and np.isfinite(leaf[3]).all()
    assert np.array_equal(leaf[2], leaf[3])


def test_hierarchy_tier_metrics_charged():
    tm = obs.enable_training_metrics()
    mesh = _mesh(4)
    data = _data(4, 2, seed=1)
    c0 = tm.hierarchy_rounds.labels("cross").value
    i0 = tm.hierarchy_rounds.labels("intra").value
    _run_rounds(mesh, data, HierarchySpec.grouped(4, 2, 2), rounds=4)
    assert tm.hierarchy_rounds.labels("cross").value - c0 == 2
    assert tm.hierarchy_rounds.labels("intra").value - i0 == 2
    assert tm.hierarchy_bytes.labels("cross").value > 0
    assert tm.hierarchy_bytes.labels("intra").value > 0


def test_mesh_spec_mismatch_rejected():
    mesh = _mesh(4)
    with pytest.raises(ValueError):
        ParameterAveragingTrainer(
            _solver(), mesh, hierarchy=HierarchySpec.flat(3)
        )


# ----------------------------------------------------------------------
# readmission


def test_readmit_state_merges_rejoiners_and_zeroes_momentum():
    mesh = _mesh(4)
    data = _data(4, 2, seed=2)
    solver = _solver(momentum=0.9)
    t = ParameterAveragingTrainer(solver, mesh)
    st = t.init_state(seed=0)
    # a few rounds so momentum is nonzero everywhere
    for r in range(2):
        st, _ = t.round(st, shard_leading(dict(data), mesh))
    before = jax.device_get(st)
    restored = jax.tree_util.tree_map(lambda x: x[3], before)  # worker 3
    merged = membership_mod.readmit_state(t, st, restored, workers=[0, 1])
    after = jax.device_get(merged)
    p_b = jax.tree_util.tree_leaves(before.params)
    p_a = jax.tree_util.tree_leaves(after.params)
    p_r = jax.tree_util.tree_leaves(restored.params)
    for b, a, r_ in zip(p_b, p_a, p_r):
        # rejoiners take the restored params; survivors untouched
        np.testing.assert_array_equal(a[0], r_)
        np.testing.assert_array_equal(a[1], r_)
        np.testing.assert_array_equal(a[2], b[2])
        np.testing.assert_array_equal(a[3], b[3])
    for b, a in zip(
        jax.tree_util.tree_leaves(before.history),
        jax.tree_util.tree_leaves(after.history),
    ):
        # the PR-5 rejoin contract: rejoiner momentum zeroed, survivor
        # momentum untouched
        assert np.all(np.asarray(a[0]) == 0)
        assert np.all(np.asarray(a[1]) == 0)
        np.testing.assert_array_equal(a[2], b[2])
        np.testing.assert_array_equal(a[3], b[3])


def test_consensus_state_skips_dead_slots():
    mesh = _mesh(4)
    solver = _solver()
    t = ParameterAveragingTrainer(solver, mesh)
    st = jax.device_get(t.init_state(seed=0))
    # mark worker-0 slots with a sentinel value
    stamped = jax.tree_util.tree_map(
        lambda x: np.concatenate(
            [np.full_like(np.asarray(x)[:1], 7.5), np.asarray(x)[1:]]
        ),
        st.params,
    )
    st = type(st)(stamped, st.stats, st.history, st.iter)
    mask = np.array([0, 1, 1, 1], np.float32)
    cons = membership_mod.consensus_state(st, mask)
    for leaf, full in zip(
        jax.tree_util.tree_leaves(cons.params),
        jax.tree_util.tree_leaves(st.params),
    ):
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(full)[1]
        )


def test_readmit_through_snapshot_restore(tmp_path):
    """The full dance: consensus snapshot -> restore_newest_valid ->
    broadcast merge -> admit — the catch-up source is the snapshot."""
    mesh = _mesh(4)
    data = _data(4, 2, seed=4)
    solver = _solver(momentum=0.9)
    t = ParameterAveragingTrainer(solver, mesh)
    c = MembershipController(_spec())
    st = t.init_state(seed=0)
    st, _ = t.round(st, shard_leading(dict(data), mesh))
    c.note_preempt(workers=[2, 3])
    c.advance(0)
    c.advance(1)  # dead
    c.note_join([2, 3])
    c.advance(2)  # joining
    prefix = str(tmp_path / "ckpt")
    st2, view = membership_mod.readmit(
        t, solver, st, prefix, c, 2, snapshot_fmt="BINARYPROTO"
    )
    assert view is not None and all(s == LIVE for s in view.states)
    # a snapshot was published (the rejoiners' catch-up source)
    from sparknet_tpu.io import checkpoint

    assert checkpoint.find_snapshots(prefix)
    after = jax.device_get(st2)
    before = jax.device_get(st)
    for a, b in zip(
        jax.tree_util.tree_leaves(after.params),
        jax.tree_util.tree_leaves(before.params),
    ):
        # survivors untouched; rejoiners equal the consensus (worker 0)
        np.testing.assert_array_equal(a[0], np.asarray(b)[0])
        np.testing.assert_allclose(
            np.asarray(a)[2], np.asarray(b)[0], rtol=0, atol=1e-6
        )
    for a in jax.tree_util.tree_leaves(after.history):
        assert np.all(np.asarray(a)[2:] == 0)  # momentum zeroed


# ----------------------------------------------------------------------
# _place_live LRU (the ISSUE 13 unbounded-cache fix)


def test_place_live_cache_is_bounded_lru_under_churn():
    """Regression: churning masks (every membership view epoch is a new
    mask value) must keep the placed-mask cache bounded, and the HOT
    all-alive mask must survive the churn (LRU, not clear-the-world)."""
    mesh = _mesh(4)
    t = ParameterAveragingTrainer(_solver(), mesh)
    hot = np.ones(4, np.float32)
    hot_placed = t._place_live(hot)
    rng = np.random.RandomState(0)
    for i in range(3 * t._LIVE_CACHE_MAX):
        m = (rng.rand(4) > 0.5).astype(np.float32)
        m[0] = 1.0 + 0.001 * i  # force a distinct value every time
        t._place_live(m)
        t._place_live(hot)  # the hot mask is touched every round
        assert len(t._live_cache) <= t._LIVE_CACHE_MAX
    # same placed array object: the hot entry was never evicted
    assert t._place_live(hot) is hot_placed


# ----------------------------------------------------------------------
# launcher slice lifecycle plumbing


def test_launcher_slice_members_grouping():
    from sparknet_tpu.tools import launch

    assert launch.proc_slice_members(4, 2) == ((0, 1), (2, 3))
    assert launch.proc_slice_members(3, 2) in (
        ((0,), (1, 2)), ((0, 1), (2,)),
    )
    assert launch.proc_slice_members(2, 1) == ((0, 1),)
    # more slices than procs clamps
    assert launch.proc_slice_members(2, 5) == ((0,), (1,))


def test_launcher_sets_slice_env_and_preempt_schedule(monkeypatch):
    """--slices/--preempt_slice plumbing WITHOUT real jax subprocesses:
    every spawned host carries SPARKNET_SLICE_ID, the preempted slice's
    processes get SIGTERM then a relaunch with SPARKNET_RELAUNCHED=1,
    and the deliberately-killed incarnation's rc is not a failure."""
    from sparknet_tpu.tools import launch

    spawned = []

    class FakeProc:
        _n = 0

        def __init__(self, cmd, env):
            self.cmd = cmd
            self.env = env
            FakeProc._n += 1
            self.pid = 9000 + FakeProc._n
            self.signals = []
            self.stdout = iter(())  # empty output stream
            self._rc = None
            self._end = time.time() + 0.6  # "runs" briefly

        def send_signal(self, sig):
            # elastic children treat SIGTERM as a preemption NOTICE
            # and keep running — the launcher must escalate to kill()
            # before relaunching the same process identity
            self.signals.append(sig)

        def poll(self):
            if self._rc is None and time.time() >= self._end:
                self._rc = 0
            return self._rc

        def wait(self, timeout=None):
            t_end = time.time() + (timeout if timeout else 60)
            while self.poll() is None:
                if time.time() >= t_end:
                    raise subprocess.TimeoutExpired(self.cmd, timeout)
                time.sleep(0.01)
            return self._rc

        def kill(self):
            if self._rc is None:
                self._rc = -9

        @property
        def returncode(self):
            return self._rc

    def fake_popen(cmd, env=None, **kw):
        p = FakeProc(cmd, env)
        spawned.append(p)
        return p

    monkeypatch.setattr(launch.subprocess, "Popen", fake_popen)

    class A:
        nprocs = 4
        devices_per_host = 1
        slices = 2
        preempt_slice = 1
        preempt_at = 0.05
        relaunch_after = 0.05
        timeout = 30
        app = "cifar"

    rc = launch._spawn_local_procs(A(), ["--rounds=1"], None)
    assert rc == 0
    # 4 originals + the 2 relaunched members of slice 1
    assert len(spawned) == 6
    # every child learned its slice
    sids = [p.env["SPARKNET_SLICE_ID"] for p in spawned[:4]]
    assert sids == ["0", "0", "1", "1"]
    # slice 1's originals were SIGTERM'd; since they kept running
    # (elastic notice semantics) the launcher escalated to a hard kill
    # and REAPED them before relaunching — and the deliberate kill's
    # rc is not a failure
    assert all(signal.SIGTERM in p.signals for p in spawned[2:4])
    assert all(p.returncode == -9 for p in spawned[2:4])
    assert all(not p.signals for p in spawned[:2])
    # the relaunched pair: same slice, relaunch marker set
    relaunched = spawned[4:]
    assert [p.env["SPARKNET_SLICE_ID"] for p in relaunched] == ["1", "1"]
    assert all(p.env.get("SPARKNET_RELAUNCHED") == "1" for p in relaunched)
    # process_id preserved across the relaunch
    orig_ids = sorted(
        a.split("=")[1] for p in spawned[2:4] for a in p.cmd
        if a.startswith("--process_id=")
    )
    new_ids = sorted(
        a.split("=")[1] for p in relaunched for a in p.cmd
        if a.startswith("--process_id=")
    )
    assert orig_ids == new_ids == ["2", "3"]


# ----------------------------------------------------------------------
# the 2-process e2e: kill and relaunch a real shipper process


def test_two_process_kill_and_relaunch_walks_membership_views(tmp_path):
    """The PR-10 fleet_ship_worker pattern: two real processes ship to
    one collector; host1 is KILLED mid-run (its workers walk
    live -> leaving/dead) and then RELAUNCHED under the same host id
    (new boot_id -> rejoin request -> joining -> admitted live)."""
    from sparknet_tpu.obs.fleet import FleetCollector
    from sparknet_tpu.utils.procs import fleet_ship_worker

    spec = _spec()
    ctl = MembershipController(spec)
    host_workers = {"host0": [0, 1], "host1": [2, 3]}
    collector = FleetCollector(
        port=0, dead_after_s=1.2, late_round_lag=2
    ).start()
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(fleet_ship_worker("MEMBER_E2E"))
    env_base = {
        **{k: v for k, v in os.environ.items()
           if not k.startswith("SPARKNET_FLEET_")},
        "PYTHONPATH": _REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "SPARKNET_SHIP_TO": collector.url,
        "SPARKNET_SHIP_INTERVAL_S": "0.1",
        "SPARKNET_FLEET_ROUNDS": "4",
        "SPARKNET_FLEET_ROUND_S": "0.1",
        "SPARKNET_FLEET_LINGER_S": "300",
    }

    def spawn(pid):
        return subprocess.Popen(
            [sys.executable, script, str(pid)],
            env={**env_base, "SPARKNET_HOST_ID": f"host{pid}"},
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    procs = [spawn(0), spawn(1)]
    relaunched = None
    seen = []
    try:
        deadline = time.time() + 300
        r = 0

        def step():
            nonlocal r
            ctl.ingest_fleet_view(collector.fleet_view(), host_workers)
            v = ctl.advance(r)
            seen.append(tuple(v.states))
            r += 1
            return v

        # phase A: both hosts live
        while time.time() < deadline:
            v = step()
            if all(s == LIVE for s in v.states) and len(
                collector.fleet_view()["hosts"]
            ) == 2:
                break
            time.sleep(0.2)
        assert all(s == LIVE for s in ctl.view.states)
        # phase B: kill host1 mid-run -> its workers must go dead
        procs[1].kill()
        while time.time() < deadline:
            v = step()
            if v.states[2:] == (DEAD, DEAD):
                break
            time.sleep(0.2)
        assert ctl.view.states[2:] == (DEAD, DEAD), seen
        assert ctl.view.states[:2] == (LIVE, LIVE)
        # phase C: relaunch host1 (same host id, NEW process/boot_id)
        relaunched = spawn(1)
        while time.time() < deadline:
            v = step()
            if ctl.pending_joiners() == (2, 3):
                break
            time.sleep(0.2)
        assert ctl.pending_joiners() == (2, 3), seen
        v = ctl.admit(r)
        assert all(s == LIVE for s in v.states)
        assert ctl.epochs_monotonic()
        kinds = [k for _, _, k, _ in ctl.transitions]
        # the full walk: a leave-class demotion (late or straight
        # death, depending on timing), then death, join, rejoin
        assert kinds[-2:] == ["join_request", "rejoin"]
        assert "death" in kinds
    finally:
        for p in procs + ([relaunched] if relaunched else []):
            if p.poll() is None:
                p.kill()
        for p in procs + ([relaunched] if relaunched else []):
            try:
                p.wait(timeout=30)
            except Exception:
                pass
        collector.close()
