"""Chaos harness (``runtime/chaos.py``): the tier-1 smoke runs the FULL
default FaultPlan on the virtual mesh — storage faults healed by retry,
a producer stall through the prefetch watchdog, a real SIGHUP
preemption with simulated process death, newest-snapshot corruption
quarantined + fallback restore, and a dead dp worker masked out of the
average — and requires every injected fault survived plus a final loss
inside the no-fault baseline's band."""

import dataclasses
import os

import pytest

from sparknet_tpu.runtime import chaos


def test_default_plan_covers_every_fault_class():
    plan = chaos.FaultPlan.default()
    assert plan.storage_faults and plan.stall_rounds
    assert plan.preempt_round is not None and plan.corrupt_newest
    assert plan.dead_worker is not None
    # the divergence fault: a poisoned worker at a seeded round, caught
    # by the numerics audit before the average (obs/health.py)
    assert plan.nan_round is not None and plan.nan_workers
    # nan fires before the preemption so the detection isn't lost to
    # the resume replay, and on a different worker than the dead one
    assert plan.nan_round < plan.preempt_round
    assert plan.dead_worker not in plan.nan_workers
    # the straggler fault: seeded before the preemption (fires once,
    # not re-fired by the replay), on a worker distinct from the nan
    # and dead ones so each fault's attribution is unambiguous, and
    # sleeping well under the stall watchdog (stalls are a different
    # fault class)
    assert plan.straggler_round is not None
    assert plan.straggler_round < plan.preempt_round
    assert plan.straggler_worker != plan.dead_worker
    assert plan.straggler_worker not in plan.nan_workers
    assert plan.straggler_s < plan.stall_timeout_s
    # the preemption must happen after at least one periodic snapshot,
    # or there is nothing valid to fall back to after the corruption
    assert plan.preempt_round + 1 > plan.snapshot_every
    # the cache faults: corruption fires BEFORE the preemption (the
    # replay must not re-fire it), the cold wipe AFTER it (the resumed
    # process is the one that pays the cold refill — the realistic case)
    assert plan.cache_corrupt_round is not None
    assert plan.cache_corrupt_round < plan.preempt_round
    assert plan.cache_cold_round is not None
    assert plan.cache_cold_round > plan.preempt_round
    # the serving-fleet faults (round 15): both fire AFTER the
    # preemption (the fleet is rebuilt lazily on the resumed process —
    # the realistic case), and the corrupt publish comes after the
    # replica death so the rejection runs against a healed fleet
    assert plan.replica_death_round is not None
    assert plan.replica_death_round > plan.preempt_round
    assert plan.publish_corrupt_round is not None
    assert plan.publish_corrupt_round > plan.replica_death_round
    # the decode-kill fault (round 19): a generation replica killed
    # mid-stream — also after the preemption (lazy gen fleet on the
    # resumed process), before the corrupt publish's round
    assert plan.decode_replica_kill_round is not None
    assert plan.decode_replica_kill_round > plan.preempt_round
    assert plan.decode_replica_kill_round <= plan.publish_corrupt_round
    # the slice preemption (round 16): the SIGTERM notice fires BEFORE
    # the SIGHUP process death (the leave must land pre-resume so the
    # replay can't re-fire it), the preempted slice is a real
    # multi-worker group, and the rejoin lands inside the run
    assert plan.slice_preempt_round is not None
    assert plan.slice_preempt_round < plan.preempt_round
    assert plan.membership_slices >= 2
    assert plan.cross_slice_every >= 2  # the two-tier schedule is on
    from sparknet_tpu.parallel.hierarchy import HierarchySpec

    spec = HierarchySpec.grouped(
        plan.workers, plan.membership_slices, plan.cross_slice_every
    )
    assert len(spec.slices[plan.slice_preempt_slice]) >= 2
    # the dead-worker fault targets a DIFFERENT slice, so the two
    # masking channels stay attributable
    assert plan.dead_worker not in spec.slices[plan.slice_preempt_slice]
    assert (
        plan.slice_preempt_round + plan.slice_relaunch_delta
        < plan.rounds
    )
    # the driver_kill fault (round 17): the crash-consistency
    # sub-scenario fires AFTER the preemption (on the resumed process,
    # like the serve faults) and inside the run
    assert plan.driver_kill_round is not None
    assert plan.preempt_round < plan.driver_kill_round < plan.rounds
    # the slow_slice fault (round 4): the bounded-staleness straggler
    # A/B fires AFTER the preemption (a bounded sub-scenario on the
    # resumed process, like driver_kill — firing before the preempt
    # would let the replay re-enter and re-fire the whole A/B), on a
    # round distinct from driver_kill so the two sub-scenarios' wall
    # clocks stay attributable, targets a real multi-worker slice
    # DISTINCT from the preempted one, and the transient slow window
    # sits strictly under the bound so zero forced waits is achievable
    assert plan.slow_slice_round is not None
    assert plan.preempt_round < plan.slow_slice_round < plan.rounds
    assert plan.slow_slice_round != plan.driver_kill_round
    assert plan.slow_slice_slice != plan.slice_preempt_slice
    assert len(spec.slices[plan.slow_slice_slice]) >= 2
    assert plan.slow_slice_rounds < plan.slow_slice_stale_bound
    assert plan.slow_slice_s < plan.stall_timeout_s


def test_no_fault_view_strips_all_faults():
    base = chaos.FaultPlan.default().no_fault_view()
    assert base.storage_faults == () and base.stall_rounds == ()
    assert base.preempt_round is None and not base.corrupt_newest
    assert base.dead_worker is None and base.nan_round is None
    assert base.straggler_round is None
    assert base.cache_corrupt_round is None
    assert base.cache_cold_round is None
    assert base.replica_death_round is None
    assert base.decode_replica_kill_round is None
    assert base.publish_corrupt_round is None
    assert base.slice_preempt_round is None
    assert base.driver_kill_round is None
    assert base.slow_slice_round is None
    # run geometry unchanged: the baseline is comparable — including
    # the two-tier hierarchy shape (both legs run the same schedule)
    plan2 = chaos.FaultPlan.default()
    assert base.membership_slices == plan2.membership_slices
    assert base.cross_slice_every == plan2.cross_slice_every
    plan = chaos.FaultPlan.default()
    for f in ("seed", "workers", "rounds", "tau", "batch"):
        assert getattr(base, f) == getattr(plan, f)


def test_corrupt_file_flips_bytes_in_place(tmp_path):
    p = str(tmp_path / "blob.bin")
    payload = bytes(range(256)) * 64
    with open(p, "wb") as f:
        f.write(payload)
    chaos.corrupt_file(p, seed=3)
    with open(p, "rb") as f:
        after = f.read()
    assert len(after) == len(payload)  # size unchanged: CRC territory
    assert after != payload


def test_storage_fault_hook_injects_then_heals():
    plan = dataclasses.replace(
        chaos.FaultPlan.default(), storage_faults=((0, 2),)
    )
    counters = {}
    hook = chaos.storage_fault_hook(plan, counters)
    with pytest.raises(ConnectionResetError):
        hook("http://x/a")
    with pytest.raises(ConnectionResetError):
        hook("http://x/a")
    assert hook("http://x/a") is None  # budget spent: attempts pass
    assert counters["storage_injected"] == 2


def test_storage_fault_hook_slots_never_bleed_into_one_fetch():
    """Each slot's faults end with a SUCCESSFUL call before the next
    slot arms — a fetch planned to survive N faults is never handed the
    next slot's faults in the same retry loop."""
    plan = dataclasses.replace(
        chaos.FaultPlan.default(), storage_faults=((0, 2), (4, 1))
    )
    counters = {}
    hook = chaos.storage_fault_hook(plan, counters)
    # fetch 1: two faults, then success (slot 0 retires on the success)
    for _ in range(2):
        with pytest.raises(ConnectionResetError):
            hook("http://x/a")
    assert hook("http://x/a") is None
    # fetch 2: exactly slot 1's single fault, then success
    with pytest.raises(ConnectionResetError):
        hook("http://x/b")
    assert hook("http://x/b") is None
    # schedule exhausted: every later call passes
    assert hook("http://x/c") is None
    assert counters["storage_injected"] == 3


def test_feed_delivers_rounds_in_order_across_watchdog_rebuild():
    """The feed's round cursor is per-producer-generation (RoundFeed):
    after a stall fires the watchdog and the feed is restarted, every
    round still arrives exactly once, in order, with the right contents
    (a stale producer thread can never skip a round) — now as the
    dp-placed device batch the training round consumes directly."""
    import jax
    import numpy as np

    from sparknet_tpu.parallel import make_mesh

    plan = dataclasses.replace(
        chaos.FaultPlan.default(),
        workers=2, tau=1, batch=4, rounds=4,
        storage_faults=(), stall_rounds=(1,),
        stall_s=0.8, stall_timeout_s=0.2,
        preempt_round=None, corrupt_newest=False, dead_worker=None,
        nan_round=None, straggler_round=None,
    )
    # distinct constant per minibatch index -> contents identify indices
    xs = [np.full((4, 3, 4, 4), i, np.float32) for i in range(8)]
    ys = [np.full((4,), float(i % 4), np.float32) for i in range(8)]
    counters = {
        "storage_injected": 0, "storage_survived": 0,
        "stalls_injected": 0, "stalls_survived": 0,
    }
    mesh = make_mesh(
        {"dp": plan.workers}, devices=jax.devices()[: plan.workers]
    )
    feed = chaos._Feed(plan, xs, ys, counters, [], mesh)
    try:
        for r in range(plan.rounds):
            b = feed.next_round(r)
            data = np.asarray(b["data"])  # placed over dp by the feed
            for w in range(plan.workers):
                for t in range(plan.tau):
                    i = (r * plan.workers * plan.tau + w * plan.tau + t) % 8
                    assert float(data[w, t, 0, 0, 0, 0]) == float(i), (
                        r, w, t,
                    )
    finally:
        feed.close()
    assert counters["stalls_injected"] == 1
    assert counters["stalls_survived"] == 1


@pytest.mark.chaos
def test_chaos_smoke_default_plan(tmp_path):
    """The tier-1 chaos smoke (ISSUE 2 acceptance): default seeded
    FaultPlan, virtual mesh, every fault survived, loss in band."""
    rep = chaos.run_chaos(workdir=str(tmp_path))

    assert rep["faults_injected"] > 0
    assert rep["faults_survived"] == rep["faults_injected"]
    # every fault CLASS fired and survived
    for kind, v in rep["faults"].items():
        assert v["injected"] >= 1, kind
        assert v["survived"] == v["injected"], (kind, v)

    # the run resumed from a VERIFIED snapshot (not the corrupted one)
    assert rep["resumed_from_iter"] is not None
    assert rep["resumed_from_iter"] < rep["final_iter"]
    assert rep["quarantined"], "corrupt snapshot must be quarantined"
    assert any(".corrupt" in q for q in rep["quarantined"])
    assert rep["recovery_latency_s"] is not None
    assert 0 < rep["recovery_latency_s"] < 60

    # final loss within the no-fault run's band
    assert rep["loss_band_ok"], (
        rep["final_loss"], rep["baseline_final_loss"], rep["loss_band"]
    )

    # the seeded straggler was attributed to EXACTLY the seeded worker
    # (the profiler's per-worker verdict, ISSUE 7 acceptance)
    assert rep["faults"]["straggler_injection"]["survived"] == 1
    assert rep["straggler_detected_worker"] == rep["straggler_worker"]

    # the cache faults (ISSUE 8 acceptance): the corrupt entry was
    # quarantined (*.corrupt in the cache) and refetched byte-identical;
    # the cold wipe refilled from the backing store
    assert rep["faults"]["cache_corruption"]["survived"] == 1
    assert rep["faults"]["cache_cold"]["survived"] == 1
    assert rep["cache_stats"]["quarantined"] >= 1
    assert rep["cache_stats"]["misses"] >= 1 and (
        rep["cache_stats"]["hits"] >= 1
    )
    cache_dir = os.path.join(str(tmp_path), "chunk_cache", "objects")
    assert any(
        f.endswith(".corrupt") for f in os.listdir(cache_dir)
    ), "quarantined cache entry must stay on disk for forensics"

    # the serving-fleet faults (round 15): the dead replica was
    # ejected + respawned with zero client errors, and the corrupt
    # publish was rejected at CRC verify and quarantined in the
    # publish dir (it never reached a canary)
    assert rep["faults"]["replica_death"]["survived"] == 1
    assert rep["faults"]["published_snapshot_corrupt"]["survived"] == 1
    # the decode-kill fault (round 19): a generation replica was
    # hard-killed mid-stream and the stream RESUMED on the sibling via
    # re-prefill with a token-identical continuation
    assert rep["faults"]["decode_replica_kill"]["survived"] == 1
    assert rep["decode_replica_kill_round"] is not None
    pub_dir = os.path.join(str(tmp_path), "publish")
    assert any(
        f.endswith(".corrupt") for f in os.listdir(pub_dir)
    ), "rejected publish must be quarantined on disk"

    # the slice preemption (round 16): leave at exactly the boundary
    # after the SIGTERM, every departed round masked, rejoin completed
    # with the roster fully live and monotonic epochs
    assert rep["faults"]["slice_preemption"]["survived"] == 1
    assert rep["slice_leave_round"] == rep["slice_preempt_round"] + 1
    assert rep["slice_rejoin_round"] is not None
    assert set(rep["slice_masked_rounds"]) >= set(
        range(rep["slice_leave_round"], rep["slice_rejoin_round"])
    )
    assert all(s == "live" for s in rep["membership"]["states"])

    # the driver_kill fault (round 17): the journaled mini-driver was
    # crashed mid-commit-append, the torn ledger tail truncated on
    # resume, at most one round replayed, and the recovered trajectory
    # BIT-IDENTICAL to its uninterrupted control
    assert rep["faults"]["driver_kill"]["survived"] == 1
    dk = rep["driver_kill"]
    assert dk["crashed"] and dk["bit_identical"]
    assert dk["journal_truncated_bytes"] > 0
    assert dk["replayed_rounds"] <= 1
    assert dk["resumed_digest"] == dk["control_digest"]

    # the slow_slice fault (round 4): the bounded-staleness straggler
    # A/B — the sync control pays the whole injected tail, the stale
    # leg absorbs it with ZERO bound-forced waits, saves most of the
    # wall-clock, names the laggiest worker inside the slow slice, and
    # lands in the sync control's loss band
    assert rep["faults"]["slow_slice"]["survived"] == 1
    ss = rep["slow_slice"]
    assert ss["survived"] and ss["straggler_named_ok"]
    assert ss["stale"]["forced_waits"] == 0
    assert ss["sync"]["tail_paid_s"] >= ss["tail_injected_s"] - 1e-9
    assert ss["wallclock_saved_s"] >= 0.6 * ss["tail_injected_s"]
    assert ss["loss_band_ok"]
    assert set(ss["stale"]["laggiest_by_slow_round"]) <= set(
        ss["workers"]
    )

    # quarantined files really are on disk, out of the resume scan
    corrupt = [f for f in os.listdir(str(tmp_path)) if f.endswith(".corrupt")]
    assert corrupt
