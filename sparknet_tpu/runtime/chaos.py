"""Deterministic chaos harness: prove the fault-tolerance layer end to end.

SparkNet got fault tolerance for free from Spark's RDD lineage — a lost
partition recomputed and the averaging loop never noticed (PAPER.md §2).
The TPU rewrite has to EARN the same property, and this module is the
proof: a seeded ``FaultPlan`` injects the defining failure modes of a
real TPU pod into a small cifar10_quick run on the virtual mesh —

- **storage faults**: transient connection-resets in the data fetch,
  healed by ``utils/retry`` (the same layer ``data/object_store._get``
  sits on),
- **feed stalls**: the producer wedges past the ``Prefetcher`` stall
  watchdog; the driver tears the prefetcher down (robust ``stop()``)
  and rebuilds it,
- **preemption**: a real SIGHUP delivered mid-run — snapshot, simulated
  process death, resume,
- **snapshot corruption**: the newest snapshot's bytes are flipped, so
  resume must quarantine it and fall back to the newest VALID one
  (``io/checkpoint.restore_newest_valid``),
- **worker death**: one dp worker drops out mid-run; survivor-aware
  averaging (``ParameterAveragingTrainer.round(live_mask=...)``) keeps
  the weights healthy.
- **nan injection**: one dp worker's batch is poisoned with NaN at a
  seeded round; the numerics audit (``obs/health.py``) must flag that
  EXACT round and the in-graph sentry mask must exclude the poisoned
  replica from the parameter average before it reaches the ``psum``.
- **straggler injection**: one dp worker's batch assembly sleeps at a
  seeded round (a slow host / degraded chip stand-in); the round-
  anatomy profiler (``obs/profile.py``) must attribute the slow round
  to EXACTLY the seeded worker (per-worker timing hooks + straggler
  verdict) — the signal ROADMAP item 1's elastic membership needs to
  know *which* worker to evict.
- **cache corruption**: the chunk cache's published entry for a seeded
  round's data chunk is byte-flipped on disk (size unchanged — only
  the CRC manifest can catch it); the cache must QUARANTINE the entry
  (``*.corrupt``) and transparently refetch byte-identical data from
  the backing store (``data/chunk_cache.py``).
- **cache cold**: the whole cache is wiped at a seeded round (host
  restart / cache-volume loss stand-in); the read must miss, refetch,
  and training must not notice.
- **replica death**: one replica of a serving fleet
  (``serve/fleet.py``) is hard-killed mid-traffic; the router must
  eject it on sight, retry its in-flight requests on live siblings
  (zero client errors), and ``respawn`` must return it to rotation.
- **published snapshot corrupt**: a snapshot published for delivery
  (``serve/publish.py``) has its model bytes flipped on disk (size
  unchanged); the delivery watcher (``serve/delivery.py``) must
  REJECT it at CRC verify — it must never reach a canary — and
  quarantine the publish ``*.corrupt``.
- **decode replica kill**: a replica of a GENERATION fleet
  (``serve/generate.py`` engines under continuous batching) is
  hard-killed MID-STREAM, with a client half-way through its tokens;
  the router must eject it and RESUME the stream on a sibling via
  re-prefill of prompt + tokens-so-far — greedy decode is
  deterministic, so the full token sequence must be IDENTICAL to an
  undisturbed run — or end with a clean error event, never a hung
  connection.

Every fault is counted as injected and (when the run recovers) survived;
``run_chaos`` returns the report (faults_injected, faults_survived,
recovery latency, loss-band check against the no-fault baseline) and the
tier-1 chaos smoke (``tests/test_chaos.py::test_chaos_smoke_default_plan``)
runs the default plan and holds every fault class to it.
"""

from __future__ import annotations

import dataclasses
import os
import random
import signal as _signal
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from sparknet_tpu import obs as _obs
from sparknet_tpu.obs import profile as _profile
from sparknet_tpu.utils import retry as _retry


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A seeded, fully-deterministic schedule of faults.

    Rounds are 0-indexed and ABSOLUTE (replayed rounds after a resume
    keep their original index, so per-round faults fire exactly once).
    The default plan is the tier-1 chaos smoke: every fault class, small
    shapes, < 1 min on a CPU box."""

    seed: int = 7
    workers: int = 4
    rounds: int = 6
    tau: int = 2
    batch: int = 8
    # round -> consecutive transient storage errors before that round's
    # fetch succeeds (healed by the retry layer)
    storage_faults: Tuple[Tuple[int, int], ...] = ((1, 2), (4, 1))
    # rounds whose fetch stalls past the prefetch watchdog (fires once).
    # Round 0 by default: the consumer has no prefetch-depth lead yet,
    # so the watchdog deterministically fires and the
    # stop()-and-rebuild recovery path is what survives the fault (a
    # stall in a later round can be absorbed by the buffer instead —
    # also a survival, just a less interesting one)
    stall_rounds: Tuple[int, ...] = (0,)
    stall_s: float = 4.0
    stall_timeout_s: float = 1.0
    # SIGHUP preemption at the END of this round (None = no preemption)
    preempt_round: Optional[int] = 3
    corrupt_newest: bool = True  # corrupt newest snapshot before resume
    # this dp worker dies (drops from the average) from this round on
    dead_worker: Optional[int] = 2
    dead_from_round: int = 4
    snapshot_every: int = 2  # periodic snapshot cadence, in rounds
    # nan_injection: poison these dp workers' batches with NaN at this
    # round (fires once, by absolute round index).  The numerics audit +
    # in-graph sentry mask (obs/health.py) must catch the poisoned
    # worker(s) BEFORE the parameter average — the divergence-sentry
    # analog of the dead-worker fault.
    nan_round: Optional[int] = 2
    nan_workers: Tuple[int, ...] = (1,)
    # straggler_injection: this dp worker's batch assembly sleeps
    # straggler_s at this round (fires once, by absolute round index).
    # The round profiler's per-worker attribution must name EXACTLY
    # this worker (worst_worker + straggler verdict).  Before the
    # preemption so the resume replay cannot re-fire it; a different
    # worker than the nan/dead ones so each fault's attribution is
    # unambiguous.  Kept well under stall_timeout_s: the straggler must
    # not trip the feed watchdog (that is the stall fault's job).
    straggler_round: Optional[int] = 1
    straggler_worker: int = 3
    straggler_s: float = 0.4
    # cache_corruption: byte-flip the chunk cache's PUBLISHED entry for
    # this round's data chunk before the read (fires once, absolute
    # round).  Survived = the cache quarantined the entry (*.corrupt on
    # disk), refetched from the backing store, and served bytes
    # IDENTICAL to a direct store read.  Before the preemption so the
    # resume replay cannot re-fire it.
    cache_corrupt_round: Optional[int] = 2
    # cache_cold: wipe every published cache entry before this round's
    # read (a host restart / lost cache volume).  Survived = the read
    # misses, refetches, and the round trains normally.  AFTER the
    # preemption: the cold-cache recovery is exercised on the resumed
    # process, the realistic case.
    cache_cold_round: Optional[int] = 5
    # collector_outage: the fleet collector (obs/fleet.py) goes DOWN at
    # the end of this round and comes back collector_outage_rounds
    # rounds later (a crashed / partitioned observability plane).  The
    # per-host shipper (obs/ship.py) must keep training unblocked,
    # buffer the run-log events + metric deltas it cannot push, and
    # REPLAY them when the collector returns — survived = zero lost
    # events, zero dropped events, and the collector actually missed
    # pushes while down (the outage really bit).  Resumes before the
    # preemption so the two faults don't compound.
    collector_outage_round: Optional[int] = 1
    collector_outage_rounds: int = 2
    # replica_death: at the END of this round a 2-replica serving fleet
    # (built lazily on the chaos box, tiny toy net) loses replica 0 to
    # a hard kill mid-traffic.  Survived = every subsequent request is
    # served (router eject-and-retry, zero client errors), the dead
    # replica reads `ejected`, and a respawn returns it to rotation.
    # AFTER the preemption: the fleet is rebuilt lazily on the resumed
    # process, and the fire-once guard keeps a replay from re-killing.
    replica_death_round: Optional[int] = 4
    # decode_replica_kill: at the END of this round a 2-replica
    # GENERATION fleet (tiny TransformerLM under StreamBatcher
    # continuous batching) loses the replica serving an in-flight
    # token stream to a hard kill.  Survived = the router ejects the
    # dead replica, RESUMES the stream on the sibling by re-prefilling
    # prompt + tokens-so-far, and the client's final token sequence is
    # IDENTICAL to an undisturbed run (greedy decode is deterministic)
    # — plus respawn returns the dead replica to rotation and a fresh
    # stream serves end-to-end afterwards.  Never a hung connection.
    decode_replica_kill_round: Optional[int] = 4
    # published_snapshot_corrupt: at the END of this round the current
    # training state is PUBLISHED for delivery (passing verdict
    # attached) and its model bytes are then flipped on disk (size
    # unchanged — only the manifest CRC can catch it).  Survived = the
    # delivery watcher rejects it at verify (it never reaches a
    # canary) and quarantines the publish *.corrupt.
    publish_corrupt_round: Optional[int] = 5
    # slice_preemption: a REAL SIGTERM at the END of this round is the
    # orchestrator's preemption notice for a whole slice (the
    # membership controller's SIGTERM hook marks slice
    # slice_preempt_slice leaving; runtime/membership.py).  The
    # departed workers leave the average at the next round boundary
    # (view epoch), train masked while gone, and the relaunched slice
    # requests a rejoin slice_relaunch_delta rounds after the notice —
    # readmitted via a fresh consensus snapshot ->
    # restore_newest_valid -> broadcast_state with momentum zeroed.
    # Survived = views advanced leave -> dead -> rejoin with monotonic
    # epochs, the leave detected at EXACTLY round R+1, the average
    # renormalized over survivors every intervening round, and the
    # final roster fully live.  Before the SIGHUP preemption's round so
    # the leave lands pre-resume and the replay can't re-fire it; the
    # run also arms a two-tier HierarchySpec (membership_slices x
    # cross_slice_every), so the chaos proof covers the hierarchical
    # schedule too.
    slice_preempt_round: Optional[int] = 2
    slice_preempt_slice: int = 0
    slice_relaunch_delta: int = 1  # note_join at END of round R+delta
    membership_slices: int = 2
    cross_slice_every: int = 2
    # driver_kill: at the END of this round, one kill-point of the
    # crash-consistency sweep runs as a bounded sub-scenario
    # (runtime/recover.py): a journaled mini-driver (int8 EF residuals,
    # sentry, membership epoch all carried as job state) is crashed
    # MID-JOURNAL-APPEND — half a commit frame lands durably — and
    # resumed.  Survived = the torn tail was truncated on open, the
    # resume rewound to the last committed boundary, re-executed at
    # most ONE round, and the final state digest is BIT-IDENTICAL to
    # an uninterrupted control.  (The in-process stand-in for the
    # SIGKILL sweep, ``run_kill_sweep`` below; every kill point runs in
    # process in ``tests/test_recover.py``.)
    driver_kill_round: Optional[int] = 5
    # slow_slice: at the END of this round, a bounded A/B sub-scenario
    # (parallel/stale.py): one whole slice of a two-tier job runs
    # +slow_slice_s per round for slow_slice_rounds consecutive
    # rounds.  The synchronous control (ParameterAveragingTrainer)
    # waits for it at every boundary and pays the full tail straight
    # onto the critical path; the bounded-staleness leg
    # (BoundedStalenessTrainer, stale_bound > slow_slice_rounds) takes
    # whoever arrived, lets the slow slice go stale, and folds it in
    # after the tail clears.  Survived = the stale leg paid ZERO
    # forced waits, its wall-clock undercuts the sync control by most
    # of the injected tail, the per-worker staleness telemetry names a
    # slow-slice member as the laggiest worker every slow round, and
    # the two final losses agree within the band (the speed is not
    # bought with divergence).
    slow_slice_round: Optional[int] = 4
    slow_slice_slice: int = 1
    slow_slice_s: float = 0.5
    slow_slice_rounds: int = 3
    slow_slice_stale_bound: int = 4

    @classmethod
    def default(cls) -> "FaultPlan":
        return cls()

    def no_fault_view(self) -> "FaultPlan":
        """The same run shape with every fault removed (the baseline)."""
        return dataclasses.replace(
            self,
            storage_faults=(),
            stall_rounds=(),
            preempt_round=None,
            corrupt_newest=False,
            dead_worker=None,
            nan_round=None,
            straggler_round=None,
            cache_corrupt_round=None,
            cache_cold_round=None,
            collector_outage_round=None,
            replica_death_round=None,
            decode_replica_kill_round=None,
            publish_corrupt_round=None,
            slice_preempt_round=None,
            driver_kill_round=None,
            slow_slice_round=None,
        )


def storage_fault_hook(plan: FaultPlan, counters: Dict[str, int]):
    """A ``data/object_store.set_fault_hook`` injector: raises
    ``ConnectionResetError`` for the first N fetch attempts per planned
    round-slot, keyed round-robin by call order.  Used by tests to prove
    ``object_store._get`` heals under the SAME fault source the chaos
    run uses."""
    remaining = {r: n for r, n in plan.storage_faults}
    order = sorted(remaining)
    slot = {"i": 0}

    def hook(url: str) -> None:
        if slot["i"] >= len(order):
            return None
        r = order[slot["i"]]
        if remaining[r] > 0:
            remaining[r] -= 1
            counters["storage_injected"] = (
                counters.get("storage_injected", 0) + 1
            )
            raise ConnectionResetError(
                f"chaos: injected storage fault (slot {r}) for {url}"
            )
        # slot spent: THIS call passes (the fetch the faults were
        # aimed at succeeds) and the next slot arms for a LATER fetch —
        # slots never bleed into one call's retry loop
        slot["i"] += 1
        return None

    return hook


def chunk_name(r: int) -> str:
    """The chunk-store object name for round ``r``'s window."""
    return f"round_{r:04d}.npz"


def write_round_chunks(plan: FaultPlan, xs, ys, chunk_dir: str) -> None:
    """Serialize every round's CLEAN window arrays (the same index math
    ``_Feed`` uses) as npz chunks in a local store directory — the
    backing objects the chunk cache fronts during the chaos run.
    Idempotent; files publish atomically."""
    import io as _io

    os.makedirs(chunk_dir, exist_ok=True)
    W, tau, B, n = plan.workers, plan.tau, plan.batch, len(xs)
    for r in range(plan.rounds):
        path = os.path.join(chunk_dir, chunk_name(r))
        if os.path.exists(path):
            continue
        data = np.empty((W, tau) + xs[0].shape, np.float32)
        label = np.empty((W, tau, B), np.float32)
        for w in range(W):
            for t in range(tau):
                i = (r * W * tau + w * tau + t) % n
                data[w, t] = xs[i]
                label[w, t] = ys[i]
        from sparknet_tpu.data.chunk_cache import atomic_write_bytes

        buf = _io.BytesIO()
        np.savez(buf, data=data, label=label)
        atomic_write_bytes(path, buf.getvalue())


def corrupt_file(path: str, seed: int = 0) -> None:
    """Flip a run of bytes in the middle of ``path`` (size unchanged —
    only a checksum can catch it; truncation is the easy case)."""
    size = os.path.getsize(path)
    rng = random.Random(seed)
    with open(path, "r+b") as f:
        off = max(0, size // 2 - 8)
        f.seek(off)
        orig = f.read(16)
        f.seek(off)
        f.write(bytes((b ^ 0xA5) for b in orig) or bytes([rng.randrange(256)]))


class _CollectorOutage:
    """The collector_outage fault: a live fleet collector + this
    process's shipper, with the collector torn down for a planned span
    of rounds.  ``on_round_end`` drives pause/resume by absolute round
    (fires once — resume replays can't re-trip it); ``finalize`` stops
    the shipper (final tail flush) and judges survival: the collector
    missed pushes while down, yet ended with every enqueued event
    delivered (0 lost, 0 dropped)."""

    def __init__(self, plan: FaultPlan, counters: Dict, note):
        from sparknet_tpu.obs import trace as _trace
        from sparknet_tpu.obs.fleet import FleetCollector
        from sparknet_tpu.obs.ship import Shipper

        self.plan = plan
        self.counters = counters
        self.note = note
        self.collector = FleetCollector(port=0).start()
        self.shipper = Shipper(
            self.collector.url, host="chaos-host", interval_s=0.1
        ).start()
        # a surrounding --ship_to run's shipper is restored on close —
        # the chaos-local shipper must not permanently steal the hook
        self._prev_ship = _trace._ship
        _obs.set_ship(self.shipper)
        self._down_at: Optional[int] = plan.collector_outage_round
        self._up_at = (
            plan.collector_outage_round + plan.collector_outage_rounds
        )
        self._received_at_pause: Optional[int] = None
        self.summary: Optional[Dict] = None

    def _host_state(self) -> Dict:
        return self.collector.fleet_view()["hosts"].get("chaos-host", {})

    def on_round_end(self, r: int) -> None:
        if self._down_at is not None and r == self._down_at:
            self._down_at = None
            self._received_at_pause = self._host_state().get(
                "received_events", 0
            )
            self.collector.pause()
            self.counters["collector_outage_injected"] = 1
            _obs.fault(
                "collector_outage", round=r,
                down_rounds=self.plan.collector_outage_rounds,
            )
            self.note(
                "round %d: fleet collector DOWN for %d round(s) — "
                "shipper must buffer and replay"
                % (r, self.plan.collector_outage_rounds)
            )
        elif self._up_at is not None and r >= self._up_at:
            self._up_at = None
            self.collector.resume()
            self.note(f"round {r}: fleet collector back up")

    def finalize(self) -> Dict:
        if self._up_at is not None:  # run ended while still down
            self._up_at = None
            self.collector.resume()
        failures = self.shipper.push_failures_total
        self.shipper.stop()  # final flush ships the buffered tail
        st = self._host_state()
        received = st.get("received_events", 0)
        replayed = received - (self._received_at_pause or 0)
        lost = st.get("lost_events", 0)
        dropped = st.get("reported_dropped_total", 0)
        survived = bool(
            self.counters.get("collector_outage_injected")
            and failures > 0  # the outage really made pushes fail
            and lost == 0
            and dropped == 0
            and replayed > 0
        )
        if survived:
            self.counters["collector_outage_survived"] = 1
            self.note(
                "collector outage survived: %d push failure(s) while "
                "down, %d event(s) replayed after resume, 0 lost / 0 "
                "dropped" % (failures, replayed)
            )
            _obs.instant(
                "recovered", kind="collector_outage", replayed=replayed
            )
        self.summary = {
            "push_failures": failures,
            "events_replayed_after_resume": replayed,
            "events_received": received,
            "events_lost": lost,
            "events_dropped": dropped,
        }
        return self.summary

    def close(self) -> None:
        from sparknet_tpu.obs import trace as _trace

        if _trace._ship is self.shipper:
            _obs.set_ship(self._prev_ship)
        if self.shipper.alive:
            self.shipper.stop()
        self.collector.close()


# deploy view of the serving-fleet fault fixture: tiny net, tiny input,
# two buckets — the fleet compiles in seconds on the chaos box
_SERVE_TOY_DEPLOY = """
name: "chaos_toy"
input: "data"
input_shape { dim: 2 dim: 3 dim: 8 dim: 8 }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "logits" top: "prob" }
"""


class _ServeFaults:
    """The serving-fleet faults: ``replica_death``,
    ``published_snapshot_corrupt`` and ``decode_replica_kill``, run as
    bounded sub-scenarios at seeded round boundaries (fire once, by
    absolute round — a post-resume replay can't re-fire them).  The
    fleet is a real ``serve/fleet.py`` pool (2 replicas, toy net)
    built lazily on first use; the corrupt-publish leg publishes the
    ACTUAL training state of the chaos run through ``serve/publish.py``
    and corrupts the published model bytes; the decode-kill leg runs a
    SEPARATE 2-replica generation fleet (tiny TransformerLM under
    continuous batching) and kills the replica serving a live token
    stream."""

    def __init__(self, plan: FaultPlan, counters: Dict, note, workdir: str):
        self.plan = plan
        self.counters = counters
        self.note = note
        self.workdir = workdir
        self._death_at = plan.replica_death_round
        self._corrupt_at = plan.publish_corrupt_round
        self._decode_kill_at = plan.decode_replica_kill_round
        self._pool = None
        self._router = None
        self._gen_pool = None
        self._gen_router = None
        self._x = np.random.RandomState(plan.seed).randn(
            1, 3, 8, 8
        ).astype(np.float32)

    def _fleet(self):
        if self._pool is None:
            from sparknet_tpu import config as _cfg
            from sparknet_tpu.serve import (
                InferenceEngine, ReplicaPool, Router,
            )

            netp = _cfg.parse_net_prototxt(_SERVE_TOY_DEPLOY)

            def make_engine(weights=None):
                return InferenceEngine(
                    netp, weights=weights, buckets=(1, 2)
                )

            self._pool = ReplicaPool(make_engine, replicas=2, max_queue=32)
            self._router = Router(self._pool, max_inflight=16)
        return self._pool, self._router

    def _gen_fleet(self):
        if self._gen_pool is None:
            from sparknet_tpu.models.transformer_lm import TransformerLM
            from sparknet_tpu.serve import ReplicaPool, Router
            from sparknet_tpu.serve.generate import GenerationEngine

            def make_engine(weights=None):
                lm = TransformerLM(
                    dim=32, depth=2, heads=2, seq_len=64, vocab=64
                )
                return GenerationEngine(
                    lm, weights=weights, prefill_buckets=(16, 64),
                    max_streams=4, kv_blocks=48, kv_block_size=8,
                    seed=self.plan.seed,
                )

            self._gen_pool = ReplicaPool(
                make_engine, replicas=2, max_queue=16, stream=True
            )
            self._gen_router = Router(self._gen_pool, max_inflight=16)
        return self._gen_pool, self._gen_router

    def on_round_end(self, r: int, solver, host_state_fn) -> None:
        if self._death_at is not None and r == self._death_at:
            self._death_at = None
            self._replica_death(r)
        if self._decode_kill_at is not None and r == self._decode_kill_at:
            self._decode_kill_at = None
            self._decode_replica_kill(r)
        if self._corrupt_at is not None and r == self._corrupt_at:
            self._corrupt_at = None
            self._publish_corrupt(r, solver, host_state_fn)

    def _replica_death(self, r: int) -> None:
        pool, router = self._fleet()
        router.submit(self._x)  # fleet proven serving before the kill
        self.counters["replica_death_injected"] = 1
        _obs.fault("replica_death", round=r, replica=0)
        self.note(f"round {r}: serving replica 0 hard-killed mid-traffic")
        pool.replicas[0].kill()
        served = 0
        for _ in range(4):
            out = router.submit(self._x)  # eject-and-retry: no errors
            served += int(out.shape[0] == 1)
        ejected = pool.replicas[0].state == "ejected"
        pool.respawn(0)
        rejoined = pool.replicas[0].state == "live"
        router.submit(self._x)
        if served == 4 and ejected and rejoined:
            self.counters["replica_death_survived"] = 1
            self.note(
                f"round {r}: router ejected the dead replica, served "
                "every request on the survivor, and the respawned "
                "replica rejoined rotation"
            )
            _obs.instant("recovered", kind="replica_death", round=r)

    def _decode_replica_kill(self, r: int) -> None:
        pool, router = self._gen_fleet()
        prompt = [5, 9, 2, 7]
        max_new = 40
        # greedy decode is deterministic: an undisturbed run on either
        # replica (identical seeded weights) is the expected sequence
        expect = list(router.submit_stream(prompt, max_new))[-1]
        self.counters["decode_kill_injected"] = 1
        _obs.fault("decode_replica_kill", round=r)
        gen = router.submit_stream(prompt, max_new, timeout=30.0)
        first = next(gen)  # stream admitted + first token delivered
        victim = None
        for rep in pool.replicas:
            if rep.batcher.active_count() > 0:
                victim = rep
                break
        self.note(
            f"round {r}: generation replica "
            f"{victim.index if victim else '?'} hard-killed with a "
            "token stream in flight"
        )
        if victim is not None:
            victim.kill()
        events = [first] + list(gen)  # bounded by timeout: never hangs
        final = events[-1]
        ejected = (
            victim is not None and victim.state == "ejected"
        )
        if victim is not None and ejected:
            pool.respawn(victim.index)
        # respawn REPLACES the Replica object — re-read from the pool
        rejoined = (
            victim is not None
            and pool.replicas[victim.index].state == "live"
        )
        after = list(router.submit_stream(prompt, max_new))[-1]
        if (
            expect["event"] == "done"
            and final["event"] == "done"
            and final["tokens"] == expect["tokens"]
            and ejected
            and rejoined
            and after["event"] == "done"
            and after["tokens"] == expect["tokens"]
        ):
            self.counters["decode_kill_survived"] = 1
            self.note(
                f"round {r}: stream resumed on the sibling via "
                "re-prefill — token sequence IDENTICAL to the "
                "undisturbed run, dead replica respawned into rotation"
            )
            _obs.instant("recovered", kind="decode_replica_kill", round=r)

    def _publish_corrupt(self, r: int, solver, host_state_fn) -> None:
        from sparknet_tpu.serve import DeliveryController
        from sparknet_tpu.serve import publish as publish_mod

        pub = os.path.join(self.workdir, "publish")
        paths = publish_mod.publish_snapshot(
            solver, host_state_fn(), pub,
            {"passing": True, "reason": "chaos seeded publish"},
        )
        corrupt_file(paths[0], seed=self.plan.seed)
        self.counters["publish_corrupt_injected"] = 1
        _obs.fault(
            "published_snapshot_corrupt", round=r,
            snapshot=os.path.basename(paths[0]),
        )
        self.note(
            f"round {r}: published snapshot "
            f"{os.path.basename(paths[0])} byte-flipped on disk"
        )
        pool, router = self._fleet()
        ctl = DeliveryController(
            pool, router, pub,
            cache_dir=os.path.join(self.workdir, "delivery_cache"),
            decision_requests=2, echo=None,
        )
        act = ctl.poll_once()
        quarantined = (ctl.last_decision or {}).get("quarantined", [])
        if (
            act == "rejected"
            and ctl.rejected == 1
            and router.canary is None  # it never reached a canary
            and any(q.endswith(".corrupt") for q in quarantined)
        ):
            self.counters["publish_corrupt_survived"] = 1
            self.note(
                f"round {r}: delivery watcher REJECTED the corrupt "
                "publish at CRC verify and quarantined it "
                "(never canaried)"
            )
            _obs.instant(
                "recovered", kind="published_snapshot_corrupt", round=r
            )

    def close(self) -> None:
        if self._router is not None:
            self._router.close()
            self._router = None
            self._pool = None
        if self._gen_router is not None:
            self._gen_router.close()
            self._gen_router = None
            self._gen_pool = None


def _driver_kill_scenario(plan: FaultPlan, counters: Dict, note, workdir):
    """The driver_kill fault: crash a journaled mini-driver mid-commit
    and prove bit-identical journal-guided recovery (in-process — the
    kill hook raises instead of SIGKILLing so the chaos harness
    survives; ``run_kill_sweep`` is the real-SIGKILL version)."""
    from sparknet_tpu.runtime import recover as recover_mod

    base = os.path.join(workdir, "driver_kill")
    ctx = recover_mod.RecoverContext(
        base, workers=2, tau=1, batch=8, seed=plan.seed
    )
    kill_rounds = 3
    kill_at = ("journal_mid_append", 1)

    def boom():
        raise recover_mod.SimulatedKill("driver_kill")

    control = recover_mod.run_driver(
        ctx, kill_rounds, run_dir=os.path.join(base, "control")
    )
    counters["driver_kill_injected"] = 1
    _obs.fault(
        "driver_kill", kill_at="%s:%d" % kill_at, rounds=kill_rounds
    )
    note(
        "driver_kill: journaled driver crashed mid-commit-append at "
        "round %d (half a frame durable on disk)" % kill_at[1]
    )
    fault_dir = os.path.join(base, "fault")
    crashed = False
    try:
        recover_mod.run_driver(
            ctx, kill_rounds, kill_at=kill_at, kill=boom,
            run_dir=fault_dir,
        )
    except recover_mod.SimulatedKill:
        crashed = True
    resumed = recover_mod.run_driver(
        ctx, kill_rounds, resume=True, run_dir=fault_dir
    )
    # the crashed run executed rounds 0..kill_at[1]; anything the
    # resume re-executes in that range is a replay
    replayed = len(
        [r for r in resumed["rounds_executed"] if r <= kill_at[1]]
    )
    bit_identical = resumed["final_digest"] == control["final_digest"]
    survived = bool(
        crashed
        and resumed["journal_truncated_bytes"] > 0  # tail really torn
        and replayed <= 1
        and bit_identical
    )
    if survived:
        counters["driver_kill_survived"] = 1
        note(
            "driver_kill survived: torn tail truncated (%d bytes), "
            "resumed at round %d replaying %d round(s), final state "
            "digest BIT-IDENTICAL to the uninterrupted control"
            % (
                resumed["journal_truncated_bytes"],
                resumed["start_round"], replayed,
            )
        )
        _obs.instant(
            "recovered", kind="driver_kill", replayed=replayed,
        )
    return {
        "kill_at": "%s:%d" % kill_at,
        "crashed": crashed,
        "journal_truncated_bytes": resumed["journal_truncated_bytes"],
        "resumed_start_round": resumed["start_round"],
        "replayed_rounds": replayed,
        "bit_identical": bit_identical,
        "control_digest": control["final_digest"],
        "resumed_digest": resumed["final_digest"],
        "recovery_latency_s": resumed["restore_s"],
    }


def _slow_slice_scenario(plan: FaultPlan, counters: Dict, note, workdir):
    """The slow_slice fault: one whole slice runs ``+slow_slice_s`` per
    round for ``slow_slice_rounds`` consecutive rounds, and the
    question is what that tail COSTS.  Two bounded legs over the same
    solver/mesh (a ``runtime/recover.py`` context, two-tier hierarchy):

    - sync control (``ParameterAveragingTrainer``): every averaging
      boundary waits for the slow slice, so the job pays the full
      K x slow_s tail straight onto the critical path;
    - stale leg (``BoundedStalenessTrainer``, bound > K): the boundary
      takes whoever arrived; the slow slice goes stale (coarsened as a
      unit) and folds in after its tail clears, so the harness never
      sleeps on its behalf — the ONLY thing that can put the tail back
      on the critical path is the bound forcing a still-slow worker.

    Survived = zero forced waits in the stale leg, its measured
    wall-clock undercuts the sync control by most of the injected
    tail, the staleness ledger names a slow-slice member as the
    laggiest worker on every slow round (the fleet side can still
    point at the exact straggler), and the two final losses agree
    within the band (the speed is not bought with divergence)."""
    from sparknet_tpu.parallel import (
        BoundedStalenessTrainer,
        ParameterAveragingTrainer,
        shard_leading,
        stale_window,
    )
    from sparknet_tpu.parallel.hierarchy import HierarchySpec
    from sparknet_tpu.runtime import recover as recover_mod

    base = os.path.join(workdir, "slow_slice")
    ctx = recover_mod.RecoverContext(
        base, workers=plan.workers, tau=1, batch=plan.batch,
        seed=plan.seed, compress="none",
    )
    spec = HierarchySpec.grouped(
        plan.workers, plan.membership_slices,
        cross_slice_every=plan.cross_slice_every,
    )
    slow_members = tuple(spec.slices[plan.slow_slice_slice])
    K, slow_s = plan.slow_slice_rounds, plan.slow_slice_s
    B = plan.slow_slice_stale_bound
    rounds = max(6, K + 3)
    slow_rounds = set(range(1, 1 + K))

    counters["slow_slice_injected"] = 1
    _obs.fault(
        "slow_slice", slice=plan.slow_slice_slice,
        workers=list(slow_members), tail_s=slow_s, rounds=K,
    )
    note(
        "slow_slice: slice %d (workers %s) +%.2fs/round for rounds %s "
        "— sync control vs stale_bound=%d A/B"
        % (plan.slow_slice_slice, list(slow_members), slow_s,
           sorted(slow_rounds), B)
    )

    def leg(stale_bound: int) -> Dict:
        if stale_bound > 0:
            trainer = BoundedStalenessTrainer(
                ctx.solver, ctx.mesh, stale_bound=stale_bound,
                hierarchy=spec,
            )
        else:
            trainer = ParameterAveragingTrainer(
                ctx.solver, ctx.mesh, hierarchy=spec
            )
        state = trainer.init_state(seed=ctx.seed)
        tail_paid_s = 0.0
        forced_waits = 0
        laggiest = []
        last_losses = None
        compute_s = []  # per-round wall-clock minus this round's sleeps
        slept_s = 0.0
        for r in range(rounds):
            slow_now = r in slow_rounds
            slept_before = tail_paid_s
            t0 = time.perf_counter()
            if stale_bound > 0:
                arrived = np.ones((plan.workers,), bool)
                if slow_now:
                    arrived[list(slow_members)] = False
                    lag = trainer.lags(r)
                    if int(lag[list(slow_members)].max()) >= stale_bound:
                        # a forced arrival of a still-slow worker: the
                        # bound puts the tail back on the critical path
                        forced_waits += 1
                        tail_paid_s += slow_s
                        time.sleep(slow_s)
                state, losses, _ = trainer.round(
                    state,
                    shard_leading(
                        stale_window(ctx.batch_for, trainer.worker_rounds),
                        ctx.mesh,
                    ),
                    arrived=arrived, round_index=r,
                )
                if slow_now:
                    # post-round attribution: the ledger's laggiest
                    # worker must be a slow-slice member
                    laggiest.append(int(np.argmax(trainer.lags(r + 1))))
            else:
                if slow_now:
                    # the synchronous boundary cannot proceed without
                    # the slow slice: the whole job eats the tail
                    tail_paid_s += slow_s
                    time.sleep(slow_s)
                state, losses, _ = trainer.round(
                    state, shard_leading(ctx.batch_for(r), ctx.mesh),
                    round_index=r,
                )
            losses = np.asarray(losses)
            if r > 0:  # round 0 carries the jit compile
                dt = time.perf_counter() - t0
                round_slept = tail_paid_s - slept_before
                compute_s.append(dt - round_slept)
                slept_s += round_slept
            last_losses = losses
        # One shared CPU core and a possible mid-leg recompile or GC
        # pause can put a one-off multi-hundred-ms spike on a single
        # round and swamp the A/B; trim each leg's single worst compute
        # round (symmetric across legs) and add the sleeps back exactly.
        trimmed = sorted(compute_s)[:-1] if len(compute_s) > 1 else (
            compute_s
        )
        elapsed = sum(trimmed) + slept_s
        finite = last_losses[np.isfinite(last_losses)]
        return {
            "elapsed_s": round(elapsed, 3),
            "tail_paid_s": round(tail_paid_s, 3),
            "forced_waits": forced_waits,
            "final_loss": round(float(np.mean(finite)), 4),
            "laggiest_by_slow_round": laggiest,
        }

    sync = leg(0)
    stale = leg(B)
    tail_injected_s = K * slow_s
    saved_s = sync["elapsed_s"] - stale["elapsed_s"]
    named_ok = bool(stale["laggiest_by_slow_round"]) and all(
        w in slow_members for w in stale["laggiest_by_slow_round"]
    )
    band = max(0.5, 0.5 * abs(sync["final_loss"]))
    loss_band_ok = (
        abs(stale["final_loss"] - sync["final_loss"]) <= band
    )
    survived = bool(
        stale["forced_waits"] == 0
        and sync["tail_paid_s"] >= tail_injected_s - 1e-9
        and saved_s >= 0.6 * tail_injected_s
        and named_ok
        and loss_band_ok
    )
    if survived:
        counters["slow_slice_survived"] = 1
        note(
            "slow_slice survived: stale leg paid 0 forced waits and "
            "saved %.2fs of the %.2fs injected tail (sync control ate "
            "all of it); laggiest worker named in %s every slow round; "
            "final losses %.4f vs %.4f within band %.4f"
            % (saved_s, tail_injected_s, list(slow_members),
               stale["final_loss"], sync["final_loss"], band)
        )
        _obs.instant(
            "stale_absorbed_tail", kind="slow_slice",
            saved_s=round(saved_s, 3),
        )
    return {
        "slice": plan.slow_slice_slice,
        "workers": list(slow_members),
        "tail_s_per_round": slow_s,
        "slow_rounds": sorted(slow_rounds),
        "stale_bound": B,
        "rounds": rounds,
        "tail_injected_s": round(tail_injected_s, 3),
        "sync": sync,
        "stale": stale,
        "wallclock_saved_s": round(saved_s, 3),
        "straggler_named_ok": named_ok,
        "loss_band": round(band, 4),
        "loss_band_ok": loss_band_ok,
        "survived": survived,
    }


def run_kill_sweep(
    workdir: Optional[str] = None,
    rounds: int = 4,
    kill_round: int = 2,
    workers: int = 2,
    tau: int = 2,
    batch: int = 8,
    seed: int = 7,
    kill_points: Optional[Tuple[str, ...]] = None,
    timeout_s: float = 900.0,
    echo=None,
) -> Dict:
    """The kill-anywhere chaos sweep
    (``tests/test_recover.py::test_subprocess_kill_sweep_smoke``):
    for every phase boundary of the journaled driver loop
    (``runtime/recover.py``), a REAL ``SIGKILL`` is delivered at that
    exact point of a subprocess run, the process is relaunched with
    ``--resume``, and the resumed trajectory is judged against an
    uninterrupted control:

    - ``bit_identical``: the full-job-state digest (params, history,
      iter, EF residuals, sentry EMA) equals the control's,
    - ``replayed_rounds``: rounds the resume re-executed that the
      killed run had already executed — must be 0 or 1 (exactly-once
      at snapshot granularity; the loop snapshots every boundary),
    - latency: the resume's restore/reconcile time.

    Plus the two controls that keep the proof honest: a ``--no_journal``
    kill+resume that must DIVERGE (the journaled state really is
    load-bearing), and a journal-off uninterrupted run whose digest
    must EQUAL the control's (the ledger itself never perturbs the
    math) — also the overhead A/B baseline."""
    import json as _json
    import subprocess
    import sys as _sys

    from sparknet_tpu.runtime import recover as recover_mod

    # stale_boundary only exists on a --stale_bound > 0 driver — the
    # dedicated stale leg below kills it under the right flags; in the
    # synchronous sweep the child would refuse the phase at argparse
    kill_points = tuple(
        kp
        for kp in (kill_points or recover_mod.KILL_POINTS)
        if kp != "stale_boundary"
    )
    workdir = workdir or tempfile.mkdtemp(prefix="recover_sweep_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    base_args = [
        "--rounds", str(rounds), "--workers", str(workers),
        "--tau", str(tau), "--batch", str(batch), "--seed", str(seed),
    ]

    def say(msg: str) -> None:
        if echo is not None:
            echo("recover: " + msg)

    def child(wd: str, *extra: str):
        cmd = (
            [_sys.executable, "-m", "sparknet_tpu.runtime.recover",
             "--workdir", wd]
            + base_args + list(extra)
        )
        t0 = time.perf_counter()
        proc = subprocess.run(
            cmd, capture_output=True, text=True, env=env,
            timeout=timeout_s,
        )
        rec = None
        if proc.returncode == 0:
            for line in reversed(proc.stdout.strip().splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    rec = _json.loads(line)
                    break
            if rec is None:
                raise RuntimeError(
                    "recover child printed no JSON:\n%s\n%s"
                    % (proc.stdout[-2000:], proc.stderr[-2000:])
                )
        return proc.returncode, rec, time.perf_counter() - t0

    say("control run (journal on, no kill)")
    rc, control, _ = child(os.path.join(workdir, "control"))
    if rc != 0:
        raise RuntimeError(f"recover control run failed (rc {rc})")
    say("journal-off control (overhead baseline + bit-neutrality)")
    rc, nojournal_full, _ = child(
        os.path.join(workdir, "nojournal_full"), "--no_journal"
    )
    if rc != 0:
        raise RuntimeError(f"recover no-journal run failed (rc {rc})")

    results = []
    for kp in kill_points:
        wd = os.path.join(workdir, "kill_" + kp)
        say(f"SIGKILL at {kp}:{kill_round} -> resume")
        rc1, _, _ = child(wd, "--kill_at", f"{kp}:{kill_round}")
        killed = rc1 != 0  # SIGKILL: -9 from subprocess.run
        rc2, rec, _ = child(wd, "--resume")
        # rounds the killed run had already EXECUTED: the kill fires
        # before trainer.round for assemble/h2d, after it otherwise
        executed_before = kill_round + (
            0 if kp in ("assemble", "h2d") else 1
        )
        row = {
            "kill_at": f"{kp}:{kill_round}",
            "killed": killed,
            "resumed_rc": rc2,
            "bit_identical": bool(
                rec and rec["final_digest"] == control["final_digest"]
            ),
            "replayed_rounds": (
                len([
                    r for r in rec["rounds_executed"]
                    if r < executed_before
                ])
                if rec else None
            ),
            "recovery_latency_s": rec["restore_s"] if rec else None,
            "resumed_from": rec["resumed_from"] if rec else None,
            "start_round": rec["start_round"] if rec else None,
            "journal_truncated_bytes": (
                rec["journal_truncated_bytes"] if rec else None
            ),
        }
        row["survived"] = bool(
            row["killed"]
            and rc2 == 0
            and row["bit_identical"]
            and row["replayed_rounds"] is not None
            and row["replayed_rounds"] <= 1
        )
        say(
            "%s: %s (replayed %s, latency %ss)"
            % (
                row["kill_at"],
                "SURVIVED bit-identical" if row["survived"] else
                "FAILED " + _json.dumps(row),
                row["replayed_rounds"], row["recovery_latency_s"],
            )
        )
        results.append(row)

    # the non-vacuous control: the SAME kill without the journal must
    # visibly diverge (plain newest-snapshot resume resets the EF
    # residuals and per-worker momentum)
    say(f"no-journal divergence control: SIGKILL at average:{kill_round}")
    wd = os.path.join(workdir, "nojournal_kill")
    rc1, _, _ = child(wd, "--no_journal", "--kill_at",
                      f"average:{kill_round}")
    rc2, njrec, _ = child(wd, "--no_journal", "--resume")
    no_journal_diverged = bool(
        rc1 != 0 and rc2 == 0 and njrec
        and njrec["final_digest"] != control["final_digest"]
    )
    say(
        "no-journal resume %s the control"
        % ("DIVERGED from" if no_journal_diverged else
           "unexpectedly matched")
    )

    # the bounded-staleness leg: the SAME SIGKILL discipline on an
    # async driver (--stale_bound), killed at the stale_boundary phase
    # — the arrival set has folded and the worker-round ledger advanced
    # in memory, but neither the snapshot nor the commit record landed.
    # Resume must rewind to the journaled per-worker round vector and
    # replay at most stale_bound rounds, bit-identically against an
    # uninterrupted stale control.
    stale_bound = 2
    stale_args = ("--stale_bound", str(stale_bound))
    say(f"stale control run (stale_bound={stale_bound}, no kill)")
    rc, stale_control, _ = child(
        os.path.join(workdir, "stale_control"), *stale_args
    )
    if rc != 0:
        raise RuntimeError(f"stale recover control failed (rc {rc})")
    wd = os.path.join(workdir, "kill_stale_boundary")
    say(f"SIGKILL at stale_boundary:{kill_round} -> resume")
    rc1, _, _ = child(
        wd, *stale_args, "--kill_at", f"stale_boundary:{kill_round}"
    )
    rc2, srec, _ = child(wd, *stale_args, "--resume")
    stale_replayed = (
        len([r for r in srec["rounds_executed"] if r <= kill_round])
        if srec else None
    )
    stale_row = {
        "kill_at": f"stale_boundary:{kill_round}",
        "stale_bound": stale_bound,
        "killed": rc1 != 0,
        "resumed_rc": rc2,
        "bit_identical": bool(
            srec
            and srec["final_digest"] == stale_control["final_digest"]
        ),
        "replayed_rounds": stale_replayed,
        "recovery_latency_s": srec["restore_s"] if srec else None,
        "start_round": srec["start_round"] if srec else None,
        "journal_truncated_bytes": (
            srec["journal_truncated_bytes"] if srec else None
        ),
        "resumed_worker_rounds": (
            (srec.get("resume_info") or {}).get("worker_rounds")
            if srec else None
        ),
        "final_worker_rounds": (
            srec.get("worker_rounds") if srec else None
        ),
    }
    stale_row["survived"] = bool(
        stale_row["killed"]
        and rc2 == 0
        and stale_row["bit_identical"]
        and stale_replayed is not None
        and stale_replayed <= stale_bound
    )
    say(
        "stale_boundary:%d %s (replayed %s <= bound %d, latency %ss)"
        % (
            kill_round,
            "SURVIVED bit-identical" if stale_row["survived"] else
            "FAILED " + _json.dumps(stale_row),
            stale_row["replayed_rounds"], stale_bound,
            stale_row["recovery_latency_s"],
        )
    )

    def p50(xs):
        s = sorted(xs)
        return s[len(s) // 2] if s else None

    # steady rounds only: round 0 carries the jit compile
    j_ms = p50(control["round_ms"][1:])
    nj_ms = p50(nojournal_full["round_ms"][1:])
    overhead_pct = (
        100.0 * (j_ms - nj_ms) / nj_ms if j_ms and nj_ms else None
    )
    return {
        "rounds": rounds,
        "workers": workers,
        "tau": tau,
        "batch": batch,
        "seed": seed,
        "kill_round": kill_round,
        "killpoints_total": len(results),
        "killpoints_survived": sum(
            1 for r in results if r["survived"]
        ),
        "killpoints": results,
        "bit_identical_all": all(r["bit_identical"] for r in results),
        "max_replayed_rounds": max(
            (r["replayed_rounds"] for r in results
             if r["replayed_rounds"] is not None),
            default=None,
        ),
        "control_digest": control["final_digest"],
        "stale": stale_row,
        "stale_control_digest": stale_control["final_digest"],
        "no_journal_diverged": no_journal_diverged,
        "no_journal_digest": njrec["final_digest"] if njrec else None,
        "journal_bit_neutral": bool(
            nojournal_full["final_digest"] == control["final_digest"]
        ),
        "journal_round_ms_p50": round(j_ms, 2) if j_ms else None,
        "nojournal_round_ms_p50": round(nj_ms, 2) if nj_ms else None,
        "journal_overhead_pct": (
            round(overhead_pct, 2) if overhead_pct is not None else None
        ),
        "workdir": workdir,
    }


# ----------------------------------------------------------------------
# the chaos training run


class _Feed:
    """Deterministic per-round window builder behind the pipelined
    ``RoundFeed`` executor (assembly + dp-sharded device_put on the
    producer thread — the same executor the apps and ``cli train``
    run), with storage faults (transient errors healed by retry) and
    stalls (producer wedges past the watchdog) injected per plan."""

    def __init__(self, plan: FaultPlan, xs, ys, counters, events, mesh,
                 fault_state=None, chunk_source=None):
        self.plan = plan
        self.xs, self.ys = xs, ys
        self.counters = counters
        self.events = events
        self.mesh = mesh
        # chunk_source: (store, cache) — the round windows then arrive
        # as npz chunks read THROUGH the content-addressed chunk cache
        # (data/chunk_cache.py), which is what the cache_corruption /
        # cache_cold faults attack.  None keeps the direct in-memory
        # build (unit tests).
        self._store, self._cache = chunk_source or (None, None)
        # fault state is SHARED across prefetcher/feed rebuilds (resume
        # replays rounds by absolute index; a per-round fault fires once)
        fault_state = fault_state if fault_state is not None else {}
        fault_state.setdefault("faults", {r: n for r, n in plan.storage_faults})
        fault_state.setdefault("stalls", set(plan.stall_rounds))
        fault_state.setdefault(
            "nans",
            set() if plan.nan_round is None else {plan.nan_round},
        )
        fault_state.setdefault(
            "stragglers",
            set() if plan.straggler_round is None else {plan.straggler_round},
        )
        fault_state.setdefault(
            "cache_corrupts",
            set() if plan.cache_corrupt_round is None
            else {plan.cache_corrupt_round},
        )
        fault_state.setdefault(
            "cache_colds",
            set() if plan.cache_cold_round is None
            else {plan.cache_cold_round},
        )
        self._faults = fault_state["faults"]
        self._stalls = fault_state["stalls"]
        self._nans = fault_state["nans"]
        self._stragglers = fault_state["stragglers"]
        self._cache_corrupts = fault_state["cache_corrupts"]
        self._cache_colds = fault_state["cache_colds"]
        self._rf = None
        self._policy = _retry.RetryPolicy(
            max_attempts=6, base_s=0.005, cap_s=0.02, budget_s=2.0
        )

    def _chunk_arrays(self, r: int):
        """Round ``r``'s clean window arrays read THROUGH the chunk
        cache, with the seeded cache faults applied first.  The
        corruption verdict requires all three: quarantine evidence on
        disk, a transparent refetch, and bytes identical to a direct
        store read."""
        import io as _io

        name = chunk_name(r)
        if r in self._cache_corrupts:
            self._cache_corrupts.discard(r)
            # ensure the entry is published, then flip bytes in the
            # PUBLISHED chunk (size unchanged — only the CRC32 in the
            # entry manifest can catch it)
            self._cache.get(self._store, name)
            entry = self._cache.entry_path(self._store.url, name)
            corrupt_file(entry, seed=self.plan.seed)
            self.counters["cache_corrupt_injected"] = (
                self.counters.get("cache_corrupt_injected", 0) + 1
            )
            self.events.append(
                f"round {r}: cache entry for {name} byte-flipped on disk"
            )
            _obs.fault("cache_corruption", round=r, chunk=name)
            q_before = self._cache.stats["quarantined"]
            blob = self._cache.get(self._store, name)
            direct = self._store.read(name)
            if (
                self._cache.stats["quarantined"] == q_before + 1
                and blob == direct
            ):
                self.counters["cache_corrupt_survived"] = (
                    self.counters.get("cache_corrupt_survived", 0) + 1
                )
                self.events.append(
                    f"round {r}: cache quarantined the corrupt entry "
                    "(*.corrupt) and refetched byte-identical data"
                )
                _obs.instant("recovered", kind="cache_corruption", round=r)
        elif r in self._cache_colds:
            self._cache_colds.discard(r)
            dropped = self._cache.clear()
            self.counters["cache_cold_injected"] = (
                self.counters.get("cache_cold_injected", 0) + 1
            )
            self.events.append(
                f"round {r}: cache wiped cold ({dropped} entries dropped)"
            )
            _obs.fault("cache_cold", round=r, entries_dropped=dropped)
            m_before = self._cache.stats["misses"]
            blob = self._cache.get(self._store, name)
            if self._cache.stats["misses"] == m_before + 1:
                self.counters["cache_cold_survived"] = (
                    self.counters.get("cache_cold_survived", 0) + 1
                )
                self.events.append(
                    f"round {r}: cold read missed and refetched from "
                    "the backing store"
                )
                _obs.instant("recovered", kind="cache_cold", round=r)
        else:
            blob = self._cache.get(self._store, name)
        with np.load(_io.BytesIO(blob)) as z:
            return z["data"], z["label"]

    def _build(self, r: int):
        p, W, tau, B = self.plan, self.plan.workers, self.plan.tau, self.plan.batch
        n = len(self.xs)
        src = self._chunk_arrays(r) if self._cache is not None else None
        straggle = None
        if r in self._stragglers:
            # straggler_injection: the planned worker's assembly sleeps
            # — a slow host partition / degraded chip stand-in.  The
            # per-worker timing hook below attributes it; the round
            # profiler's verdict must name exactly this worker.
            self._stragglers.discard(r)
            straggle = self.plan.straggler_worker
            self.counters["straggler_injected"] = (
                self.counters.get("straggler_injected", 0) + 1
            )
            self.events.append(
                "round %d: worker %d straggles %.2fs in assembly"
                % (r, straggle, self.plan.straggler_s)
            )
            _obs.fault(
                "straggler_injection", round=r, worker=straggle,
                straggler_s=self.plan.straggler_s,
            )
        data = np.empty((W, tau) + self.xs[0].shape, np.float32)
        label = np.empty((W, tau, B), np.float32)
        worker_s = []
        for w in range(W):
            t0 = time.perf_counter()
            if straggle == w:
                time.sleep(self.plan.straggler_s)
            if src is not None:
                # chunk path: the same arrays, via the cached chunk
                # (per-worker copy keeps the timing attribution honest)
                data[w] = src[0][w]
                label[w] = src[1][w]
            else:
                for t in range(tau):
                    i = (r * W * tau + w * tau + t) % n
                    data[w, t] = self.xs[i]
                    label[w, t] = self.ys[i]
            worker_s.append(time.perf_counter() - t0)
        # per-worker assemble attribution (no-op without a profiler)
        _profile.note_worker_phase(r, "assemble", worker_s)
        if r in self._nans:
            # poison the planned workers' batches with NaN — the
            # diverging-worker fault the numerics audit must catch
            # before the parameter average (fires once per plan)
            self._nans.discard(r)
            for w in self.plan.nan_workers:
                data[w] = np.nan
            self.counters["nan_injected"] = (
                self.counters.get("nan_injected", 0) + 1
            )
            self.events.append(
                "round %d: NaN injected into worker(s) %s batch"
                % (r, list(self.plan.nan_workers))
            )
            _obs.fault(
                "nan_injection", round=r,
                workers=list(self.plan.nan_workers),
            )
        return {"data": data, "label": label}

    def _produce_round(self, r: int):
        def attempt():
            if self._faults.get(r, 0) > 0:
                self._faults[r] -= 1
                self.counters["storage_injected"] += 1
                _obs.fault("storage", round=r)
                raise ConnectionResetError(
                    f"chaos: storage fault in round {r} fetch"
                )
            if r in self._stalls:
                self._stalls.discard(r)
                self.counters["stalls_injected"] += 1
                self.events.append(f"round {r}: producer stalled {self.plan.stall_s}s")
                _obs.fault("stall", round=r, stall_s=self.plan.stall_s)
                time.sleep(self.plan.stall_s)
            return self._build(r)

        injected_before = self.counters["storage_injected"]
        out = _retry.retry_call(
            attempt,
            policy=self._policy,
            rng=random.Random(self.plan.seed * 1000 + r),
        )
        healed = self.counters["storage_injected"] - injected_before
        if healed:
            self.counters["storage_survived"] += healed
            self.events.append(
                f"round {r}: retry layer healed {healed} storage fault(s)"
            )
            # fault -> recovery is two tagged instants on the trace
            _obs.instant("recovered", kind="storage", round=r, healed=healed)
        return out

    def _spawn(self, start_r: int):
        from sparknet_tpu.data.round_feed import RoundFeed

        # RoundFeed keeps the round cursor LOCAL to each producer
        # generation (a thread that outlives stop() — a stall longer
        # than the reap timeout — keeps bumping ITS cursor, never the
        # rebuilt generation's: no round can be silently skipped) and
        # issues the dp-sharded device_put on the producer thread
        self._rf = RoundFeed(
            lambda r, out: self._produce_round(r),
            mesh=self.mesh,
            depth=2,
            stall_timeout_s=self.plan.stall_timeout_s,
            start_round=start_r,
        )

    def next_round(self, r: int):
        """The dp-PLACED (workers, tau, ...) batches for absolute round
        ``r``, surviving producer stalls by restarting the feed.  A
        stall counts as survived once the round is DELIVERED — whether
        the watchdog fired and the feed was restarted, or the stall was
        absorbed by the prefetch depth (the producer was far enough
        ahead that training never noticed)."""
        from sparknet_tpu.data.round_feed import PrefetchStall

        if self._rf is None:
            self._spawn(r)
        while True:
            try:
                out = self._rf.next_round(r)
                break
            except PrefetchStall:
                exited = self._rf.restart(r)
                self.counters["watchdog_fires"] = (
                    self.counters.get("watchdog_fires", 0) + 1
                )
                self.events.append(
                    "round %d: watchdog fired; round feed stopped "
                    "(thread exited: %s); rebuilding" % (r, exited)
                )
        if r in self.plan.stall_rounds and r not in self._stalls:
            # this round's planned stall has been consumed and the round
            # still arrived
            if (
                self.counters["stalls_survived"]
                < self.counters["stalls_injected"]
            ):
                self.counters["stalls_survived"] += 1
        return out

    def close(self):
        if self._rf is not None:
            self._rf.stop()
            self._rf = None


def run_chaos(
    plan: Optional[FaultPlan] = None,
    workdir: Optional[str] = None,
    verbose: bool = False,
) -> Dict:
    """Run the full chaos scenario; returns the CHAOS artifact dict.

    Builds one cifar10_quick ParameterAveragingTrainer on the virtual
    mesh, runs the NO-FAULT baseline first (same data, same seed), then
    the faulted run: train -> faults -> SIGHUP preemption -> snapshot ->
    simulated death -> corrupt newest snapshot -> verified resume with
    fallback -> survivor-masked rounds -> final loss vs baseline band."""
    import jax

    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.data import CifarLoader
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        first_worker,
        make_mesh,
    )
    from sparknet_tpu.solver import Solver
    from sparknet_tpu.utils.signals import SignalHandler, SolverAction

    plan = plan or FaultPlan.default()
    if jax.device_count() < plan.workers:
        raise RuntimeError(
            f"chaos needs >= {plan.workers} devices (virtual CPU mesh: "
            f"utils.devices.force_virtual_cpu_devices); have "
            f"{jax.device_count()}"
        )
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_")
    os.makedirs(workdir, exist_ok=True)

    events: List[str] = []

    def note(msg: str) -> None:
        events.append(msg)
        if verbose:
            print(f"chaos: {msg}")

    # deterministic learnable data (synthetic CIFAR-format)
    data_dir = os.path.join(workdir, "data")
    if not os.path.isdir(data_dir):
        CifarLoader.write_synthetic(
            data_dir, num_train=512, num_test=64, seed=plan.seed
        )
    xs, ys = CifarLoader(data_dir).minibatches(plan.batch, train=True)

    # the data plane under test: each round's clean window is an npz
    # chunk in a local (file://) store, read THROUGH the content-
    # addressed chunk cache every round — the path the cache_corruption
    # and cache_cold faults attack (both runs use it, so the loss
    # comparison is like-for-like)
    from sparknet_tpu.data import chunk_cache as _chunk_cache
    from sparknet_tpu.data import object_store as _object_store

    chunk_dir = os.path.join(workdir, "chunk_store")
    write_round_chunks(plan, xs, ys, chunk_dir)
    chunk_source = (
        _object_store.LocalStore("file://" + chunk_dir),
        _chunk_cache.ChunkCache(os.path.join(workdir, "chunk_cache")),
    )

    netp = cfg.replace_data_layers(
        models.load_model("cifar10_quick"),
        [(plan.batch, 3, 32, 32), (plan.batch,)],
        [(plan.batch, 3, 32, 32), (plan.batch,)],
    )
    # nan_injection exercises the numerics audit + in-graph sentry mask
    # (obs/health.py): the solver computes the audit stats tree inside
    # the jitted round, and the host sentry verifies the poisoned round
    # was flagged at EXACTLY the seeded index
    audit = plan.nan_round is not None
    solver = Solver(
        models.load_model_solver("cifar10_quick"), net_param=netp,
        audit=audit,
    )
    mesh = make_mesh(
        {"dp": plan.workers}, devices=jax.devices()[: plan.workers]
    )
    # slice_preemption runs the whole scenario on the two-tier
    # hierarchical schedule (parallel/hierarchy.py): every-round psum
    # within a slice, cross-slice average every cross_slice_every
    # rounds — both legs (baseline + faulted) use the same spec so the
    # loss comparison stays like-for-like
    from sparknet_tpu.parallel.hierarchy import HierarchySpec
    from sparknet_tpu.runtime import membership as membership_mod

    spec = None
    membership_ctl = None
    if plan.slice_preempt_round is not None:
        spec = HierarchySpec.grouped(
            plan.workers, plan.membership_slices, plan.cross_slice_every
        )
        membership_ctl = membership_mod.MembershipController(
            spec, echo=note
        )
    trainer = ParameterAveragingTrainer(solver, mesh, hierarchy=spec)
    sentry = None
    if audit:
        from sparknet_tpu.obs.health import HealthSentry

        sentry = HealthSentry(policy="warn", echo=note)

    def broadcast(st):
        return trainer.broadcast_state(st)

    def final_round_loss(losses) -> float:
        return float(np.mean(np.asarray(jax.device_get(losses))))

    # ---------------- baseline: the same run shape, zero faults
    base_plan = plan.no_fault_view()
    base_counters = {
        "storage_injected": 0, "storage_survived": 0,
        "stalls_injected": 0, "stalls_survived": 0,
    }
    feed = _Feed(
        base_plan, xs, ys, base_counters, events, mesh,
        chunk_source=chunk_source,
    )
    state = trainer.init_state(seed=plan.seed)
    losses = None
    for r in range(plan.rounds):
        # round_index keeps the two-tier schedule absolute in BOTH legs
        out = trainer.round(state, feed.next_round(r), round_index=r)
        state, losses = out[0], out[1]  # audit runs drop the stats here
    feed.close()
    baseline_loss = final_round_loss(losses)
    note(f"baseline (no faults): final-round loss {baseline_loss:.4f}")
    # the artifact's cache_stats describe the FAULTED run only — the
    # shared cache also served the baseline leg, so record the offset
    cache_stats_before = dict(chunk_source[1].stats)

    # ---------------- the faulted run
    counters = {
        "storage_injected": 0, "storage_survived": 0,
        "stalls_injected": 0, "stalls_survived": 0,
    }
    fault_state: Dict = {}
    feed = _Feed(
        plan, xs, ys, counters, events, mesh, fault_state,
        chunk_source=chunk_source,
    )
    prefix = os.path.join(workdir, "chaos_ckpt")
    state = trainer.init_state(seed=plan.seed)
    losses = None
    preempted_at: Optional[int] = None
    snapshots = 0

    def take_snapshot(r: int) -> Tuple[str, str]:
        nonlocal snapshots
        if membership_ctl is not None:
            # a departed slice's slots can hold stale params between
            # cross rounds — snapshot the first LIVE worker's consensus
            st = membership_mod.consensus_state(
                state, last_mask["m"] if last_mask["m"] is not None
                else np.ones((plan.workers,), np.float32)
            )
        else:
            st = first_worker(jax.device_get(state))
        paths = checkpoint.snapshot(solver, st, prefix, fmt="BINARYPROTO")
        snapshots += 1
        note(f"round {r}: snapshot -> {os.path.basename(paths[1])}")
        return paths

    def live_mask_for(r: int):
        if plan.dead_worker is None or r < plan.dead_from_round:
            return None
        mask = np.ones((plan.workers,), np.float32)
        mask[plan.dead_worker] = 0.0
        return mask

    last_mask: Dict = {"m": None}  # the combined mask the round used

    def run_round(fd: _Feed, r: int) -> None:
        """One training round of the faulted run (shared by the
        pre-preemption loop and the post-resume replay — fault
        accounting must stay identical in both)."""
        nonlocal state, losses
        mask = live_mask_for(r)
        if mask is not None and r == plan.dead_from_round:
            counters["dead_worker_injected"] = 1
            _obs.fault("dead_worker", round=r, worker=plan.dead_worker)
            note(
                f"round {r}: dp worker {plan.dead_worker} died; "
                "averaging over survivors"
            )
        if membership_ctl is not None:
            # the membership view advances at the round BOUNDARY: the
            # preempted slice departs here, not mid-round
            mview = membership_ctl.advance(r)
            if membership_ctl.pending_joiners():
                joiners = membership_ctl.pending_joiners()
                combined = mview.live_mask()
                if mask is not None:
                    combined = combined * mask
                state, _ = membership_mod.readmit(
                    trainer, solver, state, prefix, membership_ctl,
                    r, live_mask=combined, snapshot_fmt="BINARYPROTO",
                    echo=note,
                )
                counters.setdefault("slice_rejoin_round", r)
                _obs.instant(
                    "recovered", kind="slice_preemption", round=r,
                    workers=list(joiners),
                )
                mview = membership_ctl.view
            mmask = membership_ctl.live_mask()
            mask = mmask if mask is None else mmask * mask
            if (
                counters.get("slice_preempt_injected")
                and "slice_leave_round" not in counters
                and any(s != membership_mod.LIVE for s in mview.states)
            ):
                counters["slice_leave_round"] = r
            sw = spec.slices[plan.slice_preempt_slice]
            if all(mask[w] == 0.0 for w in sw):
                # a set: post-resume replays revisit rounds by absolute
                # index and must not double-count them
                counters.setdefault("slice_masked_rounds", set()).add(r)
        last_mask["m"] = mask
        batches = fd.next_round(r)  # placed by the pipelined feed
        out = trainer.round(state, batches, live_mask=mask, round_index=r)
        state, losses = out[0], out[1]
        if sentry is not None:
            verdict = sentry.observe(r, losses, out[2])
            if verdict.nonfinite_total > 0:
                counters.setdefault("nan_detected_round", r)
            if r == plan.nan_round and counters.get("nan_injected"):
                # survived = flagged at EXACTLY the seeded round, the
                # poisoned worker(s) masked out of the average in-graph,
                # and the surviving weights stayed finite
                exact = (
                    verdict.nonfinite_total > 0
                    and verdict.masked_workers
                    == sorted(plan.nan_workers)
                    and sentry.last_anomaly_round == plan.nan_round
                )
                if exact:
                    counters["nan_survived"] = 1
                    note(
                        f"round {r}: sentry flagged + masked poisoned "
                        f"worker(s) {verdict.masked_workers}; average "
                        "stayed healthy"
                    )
        if (
            profiler is not None
            and r == plan.straggler_round
            and counters.get("straggler_injected")
            # a post-resume REPLAY of this round has no injected sleep
            # (the fault already discharged) — the first visit's verdict
            # must not be overwritten by the healthy replay's
            and "straggler_detected_worker" not in counters
        ):
            # survived = the round profiler's verdict names EXACTLY the
            # seeded worker (per-worker attribution, not just "slow")
            rec = profiler.last()
            w = (rec or {}).get("worker")
            counters["straggler_detected_worker"] = (
                w["worst_worker"] if w else None
            )
            if (
                rec is not None
                and rec["round"] == r
                and w is not None
                and w["straggler"]
                and w["worst_worker"] == plan.straggler_worker
            ):
                counters["straggler_survived"] = 1
                note(
                    "round %d: profiler attributed the slow round to "
                    "worker %d (skew %.2f) — straggler verdict exact"
                    % (r, w["worst_worker"], w["skew"])
                )
        if outage is not None:
            outage.on_round_end(r)
        if serve_faults is not None:
            serve_faults.on_round_end(
                r, solver,
                lambda: first_worker(jax.device_get(state)),
            )
        if (
            plan.driver_kill_round is not None
            and r == plan.driver_kill_round
            and not counters.get("driver_kill_injected")
        ):
            # crash-consistency fault: a journaled driver killed
            # mid-commit, recovered bit-identically (fires once; runs
            # as a bounded sub-scenario like the serve faults)
            counters["driver_kill_summary"] = _driver_kill_scenario(
                plan, counters, note, workdir
            )
        if (
            plan.slow_slice_round is not None
            and r == plan.slow_slice_round
            and not counters.get("slow_slice_injected")
        ):
            # bounded-staleness fault: a whole slice +X s/round — the
            # sync control pays the full tail, the stale leg doesn't,
            # and the ledger still names the straggler (fires once;
            # bounded A/B sub-scenario like driver_kill)
            counters["slow_slice_summary"] = _slow_slice_scenario(
                plan, counters, note, workdir
            )
        if membership_ctl is not None:
            if (
                r == plan.slice_preempt_round
                and not counters.get("slice_preempt_injected")
            ):
                # a REAL SIGTERM: the orchestrator's preemption notice
                # for slice slice_preempt_slice — the membership
                # controller's hook marks it leaving; the process (and
                # the job) keeps running
                counters["slice_preempt_injected"] = 1
                sw = list(spec.slices[plan.slice_preempt_slice])
                _obs.fault(
                    "slice_preemption", round=r,
                    slice=plan.slice_preempt_slice, workers=sw,
                )
                note(
                    f"round {r}: SIGTERM preemption notice for slice "
                    f"{plan.slice_preempt_slice} (workers {sw})"
                )
                os.kill(os.getpid(), _signal.SIGTERM)
            if (
                counters.get("slice_preempt_injected")
                and r == plan.slice_preempt_round
                + plan.slice_relaunch_delta
                and not counters.get("slice_relaunched")
            ):
                counters["slice_relaunched"] = 1
                sw = spec.slices[plan.slice_preempt_slice]
                membership_ctl.note_join(sw)
                note(
                    f"round {r}: slice {plan.slice_preempt_slice} "
                    "relaunched — rejoin requested"
                )

    # the round profiler attributes the seeded straggler (installed for
    # the faulted run only; the baseline above ran unprofiled)
    profiler = None
    if plan.straggler_round is not None:
        profiler = _profile.install(_profile.RoundProfiler())
    # collector_outage: fleet collector + shipper live for the faulted
    # run only (the baseline ran unshipped)
    outage = None
    if plan.collector_outage_round is not None:
        outage = _CollectorOutage(plan, counters, note)
    # the serving-fleet faults (replica_death, decode_replica_kill,
    # published_snapshot_corrupt)
    serve_faults = None
    if (
        plan.replica_death_round is not None
        or plan.decode_replica_kill_round is not None
        or plan.publish_corrupt_round is not None
    ):
        serve_faults = _ServeFaults(plan, counters, note, workdir)
    t_preempt = None
    if membership_ctl is not None:
        # SIGTERM -> "slice slice_preempt_slice is being preempted"
        # (utils/signals.py hook; the handler itself is installed by
        # the SignalHandler below via sigterm_hooks=True)
        membership_ctl.sigterm_marks(plan.slice_preempt_slice)
    try:
        with SignalHandler(
            sigint_effect=SolverAction.NONE,
            sighup_effect=SolverAction.SNAPSHOT,
            sigterm_hooks=membership_ctl is not None,
        ) as handler:
            for r in range(plan.rounds):
                run_round(feed, r)
                snapped = (r + 1) % plan.snapshot_every == 0
                if snapped:
                    take_snapshot(r)
                if plan.preempt_round is not None and r == plan.preempt_round:
                    # a REAL signal, not a flag: the orchestrator's
                    # preemption notice arrives as SIGHUP
                    os.kill(os.getpid(), _signal.SIGHUP)
                    # the driver's poll sees SNAPSHOT (reference SIGHUP
                    # semantics), saves — unless the periodic snapshot
                    # already covered this exact iteration — and "dies"
                    if (
                        handler.get_action() == SolverAction.SNAPSHOT
                        and not snapped
                    ):
                        take_snapshot(r)
                    counters["preempt_injected"] = 1
                    t_preempt = time.perf_counter()
                    preempted_at = r
                    _obs.fault("preemption", round=r)
                    note(
                        f"round {r}: SIGHUP preemption — simulated "
                        "process death"
                    )
                    break
        feed.close()

        resumed_from_iter = None
        quarantined: List[str] = []
        recovery_latency_s = None
        if preempted_at is not None:
            # simulated restart: live state is GONE; only files survive
            state = None
            if plan.corrupt_newest:
                newest = checkpoint.find_snapshots(prefix)[-1]
                corrupt_file(newest, seed=plan.seed)
                counters["corruption_injected"] = 1
                _obs.fault(
                    "snapshot_corruption", snapshot=os.path.basename(newest)
                )
                note(f"corrupted newest snapshot {os.path.basename(newest)}")
            st, used = checkpoint.restore_newest_valid(solver, prefix)
            resumed_from_iter = int(np.asarray(st.iter))
            quarantined = [
                os.path.basename(p)
                for p in sorted(os.listdir(workdir))
                if p.endswith(".corrupt")
            ]
            if plan.corrupt_newest:
                if quarantined and used != newest:
                    counters["corruption_survived"] = 1
                note(
                    f"resume fell back to {os.path.basename(used)} "
                    f"(quarantined: {quarantined})"
                )
            state = broadcast(st)
            recovery_latency_s = time.perf_counter() - t_preempt
            counters["preempt_survived"] = 1
            _obs.instant(
                "recovered", kind="preemption",
                latency_s=round(recovery_latency_s, 3),
                resumed_iter=resumed_from_iter,
            )
            start_round = resumed_from_iter // plan.tau
            note(
                "resumed at round %d (iter %d) in %.2fs; replaying %d "
                "round(s)"
                % (
                    start_round,
                    resumed_from_iter,
                    recovery_latency_s,
                    preempted_at + 1 - start_round,
                )
            )
            feed = _Feed(
                plan, xs, ys, counters, events, mesh, fault_state,
                chunk_source=chunk_source,
            )
            for r in range(start_round, plan.rounds):
                run_round(feed, r)
            feed.close()
    finally:
        if membership_ctl is not None:
            membership_ctl.detach()
        if profiler is not None:
            _profile.uninstall(profiler)
        if serve_faults is not None:
            serve_faults.close()
        if outage is not None:
            try:
                outage.finalize()
            finally:
                outage.close()

    final_loss = final_round_loss(losses)
    if counters.get("dead_worker_injected") and np.isfinite(final_loss):
        counters["dead_worker_survived"] = 1
    if counters.get("slice_preempt_injected") and membership_ctl is not None:
        # survived = the departure took effect at EXACTLY the round
        # boundary after the notice, every intervening round's average
        # excluded the departed slice (renormalized over survivors),
        # the views advanced with monotonic epochs, and the rejoin
        # completed (whole roster live again)
        leave_r = counters.get("slice_leave_round")
        rejoin_r = counters.get("slice_rejoin_round")
        masked = set(counters.get("slice_masked_rounds", []))
        gone = (
            set(range(leave_r, rejoin_r))
            if leave_r is not None and rejoin_r is not None
            else None
        )
        if (
            leave_r == plan.slice_preempt_round + 1
            and gone is not None
            and gone <= masked
            and membership_ctl.epochs_monotonic()
            and all(
                s == membership_mod.LIVE
                for s in membership_ctl.view.states
            )
            and np.isfinite(final_loss)
        ):
            counters["slice_preempt_survived"] = 1
            note(
                "slice preemption survived: left at round %d, masked "
                "rounds %s, rejoined at round %d, final epoch %d"
                % (leave_r, sorted(masked), rejoin_r,
                   membership_ctl.epoch)
            )

    loss_band = max(0.25, 0.25 * abs(baseline_loss))
    loss_band_ok = bool(abs(final_loss - baseline_loss) <= loss_band)
    note(
        f"final-round loss {final_loss:.4f} vs baseline "
        f"{baseline_loss:.4f} (band +/-{loss_band:.3f}: "
        f"{'OK' if loss_band_ok else 'OUT OF BAND'})"
    )

    fault_kinds = {
        "storage": ("storage_injected", "storage_survived"),
        "stall": ("stalls_injected", "stalls_survived"),
        "preemption": ("preempt_injected", "preempt_survived"),
        "snapshot_corruption": (
            "corruption_injected", "corruption_survived",
        ),
        "dead_worker": ("dead_worker_injected", "dead_worker_survived"),
        "nan_injection": ("nan_injected", "nan_survived"),
        "straggler_injection": (
            "straggler_injected", "straggler_survived",
        ),
        "cache_corruption": (
            "cache_corrupt_injected", "cache_corrupt_survived",
        ),
        "cache_cold": ("cache_cold_injected", "cache_cold_survived"),
        "collector_outage": (
            "collector_outage_injected", "collector_outage_survived",
        ),
        "replica_death": (
            "replica_death_injected", "replica_death_survived",
        ),
        "decode_replica_kill": (
            "decode_kill_injected", "decode_kill_survived",
        ),
        "published_snapshot_corrupt": (
            "publish_corrupt_injected", "publish_corrupt_survived",
        ),
        "slice_preemption": (
            "slice_preempt_injected", "slice_preempt_survived",
        ),
        "driver_kill": (
            "driver_kill_injected", "driver_kill_survived",
        ),
        "slow_slice": (
            "slow_slice_injected", "slow_slice_survived",
        ),
    }
    faults = {
        kind: {
            "injected": int(counters.get(ik, 0)),
            "survived": int(counters.get(sk, 0)),
        }
        for kind, (ik, sk) in fault_kinds.items()
    }
    injected = sum(v["injected"] for v in faults.values())
    survived = sum(v["survived"] for v in faults.values())
    return {
        "seed": plan.seed,
        "workers": plan.workers,
        "rounds": plan.rounds,
        "tau": plan.tau,
        "batch": plan.batch,
        "faults_injected": injected,
        "faults_survived": survived,
        "faults": faults,
        "watchdog_fires": int(counters.get("watchdog_fires", 0)),
        "nan_round": plan.nan_round,
        "nan_detected_round": counters.get("nan_detected_round"),
        "straggler_round": plan.straggler_round,
        "straggler_worker": plan.straggler_worker,
        "straggler_detected_worker": counters.get(
            "straggler_detected_worker"
        ),
        "cache_corrupt_round": plan.cache_corrupt_round,
        "cache_cold_round": plan.cache_cold_round,
        "collector_outage_round": plan.collector_outage_round,
        "collector_outage": outage.summary if outage is not None else None,
        "replica_death_round": plan.replica_death_round,
        "decode_replica_kill_round": plan.decode_replica_kill_round,
        "publish_corrupt_round": plan.publish_corrupt_round,
        "driver_kill_round": plan.driver_kill_round,
        "driver_kill": counters.get("driver_kill_summary"),
        "slow_slice_round": plan.slow_slice_round,
        "slow_slice": counters.get("slow_slice_summary"),
        "slice_preempt_round": plan.slice_preempt_round,
        "slice_preempt_slice": plan.slice_preempt_slice,
        "slice_leave_round": counters.get("slice_leave_round"),
        "slice_rejoin_round": counters.get("slice_rejoin_round"),
        "slice_masked_rounds": sorted(
            counters.get("slice_masked_rounds", [])
        ),
        "membership": (
            membership_ctl.state_dict()
            if membership_ctl is not None else None
        ),
        # the faulted run's own cache traffic (baseline-leg reads on the
        # shared cache subtracted out)
        "cache_stats": {
            k: v - cache_stats_before.get(k, 0)
            for k, v in chunk_source[1].stats.items()
        },
        "recovery_latency_s": (
            round(recovery_latency_s, 3)
            if recovery_latency_s is not None
            else None
        ),
        "resumed_from_iter": resumed_from_iter,
        "quarantined": quarantined,
        "final_loss": round(final_loss, 4),
        "baseline_final_loss": round(baseline_loss, 4),
        "loss_band": round(loss_band, 4),
        "loss_band_ok": loss_band_ok,
        "final_iter": plan.rounds * plan.tau,
        "events": events,
    }
