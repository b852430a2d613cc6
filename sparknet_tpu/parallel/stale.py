"""Bounded-staleness parameter averaging: τ as a spectrum, not a gate.

The synchronous round (``ParameterAveragingTrainer``) is gated on the
slowest worker: one straggling slice taxes the whole fleet every
boundary.  This module implements the stale-synchronous-parallel relief
valve (Ho et al., SSP; FedBuff's buffered async aggregation): workers
run ahead up to a **staleness bound B** (``--stale_bound``), and the
averaging boundary takes **whoever has arrived** —

- each boundary ``b`` folds in the workers whose local τ-window has
  finished; the arrival set becomes a weight mask over the averaging
  collective, with per-worker **staleness-discounted weights**
  ``discount ** lag`` where ``lag = b - worker_rounds[w]``,
- a worker whose window is still in flight keeps ALL its local state
  (params, BN stats, momentum, iter) untouched — its contribution folds
  in at a later boundary instead of stalling this one,
- the bound is hard: a live worker at ``lag >= B`` is *forced* into the
  boundary — the harness blocks for it, which is exactly the (bounded)
  synchronous cost SSP pays to keep convergence guarantees,
- ``B = 0`` forces every live worker every round, and ``round()``
  delegates verbatim to the synchronous trainer — **bit-identical** to
  today's averaging (pinned by ``tests/test_stale.py``).

The averaging math changes with fractional weights.  The synchronous
``wmean`` is a *masked mean*: contributions enter at full value and the
denominator counts heads — correct for 0/1 masks, wrong for discounts
(a half-weight worker would be over-counted).  The stale programs use a
true weighted mean ``psum(w·θ) / psum(w)``, ``where``-guarded on both
sides so an absent worker's (possibly junk) replica can never leak
through ``0 * NaN`` into the sum.  Arrived workers adopt the mean;
absent workers keep their own replica — per-worker params now *diverge
between boundaries by design*, which is why stale jobstate snapshots
carry full per-worker replicas (``export_worker_replicas``) instead of
the consensus-plus-history layout of the sync driver.

Hierarchy goes **asymmetric** (the real-pod-elasticity leg): intra-slice
boundaries stay fast synchronous-style averaging *within each arrived
slice* every round, while the cross-slice tier is lazy and
stale-tolerant — a late or preempted slice is simply a maximally-stale
one, readmitted by the same discounted fold-in as any straggler.
Arrivals are coarsened to slices (a slice moves together, so its
members share one round clock).

Interplay contracts:

- **journal** (``io/journal.py``): the driver versions the full
  ``worker_rounds`` vector into every intent/commit record; a
  kill-anywhere resume replays ≤ B rounds bit-identically
  (``runtime/recover.py``, kill point ``stale_boundary``).
- **membership** (``runtime/membership.py``): the epoch clock orders
  roster views; a dead worker is excluded from forcing (it cannot
  arrive) and rejoins as maximally stale.
- **sentry** (``obs/health.py``): losses/audit stats of non-arrived
  workers are zeroed in-graph; ``HealthSentry.observe`` takes the
  arrival mask + ``worker_rounds`` so a lagging worker's loss is judged
  at its OWN round index and never trips a false anomaly.

Honesty note: on the virtual CPU mesh "running ahead" is *modeled* —
the harness decides arrival sets (seeded straggler schedules, sleeps
for wall-clock) and the trainer executes one fused program per
boundary in which non-arrived workers' speculative windows are
discarded in-graph.  The arrival/weight/ledger semantics, the journal
versioning, and the recovery contract are the real ones; only the
overlap of straggler compute with the boundary is simulated.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from sparknet_tpu import obs
from sparknet_tpu.parallel.hierarchy import HierarchySpec
from sparknet_tpu.parallel.trainers import (
    ParameterAveragingTrainer,
    leading_sharding,
    shard_leading,
)
from sparknet_tpu.solver import Solver, TrainState
from sparknet_tpu.utils.rngs import default_train_key

tree_map = jax.tree_util.tree_map

# division guard for the weighted-mean denominator: an all-absent
# boundary never divides (the host skips dispatch), but an
# all-masked-by-audit one reaches the program with psum(w) == 0
_DENOM_EPS = 1e-8


def stale_window(window_fn, worker_rounds) -> Dict[str, np.ndarray]:
    """Assemble the mixed-round batch for one stale boundary: worker
    ``w``'s rows come from ``window_fn(worker_rounds[w])`` — each worker
    consumes the window of its OWN next round, not the boundary's.
    ``window_fn(r)`` is the usual absolute-round feed (leaves
    ``(num_workers, tau, ...)``); the result keeps that layout.  Rounds
    are deduplicated so a mostly-synchronous fleet costs ~1 feed call."""
    rounds = [int(r) for r in np.asarray(worker_rounds).reshape(-1)]
    per_round = {r: window_fn(r) for r in sorted(set(rounds))}
    out: Dict[str, np.ndarray] = {}
    first = per_round[rounds[0]]
    for key in first:
        base = np.array(np.asarray(first[key]), copy=True)
        for w, r in enumerate(rounds):
            base[w] = np.asarray(per_round[r][key])[w]
        out[key] = base
    return out


def export_worker_replicas(host_state) -> Dict:
    """Full per-worker TrainState stacks as a jobstate fragment (the
    ``stale`` key's ``replicas`` block).  Stale averaging makes worker
    replicas diverge between boundaries *by design* — absent workers
    keep their own params — so the sync driver's consensus-plus-history
    snapshot under-determines the fleet; resume needs every slot."""
    return {
        str(i): np.asarray(l)
        for i, l in enumerate(jax.tree_util.tree_leaves(host_state))
    }


def restore_worker_replicas(state, replicas: Dict, mesh: Mesh,
                            axis: str = "dp"):
    """Inverse of ``export_worker_replicas``: put journaled per-worker
    stacks back onto a placed state of the same geometry.  Shape
    mismatches fail loudly — the jobstate belongs to a different
    trainer geometry."""
    cur, treedef = jax.tree_util.tree_flatten(state)
    leaves = [np.asarray(replicas[str(i)]) for i in range(len(cur))]
    if any(
        tuple(l.shape) != tuple(np.asarray(c).shape)
        for l, c in zip(leaves, cur)
    ):
        raise ValueError(
            "jobstate worker replicas do not match this trainer's shapes"
        )
    host = jax.tree_util.tree_unflatten(treedef, leaves)
    return shard_leading(host, mesh, axis)


class BoundedStalenessTrainer:
    """τ-step local SGD + bounded-staleness weighted averaging.

    Wraps a synchronous ``ParameterAveragingTrainer`` (the classic
    fused round — the comm plane's compressed/overlapped collectives
    assume a synchronous boundary and are rejected for ``B > 0``) and
    adds the staleness machinery:

    - ``worker_rounds`` — the host-side round ledger, one entry per
      worker: how many τ-windows that worker has folded into a
      boundary.  ``lag = boundary - worker_rounds[w]``; journaled by
      the driver every intent/commit (``export_stale_state``).
    - ``round(state, batches, arrived=...)`` — one boundary.  With
      ``stale_bound == 0`` this is a verbatim delegation to the sync
      trainer (bit-identity).  Otherwise the arrival set (host bools,
      coarsened to slices under a two-tier hierarchy, forced at
      ``lag >= B``, masked by ``live_mask``) picks the jitted stale
      program: global weighted mean on flat/cross boundaries,
      per-slice weighted mean on intra boundaries.
    - ``last_boundary`` — the boundary's host-side readout (lags,
      arrival/forced/skipped masks, weights): the telemetry source and
      what drivers journal beside ``worker_rounds``.

    ``batches`` at a stale boundary must be mixed-round (each worker's
    rows from ITS own next round — ``stale_window``); non-arrived
    workers' rows are computed speculatively and discarded in-graph, so
    their content only matters for arrived workers.
    """

    def __init__(
        self,
        solver: Solver,
        mesh: Mesh,
        axis: str = "dp",
        *,
        stale_bound: int = 0,
        discount: float = 0.5,
        average_stats: bool = True,
        mask_nonfinite: bool = True,
        compress: str = "none",
        overlap_avg: bool = False,
        hierarchy: Optional[HierarchySpec] = None,
        batch_spec=None,
    ):
        if stale_bound < 0:
            raise ValueError(f"stale_bound={stale_bound}: must be >= 0")
        if not (0.0 < discount <= 1.0):
            raise ValueError(
                f"discount={discount}: must be in (0, 1]"
            )
        if stale_bound > 0 and (compress != "none" or overlap_avg):
            # the comm plane's delta-quantized/overlapped collectives
            # carry error-feedback residuals anchored on a synchronous
            # consensus; a partial-arrival boundary breaks the anchor.
            raise ValueError(
                "stale_bound > 0 does not compose with "
                "compress/overlap_avg (the comm plane assumes "
                "synchronous boundaries); run compress='none'"
            )
        self.base = ParameterAveragingTrainer(
            solver, mesh, axis,
            average_stats=average_stats,
            mask_nonfinite=mask_nonfinite,
            compress=compress,
            overlap_avg=overlap_avg,
            hierarchy=hierarchy,
            batch_spec=batch_spec,
        )
        self.solver = solver
        self.mesh = mesh
        self.axis = axis
        self.num_workers = self.base.num_workers
        self.audit = self.base.audit
        self.hierarchy = hierarchy
        self.stale_bound = int(stale_bound)
        self.discount = float(discount)
        # the staleness ledger: worker w has folded worker_rounds[w]
        # τ-windows into some boundary; boundary counter rides beside
        # it for drivers that don't pass absolute round indices
        self.worker_rounds = np.zeros((self.num_workers,), np.int64)
        self._boundary = 0
        # last boundary's host readout (None until the first round)
        self.last_boundary: Optional[Dict] = None

        if self.stale_bound == 0:
            # pure delegation — no stale programs to build
            self._stale_round = None
            self._stale_slice_round = None
            return

        audit = self.audit
        mask_nf = self.base.mask_nonfinite
        two_tier = self.base._two_tier

        def fold(st, bt, rng, weights, stepm):
            """Shared per-worker body: speculative τ-window + in-graph
            discard for non-arrived workers.  Returns the post-select
            state pieces and this worker's (weight, stepped, bad)."""
            widx = jax.lax.axis_index(axis)
            lrng = jax.random.fold_in(rng, widx)
            stepped, out = solver._step_tau(st, bt, lrng)
            if audit:
                losses, astats = out
            else:
                losses, astats = out, None
            step = stepm[0]
            w = weights[0]
            keep = step > 0
            # a non-arrived worker's window is still in flight: the
            # speculative step is discarded wholesale — params, BN
            # stats, momentum, iter, losses, audit stats — so its
            # replica is bit-untouched until its own fold-in boundary
            sel = lambda a, b: jnp.where(keep, a, b)
            params = tree_map(sel, stepped.params, st.params)
            stats = tree_map(sel, stepped.stats, st.stats)
            history = tree_map(sel, stepped.history, st.history)
            it = jnp.where(keep, stepped.iter, st.iter)
            losses = jnp.where(keep, losses, jnp.zeros_like(losses))
            bad = None
            if audit:
                astats = tree_map(
                    lambda a: jnp.where(keep, a, jnp.zeros_like(a)),
                    astats,
                )
            if mask_nf:
                # in-graph sentry mask composes: an ARRIVED worker
                # whose own window produced non-finite grads/params
                # contributes weight 0 (its astats are zeroed above
                # when absent, so absent never reads as bad)
                bad = (
                    jnp.sum(astats["nonfinite_grads"])
                    + jnp.sum(astats["nonfinite_params"])
                ) > 0
                ok = jnp.where(bad, 0.0, 1.0)
                w = w * ok
                astats = dict(astats, masked=(1.0 - ok) * step)
            return params, stats, history, it, losses, astats, w, keep, bad

        def finish(params, stats, history, it, losses, astats,
                   keep, bad, swmean, any_arr):
            avg_params = tree_map(swmean, params)
            avg_stats = tree_map(swmean, stats) if average_stats else stats
            if mask_nf:
                # an audit-masked arrival adopts the survivor mean but
                # its momentum still holds the poisoned window — zero
                # it (the sync round's rejoin contract); absent workers
                # never match (bad is zeroed with their astats)
                rejoined = jnp.logical_and(
                    bad, jnp.logical_and(keep, any_arr)
                )
                history = tree_map(
                    lambda h: jnp.where(rejoined, jnp.zeros_like(h), h),
                    history,
                )
            st = TrainState(avg_params, avg_stats, history, it)
            if audit:
                return (
                    tree_map(lambda x: x[None], st),
                    losses[None],
                    tree_map(lambda x: x[None], astats),
                )
            return tree_map(lambda x: x[None], st), losses[None]

        def stale_body(state, batches, rng, weights, stepm):
            st = tree_map(lambda x: x[0], state)
            bt = tree_map(lambda x: x[0], batches)
            (params, stats, history, it, losses, astats,
             w, keep, bad) = fold(st, bt, rng, weights, stepm)
            # true weighted mean psum(w·θ)/psum(w): discounted weights
            # are fractional, so the head-count denominator of the sync
            # wmean would over-weight stale arrivals.  where()-guarded
            # on both sides: an absent worker's replica never enters
            # the sum, and only arrived workers adopt the mean.
            denomw0 = jax.lax.psum(w, axis)
            denomw = jnp.maximum(denomw0, _DENOM_EPS)
            any_arr = denomw0 > 0

            def swmean(x):
                contrib = jnp.where(
                    w > 0, x * w.astype(x.dtype), jnp.zeros_like(x)
                )
                m = jax.lax.psum(contrib, axis) / denomw.astype(x.dtype)
                # arrived adopt the mean (an audit-masked arrival
                # rejoins healthy, like the sync round); absent keep
                # their own replica; if NO arrival is finite everyone
                # keeps own so the host sentry sees the damage
                return jnp.where(
                    jnp.logical_and(keep, any_arr), m, x
                )

            return finish(params, stats, history, it, losses, astats,
                          keep, bad, swmean, any_arr)

        out_specs = (
            (P(axis), P(axis), P(axis)) if audit else (P(axis), P(axis))
        )
        batch_in_spec = (
            P(axis) if batch_spec is None else batch_spec
        )
        self._stale_round = obs.Program("stale_round", jax.jit(
            shard_map(
                stale_body,
                mesh=mesh,
                in_specs=(
                    P(axis), batch_in_spec, P(), P(axis), P(axis)
                ),
                out_specs=out_specs,
            ),
            donate_argnums=(0, 1),
        ), watch=(1,), devices=mesh.local_devices)

        # asymmetric hierarchy: intra-slice boundaries average the
        # arrived workers WITHIN each slice (stacked per-slice psum —
        # same lowering workaround as the sync slice program); the
        # cross tier reuses the global stale program above
        self._stale_slice_round = None
        if two_tier:
            slice_ids = jnp.asarray(hierarchy.slice_ids(), jnp.int32)
            num_slices = hierarchy.num_slices

            def stale_slice_body(state, batches, rng, weights, stepm):
                st = tree_map(lambda x: x[0], state)
                bt = tree_map(lambda x: x[0], batches)
                (params, stats, history, it, losses, astats,
                 w, keep, bad) = fold(st, bt, rng, weights, stepm)
                widx = jax.lax.axis_index(axis)
                sid = slice_ids[widx]
                onehot = (
                    jnp.arange(num_slices, dtype=jnp.int32) == sid
                ).astype(jnp.float32)
                denomw_all = jax.lax.psum(onehot * w, axis)
                denomw0 = jnp.take(denomw_all, sid)
                denomw = jnp.maximum(denomw0, _DENOM_EPS)
                any_arr = denomw0 > 0

                def sswmean(x):
                    contrib = jnp.where(
                        w > 0, x * w.astype(x.dtype), jnp.zeros_like(x)
                    )
                    stacked = (
                        onehot.reshape((num_slices,) + (1,) * x.ndim)
                        * contrib[None]
                    )
                    sums = jax.lax.psum(stacked, axis)
                    m = jnp.take(sums, sid, axis=0) / denomw.astype(
                        x.dtype
                    )
                    return jnp.where(
                        jnp.logical_and(keep, any_arr), m, x
                    )

                return finish(params, stats, history, it, losses,
                              astats, keep, bad, sswmean, any_arr)

            self._stale_slice_round = obs.Program(
                "stale_slice_round",
                jax.jit(
                    shard_map(
                        stale_slice_body,
                        mesh=mesh,
                        in_specs=(
                            P(axis), batch_in_spec, P(), P(axis), P(axis)
                        ),
                        out_specs=out_specs,
                    ),
                    donate_argnums=(0, 1),
                ),
                watch=(1,), devices=mesh.local_devices,
            )

    # ------------------------------------------------------------------
    # delegation: placement / eval / jobstate surfaces are the base's
    def init_state(self, seed: int = 0) -> TrainState:
        return self.base.init_state(seed)

    def broadcast_state(self, st: TrainState) -> TrainState:
        return self.base.broadcast_state(st)

    def test_and_store_result(self, *a, **kw):
        return self.base.test_and_store_result(*a, **kw)

    def finalize(self, state: TrainState) -> TrainState:
        return self.base.finalize(state)

    def export_comm_state(self):
        return self.base.export_comm_state()

    def restore_comm_state(self, exported) -> None:
        self.base.restore_comm_state(exported)

    def reset_comm_state(self) -> None:
        self.base.reset_comm_state()

    # ------------------------------------------------------------------
    # the staleness ledger (journaled every intent/commit)
    def export_stale_state(self) -> Dict:
        """The ledger as a jobstate/journal fragment: the bound, the
        discount, the boundary counter, and the full per-worker round
        vector — what a kill-anywhere resume replays from."""
        return {
            "stale_bound": np.asarray(self.stale_bound, np.int64),
            "discount": np.asarray(self.discount, np.float64),
            "boundary": np.asarray(self._boundary, np.int64),
            "worker_rounds": np.asarray(self.worker_rounds, np.int64),
        }

    def reset_stale_state(self) -> None:
        """Zero the ledger (fresh-run entry for a reused trainer: the
        in-process chaos/recover harnesses run control/crash/resume
        legs off one compiled context)."""
        self.worker_rounds[:] = 0
        self._boundary = 0
        self.last_boundary = None

    def load_stale_state(self, frag: Dict) -> None:
        wr = np.asarray(frag["worker_rounds"], np.int64).reshape(-1)
        if wr.shape[0] != self.num_workers:
            raise ValueError(
                f"stale jobstate covers {wr.shape[0]} workers, mesh "
                f"has {self.num_workers}"
            )
        self.worker_rounds = wr.copy()
        self._boundary = int(np.asarray(frag["boundary"]))

    def lags(self, boundary: Optional[int] = None) -> np.ndarray:
        """Per-worker staleness at ``boundary`` (default: the next
        one): ``boundary - worker_rounds``, floored at 0."""
        b = self._boundary if boundary is None else int(boundary)
        return np.maximum(b - self.worker_rounds, 0)

    # ------------------------------------------------------------------
    def _arrival_sets(self, b: int, arrived, live: np.ndarray):
        """Resolve one boundary's arrival semantics on the host:
        returns ``(eff, forced, lag)`` — the effective arrival mask
        (bools), which of those were forced by the bound, and the
        per-worker lag.  Dead workers never arrive and never force (a
        preempted slice just goes maximally stale); under a two-tier
        hierarchy arrivals coarsen to whole slices."""
        lag = np.maximum(b - self.worker_rounds, 0)
        if arrived is None:
            arr = live > 0
        else:
            arr = np.asarray(arrived, bool).reshape(-1)
            if arr.shape[0] != self.num_workers:
                raise ValueError(
                    f"arrived has {arr.shape[0]} entries, mesh has "
                    f"{self.num_workers} workers"
                )
            arr = arr & (live > 0)
        # the hard bound: a LIVE worker at lag >= B is forced into the
        # boundary (the harness blocks for it — SSP's bounded sync
        # cost).  Dead workers are exempt: they cannot arrive at all.
        forced = (lag >= self.stale_bound) & (live > 0) & ~arr
        eff = arr | forced
        if self.base._two_tier:
            # slices move together: a slice arrives iff every live
            # member did (dead members don't hold it back), so members
            # share one round clock
            eff2 = eff.copy()
            for members in self.hierarchy.slices:
                m = np.asarray(members, np.int64)
                lv = live[m] > 0
                ok = bool(np.all(eff[m] | ~lv)) and bool(np.any(lv))
                eff2[m] = ok & lv
            forced = forced & eff2
            eff = eff2
        return eff, forced, lag

    def round(
        self,
        state: TrainState,
        batches: Dict[str, jax.Array],
        rng=None,
        arrived=None,
        live_mask=None,
        round_index: Optional[int] = None,
    ):
        """One averaging boundary.

        ``arrived`` (num_workers,) bools: whose τ-window has finished
        by this boundary (None = everyone live — the synchronous
        degenerate case).  The trainer forces live workers at
        ``lag >= stale_bound`` into the set and coarsens to slices
        under a two-tier hierarchy; the resolved masks land in
        ``self.last_boundary``.

        With ``stale_bound == 0`` this delegates verbatim to the
        synchronous ``ParameterAveragingTrainer.round`` (bit-identity
        pinned by the degenerate-path regression test).  A boundary
        with NO arrivals (possible only for ``B > 0``) skips dispatch
        entirely: returns the state untouched with zero losses (and
        ``None`` audit stats) — drivers consult ``last_boundary`` and
        skip the sentry for skipped boundaries."""
        b = self._boundary if round_index is None else int(round_index)
        if live_mask is None:
            live = np.ones((self.num_workers,), np.float32)
        else:
            live = np.asarray(live_mask, np.float32).reshape(-1)
        if self.stale_bound == 0:
            out = self.base.round(
                state, batches, rng=rng, live_mask=live_mask,
                round_index=round_index,
            )
            self._boundary = b + 1
            # ledger stays coherent for telemetry/journal symmetry:
            # every live worker folded its window this boundary
            self.worker_rounds[live > 0] += 1
            self.last_boundary = {
                "boundary": b,
                "lag": [0] * self.num_workers,
                "arrived": [bool(v > 0) for v in live],
                "forced": [False] * self.num_workers,
                "weights": [float(v > 0) for v in live],
                "skipped": False,
                "tier": "sync",
            }
            self._emit_metrics()
            return out

        eff, forced, lag = self._arrival_sets(b, arrived, live)
        weights = np.where(
            eff, np.power(self.discount, lag.astype(np.float64)), 0.0
        ).astype(np.float32)
        intra = (
            self.base._two_tier
            and not self.hierarchy.is_cross_round(b)
        )
        tier = "intra" if intra else "cross"
        self.last_boundary = {
            "boundary": b,
            "lag": [int(v) for v in lag],
            "arrived": [bool(v) for v in eff],
            "forced": [bool(v) for v in forced],
            "weights": [float(v) for v in weights],
            "skipped": not bool(eff.any()),
            "tier": tier,
        }
        self._boundary = b + 1
        if not eff.any():
            # nobody reached this boundary (all in flight, none at the
            # bound): the boundary itself is skipped — no program, no
            # state change, no ledger advance
            self._emit_metrics()
            tau = int(
                next(iter(jax.tree_util.tree_leaves(batches))).shape[1]
            )
            losses = np.zeros((self.num_workers, tau), np.float32)
            if self.audit:
                return state, losses, None
            return state, losses
        self.worker_rounds[eff] += 1

        rng = rng if rng is not None else default_train_key(0)
        sharding = leading_sharding(self.mesh, self.axis)
        w_dev = jax.device_put(weights, sharding)
        step_dev = jax.device_put(
            eff.astype(np.float32), sharding
        )
        astats = None
        with obs.span("average"):
            prog = (
                self._stale_slice_round if intra else self._stale_round
            )
            with obs.span("execute"):
                if self.audit:
                    state, losses, astats = prog(
                        state, batches, rng, w_dev, step_dev
                    )
                else:
                    state, losses = prog(
                        state, batches, rng, w_dev, step_dev
                    )
            self.solver.note_losses(losses)
        tm = obs.training_metrics()
        if tm is not None:
            tm.rounds.inc()
            tm.iters.inc(losses.shape[-1])
            if self.hierarchy is not None:
                tm.hierarchy_rounds.labels(tier).inc()
                tm.hierarchy_bytes.labels(tier).inc(
                    self.base._payload_bytes(state)
                )
        self._emit_metrics()
        obs.report_healthy()
        if self.audit:
            return state, losses, astats
        return state, losses

    def _emit_metrics(self) -> None:
        """Publish the boundary readout on the shared registry:
        per-worker staleness gauge, arrival/skip counters, forced-wait
        counter (the bound's synchronous cost, the quantity the stale
        bench wants ≈ 0 for a straggler within the bound)."""
        tm = obs.training_metrics()
        lb = self.last_boundary
        if tm is None or lb is None:
            return
        for w in range(self.num_workers):
            tm.staleness.labels(str(w)).set(float(lb["lag"][w]))
            if lb["arrived"][w]:
                tm.stale_arrivals.labels(str(w)).inc()
            else:
                tm.stale_skipped.labels(str(w)).inc()
        nforced = sum(1 for v in lb["forced"] if v)
        if nforced:
            tm.stale_forced_waits.inc(nforced)
        if lb["skipped"]:
            tm.stale_boundaries_skipped.inc()
