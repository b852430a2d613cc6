"""Training-health sentry: in-graph numerics audit + divergence policy.

The parameter-averaging loop has a failure mode the round-span/metrics
layer can see but not diagnose: one worker's diverging local SGD
(NaN/Inf grads, loss spike) silently poisons the ``psum`` average for
every worker, and the only record afterward is a flat loss curve.  This
module closes the loop — detect, record, recover:

- **audit** (pure jnp, fused into the jitted step): per-iteration global
  grad L2 norm (the same reduction ``clip_gradients`` already pays —
  computed once, shared), per-param-group param norm and update/param
  ratio, and non-finite counts over grads/params/loss.  Enabled by
  ``Solver(audit=True)``; the stats are pure READOUTS — the training
  trajectory is bit-identical with the audit on or off
  (``tests/test_health.py``).
- **in-graph worker masking** (``ParameterAveragingTrainer``): a dp
  worker whose local window produced any non-finite grad/param is
  excluded from that round's average *inside the jitted round* — the
  poison never reaches the ``psum`` — and the masked slot is overwritten
  with the survivor mean (it rejoins healthy next round).  Composes with
  the fault-tolerance ``live_mask``.
- **HealthSentry** (host side): consumes the stats tree each round,
  keeps a loss EMA and flags spikes by z-score, feeds the shared metrics
  registry (``sparknet_grad_norm``, ``sparknet_nonfinite_total``,
  ``sparknet_update_ratio{group}``) and the JSONL run log, records every
  verdict into the flight recorder, and acts per policy:

  ``warn``      log + metrics only; training continues.
  ``halt``      dump a flight bundle, flip /healthz to 503, raise
                ``SentryHalt`` (the driver exits WITHOUT snapshotting
                the poisoned weights).
  ``rollback``  restore the newest verified snapshot
                (``io/checkpoint.restore_newest_valid``) and continue
                with the NEXT round's data — the poisoned window is
                skipped and ``state.iter`` rewinds, so the LR schedule
                replays from the restore point (the LR-backoff /
                skip-window semantics); after ``max_rollbacks`` the
                sentry escalates to halt.

Cost: the audit itself is a handful of fused reductions inside the
existing program, bit-neutral to the trajectory
(``tests/test_health.py::test_audit_bit_identity_and_in_graph_mask``);
the sentry adds one small per-round device_get of scalar stats — a host
sync in the round loop — so ``--health`` is opt-in.
"""

from __future__ import annotations

import math
import os
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

POLICIES = ("warn", "halt", "rollback")


class SentryHalt(RuntimeError):
    """The divergence sentry halted the run (policy ``halt``, or
    ``rollback`` with no restore point / rollback budget exhausted)."""

    def __init__(self, round_index: int, reason: str):
        super().__init__(f"sentry halt at round {round_index}: {reason}")
        self.round_index = round_index
        self.reason = reason


# ----------------------------------------------------------------------
# in-graph audit (pure jnp — traced into the jitted step bodies)


def nonfinite_count(tree):
    """int32 count of non-finite values across a pytree (0 if empty)."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.asarray(0, jnp.int32)
    total = None
    for l in leaves:
        c = jnp.sum(~jnp.isfinite(l)).astype(jnp.int32)
        total = c if total is None else total + c
    return total


def audit_iteration(grads, params, new_params, loss, grad_norm):
    """Per-iteration stats tree, computed INSIDE the jitted step (pure
    readouts of values the update already produced — nothing feeds back
    into the training math).  ``grad_norm`` is the raw pre-clip global
    L2 the solver already computes for ``clip_gradients``.

    Division discipline: the update/param ratio is 0 (not NaN) when a
    group's param norm is zero — all-zero grads / freshly-zeroed blobs
    never poison the audit itself."""
    import jax.numpy as jnp

    stats = {
        "grad_norm": jnp.asarray(grad_norm, jnp.float32),
        "nonfinite_grads": nonfinite_count(grads),
        "nonfinite_params": nonfinite_count(new_params),
        "nonfinite_loss": jnp.sum(~jnp.isfinite(loss)).astype(jnp.int32),
        "param_norm": {},
        "update_ratio": {},
    }
    for key, blobs in new_params.items():
        psq = None
        usq = None
        for w_new, w_old in zip(blobs, params[key]):
            wn = w_new.astype(jnp.float32)
            dw = wn - w_old.astype(jnp.float32)
            p = jnp.sum(jnp.square(wn))
            u = jnp.sum(jnp.square(dw))
            psq = p if psq is None else psq + p
            usq = u if usq is None else usq + u
        pnorm = jnp.sqrt(psq)
        unorm = jnp.sqrt(usq)
        stats["param_norm"][key] = pnorm
        stats["update_ratio"][key] = jnp.where(
            pnorm > 0.0, unorm / jnp.maximum(pnorm, 1e-12), 0.0
        )
    return stats


# ----------------------------------------------------------------------
# host side: verdicts + the sentry


class HealthVerdict:
    """One round's health readout (host floats, JSON-safe via
    ``as_dict``)."""

    def __init__(
        self,
        round_index: int,
        loss: float,
        zscore: float,
        grad_norm: float,
        nonfinite_grads: int,
        nonfinite_params: int,
        nonfinite_loss: int,
        per_worker_nonfinite: Optional[List[int]],
        masked_workers: List[int],
        reasons: List[str],
    ):
        self.round_index = round_index
        self.loss = loss
        self.zscore = zscore
        self.grad_norm = grad_norm
        self.nonfinite_grads = nonfinite_grads
        self.nonfinite_params = nonfinite_params
        self.nonfinite_loss = nonfinite_loss
        self.per_worker_nonfinite = per_worker_nonfinite
        self.masked_workers = masked_workers
        self.reasons = list(reasons)
        self.action = "none"  # filled by the sentry: none|warn|masked|
        #                       rollback|halt

    @property
    def nonfinite_total(self) -> int:
        return (
            self.nonfinite_grads + self.nonfinite_params + self.nonfinite_loss
        )

    @property
    def ok(self) -> bool:
        return not self.reasons

    def as_dict(self) -> Dict:
        return {
            "round": self.round_index,
            "loss": self.loss,
            "zscore": round(self.zscore, 3),
            "grad_norm": self.grad_norm,
            "nonfinite": self.nonfinite_total,
            "nonfinite_grads": self.nonfinite_grads,
            "nonfinite_params": self.nonfinite_params,
            "nonfinite_loss": self.nonfinite_loss,
            "per_worker_nonfinite": self.per_worker_nonfinite,
            "masked_workers": self.masked_workers,
            "ok": self.ok,
            "reasons": self.reasons,
            "action": self.action,
        }


class HealthSentry:
    """Consumes round audit stats, classifies, and acts per policy.

    Loop glue: ``guarded_round(trainer, state, batches)`` for the
    parameter-averaging trainer and ``guarded_step(stepper, state,
    batches)`` for ``Solver``/``AllReduceTrainer`` both return the plain
    ``(state, losses)`` the unguarded loops already unpack — the stats
    tree is consumed here.  ``observe(r, losses, stats)`` is the lower-
    level entry for loops that drive the trainer themselves (chaos)."""

    def __init__(
        self,
        policy: str = "warn",
        *,
        z_threshold: float = 6.0,
        ema_beta: float = 0.9,
        warmup_rounds: int = 3,
        cooldown_rounds: int = 3,
        max_rollbacks: int = 3,
        restore_fn: Optional[Callable[[], Tuple[object, str]]] = None,
        echo=None,
    ):
        if policy not in POLICIES:
            raise ValueError(
                f"health policy {policy!r} not in {'|'.join(POLICIES)}"
            )
        self.policy = policy
        self.z_threshold = float(z_threshold)
        self.ema_beta = float(ema_beta)
        self.warmup_rounds = int(warmup_rounds)
        self.cooldown_rounds = int(cooldown_rounds)
        self.max_rollbacks = int(max_rollbacks)
        # restore_fn() -> (ready-to-train state, snapshot path) — see
        # make_restore_fn; None means ``rollback`` degrades to halt
        self.restore_fn = restore_fn
        self._echo = echo
        # EMA of the round-mean loss + EMA variance (spike z-score)
        self._ema: Optional[float] = None
        self._emvar = 0.0
        self._seen = 0
        self._cooldown = 0
        # per-round EMA snapshots (bounded ring): the z-score lens AT a
        # past round, so a bounded-staleness arrival is judged at its
        # OWN round index — a lag-L worker's (legitimately higher) loss
        # compared against round b's EMA would read as a spike
        self._ema_ring: "OrderedDict[int, Tuple[Optional[float], float, int]]" = (
            OrderedDict()
        )
        # exported state (the /healthz surface)
        self.last_anomaly_round: Optional[int] = None
        # last round INDEX observed — resumed runs pass absolute
        # indices, so rounds_since_anomaly must not lean on the count
        self.last_round: Optional[int] = None
        self.rounds_observed = 0
        self.anomalies = 0
        self.rollbacks = 0
        self.halted = False
        self.halt_reason: Optional[str] = None
        self.verdicts: List[HealthVerdict] = []

    # ------------------------------------------------------------------
    def _say(self, msg: str) -> None:
        if self._echo is not None:
            self._echo("health: " + msg)

    def state_dict(self) -> Dict:
        """The /healthz sentry block — orchestrators read this to tell
        "training stalled" from "training diverged"."""
        last = self.last_anomaly_round
        return {
            "policy": self.policy,
            "last_anomaly_round": last,
            "rounds_since_anomaly": (
                None
                if last is None or self.last_round is None
                else max(0, self.last_round - last)
            ),
            "anomalies": self.anomalies,
            "rollbacks": self.rollbacks,
            "halted": self.halted,
            "halt_reason": self.halt_reason,
        }

    # ------------------------------------------------------------------
    # full job state (crash consistency): the sentry's carried scalars.
    # A resume that drops these silently restarts the loss-EMA warmup
    # and forgets an active cooldown — the z-score lens changes, so a
    # spike right after restart reads differently than it would have in
    # the uninterrupted run.  Journaled beside params by the recover
    # loop (io/checkpoint extra_state; runtime/recover.py).
    def export_state(self) -> Dict:
        return {
            "ema": self._ema,
            "emvar": self._emvar,
            "seen": self._seen,
            "cooldown": self._cooldown,
            # the per-round lens ring (bounded-staleness judging): a
            # resume that drops it would re-judge a replayed stale
            # arrival with an empty lens
            "ema_ring": {
                str(r): [v[0], v[1], v[2]]
                for r, v in self._ema_ring.items()
            },
            "last_anomaly_round": self.last_anomaly_round,
            "last_round": self.last_round,
            "rounds_observed": self.rounds_observed,
            "anomalies": self.anomalies,
            "rollbacks": self.rollbacks,
        }

    def load_state(self, d: Dict) -> None:
        self._ema = None if d.get("ema") is None else float(d["ema"])
        self._emvar = float(d.get("emvar", 0.0))
        self._seen = int(d.get("seen", 0))
        self._cooldown = int(d.get("cooldown", 0))
        self._ema_ring = OrderedDict(
            (
                int(r),
                (
                    None if v[0] is None else float(v[0]),
                    float(v[1]),
                    int(v[2]),
                ),
            )
            for r, v in sorted(
                (d.get("ema_ring") or {}).items(),
                key=lambda kv: int(kv[0]),
            )
        )
        lar = d.get("last_anomaly_round")
        self.last_anomaly_round = None if lar is None else int(lar)
        lr = d.get("last_round")
        self.last_round = None if lr is None else int(lr)
        self.rounds_observed = int(d.get("rounds_observed", 0))
        self.anomalies = int(d.get("anomalies", 0))
        self.rollbacks = int(d.get("rollbacks", 0))

    # ------------------------------------------------------------------
    # z-score machinery (host floats only)
    def _spike(self, z: float) -> bool:
        """Strictly ABOVE the threshold flags — a loss sitting exactly
        at the threshold does not (tested boundary)."""
        return z > self.z_threshold

    def _zscore(self, loss: float) -> float:
        return self._zscore_at(loss, (self._ema, self._emvar, self._seen))

    def _zscore_at(self, loss: float, lens) -> float:
        """z-score against an explicit (ema, emvar, seen) lens — the
        current one, or a past round's snapshot from ``_ema_ring``
        (bounded-staleness arrivals are judged at their OWN round)."""
        if lens is None:
            return 0.0
        ema, emvar, seen = lens
        if ema is None or seen < self.warmup_rounds:
            return 0.0
        # variance floor at 5% of the loss scale: with a near-constant
        # loss the EMA variance collapses and raw z would flag noise
        sigma = math.sqrt(max(0.0, emvar))
        denom = max(sigma, 0.05 * abs(ema) + 1e-8)
        return (loss - ema) / denom

    def _update_ema(self, loss: float) -> None:
        if not math.isfinite(loss):
            return  # never seed the EMA with poison
        if self._ema is None:
            self._ema = loss
            self._emvar = 0.0
        else:
            d = loss - self._ema
            self._ema += (1.0 - self.ema_beta) * d
            self._emvar = self.ema_beta * (
                self._emvar + (1.0 - self.ema_beta) * d * d
            )
        self._seen += 1

    def _reset_ema(self) -> None:
        self._ema = None
        self._emvar = 0.0
        self._seen = 0

    # ------------------------------------------------------------------
    def observe(
        self, round_index: int, losses, stats, *,
        arrived=None, worker_rounds=None,
    ) -> HealthVerdict:
        """Classify one round from its losses + audit stats tree.  The
        (small, scalar-only) stats fetch is the audit's one deliberate
        device->host sync per round.

        Bounded-staleness boundaries (``parallel/stale.py``) pass
        ``arrived`` (num_workers, bools: whose window folded in — the
        others' losses/stats are zeroed in-graph and must not drag the
        EMA) and ``worker_rounds`` (num_workers, ints: the absolute
        round each worker's folded window BELONGS to).  Each stale
        arrival is then judged against the EMA lens at its OWN round
        (the ``_ema_ring`` snapshot), not the boundary's — a lag-L
        worker's legitimately higher loss never trips a false
        spike, while a genuinely divergent one still does."""
        import jax

        from sparknet_tpu import obs as _obs
        from sparknet_tpu.obs import flight as _flight

        def _get_local(x):
            # multi-host: trainer stats/losses are dp-sharded across
            # processes and a plain device_get on a spanning jax.Array
            # raises.  Each process's sentry judges its ADDRESSABLE
            # workers — the same local-view rule Solver._drain_losses
            # uses for the loss window.
            if getattr(x, "is_fully_addressable", True):
                return np.asarray(jax.device_get(x))
            shards = [np.asarray(s.data) for s in x.addressable_shards]
            return np.concatenate(shards, axis=0)

        host = jax.tree_util.tree_map(_get_local, stats)
        loss_arr = np.asarray(_get_local(losses), np.float64)
        # arrival-aware loss view: the round-mean (and the EMA it
        # feeds) covers CURRENT-round arrivals; stale arrivals are
        # judged separately at their own round's lens below
        arr_mask = None
        wr = None
        stale_z = 0.0
        if (
            arrived is not None
            and loss_arr.ndim >= 2
            and np.asarray(arrived).reshape(-1).shape[0]
            == loss_arr.shape[0]
        ):
            arr_mask = np.asarray(arrived, bool).reshape(-1)
            if worker_rounds is not None:
                wr = np.asarray(worker_rounds, np.int64).reshape(-1)
            fresh = (
                arr_mask
                if wr is None
                else arr_mask & (wr >= round_index)
            )
            base = fresh if fresh.any() else arr_mask
            sel = loss_arr[base] if base.any() else loss_arr[arr_mask]
            loss = float(np.mean(sel)) if sel.size else float("nan")
            if wr is not None:
                for w in np.nonzero(arr_mask & (wr < round_index))[0]:
                    lens = self._ema_ring.get(int(wr[w]))
                    zw = self._zscore_at(
                        float(np.mean(loss_arr[w])), lens
                    )
                    stale_z = max(stale_z, zw)
        else:
            loss = (
                float(np.mean(loss_arr))
                if loss_arr.size
                else float("nan")
            )

        def total(name) -> int:
            return int(np.sum(np.asarray(host.get(name, 0))))

        nf_grads = total("nonfinite_grads")
        nf_params = total("nonfinite_params")
        # the audited step already counts the window's losses in-graph;
        # the host re-count covers stats trees that lack the series
        # (stubs, partial audits).  max(), not +: they see the SAME
        # losses, summing would double-report every poisoned round.
        nf_loss = max(
            total("nonfinite_loss"), int(np.sum(~np.isfinite(loss_arr)))
        )
        # per-worker attribution: trainer stats carry a leading workers
        # axis; single-process stats are (tau,) scalars per iter
        per_worker = None
        nf_w = np.asarray(host.get("nonfinite_grads", 0)) + np.asarray(
            host.get("nonfinite_params", 0)
        )
        if nf_w.ndim == 2:
            per_worker = [int(v) for v in nf_w.sum(axis=1)]
        masked = []
        if "masked" in host:
            m = np.asarray(host["masked"]).reshape(-1)
            masked = [int(w) for w in np.nonzero(m > 0)[0]]

        z = self._zscore(loss)
        reasons = []
        if nf_grads or nf_params or nf_loss:
            reasons.append("nonfinite")
        if self._cooldown > 0:
            self._cooldown -= 1
        elif self._spike(z) or self._spike(stale_z):
            # z: current-round arrivals vs the live EMA; stale_z: each
            # stale arrival vs the lens AT its own round — both real
            # divergence signals, neither a staleness artifact
            reasons.append("loss_spike")
        v = HealthVerdict(
            round_index, loss, z, self._last_scalar(host, "grad_norm"),
            nf_grads, nf_params, nf_loss, per_worker, masked, reasons,
        )
        # snapshot the pre-update lens for this round, then fold the
        # loss in: a future lag-L arrival whose window was round r is
        # judged against what the EMA was AT round r
        self._ema_ring[int(round_index)] = (
            self._ema, self._emvar, self._seen
        )
        while len(self._ema_ring) > 128:
            self._ema_ring.popitem(last=False)
        self._update_ema(loss)
        self.last_round = round_index
        self.rounds_observed += 1
        self.verdicts.append(v)
        if len(self.verdicts) > 4096:
            del self.verdicts[:2048]

        # metrics: the issue-named series on the shared registry
        tm = _obs.training_metrics()
        if tm is not None:
            tm.grad_norm.set(v.grad_norm)
            if v.nonfinite_total:
                tm.nonfinite.inc(v.nonfinite_total)
            ratios = host.get("update_ratio") or {}
            for group in ratios:
                tm.update_ratio.labels(group).set(
                    self._last_scalar(ratios, group)
                )
        # run log + flight ring: one health instant per round, so the
        # postmortem table is round-by-round even for healthy rounds
        _obs.instant("health", cat="health", **v.as_dict())
        _flight.record_verdict(v.as_dict())
        _flight.record_sample("loss", loss, round=round_index)
        _flight.record_sample("grad_norm", v.grad_norm, round=round_index)
        if not v.ok:
            self.anomalies += 1
            self.last_anomaly_round = round_index
            if tm is not None:
                for kind in v.reasons:
                    tm.health_anomalies.labels(kind).inc()
            _obs.instant(
                "health_anomaly", cat="health",
                round=round_index, reasons=v.reasons,
            )
            self._say(
                "round %d ANOMALY (%s): loss %.4g z %.2f nonfinite %d "
                "masked %s"
                % (
                    round_index, ",".join(v.reasons), loss, z,
                    v.nonfinite_total, masked,
                )
            )
        return v

    @staticmethod
    def _last_scalar(host: Dict, name: str) -> float:
        arr = np.asarray(host.get(name, np.nan), np.float64).reshape(-1)
        return float(arr[-1]) if arr.size else float("nan")

    # ------------------------------------------------------------------
    def _act(self, v: HealthVerdict, state):
        """Apply the policy to an anomalous verdict; returns the state
        to continue with (possibly restored)."""
        from sparknet_tpu import obs as _obs
        from sparknet_tpu.obs import flight as _flight

        absorbed = (
            v.masked_workers
            and v.per_worker_nonfinite is not None
            and len(v.masked_workers) < len(v.per_worker_nonfinite)
            and "loss_spike" not in v.reasons
        )
        if absorbed:
            # the in-graph mask already excluded the poisoned worker(s)
            # from the average; the weights are healthy — no escalation
            v.action = "masked"
            _flight.record_verdict(v.as_dict())  # refresh: action set
            self._say(
                "round %d: poisoned worker(s) %s masked out of the "
                "average; training continues"
                % (v.round_index, v.masked_workers)
            )
            return state
        if self.policy == "warn":
            v.action = "warn"
            _flight.record_verdict(v.as_dict())
            return state
        if self.policy == "rollback":
            if self.restore_fn is not None and (
                self.rollbacks < self.max_rollbacks
            ):
                try:
                    state, used = self.restore_fn()
                except (FileNotFoundError, RuntimeError) as e:
                    # no snapshot at all, or every candidate corrupt
                    # (SnapshotCorrupt) — nothing valid to roll back to
                    self._halt(v, f"rollback restore failed ({e})")
                self.rollbacks += 1
                self._cooldown = self.cooldown_rounds
                self._reset_ema()
                tm = _obs.training_metrics()
                if tm is not None:
                    tm.health_rollbacks.inc()
                v.action = "rollback"
                _flight.record_verdict(v.as_dict())  # refresh: action set
                _obs.instant(
                    "health_rollback", cat="health",
                    round=v.round_index, snapshot=os.path.basename(str(used)),
                )
                _flight.dump_if_active(
                    "sentry_rollback", extra={"round": v.round_index}
                )
                self._say(
                    "round %d: rolled back to %s; skipping the poisoned "
                    "window (LR schedule replays from the restore point)"
                    % (v.round_index, os.path.basename(str(used)))
                )
                return state
            why = (
                "rollback budget exhausted (%d)" % self.max_rollbacks
                if self.restore_fn is not None
                else "no restore point wired for rollback"
            )
            self._halt(v, why)
        self._halt(v, "policy=halt")

    def _halt(self, v: HealthVerdict, why: str):
        from sparknet_tpu import obs as _obs
        from sparknet_tpu.obs import flight as _flight

        v.action = "halt"
        _flight.record_verdict(v.as_dict())  # refresh BEFORE the dump
        self.halted = True
        self.halt_reason = f"{','.join(v.reasons)} at round {v.round_index}"
        _obs.report_unhealthy("sentry_halt: " + self.halt_reason)
        _flight.dump_if_active(
            "sentry_halt",
            extra={"round": v.round_index, "why": why},
        )
        self._say(f"HALT at round {v.round_index}: {why}")
        raise SentryHalt(v.round_index, why)

    # ------------------------------------------------------------------
    # loop glue — drop-in guards returning the plain (state, losses)
    def guarded_round(
        self, trainer, state, batches, *, rng=None, live_mask=None,
        round_index: Optional[int] = None,
    ):
        """One ``ParameterAveragingTrainer.round`` under the sentry."""
        r = self.rounds_observed if round_index is None else round_index
        state, losses, stats = trainer.round(
            state, batches, rng=rng, live_mask=live_mask,
            round_index=round_index,
        )
        v = self.observe(r, losses, stats)
        if not v.ok:
            state = self._act(v, state)
        return state, losses

    def guarded_step(
        self, stepper, state, batches, *, rng=None,
        round_index: Optional[int] = None,
    ):
        """One ``Solver.step`` / ``AllReduceTrainer.step`` window under
        the sentry."""
        r = self.rounds_observed if round_index is None else round_index
        state, losses, stats = stepper.step(state, batches, rng=rng)
        v = self.observe(r, losses, stats)
        if not v.ok:
            state = self._act(v, state)
        return state, losses


# ----------------------------------------------------------------------
# wiring helpers (the --health/--health_policy CLI surface)


def sentry_from_args(args, solver, restore_fn=None, echo=None):
    """Build (or skip) the sentry from parsed CLI args and flip the
    solver's audit on.  MUST run before a ``ParameterAveragingTrainer``
    is constructed from ``solver`` — the trainer bakes the audit arity
    into its shard_map output spec."""
    policy = getattr(args, "health_policy", None) or getattr(
        args, "health", None
    )
    if policy is None:
        return None
    from sparknet_tpu import obs as _obs

    solver.audit = True
    _obs.enable_training_metrics()
    sentry = HealthSentry(policy=policy, restore_fn=restore_fn, echo=echo)
    _obs.set_sentry(sentry)
    return sentry


def make_restore_fn(solver, prefix: str, trainer=None):
    """A ``restore_fn`` for rollback: newest VERIFIED snapshot under
    ``prefix`` (corrupt ones quarantined — ``restore_newest_valid``),
    re-placed for the caller's trainer (parameter-averaging broadcast /
    allreduce shard) or used directly for a single-process solver."""
    from sparknet_tpu.io import checkpoint

    def restore():
        st, used = checkpoint.restore_newest_valid(solver, prefix)
        if trainer is not None and hasattr(trainer, "broadcast_state"):
            st = trainer.broadcast_state(st)
        elif trainer is not None and hasattr(trainer, "shard_state"):
            st = trainer.shard_state(st)
        return st, used

    return restore
