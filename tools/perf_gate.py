"""Perf-regression gate over the committed benchmark artifacts.

The repo's perf story is a trajectory of committed one-line JSON
artifacts (PIPELINE_*, OBS_*, COMM_*, PROFILE_*, ...).  Each carries pinned bands in its schema tests, but nothing
checked them *as a set*, and nothing compared a live run against them.
This gate does both:

``--check``
    Validate the NEWEST artifact of every family in the repo root
    against its pinned-band rules (the same done-bars the bench modes
    print), plus the cross-artifact rules (e.g. the live
    hidden-fraction in PROFILE_* must sit within band of PIPELINE_*'s
    offline overlap efficiency).  Exit 1 on any out-of-band value —
    the tier-1 guard that makes a PR which regresses a pinned band
    fail fast.

``--live RUN.json``
    Fold a live profile (a ``RoundProfiler.summary()`` dump, or a
    PROFILE_* artifact) against the committed baselines: hidden
    fraction within band, round time within tolerance of the committed
    profile leg.  Exit 1 when the live run regressed out of band.

    python tools/perf_gate.py --check
    python tools/perf_gate.py --check --json
    python tools/perf_gate.py --live my_profile.json --tolerance 0.5
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# live hidden-fraction band vs PIPELINE's offline overlap efficiency:
# the two measure the same overlap through different protocols (A/B
# wall-clock vs span-interval accounting), so the band is generous but
# a collapsed pipeline (fraction ~0) must fail.
HIDDEN_FRACTION_BAND = 0.25


def _get(d: dict, path: str):
    cur = d
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


class Rule:
    """One pinned band: ``key op bound`` over an artifact dict."""

    def __init__(self, key: str, op: str, bound):
        self.key, self.op, self.bound = key, op, bound

    def check(self, art: dict) -> Tuple[bool, str]:
        v = _get(art, self.key)
        ok = False
        if v is None:
            return False, f"{self.key}: MISSING (want {self.op} {self.bound})"
        if self.op == ">":
            ok = v > self.bound
        elif self.op == ">=":
            ok = v >= self.bound
        elif self.op == "<":
            ok = v < self.bound
        elif self.op == "<=":
            ok = v <= self.bound
        elif self.op == "==":
            ok = v == self.bound
        elif self.op == "is":
            ok = v is self.bound
        return ok, f"{self.key}={v!r} {self.op} {self.bound!r}"


# pinned bands per artifact family — the same done-bars the bench modes
# and test_bench_smoke schema tests enforce, applied to the NEWEST
# artifact of each family.  Older artifacts are history, not contracts.
RULES: Dict[str, List[Rule]] = {
    # MULTICHIP artifacts are pass/fail dryrun records, not rates
    "MULTICHIP": [Rule("ok", "is", True), Rule("rc", "==", 0)],
    "SCALING": [Rule("value", ">", 0)],
    "SERVE": [
        Rule("value", ">", 0),
        Rule("recompiles_after_warmup", "==", 0),
        Rule("batch_occupancy_mean", ">", 0),
        Rule("batch_occupancy_mean", "<=", 1.0),
    ],
    "CHAOS": [
        Rule("loss_band_ok", "is", True),
        Rule("faults_injected", ">", 0),
        # the slow_slice A/B (runtime/chaos._slow_slice_scenario): the
        # stale leg absorbed the whole slice's tail with zero forced
        # waits while the sync control paid it, and the staleness
        # ledger named a slow-slice member laggiest every slow round
        Rule("slow_slice.survived", "is", True),
        Rule("slow_slice.straggler_named_ok", "is", True),
        Rule("slow_slice.stale.forced_waits", "==", 0),
        Rule("slow_slice.loss_band_ok", "is", True),
    ],
    "PIPELINE": [
        Rule("value", ">", 1.0),  # pipelined strictly faster than serial
        Rule("overlap_efficiency", ">=", 0.5),
    ],
    "OBS": [
        Rule("overhead_traced_pct", "<", 2.0),
        Rule("off_span_ns", "<", 100_000),
        Rule("producer_overlap_observed", "is", True),
    ],
    "HEALTH": [
        Rule("overhead_audit_pct", "<", 2.0),
        Rule("bit_identical", "is", True),
        Rule("detection_exact", "is", True),
        Rule("loss_band_ok", "is", True),
        Rule("rollbacks", ">=", 1),
    ],
    "COMM": [
        Rule("overlap_vs_ideal", "<=", 1.15),
        Rule("bytes_ratio_int8", ">=", 4.0 - 0.005),
        Rule("bytes_ratio_bf16", ">=", 2.0 - 0.005),
        Rule("loss_band_ok", "is", True),
    ],
    "PROFILE": [
        Rule("overhead_profiled_pct", "<", 2.0),
        Rule("straggler_attributed", "is", True),
        Rule("hidden_frac_h2d_p50", ">", 0.0),
        Rule("flops_cross_check_ratio", ">", 0.0),
    ],
    "SANITIZE": [
        # the hot-path invariant contract (bench.py --mode=sanitize,
        # the dynamic half of tools/lint.py): >=5 steady-state
        # pipelined rounds under jax.transfer_guard(disallow) with
        # zero disallowed transfers and a flat jit cache, the guard
        # proven armed by a control, one fresh-compile round under
        # jax.checking_leaks, zero new lint findings, and a non-empty
        # enumerated deliberate-sync inventory
        Rule("value", ">=", 5),
        Rule("rounds_guarded", ">=", 5),
        Rule("disallowed_transfers", "==", 0),
        Rule("recompiles_post_warmup", "==", 0),
        Rule("guard_armed", "is", True),
        Rule("leak_check_ok", "is", True),
        Rule("lint_new_findings", "==", 0),
        Rule("annotated_sync_count", ">", 0),
    ],
    "FLEET": [
        # the fleet observability plane contract (bench.py
        # --mode=fleet): shipper overhead inside the <2% acceptance
        # budget, the seeded dead host and seeded cross-host straggler
        # both attributed EXACTLY (right host, right round), the
        # injected clock skews recovered by the collector's offset
        # estimation (merged traces interleave only after correction),
        # and the collector-outage leg replayed the shipper's buffer
        # with zero lost and zero dropped events
        Rule("overhead_shipped_pct", "<", 2.0),
        Rule("hosts", ">=", 2),
        Rule("straggler_attributed", "is", True),
        Rule("dead_detection_exact", "is", True),
        Rule("clock_offset_bounded", "is", True),
        Rule("trace_interleaves_after_correction", "is", True),
        Rule("overhead_lost_events", "==", 0),
        Rule("outage_push_failures", ">", 0),
        Rule("outage_replayed_events", ">", 0),
        Rule("outage_lost_events", "==", 0),
        Rule("outage_dropped_events", "==", 0),
    ],
    "DELIVERY": [
        # the serving fleet + train-to-serve contract (bench.py
        # --mode=delivery): fleet throughput scales with replicas under
        # the modeled per-replica device cost (the real-engine leg is
        # disclosed, not gated — a 1-core box measures CPU contention,
        # not fleet design), the fleet-wide 429 shed count is invariant
        # in the replica count at fixed offered load, a good publish
        # promotes with ZERO dropped in-flight requests and
        # bit-identical outputs, the seeded-bad publish rolls back
        # named at exactly the injected publish with the incumbent
        # held, and a mid-traffic replica kill ejects + respawns with
        # zero client errors
        Rule("value", ">", 0),
        Rule("scaling_ratio_modeled", ">", 1.2),
        Rule("shed_invariant_ok", "is", True),
        Rule("promote_ok", "is", True),
        Rule("promote_dropped_inflight", "==", 0),
        Rule("promote_bit_identical", "is", True),
        Rule("rollback_exact", "is", True),
        Rule("rollback_dropped_inflight", "==", 0),
        Rule("incumbent_held_after_rollback", "is", True),
        Rule("replica_kill_ok", "is", True),
        Rule("replica_kill_client_errors", "==", 0),
    ],
    "ELASTIC": [
        # the elastic membership + two-tier hierarchy contract
        # (bench.py --mode=elastic): a flat HierarchySpec's round
        # bit-identical to the single-tier round, the SIGTERM'd
        # slice's departure landing at EXACTLY the next round
        # boundary, the rejoin completing (whole roster live, views
        # monotonic), the faulted run's final loss inside the no-fault
        # band, and the two-tier schedule's measured cross-slice
        # bytes ~K x below the every-round flat run (K=4 committed;
        # the K-relative band is the extra rule below)
        Rule("value", ">", 1.0),
        Rule("flat_bit_identical", "is", True),
        Rule("departure_detected_exact", "is", True),
        Rule("rejoin_completed", "is", True),
        Rule("views_monotonic", "is", True),
        Rule("loss_band_ok", "is", True),
        Rule("cross_bytes_ratio", ">=", 3.9),
    ],
    "RECOVER": [
        # the crash-consistency contract (bench.py --mode=recover):
        # every seeded kill-point survived with the resumed trajectory
        # BIT-IDENTICAL to the uninterrupted control, at most one
        # replayed round per recovery, the no-journal control visibly
        # diverged (the zero is not vacuous), the journal itself
        # bit-neutral on an uninterrupted run, and its overhead inside
        # the +/-1-3% noise floor
        Rule("value", ">=", 6),
        Rule("killpoints_total", ">=", 6),
        Rule("bit_identical_all", "is", True),
        Rule("max_replayed_rounds", "<=", 1),
        Rule("no_journal_diverged", "is", True),
        Rule("journal_bit_neutral", "is", True),
        Rule("journal_overhead_pct", "<", 3.0),
        # the bounded-staleness leg: SIGKILL at the stale_boundary
        # phase, resumed from the journaled worker-round vector
        # bit-identically (the <=stale_bound replay is the extra rule)
        Rule("stale.survived", "is", True),
        Rule("stale.bit_identical", "is", True),
    ],
    "LM": [
        # the transformer-LM workload contract (bench.py --mode=lm):
        # the sp=2 ring-attention run reproduces the sp=1 dense run's
        # trajectory within the pinned associativity tolerance (the
        # extra rule below compares the measured diff against the
        # artifact's own pin), the seeded run's loss strictly
        # decreases (the identity is not two broken runs agreeing),
        # and the modeled ring-hop KV bytes are recorded for a real
        # sp>1 mesh
        Rule("value", ">", 0),
        Rule("sp", ">=", 2),
        Rule("rounds", ">=", 4),
        Rule("sp_trajectory_ok", "is", True),
        Rule("loss_strictly_decreasing", "is", True),
        Rule("ring_hop_bytes_per_round", ">", 0),
        Rule("tokens_per_round", ">", 0),
    ],
    "GENSERVE": [
        # the autoregressive generation-serving contract (bench.py
        # --mode=genserve): continuous batching strictly beats static
        # generation-level batching on the mixed-length workload with
        # IDENTICAL greedy token sequences (the ratio isolates
        # scheduling — the absolute tokens/s is this CPU box's number,
        # disclosed ungated), the 429 storm sheds at admission (never
        # a mid-stream OOM) with client-measured p99 TTFT bounded,
        # ZERO recompiles after warmup across every leg, KV-block
        # accounting exact at drain, the verdicted publish promotes
        # under live stream traffic with zero dropped decodes and a
        # token-identical probe, and the forged-verdict poisoned
        # publish rolls back on per-token logprob divergence with the
        # incumbent held (the extra rules below compare the measured
        # divergences against the artifact's own pin)
        Rule("value", ">", 0),
        Rule("continuous_vs_static_ratio", ">=", 1.05),
        Rule("ab_tokens_identical", "is", True),
        Rule("storm_shed_429", ">", 0),
        Rule("storm_errors", "==", 0),
        Rule("storm_p99_ttft_ms", "<", 2000.0),
        Rule("post_warmup_recompiles", "==", 0),
        Rule("kv_exact", "is", True),
        Rule("kv_blocks_in_use_after_drain", "==", 0),
        Rule("promote_ok", "is", True),
        Rule("promote_dropped_streams", "==", 0),
        Rule("promote_token_identical", "is", True),
        Rule("rollback_exact", "is", True),
        Rule("rollback_dropped_streams", "==", 0),
        Rule("incumbent_held_after_rollback", "is", True),
    ],
    "STALE": [
        # the bounded-staleness contract (bench.py --mode=stale):
        # --stale_bound 0 BITWISE identical to the sync trainer (flat
        # and two-tier), the transient straggler's tail off the
        # critical path (straggled-round p50 within the pinned band of
        # the no-straggler baseline — the extra rule makes the split
        # artifact-self-relative), zero bound-forced folds inside the
        # window (K < B by construction), the final loss inside the
        # sync control's band, and the asymmetric two-tier leg naming
        # the straggler's coarsened slice laggiest with finite losses
        Rule("value", "<=", 25.0),
        Rule("b0_bit_identical", "is", True),
        Rule("b0_flat_bit_identical", "is", True),
        Rule("b0_hier_bit_identical", "is", True),
        Rule("stale_straggler_penalty_pct", "<=", 25.0),
        Rule("forced_folds", "==", 0),
        Rule("stale_bound", ">=", 1),
        Rule("loss_band_ok", "is", True),
        Rule("hier_laggiest_ok", "is", True),
        Rule("hier_finite", "is", True),
    ],
    "KERNELS": [
        # the Pallas raw-speed pass contract (bench.py --mode=kernels):
        # flash fwd+bwd pinned against the dense reference in interpret
        # mode (fp32, bf16, ragged T_q, end-aligned T_q<T_k causal),
        # the ring flash path within the LM associativity tolerance,
        # the fused averaging epilogue BITWISE identical to the
        # unfused trainer with the int8 leg inside the COMM loss band,
        # zero post-warmup recompiles with the kernel in a jitted
        # step, and both modeled HBM-bytes ratios above 1 (the
        # wall-clock rules are the extra rule below: armed, enforced
        # only on-chip).  The measured-diff-vs-own-pin comparisons are
        # the extra rule; the LM/COMM cross-checks live in
        # _cross_rules.
        Rule("value", ">", 1.0),
        Rule("flash_fwd_ok", "is", True),
        Rule("flash_grad_ok", "is", True),
        Rule("flash_ragged_ok", "is", True),
        Rule("flash_bf16_ok", "is", True),
        Rule("ring_flash_ok", "is", True),
        Rule("trainer_ab_bitwise", "is", True),
        Rule("fused_kernel_launches", ">", 0),
        Rule("loss_band_ok", "is", True),
        Rule("post_warmup_recompiles", "==", 0),
        Rule("attn_hbm_ratio", ">", 1.0),
        Rule("epilogue_hbm_ratio", ">", 1.0),
        Rule("wallclock_rules_armed", "is", True),
    ],
    "DATACACHE": [
        # the I/O-flat contract: a warm (cache-filled, shuffled-
        # assignment) epoch makes ZERO network fetches and is strictly
        # faster than the cold epoch, with cached bytes byte-identical
        # to streamed bytes
        Rule("value", ">", 1.0),
        Rule("warm_epoch_fetches", "==", 0),
        Rule("cold_epoch_fetches", ">", 0),
        Rule("nocache_epoch2_fetches", ">", 0),
        Rule("bytes_identical", "is", True),
        Rule("minibatches_identical", "is", True),
    ],
    "SERVEOBS": [
        # the request-anatomy observability contract (bench.py
        # --mode=servetrace, obs/reqtrace.py): tracing overhead on the
        # interleaved A/B inside the OBS <2% acceptance (disclosed
        # against the box's own untraced spread — the noise-floor
        # contract), zero post-warmup recompiles with the
        # instrumentation live, every request stage covered end to end
        # through a real HTTP server (including the chunked-NDJSON
        # stream_write), the 429 carrying its machine-readable shed
        # cause, the /healthz request-profile block present, the
        # seeded KV-pool squeeze ATTRIBUTED kv-bound (a squeezed arena
        # sheds instead of queuing — time-shares alone cannot see
        # it), and the seeded slow replica NAMED exactly with the
        # two-condition skew guard tripped.  The TPOT-vs-throughput
        # consistency check lives in _cross_rules vs GENSERVE.
        Rule("value", "<", 2.0),
        Rule("overhead_pct", "<", 2.0),
        Rule("traced_requests", ">", 0),
        Rule("post_warmup_recompiles", "==", 0),
        Rule("stages_covered", ">=", 5),
        Rule("shed_cause_header", "==", "kv_reserve"),
        Rule("healthz_has_profile", "is", True),
        Rule("metrics_has_req_series", "is", True),
        Rule("kv_squeeze_attributed", "==", 1),
        Rule("slow_replica_correct", "==", 1),
        Rule("replica_skew", ">=", 1.5),
    ],
    "SLO": [
        # the time-series + burn-rate alerting contract (bench.py
        # --mode=slo, obs/tsdb.py + obs/slo.py): each seeded fault's
        # FIRST alert lands within one 300 s burn window of its seed
        # (value = worst delay / window), the healthy control replay
        # fires ZERO alerts across real evaluations, the ring+rollup
        # store holds the full 3-host series set under its byte budget
        # without dropping series, the 10 s rollups agree with raw
        # step-1 queries, /signals matches recomputation from raw
        # series, and the collector's HTTP surface answers end to end.
        # Threshold-vs-measured-latency sanity lives in _cross_rules
        # vs SERVEOBS; signal trustworthiness vs FLEET.
        Rule("value", "<", 1.0),
        Rule("latency_alert_fired", "is", True),
        Rule("shed_alert_fired", "is", True),
        Rule("latency_detect_delay_s", "<", 300.0),
        Rule("shed_detect_delay_s", "<", 300.0),
        Rule("control_false_alarms", "==", 0),
        Rule("control_evals", ">", 0),
        Rule("tsdb_under_budget", "is", True),
        Rule("tsdb_dropped_series", "==", 0),
        Rule("downsample_agree", "is", True),
        Rule("signals_match", "is", True),
        Rule("endpoints_ok", "is", True),
    ],
}


def find_artifacts(root: str = _REPO) -> Dict[str, Tuple[int, List[str]]]:
    """Newest committed artifacts per family: ``FAMILY -> (round,
    [paths])``.  Suffixed variants (SCALING_r04_googlenet) count in their
    family and ALL same-newest-round variants are returned (sorted, the
    unsuffixed one first) so the gate validates every one of them — a
    single arbitrary glob-order pick would silently skip siblings.
    BASELINE.json and non-artifact JSONs are ignored."""
    newest: Dict[str, Tuple[int, List[str]]] = {}
    for path in glob.glob(os.path.join(root, "*.json")):
        m = re.match(
            # suffixes may contain underscores (SCALING_r06_cifar10_full)
            r"([A-Z]+)_r(\d+)(?:_[A-Za-z0-9_]+)?\.json$",
            os.path.basename(path),
        )
        if not m or m.group(1) not in RULES:
            continue
        fam, rnd = m.group(1), int(m.group(2))
        if fam not in newest or rnd > newest[fam][0]:
            newest[fam] = (rnd, [path])
        elif rnd == newest[fam][0]:
            newest[fam][1].append(path)
    for rnd, paths in newest.values():
        paths.sort()
    return newest


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _chaos_survival_rule(art: dict) -> Tuple[bool, str]:
    ok = art.get("faults_survived") == art.get("faults_injected")
    return ok, (
        "faults_survived=%r == faults_injected=%r"
        % (art.get("faults_survived"), art.get("faults_injected"))
    )


def _pipeline_order_rule(art: dict) -> Tuple[bool, str]:
    ok = art.get("pipelined_round_ms", 1e99) < art.get("serial_round_ms", 0)
    return ok, (
        "pipelined_round_ms=%r < serial_round_ms=%r"
        % (art.get("pipelined_round_ms"), art.get("serial_round_ms"))
    )


def _elastic_ratio_rule(art: dict) -> Tuple[bool, str]:
    """The cross-slice byte reduction must track the artifact's OWN K
    (cross_slice_every), whatever K the bench ran with."""
    k = art.get("cross_slice_every") or 0
    ratio = art.get("cross_bytes_ratio") or 0
    ok = bool(k and ratio >= k * 0.95)
    return ok, (
        "cross_bytes_ratio=%r >= 0.95*cross_slice_every=%r" % (ratio, k)
    )


def _lm_tolerance_rule(art: dict) -> Tuple[bool, str]:
    """The measured sp=1-vs-sp=2 trajectory diff must sit inside the
    artifact's OWN pinned associativity tolerance, whatever tolerance
    the bench ran with."""
    tol = art.get("sp_tolerance")
    diff = art.get("sp_max_abs_param_diff")
    ok = bool(
        tol is not None and diff is not None and 0 <= diff <= tol
    )
    return ok, (
        "sp_max_abs_param_diff=%r <= sp_tolerance=%r" % (diff, tol)
    )


def _recover_survival_rule(art: dict) -> Tuple[bool, str]:
    ok = art.get("killpoints_survived") == art.get("killpoints_total")
    return ok, (
        "killpoints_survived=%r == killpoints_total=%r"
        % (art.get("killpoints_survived"), art.get("killpoints_total"))
    )


def _recover_stale_replay_rule(art: dict) -> Tuple[bool, str]:
    """The stale leg's replay must sit inside the artifact's OWN bound:
    a stale_boundary kill rewinds to the journaled worker-round vector
    and re-executes at most stale_bound rounds."""
    s = art.get("stale") or {}
    rep, bound = s.get("replayed_rounds"), s.get("stale_bound")
    ok = bool(
        bound and rep is not None and 0 <= rep <= bound
    )
    return ok, (
        "stale.replayed_rounds=%r <= stale.stale_bound=%r" % (rep, bound)
    )


def _stale_wallclock_rule(art: dict) -> Tuple[bool, str]:
    """The penalty split, self-relative to the artifact's own tail:
    the stale leg's straggled-round p50 sits within the pinned band of
    the no-straggler baseline while the sync control measurably pays
    the tail it injected — whatever tail_s the bench calibrated."""
    base = art.get("baseline_round_ms_p50") or 0
    sync = art.get("sync_slow_round_ms_p50") or 0
    stale = art.get("stale_slow_round_ms_p50")
    tail_ms = 1e3 * (art.get("tail_s") or 0)
    ok = bool(
        base and tail_ms and stale is not None
        and stale <= base * 1.25
        and sync >= base + 0.8 * tail_ms
    )
    return ok, (
        "stale_slow_round_ms_p50=%r <= 1.25*baseline=%r and "
        "sync_slow_round_ms_p50=%r >= baseline+0.8*tail=%r"
        % (stale, round(base * 1.25, 1), sync,
           round(base + 0.8 * tail_ms, 1))
    )


def _genserve_kv_rule(art: dict) -> Tuple[bool, str]:
    a, f = art.get("kv_allocated_total"), art.get("kv_freed_total")
    ok = bool(a is not None and a > 0 and a == f)
    return ok, (
        "kv_allocated_total=%r == kv_freed_total=%r (and > 0)" % (a, f)
    )


def _genserve_divergence_rule(art: dict) -> Tuple[bool, str]:
    """The canary decision must be decisive against the artifact's OWN
    pin: the good publish's per-token logprob divergence sits inside
    it, the poisoned publish's strictly outside."""
    pin = art.get("divergence_max")
    good = art.get("promote_max_divergence")
    bad = art.get("rollback_divergence")
    ok = bool(
        pin is not None and good is not None and bad is not None
        and 0 <= good <= pin < bad
    )
    return ok, (
        "promote_max_divergence=%r <= divergence_max=%r < "
        "rollback_divergence=%r" % (good, pin, bad)
    )


def _kernels_pins_rule(art: dict) -> Tuple[bool, str]:
    """Every measured kernel diff must sit inside the artifact's OWN
    pin, whatever tolerances the bench ran with (the ok flags above
    must agree with the numbers, not just with themselves)."""
    pairs = (
        ("flash_fwd_max_diff", "flash_fwd_tol"),
        ("flash_grad_max_diff", "flash_grad_tol"),
        ("flash_ragged_fwd_max_diff", "flash_fwd_tol"),
        ("flash_ragged_grad_max_diff", "flash_grad_tol"),
        ("flash_bf16_fwd_max_diff", "flash_bf16_fwd_tol"),
        ("flash_bf16_grad_max_diff", "flash_bf16_grad_tol"),
        ("ring_flash_max_diff", "ring_tolerance"),
        ("int8_loss_gap", "loss_band"),
    )
    bad = []
    for mk, tk in pairs:
        m, t = art.get(mk), art.get(tk)
        if m is None or t is None or not (0 <= m <= t):
            bad.append("%s=%r vs %s=%r" % (mk, m, tk, t))
    return not bad, (
        "all measured diffs inside the artifact's own pins"
        if not bad else "out of pin: " + "; ".join(bad)
    )


def _kernels_wallclock_rule(art: dict) -> Tuple[bool, str]:
    """Wall-clock speedup rules: ARMED everywhere, enforced only for an
    artifact measured on-chip — an interpret-mode CPU record discloses
    itself (wallclock_measured false) and skips, it does not fake a
    speedup."""
    if art.get("platform") != "tpu":
        ok = art.get("wallclock_measured") is False
        return ok, (
            "off-chip artifact (platform=%r): wall-clock rules armed "
            "but skipped, wallclock_measured=%r"
            % (art.get("platform"), art.get("wallclock_measured"))
        )
    spd = art.get("wallclock_attn_speedup")
    ok = bool(
        art.get("wallclock_measured") is True
        and spd is not None and spd > 1.0
    )
    return ok, "on-chip: wallclock_attn_speedup=%r > 1.0" % (spd,)


_EXTRA_RULES = {
    "CHAOS": [_chaos_survival_rule],
    "PIPELINE": [_pipeline_order_rule],
    "ELASTIC": [_elastic_ratio_rule],
    "RECOVER": [_recover_survival_rule, _recover_stale_replay_rule],
    "STALE": [_stale_wallclock_rule],
    "LM": [_lm_tolerance_rule],
    "GENSERVE": [_genserve_kv_rule, _genserve_divergence_rule],
    "KERNELS": [_kernels_pins_rule, _kernels_wallclock_rule],
}


def _cross_rules(arts: Dict[str, dict]) -> List[Tuple[str, bool, str]]:
    """Cross-artifact bands: a claim proved offline must still hold in
    the live-profile artifact."""
    out = []
    prof = arts.get("PROFILE")
    pipe = arts.get("PIPELINE")
    if prof is not None and pipe is not None:
        eff = pipe.get("overlap_efficiency")
        live = prof.get("hidden_frac_h2d_p50")
        if eff is not None and live is not None:
            floor = eff - HIDDEN_FRACTION_BAND
            out.append((
                "PROFILE x PIPELINE",
                live >= floor,
                "live hidden_frac_h2d_p50=%r >= overlap_efficiency-%.2f"
                "=%.3f" % (live, HIDDEN_FRACTION_BAND, floor),
            ))
    kern = arts.get("KERNELS")
    lm = arts.get("LM")
    if kern is not None and lm is not None:
        # the ring flash path must sit inside the LM artifact's OWN
        # associativity tolerance — the sp training run's pin, not a
        # band the kernels bench picked for itself
        diff, tol = kern.get("ring_flash_max_diff"), lm.get("sp_tolerance")
        out.append((
            "KERNELS x LM",
            bool(tol is not None and diff is not None
                 and 0 <= diff <= tol),
            "ring_flash_max_diff=%r <= LM sp_tolerance=%r" % (diff, tol),
        ))
    sobs = arts.get("SERVEOBS")
    gen = arts.get("GENSERVE")
    if sobs is not None and gen is not None:
        # attribution consistency: the profiler's decode-attributed
        # per-token time must agree with the genserve round's
        # INDEPENDENTLY measured continuous throughput — 4x covers the
        # workload-mix and partial-occupancy gap, not a broken fold
        tpot = sobs.get("tpot_p50_ms")
        tps = gen.get("continuous_tokens_per_s")
        slots = gen.get("decode_slots")
        implied = (
            1e3 * slots / tps if tps and slots else None
        )
        out.append((
            "SERVEOBS x GENSERVE",
            bool(tpot is not None and implied is not None
                 and 0 < tpot <= 4.0 * implied),
            "profiled tpot_p50_ms=%r <= 4x genserve implied per-slot "
            "token time %s ms"
            % (tpot, "%.3f" % implied if implied else implied),
        ))
        # and tracing must not collapse serve throughput: the traced
        # leg keeps >=25% of the genserve continuous rate (different
        # token mix, same engine/box)
        ttps = sobs.get("traced_tokens_per_s")
        out.append((
            "SERVEOBS x GENSERVE",
            bool(ttps is not None and tps is not None
                 and ttps >= 0.25 * tps),
            "traced_tokens_per_s=%r >= 0.25 x genserve "
            "continuous_tokens_per_s=%r" % (ttps, tps),
        ))
    slo = arts.get("SLO")
    if slo is not None and sobs is not None:
        # the latency objective must be ACHIEVABLE on this box: the
        # 0.5 s TTFT threshold has to clear the serveobs artifact's
        # independently measured p95 — an objective the hardware
        # cannot meet would page forever and the control-leg silence
        # above would be vacuous
        thr = slo.get("ttft_threshold_ms")
        p95 = sobs.get("ttft_p95_ms")
        out.append((
            "SLO x SERVEOBS",
            bool(thr is not None and p95 is not None and thr >= p95),
            "slo ttft_threshold_ms=%r >= serveobs measured "
            "ttft_p95_ms=%r" % (thr, p95),
        ))
    fleet = arts.get("FLEET")
    if slo is not None and fleet is not None:
        # /signals is only as trustworthy as the fleet plane under it:
        # the collector must have proven dead-host detection and
        # bounded clock offset, and the signal API must cover every
        # simulated host's round rate
        out.append((
            "SLO x FLEET",
            bool(
                fleet.get("dead_detected") is True
                and fleet.get("clock_offset_bounded") is True
                and slo.get("round_rate_hosts") == slo.get("hosts")
            ),
            "fleet dead_detected=%r, clock_offset_bounded=%r, slo "
            "round_rate_hosts=%r == hosts=%r" % (
                fleet.get("dead_detected"),
                fleet.get("clock_offset_bounded"),
                slo.get("round_rate_hosts"), slo.get("hosts"),
            ),
        ))
    comm = arts.get("COMM")
    if kern is not None and comm is not None:
        # the fused int8 leg's loss gap must sit inside the COMM
        # artifact's committed band (same cifar10_quick protocol)
        gap, band = kern.get("int8_loss_gap"), comm.get("loss_band")
        out.append((
            "KERNELS x COMM",
            bool(band is not None and gap is not None
                 and 0 <= gap <= band),
            "int8_loss_gap=%r <= COMM loss_band=%r" % (gap, band),
        ))
    return out


def check(root: str = _REPO) -> Tuple[int, List[dict]]:
    """Run every family's rules over its newest artifact.  Returns
    (exit code, result rows)."""
    rows: List[dict] = []
    arts: Dict[str, dict] = {}
    rc = 0
    for fam, (rnd, paths) in sorted(find_artifacts(root).items()):
        for path in paths:
            try:
                art = _load(path)
            except (OSError, ValueError) as e:
                rows.append({
                    "family": fam, "artifact": os.path.basename(path),
                    "ok": False, "detail": f"unreadable: {e}",
                })
                rc = 1
                continue
            # cross-rules read the family's primary (unsuffixed-first)
            # artifact — the sorted order puts it at paths[0]
            arts.setdefault(fam, art)
            for rule in RULES[fam]:
                ok, detail = rule.check(art)
                rows.append({
                    "family": fam, "artifact": os.path.basename(path),
                    "ok": ok, "detail": detail,
                })
                rc = rc or (0 if ok else 1)
            for fn in _EXTRA_RULES.get(fam, ()):
                ok, detail = fn(art)
                rows.append({
                    "family": fam, "artifact": os.path.basename(path),
                    "ok": ok, "detail": detail,
                })
                rc = rc or (0 if ok else 1)
    for name, ok, detail in _cross_rules(arts):
        rows.append({
            "family": name, "artifact": "(cross)", "ok": ok,
            "detail": detail,
        })
        rc = rc or (0 if ok else 1)
    return rc, rows


def check_live(
    live_path: str, root: str = _REPO, tolerance: float = 0.5
) -> Tuple[int, List[dict]]:
    """Fold a live profile against the committed baselines.  Accepts a
    ``RoundProfiler.summary()`` JSON dump or a PROFILE_* artifact;
    ``tolerance`` bounds the allowed round-time growth vs the committed
    profile leg (0.5 = +50%, generous because boxes differ — the gate
    catches collapses, CI pins exact bands)."""
    live = _load(live_path)
    arts = {
        fam: _load(paths[0])  # the primary (unsuffixed-first) artifact
        for fam, (_, paths) in find_artifacts(root).items()
    }
    rows: List[dict] = []
    rc = 0

    def row(ok: bool, detail: str, vs: str) -> None:
        nonlocal rc
        rows.append({
            "family": "LIVE", "artifact": vs, "ok": ok, "detail": detail,
        })
        rc = rc or (0 if ok else 1)

    # live summary vs artifact field naming
    live_hidden = (
        _get(live, "hidden_frac_h2d.p50")
        if isinstance(live.get("hidden_frac_h2d"), dict)
        else live.get("hidden_frac_h2d_p50")
    )
    pipe = arts.get("PIPELINE")
    if pipe is not None and live_hidden is not None:
        floor = pipe.get("overlap_efficiency", 0) - HIDDEN_FRACTION_BAND
        row(
            live_hidden >= floor,
            "hidden_frac_h2d p50=%r >= %.3f (PIPELINE overlap_efficiency"
            " - %.2f)" % (live_hidden, floor, HIDDEN_FRACTION_BAND),
            "PIPELINE",
        )
    elif live_hidden is None:
        # a serial-feed / bare-solver run has no producer spans at all
        # (hidden_frac_h2d: null) — nothing to compare, not a
        # regression.  A COLLAPSED pipeline still reads ~0.0, not null,
        # and fails the band check above.
        row(True, "live profile carries no hidden_frac_h2d "
            "(serial feed or no RoundFeed) — overlap check skipped",
            "PIPELINE")
    live_round = (
        _get(live, "round_ms.p50")
        if isinstance(live.get("round_ms"), dict)
        else live.get("profiled_round_ms")
    )
    prof = arts.get("PROFILE")
    if prof is not None and live_round is not None:
        base = prof.get("profiled_round_ms")
        if base:
            ceil = base * (1.0 + tolerance)
            row(
                live_round <= ceil,
                "round_ms p50=%r <= %.1f (committed profile leg %.1f "
                "+%d%%)" % (live_round, ceil, base, int(tolerance * 100)),
                "PROFILE",
            )
    # prefer the window-scoped count: `rounds` is capped at the record
    # window while `straggler_rounds` counts for the run's lifetime —
    # comparing the two would flag long-healed runs as standing.  A
    # PROFILE_* bench artifact carries a DELIBERATELY seeded straggler
    # leg (straggler_seeded_worker) whose counter says nothing about a
    # standing slow worker — skip the check for those inputs.
    sr = live.get("straggler_rounds_window", live.get("straggler_rounds"))
    if "straggler_seeded_worker" in live:
        sr = None
    if sr is not None:
        # informational unless the live run says a straggler verdict
        # fired every round — that is a standing slow worker
        rounds = live.get("rounds") or live.get("rounds_profiled") or 0
        standing = bool(rounds and sr >= rounds and rounds > 1)
        row(
            not standing,
            "straggler_rounds=%r of %r rounds%s"
            % (sr, rounds, " — standing straggler" if standing else ""),
            "(live)",
        )
    return rc, rows


def format_rows(rows: List[dict]) -> str:
    lines = []
    for r in rows:
        lines.append(
            "%-4s %-18s %-24s %s"
            % ("ok" if r["ok"] else "FAIL", r["family"], r["artifact"],
               r["detail"])
        )
    fails = sum(1 for r in rows if not r["ok"])
    lines.append(
        "perf gate: %d check(s), %d failure(s)" % (len(rows), fails)
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--check", action="store_true",
        help="validate the newest committed artifact of every family "
        "against its pinned bands (+ cross-artifact rules)",
    )
    ap.add_argument(
        "--live", metavar="RUN.json", default=None,
        help="fold a live RoundProfiler.summary() dump (or PROFILE_* "
        "artifact) against the committed baselines",
    )
    ap.add_argument(
        "--root", default=_REPO,
        help="repo root holding the committed artifacts",
    )
    ap.add_argument(
        "--tolerance", type=float, default=0.5,
        help="--live round-time growth tolerance vs the committed "
        "profile leg (0.5 = +50%%)",
    )
    ap.add_argument("--json", action="store_true",
                    help="emit results as JSON rows")
    args = ap.parse_args(argv)
    if not args.check and not args.live:
        ap.error("pass --check and/or --live RUN.json")
    rc = 0
    rows: List[dict] = []
    if args.check:
        c_rc, c_rows = check(args.root)
        rc, rows = rc or c_rc, rows + c_rows
    if args.live:
        l_rc, l_rows = check_live(args.live, args.root, args.tolerance)
        rc, rows = rc or l_rc, rows + l_rows
    if args.json:
        print(json.dumps(rows, indent=2))
    else:
        print(format_rows(rows))
    return rc


if __name__ == "__main__":
    sys.exit(main())
