"""Unified telemetry layer — tracing, metrics, and the /metrics sidecar.

One observability surface shared by training, the data plane, and
serving (ARCHITECTURE.md "Observability"):

- ``obs.metrics``  — Counter/Gauge/Histogram (+ label families) and the
  Prometheus-text ``MetricsRegistry``; ``serve.metrics`` re-exports it.
- ``obs.trace``    — low-overhead ``span()``/``instant()`` emitting
  Chrome trace-event JSON (Perfetto-loadable, thread-correct) plus a
  structured JSONL run log.
- ``obs.exporter`` — the opt-in ``/metrics`` + ``/healthz`` HTTP
  sidecar every ``cli train``/app run gets via ``--obs`` (also exports
  the divergence sentry's state: ``last_anomaly_round``, policy, 503
  while halted).
- ``obs.health``   — the training-health sentry: in-graph numerics
  audit (grad norm, update/param ratios, non-finite counts, fused into
  the jitted step), in-graph poisoned-worker masking, and the
  warn/halt/rollback divergence policy (``--health``).
- ``obs.flight``   — the crash flight recorder: a bounded ring of
  recent spans/verdicts/samples dumped as one postmortem JSON bundle
  on crash/SIGTERM/stall/halt/chaos fault (``--flight_recorder``;
  folded by ``tools/health_report.py``).
- ``obs.profile``  — the round-anatomy profiler (``--profile``): live
  per-phase breakdown, measured H2D/collective hidden fractions,
  per-worker skew + straggler verdicts, MFU/roofline gauges.

Instrumented code calls the module-level hooks (``obs.span``,
``obs.instant``, ``obs.training_metrics()``, ``obs.fault``), which are
near-free no-ops until ``obs.start(...)`` — wired to ``--obs`` /
``--trace_out`` / ``--flight_recorder`` flags by
``add_cli_args``/``start_from_args`` — turns them on.  (``obs.health``
is imported on demand — it pulls jax; the rest of the package stays
import-light for CLI startup.)
"""

from __future__ import annotations

import json
import threading
import time
from typing import Optional

from sparknet_tpu.obs import flight  # noqa: F401
from sparknet_tpu.obs import profile as profile  # noqa: F401
from sparknet_tpu.obs.exporter import JsonHTTPHandler, ObsExporter  # noqa: F401
from sparknet_tpu.obs.fleet import (  # noqa: F401
    DEFAULT_FLEET_PORT,
    FleetCollector,
)
from sparknet_tpu.obs import program  # noqa: F401
from sparknet_tpu.obs.flight import FlightRecorder  # noqa: F401
from sparknet_tpu.obs.profile import RoundProfiler  # noqa: F401
from sparknet_tpu.obs.program import (  # noqa: F401
    Program,
    memory_marks,
    programs,
)
from sparknet_tpu.obs.ship import Shipper  # noqa: F401
from sparknet_tpu.obs.metrics import (  # noqa: F401
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricFamily,
    MetricsRegistry,
)
from sparknet_tpu.obs.trace import (  # noqa: F401
    Tracer,
    get_tracer,
    install_tracer,
    instant,
    jsonl_path_for,
    recording,
    set_phase_observer,
    set_ship,
    span,
    uninstall_tracer,
)

DEFAULT_OBS_PORT = 8380


def _host_rss_bytes() -> float:
    try:
        import resource

        return float(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        )
    except Exception:
        return 0.0


class TrainingMetrics:
    """The training-side series, registered once per process on the
    shared registry (the serving stack registers its own ``serve_*``
    series on its registry the same way)."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        t0 = time.monotonic()
        self.uptime = registry.gauge(
            "sparknet_uptime_seconds", "seconds since telemetry start",
            fn=lambda: time.monotonic() - t0,
        )
        self.rounds = registry.counter(
            "sparknet_rounds_total",
            "training rounds completed (rate() gives rounds/s)",
        )
        self.iters = registry.counter(
            "sparknet_iters_total", "solver iterations completed"
        )
        self.phase_latency = registry.histogram(
            "sparknet_phase_latency_seconds",
            "wall seconds per round phase (assemble/h2d/execute/average/"
            "quantize/allreduce/dequantize/snapshot/restore/verify — "
            "the canonical phase set in analysis/registry.py)",
            labels=("phase",),
        )
        self.feed_queue_depth = registry.gauge(
            "sparknet_feed_queue_depth",
            "device batches ready in the round-feed prefetch queue",
        )
        self.feed_stalls = registry.counter(
            "sparknet_feed_stalls_total",
            "PrefetchStall watchdog fires (producer silent past timeout)",
        )
        self.retries = registry.counter(
            "sparknet_io_retries_total",
            "retry_call attempts that failed and were rescheduled",
        )
        self.snapshots = registry.counter(
            "sparknet_snapshots_total", "checkpoints written"
        )
        self.restores = registry.counter(
            "sparknet_restores_total", "checkpoints restored"
        )
        self.quarantined = registry.counter(
            "sparknet_snapshots_quarantined_total",
            "corrupt snapshots renamed *.corrupt by restore_newest_valid",
        )
        self.faults = registry.counter(
            "sparknet_faults_total",
            "chaos-injected faults observed, by kind",
            labels=("kind",),
        )
        # chunk-cache series (data/chunk_cache.py, --cache_dir) — zero
        # until a run fronts its object store with a ChunkCache
        self.cache_hits = registry.counter(
            "sparknet_cache_hits_total",
            "chunk-cache reads served from verified local entries",
        )
        self.cache_misses = registry.counter(
            "sparknet_cache_misses_total",
            "chunk-cache reads that fetched from the backing object "
            "store (cold, evicted, stale-etag, or quarantined entries)",
        )
        self.cache_evictions = registry.counter(
            "sparknet_cache_evictions_total",
            "chunk-cache entries LRU-evicted at the byte budget",
        )
        self.cache_bytes = registry.counter(
            "sparknet_cache_bytes_total",
            "bytes served through the chunk cache, by source "
            "(hit = local disk, miss = network fetch); an I/O-flat "
            "multi-epoch run's miss series goes flat after epoch 1",
            labels=("src",),
        )
        self.collective_bytes = registry.counter(
            "sparknet_collective_bytes_total",
            "modeled interconnect payload bytes moved by the parameter-"
            "averaging collective (ring factor x compressed payload), "
            "by compression mode",
            labels=("compress",),
        )
        self.quant_error = registry.gauge(
            "sparknet_quant_error_max_abs",
            "last round's max |delta - dequant(delta)| quantization "
            "error of the compressed averaging collective, by "
            "compression mode (parallel/comm.py delta quantization)",
            labels=("compress",),
        )
        self.quant_snr_db = registry.gauge(
            "sparknet_quant_snr_db",
            "last round's delta-vs-quantization-error SNR in dB "
            "(10*log10(|delta|^2/|err|^2); capped at 300 when the "
            "error underflows to 0), by compression mode",
            labels=("compress",),
        )
        self.kernel_path = registry.gauge(
            "sparknet_kernel_path",
            "1 when the named hot path rides its fused Pallas kernel, "
            "0 on the dense/XLA fallback (the ops/pallas_attention."
            "lowerable() routing gate; kernel=attention|epilogue|"
            "sparse_attention; attention_backward: 1 on the one-pass "
            "flash backward, 0 on its two passes or XLA)",
            labels=("kernel",),
        )
        self.kernel_fused_chunks = registry.counter(
            "sparknet_kernel_fused_chunks_total",
            "fused averaging-epilogue kernel launches by the comm "
            "plane (one per comm chunk per stage per round; "
            "stage=encode|apply — ops/pallas_comm.py)",
            labels=("stage",),
        )
        # round-anatomy profiler series (obs/profile.py, --profile) —
        # zero until a RoundProfiler is installed
        self.hidden_fraction = registry.gauge(
            "sparknet_hidden_fraction",
            "measured fraction of overlap-capable work hidden under "
            "consumer execute last round: kind=h2d (RoundFeed producer "
            "assemble+H2D) or kind=comm (CommPlane chunked allreduce)",
            labels=("kind",),
        )
        self.worker_skew = registry.gauge(
            "sparknet_worker_skew",
            "last round's per-worker attributed-time max/median ratio "
            "(1.0 = homogeneous workers)",
        )
        self.straggler_worker = registry.gauge(
            "sparknet_straggler_worker",
            "dp index of the worker the profiler called a straggler "
            "last round (-1 = none)",
        )
        self.straggler_rounds = registry.counter(
            "sparknet_straggler_rounds_total",
            "rounds whose straggler verdict fired (skew past threshold)",
        )
        self.achieved_flops = registry.gauge(
            "sparknet_achieved_flops",
            "modeled achieved FLOP/s last round (analytic utils/flops.py "
            "MXU count / measured round wall)",
        )
        self.mfu = registry.gauge(
            "sparknet_mfu",
            "model FLOP utilization vs the chip's bf16 peak (0 when the "
            "peak is unknown, e.g. CPU)",
        )
        # every program the trainer builds (obs/program.py Program):
        # zero until one is built with metrics on
        self.program_builds = registry.counter(
            "sparknet_program_builds_total",
            "programs lowered and compiled, by program name and "
            "persistent-cache verdict (hit/miss/off); flat after warm-up "
            "iff nothing recompiles, and a second build under one name IS "
            "a recompile (obs.programs() holds both signatures' shapes)",
            labels=("program", "cache"),
        )
        self.program_build_seconds = registry.gauge(
            "sparknet_program_build_seconds",
            "the program's last build: stage=trace_lower (Python tracing "
            "and lowering to StableHLO, which no cache saves) or "
            "stage=compile (XLA's compile, or the fetch from the "
            "persistent cache)",
            labels=("program", "stage"),
        )
        self.program_bytes = registry.gauge(
            "sparknet_program_bytes",
            "the last-built executable's Compiled.memory_analysis() per "
            "device: kind=temp|argument|output|alias|code",
            labels=("program", "kind"),
        )
        self.device_memory = registry.gauge(
            "sparknet_device_memory_bytes",
            "device.memory_stats() of this process's fullest device at "
            "scrape: kind=in_use|peak_in_use|reserved|peak_reserved|limit "
            "(temporaries and reserved bytes included; 0 where the "
            "backend reports nothing)",
            labels=("kind",), fn=program.memory_gauge,
        )
        for kind in program.MEMORY_KINDS:
            self.device_memory.labels(kind)
        self.host_rss = registry.gauge(
            "sparknet_host_rss_bytes", "peak resident set size",
            fn=_host_rss_bytes,
        )
        # training-health series (obs/health.py numerics audit) — zero
        # until a run enables the audit (--health)
        self.grad_norm = registry.gauge(
            "sparknet_grad_norm",
            "global L2 norm of the last audited iteration's raw "
            "gradients (pre-clip)",
        )
        self.nonfinite = registry.counter(
            "sparknet_nonfinite_total",
            "non-finite values seen by the numerics audit "
            "(grads + params + loss)",
        )
        self.update_ratio = registry.gauge(
            "sparknet_update_ratio",
            "per-param-group update/param L2 ratio of the last audited "
            "iteration",
            labels=("group",),
        )
        self.health_anomalies = registry.counter(
            "sparknet_health_anomalies_total",
            "divergence-sentry anomaly verdicts, by kind",
            labels=("kind",),
        )
        self.health_rollbacks = registry.counter(
            "sparknet_health_rollbacks_total",
            "sentry-triggered rollbacks to a verified snapshot",
        )
        # elastic-membership series (runtime/membership.py, --elastic)
        # — zero until a run arms the membership controller
        self.membership_epoch = registry.gauge(
            "sparknet_membership_epoch",
            "current membership view epoch (bumps once per roster "
            "change applied at a round boundary)",
        )
        self.membership_workers = registry.gauge(
            "sparknet_membership_workers",
            "dp workers per membership state (live carry mask weight; "
            "leaving/dead/joining are excluded from the average)",
            labels=("state",),
        )
        self.membership_transitions = registry.counter(
            "sparknet_membership_transitions_total",
            "membership state transitions applied at round boundaries, "
            "by kind (leave/late/death/join_request/rejoin)",
            labels=("kind",),
        )
        # two-tier hierarchical averaging series (parallel/hierarchy.py,
        # --slices/--cross_slice_every) — zero on flat (single-tier)
        # runs
        self.hierarchy_rounds = registry.counter(
            "sparknet_hierarchy_rounds_total",
            "averaging rounds by tier: intra = within-slice (ICI) "
            "average only, cross = the every-K-rounds global (DCN) "
            "average",
            labels=("tier",),
        )
        self.hierarchy_bytes = registry.counter(
            "sparknet_hierarchy_bytes_total",
            "modeled collective payload bytes by tier (ring factor x "
            "payload; the cross series is what the two-tier schedule "
            "divides by K vs an every-round flat run)",
            labels=("tier",),
        )
        # fleet-shipper series (obs/ship.py, --ship_to) — zero until a
        # run ships to a fleet collector
        self.ship_events = registry.counter(
            "sparknet_ship_events_total",
            "run-log events enqueued for shipping to the fleet "
            "collector (includes later-dropped ones)",
        )
        self.ship_dropped = registry.counter(
            "sparknet_ship_dropped_total",
            "buffered events dropped (oldest first) at the shipper's "
            "bound while the collector was unreachable",
        )
        self.ship_pushes = registry.counter(
            "sparknet_ship_pushes_total",
            "successful pushes to the fleet collector",
        )
        self.ship_push_failures = registry.counter(
            "sparknet_ship_push_failures_total",
            "pushes that exhausted their retry budget (collector "
            "unreachable; events stayed buffered)",
        )
        # run-journal / crash-recovery series (io/journal.py +
        # journaled resume paths) — zero until a run arms --journal
        self.journal_records = registry.counter(
            "sparknet_journal_records_total",
            "run-journal records appended, by kind (intent = round "
            "write-ahead, commit = durable round boundary)",
            labels=("kind",),
        )
        self.journal_truncated = registry.counter(
            "sparknet_journal_truncated_total",
            "torn journal tails truncated on open (a kill landed "
            "mid-append; the partial frame failed its CRC)",
        )
        self.recover_replayed = registry.counter(
            "sparknet_recover_replayed_rounds_total",
            "rounds re-executed after a journal-guided resume (the "
            "in-flight round whose commit never landed; at most one "
            "per recovery when every boundary snapshots)",
        )
        # transformer-LM workload series (apps/lm_app.py, --sp) — zero
        # for the CNN apps
        self.lm_tokens = registry.counter(
            "sparknet_lm_tokens_total",
            "tokens trained by the LM workload (dp workers x tau x "
            "batch x seq_len per round)",
        )
        self.lm_ring_bytes = registry.counter(
            "sparknet_lm_ring_hop_bytes_total",
            "modeled ring-attention KV exchange bytes (sequence "
            "parallelism: K+V shards x (sp-1) hops x layers, "
            "forward + transposed backward; zero when sp=1)",
        )
        self.lm_held_assignments = registry.gauge(
            "sparknet_lm_held_assignments_per_token",
            "assignments a token sends to the routed experts HELD on this "
            "chip (top_k x held / experts expected), by layer; set outside "
            "the round loop from models/hybrid_lm routing_counts",
            labels=("layer",),
        )
        self.lm_held_load_skew = registry.gauge(
            "sparknet_lm_held_load_skew",
            "largest held expert's assignments over the held experts' "
            "mean (1.0 = even routing), by layer",
            labels=("layer",),
        )
        self.lm_grouped_rows_used = registry.gauge(
            "sparknet_lm_grouped_rows_used_share",
            "held assignments over the rows of the grouped expert path "
            "(ops/moe.fast_rows_for): the share of those rows the grouped "
            "products run where they stop at the held assignments, over 1 "
            "where the layer runs in token chunks, by layer",
            labels=("layer",),
        )
        self.lm_indexer_loss = registry.gauge(
            "sparknet_lm_indexer_loss",
            "a selected-key attention layer's alignment loss (the KL from "
            "the attention's head-mean probabilities over the selected keys "
            "to the indexer's softmax over them), by layer; set outside the "
            "round loop from models/hybrid_lm selection_readings",
            labels=("layer",),
        )
        self.lm_selection_mass = registry.gauge(
            "sparknet_lm_selection_mass",
            "share of the DENSE causal attention's probability (head mean) "
            "that the indexer's selected keys hold, by layer: what the "
            "alignment loss raises",
            labels=("layer",),
        )
        # bounded-staleness averaging series (parallel/stale.py,
        # --stale_bound) — zero on the synchronous round
        self.staleness = registry.gauge(
            "sparknet_staleness",
            "per-worker staleness at the last averaging boundary "
            "(boundary index minus the worker's own round; 0 on the "
            "synchronous path, bounded by --stale_bound otherwise)",
            labels=("worker",),
        )
        self.stale_arrivals = registry.counter(
            "sparknet_stale_arrivals_total",
            "boundary fold-ins per worker (the arrival mask: the "
            "worker's finished tau-window entered this boundary's "
            "staleness-weighted mean)",
            labels=("worker",),
        )
        self.stale_skipped = registry.counter(
            "sparknet_stale_skipped_total",
            "boundaries a worker sat out (window still in flight; its "
            "contribution folds in at a later boundary instead of "
            "stalling this one)",
            labels=("worker",),
        )
        self.stale_forced_waits = registry.counter(
            "sparknet_stale_forced_waits_total",
            "arrivals forced by the staleness bound (a live worker hit "
            "lag B and the boundary blocked for it — the bounded "
            "synchronous cost; ~0 is the stale bench's win condition)",
        )
        self.stale_boundaries_skipped = registry.counter(
            "sparknet_stale_boundaries_skipped_total",
            "averaging boundaries skipped outright because no worker "
            "had arrived (state untouched, no collective dispatched)",
        )


_lock = threading.Lock()
_training: Optional[TrainingMetrics] = None
_unhealthy_reason: Optional[str] = None
# the active divergence sentry (obs/health.py) — /healthz exports its
# state so an orchestrator can tell "stalled" from "diverged"
_sentry = None
# the active elastic membership controller (runtime/membership.py) —
# /healthz exports its view so an orchestrator can tell "slice 1 is
# leaving" from "the job is wedged"
_membership = None
# the active burn-rate SLO evaluator (obs/slo.py, --slo) — /healthz
# exports objective statuses + recent alert transitions
_slo_evaluator = None


def enable_training_metrics() -> TrainingMetrics:
    """Create (idempotently) the process-wide training registry +
    series, and wire phase-cat spans into the per-phase histogram."""
    global _training
    with _lock:
        if _training is None:
            _training = TrainingMetrics(MetricsRegistry())
            program.set_metrics(_training)
            fam = _training.phase_latency
            set_phase_observer(
                lambda name, dur_s: fam.labels(name).observe(dur_s)
            )
    return _training


def training_metrics() -> Optional[TrainingMetrics]:
    """The enabled training metrics, or None — instrumented code guards
    with one read: ``tm = obs.training_metrics();  if tm: ...``."""
    return _training


def _reset_training_metrics_for_tests() -> None:
    """Drop the process singleton so a test gets fresh counters; NOT
    for production code (instrumented sites cache nothing, so the swap
    is safe mid-process)."""
    global _training, _unhealthy_reason, _sentry, _membership
    global _slo_evaluator
    with _lock:
        _training = None
        _unhealthy_reason = None
        _sentry = None
        _membership = None
        _slo_evaluator = None
        program.set_metrics(None)
        set_phase_observer(None)
        set_ship(None)
    flight.uninstall()
    profile.uninstall()


def set_sentry(sentry) -> None:
    """Register the run's HealthSentry (None clears).  /healthz and
    flight bundles read its ``state_dict()``."""
    global _sentry
    _sentry = sentry


def set_membership(controller) -> None:
    """Register the run's MembershipController (None clears) —
    /healthz gains a ``membership`` block with the current view."""
    global _membership
    _membership = controller


def membership_state() -> Optional[dict]:
    """The active membership controller's exported view, or None."""
    m = _membership
    if m is None:
        return None
    return m.state_dict()


def sentry_state() -> Optional[dict]:
    """The active sentry's exported state, or None when no sentry."""
    s = _sentry
    if s is None:
        return None
    return s.state_dict()


def profile_state() -> Optional[dict]:
    """The active round profiler's exported state (straggler verdict,
    hidden fractions), or None — the /healthz "profile" block."""
    return profile.state()


def set_slo_evaluator(evaluator) -> None:
    """Register the run's SLO evaluator (None clears) — /healthz gains
    an ``slo`` block with objective statuses and recent alerts."""
    global _slo_evaluator
    _slo_evaluator = evaluator


def slo_state() -> Optional[dict]:
    """The active SLO evaluator's compact state, or None."""
    ev = _slo_evaluator
    if ev is None:
        return None
    return ev.state()


def fault(kind: str, **args) -> None:
    """Tag a fault: an instant event on the trace (so fault ->
    recovery latency is readable off the timeline) + the per-kind
    counter when metrics are on + a flight-recorder postmortem dump
    when one is installed (faults are exactly the moments whose recent
    history a postmortem wants)."""
    instant(f"fault_{kind}", cat="fault", **args)
    tm = _training
    if tm is not None:
        tm.faults.labels(kind).inc()
    flight.dump_if_active(f"fault_{kind}", extra=args or None)


def report_unhealthy(reason: str) -> None:
    """Flip /healthz to 503 (stalled feed / wedged round)."""
    global _unhealthy_reason
    _unhealthy_reason = reason


def report_healthy() -> None:
    """A round completed: clear the unhealthy flag."""
    global _unhealthy_reason
    if _unhealthy_reason is not None:
        _unhealthy_reason = None


def health_reason() -> Optional[str]:
    return _unhealthy_reason


# ----------------------------------------------------------------------
# CLI wiring: every training entry point gets the same two flags


def add_cli_args(parser) -> None:
    parser.add_argument(
        "--obs", action="store_true",
        help="serve live Prometheus /metrics + /healthz for this run "
        "(sidecar on --obs_port)",
    )
    parser.add_argument(
        "--obs_port", type=int, default=DEFAULT_OBS_PORT,
        help="telemetry sidecar port (0 = ephemeral)",
    )
    parser.add_argument(
        "--trace_out", "--trace-out", default=None, metavar="TRACE.json",
        help="write a Chrome trace (load in Perfetto: ui.perfetto.dev) "
        "of round phases to this path, plus a .jsonl structured run log",
    )
    parser.add_argument(
        "--health", nargs="?", const="warn", default=None,
        choices=["warn", "halt", "rollback"], metavar="POLICY",
        help="enable the in-graph numerics audit + divergence sentry "
        "(warn|halt|rollback; bare --health = warn).  rollback restores "
        "the newest verified snapshot and skips the poisoned window "
        "(needs snapshot machinery; loops without it degrade to halt)",
    )
    parser.add_argument(
        "--health_policy", default=None,
        choices=["warn", "halt", "rollback"],
        help="sentry policy (overrides --health's value)",
    )
    parser.add_argument(
        "--profile", action="store_true",
        help="install the round-anatomy profiler (obs/profile.py): "
        "live per-phase breakdown, measured H2D/collective hidden "
        "fractions, per-worker skew + straggler verdicts, and "
        "MFU/roofline gauges on /metrics, /healthz and the JSONL run "
        "log; a summary table prints when the run closes",
    )
    parser.add_argument(
        "--profile_out", default=None, metavar="SUMMARY.json",
        help="write the end-of-run RoundProfiler.summary() as JSON "
        "(implies --profile)",
    )
    parser.add_argument(
        "--slo", action="store_true",
        help="retain metric history in the in-process TSDB "
        "(obs/tsdb.py ring buffers, staged 1s/10s/60s rollups) and "
        "evaluate burn-rate SLOs over it (obs/slo.py): the sidecar "
        "gains /query, /slo and /signals plus an slo /healthz block "
        "(implies --obs)",
    )
    parser.add_argument(
        "--ship_to", default=None, metavar="http://HOST:PORT",
        help="ship this process's metric deltas + run-log events to a "
        "fleet collector (obs/ship.py; dedicated thread, bounded "
        "buffer, retry backoff — training never blocks on the network)",
    )
    parser.add_argument(
        "--fleet_collector", nargs="?",
        const=f"127.0.0.1:{DEFAULT_FLEET_PORT}", default=None,
        metavar="HOST:PORT",
        help="start the fleet collector in this process (obs/fleet.py: "
        "cross-host metric/event merge, clock-aligned /trace + "
        "/runlog, global /fleet + /metrics with live|late|dead "
        "attribution).  Without --ship_to this process also ships to "
        "its own collector",
    )
    parser.add_argument(
        "--host_id", default=None,
        help="this process's identity in the fleet view (default: "
        "$SPARKNET_HOST_ID, else hostname:pid)",
    )
    parser.add_argument(
        "--flight_recorder", nargs="?",
        const=flight.DEFAULT_BUNDLE_PATH, default=None,
        metavar="BUNDLE.json",
        help="keep a bounded in-memory ring of recent spans/metric "
        "samples/health verdicts and dump it as a postmortem JSON "
        "bundle on crash, SIGTERM, feed stall, sentry halt, or chaos "
        "fault (fold it with tools/health_report.py)",
    )


class ObsRun:
    """Handle for one run's telemetry; ``close()`` is idempotent —
    stops the sidecar and writes the trace file.

    Deliberately NOT torn down: the training-metrics registry and the
    span->histogram observer.  They are process-wide and shared (the
    Prometheus model: counters are cumulative over the PROCESS's
    lifetime and survive run boundaries — ``rate()`` handles restarts;
    a later ``--obs`` run in the same process scrapes continuing
    totals, not zeros).  The residual cost of the observer once metrics
    have ever been enabled is one histogram observe per phase span."""

    def __init__(self, exporter=None, tracer=None, trace_out=None,
                 metrics: Optional[TrainingMetrics] = None,
                 recorder: Optional[FlightRecorder] = None,
                 profiler: Optional["RoundProfiler"] = None,
                 echo=None, profile_out: Optional[str] = None,
                 shipper: Optional["Shipper"] = None,
                 collector: Optional["FleetCollector"] = None,
                 sampler=None):
        self.exporter = exporter
        self.tracer = tracer
        self.trace_out = trace_out
        self.metrics = metrics
        self.recorder = recorder
        self.profiler = profiler
        self.profile_out = profile_out
        self.shipper = shipper
        self.collector = collector
        self.sampler = sampler
        self._echo = echo
        self._closed = False

    @property
    def address(self):
        return self.exporter.address if self.exporter is not None else None

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.profiler is not None:
            # print the round-anatomy summary BEFORE tearing telemetry
            # down — a --profile run with no tracer still gets its table
            if self._echo is not None and self.profiler.rounds_profiled:
                try:
                    self._echo(profile_summary_text(self.profiler))
                except Exception:  # noqa: BLE001 — teardown must not die
                    pass
            if self.profile_out:
                try:
                    with open(self.profile_out, "w") as f:
                        json.dump(self.profiler.summary(), f, indent=1)
                    if self._echo is not None:
                        self._echo(
                            "obs: profile summary -> %s" % self.profile_out
                        )
                except Exception:  # noqa: BLE001 — teardown must not die
                    pass
            profile.uninstall(self.profiler)
        if self.sampler is not None:
            # final sample + evaluator pass, then detach: a later run in
            # this process must not inherit this run's alert state
            self.sampler.stop()
            set_slo_evaluator(None)
        if self.exporter is not None:
            self.exporter.close()
        if self.tracer is not None:
            if get_tracer() is self.tracer:
                uninstall_tracer()
            if self.trace_out:
                self.tracer.save(self.trace_out)
            self.tracer.close()
        if self.recorder is not None:
            # clean close: detach WITHOUT dumping (bundles are
            # postmortems; any already-dumped one stays on disk)
            flight.uninstall(self.recorder)
        if self.shipper is not None:
            # detach the trace hook FIRST (no events enqueue during the
            # final flush), then stop — stop() ships the buffered tail
            from sparknet_tpu.obs import trace as _trace

            if _trace._ship is self.shipper:
                set_ship(None)
            self.shipper.stop()
        if self.collector is not None:
            # after the shipper's final flush, so a local collector
            # sees this run's tail before the listener goes down
            self.collector.close()
        # the run's divergence sentry is scoped to the run as well: a
        # later run in this process must not inherit a halted /healthz
        # or embed this run's verdicts in its flight bundles
        set_sentry(None)
        # ... and so is its membership controller (same scoping rule)
        set_membership(None)


def profile_summary_text(profiler) -> str:
    """Human one-screen rendering of a profiler summary (the --profile
    end-of-run table)."""
    s = profiler.summary()
    lines = ["profile: round anatomy over %d round(s)" % s["rounds"]]
    for name, p in s["phases"].items():
        lines.append(
            "  %-10s p50 %9.2f ms  p90 %9.2f ms  max %9.2f ms  [%s]"
            % (name, p["p50_ms"], p["p90_ms"], p["max_ms"], p["bound"])
        )
    for key, label in (
        ("hidden_frac_h2d", "H2D hidden fraction"),
        ("hidden_frac_comm", "collective hidden fraction"),
    ):
        if s.get(key):
            lines.append(
                "  %s: p50 %.3f (min %.3f)"
                % (label, s[key]["p50"], s[key]["min"])
            )
    if s.get("worker_skew"):
        lines.append(
            "  worker skew (max/median): p50 %.3f max %.3f; straggler "
            "rounds %d%s"
            % (
                s["worker_skew"]["p50"], s["worker_skew"]["max"],
                s["straggler_rounds"],
                " (last: worker %s @ round %s)"
                % (s["last_straggler_worker"], s["last_straggler_round"])
                if s["last_straggler_worker"] is not None else "",
            )
        )
    if s.get("achieved_flops_per_s"):
        mfu = s.get("mfu")
        lines.append(
            "  achieved %.2f GFLOP/s%s"
            % (
                s["achieved_flops_per_s"] / 1e9,
                "  (MFU %.2f%%)" % (100 * mfu) if mfu else
                "  (no bf16 peak on this platform: MFU n/a)",
            )
        )
    return "\n".join(lines)


def start(
    metrics: bool = False,
    port: int = DEFAULT_OBS_PORT,
    host: str = "127.0.0.1",
    trace_out: Optional[str] = None,
    flight_out: Optional[str] = None,
    profile_rounds: bool = False,
    profile_out: Optional[str] = None,
    ship_to: Optional[str] = None,
    fleet_collector: Optional[str] = None,
    host_id: Optional[str] = None,
    slo: bool = False,
    echo=print,
) -> ObsRun:
    """Turn telemetry on for this run: ``metrics=True`` starts the
    /metrics + /healthz sidecar; ``trace_out`` installs the tracer;
    ``flight_out`` installs the crash flight recorder (bundle path);
    ``profile_rounds`` installs the round-anatomy profiler;
    ``fleet_collector`` ("HOST:PORT") starts the cross-host fleet
    collector in this process; ``ship_to`` (a collector URL) ships this
    process's metric deltas + run-log events there — with a collector
    but no ``ship_to`` the process ships to its own collector.
    ``slo=True`` (implies metrics) arms the in-process TSDB sampler +
    burn-rate SLO evaluator, and the sidecar additionally serves
    /query, /slo and /signals.
    metrics/trace/profile/ship also enable the training metric series
    (spans feed the per-phase histogram; the shipper snapshots it).
    Returns an ``ObsRun`` to ``close()`` in the run's ``finally``."""
    profile_rounds = profile_rounds or bool(profile_out)
    metrics = metrics or slo
    if not any((metrics, trace_out, flight_out, profile_rounds, ship_to,
                fleet_collector)):
        return ObsRun()
    recorder = None
    if flight_out:
        recorder = flight.install(FlightRecorder(path=flight_out))
        if echo is not None:
            echo(f"obs: flight recorder armed -> {flight_out}")
    profiler = None
    if profile_rounds:
        profiler = profile.install(RoundProfiler())
        if echo is not None:
            echo(
                "obs: round-anatomy profiler on (phase breakdown, "
                "hidden fractions, straggler verdicts)"
            )
    collector = None
    if fleet_collector:
        from sparknet_tpu.obs.fleet import parse_hostport

        chost, cport = parse_hostport(fleet_collector)
        collector = FleetCollector(host=chost, port=cport).start()
        if echo is not None:
            echo(
                "obs: fleet collector on %s/fleet (merged /metrics, "
                "clock-aligned /trace + /runlog)" % collector.url
            )
        if not ship_to:
            ship_to = collector.url  # one flag = a self-shipping fleet
    if not any((metrics, trace_out, profile_rounds, ship_to)):
        return ObsRun(recorder=recorder, collector=collector, echo=echo)
    tm = enable_training_metrics()
    sampler = None
    evaluator = None
    tsdb = None
    if slo:
        from sparknet_tpu.obs.slo import SLOEvaluator, TsdbSampler
        from sparknet_tpu.obs.tsdb import TSDB

        tsdb = TSDB(registry=tm.registry)
        evaluator = SLOEvaluator(
            tsdb, registry=tm.registry, live_registry=tm.registry,
            host=host_id,
        )
        set_slo_evaluator(evaluator)
        sampler = TsdbSampler(
            tsdb, tm.registry, evaluator=evaluator,
            host=host_id or "local",
        ).start()
        if echo is not None:
            echo(
                "obs: SLO plane armed — TSDB sampler + burn-rate "
                "evaluator (/query, /slo, /signals)"
            )
    exporter = None
    if metrics:
        exporter = ObsExporter(
            tm.registry, host=host, port=port, health_fn=health_reason,
            tsdb=tsdb, slo=evaluator,
        ).start()
        if echo is not None:
            h, p = exporter.address
            echo(f"obs: serving /metrics and /healthz on http://{h}:{p}")
    tracer = None
    if trace_out:
        tracer = install_tracer(Tracer(jsonl_path=jsonl_path_for(trace_out)))
        if echo is not None:
            echo(
                f"obs: tracing round phases -> {trace_out} "
                f"(+ {jsonl_path_for(trace_out)})"
            )
    shipper = None
    if ship_to:
        shipper = Shipper(
            ship_to, host=host_id, registry=tm.registry
        ).start()
        set_ship(shipper)
        if echo is not None:
            echo(
                "obs: shipping metric deltas + run-log events to "
                "%s as host %r" % (shipper.url, shipper.host)
            )
    return ObsRun(exporter, tracer, trace_out, tm, recorder, profiler, echo,
                  profile_out=profile_out, shipper=shipper,
                  collector=collector, sampler=sampler)


def start_from_args(args, echo=print) -> ObsRun:
    return start(
        metrics=getattr(args, "obs", False),
        port=getattr(args, "obs_port", DEFAULT_OBS_PORT),
        trace_out=getattr(args, "trace_out", None),
        flight_out=getattr(args, "flight_recorder", None),
        profile_rounds=getattr(args, "profile", False),
        profile_out=getattr(args, "profile_out", None),
        ship_to=getattr(args, "ship_to", None),
        fleet_collector=getattr(args, "fleet_collector", None),
        host_id=getattr(args, "host_id", None),
        slo=getattr(args, "slo", False),
        echo=echo,
    )
