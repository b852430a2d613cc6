"""Training-health sentry (``obs/health.py``), flight recorder
(``obs/flight.py``) and their wiring: audit numerics on adversarial
inputs, policy actions, /healthz sentry state, bundle dump/fold, and the
bit-identity contract of the in-graph audit."""

import json
import math
import os
import urllib.request

import numpy as np
import pytest

from sparknet_tpu import obs
from sparknet_tpu.obs import flight, health
from sparknet_tpu.obs.exporter import ObsExporter
from sparknet_tpu.obs.health import HealthSentry, SentryHalt
from sparknet_tpu.obs.trace import _NULL_SPAN


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Telemetry fully off before and after every test — the tracer,
    training metrics, sentry and flight recorder are process-wide."""
    obs.uninstall_tracer()
    obs._reset_training_metrics_for_tests()
    yield
    t = obs.uninstall_tracer()
    if t is not None:
        t.close()
    obs._reset_training_metrics_for_tests()


# ---------------------------------------------------------------------------
# audit numerics on adversarial inputs (pure jnp)


def _run_audit(grads, params, new_params, loss, grad_norm):
    import jax

    stats = health.audit_iteration(grads, params, new_params, loss, grad_norm)
    return jax.device_get(stats)


def test_audit_all_zero_grads_no_division_poison():
    """All-zero grads / all-zero params: the update/param ratio must be
    an exact finite 0, never NaN from 0/0."""
    import jax.numpy as jnp

    z = {"conv1": [jnp.zeros((3, 3))], "fc": [jnp.zeros((4,))]}
    stats = _run_audit(z, z, z, jnp.asarray(0.5), jnp.asarray(0.0))
    for group in ("conv1", "fc"):
        assert float(stats["update_ratio"][group]) == 0.0
        assert float(stats["param_norm"][group]) == 0.0
    assert float(stats["grad_norm"]) == 0.0
    assert int(stats["nonfinite_grads"]) == 0
    assert int(stats["nonfinite_params"]) == 0
    assert int(stats["nonfinite_loss"]) == 0


def test_audit_counts_fp32_overflow_to_inf():
    """An fp32 value pushed past float max overflows to Inf and must be
    counted (grads AND params), as must a NaN loss."""
    import jax.numpy as jnp

    big = jnp.asarray(3e38, jnp.float32) * 2.0  # -> inf in fp32
    assert not bool(jnp.isfinite(big))
    g = {"fc": [jnp.asarray([1.0, float(big)], jnp.float32)]}
    p_old = {"fc": [jnp.asarray([1.0, 1.0], jnp.float32)]}
    p_new = {"fc": [jnp.asarray([1.0, float(big)], jnp.float32)]}
    stats = _run_audit(
        g, p_old, p_new, jnp.asarray(float("nan")), jnp.asarray(float(big))
    )
    assert int(stats["nonfinite_grads"]) == 1
    assert int(stats["nonfinite_params"]) == 1
    assert int(stats["nonfinite_loss"]) == 1
    assert not math.isfinite(float(stats["grad_norm"]))


def test_nonfinite_count_empty_tree():
    import jax

    assert int(jax.device_get(health.nonfinite_count({}))) == 0


# ---------------------------------------------------------------------------
# host sentry: stats fixtures (observe() accepts host numpy trees)


def _stats(tau=2, workers=None, nonfinite_grads=0, nonfinite_params=0,
           masked=None, grad_norm=1.0):
    lead = () if workers is None else (workers,)
    full = lead + (tau,)

    def fill(v, dtype=np.float32):
        return np.full(full, v, dtype)

    s = {
        "grad_norm": fill(grad_norm),
        "nonfinite_grads": np.zeros(full, np.int32),
        "nonfinite_params": np.zeros(full, np.int32),
        "nonfinite_loss": np.zeros(full, np.int32),
        "param_norm": {"conv1": fill(3.0)},
        "update_ratio": {"conv1": fill(0.01)},
    }
    if workers is not None:
        # poison worker 1 by default when counts are requested
        s["nonfinite_grads"][-1] = nonfinite_grads
        s["nonfinite_params"][-1] = nonfinite_params
        if masked is not None:
            s["masked"] = np.asarray(masked, np.float32)
    else:
        s["nonfinite_grads"][:] = nonfinite_grads
        s["nonfinite_params"][:] = nonfinite_params
    return s


def test_observe_healthy_round_is_ok():
    s = HealthSentry(policy="warn")
    v = s.observe(0, np.asarray([1.0, 0.9]), _stats())
    assert v.ok and v.action == "none"
    assert s.state_dict()["last_anomaly_round"] is None


def test_observe_flags_nonfinite_and_attributes_worker():
    s = HealthSentry(policy="warn")
    v = s.observe(
        3,
        np.asarray([[1.0, 0.9], [np.nan, np.nan]]),
        _stats(workers=2, nonfinite_grads=7, masked=[0.0, 1.0]),
    )
    assert not v.ok and "nonfinite" in v.reasons
    assert v.per_worker_nonfinite == [0, 14]  # 7 per tau slot x2
    assert v.masked_workers == [1]
    assert s.last_anomaly_round == 3
    sd = s.state_dict()
    assert sd["anomalies"] == 1 and sd["last_anomaly_round"] == 3


def test_spike_boundary_exactly_at_threshold_does_not_flag():
    """A z-score EXACTLY at the threshold is not a spike — only
    strictly above flags (the documented boundary)."""
    s = HealthSentry(z_threshold=4.0)
    assert s._spike(4.0) is False
    assert s._spike(math.nextafter(4.0, 5.0)) is True
    assert s._spike(3.999) is False


def test_loss_spike_flags_after_warmup():
    s = HealthSentry(policy="warn", z_threshold=4.0, warmup_rounds=3)
    for r in range(6):
        v = s.observe(r, np.asarray([1.0]), _stats())
        assert v.ok, r
    v = s.observe(6, np.asarray([30.0]), _stats())
    assert "loss_spike" in v.reasons
    # rounds_since_anomaly tracks forward from the flagged round
    s.observe(7, np.asarray([1.0]), _stats())
    assert s.state_dict()["rounds_since_anomaly"] == 1


def test_rounds_since_anomaly_uses_absolute_round_indices():
    """Resumed runs pass ABSOLUTE round indices (imagenet_run_db_app
    --resume at start_round=100): rounds_since_anomaly must track the
    round axis, not the sentry's observation count."""
    s = HealthSentry(policy="warn", warmup_rounds=0)
    for r in range(100, 103):
        s.observe(r, np.asarray([1.0]), _stats())
    s.observe(103, np.asarray([np.nan]), _stats(nonfinite_grads=1))
    assert s.state_dict()["rounds_since_anomaly"] == 0
    s.observe(104, np.asarray([1.0]), _stats())
    s.observe(105, np.asarray([1.0]), _stats())
    assert s.state_dict()["last_anomaly_round"] == 103
    assert s.state_dict()["rounds_since_anomaly"] == 2


def test_nonfinite_loss_not_double_counted():
    """The audited step counts window losses in-graph AND observe()
    sees the same losses host-side — the verdict must report the count
    once, not the sum of both views."""
    s = HealthSentry(policy="warn")
    stats = _stats()
    stats["nonfinite_loss"][:] = 1  # in-graph: 1 per tau slot = 2
    v = s.observe(0, np.asarray([np.nan, np.nan]), stats)
    assert v.nonfinite_loss == 2


def test_observe_tolerates_partial_stats_tree():
    """A stub/partial stats tree missing series (no nonfinite_loss, no
    grad_norm) must not KeyError — the host-side loss re-count covers
    the missing in-graph count, exactly as the code comment promises."""
    s = HealthSentry(policy="warn")
    v = s.observe(
        0,
        np.asarray([np.nan]),
        {"nonfinite_grads": np.zeros((2,), np.int32)},
    )
    assert v.nonfinite_loss == 1 and "nonfinite" in v.reasons
    assert math.isnan(v.grad_norm)


def test_flight_dump_survives_non_json_ring_entries(tmp_path):
    """dump() runs inside the crash excepthook / SIGTERM handler: a
    non-JSON value smuggled into the ring (a numpy scalar in span args)
    must degrade to its repr, not blow up the postmortem."""
    rec = flight.FlightRecorder(path=str(tmp_path / "b.json"))
    rec.record_event({"kind": "instant", "name": "x",
                     "args": {"v": np.float32(1.5)}})
    out = rec.dump("test")
    b = json.load(open(out))
    assert b["reason"] == "test" and len(b["events"]) == 1


def test_obs_run_close_clears_global_sentry():
    """ObsRun.close() scopes the sentry to its run: a later run in the
    same process must not inherit a halted /healthz or embed stale
    verdicts in its flight bundles."""
    s = HealthSentry(policy="halt")
    s.halted = True
    obs.set_sentry(s)
    assert obs.sentry_state() is not None
    obs.ObsRun().close()
    assert obs.sentry_state() is None


class _StubStepper:
    """A Solver/AllReduceTrainer stand-in: returns scripted
    (state, losses, stats) triples per call."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def step(self, state, batches, rng=None):
        out = self.script[min(self.calls, len(self.script) - 1)]
        self.calls += 1
        return (out[0], out[1], out[2])


def test_halt_policy_raises_and_flips_healthz():
    s = HealthSentry(policy="halt")
    obs.set_sentry(s)
    stepper = _StubStepper([
        ("S1", np.asarray([np.nan]), _stats(nonfinite_grads=5)),
    ])
    with pytest.raises(SentryHalt):
        s.guarded_step(stepper, "S0", {}, round_index=0)
    assert s.halted
    assert obs.sentry_state()["halted"] is True
    assert (obs.health_reason() or "").startswith("sentry_halt")


def test_rollback_policy_restores_and_cools_down():
    restored = []

    def restore():
        restored.append(1)
        return "RESTORED", "/tmp/snap_iter_4.solverstate.npz"

    s = HealthSentry(policy="rollback", restore_fn=restore,
                     cooldown_rounds=2)
    stepper = _StubStepper([
        ("S1", np.asarray([np.nan]), _stats(nonfinite_grads=3)),
        ("S2", np.asarray([1.0]), _stats()),
    ])
    state, _ = s.guarded_step(stepper, "S0", {}, round_index=0)
    assert state == "RESTORED" and restored == [1]
    assert s.rollbacks == 1 and not s.halted
    # healthy rounds continue normally after the rollback
    state, _ = s.guarded_step(stepper, state, {}, round_index=1)
    assert state == "S2"


def test_rollback_without_restore_point_halts():
    s = HealthSentry(policy="rollback", restore_fn=None)
    stepper = _StubStepper([
        ("S1", np.asarray([np.nan]), _stats(nonfinite_params=1)),
    ])
    with pytest.raises(SentryHalt):
        s.guarded_step(stepper, "S0", {}, round_index=0)
    assert s.halted


def test_rollback_budget_exhaustion_escalates_to_halt():
    s = HealthSentry(
        policy="rollback", max_rollbacks=1, cooldown_rounds=0,
        restore_fn=lambda: ("R", "snap"),
    )
    bad = ("S", np.asarray([np.nan]), _stats(nonfinite_grads=1))
    stepper = _StubStepper([bad, bad])
    s.guarded_step(stepper, "S0", {}, round_index=0)
    assert s.rollbacks == 1
    with pytest.raises(SentryHalt):
        s.guarded_step(stepper, "R", {}, round_index=1)


def test_single_masked_worker_is_absorbed_not_escalated():
    """The in-graph mask already excluded the poisoned worker: even
    under policy=halt the sentry records the anomaly but does NOT stop
    the run (escalation is for poison that reached the average)."""

    class _StubTrainer:
        def round(self, state, batches, rng=None, live_mask=None,
                  round_index=None):
            return (
                "NEXT",
                np.asarray([[1.0], [np.nan]]),
                _stats(
                    workers=2, tau=1, nonfinite_grads=9,
                    masked=[0.0, 1.0],
                ),
            )

    s = HealthSentry(policy="halt")
    state, _ = s.guarded_round(_StubTrainer(), "S0", {}, round_index=0)
    assert state == "NEXT" and not s.halted
    assert s.verdicts[-1].action == "masked"
    assert s.anomalies == 1


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_ring_receives_spans_and_instants_without_tracer():
    rec = flight.install(flight.FlightRecorder(capacity=8))
    try:
        assert obs.span("x") is not _NULL_SPAN  # armed: spans record
        with obs.span("execute", round=1):
            pass
        obs.instant("prefetch_stall", cat="fault", msg="m")
        counts = rec.counts()
        assert counts["events"] == 2
        # bounded: the ring keeps only the newest `capacity` records
        for i in range(20):
            obs.instant("tick", i=i)
        assert rec.counts()["events"] == 8
    finally:
        flight.uninstall(rec)
    assert obs.span("x") is _NULL_SPAN  # fully off again


def test_flight_dump_bundle_schema_and_fault_trigger(tmp_path):
    path = str(tmp_path / "bundle.json")
    rec = flight.install(flight.FlightRecorder(path=path))
    try:
        with obs.span("average", round=0):
            pass
        flight.record_verdict({"round": 0, "ok": True, "nonfinite": 0})
        flight.record_sample("loss", 1.25, round=0)
        # obs.fault() is a dump trigger (chaos faults are postmortem
        # moments)
        obs.fault("nan_injection", round=3, workers=[1])
        assert os.path.exists(path)
        bundle = flight.load_bundle(path)
        assert bundle["reason"] == "fault_nan_injection"
        assert bundle["extra"] == {"round": 3, "workers": [1]}
        assert bundle["dump_index"] == 1
        assert any(e["name"] == "average" for e in bundle["events"])
        assert bundle["verdicts"] == [
            {"round": 0, "ok": True, "nonfinite": 0}
        ]
        assert bundle["samples"][0]["name"] == "loss"
        # a second dump overwrites (newest wins), bumping the index
        rec.dump("sentry_halt")
        assert flight.load_bundle(path)["dump_index"] == 2
    finally:
        flight.uninstall(rec)


def test_flight_dump_on_uncaught_exception(tmp_path):
    import subprocess
    import sys

    path = str(tmp_path / "crash.json")
    code = (
        "from sparknet_tpu.obs import flight\n"
        "rec = flight.install(flight.FlightRecorder(path=%r))\n"
        "from sparknet_tpu import obs\n"
        "obs.instant('last_thing', i=7)\n"
        "raise RuntimeError('boom')\n" % path
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0 and "boom" in out.stderr
    bundle = flight.load_bundle(path)
    assert bundle["reason"] == "crash:RuntimeError"
    assert "boom" in bundle["extra"]["exception"]
    assert any(e["name"] == "last_thing" for e in bundle["events"])


def test_prefetch_stall_dumps_flight_bundle(tmp_path):
    import time as _time

    from sparknet_tpu.data.prefetch import Prefetcher, PrefetchStall

    path = str(tmp_path / "stall.json")
    rec = flight.install(flight.FlightRecorder(path=path))
    try:
        pf = Prefetcher(
            lambda: _time.sleep(30) or {}, device_put=False,
            stall_timeout_s=0.2,
        )
        with pytest.raises(PrefetchStall):
            next(pf)
        pf.stop(timeout=0.1)
        assert flight.load_bundle(path)["reason"] == "prefetch_stall"
    finally:
        flight.uninstall(rec)


def test_sigterm_dumps_flight_bundle_via_signal_handler(tmp_path):
    import signal as _sig

    from sparknet_tpu.utils.signals import SignalHandler, SolverAction

    path = str(tmp_path / "term.json")
    rec = flight.install(flight.FlightRecorder(path=path))
    try:
        obs.instant("about_to_die")
        with SignalHandler(sigterm_effect=SolverAction.STOP) as h:
            os.kill(os.getpid(), _sig.SIGTERM)
            assert h.get_action() == SolverAction.STOP
        bundle = flight.load_bundle(path)
        assert bundle["reason"] == "signal_SIGTERM"
        assert any(e["name"] == "about_to_die" for e in bundle["events"])
    finally:
        flight.uninstall(rec)


# ---------------------------------------------------------------------------
# /healthz sentry surface + metrics series


def _get(url):
    return urllib.request.urlopen(url, timeout=5)


def test_healthz_exports_sentry_state_and_503_on_halt():
    tm = obs.enable_training_metrics()
    s = HealthSentry(policy="halt")
    obs.set_sentry(s)
    ex = ObsExporter(
        tm.registry, port=0, health_fn=obs.health_reason
    ).start()
    try:
        h, p = ex.address
        ok = _get(f"http://{h}:{p}/healthz")
        body = json.loads(ok.read())
        assert ok.status == 200 and body["status"] == "ok"
        assert body["sentry"]["policy"] == "halt"
        assert body["sentry"]["halted"] is False
        # a halted sentry flips /healthz to 503 with the sentry block
        stepper = _StubStepper([
            ("S", np.asarray([np.nan]), _stats(nonfinite_grads=2)),
        ])
        with pytest.raises(SentryHalt):
            s.guarded_step(stepper, "S0", {}, round_index=5)
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(f"http://{h}:{p}/healthz")
        assert e.value.code == 503
        body = json.loads(e.value.read())
        assert body["sentry"]["halted"] is True
        assert body["sentry"]["last_anomaly_round"] == 5
        assert "sentry_halt" in body["reason"]
    finally:
        ex.close()


def test_sentry_feeds_issue_named_metric_series():
    tm = obs.enable_training_metrics()
    s = HealthSentry(policy="warn")
    s.observe(0, np.asarray([1.0]), _stats(grad_norm=2.5))
    s.observe(1, np.asarray([np.nan]), _stats(nonfinite_grads=4))
    text = tm.registry.render()
    assert "sparknet_grad_norm" in text
    # 4 per tau slot x2 grads + the NaN round-loss itself
    assert "sparknet_nonfinite_total 9" in text
    assert 'sparknet_update_ratio{group="conv1"}' in text
    assert 'sparknet_health_anomalies_total{kind="nonfinite"} 1' in text


def test_health_cli_args_parse():
    import argparse

    p = argparse.ArgumentParser()
    obs.add_cli_args(p)
    a = p.parse_args([])
    assert a.health is None and a.flight_recorder is None
    a = p.parse_args(["--health"])
    assert a.health == "warn"
    a = p.parse_args(["--health", "rollback", "--flight_recorder"])
    assert a.health == "rollback"
    assert a.flight_recorder == flight.DEFAULT_BUNDLE_PATH
    a = p.parse_args(["--health", "warn", "--health_policy", "halt",
                      "--flight_recorder", "b.json"])
    assert a.health_policy == "halt" and a.flight_recorder == "b.json"


# ---------------------------------------------------------------------------
# tools/health_report.py folding


def _load_health_report():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_health_report", os.path.join(repo, "tools", "health_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_health_report_folds_bundle_and_names_first_poisoned(tmp_path):
    hr = _load_health_report()
    path = str(tmp_path / "b.json")
    rec = flight.FlightRecorder(path=path)
    for r in range(5):
        bad = r == 3
        rec.record_verdict({
            "round": r, "loss": float("nan") if bad else 1.0,
            "zscore": 0.0, "grad_norm": 1.0,
            "nonfinite": 10 if bad else 0, "ok": not bad,
            "reasons": ["nonfinite"] if bad else [],
            "masked_workers": [], "action": "rollback" if bad else "none",
        })
    rec.dump("sentry_rollback")
    rep = hr.fold(hr.load_records(path))
    assert rep["rounds_observed"] == 5
    assert rep["first_poisoned_round"] == 3
    assert rep["anomalies"] == 1
    assert rep["actions"] == {"rollback": 1}
    table = hr.format_report(rep)
    assert "first poisoned round: 3" in table


def test_health_report_folds_jsonl_run_log(tmp_path):
    hr = _load_health_report()
    path = str(tmp_path / "run.trace.jsonl")
    with open(path, "w") as f:
        for r in range(3):
            f.write(json.dumps({
                "kind": "instant", "name": "health", "cat": "health",
                "ts_s": r * 1.0, "thread": "MainThread",
                "args": {"round": r, "loss": 1.0, "nonfinite": 0,
                         "ok": r != 2, "reasons": [] if r != 2 else
                         ["loss_spike"], "action": "none"},
            }) + "\n")
            f.write(json.dumps({
                "kind": "span", "name": "execute", "cat": "phase",
                "ts_s": r * 1.0, "dur_ms": 5.0, "thread": "MainThread",
            }) + "\n")
    rep = hr.fold(hr.load_records(path))
    assert rep["rounds_observed"] == 3
    # no non-finite round: the first FLAGGED round is the answer
    assert rep["first_poisoned_round"] == 2


# ---------------------------------------------------------------------------
# the bit-identity contract + in-graph masking, on a real trained net


def test_audit_bit_identity_and_in_graph_mask():
    """The tentpole contract, end to end on cifar10_quick over the
    virtual dp mesh: (1) the full TrainState after audited rounds is
    BIT-IDENTICAL to the unaudited trajectory (stats are pure
    readouts); (2) a single worker's NaN-poisoned batch is masked out
    of the average IN-GRAPH — the surviving weights stay finite and the
    stats name the worker."""
    import jax

    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.data import CifarLoader
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        make_mesh,
        shard_leading,
    )
    from sparknet_tpu.solver import Solver

    workers, tau, batch, rounds = 2, 1, 4, 2
    import tempfile

    data_dir = os.path.join(tempfile.mkdtemp(prefix="health_bit_"), "d")
    CifarLoader.write_synthetic(data_dir, num_train=32, num_test=8, seed=5)
    xs, ys = CifarLoader(data_dir).minibatches(batch, train=True)

    def window(r):
        data = np.stack(
            [np.stack([xs[(r * workers + w) % len(xs)]])
             for w in range(workers)]
        )
        label = np.stack(
            [np.stack([ys[(r * workers + w) % len(ys)]])
             for w in range(workers)]
        )
        return {"data": data, "label": label}

    netp = cfg.replace_data_layers(
        models.load_model("cifar10_quick"),
        [(batch, 3, 32, 32), (batch,)],
        [(batch, 3, 32, 32), (batch,)],
    )
    mesh = make_mesh({"dp": workers}, devices=jax.devices()[:workers])

    def build(audit):
        solver = Solver(
            models.load_model_solver("cifar10_quick"), net_param=netp,
            audit=audit,
        )
        return ParameterAveragingTrainer(solver, mesh)

    def run(trainer, poison_round=None, n_rounds=rounds):
        state = trainer.init_state(seed=0)
        stats = None
        for r in range(n_rounds):
            w = window(r)
            if poison_round == r:
                w["data"][1] = np.nan  # worker 1's batch only
            out = trainer.round(state, shard_leading(w, mesh))
            state = out[0]
            if trainer.audit:
                stats = out[2]
        return jax.device_get(state), stats

    t_off, t_on = build(False), build(True)
    st_off, _ = run(t_off)
    st_on, stats = run(t_on)
    la = jax.tree_util.tree_leaves(st_off)
    lb = jax.tree_util.tree_leaves(st_on)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # healthy run: audit reports all-finite, nothing masked
    host = jax.device_get(stats)
    assert int(np.sum(host["nonfinite_grads"])) == 0
    assert np.all(np.asarray(host["masked"]) == 0.0)

    # poisoned worker 1 at the last round: masked in-graph, average
    # stays finite, per-worker stats attribute the poison (reuses the
    # already-compiled audited program — data change only)
    st_p, stats_p = run(t_on, poison_round=rounds - 1)
    host = jax.device_get(stats_p)
    nf = (
        np.asarray(host["nonfinite_grads"])
        + np.asarray(host["nonfinite_params"])
    ).sum(axis=1)
    assert nf[0] == 0 and nf[1] > 0
    assert np.asarray(host["masked"]).tolist() == [0.0, 1.0]
    for leaf in jax.tree_util.tree_leaves(st_p.params):
        assert np.isfinite(np.asarray(leaf)).all()

    # rejoin contract: a worker masked at round r trains healthy at
    # r+1 — its params AND momentum history were replaced (history
    # zeroed in-graph), so one bad batch can't re-poison it from
    # momentum and leave it masked forever
    st_rj, stats_rj = run(t_on, poison_round=0, n_rounds=rounds)
    host = jax.device_get(stats_rj)  # stats of the LAST (healthy) round
    assert np.asarray(host["masked"]).tolist() == [0.0, 0.0]
    nf = (
        np.asarray(host["nonfinite_grads"])
        + np.asarray(host["nonfinite_params"])
    ).sum(axis=1)
    assert nf.tolist() == [0, 0]
    for leaf in jax.tree_util.tree_leaves(st_rj):
        assert np.isfinite(np.asarray(leaf)).all()


def test_all_worker_nan_is_detected_in_its_round_and_rolled_back(tmp_path):
    """The sentry across the live loop: the chaos feed poisons EVERY dp
    worker's batch at one round (so the in-graph mask cannot absorb it),
    the ``rollback`` policy restores the newest verified snapshot and the
    run goes on inside the no-fault run's loss band, and the flight
    bundle dumped at the rollback folds to a report that names the
    round."""
    import dataclasses

    import jax

    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.data import CifarLoader
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.obs.health import make_restore_fn
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        first_worker,
        make_mesh,
    )
    from sparknet_tpu.runtime import chaos
    from sparknet_tpu.solver import Solver

    workers, tau, batch, rounds, nan_round = 2, 1, 4, 6, 3
    data_dir = str(tmp_path / "data")
    CifarLoader.write_synthetic(data_dir, num_train=64, num_test=8, seed=10)
    xs, ys = CifarLoader(data_dir).minibatches(batch, train=True)
    netp = cfg.replace_data_layers(
        models.load_model("cifar10_quick"),
        [(batch, 3, 32, 32), (batch,)],
        [(batch, 3, 32, 32), (batch,)],
    )
    mesh = make_mesh({"dp": workers}, devices=jax.devices()[:workers])
    solver = Solver(
        models.load_model_solver("cifar10_quick"), net_param=netp, audit=True
    )
    trainer = ParameterAveragingTrainer(solver, mesh)
    plan = dataclasses.replace(
        chaos.FaultPlan.default(),
        seed=10, workers=workers, rounds=rounds, tau=tau, batch=batch,
        storage_faults=(), stall_rounds=(), preempt_round=None,
        corrupt_newest=False, dead_worker=None, straggler_round=None,
        nan_round=nan_round, nan_workers=tuple(range(workers)),
    )

    def run(p, sentry, prefix):
        counters = dict.fromkeys(
            ("storage_injected", "storage_survived", "stalls_injected",
             "stalls_survived"), 0,
        )
        sentry.restore_fn = make_restore_fn(solver, prefix, trainer=trainer)
        feed = chaos._Feed(p, xs, ys, counters, [], mesh)
        state = trainer.init_state(seed=0)
        try:
            for r in range(p.rounds):
                state, losses = sentry.guarded_round(
                    trainer, state, feed.next_round(r), round_index=r
                )
                if (r + 1) % 2 == 0:
                    checkpoint.snapshot(
                        solver, first_worker(jax.device_get(state)), prefix
                    )
        finally:
            feed.close()
        return float(np.mean(np.asarray(jax.device_get(losses))))

    clean = HealthSentry(policy="rollback", echo=None)
    no_fault_loss = run(plan.no_fault_view(), clean, str(tmp_path / "clean"))
    assert clean.rollbacks == 0 and clean.last_anomaly_round is None

    bundle = str(tmp_path / "flight_postmortem.json")
    recorder = flight.install(flight.FlightRecorder(path=bundle))
    sentry = HealthSentry(policy="rollback", echo=None)
    obs.set_sentry(sentry)
    try:
        final_loss = run(plan, sentry, str(tmp_path / "faulted"))
    finally:
        flight.uninstall(recorder)
        obs.set_sentry(None)
    assert sentry.last_anomaly_round == nan_round
    assert sentry.rollbacks >= 1
    assert math.isfinite(final_loss)
    assert abs(final_loss - no_fault_loss) <= max(0.25, 0.25 * abs(no_fault_loss))
    hr = _load_health_report()
    rep = hr.fold(hr.load_records(bundle))
    assert rep["first_poisoned_round"] == nan_round
    assert flight.load_bundle(bundle)["reason"].startswith("sentry")
