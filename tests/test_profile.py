"""Round-anatomy profiler (``sparknet_tpu/obs/profile.py``): span
folding, hidden-fraction accounting, per-worker straggler verdicts, the
execute probe, and the metrics/healthz export surface."""

import threading
import time

import numpy as np
import pytest

from sparknet_tpu import obs
from sparknet_tpu.obs import profile as profile_mod


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Profiler + metrics are process-wide module state."""
    obs.uninstall_tracer()
    obs._reset_training_metrics_for_tests()
    yield
    t = obs.uninstall_tracer()
    if t is not None:
        t.close()
    obs._reset_training_metrics_for_tests()


# ---------------------------------------------------------------------------
# module hooks are no-ops until installed


def test_hooks_are_noops_when_uninstalled():
    assert profile_mod.active() is None
    profile_mod.note_consumed_round(3)  # must not raise
    profile_mod.note_worker_phase(0, "assemble", [0.1, 0.2])
    profile_mod.observe_round_if_active(None)
    with profile_mod.worker_timer(0, 1, 4):
        pass
    assert profile_mod.worker_timer(0, 1, 4) is profile_mod._NULL_TIMER
    assert profile_mod.state() is None
    # timed_worker_windows degrades to the plain draw
    out = profile_mod.timed_worker_windows(0, [lambda: 1, lambda: 2])
    assert out == [1, 2]


def test_install_uninstall_flips_span_observer():
    from sparknet_tpu.obs import trace as trace_mod

    p = profile_mod.install(profile_mod.RoundProfiler())
    try:
        assert profile_mod.active() is p
        assert trace_mod._span_observer == p.on_span
        # span() must no longer return the shared no-op
        assert obs.span("execute") is not trace_mod._NULL_SPAN
    finally:
        profile_mod.uninstall(p)
    assert profile_mod.active() is None
    assert trace_mod._span_observer is None
    assert obs.span("execute") is trace_mod._NULL_SPAN


# ---------------------------------------------------------------------------
# span folding + hidden fractions (deterministic synthetic intervals)


def _consumer(p, t0, t1, thread="consumer"):
    p.on_span("execute", "phase", t0, t1, thread, None)


def _producer(p, r, t0, t1, name="assemble", nbytes=None):
    args = {"round": r}
    if nbytes is not None:
        args["nbytes"] = nbytes
    p.on_span(name, "phase", t0, t1, "prefetch-producer", args)


def test_hidden_fraction_from_busy_window_overlap():
    p = profile_mod.RoundProfiler(probe_workers=False)
    # round 0: consumer busy [0, 1]; its batch was produced in the open
    _producer(p, 0, -0.5, -0.2)
    p.note_consumed_round(0)
    _consumer(p, 0.0, 1.0)
    rec0 = p.observe_round()
    assert rec0["round"] == 0
    assert rec0["hidden_frac_h2d"] == 0.0  # produced before any busy
    # round 1's batch was produced fully inside round 0's busy window
    _producer(p, 1, 0.2, 0.5)
    _producer(p, 1, 0.5, 0.7, name="h2d", nbytes=4096)
    p.note_consumed_round(1)
    _consumer(p, 1.1, 2.0)
    rec1 = p.observe_round()
    assert rec1["round"] == 1
    assert rec1["hidden_frac_h2d"] == pytest.approx(1.0)
    assert rec1["h2d_bytes"] == 4096
    # round 2's production HALF overlapped round 1's busy window
    _producer(p, 2, 1.5, 2.5)
    p.note_consumed_round(2)
    _consumer(p, 2.6, 3.0)
    rec2 = p.observe_round()
    assert rec2["hidden_frac_h2d"] == pytest.approx(0.5)
    # a round with no producer spans reads None, not 0 (serial trainers)
    p.note_consumed_round(3)
    _consumer(p, 3.1, 3.5)
    assert p.observe_round()["hidden_frac_h2d"] is None
    s = p.summary()
    assert s["rounds"] == 4
    assert s["hidden_frac_h2d"]["min"] == 0.0
    assert s["hidden_frac_h2d"]["max"] == 1.0


def test_comm_hidden_fraction_distinguishes_threads():
    p = profile_mod.RoundProfiler(probe_workers=False)
    # consumer round 0 busy [0, 1]
    p.note_consumed_round(0)
    _consumer(p, 0.0, 1.0)
    p.observe_round()
    # round 1: overlapped chunks ride a comm thread INSIDE round 1's
    # busy window; a barriered chunk lands on the consumer thread
    p.note_consumed_round(1)
    _consumer(p, 1.1, 2.0)
    p.on_span("allreduce", "phase", 1.2, 1.5, "comm-averaging",
              {"chunk": 0, "nbytes": 100})
    p.on_span("allreduce", "phase", 1.5, 1.8, "comm-averaging",
              {"chunk": 1, "nbytes": 100})
    rec = p.observe_round()
    assert rec["hidden_frac_comm"] == pytest.approx(1.0)
    assert rec["comm_chunk_bytes"] == 200
    # barriered: allreduce on the consumer thread = visible by definition
    p.note_consumed_round(2)
    _consumer(p, 2.1, 3.0)
    p.on_span("allreduce", "phase", 2.2, 2.6, "consumer", {"chunk": 0})
    rec2 = p.observe_round()
    assert rec2["hidden_frac_comm"] == 0.0
    # no comm spans at all -> None
    p.note_consumed_round(3)
    _consumer(p, 3.1, 3.4)
    assert p.observe_round()["hidden_frac_comm"] is None


def test_phase_breakdown_accumulates_per_round():
    p = profile_mod.RoundProfiler(probe_workers=False)
    p.note_consumed_round(0)
    p.on_span("average", "phase", 0.0, 1.0, "consumer", None)
    p.on_span("execute", "phase", 0.1, 0.6, "consumer", None)
    p.on_span("execute", "phase", 0.6, 0.9, "consumer", None)
    p.on_span("quantize", "phase", 0.9, 0.95, "consumer",
              {"compress": "int8"})
    rec = p.observe_round()
    assert rec["phases_ms"]["average"] == pytest.approx(1000.0)
    assert rec["phases_ms"]["execute"] == pytest.approx(800.0)
    assert rec["phases_ms"]["quantize"] == pytest.approx(50.0)
    s = p.summary()
    assert s["phases"]["execute"]["bound"] == "compute"
    assert s["phases"]["quantize"]["bound"] == "bandwidth"


# ---------------------------------------------------------------------------
# per-worker attribution + straggler verdict


def test_straggler_verdict_per_phase_not_washed_out():
    """A 0.3s assembly straggler must be attributed even when a
    uniformly-large probe phase (~2s/worker) dominates the totals."""
    p = profile_mod.RoundProfiler(probe_workers=False)
    p.note_worker_phase(0, "assemble", [0.001, 0.001, 0.001, 0.301])
    p.note_worker_phase(0, "execute_probe", [2.0, 2.001, 2.0, 2.002])
    p.note_consumed_round(0)
    _consumer(p, 0.0, 1.0)
    rec = p.observe_round()
    w = rec["worker"]
    assert w["straggler"] is True
    assert w["worst_worker"] == 3
    assert w["straggler_phase"] == "assemble"
    assert w["per_phase"]["assemble"]["straggler"] is True
    assert w["per_phase"]["execute_probe"]["straggler"] is False
    assert p.straggler_rounds == 1
    assert p.last_straggler_worker == 3
    assert p.last_straggler_round == 0
    assert p.state_dict()["last_straggler_worker"] == 3


def test_no_straggler_on_homogeneous_or_microsecond_noise():
    p = profile_mod.RoundProfiler(probe_workers=False)
    # homogeneous workers
    p.note_worker_phase(0, "assemble", [0.1, 0.1, 0.1, 0.1])
    p.note_consumed_round(0)
    rec = p.observe_round()
    assert rec["worker"]["straggler"] is False
    # large RATIO but microsecond absolute gap: the floor suppresses it
    p.note_worker_phase(1, "assemble", [1e-6, 1e-6, 1e-6, 9e-6])
    p.note_consumed_round(1)
    rec = p.observe_round()
    assert rec["worker"]["straggler"] is False
    assert p.straggler_rounds == 0


def test_worker_timer_and_timed_windows_feed_attribution():
    p = profile_mod.install(profile_mod.RoundProfiler(probe_workers=False))
    try:
        with profile_mod.worker_timer(0, 2, 4):
            time.sleep(0.01)
        out = profile_mod.timed_worker_windows(1, [lambda: "a", lambda: "b"])
        assert out == ["a", "b"]
        p.note_consumed_round(0)
        rec = p.observe_round()
        times = rec["worker"]["times_ms"]
        assert len(times) == 4 and times[2] >= 10.0
        assert times[0] == 0.0
        p.note_consumed_round(1)
        rec1 = p.observe_round()
        assert len(rec1["worker"]["times_ms"]) == 2
    finally:
        profile_mod.uninstall(p)


def test_round_keying_follows_consumed_round_across_replay():
    """Resume replays re-deliver absolute rounds: records key by the
    round the feed delivered, not a monotonic counter."""
    p = profile_mod.RoundProfiler(probe_workers=False)
    for r in (0, 1, 2, 1, 2, 3):  # preempt after 2, replay from 1
        p.note_worker_phase(r, "assemble", [0.01, 0.02])
        p.note_consumed_round(r)
        p.observe_round()
    assert [rec["round"] for rec in p._records] == [0, 1, 2, 1, 2, 3]


# ---------------------------------------------------------------------------
# execute probe (real sharded array over the virtual mesh)


def test_probe_execute_times_each_dp_shard():
    import jax

    from sparknet_tpu.parallel import make_mesh
    from sparknet_tpu.parallel.trainers import leading_sharding

    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    arr = jax.device_put(
        np.zeros((2, 4), np.float32), leading_sharding(mesh)
    )
    p = profile_mod.RoundProfiler()
    times = p.probe_execute(arr)
    assert times is not None and times.shape == (2,)
    assert np.all(times >= 0)
    # replicated/one-shard arrays are un-probeable -> None, not a crash
    assert p.probe_execute(np.zeros(3)) is None
    # a REPLICATED device array (the AllReduce trainer's losses) has >=2
    # shards but they all map to worker 0 — must bail to None before
    # polling (polling would add a per-round sync and misattribute the
    # whole drain to 'worker 0')
    from sparknet_tpu.parallel.trainers import replicated_sharding

    repl = jax.device_put(np.zeros((2, 4), np.float32),
                          replicated_sharding(mesh))
    assert len(list(repl.addressable_shards)) >= 2
    assert p.probe_execute(repl) is None


# ---------------------------------------------------------------------------
# export surface: gauges, /healthz block, run-log instant


def test_metrics_gauges_and_healthz_block():
    tm = obs.enable_training_metrics()
    p = profile_mod.install(profile_mod.RoundProfiler(probe_workers=False))
    try:
        _producer(p, 0, -0.5, -0.2)
        p.note_worker_phase(0, "assemble", [0.001, 0.4])
        p.note_consumed_round(0)
        _consumer(p, 0.0, 1.0)
        p.note_round_work(
            flops_per_round=1e9, comm_bytes_per_round=1e6,
            compress="int8", num_workers=2,
        )
        p.observe_round()
        text = tm.registry.render()
        assert 'sparknet_hidden_fraction{kind="h2d"}' in text
        assert "sparknet_worker_skew" in text
        assert "sparknet_straggler_worker 1" in text
        assert "sparknet_straggler_rounds_total 1" in text
        state = obs.profile_state()
        assert state["rounds_profiled"] == 1
        assert state["last_worst_worker"] == 1
        s = p.summary()
        assert s["arithmetic_intensity_flops_per_byte"] == pytest.approx(
            1000.0
        )
        assert s["compress"] == "int8"
    finally:
        profile_mod.uninstall(p)
    assert obs.profile_state() is None


def test_profile_instant_rides_run_log(tmp_path):
    from sparknet_tpu.obs.trace import Tracer

    jl = str(tmp_path / "run.trace.jsonl")
    tracer = obs.install_tracer(Tracer(jsonl_path=jl))
    p = profile_mod.install(profile_mod.RoundProfiler(probe_workers=False))
    try:
        p.note_consumed_round(0)
        _consumer(p, 0.0, 1.0)
        p.observe_round()
    finally:
        profile_mod.uninstall(p)
        obs.uninstall_tracer()
        tracer.close()
    import json

    recs = [json.loads(line) for line in open(jl)]
    prof = [r for r in recs if r["name"] == "profile"]
    assert prof and prof[0]["args"]["round"] == 0


def test_obs_start_wires_profiler_and_prints_summary(capsys):
    run = obs.start(profile_rounds=True)
    assert run.profiler is not None
    assert profile_mod.active() is run.profiler
    run.profiler.note_consumed_round(0)
    _consumer(run.profiler, 0.0, 0.5)
    run.profiler.observe_round()
    run.close()
    assert profile_mod.active() is None
    out = capsys.readouterr().out
    assert "round-anatomy profiler on" in out
    assert "profile: round anatomy over 1 round(s)" in out


def test_profile_out_dumps_summary_json(tmp_path):
    """``--profile_out`` (obs.start(profile_out=...)): the end-of-run
    RoundProfiler.summary() lands as JSON.  Implies profiling."""
    import json

    out = tmp_path / "anatomy.json"
    run = obs.start(profile_out=str(out))
    assert run.profiler is not None  # profile_out alone implies --profile
    run.profiler.note_consumed_round(0)
    _consumer(run.profiler, 0.0, 0.5)
    run.profiler.observe_round()
    run.close()
    s = json.loads(out.read_text())
    assert s["rounds"] == 1
    assert "phases" in s and "execute" in s["phases"]


def test_profiled_training_round_end_to_end():
    """A real 2-worker cifar10_quick round under the profiler: phases
    fold, the record carries the modeled work sizes, and per-shard
    probes ran (uniform on the single-program CPU mesh — disclosed)."""
    import jax

    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.parallel import (
        ParameterAveragingTrainer,
        make_mesh,
        shard_leading,
    )
    from sparknet_tpu.solver import Solver

    batch = 4
    netp = cfg.replace_data_layers(
        models.load_model("cifar10_quick"),
        [(batch, 3, 32, 32), (batch,)],
        [(batch, 3, 32, 32), (batch,)],
    )
    solver = Solver(
        models.load_model_solver("cifar10_quick"), net_param=netp
    )
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    trainer = ParameterAveragingTrainer(solver, mesh)
    rng = np.random.RandomState(0)
    window = {
        "data": rng.rand(2, 1, batch, 3, 32, 32).astype(np.float32),
        "label": np.zeros((2, 1, batch), np.float32),
    }
    p = profile_mod.install(profile_mod.RoundProfiler())
    try:
        state = trainer.init_state(seed=0)
        out = trainer.round(state, shard_leading(window, mesh))
        jax.block_until_ready(out[1])
    finally:
        profile_mod.uninstall(p)
    rec = p.last()
    assert rec is not None
    assert "execute" in rec["phases_ms"] and "average" in rec["phases_ms"]
    assert rec["worker"]["phases"] == ["execute_probe"]
    assert len(rec["worker"]["times_ms"]) == 2
    # the trainer told the profiler its modeled per-round work
    assert p.flops_per_round and p.flops_per_round > 0
    assert p.comm_bytes_per_round and p.comm_bytes_per_round > 0
    assert p.num_workers == 2 and p.compress == "none"
