"""Unified telemetry layer (``sparknet_tpu/obs``): tracer, shared
metrics registry (+labels), /metrics + /healthz exporter, instrumented
subsystems, and the log-parsing satellites."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from sparknet_tpu import obs
from sparknet_tpu.obs.exporter import ObsExporter
from sparknet_tpu.obs.metrics import MetricsRegistry
from sparknet_tpu.obs.trace import Tracer, _NULL_SPAN


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends with telemetry fully off — the module
    globals (tracer, training metrics, health) are process-wide."""
    obs.uninstall_tracer()
    obs._reset_training_metrics_for_tests()
    yield
    t = obs.uninstall_tracer()
    if t is not None:
        t.close()
    obs._reset_training_metrics_for_tests()


# ---------------------------------------------------------------------------
# tracer


def test_span_is_shared_noop_when_disabled():
    assert obs.span("anything") is _NULL_SPAN
    assert obs.get_tracer() is None
    obs.instant("ignored")  # must not raise


def test_span_nesting_and_thread_attribution(tmp_path):
    tracer = obs.install_tracer(Tracer())
    with obs.span("average", round=0):
        with obs.span("execute", round=0):
            time.sleep(0.01)

    def producer():
        with obs.span("assemble", round=1):
            time.sleep(0.01)

    t = threading.Thread(target=producer, name="fake-producer")
    t.start()
    t.join()
    events = tracer.events()
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(spans) == {"average", "execute", "assemble"}
    # nesting: execute's [ts, ts+dur] sits inside average's
    avg, exe = spans["average"], spans["execute"]
    assert avg["ts"] <= exe["ts"]
    assert exe["ts"] + exe["dur"] <= avg["ts"] + avg["dur"] + 1e-6
    # thread attribution: same tid for nested spans, different for the
    # producer thread, and thread_name metadata labels both tracks
    assert avg["tid"] == exe["tid"]
    assert spans["assemble"]["tid"] != avg["tid"]
    meta = {
        e["tid"]: e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e["name"] == "thread_name"
    }
    assert meta[spans["assemble"]["tid"]] == "fake-producer"
    assert spans["assemble"]["args"] == {"round": 1}


def test_chrome_trace_json_schema(tmp_path):
    tracer = obs.install_tracer(Tracer())
    with obs.span("execute"):
        pass
    obs.instant("fault_storage", cat="fault", round=2)
    path = str(tmp_path / "t.trace.json")
    tracer.save(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert e["ph"] in ("X", "i", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["name"] == "fault_storage"
    assert inst[0]["s"] == "t"  # thread-scoped instant


def test_jsonl_run_log_lines_valid(tmp_path):
    jl = str(tmp_path / "run.trace.jsonl")
    tracer = obs.install_tracer(Tracer(jsonl_path=jl))
    with obs.span("h2d", round=3):
        pass
    obs.instant("retry", cat="io", attempt=0)
    tracer.close()
    lines = [json.loads(l) for l in open(jl)]
    assert len(lines) == 2
    span_rec, inst_rec = lines
    assert span_rec["kind"] == "span" and span_rec["name"] == "h2d"
    assert span_rec["dur_ms"] >= 0 and span_rec["ts_s"] >= 0
    assert span_rec["args"] == {"round": 3}
    assert isinstance(span_rec["thread"], str)
    assert inst_rec["kind"] == "instant" and inst_rec["name"] == "retry"
    # a NEW tracer on the same path starts a fresh run log (truncate,
    # matching save()'s rewrite of the Chrome JSON) — two runs never
    # interleave in one .jsonl
    obs.uninstall_tracer()
    t2 = obs.install_tracer(Tracer(jsonl_path=jl))
    obs.instant("fresh")
    t2.close()
    lines2 = [json.loads(l) for l in open(jl)]
    assert [r["name"] for r in lines2] == ["fresh"]


def test_jsonl_path_for():
    assert obs.jsonl_path_for("a/run.trace.json") == "a/run.trace.jsonl"
    assert obs.jsonl_path_for("a/run") == "a/run.jsonl"


# ---------------------------------------------------------------------------
# metrics registry: labels + rendering


def test_labeled_family_renders_prometheus_text():
    r = MetricsRegistry()
    lat = r.histogram(
        "phase_seconds", "per-phase", buckets=(0.1, 1.0), labels=("phase",)
    )
    lat.labels("execute").observe(0.05)
    lat.labels("execute").observe(0.5)
    lat.labels("assemble").observe(2.0)
    faults = r.counter("faults_total", "by kind", labels=("kind",))
    faults.labels("storage").inc(3)
    text = r.render()
    # ONE TYPE block per family; children merge labels with le
    assert text.count("# TYPE phase_seconds histogram") == 1
    assert 'phase_seconds_bucket{phase="execute",le="0.1"} 1' in text
    assert 'phase_seconds_bucket{phase="execute",le="+Inf"} 2' in text
    assert 'phase_seconds_count{phase="assemble"} 1' in text
    assert 'faults_total{kind="storage"} 3' in text
    # the same child comes back on repeat lookup
    assert lat.labels("execute") is lat.labels("execute")


def test_label_arity_and_duplicates_rejected():
    r = MetricsRegistry()
    fam = r.counter("c_total", "", labels=("kind",))
    with pytest.raises(ValueError):
        fam.labels("a", "b")
    with pytest.raises(ValueError):
        r.counter("c_total", "dup")
    # a labeled CALLBACK gauge hands each child's label values to the one
    # fn, so that the children differ (no dead zeros); a child exists once
    # labels(...) has named it
    sizes = {"a": 1.0, "b": 2.5}
    fam = r.gauge("g_bytes", "", fn=sizes.get, labels=("device",))
    assert "g_bytes{" not in r.render()
    fam.labels("a"), fam.labels("b")
    assert 'g_bytes{device="a"} 1' in r.render()
    assert 'g_bytes{device="b"} 2.5' in r.render()
    sizes["a"] = 4.0  # sampled at render time
    assert 'g_bytes{device="a"} 4' in r.render()
    # labeled set()-style gauges are fine
    g = r.gauge("g_depth", "", labels=("queue",))
    g.labels("feed").set(3)
    assert 'g_depth{queue="feed"} 3' in r.render()


def test_label_values_escaped():
    r = MetricsRegistry()
    fam = r.counter("e_total", "", labels=("msg",))
    fam.labels('say "hi"\n').inc()
    assert 'e_total{msg="say \\"hi\\"\\n"} 1' in r.render()


# ---------------------------------------------------------------------------
# exporter


def test_exporter_metrics_and_healthz():
    r = MetricsRegistry()
    r.counter("demo_total", "demo").inc(7)
    state = {"reason": None}
    ex = ObsExporter(
        r, port=0, health_fn=lambda: state["reason"]
    ).start()
    try:
        h, p = ex.address
        body = urllib.request.urlopen(
            f"http://{h}:{p}/metrics", timeout=5
        ).read().decode()
        assert "demo_total 7" in body
        hz = urllib.request.urlopen(f"http://{h}:{p}/healthz", timeout=5)
        assert json.loads(hz.read()) == {"status": "ok"}
        state["reason"] = "prefetch_stall: wedged"
        try:
            urllib.request.urlopen(f"http://{h}:{p}/healthz", timeout=5)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["reason"].startswith("prefetch_stall")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{h}:{p}/nope", timeout=5)
    finally:
        ex.close()


def test_obs_start_wires_exporter_health_to_global_state(tmp_path):
    run = obs.start(
        metrics=True, port=0,
        trace_out=str(tmp_path / "r.trace.json"), echo=None,
    )
    try:
        h, p = run.address
        obs.report_unhealthy("stalled round")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{h}:{p}/healthz", timeout=5)
        obs.report_healthy()
        ok = urllib.request.urlopen(f"http://{h}:{p}/healthz", timeout=5)
        assert ok.status == 200
    finally:
        run.close()
    # close() saved the chrome trace and is idempotent
    assert os.path.exists(tmp_path / "r.trace.json")
    run.close()


# ---------------------------------------------------------------------------
# instrumented subsystems feed the shared registry


def test_phase_spans_feed_latency_histogram():
    tm = obs.enable_training_metrics()
    with obs.span("execute"):
        time.sleep(0.002)
    with obs.span("inner_detail", cat="detail"):  # non-phase: not observed
        pass
    child = tm.phase_latency.labels("execute")
    assert child.count == 1 and child.sum > 0
    assert tm.phase_latency.children() == [child]


def test_retry_ticks_counter_and_instant():
    import random

    from sparknet_tpu.utils.retry import RetryPolicy, retry_call

    tm = obs.enable_training_metrics()
    tracer = obs.install_tracer(Tracer())
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionResetError("boom")
        return "ok"

    assert retry_call(
        flaky,
        policy=RetryPolicy(max_attempts=5, base_s=0.001, cap_s=0.002),
        rng=random.Random(0),
        sleep=lambda s: None,
    ) == "ok"
    assert tm.retries.value == 2
    retries = [
        e for e in tracer.events()
        if e.get("ph") == "i" and e["name"] == "retry"
    ]
    assert len(retries) == 2
    assert retries[0]["args"]["error"] == "ConnectionResetError"


def test_prefetch_stall_counts_and_flips_health():
    from sparknet_tpu.data.prefetch import Prefetcher, PrefetchStall

    tm = obs.enable_training_metrics()
    release = threading.Event()

    def wedged():
        release.wait(5.0)
        return None

    pf = Prefetcher(wedged, stall_timeout_s=0.1)
    try:
        with pytest.raises(PrefetchStall):
            next(pf)
        assert tm.feed_stalls.value == 1
        assert obs.health_reason().startswith("prefetch_stall")
        obs.report_healthy()
        assert obs.health_reason() is None
    finally:
        release.set()
        pf.stop()


def test_quarantine_ticks_counter(tmp_path):
    from sparknet_tpu.io import checkpoint

    tm = obs.enable_training_metrics()
    state_path = str(tmp_path / "p_iter_4.solverstate.npz")
    for p in (state_path, str(tmp_path / "p_iter_4.caffemodel")):
        with open(p, "wb") as f:
            f.write(b"junk")
    moved = checkpoint._quarantine(state_path)
    assert moved and all(m.endswith(".corrupt") for m in moved)
    assert tm.quarantined.value == 1


def test_serve_registry_exports_uptime_and_open_requests():
    """The serving front-end's satellite gauges ride the SAME shared
    registry the batcher built (obs.metrics — no second registry)."""
    from sparknet_tpu import models
    from sparknet_tpu.serve import InferenceEngine, ServeServer

    netp = models.deploy_variant(models.load_model("cifar10_quick"), batch=1)
    server = ServeServer(
        InferenceEngine(netp, buckets=[1]), port=0
    )
    try:
        text = server.metrics.render()
        assert "# TYPE serve_uptime_seconds gauge" in text
        assert "# TYPE serve_open_requests gauge" in text
        assert "serve_open_requests 0" in text
        assert server.metrics.get("serve_uptime_seconds").value >= 0
        # one MetricsRegistry instance end to end
        assert server.metrics is server.batcher.metrics
        assert isinstance(server.metrics, MetricsRegistry)
    finally:
        server.batcher.stop(drain=False, timeout=5)
        server.httpd.server_close()


# ---------------------------------------------------------------------------
# trainlog satellite


def test_trainlog_context_manager_idempotent_close(tmp_path):
    with obs.span("x"):  # no tracer: log mirror must be a no-op
        pass
    log_path = str(tmp_path / "sub" / "mylog.txt")
    with __import__("sparknet_tpu").utils.trainlog.TrainingLog(
        path=log_path, echo=False
    ) as log:
        log.log("hello", i=3)
        log.log("plain")
        assert not log.closed
    assert log.closed
    log.close()  # idempotent
    lines = open(log_path).read().splitlines()
    assert len(lines) == 2
    assert ", i = 3: hello" in lines[0]
    assert lines[1].endswith(": plain")
    with pytest.raises(ValueError):
        log.log("after close")


def test_trainlog_env_directory_routing(tmp_path, monkeypatch):
    from sparknet_tpu.utils import TrainingLog

    monkeypatch.setenv("SPARKNET_LOG_DIR", str(tmp_path))
    log = TrainingLog(tag="routed", echo=False)
    log.log("x")
    log.close()
    assert os.path.dirname(log.path) == str(tmp_path)
    assert os.path.basename(log.path).startswith("training_log_")
    assert log.path.endswith("_routed.txt")
    # explicit directory still wins over the env default
    other = tmp_path / "explicit"
    log2 = TrainingLog(directory=str(other), echo=False)
    log2.close()
    assert os.path.dirname(log2.path) == str(other)


def test_trainlog_mirrors_into_jsonl_run_log(tmp_path):
    from sparknet_tpu.utils import TrainingLog

    jl = str(tmp_path / "run.trace.jsonl")
    tracer = obs.install_tracer(Tracer(jsonl_path=jl))
    with TrainingLog(directory=str(tmp_path), echo=False) as log:
        log.log("iter 10 smoothed_loss 1.5000")
        log.log("training", i=4)
    tracer.close()
    recs = [json.loads(l) for l in open(jl)]
    assert [r["name"] for r in recs] == ["log", "log"]
    assert recs[0]["args"]["msg"] == "iter 10 smoothed_loss 1.5000"
    assert recs[1]["args"]["i"] == 4


# ---------------------------------------------------------------------------
# parse_log satellite: flat + JSONL through the same recognizers


_FLAT = """\
1.000: iter 10 smoothed_loss 2.3000
2.000: test output accuracy = 0.5000
2.000: test output loss = 1.2000
3.500: round 3 trained, smoothed_loss 1.9000
"""


def test_parse_log_flat_format(tmp_path):
    from sparknet_tpu.tools import parse_log as pl

    p = tmp_path / "training_log_1_x.txt"
    p.write_text(_FLAT)
    train, test = pl.parse_log(str(p))
    assert train == [
        {"seconds": 1.0, "round_or_iter": 10, "smoothed_loss": 2.3},
        {"seconds": 3.5, "round_or_iter": 3, "smoothed_loss": 1.9},
    ]
    assert test == [{"seconds": 2.0, "accuracy": 0.5, "loss": 1.2}]


def test_parse_log_jsonl_format(tmp_path):
    from sparknet_tpu.tools import parse_log as pl
    from sparknet_tpu.utils import TrainingLog

    jl = str(tmp_path / "run.trace.jsonl")
    tracer = obs.install_tracer(Tracer(jsonl_path=jl))
    with obs.span("execute"):  # span records must be skipped cleanly
        pass
    with TrainingLog(directory=str(tmp_path), echo=False) as log:
        log.log("iter 10 smoothed_loss 2.3000")
        log.log("test output accuracy = 0.5000")
        log.log("test output loss = 1.2000")
        log.log("round 3 trained, smoothed_loss 1.9000")
    tracer.close()
    assert pl.is_jsonl_log(jl)
    train, test = pl.parse_log(jl)
    assert [t["round_or_iter"] for t in train] == [10, 3]
    assert [t["smoothed_loss"] for t in train] == [2.3, 1.9]
    # the two test-output lines carry REAL elapsed timestamps; they
    # merge into one row only when logged within the same millisecond,
    # so accept either shape (the flat-format test above pins the
    # same-timestamp merge deterministically)
    merged = {k: v for row in test for k, v in row.items()}
    assert 1 <= len(test) <= 2
    assert merged["accuracy"] == 0.5 and merged["loss"] == 1.2
    # CSV writer round-trips the same rows for both formats
    paths = pl.write_csvs(train, test, str(tmp_path / "out"))
    assert [os.path.basename(p) for p in paths] == [
        "out.train.csv", "out.test.csv"
    ]


def test_parse_log_flat_not_misdetected(tmp_path):
    from sparknet_tpu.tools import parse_log as pl

    p = tmp_path / "t.txt"
    p.write_text(_FLAT)
    assert not pl.is_jsonl_log(str(p))


# ---------------------------------------------------------------------------
# tools/trace_report.py


def _repo_tools_trace_report():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(repo, "tools", "trace_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_folds_phases_and_measures_hidden_fraction(tmp_path):
    tr = _repo_tools_trace_report()
    # hand-built events: producer assemble HALF overlaps consumer
    # execute (150 of 300 us inside the first execute span)
    events = [
        {"name": "execute", "ph": "X", "ts": 0.0, "dur": 1000.0, "tid": 1},
        {"name": "assemble", "ph": "X", "ts": 850.0, "dur": 300.0,
         "tid": 2, "args": {"round": 1}},
        {"name": "execute", "ph": "X", "ts": 1200.0, "dur": 800.0, "tid": 1},
        {"name": "fault_storage", "ph": "i", "ts": 50.0, "tid": 2},
    ]
    rep = tr.fold(events)
    # the boolean audit is now DERIVED from the measured fraction
    assert rep["producer_overlap_observed"] is True
    assert rep["producer_hidden_fraction"] == pytest.approx(0.5)
    per = rep["producer_hidden_fraction_per_round"]
    assert per["rounds"] == 1 and per["p50"] == pytest.approx(0.5)
    assert rep["phases"]["execute"]["count"] == 2
    assert rep["phases"]["execute"]["total_ms"] == 1.8
    assert rep["phases"]["assemble"]["mean_ms"] == 0.3
    assert rep["instants"] == {"fault_storage": 1}
    assert rep["comm"] is None  # trace predates the comm plane
    table = tr.format_report(rep)
    assert "execute" in table and "hidden under execute: 50.0%" in table
    # serial trace (same tid): 0 hidden, no overlap claimed
    serial = [dict(e, tid=1) for e in events if e["ph"] == "X"]
    rep2 = tr.fold(serial)
    assert rep2["producer_overlap_observed"] is False
    assert rep2["producer_hidden_fraction"] == 0.0


def test_trace_report_hidden_fraction_not_inflated_by_nested_spans():
    """The PA trainer's traces NEST execute inside average on the same
    consumer thread — coverage must be the interval UNION, not the
    pairwise sum (which double-counts and can report a half-hidden
    producer as fully hidden, masking a partially collapsed pipeline)."""
    tr = _repo_tools_trace_report()
    events = [
        # consumer: average 0-50us wrapping execute 1-49us (nested)
        {"name": "average", "ph": "X", "ts": 0.0, "dur": 50.0, "tid": 1},
        {"name": "execute", "ph": "X", "ts": 1.0, "dur": 48.0, "tid": 1},
        # producer: 0-100us — exactly half runs under the consumer
        {"name": "assemble", "ph": "X", "ts": 0.0, "dur": 100.0,
         "tid": 2, "args": {"round": 0}},
    ]
    rep = tr.fold(events)
    assert rep["producer_hidden_fraction"] == pytest.approx(0.5)


def test_trace_report_folds_comm_spans():
    """The PR-6 comm spans (quantize/allreduce/dequantize with their
    chunk=/stage=/compress= args) fold into the compressed-collective
    section — alongside, not instead of, the producer phases."""
    tr = _repo_tools_trace_report()
    events = [
        {"name": "execute", "ph": "X", "ts": 0.0, "dur": 500.0, "tid": 1},
        {"name": "quantize", "ph": "X", "ts": 500.0, "dur": 40.0,
         "tid": 1, "args": {"compress": "int8"}},
        {"name": "allreduce", "ph": "X", "ts": 540.0, "dur": 100.0,
         "tid": 9, "args": {"chunk": 0, "nbytes": 4096}},
        {"name": "allreduce", "ph": "X", "ts": 640.0, "dur": 120.0,
         "tid": 9, "args": {"chunk": 3, "nbytes": 8192}},
        {"name": "dequantize", "ph": "X", "ts": 760.0, "dur": 30.0,
         "tid": 1, "args": {"stage": "correction"}},
        {"name": "assemble", "ph": "X", "ts": 100.0, "dur": 200.0,
         "tid": 2, "args": {"round": 1}},
    ]
    rep = tr.fold(events)
    comm = rep["comm"]
    assert comm["allreduce"]["count"] == 2
    assert comm["allreduce"]["chunks"] == [0, 3]
    assert comm["allreduce"]["nbytes_total"] == 4096 + 8192
    assert comm["allreduce"]["total_ms"] == pytest.approx(0.22)
    assert comm["quantize"]["compress"] == ["int8"]
    assert comm["dequantize"]["stages"] == {"correction": 1}
    # producer phases still fold beside the comm section
    assert rep["phases"]["assemble"]["count"] == 1
    assert rep["producer_hidden_fraction"] == pytest.approx(1.0)
    table = tr.format_report(rep)
    assert "compressed collective: allreduce x2" in table
    assert "quantize x1" in table and "dequantize x1" in table


def test_trace_report_reads_tracer_output_both_formats(tmp_path):
    tr = _repo_tools_trace_report()
    jl = str(tmp_path / "r.trace.jsonl")
    tracer = obs.install_tracer(Tracer(jsonl_path=jl))
    with obs.span("execute", round=0):
        time.sleep(0.001)
    obs.instant("quarantine", cat="fault")
    chrome = str(tmp_path / "r.trace.json")
    tracer.save(chrome)
    tracer.close()
    for path in (chrome, jl):
        rep = tr.fold(tr.load_events(path))
        assert rep["phases"]["execute"]["count"] == 1, path
        assert rep["instants"]["quarantine"] == 1, path
    # the CLI entry point renders without error
    assert tr.main([chrome]) == 0
    assert tr.main([jl, "--json"]) == 0


def test_obs_traced_run_tier1_smoke(tmp_path):
    """Tier-1 telemetry smoke (in-process, small): a short traced
    cifar10_quick run on the virtual mesh produces a Perfetto-loadable
    trace whose assemble/h2d/execute/average spans exist, nest sanely,
    and attribute the producer phases to the feed thread."""
    import jax

    from sparknet_tpu import config as cfg, models, obs
    from sparknet_tpu.data import CifarLoader, RoundFeed
    from sparknet_tpu.obs.trace import Tracer
    from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh
    from sparknet_tpu.solver import Solver

    workers, tau, batch, rounds = 2, 1, 4, 3
    data_dir = str(tmp_path / "data")
    CifarLoader.write_synthetic(data_dir, num_train=32, num_test=8, seed=3)
    xs, ys = CifarLoader(data_dir).minibatches(batch, train=True)

    def window(r):
        import numpy as np

        data = np.stack([xs[(r * workers + w) % len(xs)] for w in range(workers)])
        label = np.stack([ys[(r * workers + w) % len(ys)] for w in range(workers)])
        return {"data": data[:, None], "label": label[:, None]}

    netp = cfg.replace_data_layers(
        models.load_model("cifar10_quick"),
        [(batch, 3, 32, 32), (batch,)],
        [(batch, 3, 32, 32), (batch,)],
    )
    solver = Solver(models.load_model_solver("cifar10_quick"), net_param=netp)
    mesh = make_mesh({"dp": workers}, devices=jax.devices()[:workers])
    trainer = ParameterAveragingTrainer(solver, mesh)
    tracer = obs.install_tracer(Tracer())
    feed = RoundFeed(lambda r, out: window(r), mesh=mesh, num_rounds=rounds)
    try:
        state = trainer.init_state(seed=0)
        for r in range(rounds):
            state, losses = trainer.round(state, feed.next_round(r))
        jax.block_until_ready(losses)
    finally:
        feed.stop()
        obs.uninstall_tracer()
    path = str(tmp_path / "run.trace.json")
    tracer.save(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("assemble", "h2d", "execute", "average"):
        assert len(by_name.get(name, [])) == rounds, (name, by_name.keys())
    # nesting: every execute sits inside exactly one average span on
    # the SAME thread; assemble/h2d live on the producer thread
    for exe in by_name["execute"]:
        parents = [
            a for a in by_name["average"]
            if a["tid"] == exe["tid"]
            and a["ts"] <= exe["ts"]
            and exe["ts"] + exe["dur"] <= a["ts"] + a["dur"] + 1.0
        ]
        assert len(parents) == 1, exe
    exec_tids = {e["tid"] for e in by_name["execute"]}
    feed_tids = {e["tid"] for e in by_name["assemble"] + by_name["h2d"]}
    assert exec_tids and feed_tids and not (exec_tids & feed_tids)
    # per-round h2d follows its round's assemble on the producer
    asm = sorted(by_name["assemble"], key=lambda e: e["ts"])
    h2d = sorted(by_name["h2d"], key=lambda e: e["ts"])
    for a, h in zip(asm, h2d):
        assert a["args"]["round"] == h["args"]["round"]
        assert a["ts"] + a["dur"] <= h["ts"] + 1.0
