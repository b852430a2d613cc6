"""Request-anatomy tests (ISSUE 19, ``obs/reqtrace.py`` + the serve
instrumentation): request ids minted only while tracing is on (the
zero-overhead no-op path), span nesting/ordering under concurrent
streams, synthetic queue- vs decode- vs kv-bound verdicts, shed-cause
labels on the counter and the ``X-Shed-Cause`` response header, the
observer-composition seam, and the fleet host-tagged merge through
``tools/request_report.py`` (one folding implementation)."""

import importlib.util
import json
import os
import urllib.error
import urllib.request

import pytest

from sparknet_tpu.models.transformer_lm import TransformerLM
from sparknet_tpu.obs import reqtrace
from sparknet_tpu.obs import trace as trace_mod
from sparknet_tpu.obs.reqtrace import RequestProfiler
from sparknet_tpu.obs.trace import _NULL_SPAN, span
from sparknet_tpu.serve import (
    GenerationEngine,
    KVBudgetExceeded,
    QueueFull,
    StreamBatcher,
)
from sparknet_tpu.serve.server import ServeServer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T = 32  # model context for every engine in this module


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(dim=32, depth=2, heads=2, seq_len=T, vocab=64)


@pytest.fixture(scope="module")
def engine(lm):
    eng = GenerationEngine(
        lm, prefill_buckets=(8, T), max_streams=3, kv_blocks=30,
        kv_block_size=4, seed=0,
    )
    eng.warmup()
    return eng


@pytest.fixture(autouse=True)
def _clean_seams():
    """Every test starts and ends with no profiler and no observer —
    a leaked seam would silently turn the no-op path on for the rest
    of the suite."""
    reqtrace.uninstall()
    trace_mod.set_span_observer(None)
    yield
    reqtrace.uninstall()
    trace_mod.set_span_observer(None)


# ----------------------------------------------------------------------
# the zero-overhead no-op path
def test_noop_path_when_tracing_off(engine):
    assert reqtrace.tracing_enabled() is False
    assert reqtrace.maybe_rid() is None
    assert reqtrace.maybe_rid("req-000042") == "req-000042"  # passthrough
    # span() hands back the shared no-op singleton, not a fresh object
    assert span("queue_wait", cat="req", req="x") is _NULL_SPAN
    assert reqtrace.state() is None
    reqtrace.note_shed("queue_full")  # must not raise with nothing on
    # a full stream run mints NO id and folds nothing
    sb = StreamBatcher(engine, max_queue=4)
    try:
        st = sb.submit_stream([1, 7, 3], 4)
        assert st.rid is None
        assert st.result(timeout=60.0)["event"] == "done"
    finally:
        sb.stop(drain=True, timeout=30.0)


def test_rid_minted_when_observer_installed(engine):
    prof = reqtrace.install(RequestProfiler())
    try:
        assert reqtrace.tracing_enabled() is True
        rid = reqtrace.maybe_rid()
        assert rid is not None and rid.startswith("req-")
        assert reqtrace.active() is prof
    finally:
        reqtrace.uninstall(prof)
    assert reqtrace.tracing_enabled() is False


# ----------------------------------------------------------------------
# span nesting/ordering + live folding under concurrent streams
def test_concurrent_streams_fold_and_nest(engine):
    records = []

    def recorder(name, cat, t0, t1, thread, args):
        records.append((name, cat, t0, t1, dict(args or {})))

    trace_mod.set_span_observer(recorder)
    prof = reqtrace.install(RequestProfiler())  # composes with recorder
    jobs = 6
    sb = StreamBatcher(engine, max_queue=jobs)
    try:
        streams = [
            sb.submit_stream([1 + i, 7, 3], 4 + (i % 3)) for i in range(jobs)
        ]
        finals = [st.result(timeout=120.0) for st in streams]
    finally:
        sb.stop(drain=True, timeout=30.0)
        reqtrace.uninstall(prof)
    assert all(f["event"] == "done" for f in finals)
    rids = [st.rid for st in streams]
    assert len(set(rids)) == jobs and all(r is not None for r in rids)

    # every request folded live with its full stage anatomy
    assert prof.requests_profiled == jobs
    rows = {r["rid"]: r for r in prof.requests_table(n=jobs)}
    for st, fin in zip(streams, finals):
        row = rows[st.rid]
        assert row["outcome"] == "done"
        assert row["tokens"] == len(fin["tokens"])
        # prefill emits the first token, decode the rest
        assert row["decode_steps"] >= row["tokens"] - 1
        for stage in ("queue_wait", "prefill", "decode"):
            assert stage in row["stages_ms"], (st.rid, row)
        assert row["ttft_ms"] is not None and row["ttft_ms"] >= 0

    # nesting/ordering per rid: request envelope opens before the
    # queue wait, which closes before prefill starts, which closes
    # before the rid's first decode step; the envelope closes last
    by_rid = {}
    for name, cat, t0, t1, args in records:
        for r in [args.get("req")] + list(args.get("reqs") or ()):
            if r is not None:
                by_rid.setdefault(r, {}).setdefault(name, []).append(
                    (t0, t1)
                )
    for rid in rids:
        sp = by_rid[rid]
        (req0, req1), = sp["request"]
        (q0, q1), = sp["queue_wait"]
        (p0, p1), = sp["prefill"]
        decodes = sorted(sp["decode_step"])
        assert req0 <= q0 <= q1 <= p0 <= p1 <= decodes[0][0]
        assert decodes[-1][1] <= req1
    # the concurrent phase really interleaved: some decode step
    # carried more than one live request id
    assert any(
        len(args.get("reqs") or ()) > 1
        for name, _, _, _, args in records if name == "decode_step"
    )


# ----------------------------------------------------------------------
# synthetic verdicts: the folding math, no engine
def _synthetic_request(prof, rid, queue_s, decode_s, t0=0.0):
    t = t0
    prof.on_span("queue_wait", "req", t, t + queue_s, "t", {"req": rid})
    t += queue_s
    prof.on_span("prefill", "gen", t, t + 0.002, "t", {"req": rid})
    t += 0.002
    prof.on_span(
        "decode_step", "gen", t, t + decode_s, "t", {"reqs": [rid]}
    )
    t += decode_s
    prof.on_span("stream_write", "req", t, t + 0.0005, "t", {"req": rid})
    prof.on_span(
        "request", "req", t0, t + 0.0005, "t",
        {"req": rid, "outcome": "done", "tokens": 4},
    )


def test_queue_bound_vs_decode_bound_verdicts():
    queue_prof = RequestProfiler(export_every=1 << 30)
    for i in range(5):
        _synthetic_request(queue_prof, f"q{i}", queue_s=1.0, decode_s=0.01)
    decode_prof = RequestProfiler(export_every=1 << 30)
    for i in range(5):
        _synthetic_request(decode_prof, f"d{i}", queue_s=0.001, decode_s=1.0)
    qs, ds = queue_prof.summary(), decode_prof.summary()
    assert qs["verdict"] == "queue"
    assert ds["verdict"] == "decode"
    assert qs["verdict"] != ds["verdict"]
    # TTFT decomposes as submit -> first token: queue-bound requests
    # pay their wait in TTFT, decode-bound ones don't
    assert qs["ttft_ms"]["p50"] > 500.0
    assert ds["ttft_ms"]["p50"] < 100.0
    # per-stage shares follow the seeded imbalance
    assert qs["stage_shares"]["queue_wait"] > 0.9
    assert ds["stage_shares"]["decode"] > 0.9


def test_kv_shed_fraction_overrides_stage_shares():
    """A squeezed arena sheds instead of queuing: the kv verdict must
    fire on shed fraction even when the COMPLETED requests' time is
    all decode."""
    prof = RequestProfiler(export_every=1 << 30)
    for i in range(3):
        _synthetic_request(prof, f"r{i}", queue_s=0.001, decode_s=1.0)
    for _ in range(5):
        prof.on_shed("kv_reserve")
    s = prof.summary()
    assert s["verdict"] == "kv"
    assert s["kv_shed_frac"] == round(5 / 8, 4)
    assert prof.state_dict()["verdict"] == "kv"
    assert s["sheds"] == {"kv_reserve": 5}


def test_slow_replica_named_from_synthetic_skew():
    prof = RequestProfiler(export_every=1 << 30)
    for i in range(4):
        _synthetic_request(prof, f"f{i}", queue_s=0.001, decode_s=0.02)
        # queue_wait carries the replica tag (the batcher sets it)
        prof.on_span(
            "queue_wait", "req", 0.0, 0.001, "t",
            {"req": f"f{i}", "replica": i % 2},
        )
    for i in range(4):
        rid = f"s{i}"
        prof.on_span(
            "queue_wait", "req", 0.0, 0.001, "t",
            {"req": rid, "replica": 1},
        )
        _synthetic_request(prof, rid, queue_s=0.001, decode_s=0.5)
    s = prof.summary()
    assert s["slow_replica"] == 1
    assert s["skew"] > 1.5
    assert set(s["replicas"]) == {"0", "1"}


def test_live_kv_squeeze_is_read_kv_bound(lm):
    """The verdict across the live loop: a storm against a small arena
    behind a queue too large to fill, so every shed is the KV budget's.
    Time shares alone cannot see a squeeze that sheds instead of queuing;
    the window must still read ``kv``, with no stream lost, no block left
    in use and no recompile."""
    import threading

    eng = GenerationEngine(
        lm, prefill_buckets=(8,), max_streams=2, kv_blocks=8,
        kv_block_size=4, seed=0,
    )
    pinned = eng.warmup()
    prof = reqtrace.install(RequestProfiler())
    sb = StreamBatcher(eng, max_queue=256)
    tally = {"ok": 0, "shed": 0, "errors": 0}
    lock = threading.Lock()

    def client(i):
        for k in range(2):
            try:
                # 4 + 12 positions = 4 of the arena's 8 blocks
                st = sb.submit_stream([1 + i % 5, 7, 3, k + 1], 12)
            except QueueFull:
                with lock:
                    tally["shed"] += 1
                continue
            ev = st.result(timeout=120.0)
            with lock:
                tally["ok" if ev["event"] == "done" else "errors"] += 1

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    sb.stop(drain=True, timeout=30.0)
    summary = prof.summary()
    assert tally["errors"] == 0 and tally["ok"] >= 1, tally
    assert tally["shed"] >= 1, tally
    assert summary["sheds"] == {"kv_reserve": tally["shed"]}
    assert summary["verdict"] == "kv", summary
    assert eng.pool.used() == 0
    assert eng.pool.allocated_total == eng.pool.freed_total > 0
    assert eng.jit_cache_size() == pinned


def test_live_slow_replica_is_named(lm):
    """Two stream replicas behind the router, the second one's decode
    step seeded slow: the per-replica skew must name exactly that one."""
    import time

    from sparknet_tpu.serve import ReplicaPool, Router

    def make_engine(weights=None):
        return GenerationEngine(
            lm, prefill_buckets=(8,), max_streams=2, kv_blocks=30,
            kv_block_size=4, seed=0,
        )

    pool = ReplicaPool(make_engine, replicas=2, max_queue=16, stream=True)
    router = Router(pool, max_inflight=16)
    slow = pool.replicas[1].engine
    step = slow.step

    def slow_step():
        time.sleep(0.02)
        return step()

    slow.step = slow_step
    prof = reqtrace.install(RequestProfiler())
    try:
        for i in range(8):
            evs = list(router.submit_stream([1 + i % 5, 7, 3], 6, timeout=60.0))
            assert evs[-1]["event"] == "done", evs[-1]
        summary = prof.summary()
    finally:
        slow.step = step
        router.close()
    assert set(summary["replicas"]) == {"0", "1"}, summary
    assert summary["slow_replica"] == 1, summary
    assert summary["skew"] >= 1.5


# ----------------------------------------------------------------------
# shed causes: counter labels + exceptions per cause
def test_shed_cause_labels_on_counter(lm, engine):
    prof = reqtrace.install(RequestProfiler())
    try:
        # draining -> RuntimeError (503)
        sb = StreamBatcher(engine, max_queue=4)
        sb.drain()
        with pytest.raises(RuntimeError):
            sb.submit_stream([1, 7, 3], 4)
        assert 'sparknet_gen_streams_shed_total{cause="draining"} 1' in (
            sb.metrics.render()
        )
        sb.stop(drain=True, timeout=30.0)
        # queue_full -> QueueFull (429)
        sb0 = StreamBatcher(engine, max_queue=0)
        with pytest.raises(QueueFull):
            sb0.submit_stream([1, 7, 3], 4)
        assert 'cause="queue_full"' in sb0.metrics.render()
        sb0.stop(drain=True, timeout=30.0)
        # kv_reserve -> KVBudgetExceeded (a QueueFull subtype, 429):
        # 3 prompt + 24 new = 27 positions = 7 blocks > a 6-block arena
        tiny = GenerationEngine(
            lm, prefill_buckets=(8,), max_streams=2, kv_blocks=6,
            kv_block_size=4, seed=0,
        )
        sbk = StreamBatcher(tiny, max_queue=4)
        with pytest.raises(KVBudgetExceeded):
            sbk.submit_stream([1, 7, 3], 24)
        assert 'cause="kv_reserve"' in sbk.metrics.render()
        sbk.stop(drain=True, timeout=30.0)
        assert prof.sheds == {
            "draining": 1, "queue_full": 1, "kv_reserve": 1,
        }
    finally:
        reqtrace.uninstall(prof)


def test_http_shed_cause_header_and_healthz_profile(lm):
    """The 429 names its cause machine-readably (header + body) and
    /healthz carries the live request-profile block while /metrics
    renders the sparknet_req_* families."""
    eng = GenerationEngine(
        lm, prefill_buckets=(8,), max_streams=2, kv_blocks=6,
        kv_block_size=4, seed=0,
    )
    eng.warmup()
    prof = reqtrace.install(
        RequestProfiler(registry=eng.pool.metrics, export_every=1)
    )
    srv = ServeServer(engine=eng, port=0)
    srv.start()
    try:
        h, p = srv.address
        base = f"http://{h}:{p}"
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1, 7, 3], "max_new": 4}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            lines = [
                json.loads(ln)
                for ln in resp.read().decode().splitlines() if ln
            ]
        assert lines[-1]["event"] == "done"
        # over-budget: 7 blocks against the 6-block arena
        bad = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1, 7, 3], "max_new": 24}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=60)
        assert ei.value.code == 429
        assert ei.value.headers.get("X-Shed-Cause") == "kv_reserve"
        assert json.loads(ei.value.read())["cause"] == "kv_reserve"
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health["request_profile"]["requests_profiled"] >= 1
        assert health["request_profile"]["sheds"] == {"kv_reserve": 1}
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "sparknet_req_stage_seconds" in text
        assert "sparknet_req_bound_stage" in text
        assert 'cause="kv_reserve"' in text
    finally:
        srv.shutdown()
        reqtrace.uninstall(prof)


# ----------------------------------------------------------------------
# observer composition: install must not clobber an existing observer
def test_observer_composition_and_restore():
    seen = []
    trace_mod.set_span_observer(
        lambda name, cat, t0, t1, th, args: seen.append(name)
    )
    prof = reqtrace.install(RequestProfiler())
    with span("queue_wait", cat="req", req="req-000001"):
        pass
    prof.on_span  # both sides of the composition saw the span:
    assert seen == ["queue_wait"]
    assert prof.summary()["stages"]["queue_wait"]["count"] == 1
    reqtrace.uninstall(prof)
    # the previous observer is restored, not dropped
    with span("kv_reserve", cat="req", req="req-000002"):
        pass
    assert seen == ["queue_wait", "kv_reserve"]
    assert prof.summary()["stages"]["kv_reserve"]["count"] == 0


# ----------------------------------------------------------------------
# fleet bundle: host-tagged rids fold without cross-host merging
def test_fleet_host_tagged_merge(tmp_path):
    rr = _load_tool("request_report")
    recs = []
    for host, decode_ms in (("a", 2.0), ("b", 40.0)):
        recs += [
            {"kind": "span", "name": "queue_wait", "cat": "req",
             "ts_s": 0.0, "dur_ms": 1.0, "thread": "t",
             "args": {"req": "req-000001", "replica": 0}, "host": host},
            {"kind": "span", "name": "prefill", "cat": "gen",
             "ts_s": 0.001, "dur_ms": 2.0, "thread": "t",
             "args": {"req": "req-000001"}, "host": host},
            {"kind": "span", "name": "decode_step", "cat": "gen",
             "ts_s": 0.003, "dur_ms": decode_ms, "thread": "t",
             "args": {"reqs": ["req-000001"], "active": 1}, "host": host},
            {"kind": "span", "name": "request", "cat": "req",
             "ts_s": 0.0, "dur_ms": 3.0 + decode_ms, "thread": "t",
             "args": {"req": "req-000001", "outcome": "done",
                      "tokens": 4}, "host": host},
        ]
    recs.append(
        {"kind": "instant", "name": "shed", "cat": "req", "t_s": 0.02,
         "thread": "t", "args": {"cause": "queue_full"}, "host": "b"}
    )
    p = tmp_path / "bundle.runlog.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")

    spans, sheds = rr.load_records(str(p))
    prof = rr.fold(spans, sheds)
    rep = rr.report(prof, top=10)
    s = rep["summary"]
    # two hosts' identical rids stay TWO requests, host-qualified
    assert s["requests_profiled"] == 2
    rids = {r["rid"] for r in rep["slowest"]}
    assert rids == {"a/req-000001", "b/req-000001"}
    assert rep["slowest"][0]["rid"] == "b/req-000001"  # slowest first
    assert s["sheds"] == {"queue_full": 1}
    # the rendered table carries the same qualified ids
    text = rr.render(rep)
    assert "b/req-000001" in text and "queue_full" in text


def test_offline_report_matches_live_fold(engine, tmp_path):
    """One folding implementation: replaying the run's spans through
    tools/request_report.py must reproduce the LIVE profiler's summary
    (same entry points, same numbers)."""
    rr = _load_tool("request_report")
    records = []

    def recorder(name, cat, t0, t1, thread, args):
        records.append({
            "kind": "span", "name": name, "cat": cat, "ts_s": t0,
            "dur_ms": (t1 - t0) * 1e3, "thread": thread,
            "args": dict(args or {}),
        })

    trace_mod.set_span_observer(recorder)
    live = reqtrace.install(RequestProfiler(export_every=1 << 30))
    sb = StreamBatcher(engine, max_queue=4)
    try:
        sts = [sb.submit_stream([1 + i, 7, 3], 4) for i in range(3)]
        for st in sts:
            assert st.result(timeout=60.0)["event"] == "done"
    finally:
        sb.stop(drain=True, timeout=30.0)
        reqtrace.uninstall(live)
    p = tmp_path / "run.trace.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    offline = rr.fold(*rr.load_records(str(p)))
    ls, os_ = live.summary(), offline.summary()
    assert os_["requests_profiled"] == ls["requests_profiled"] == 3
    assert os_["verdict"] == ls["verdict"]
    # float round-trips through dur_ms keep 3-decimal-ms agreement
    for stage in ("queue_wait", "prefill", "decode"):
        assert os_["stages"][stage]["count"] == ls["stages"][stage]["count"]
        assert abs(
            os_["stages"][stage]["p50_ms"] - ls["stages"][stage]["p50_ms"]
        ) < 0.01
    assert rr.main([str(p), "--json"]) == 0
