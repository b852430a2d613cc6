"""Unit tests for the serving subsystem: bucket selection, pad/demux
correctness (byte-equal with single-shot JaxNet.forward), the
no-recompile-after-warmup invariant, queue overflow, and the metrics
registry's Prometheus rendering."""

import threading

import numpy as np
import pytest

from sparknet_tpu import config
from sparknet_tpu.net import JaxNet
from sparknet_tpu.serve import (
    InferenceEngine,
    MetricsRegistry,
    MicroBatcher,
    QueueFull,
)
from sparknet_tpu.serve.metrics import Counter, Gauge, Histogram

TOY_DEPLOY = """
name: "toy"
input: "data"
input_shape { dim: 2 dim: 3 dim: 8 dim: 8 }
layer { name: "conv" type: "Convolution" bottom: "data" top: "conv"
  convolution_param { num_output: 4 kernel_size: 3 weight_filler { type: "xavier" } } }
layer { name: "ip" type: "InnerProduct" bottom: "conv" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "logits" top: "prob" }
"""

TOY_TRAIN_TEST = """
name: "toy_tt"
layer { name: "data" type: "HostData" top: "data" top: "label"
  java_data_param { shape { dim: 4 dim: 3 dim: 8 dim: 8 } shape { dim: 4 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "acc" type: "Accuracy" bottom: "logits" bottom: "label" top: "accuracy"
  include { phase: TEST } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(
        config.parse_net_prototxt(TOY_DEPLOY), buckets=(1, 4, 8)
    )
    eng.warmup()
    return eng


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_bucket_selection(engine):
    assert engine.bucket_for(1) == 1
    assert engine.bucket_for(2) == 4
    assert engine.bucket_for(4) == 4
    assert engine.bucket_for(5) == 8
    assert engine.bucket_for(8) == 8
    # beyond the top bucket: chunked by the caller at max_bucket
    assert engine.bucket_for(9) == 8
    with pytest.raises(ValueError):
        engine.bucket_for(0)


def test_padding_shapes(engine):
    x = np.ones((3, 3, 8, 8), np.float32)
    padded, n = engine.pad_to_bucket(x)
    assert n == 3 and padded.shape == (4, 3, 8, 8)
    assert np.array_equal(padded[:3], x)
    assert not padded[3:].any()  # zero pad rows


def test_infer_byte_equal_with_single_shot_forward(engine):
    """Serving outputs must be BYTE-EQUAL to JaxNet.forward at the same
    bucket shape — padding rows change nothing for the real rows."""
    import jax

    net = JaxNet(config.parse_net_prototxt(TOY_DEPLOY), phase="TEST")
    x = np.random.RandomState(0).randn(6, 3, 8, 8).astype(np.float32)
    out = engine.infer(x)
    padded, _ = engine.pad_to_bucket(x)
    ref = np.asarray(
        jax.jit(net.forward)(
            engine.params, engine.stats, {"data": padded}
        )["prob"]
    )[:6]
    assert out.dtype == ref.dtype
    assert np.array_equal(out, ref)


def test_infer_single_item_and_oversized(engine):
    one = engine.infer(np.zeros((3, 8, 8), np.float32))  # no batch dim
    assert one.shape == (1, 5)
    big = engine.infer(np.zeros((19, 3, 8, 8), np.float32))  # > max bucket
    assert big.shape == (19, 5)


def test_no_recompile_after_warmup(engine):
    before = engine.jit_cache_size()
    assert before == len(engine.buckets)
    for n in (1, 2, 3, 5, 8, 11):
        engine.infer(np.zeros((n, 3, 8, 8), np.float32))
    assert engine.jit_cache_size() == before


def test_train_test_config_derives_deploy_view():
    eng = InferenceEngine(
        config.parse_net_prototxt(TOY_TRAIN_TEST), buckets=(1, 2)
    )
    # the deploy view has a single data feed and a prob head
    assert eng.data_blob == "data"
    assert eng.output_blob == "prob"
    eng.warmup()
    out = eng.infer(np.zeros((2, 3, 8, 8), np.float32))
    assert out.shape == (2, 5)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)


def test_engine_rejects_bad_shapes(engine):
    with pytest.raises(ValueError):
        engine.run_padded(np.zeros((3, 3, 8, 8), np.float32))  # not a bucket
    with pytest.raises(ValueError):
        engine.run_padded(np.zeros((4, 3, 7, 7), np.float32))  # item shape
    with pytest.raises(ValueError):
        InferenceEngine(
            config.parse_net_prototxt(TOY_DEPLOY), buckets=(0, 4)
        )
    with pytest.raises(ValueError):
        InferenceEngine(
            config.parse_net_prototxt(TOY_DEPLOY), output_blob="nope"
        )


def test_engine_loads_caffemodel_weights(tmp_path):
    from sparknet_tpu.io import caffemodel

    eng0 = InferenceEngine(
        config.parse_net_prototxt(TOY_DEPLOY), buckets=(2,), seed=3
    )
    blobs = caffemodel.net_blobs(eng0.net, eng0.params, eng0.stats)
    path = str(tmp_path / "toy.caffemodel")
    caffemodel.save_weights(blobs, path)

    eng1 = InferenceEngine(
        config.parse_net_prototxt(TOY_DEPLOY), weights=path, buckets=(2,),
        seed=9,  # different init seed: weights must come from the file
    )
    x = np.random.RandomState(2).randn(2, 3, 8, 8).astype(np.float32)
    assert np.array_equal(eng0.infer(x), eng1.infer(x))


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

def test_batcher_demux_matches_single_shot(engine):
    # generous coalescing window: the assertion below needs at least one
    # coalesce to happen even on a loaded 2-core CI box
    mb = MicroBatcher(engine, max_queue=32, max_wait_ms=50.0)
    try:
        x = np.random.RandomState(1).randn(6, 3, 8, 8).astype(np.float32)
        ref = engine.infer(x)
        results = {}

        def client(i):
            results[i] = mb.submit(x[i])

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(6):
            assert results[i].shape == (1, 5)
            assert np.array_equal(results[i][0], ref[i]), i
        # concurrency coalesced: fewer batches than requests
        assert mb.m_batches.value < 6
        assert mb.m_images.value == 6
        assert mb.m_occupancy.count == mb.m_batches.value
        assert mb.m_latency.count == 6
    finally:
        mb.stop()


def test_closed_loop_clients_never_recompile(engine):
    """The serving contract across the live loop: closed-loop client
    threads firing single-image requests back to back (concurrency, not
    the client, fills the buckets) leave the jit cache where warm-up put
    it, every request is answered, and occupancy is a share."""
    clients, per_client = 6, 8
    mb = MicroBatcher(engine, max_queue=64, max_wait_ms=2.0)
    before = engine.jit_cache_size()
    x = np.random.RandomState(2).randn(3, 8, 8).astype(np.float32)
    ref = engine.infer(x[None])[0]
    errors = []

    def client():
        try:
            for _ in range(per_client):
                # whichever bucket the request rode in: same answer up to
                # the bucket program's float order
                np.testing.assert_allclose(
                    mb.submit(x, timeout=60.0)[0], ref, rtol=1e-5, atol=1e-6
                )
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(repr(e))

    try:
        threads = [threading.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert engine.jit_cache_size() == before == len(engine.buckets)
        assert mb.m_images.value == clients * per_client
        assert mb.m_latency.count == clients * per_client
        assert 0 < mb.m_occupancy.mean() <= 1.0
        lat = [mb.m_latency.quantile(q) for q in (0.50, 0.95, 0.99)]
        assert 0 < lat[0] <= lat[1] <= lat[2]
    finally:
        mb.stop()


def test_batcher_multi_item_requests(engine):
    mb = MicroBatcher(engine, max_queue=32, max_wait_ms=1.0)
    try:
        x = np.random.RandomState(4).randn(5, 3, 8, 8).astype(np.float32)
        out = mb.submit(x)
        assert np.array_equal(out, engine.infer(x))
        # oversized request (> max bucket) chunks transparently
        big = np.random.RandomState(5).randn(11, 3, 8, 8).astype(np.float32)
        assert np.array_equal(mb.submit(big), engine.infer(big))
    finally:
        mb.stop()


def test_batcher_queue_full_sheds(engine):
    mb = MicroBatcher(engine, max_queue=2, max_wait_ms=200.0)
    try:
        x = np.zeros((1, 3, 8, 8), np.float32)
        # fill the admission queue from background threads (they block in
        # submit), then overflow it synchronously
        for _ in range(2):
            threading.Thread(
                target=lambda: mb.submit(x), daemon=True
            ).start()
        deadline = 50
        while mb.queue_depth() < 2 and deadline:
            threading.Event().wait(0.01)
            deadline -= 1
        assert mb.queue_depth() == 2
        with pytest.raises(QueueFull):
            mb.submit(x)
        assert mb.m_shed.value == 1
    finally:
        mb.stop()


def test_batcher_drain_serves_queued_then_rejects(engine):
    mb = MicroBatcher(engine, max_queue=32, max_wait_ms=50.0)
    x = np.zeros((1, 3, 8, 8), np.float32)
    results = []
    threads = [
        threading.Thread(target=lambda: results.append(mb.submit(x)))
        for _ in range(3)
    ]
    for t in threads:
        t.start()
    while mb.queue_depth() < 3:
        threading.Event().wait(0.005)
    mb.stop(drain=True)  # drain: queued requests still get answers
    for t in threads:
        t.join(10.0)
    assert len(results) == 3
    with pytest.raises(RuntimeError):
        mb.submit(x)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram():
    c = Counter("c_total")
    c.inc()
    c.inc(2)
    assert c.value == 3
    g = Gauge("g")
    g.set(5)
    g.dec()
    assert g.value == 4
    h = Histogram("h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count == 3
    assert h.sum == pytest.approx(2.55)
    assert h.mean() == pytest.approx(0.85)
    assert h.quantile(0.0) == 0.05
    assert h.quantile(0.99) == 2.0


def test_histogram_quantiles_reservoir():
    h = Histogram("h", reservoir=100)
    for v in range(1, 101):
        h.observe(v / 100.0)
    assert h.quantile(0.5) == pytest.approx(0.51)
    assert h.quantile(0.95) == pytest.approx(0.96)


def test_histogram_quantile_sorts_once_per_scrape():
    """A scrape reading p50/p95/p99 must sort the reservoir ONCE (the
    cached sorted view is shared across consecutive quantile reads) and
    the next observation must invalidate it — with values consistent
    with a fresh nearest-rank computation throughout."""
    h = Histogram("h", reservoir=64)
    rng = __import__("random").Random(3)
    vals = [rng.random() for _ in range(64)]
    for v in vals:
        h.observe(v)
    p50, p95, p99 = h.quantile(0.50), h.quantile(0.95), h.quantile(0.99)
    # all three reads shared one sorted view (same list object)
    assert h._sorted is not None
    first_view = h._sorted
    assert h.quantile(0.95) == p95 and h._sorted is first_view
    # ordered and consistent with an independent nearest-rank compute
    ref = sorted(vals)
    assert p50 <= p95 <= p99
    assert p50 == ref[min(63, int(0.50 * 64))]
    assert p95 == ref[min(63, int(0.95 * 64))]
    assert p99 == ref[min(63, int(0.99 * 64))]
    # an observation invalidates the cache; the next read re-sorts
    h.observe(123.0)
    assert h._sorted is None
    assert h.quantile(0.99) == 123.0  # overwrote the oldest; new max
    assert h._sorted is not first_view


def test_registry_renders_prometheus_text():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "total requests")
    c.inc(7)
    reg.gauge("depth", "queue depth", fn=lambda: 3)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render()
    assert "# HELP requests_total total requests" in text
    assert "# TYPE requests_total counter" in text
    assert "requests_total 7" in text
    assert "depth 3" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    with pytest.raises(ValueError):
        reg.counter("depth")  # duplicate name


def test_signal_handler_sigterm_effect():
    """serve's graceful-drain hook: SIGTERM maps through utils/signals."""
    import os
    import signal

    from sparknet_tpu.utils.signals import SignalHandler, SolverAction

    h = SignalHandler(
        sigint_effect=SolverAction.NONE,
        sighup_effect=SolverAction.NONE,
        sigterm_effect=SolverAction.STOP,
    )
    try:
        assert h.get_action() == SolverAction.NONE
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.get_action() == SolverAction.STOP
        assert h.get_action() == SolverAction.NONE  # poll-and-clear
    finally:
        h.restore()
