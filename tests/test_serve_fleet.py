"""Serving fleet + train-to-serve delivery (``serve/fleet.py``,
``serve/delivery.py``, ``serve/publish.py`` — ISSUE 12): reload
bit-identity, fleet-wide shed consistency at saturation, canary
rollback on seeded divergence, in-flight requests surviving a promote,
eject/respawn on replica death, the per-replica /healthz contract
(503 only when the WHOLE fleet is unservable), the verdict-gated
publisher, and the shared read-only manifest-verify helpers."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from sparknet_tpu import config
from sparknet_tpu.config import parse_solver_prototxt
from sparknet_tpu.io import checkpoint
from sparknet_tpu.serve import (
    DeliveryController,
    InferenceEngine,
    PublishRefused,
    QueueFull,
    ReplicaPool,
    Router,
    ServeServer,
    publish_snapshot,
)
from sparknet_tpu.serve import publish as publish_mod
from sparknet_tpu.solver import Solver

TOY_TRAIN = """
name: "toy"
layer { name: "data" type: "Input" top: "data" top: "label"
  input_param { shape { dim: 4 dim: 3 dim: 8 dim: 8 } shape { dim: 4 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""
TOY_DEPLOY = """
name: "toy"
input: "data"
input_shape { dim: 2 dim: 3 dim: 8 dim: 8 }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "logits"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "logits" top: "prob" }
"""

X = np.random.RandomState(0).randn(1, 3, 8, 8).astype(np.float32)


@pytest.fixture(scope="module")
def netp_deploy():
    return config.parse_net_prototxt(TOY_DEPLOY)


@pytest.fixture(scope="module")
def toy_solver():
    solver = Solver(
        parse_solver_prototxt('base_lr: 0.01 lr_policy: "fixed"'),
        net_param=config.parse_net_prototxt(TOY_TRAIN),
    )
    return solver, solver.init_state(seed=3)


def _make_engine_factory(netp):
    def make_engine(weights=None):
        return InferenceEngine(netp, weights=weights, buckets=(1, 4))

    return make_engine


def _fleet(netp, replicas=2, max_inflight=32, canary_frac=0.5,
           max_queue=64):
    pool = ReplicaPool(
        _make_engine_factory(netp), replicas=replicas, max_queue=max_queue
    )
    router = Router(
        pool, max_inflight=max_inflight, canary_frac=canary_frac
    )
    return pool, router


def _gate_engines(pool):
    """Wrap every replica's forward behind an Event so requests park
    deterministically (the saturation fixture)."""
    gate = threading.Event()
    for rep in pool.replicas:
        eng = rep.engine
        orig = eng.run_padded

        def run_padded(px, _orig=orig):
            gate.wait()
            return _orig(px)

        eng.run_padded = run_padded
    return gate


# ----------------------------------------------------------------------
# router: routing, shed consistency, eject/respawn


def test_pool_pins_replica_i_to_device_i(netp_deploy):
    """``devices=`` commits each replica's engine to its own device:
    the forward runs there from a thread with another default device,
    and serving after warm-up compiles nothing."""
    import jax

    devices = jax.devices()[:2]
    pool = ReplicaPool(
        _make_engine_factory(netp_deploy), replicas=3, devices=devices
    )
    try:
        for rep in pool.replicas:
            eng = rep.engine
            assert eng.device == devices[rep.index % 2]
            warm = eng.jit_cache_size()
            out = eng._fwd(eng.params, eng.stats, np.repeat(X, 4, axis=0))
            assert out.devices() == {eng.device}
            eng.infer(X)
            assert eng.jit_cache_size() == warm
    finally:
        pool.close()


def test_router_routes_and_matches_single_engine(netp_deploy):
    pool, router = _fleet(netp_deploy, replicas=2)
    try:
        out = router.submit(X)
        assert np.array_equal(out, pool.replicas[0].engine.infer(X))
        # both replicas serve the identical boot weights
        assert np.array_equal(out, pool.replicas[1].engine.infer(X))
    finally:
        router.close()


@pytest.mark.parametrize("replicas", [1, 2])
def test_shed_consistency_at_saturation(netp_deploy, replicas):
    """The fleet-wide bounded-admission contract: at a fixed offered
    load past saturation, the number of 429s is EXACTLY offered-bound
    regardless of the replica count — adding replicas never silently
    loosens admission."""
    offered, bound = 12, 4
    pool, router = _fleet(
        netp_deploy, replicas=replicas, max_inflight=bound
    )
    gate = _gate_engines(pool)
    codes = []
    lock = threading.Lock()

    def client():
        try:
            router.submit(X, timeout=60.0)
            c = 200
        except QueueFull:
            c = 429
        with lock:
            codes.append(c)

    threads = [
        threading.Thread(target=client, name=f"shed-{i}", daemon=True)
        for i in range(offered)
    ]
    try:
        for t in threads:
            t.start()
        deadline = time.time() + 30
        while len(codes) < offered - bound and time.time() < deadline:
            time.sleep(0.01)
        # while saturated: exactly offered - bound shed, none served
        assert codes.count(429) == offered - bound
        gate.set()
        for t in threads:
            t.join(60)
        assert codes.count(200) == bound
        assert codes.count(429) == offered - bound
    finally:
        gate.set()
        router.close()


def test_dead_replica_ejected_requests_retried_and_respawned(netp_deploy):
    pool, router = _fleet(netp_deploy, replicas=2)
    try:
        router.submit(X)
        pool.replicas[0].kill()
        # every request still answered (eject-and-retry, idempotent)
        for _ in range(4):
            assert router.submit(X).shape == (1, 5)
        assert pool.replicas[0].state == "ejected"
        assert int(pool.m_ejections.value) == 1
        rep = pool.respawn(0)
        assert rep.state == "live" and rep.healthy
        assert int(pool.m_respawns.value) == 1
        # the respawned replica serves the incumbent weights
        assert np.array_equal(
            rep.engine.infer(X), pool.replicas[1].engine.infer(X)
        )
    finally:
        router.close()


def test_whole_fleet_dead_is_unservable(netp_deploy):
    from sparknet_tpu.serve import FleetUnservable

    pool, router = _fleet(netp_deploy, replicas=2)
    try:
        pool.replicas[0].kill()
        pool.replicas[1].kill()
        with pytest.raises(FleetUnservable):
            router.submit(X)
    finally:
        router.close()


def test_fleet_metrics_render_on_shared_registry(netp_deploy):
    pool, router = _fleet(netp_deploy, replicas=2)
    try:
        router.submit(X)
        router.submit(X)
        pool.eject(1)
        text = pool.registry.render()
        assert 'sparknet_serve_replica_state{replica="0"} 0' in text
        assert 'sparknet_serve_replica_state{replica="1"} 2' in text
        # both requests landed somewhere in the per-replica family
        # (tie-breaks round-robin, so don't pin which child)
        served = sum(
            c.value for c in pool.m_requests.children()
        )
        assert served == 2
        assert "sparknet_serve_replica_requests_total" in text
        assert "sparknet_serve_replica_ejections_total 1" in text
        assert "serve_requests_total 2" in text  # the fleet sum
        assert "sparknet_delivery_canary_mirrors_total 0" in text
    finally:
        router.close()


# ----------------------------------------------------------------------
# hot reload: bit identity + in-flight survival


def _write_weights(netp, seed, path):
    """A .caffemodel with fresh seeded weights for the toy net."""
    from sparknet_tpu.io import caffemodel
    from sparknet_tpu.net import JaxNet

    net = JaxNet(netp, phase="TEST")
    params, stats = net.init(seed)
    caffemodel.save_weights(
        caffemodel.net_blobs(net, params, stats), path, net_name="toy"
    )
    return path


def test_promote_reload_bit_identity(netp_deploy, tmp_path):
    """The promoted fleet's outputs must EXACTLY equal a fresh engine
    loaded from the same snapshot — hot reload changes nothing but the
    weights."""
    w1 = _write_weights(netp_deploy, 11, str(tmp_path / "w1.caffemodel"))
    pool, router = _fleet(netp_deploy, replicas=2)
    try:
        before = router.submit(X)
        swapped = pool.promote(w1, publish_id="w1")
        assert swapped == 2
        assert pool.incumbent_id == "w1"
        after = router.submit(X)
        fresh = InferenceEngine(netp_deploy, weights=w1, buckets=(1, 4))
        fresh.warmup()
        assert np.array_equal(after, fresh.infer(X))
        assert not np.array_equal(before, after)
        # every replica swapped (shared-nothing: each owns its engine)
        for rep in pool.replicas:
            assert np.array_equal(rep.engine.infer(X), after)
    finally:
        router.close()


def test_inflight_requests_survive_promote(netp_deploy, tmp_path):
    """Zero dropped in-flight requests across a hot promote: requests
    admitted before/while the swap lands all complete (on whichever
    engine admitted their batch)."""
    w1 = _write_weights(netp_deploy, 12, str(tmp_path / "w1.caffemodel"))
    pool, router = _fleet(netp_deploy, replicas=2)
    # slow the forwards a little so the swap lands mid-stream
    for rep in pool.replicas:
        eng = rep.engine
        orig = eng.run_padded

        def run_padded(px, _orig=orig):
            time.sleep(0.01)
            return _orig(px)

        eng.run_padded = run_padded
    errors = []
    results = []
    lock = threading.Lock()

    def client(i):
        try:
            for _ in range(10):
                out = router.submit(X, timeout=60.0)
                with lock:
                    results.append(out)
        except BaseException as e:  # noqa: BLE001
            with lock:
                errors.append(repr(e))

    threads = [
        threading.Thread(target=client, args=(i,), name=f"pm-{i}",
                         daemon=True)
        for i in range(4)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.05)  # clients in flight
        pool.promote(w1, publish_id="w1")
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert len(results) == 40  # nothing dropped
        for out in results:
            assert out.shape == (1, 5)
        # steady state post-promote: the new weights serve
        fresh = InferenceEngine(netp_deploy, weights=w1, buckets=(1, 4))
        fresh.warmup()
        assert np.array_equal(router.submit(X), fresh.infer(X))
    finally:
        router.close()


# ----------------------------------------------------------------------
# delivery: publish gate, verify-reject, canary promote/rollback


def test_publish_refuses_failing_verdict(toy_solver, tmp_path):
    solver, state = toy_solver
    with pytest.raises(PublishRefused):
        publish_snapshot(
            solver, state, str(tmp_path),
            {"passing": False, "reason": "seeded failure"},
        )
    assert not os.listdir(tmp_path)  # nothing was written


def test_verdict_from_sentry_gates_on_health():
    from sparknet_tpu.obs.health import HealthSentry

    assert publish_mod.verdict_from_sentry(None)["passing"] is False
    s = HealthSentry(policy="warn")
    v = publish_mod.verdict_from_sentry(s)
    assert v["passing"] is False  # no rounds observed: no evidence
    s.rounds_observed = 5
    s.last_round = 4
    assert publish_mod.verdict_from_sentry(s)["passing"] is True
    s.last_anomaly_round = 4  # anomaly inside the cooldown window
    assert publish_mod.verdict_from_sentry(s)["passing"] is False
    s.last_anomaly_round = 1  # cold anomaly: cooled down
    assert publish_mod.verdict_from_sentry(s)["passing"] is True
    s.halted = True
    s.halt_reason = "seeded"
    assert publish_mod.verdict_from_sentry(s)["passing"] is False


def test_publish_attaches_verdict_to_manifest(toy_solver, tmp_path):
    solver, state = toy_solver
    verdict = {"passing": True, "reason": "seeded"}
    paths = publish_snapshot(solver, state, str(tmp_path), verdict)
    mpath = checkpoint.manifest_path_for(paths[1])
    with open(mpath) as f:
        manifest = json.load(f)
    assert manifest["verdict"]["passing"] is True
    # the manifest still CRC-verifies end to end (read-only helper)
    assert checkpoint.verify_manifest(mpath)["verdict"]["reason"] == (
        "seeded"
    )


def test_delivery_rejects_unverdicted_publish(
    netp_deploy, toy_solver, tmp_path
):
    """A publish without a passing verdict must be rejected BEFORE any
    engine is built — the watcher trusts only sentry-verified
    snapshots (require_passing=False models a rogue/legacy writer)."""
    solver, state = toy_solver
    publish_snapshot(
        solver, state, str(tmp_path),
        {"passing": False, "reason": "unverified"}, require_passing=False,
    )
    pool, router = _fleet(netp_deploy, replicas=1)
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
        )
        assert ctl.poll_once() == "rejected"
        assert ctl.rejected == 1 and router.canary is None
        assert ctl.phase == "idle"
    finally:
        router.close()


def test_delivery_rejects_corrupt_publish_at_verify(
    netp_deploy, toy_solver, tmp_path
):
    """Corrupt publish (size unchanged, bytes flipped) must be caught
    by the CRC verify and quarantined — it must NEVER be canaried."""
    from sparknet_tpu.runtime.chaos import corrupt_file

    solver, state = toy_solver
    paths = publish_snapshot(
        solver, state, str(tmp_path), {"passing": True, "reason": "ok"}
    )
    corrupt_file(paths[0], seed=9)
    pool, router = _fleet(netp_deploy, replicas=1)
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
        )
        assert ctl.poll_once() == "rejected"
        assert ctl.rejected == 1
        assert router.canary is None
        quarantined = ctl.last_decision["quarantined"]
        assert quarantined and all(
            q.endswith(".corrupt") for q in quarantined
        )
        # a later poll does not resurrect it
        assert ctl.poll_once() is None
    finally:
        router.close()


def _drive(ctl, router, pred, timeout_s=60.0):
    deadline = time.time() + timeout_s
    while not pred() and time.time() < deadline:
        router.submit(X)
        ctl.poll_once()
        time.sleep(0.02)
    assert pred(), ctl.status()


def test_canary_rollback_on_seeded_divergence(
    netp_deploy, toy_solver, tmp_path
):
    """A published snapshot whose outputs diverge past the bound must
    roll back automatically: canary cleared, publish quarantined,
    incumbent untouched — under live (finite!) divergence, not just
    NaN."""
    import jax

    solver, state = toy_solver
    # seeded divergence: params scaled far off — outputs move, stay
    # finite (exercises the divergence rule, not the nonfinite rule)
    bad_params = jax.tree_util.tree_map(
        lambda a: np.asarray(a) * np.float32(50.0),
        jax.device_get(state.params),
    )
    bad_state = state._replace(
        params=jax.device_put(bad_params),
        iter=np.asarray(7, np.int32),
    )
    publish_snapshot(
        solver, bad_state, str(tmp_path),
        {"passing": True, "reason": "forged: canary is the last line"},
    )
    pool, router = _fleet(netp_deploy, replicas=1, canary_frac=0.5)
    try:
        incumbent = router.submit(X)
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
            decision_requests=4, divergence_max=0.05,
        )
        assert ctl.poll_once() == "canary"
        assert ctl.phase == "canary"
        _drive(ctl, router, lambda: ctl.rollbacks == 1)
        d = ctl.last_decision
        assert d["action"] == "rolled_back"
        assert d["publish_id"] == "published_iter_7"
        assert "divergence" in d["why"]
        assert d["quarantined"]
        assert router.canary is None and ctl.phase == "idle"
        # the incumbent kept serving its own weights, bit-identical
        assert np.array_equal(router.submit(X), incumbent)
        assert int(pool.registry.get(
            "sparknet_delivery_rollbacks_total"
        ).value) == 1
    finally:
        router.close()


def test_delivery_promotes_good_publish(netp_deploy, toy_solver, tmp_path):
    solver, state = toy_solver
    paths = publish_snapshot(
        solver, state, str(tmp_path), {"passing": True, "reason": "ok"}
    )
    pool, router = _fleet(netp_deploy, replicas=1, canary_frac=0.5)
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
            decision_requests=4, divergence_max=10.0,
        )
        assert ctl.poll_once() == "canary"
        _drive(ctl, router, lambda: ctl.promotions == 1)
        assert pool.incumbent_id == "published_iter_0"
        fresh = InferenceEngine(
            netp_deploy, weights=paths[0], buckets=(1, 4)
        )
        fresh.warmup()
        assert np.array_equal(router.submit(X), fresh.infer(X))
    finally:
        router.close()


def test_watcher_promotes_under_client_traffic_dropping_nothing(
    netp_deploy, toy_solver, tmp_path
):
    """The delivery loop as it runs: client threads keep the two-replica
    fleet busy while the watcher verifies, warms off the serving path,
    canaries and promotes.  No client sees an error across the swap and
    the promoted fleet answers bit-identically to a fresh engine loaded
    from the published snapshot."""
    import jax

    solver, state = toy_solver
    state = state._replace(
        params=jax.tree_util.tree_map(
            lambda a: a * np.float32(1.01), state.params
        ),
        iter=np.asarray(5, np.int32),
    )
    paths = publish_snapshot(
        solver, state, str(tmp_path), {"passing": True, "reason": "ok"}
    )
    pool, router = _fleet(netp_deploy, replicas=2, canary_frac=0.5)
    stop = threading.Event()
    errors, answered = [], []
    lock = threading.Lock()

    def client():
        try:
            while not stop.is_set():
                out = router.submit(X, timeout=60.0)
                with lock:
                    answered.append(out.shape)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            with lock:
                errors.append(repr(e))

    threads = [
        threading.Thread(target=client, name=f"dl-{i}", daemon=True)
        for i in range(3)
    ]
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
            decision_requests=4, divergence_max=10.0,
        )
        for t in threads:
            t.start()
        _drive(ctl, router, lambda: ctl.promotions == 1)
        served_at_promote = len(answered)
        _drive(ctl, router, lambda: len(answered) >= served_at_promote + 6)
        stop.set()
        for t in threads:
            t.join(60)
        assert not errors, errors[:3]
        assert answered and set(answered) == {(1, 5)}
        assert pool.incumbent_id == "published_iter_5"
        assert ctl.rollbacks == 0
        fresh = InferenceEngine(netp_deploy, weights=paths[0], buckets=(1, 4))
        fresh.warmup()
        assert np.array_equal(router.submit(X), fresh.infer(X))
    finally:
        stop.set()
        router.close()


# ----------------------------------------------------------------------
# the fleet /healthz contract


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return r.status, json.loads(r.read())


def test_fleet_healthz_per_replica_and_503_only_when_unservable(
    netp_deploy, toy_solver, tmp_path
):
    solver, state = toy_solver
    pool, router = _fleet(netp_deploy, replicas=2)
    ctl = DeliveryController(
        pool, router, str(tmp_path), cache_dir=str(tmp_path / "cache")
    )
    srv = ServeServer(router=router, delivery=ctl, port=0)
    srv.start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    try:
        status, body = _get(base, "/healthz")
        assert status == 200 and body["status"] == "ok"
        assert [r["state"] for r in body["replicas"]] == ["live", "live"]
        assert body["fleet"]["live"] == 2
        assert body["delivery"]["phase"] == "idle"
        assert body["delivery"]["promotions"] == 0

        # ONE replica draining/ejected: the fleet stays 200 (an LB must
        # not pull a healthy fleet for one replica's maintenance)
        pool.eject(0)
        status, body = _get(base, "/healthz")
        assert status == 200 and body["status"] == "ok"
        assert [r["state"] for r in body["replicas"]] == [
            "ejected", "live",
        ]
        # /predict still serves through the survivor
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"data": X[0].tolist()}).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200

        # the WHOLE fleet out -> 503 unservable (and /predict 503s)
        pool.replicas[1].kill()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base, "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "unservable"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") == "1"
    finally:
        srv.shutdown()


# ----------------------------------------------------------------------
# the shared read-only manifest-verify helpers (io/checkpoint.py)


def test_checkpoint_readonly_verify_helpers(toy_solver, tmp_path):
    from sparknet_tpu.runtime.chaos import corrupt_file

    solver, state = toy_solver
    model, statep = checkpoint.snapshot(
        solver, state, str(tmp_path / "snap")
    )
    mpath = checkpoint.manifest_path_for(statep)
    # verify_manifest: read-only, no solver, returns the manifest
    manifest = checkpoint.verify_manifest(mpath)
    assert os.path.basename(model) in manifest["files"]
    # bytes-level verify (the delivery watcher path)
    with open(model, "rb") as f:
        data = f.read()
    checkpoint.verify_bytes_entry(
        os.path.basename(model), data, manifest
    )
    with pytest.raises(checkpoint.SnapshotCorrupt):
        checkpoint.verify_bytes_entry(
            os.path.basename(model), data[:-1], manifest
        )
    with pytest.raises(checkpoint.SnapshotCorrupt):
        checkpoint.verify_bytes_entry("nope.caffemodel", data, manifest)
    # file-level verify catches a byte flip (size unchanged)
    corrupt_file(model, seed=1)
    with pytest.raises(checkpoint.SnapshotCorrupt):
        checkpoint.verify_manifest(mpath)
    # garbage manifests classify as corruption, not I/O
    with pytest.raises(checkpoint.SnapshotCorrupt):
        checkpoint.parse_manifest(b"not json")
    with pytest.raises(checkpoint.SnapshotCorrupt):
        checkpoint.parse_manifest(b'{"files": 3}')
    # no manifest at all: pre-manifest snapshots pass (None)
    assert checkpoint.verify_manifest(str(tmp_path / "missing.json")) is (
        None
    )
    # crc32_bytes/crc32_file agree (the one checksum convention shared
    # with the chunk cache)
    crc, size = checkpoint.crc32_file(statep)
    with open(statep, "rb") as f:
        assert checkpoint.crc32_bytes(f.read()) == crc


def test_serve_metrics_shim_still_importable():
    """The deprecation shim (one line) keeps external imports alive."""
    from sparknet_tpu.serve.metrics import (  # noqa: F401
        Counter,
        Gauge,
        Histogram,
        MetricsRegistry,
    )
    from sparknet_tpu.obs import metrics as obs_metrics

    assert Counter is obs_metrics.Counter
    assert MetricsRegistry is obs_metrics.MetricsRegistry


# ----------------------------------------------------------------------
# review-hardening regressions (round 15 post-review)


def test_window_timeout_is_inconclusive_not_condemning(
    netp_deploy, toy_solver, tmp_path
):
    """An idle server that gathers no canary evidence must bring the
    canary down WITHOUT quarantining the publish — a timeout is not
    corruption, and the trainer's artifacts must survive it."""
    solver, state = toy_solver
    paths = publish_snapshot(
        solver, state, str(tmp_path), {"passing": True, "reason": "ok"}
    )
    pool, router = _fleet(netp_deploy, replicas=1, canary_frac=0.5)
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
            decision_requests=8, window_timeout_s=0.2,
        )
        assert ctl.poll_once() == "canary"
        time.sleep(0.3)  # window expires with zero traffic mirrored
        deadline = time.time() + 10
        while ctl.rollbacks == 0 and time.time() < deadline:
            ctl.poll_once()
            time.sleep(0.02)
        d = ctl.last_decision
        assert d["action"] == "rolled_back"
        assert "inconclusive" in d["why"]
        assert d["quarantined"] == []  # nothing condemned
        # the publish files are intact on disk, un-renamed
        for p in paths:
            assert os.path.exists(p), p
        assert router.canary is None and ctl.phase == "idle"
    finally:
        router.close()


def test_stale_cache_entry_refreshes_on_republish(
    netp_deploy, toy_solver, tmp_path
):
    """A republish under the SAME name (same iter, new weights) must
    verify against the fresh store bytes even when an earlier watcher
    cached the old bytes under that name — stale entries refresh, the
    valid publish is never rejected."""
    solver, state = toy_solver
    publish_snapshot(
        solver, state, str(tmp_path), {"passing": True, "reason": "v1"}
    )
    pool, router = _fleet(netp_deploy, replicas=1, canary_frac=0.5)
    cache_dir = str(tmp_path / "cache")
    try:
        ctl1 = DeliveryController(
            pool, router, str(tmp_path), cache_dir=cache_dir
        )
        assert ctl1.poll_once() == "canary"  # v1 staged into the cache
        router.clear_canary()
        # republish the same iter with DIFFERENT weights (rerun)
        import jax

        state2 = state._replace(
            params=jax.device_put(jax.tree_util.tree_map(
                lambda a: np.asarray(a) + np.float32(0.5),
                jax.device_get(state.params),
            ))
        )
        publish_snapshot(
            solver, state2, str(tmp_path),
            {"passing": True, "reason": "v2"},
        )
        # a fresh watcher (restart) with the SAME cache dir must accept
        ctl2 = DeliveryController(
            pool, router, str(tmp_path), cache_dir=cache_dir
        )
        assert ctl2.poll_once() == "canary"
        assert ctl2.rejected == 0
    finally:
        router.close()


def test_rollback_quarantines_nested_publish_location(
    netp_deploy, toy_solver, tmp_path
):
    """A publish living in a subdirectory of the watch root must be
    quarantined AT its real location on rollback."""
    import jax

    solver, state = toy_solver
    bad_params = jax.tree_util.tree_map(
        lambda a: np.asarray(a) * np.float32(50.0),
        jax.device_get(state.params),
    )
    bad_state = state._replace(params=jax.device_put(bad_params))
    sub = tmp_path / "runA"
    paths = publish_snapshot(
        solver, bad_state, str(sub),
        {"passing": True, "reason": "forged"},
    )
    pool, router = _fleet(netp_deploy, replicas=1, canary_frac=0.5)
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),  # watching the PARENT root
            cache_dir=str(tmp_path / "cache"),
            decision_requests=4, divergence_max=0.05,
        )
        assert ctl.poll_once() == "canary"
        _drive(ctl, router, lambda: ctl.rollbacks == 1)
        moved = ctl.last_decision["quarantined"]
        assert moved, "condemned nested publish must be quarantined"
        for q in moved:
            assert os.path.dirname(q) == str(sub)
            assert os.path.exists(q)
        for p in paths:
            assert not os.path.exists(p), p  # renamed away
    finally:
        router.close()


def test_incompatible_publish_rejected_without_wedging(
    netp_deploy, tmp_path
):
    """Verified bytes that cannot build THIS fleet's engine (different
    net shapes) must reject cleanly — idle phase, no quarantine, the
    watcher keeps polling — never wedge in 'warming'."""
    wide_train = TOY_TRAIN.replace("num_output: 5", "num_output: 7")
    solver = Solver(
        parse_solver_prototxt('base_lr: 0.01 lr_policy: "fixed"'),
        net_param=config.parse_net_prototxt(wide_train),
    )
    paths = publish_snapshot(
        solver, solver.init_state(seed=0), str(tmp_path),
        {"passing": True, "reason": "wrong net"},
    )
    pool, router = _fleet(netp_deploy, replicas=1)
    try:
        ctl = DeliveryController(
            pool, router, str(tmp_path),
            cache_dir=str(tmp_path / "cache"),
        )
        assert ctl.poll_once() == "rejected"
        assert ctl.rejected == 1 and ctl.phase == "idle"
        assert "build failed" in ctl.last_decision["why"]
        assert ctl.last_decision["quarantined"] == []
        for p in paths:
            assert os.path.exists(p), p  # intact for a compatible fleet
        assert router.canary is None
        assert ctl.poll_once() is None  # not wedged, not re-looping
        # the fleet still serves
        assert router.submit(X).shape == (1, 5)
    finally:
        router.close()


def test_publish_is_atomic_with_verdict(toy_solver, tmp_path):
    """The first manifest a watcher can ever see carries the verdict
    (staged + renamed manifest-last); no staging residue remains."""
    solver, state = toy_solver
    publish_snapshot(
        solver, state, str(tmp_path), {"passing": True, "reason": "ok"}
    )
    entries = sorted(os.listdir(tmp_path))
    assert not any(e.startswith(".") for e in entries), entries
    assert len(entries) == 3  # model + state + manifest, nothing else
    mpath = [e for e in entries if e.endswith(".manifest.json")][0]
    with open(tmp_path / mpath) as f:
        assert json.load(f)["verdict"]["passing"] is True
