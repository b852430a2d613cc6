"""Engine-parity subsystem tests: binary weight I/O, checkpoint/resume,
signals, profiler, training log.

The key invariant (reference: ``test_gradient_based_solver.cpp:179-211``
snapshot tests): training tau, snapshotting, restoring, then training tau
more must equal training 2*tau straight through — including solver history.
"""

import os
import signal

import numpy as np
import pytest
import jax

from sparknet_tpu import config
from sparknet_tpu.io import caffemodel, checkpoint, wire
from sparknet_tpu.solver import Solver

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NET = """
name: "ckpt_net"
layer { name: "data" type: "HostData" top: "x" top: "label"
  java_data_param { shape { dim: 8 dim: 4 } shape { dim: 8 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
  inner_product_param { num_output: 8 weight_filler { type: "xavier" } } }
layer { name: "bn" type: "BatchNorm" bottom: "h" top: "hb" }
layer { name: "ip2" type: "InnerProduct" bottom: "hb" top: "logits"
  inner_product_param { num_output: 3 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""


def _solver(type_=""):
    sp = config.parse_solver_prototxt(
        f'base_lr: 0.05 lr_policy: "fixed" momentum: 0.9 {type_}'
    )
    return Solver(sp, net_param=config.parse_net_prototxt(NET))


def _batches(tau, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": rng.randn(tau, 8, 4).astype(np.float32),
        "label": rng.randint(0, 3, (tau, 8)).astype(np.float32),
    }


def test_wire_varint_roundtrip():
    for v in [0, 1, 127, 128, 300, 2**32, 2**63 - 1]:
        enc = wire.encode_varint(v)
        dec, pos = wire.decode_varint(memoryview(enc), 0)
        assert dec == v and pos == len(enc)


def test_blob_roundtrip():
    arr = np.random.RandomState(0).randn(4, 3, 2).astype(np.float32)
    dec = caffemodel.decode_blob(caffemodel.encode_blob(arr))
    np.testing.assert_array_equal(dec, arr)


def test_caffemodel_roundtrip(tmp_path):
    blobs = {
        "conv1": [
            np.random.RandomState(1).randn(8, 3, 5, 5).astype(np.float32),
            np.zeros(8, np.float32),
        ],
        "fc": [np.random.RandomState(2).randn(10, 128).astype(np.float32)],
    }
    path = str(tmp_path / "w.caffemodel")
    caffemodel.save_weights(blobs, path)
    loaded = caffemodel.load_weights(path)
    assert set(loaded) == {"conv1", "fc"}
    for k in blobs:
        for a, b in zip(blobs[k], loaded[k]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fmt", ["BINARYPROTO", "HDF5"])
def test_async_checkpointer_matches_sync(tmp_path, fmt):
    """AsyncCheckpointer writes the same restorable snapshot as the sync
    path, keeps training unblocked, and publishes atomically."""
    s = _solver()
    st = s.init_state(0)
    st, _ = s.step(st, _batches(5, 0))

    sync_paths = checkpoint.snapshot(
        s, st, str(tmp_path / "sync"), fmt=fmt
    )
    ckpt = checkpoint.AsyncCheckpointer()
    ckpt.save(s, st, str(tmp_path / "async"), fmt=fmt)
    # training continues while the write is in flight
    st2, _ = s.step(st, _batches(5, 1))
    model_path, state_path = ckpt.wait()
    assert os.path.exists(model_path) and os.path.exists(state_path)
    # no temp files survive the publish
    assert not [
        f for f in os.listdir(tmp_path) if ".tmp-" in f
    ]

    # the async snapshot restores to the exact pre-save state
    s_sync, s_async = _solver(), _solver()
    st_sync = checkpoint.restore(s_sync, sync_paths[1])
    st_async = checkpoint.restore(s_async, state_path)
    for a, b in zip(
        jax.tree_util.tree_leaves((st_sync.params, st_sync.history)),
        jax.tree_util.tree_leaves((st_async.params, st_async.history)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ... and continuing from it matches continuing from the live state
    st_resumed, _ = s_async.step(st_async, _batches(5, 1))
    np.testing.assert_allclose(
        np.asarray(st_resumed.params["ip1"][0]),
        np.asarray(st2.params["ip1"][0]),
        rtol=1e-6,
        atol=1e-7,
    )


def test_async_checkpointer_propagates_errors(tmp_path):
    s = _solver()
    st = s.init_state(0)
    ckpt = checkpoint.AsyncCheckpointer()
    blocked = tmp_path / "not_a_dir"
    blocked.write_text("file where a directory is needed")
    ckpt.save(s, st, str(blocked / "prefix"))
    with pytest.raises(OSError):
        ckpt.wait()
    # a failed write leaves the checkpointer usable
    ckpt.save(s, st, str(tmp_path / "ok"))
    assert ckpt.wait() is not None


def test_mean_image_roundtrip(tmp_path):
    mean = np.random.RandomState(0).rand(3, 32, 32).astype(np.float32)
    path = str(tmp_path / "mean.binaryproto")
    caffemodel.save_mean_image(mean, path)
    np.testing.assert_allclose(caffemodel.load_mean_image(path), mean)


@pytest.mark.parametrize("fmt", ["BINARYPROTO", "HDF5"])
def test_snapshot_restore_continues_exactly(tmp_path, fmt):
    prefix = str(tmp_path / "snap")
    batches = _batches(5)
    # straight-through run: 10 iters
    s_ref = _solver()
    st_ref = s_ref.init_state(0)
    st_ref, _ = s_ref.step(st_ref, _batches(5, 0))
    st_ref, _ = s_ref.step(st_ref, _batches(5, 1))
    final_ref = np.asarray(st_ref.params["ip1"][0])

    # snapshot mid-way, restore in a FRESH solver, continue
    s_a = _solver()
    st_a = s_a.init_state(0)
    st_a, _ = s_a.step(st_a, _batches(5, 0))
    model_path, state_path = checkpoint.snapshot(s_a, st_a, prefix, fmt=fmt)
    assert os.path.exists(model_path) and os.path.exists(state_path)
    if fmt == "HDF5":
        assert model_path.endswith(".h5") and state_path.endswith(".h5")

    s_b = _solver()
    st_b = checkpoint.restore(s_b, state_path)
    assert int(st_b.iter) == 5
    st_b, _ = s_b.step(st_b, _batches(5, 1))
    np.testing.assert_allclose(
        np.asarray(st_b.params["ip1"][0]), final_ref, rtol=1e-6
    )
    # BN stats restored too
    np.testing.assert_allclose(
        np.asarray(st_b.stats["bn"][0]),
        np.asarray(st_ref.stats["bn"][0]),
        rtol=1e-6,
    )


def test_weights_warm_start(tmp_path):
    s = _solver()
    st = s.init_state(0)
    st, _ = s.step(st, _batches(3))
    blobs = caffemodel.net_blobs(s.net, st.params, st.stats)
    path = str(tmp_path / "warm.caffemodel")
    caffemodel.save_weights(blobs, path)

    s2 = _solver()
    st2 = s2.init_state(seed=42)  # different init
    st2 = checkpoint.load_weights_into_state(s2, st2, path)
    np.testing.assert_allclose(
        np.asarray(st2.params["ip1"][0]), np.asarray(st.params["ip1"][0])
    )
    assert int(st2.iter) == 0  # iter untouched by warm start


def test_legacy_4d_blob_shapes_right_align(tmp_path):
    """BVLC-era files store IP weights as (1,1,M,N) and biases as (1,1,1,N);
    Blob::ShapeEquals right-aligns them (blob.cpp:390-404) — loading such a
    file must succeed, not shape-mismatch."""
    net = _solver().net
    params, stats = net.init(seed=0)
    w = np.random.RandomState(3).randn(8, 4).astype(np.float32)
    b = np.random.RandomState(4).randn(8).astype(np.float32)

    def legacy_blob(arr4d):
        return (
            wire.field_varint(1, arr4d.shape[0])
            + wire.field_varint(2, arr4d.shape[1])
            + wire.field_varint(3, arr4d.shape[2])
            + wire.field_varint(4, arr4d.shape[3])
            + wire.field_packed_floats(5, arr4d.reshape(-1))
        )

    layer_msg = (
        wire.field_string(1, "ip1")
        + wire.field_bytes(7, legacy_blob(w.reshape(1, 1, 8, 4)))
        + wire.field_bytes(7, legacy_blob(b.reshape(1, 1, 1, 8)))
    )
    path = str(tmp_path / "legacy.caffemodel")
    with open(path, "wb") as f:
        f.write(wire.field_bytes(100, layer_msg))

    loaded = caffemodel.load_weights(path)
    params2, _ = caffemodel.apply_blobs(net, params, stats, loaded)
    np.testing.assert_array_equal(params2["ip1"][0], w)
    np.testing.assert_array_equal(params2["ip1"][1], b)


def test_double_data_blob_decodes():
    arr = np.random.RandomState(0).randn(3, 2).astype(np.float64)
    msg = wire.field_bytes(
        7, wire.field_packed_varints(1, arr.shape)
    ) + wire.field_bytes(8, np.ascontiguousarray(arr, "<f8").tobytes())
    dec = caffemodel.decode_blob(msg)
    assert dec.dtype == np.float32
    np.testing.assert_allclose(dec, arr.astype(np.float32))


def test_blob_with_shape_but_no_data_raises():
    msg = wire.field_bytes(7, wire.field_packed_varints(1, (2, 3)))
    with pytest.raises(ValueError, match="no data"):
        caffemodel.decode_blob(msg)


def test_apply_blobs_shape_mismatch_raises():
    s = _solver()
    st = s.init_state(0)
    bad = {"ip1": [np.zeros((7, 7), np.float32), np.zeros(8, np.float32)]}
    with pytest.raises(ValueError, match="shape"):
        caffemodel.apply_blobs(s.net, st.params, st.stats, bad)
    # unknown layer names are skipped silently (CopyTrainedLayersFrom)
    p, _ = caffemodel.apply_blobs(
        s.net, st.params, st.stats, {"nonexistent": [np.zeros(3)]}
    )


def test_signal_handler():
    from sparknet_tpu.utils import SignalHandler, SolverAction

    h = SignalHandler()
    assert h.get_action() == SolverAction.NONE
    os.kill(os.getpid(), signal.SIGHUP)
    assert h.get_action() == SolverAction.SNAPSHOT
    assert h.get_action() == SolverAction.NONE  # cleared after poll
    os.kill(os.getpid(), signal.SIGINT)
    os.kill(os.getpid(), signal.SIGHUP)
    assert h.get_action() == SolverAction.STOP  # STOP wins
    assert h.get_action() == SolverAction.SNAPSHOT
    h.restore()


def test_profiler_runs():
    from sparknet_tpu.net import JaxNet
    from sparknet_tpu.utils.profiler import format_profile, profile_net

    net = JaxNet(config.parse_net_prototxt(NET), phase="TRAIN")
    params, stats = net.init(0)
    batch = {k: v[0] for k, v in _batches(1).items()}
    batch = {"x": batch["x"], "label": batch["label"]}
    result = profile_net(net, params, stats, batch, iterations=2)
    assert set(result["layers"]) == {"ip1", "bn", "ip2", "loss"}
    assert result["total_fwdbwd_ms"] > 0
    report = format_profile(result)
    assert "ip1" in report and "fused whole-net" in report


def test_training_log(tmp_path):
    from sparknet_tpu.utils import TrainingLog

    log = TrainingLog(directory=str(tmp_path), tag="t", echo=False)
    log.log("hello phase")
    log.close()
    content = open(log.path).read()
    assert "hello phase" in content
    # "elapsed: message" format like CifarApp.scala:44
    assert content.split(":")[0].replace(".", "").isdigit()


def test_cpu_timer_lifecycle_and_units():
    """CPUTimer (utils/timers.py): start/stop semantics, the has-run
    flag, unit conversions, and idempotent stop."""
    import time

    from sparknet_tpu.utils.timers import CPUTimer

    t = CPUTimer()
    assert t.has_run_at_least_once is False
    assert t.milli_seconds() == 0.0
    assert t.stop() is t  # stop before start: a no-op, not a crash
    assert t.has_run_at_least_once is False
    t.start()
    time.sleep(0.01)
    t.stop()
    assert t.has_run_at_least_once is True
    assert t.seconds() >= 0.01
    assert t.milli_seconds() == pytest.approx(t.seconds() * 1e3)
    assert t.micro_seconds() == pytest.approx(t.seconds() * 1e6)
    # a second stop without a start keeps the previous reading
    prev = t.seconds()
    t.stop()
    assert t.seconds() == prev
    # restart overwrites, not accumulates (the reference's semantics)
    t.start()
    t.stop()
    assert t.seconds() < prev


def test_device_timer_syncs_on_given_arrays(monkeypatch):
    """Timer (the device-sync path): stop() must block on the sync_on
    arrays BEFORE reading the clock — the cudaEvent-timer analog.  The
    wiring is asserted deterministically (block_until_ready called with
    exactly the sync target, before the clock read), plus a live run
    against a real dispatched computation."""
    import time

    import jax.numpy as jnp

    from sparknet_tpu.utils import timers

    calls = []
    real_block = jax.block_until_ready

    def spy(x):
        calls.append(x)
        return real_block(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    target = jnp.arange(4.0)
    t = timers.Timer(sync_on=target)
    t.start()
    time.sleep(0.002)
    t.stop()
    assert calls == [target]  # synced on exactly the given arrays
    assert t.has_run_at_least_once and t.seconds() > 0
    monkeypatch.undo()

    # live: the timed window covers a real dispatched computation
    x = jnp.ones((256, 256))
    y = x @ x @ x
    t2 = timers.Timer(sync_on=y)
    t2.start()
    t2.stop()
    assert t2.has_run_at_least_once
    assert float(y[0, 0]) > 0  # the synced value is usable immediately

    # sync_on=None degrades to the pure wall-clock CPUTimer
    t3 = timers.Timer()
    t3.start()
    t3.stop()
    assert t3.has_run_at_least_once


def test_repo_root_log_hygiene():
    """Tier-1 runs must not litter the repo root with training_log_*.txt
    (regression guard for the PR-4 conftest tmpdir routing): the current
    repo-root log set must equal the session-start baseline, and a
    default TrainingLog must route into $SPARKNET_LOG_DIR, not the CWD."""
    import glob

    import conftest
    from sparknet_tpu.utils import TrainingLog

    assert os.environ.get("SPARKNET_LOG_DIR"), "conftest routing missing"
    now = frozenset(
        os.path.basename(p)
        for p in glob.glob(os.path.join(_REPO, "training_log_*.txt"))
    )
    new = now - conftest.REPO_ROOT_TRAINING_LOGS
    assert not new, f"tests wrote logs into the repo root: {sorted(new)}"
    log = TrainingLog(tag="hygiene_probe")
    try:
        assert os.path.dirname(os.path.abspath(log.path)) == (
            os.path.abspath(os.environ["SPARKNET_LOG_DIR"])
        )
        assert not os.path.abspath(log.path).startswith(_REPO + os.sep)
    finally:
        log.close()
        os.unlink(log.path)
