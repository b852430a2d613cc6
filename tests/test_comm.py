"""Comm-plane tests (``parallel/comm.py``): delta-quantized chunked
collectives, error-feedback residuals, masked-worker semantics, the
overlap schedule, and the pinned int8 loss band.

Key contracts:
- the DEFAULT path (compress=none, overlap off) never builds a comm
  plane — it runs the same fused program as the pre-comm trainer
  (bit-identity by construction, asserted structurally AND bitwise),
- fp32 comm-plane averaging matches the fused round numerically,
- a dead (live_mask) or sentry-masked (audit) worker contributes
  exactly ZERO to every chunk, its slot receives the survivor
  consensus, and its error-feedback residual resets on rejoin
  (mirroring the momentum-zeroing rejoin contract),
- the int8 leg's final loss lands inside the pinned band
  (``comm.LOSS_BAND``).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu import obs
from sparknet_tpu.parallel import (
    ParameterAveragingTrainer,
    comm,
    leading_sharding,
    make_mesh,
    replicated_sharding,
    shard_leading,
)

from tests.test_parallel import _data, _solver


@pytest.fixture(autouse=True)
def _reset_obs():
    yield
    obs._reset_training_metrics_for_tests()


def _mesh(n=4):
    return make_mesh({"dp": n}, devices=jax.devices()[:n])


def _run_rounds(mesh, data, rounds=3, live_masks=None, audit=False, **kw):
    solver = _solver(momentum=0.9)
    if audit:
        solver.audit = True
    trainer = ParameterAveragingTrainer(solver, mesh, **kw)
    st = trainer.init_state(seed=0)
    out = None
    for r in range(rounds):
        live = live_masks[r] if live_masks else None
        out = trainer.round(st, shard_leading(data, mesh), live_mask=live)
        st = out[0]
    st = trainer.finalize(st)
    return trainer, st, out


def test_default_path_builds_no_comm_plane_and_is_bit_identical():
    """compress=none + overlap off is the fused pre-comm round: no comm
    plane is constructed, and an explicitly-defaulted trainer is
    BITWISE identical to the implicit default."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    t_default, st_default, _ = _run_rounds(mesh, data)
    t_explicit, st_explicit, _ = _run_rounds(
        mesh, data, compress="none", overlap_avg=False
    )
    assert t_default._comm is None and t_explicit._comm is None
    for a, b in zip(
        jax.tree_util.tree_leaves(st_default),
        jax.tree_util.tree_leaves(st_explicit),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fp32_comm_plane_matches_fused_round():
    """Chunked fp32 delta averaging == the fused psum round up to
    float reassociation (anchor + mean(theta - anchor) vs mean(theta))."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    _, st_ref, _ = _run_rounds(mesh, data)
    t, st, _ = _run_rounds(mesh, data, compress="fp32")
    assert t._comm is not None
    assert len(t._comm._chunk_slices) >= 2  # genuinely chunked
    for a, b in zip(
        jax.tree_util.tree_leaves(st_ref.params),
        jax.tree_util.tree_leaves(st.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_quantized_modes_track_fused_round(mode):
    """Error-feedback delta quantization stays near the fp32 trajectory
    on the toy protocol (multi-round, momentum on)."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    _, st_ref, _ = _run_rounds(mesh, data, rounds=4)
    _, st, _ = _run_rounds(mesh, data, rounds=4, compress=mode)
    ref = np.asarray(st_ref.params["ip1"][0][0])
    got = np.asarray(st.params["ip1"][0][0])
    assert np.max(np.abs(got - ref)) < 5e-3
    # all worker slots hold the identical consensus (barriered rounds
    # end consistent, quantized or not)
    slots = np.asarray(st.params["ip1"][0])
    for w in range(1, 4):
        np.testing.assert_array_equal(slots[w], slots[0])


def test_dead_worker_contributes_zero_and_gets_consensus():
    """A live_mask-dead worker is excluded from the quantized average
    (its garbage never reaches any chunk) and its slot lands on the
    survivor consensus — within quantization distance of the fused
    masked round."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    mask = np.array([1, 1, 0, 1], np.float32)
    _, st_ref, _ = _run_rounds(mesh, data, rounds=1, live_masks=[mask])
    t, st, _ = _run_rounds(
        mesh, data, rounds=1, live_masks=[mask], compress="int8"
    )
    ref = np.asarray(st_ref.params["ip1"][0])
    got = np.asarray(st.params["ip1"][0])
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - ref)) < 1e-3
    slots = np.asarray(st.params["ip2"][0])
    for w in range(1, 4):
        np.testing.assert_array_equal(slots[w], slots[0])


def test_masked_worker_zero_in_every_chunk_directly():
    """Chunk-level proof: a NaN-poisoned masked worker's payload is
    where()'d out of EVERY chunk's reduce — the mean equals the
    survivors' mean and stays finite."""
    mesh = _mesh(4)
    data = _data(4, 2, seed=7)
    t, st, _ = _run_rounds(mesh, data, rounds=1, compress="fp32")
    plane = t._comm
    leaves = plane._comm_leaves(st)
    # craft per-worker deltas: worker 2 poisoned with NaN
    rng = np.random.RandomState(0)
    q = []
    for x in leaves:
        v = rng.randn(*x.shape).astype(np.float32)
        v[2] = np.nan
        q.append(jax.device_put(v, leading_sharding(mesh)))
    scales = [jnp.zeros((x.shape[0],), jnp.float32) for x in leaves]
    alive = jax.device_put(
        np.array([1, 1, 0, 1], np.float32), leading_sharding(mesh)
    )
    assert len(plane._chunk_slices) >= 2
    for sl in plane._chunk_slices:
        idx = tuple(range(sl.start, sl.stop))
        means, denom0 = plane._allreduce(
            tuple(q[sl]), tuple(scales[sl]), alive, idx
        )
        assert float(denom0) == 3.0
        for j, m in zip(idx, means):
            host = np.asarray(q[j])
            expect = host[[0, 1, 3]].mean(axis=0)
            got = np.asarray(m)
            assert np.isfinite(got).all()
            np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_residual_resets_on_rejoin():
    """The error-feedback residual of an excluded worker resets when it
    rejoins (receives the consensus), mirroring the momentum-zeroing
    contract; survivors keep their residuals."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    mask = np.array([1, 1, 0, 1], np.float32)
    t, st, _ = _run_rounds(
        mesh, data, rounds=1, live_masks=[mask], compress="int8"
    )
    res = [np.asarray(r) for r in t._comm._resid]
    assert all((r[2] == 0).all() for r in res)
    assert any((r[w] != 0).any() for r in res for w in (0, 1, 3))


def test_audit_masked_worker_momentum_and_residual_zeroed():
    """Sentry-masked (in-graph audit) worker x quantized delta: masked
    flag raised, zero contribution, momentum history AND residual
    zeroed, slot rejoins on the consensus — and the astats contract
    (masked key) matches the fused round's."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    data = {k: v.copy() for k, v in data.items()}
    data["x"][2, 1, 0, 0] = np.nan  # poison worker 2's window
    _, st_ref, out_ref = _run_rounds(mesh, data, rounds=1, audit=True)
    t, st, out = _run_rounds(
        mesh, data, rounds=1, audit=True, compress="int8"
    )
    astats = out[2]
    np.testing.assert_array_equal(
        np.asarray(astats["masked"]), np.asarray(out_ref[2]["masked"])
    )
    np.testing.assert_array_equal(
        np.asarray(astats["masked"]), np.array([0, 0, 1, 0], np.float32)
    )
    got = np.asarray(st.params["ip1"][0])
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - np.asarray(st_ref.params["ip1"][0]))) < 1e-3
    hist = np.asarray(st.history["ip1"][0])
    assert (hist[2] == 0).all() and (hist[0] != 0).any()
    res = [np.asarray(r) for r in t._comm._resid]
    assert all((r[2] == 0).all() for r in res)


@pytest.mark.parametrize("compress", ["bf16", "int8"])
def test_audit_is_a_pure_readout_under_compression(compress):
    """``--health`` beside ``--compress``: the comm plane's audited local
    program only reads, so the audited trajectory (params, per-worker
    momentum, iteration) is BITWISE the unaudited one, and a healthy
    fleet masks nobody."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    _, off, _ = _run_rounds(mesh, data, compress=compress)
    _, on, out = _run_rounds(mesh, data, audit=True, compress=compress)
    la = jax.tree_util.tree_leaves(off)
    lb = jax.tree_util.tree_leaves(on)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(out[2]["masked"]).any()


def test_overlap_degrades_to_barriered_on_masked_round():
    """An overlapped round with a dead worker falls back to the strict
    barriered apply (identical result, nothing left in flight)."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    mask = np.array([1, 1, 0, 1], np.float32)
    _, st_bar, _ = _run_rounds(
        mesh, data, rounds=1, live_masks=[mask], compress="int8"
    )
    t, st_ov, _ = _run_rounds(
        mesh, data, rounds=1, live_masks=[mask], compress="int8",
        overlap_avg=True,
    )
    assert not t._comm.has_pending
    for a, b in zip(
        jax.tree_util.tree_leaves(st_bar.params),
        jax.tree_util.tree_leaves(st_ov.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overlap_finalize_lands_last_average():
    """After finalize() every worker sits on the consensus (the overlap
    correction ``x + (mean - own_delta)`` equals ``anchor + mean`` in
    exact math; per-worker reassociation leaves ULP-level drift, so the
    assert is a tight allclose, not bitwise), and the trajectory matches
    the barriered fp32 run that applied each average in-line (same
    math, different schedule edge: here the last window's average lands
    at finalize)."""
    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    t, st, _ = _run_rounds(
        mesh, data, rounds=3, compress="fp32", overlap_avg=True
    )
    assert not t._comm.has_pending
    slots = np.asarray(st.params["ip1"][0])
    for w in range(1, 4):
        np.testing.assert_allclose(
            slots[w], slots[0], rtol=1e-6, atol=1e-7
        )
    # and the consensus is a real average: close to the fused trainer's
    _, st_ref, _ = _run_rounds(mesh, data, rounds=3)
    assert np.max(
        np.abs(slots[0] - np.asarray(st_ref.params["ip1"][0][0]))
    ) < 5e-2


def test_broadcast_state_resets_comm_plane():
    """broadcast_state (rollback/rejoin/resume) drops the anchor, the
    in-flight collective, and zeroes residuals — a stale correction
    must never land on restored params."""
    from sparknet_tpu.parallel import first_worker

    mesh = _mesh(4)
    data = _data(4, 3, seed=5)
    solver = _solver(momentum=0.9)
    trainer = ParameterAveragingTrainer(
        solver, mesh, compress="int8", overlap_avg=True
    )
    st = trainer.init_state(seed=0)
    for r in range(2):
        st, _ = trainer.round(st, shard_leading(data, mesh))
    assert trainer._comm.has_pending
    single = first_worker(jax.device_get(st))
    restored = trainer.broadcast_state(single)
    assert not trainer._comm.has_pending
    assert trainer._comm._anchor is None
    assert all(
        (np.asarray(r) == 0).all() for r in trainer._comm._resid
    )
    # and training continues cleanly from the restored state
    restored, losses = trainer.round(restored, shard_leading(data, mesh))
    assert np.isfinite(np.asarray(losses)).all()


def test_collective_bytes_counter_ratios():
    """sparknet_collective_bytes_total: the fused fp32 path charges the
    ring-model payload; bf16 charges exactly 2x less and int8 ~4x less
    — minus the per-tensor f32 scale int8 honestly carries, which is
    VISIBLE on this toy model's tiny tensors (and negligible at
    cifar10_quick scale).  The charged
    value must equal the comm plane's own payload model exactly."""
    mesh = _mesh(2)
    data = _data(2, 2, seed=3)
    tm = obs.enable_training_metrics()
    per_mode = {}
    for mode in ("none", "bf16", "int8"):
        ctr = tm.collective_bytes.labels(mode)
        before = ctr.value
        kw = {} if mode == "none" else {"compress": mode}
        t, _, _ = _run_rounds(mesh, data, rounds=2, **kw)
        per_mode[mode] = (ctr.value - before) / 2
        if t._comm is not None:  # counter == the plane's model, exactly
            assert per_mode[mode] == t._comm.payload_bytes_per_round
    assert per_mode["none"] > 0
    assert per_mode["none"] / per_mode["bf16"] == pytest.approx(2.0, rel=0.01)
    assert 3.5 < per_mode["none"] / per_mode["int8"] <= 4.0


def test_average_span_breakdown_present():
    """The comm-plane round emits the span('average') breakdown:
    quantize/allreduce/dequantize nested in the round's trace."""
    from sparknet_tpu.obs.trace import Tracer

    mesh = _mesh(2)
    data = _data(2, 2, seed=3)
    tracer = obs.install_tracer(Tracer())
    try:
        _run_rounds(mesh, data, rounds=2, compress="int8")
    finally:
        obs.uninstall_tracer()
    names = {}
    for e in tracer.events():
        if e.get("ph") == "X":
            names[e["name"]] = names.get(e["name"], 0) + 1
    assert names.get("average", 0) == 2
    assert names.get("quantize", 0) == 2
    assert names.get("dequantize", 0) == 2
    assert names.get("allreduce", 0) >= 2  # per chunk per round


def test_compress_rejects_unknown_mode():
    mesh = _mesh(2)
    with pytest.raises(ValueError, match="compress"):
        ParameterAveragingTrainer(_solver(), mesh, compress="int4")


def test_quant_error_telemetry_gauges():
    """Per-round quantization-error telemetry: int8/bf16 legs export a
    nonzero delta max-abs-err and a finite SNR gauge labeled by mode;
    the fp32-payload plane reads exactly-zero error at the 300 dB cap.
    The readout is dispatched in round r and landed at round r+1 (or at
    finalize) so it never syncs the dispatch path."""
    mesh = _mesh(2)
    data = _data(2, 2, seed=3)
    tm = obs.enable_training_metrics()
    for mode, lossy in (("int8", True), ("bf16", True), ("fp32", False)):
        t, _, _ = _run_rounds(mesh, data, rounds=3, compress=mode)
        # finalize flushed the last pending readout into the gauges
        err = tm.quant_error.labels(t._comm.compress).value
        snr = tm.quant_snr_db.labels(t._comm.compress).value
        if lossy:
            assert err > 0, mode
            assert 0 < snr < 300, mode
        else:
            assert err == 0.0
            assert snr == 300.0  # error underflowed to exactly 0
    # int8's coarser grid must show a worse SNR than bf16's
    assert (
        tm.quant_snr_db.labels("int8").value
        < tm.quant_snr_db.labels("bf16").value
    )


def test_quant_error_readout_returns_values():
    """flush_quant_error returns the readout dict (None when nothing is
    pending) — the surface bench/scaling legs read directly."""
    mesh = _mesh(2)
    data = _data(2, 2, seed=5)
    obs.enable_training_metrics()
    solver = _solver(momentum=0.9)
    trainer = ParameterAveragingTrainer(solver, mesh, compress="int8")
    st = trainer.init_state(seed=0)
    st, _ = trainer.round(st, shard_leading(data, mesh))
    # the round DISPATCHED the readout but deliberately did not sync it
    rec = trainer._comm.flush_quant_error()
    assert rec is not None
    assert rec["compress"] == "int8"
    assert rec["max_abs_err"] > 0
    assert np.isfinite(rec["snr_db"])
    # nothing pending anymore
    assert trainer._comm.flush_quant_error() is None


def test_cli_args_roundtrip():
    import argparse

    p = argparse.ArgumentParser()
    comm.add_cli_args(p)
    args = p.parse_args(["--compress", "int8", "--overlap_avg"])
    kw = comm.comm_kwargs_from_args(args)
    assert kw == {"compress": "int8", "overlap_avg": True}
    with pytest.raises(SystemExit):
        p.parse_args(["--compress", "fp64"])


def test_sharding_cache_keyed_on_mesh_identity():
    """Satellite: repeated trainer/mesh construction must not grow the
    sharding caches monotonically — they live ON the (interned) mesh
    object, and cache hits return the identical object."""
    sizes = []
    for _ in range(12):
        mesh = _mesh(2)
        solver = _solver()
        trainer = ParameterAveragingTrainer(solver, mesh)
        trainer.init_state(seed=0)
        assert leading_sharding(mesh, "dp") is leading_sharding(mesh, "dp")
        assert replicated_sharding(mesh) is replicated_sharding(mesh)
        cache = getattr(mesh, "_sparknet_shardings", None)
        assert cache is not None
        sizes.append(len(cache))
        # per-instance live-mask cache starts empty and holds only the
        # masks this trainer saw
        assert len(trainer._live_cache) <= 1
    assert len(set(sizes)) == 1, sizes  # flat, not monotonic


@pytest.mark.slow
def test_overlap_multihost_rejected(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(ValueError, match="single-process"):
        comm.CommPlane(_solver(), _mesh(2), "dp", overlap=True)


def _quick_trainer(batch, workers, audit=False, **kw):
    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.solver import Solver

    netp = cfg.replace_data_layers(
        models.load_model("cifar10_quick"),
        [(batch, 3, 32, 32), (batch,)],
        [(batch, 3, 32, 32), (batch,)],
    )
    solver = Solver(
        models.load_model_solver("cifar10_quick"), net_param=netp
    )
    mesh = make_mesh({"dp": workers}, devices=jax.devices()[:workers])
    return solver, ParameterAveragingTrainer(solver, mesh, **kw)


def test_int8_final_loss_inside_pinned_band(tmp_path):
    """Tier-1 acceptance smoke: on the cifar10_quick protocol the int8
    delta-averaged leg's final smoothed loss lands inside the pinned
    band (comm.LOSS_BAND) of the fp32 fused collective."""
    from sparknet_tpu.data import CifarLoader

    workers, tau, batch, rounds = 2, 2, 8, 5
    data_dir = str(tmp_path / "data")
    CifarLoader.write_synthetic(data_dir, num_train=128, num_test=16, seed=11)
    xs, ys = CifarLoader(data_dir).minibatches(batch, train=True)

    def window(r):
        n = len(xs)
        data = np.empty((workers, tau) + xs[0].shape, np.float32)
        label = np.empty((workers, tau, batch), np.float32)
        for w in range(workers):
            for t in range(tau):
                i = (r * workers * tau + w * tau + t) % n
                data[w, t] = xs[i]
                label[w, t] = ys[i]
        return {"data": data, "label": label}

    finals = {}
    for mode in ("none", "int8"):
        kw = {} if mode == "none" else {"compress": mode}
        solver, trainer = _quick_trainer(batch, workers, **kw)
        st = trainer.init_state(seed=0)
        for r in range(rounds):
            st, losses = trainer.round(st, window(r))
        jax.block_until_ready(losses)
        finals[mode] = float(solver.smoothed_loss)
    assert abs(finals["int8"] - finals["none"]) <= comm.LOSS_BAND, finals
