"""Bounded-staleness averaging (``parallel/stale.py``) semantics.

The load-bearing pins:

- **B=0 is the synchronous round, bitwise** — flat and two-tier, audit
  on and off: the degenerate path IS ``ParameterAveragingTrainer``
  (same jitted program via delegation), so ``--stale_bound 0`` can
  never drift from today's averaging.
- an absent worker's replica (params, BN stats, momentum, iter) is
  **bit-untouched** by a boundary it missed, and its loss rows are
  zeroed,
- the bound is hard: a live worker at ``lag >= B`` is FORCED into the
  boundary; a dead worker never is (it just goes maximally stale),
- arrivals carry ``discount ** lag`` weights; with ``discount=1.0``
  a full-arrival stale boundary matches the sync average numerically,
- under a two-tier hierarchy arrivals coarsen to slices (a slice
  arrives iff every live member did),
- the ledger (``worker_rounds`` / ``export_stale_state``) round-trips
  through the journal fragment, and mixed-round batch assembly
  (``stale_window``) gives each worker ITS OWN round's rows,
- the health sentry judges a stale arrival at its own round's EMA
  lens — a legitimately-lagging worker never trips a false anomaly,
  even under ``--health rollback``.
"""

import numpy as np
import pytest
import jax

from sparknet_tpu import config
from sparknet_tpu.parallel import (
    BoundedStalenessTrainer,
    ParameterAveragingTrainer,
    export_worker_replicas,
    make_mesh,
    restore_worker_replicas,
    shard_leading,
    stale_window,
)
from sparknet_tpu.parallel.hierarchy import HierarchySpec
from sparknet_tpu.solver import Solver

NET = """
name: "toy"
layer { name: "data" type: "HostData" top: "x" top: "label"
  java_data_param { shape { dim: 8 dim: 6 } shape { dim: 8 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
  inner_product_param { num_output: 16 weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "logits"
  inner_product_param { num_output: 4 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits" bottom: "label" top: "loss" }
"""


def _solver():
    sp = config.parse_solver_prototxt(
        'base_lr: 0.05 lr_policy: "fixed" momentum: 0.9'
    )
    netp = config.parse_net_prototxt(NET)
    return Solver(sp, net_param=netp)


def _window(n_workers, tau, r, batch=8, seed=0):
    rng = np.random.RandomState(seed * 1000 + r)
    return {
        "x": rng.randn(n_workers, tau, batch, 6).astype(np.float32),
        "label": rng.randint(0, 4, (n_workers, tau, batch)).astype(
            np.float32
        ),
    }


def _leaves(state):
    return [np.asarray(v) for v in jax.tree_util.tree_leaves(state)]


def _bitwise_equal(a, b):
    return all(
        np.array_equal(x, y, equal_nan=True)
        for x, y in zip(_leaves(a), _leaves(b))
    )


# ----------------------------------------------------------------------
# construction


def test_constructor_validation():
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError):
        BoundedStalenessTrainer(_solver(), mesh, stale_bound=-1)
    with pytest.raises(ValueError):
        BoundedStalenessTrainer(_solver(), mesh, stale_bound=2, discount=0.0)
    with pytest.raises(ValueError):
        BoundedStalenessTrainer(_solver(), mesh, stale_bound=2, discount=1.5)
    # the comm plane's EF residuals assume synchronous boundaries
    with pytest.raises(ValueError):
        BoundedStalenessTrainer(
            _solver(), mesh, stale_bound=2, compress="int8"
        )
    with pytest.raises(ValueError):
        BoundedStalenessTrainer(
            _solver(), mesh, stale_bound=1, overlap_avg=True
        )
    # ...but B = 0 composes with everything (pure delegation)
    BoundedStalenessTrainer(_solver(), mesh, stale_bound=0, compress="int8")


# ----------------------------------------------------------------------
# the degenerate-path pin: B = 0 is sync averaging, bitwise


@pytest.mark.parametrize("hier", [None, "flat", "two_tier"])
def test_b0_bit_identical_to_sync(hier):
    n, tau = 4, 3
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    spec = {
        None: None,
        "flat": HierarchySpec.flat(n),
        "two_tier": HierarchySpec.grouped(n, 2, cross_slice_every=2),
    }[hier]
    sync = ParameterAveragingTrainer(_solver(), mesh, hierarchy=spec)
    stale = BoundedStalenessTrainer(
        _solver(), mesh, stale_bound=0, hierarchy=spec
    )
    s1 = sync.init_state(seed=0)
    s2 = stale.init_state(seed=0)
    for r in range(3):
        w = _window(n, tau, r)
        s1, l1 = sync.round(s1, shard_leading(w, mesh), round_index=r)
        s2, l2 = stale.round(s2, shard_leading(w, mesh), round_index=r)
        assert np.array_equal(np.asarray(l1), np.asarray(l2))
    assert _bitwise_equal(s1, s2)
    # the ledger stays coherent even on the delegated path
    assert list(stale.worker_rounds) == [3] * n
    assert stale.last_boundary["tier"] == "sync"
    assert stale.last_boundary["forced"] == [False] * n


def test_b0_bit_identical_with_audit():
    n, tau = 2, 2
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    sy_solver, st_solver = _solver(), _solver()
    sy_solver.audit, st_solver.audit = True, True
    sync = ParameterAveragingTrainer(sy_solver, mesh)
    stale = BoundedStalenessTrainer(st_solver, mesh, stale_bound=0)
    assert stale.audit
    s1 = sync.init_state(seed=1)
    s2 = stale.init_state(seed=1)
    w = _window(n, tau, 0, seed=1)
    s1, l1, a1 = sync.round(s1, shard_leading(w, mesh), round_index=0)
    s2, l2, a2 = stale.round(s2, shard_leading(w, mesh), round_index=0)
    assert np.array_equal(np.asarray(l1), np.asarray(l2))
    assert _bitwise_equal(s1, s2)
    assert _bitwise_equal(a1, a2)


# ----------------------------------------------------------------------
# partial-arrival boundaries


def test_absent_worker_replica_untouched():
    n, tau, B = 4, 2, 3
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(_solver(), mesh, stale_bound=B)
    st = t.init_state(seed=0)
    before = _leaves(st)
    arrived = np.array([True, True, True, False])
    st, losses = t.round(
        st, shard_leading(_window(n, tau, 0), mesh),
        arrived=arrived, round_index=0,
    )
    after = _leaves(st)
    # worker 3's slot in EVERY leaf (params, stats, history, iter) is
    # bit-untouched; arrived workers' params moved and agree
    for b, a in zip(before, after):
        if b.ndim == 0 or b.shape[0] != n:
            continue
        assert np.array_equal(b[3], a[3])
    p_before = np.asarray(before[0])
    p_after = np.asarray(after[0])
    assert not np.array_equal(p_before[0], p_after[0])
    np.testing.assert_array_equal(p_after[0], p_after[1])
    np.testing.assert_array_equal(p_after[0], p_after[2])
    # absent loss rows are zeroed in-graph
    larr = np.asarray(losses)
    assert np.all(larr[3] == 0.0)
    assert np.all(larr[:3] != 0.0)
    lb = t.last_boundary
    assert lb["arrived"] == [True, True, True, False]
    assert lb["weights"][3] == 0.0
    assert list(t.worker_rounds) == [1, 1, 1, 0]


def test_bound_forces_live_straggler():
    n, tau, B = 2, 2, 2
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(_solver(), mesh, stale_bound=B)
    st = t.init_state(seed=0)
    absent = np.array([True, False])
    for r in range(B):  # lag climbs 1, 2 — under the bound: skipped
        st, _ = t.round(
            st, shard_leading(_window(n, tau, r), mesh),
            arrived=absent, round_index=r,
        )
        assert t.last_boundary["forced"] == [False, False]
    # boundary B: lag(w1) == B -> forced in despite arrived=False
    st, _ = t.round(
        st, shard_leading(_window(n, tau, B), mesh),
        arrived=absent, round_index=B,
    )
    lb = t.last_boundary
    assert lb["forced"] == [False, True]
    assert lb["arrived"] == [True, True]
    assert lb["weights"][1] == pytest.approx(t.discount ** B)
    assert list(t.worker_rounds) == [B + 1, 1]


def test_dead_worker_never_forced():
    n, tau, B = 2, 2, 1
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(_solver(), mesh, stale_bound=B)
    st = t.init_state(seed=0)
    live = np.array([1.0, 0.0])
    for r in range(3):  # lag far beyond the bound — still never forced
        st, _ = t.round(
            st, shard_leading(_window(n, tau, r), mesh),
            live_mask=live, round_index=r,
        )
        lb = t.last_boundary
        assert lb["forced"] == [False, False]
        assert lb["arrived"] == [True, False]
    assert list(t.worker_rounds) == [3, 0]
    assert list(t.lags(3)) == [0, 3]


def test_all_absent_boundary_skipped():
    n, tau, B = 2, 2, 3
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(_solver(), mesh, stale_bound=B)
    st = t.init_state(seed=0)
    before = _leaves(st)
    st, losses = t.round(
        st, shard_leading(_window(n, tau, 0), mesh),
        arrived=np.zeros(n, bool), round_index=0,
    )
    assert t.last_boundary["skipped"]
    assert np.all(np.asarray(losses) == 0.0)
    assert np.asarray(losses).shape == (n, tau)
    assert all(
        np.array_equal(b, a) for b, a in zip(before, _leaves(st))
    )
    assert list(t.worker_rounds) == [0, 0]


def test_full_arrival_discount1_matches_sync_average():
    # weighted mean with equal unit weights == the sync masked mean
    # (different program, same math — allclose, not bitwise)
    n, tau = 2, 2
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    sync = ParameterAveragingTrainer(_solver(), mesh)
    stale = BoundedStalenessTrainer(
        _solver(), mesh, stale_bound=2, discount=1.0
    )
    s1 = sync.init_state(seed=0)
    s2 = stale.init_state(seed=0)
    w = _window(n, tau, 0)
    s1, _ = sync.round(s1, shard_leading(w, mesh), round_index=0)
    s2, _ = stale.round(
        s2, shard_leading(w, mesh), arrived=np.ones(n, bool),
        round_index=0,
    )
    np.testing.assert_allclose(
        np.asarray(s1.params["ip2"][0]), np.asarray(s2.params["ip2"][0]),
        rtol=1e-5, atol=1e-6,
    )


# ----------------------------------------------------------------------
# slice coarsening (asymmetric hierarchy)


def test_two_tier_arrivals_coarsen_to_slices():
    n, tau, B = 4, 2, 3
    spec = HierarchySpec.grouped(n, 2, cross_slice_every=2)
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(
        _solver(), mesh, stale_bound=B, hierarchy=spec
    )
    st = t.init_state(seed=0)
    # worker 3 absent -> its whole slice {2,3} goes stale as a unit,
    # even though worker 2 raised its hand
    st, _ = t.round(
        st, shard_leading(_window(n, tau, 0), mesh),
        arrived=np.array([True, True, True, False]), round_index=0,
    )
    lb = t.last_boundary
    assert lb["arrived"] == [True, True, False, False]
    assert list(t.worker_rounds) == [1, 1, 0, 0]
    # a dead member does not hold its slice back
    st, _ = t.round(
        st, shard_leading(_window(n, tau, 1), mesh),
        arrived=np.array([True, True, True, False]),
        live_mask=np.array([1.0, 1.0, 1.0, 0.0]), round_index=1,
    )
    assert t.last_boundary["arrived"] == [True, True, True, False]
    assert list(t.worker_rounds) == [2, 2, 1, 0]


def test_two_tier_intra_vs_cross_tier():
    n, tau = 4, 2
    spec = HierarchySpec.grouped(n, 2, cross_slice_every=2)
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(
        _solver(), mesh, stale_bound=2, hierarchy=spec
    )
    st = t.init_state(seed=0)
    tiers = []
    for r in range(2):
        st, _ = t.round(
            st, shard_leading(_window(n, tau, r), mesh),
            arrived=np.ones(n, bool), round_index=r,
        )
        tiers.append(t.last_boundary["tier"])
    assert tiers == ["intra", "cross"]  # (r+1) % K picks the tier


# ----------------------------------------------------------------------
# ledger / journal fragment / mixed-round batches


def test_stale_state_export_load_reset():
    n = 2
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(_solver(), mesh, stale_bound=2)
    st = t.init_state(seed=0)
    st, _ = t.round(
        st, shard_leading(_window(n, 2, 0), mesh),
        arrived=np.array([True, False]), round_index=0,
    )
    frag = t.export_stale_state()
    assert int(frag["boundary"]) == 1
    assert list(frag["worker_rounds"]) == [1, 0]

    t2 = BoundedStalenessTrainer(_solver(), mesh, stale_bound=2)
    t2.load_stale_state(frag)
    assert t2._boundary == 1
    assert list(t2.worker_rounds) == [1, 0]
    with pytest.raises(ValueError):
        t2.load_stale_state(
            {"worker_rounds": np.zeros(5, np.int64), "boundary": 0}
        )
    t2.reset_stale_state()
    assert t2._boundary == 0
    assert list(t2.worker_rounds) == [0, 0]
    assert t2.last_boundary is None


def test_stale_window_mixed_rounds():
    calls = []

    def window_fn(r):
        calls.append(r)
        base = np.full((3, 2, 4), float(r), np.float32)
        for w in range(3):
            base[w] += w * 10
        return {"x": base}

    out = stale_window(window_fn, [2, 0, 2])
    # worker w's rows come from ITS round; dedup -> 2 feed calls
    assert sorted(calls) == [0, 2]
    assert np.all(out["x"][0] == 2.0)
    assert np.all(out["x"][1] == 10.0)
    assert np.all(out["x"][2] == 22.0)


def test_worker_replicas_roundtrip():
    n = 2
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    t = BoundedStalenessTrainer(_solver(), mesh, stale_bound=2)
    st = t.init_state(seed=0)
    st, _ = t.round(
        st, shard_leading(_window(n, 2, 0), mesh),
        arrived=np.array([True, False]), round_index=0,
    )
    host = jax.device_get(st)
    frag = export_worker_replicas(host)
    st2 = restore_worker_replicas(t.init_state(seed=9), frag, mesh)
    assert _bitwise_equal(jax.device_get(st2), host)
    # geometry mismatch fails loudly
    bad = {k: v[..., :1] for k, v in frag.items()}
    with pytest.raises(ValueError):
        restore_worker_replicas(t.init_state(seed=9), bad, mesh)


# ----------------------------------------------------------------------
# sentry interplay: stale arrivals judged at their OWN round


# Warmup curve: a loss CLIFF between the round-4 plateau (5.0) and the
# settled level (1.0).  Round 4's EMA lens sits at 5.0 while the live
# round-11 lens has settled near 1.0 — exactly the regime where a
# lag-7 arrival reporting the round-4 level reads as a 4-sigma spike
# to the naive boundary mean but as z ~ 0 at its own round's lens.
_WARM_CURVE = [5.0] * 5 + [1.0] * 7
_WARM_BOUNDARY = len(_WARM_CURVE)  # next boundary index: 12


def _warmed_sentry(policy="warn", **kw):
    from sparknet_tpu.obs.health import HealthSentry

    # ema_beta 0.5: the cliff's variance spike decays within the
    # settled plateau instead of memorializing itself into sigma
    s = HealthSentry(
        policy=policy, z_threshold=4.0, warmup_rounds=2, ema_beta=0.5,
        **kw,
    )
    for r, base in enumerate(_WARM_CURVE):
        losses = np.full((2, 3), base, np.float64)
        s.observe(
            r, losses, {}, arrived=[True, True], worker_rounds=[r, r]
        )
        assert s.verdicts[-1].ok
    return s


def test_sentry_stale_arrival_no_false_anomaly():
    s = _warmed_sentry()
    # boundary 12: worker 1 folds its round-4 window — a legitimately
    # HIGHER loss (the round-4 plateau).  Judged at round 4's lens: ok.
    losses = np.array([[1.0] * 3, [5.0] * 3])
    v = s.observe(
        _WARM_BOUNDARY, losses, {},
        arrived=[True, True], worker_rounds=[_WARM_BOUNDARY, 4],
    )
    assert v.ok, v.reasons
    # the same numbers judged WITHOUT staleness context (the naive
    # boundary mean, (1+5)/2 = 3.0 against the ~1.0 settled EMA) spike
    # the z-score — the false anomaly the arrival-aware path exists to
    # prevent
    s2 = _warmed_sentry()
    v2 = s2.observe(_WARM_BOUNDARY, losses, {})
    assert not v2.ok and "loss_spike" in v2.reasons


def test_sentry_stale_arrival_real_divergence_still_caught():
    s = _warmed_sentry()
    # worker 1's round-4 window at loss 40: divergent even by round
    # 4's lens — stale_z still trips
    losses = np.array([[1.0] * 3, [40.0] * 3])
    v = s.observe(
        _WARM_BOUNDARY, losses, {},
        arrived=[True, True], worker_rounds=[_WARM_BOUNDARY, 4],
    )
    assert not v.ok and "loss_spike" in v.reasons


def test_sentry_rollback_policy_ignores_lagging_worker():
    # --health rollback: a lagging-but-healthy worker must not burn a
    # rollback.  No restore_fn is called because no anomaly fires.
    calls = []

    def restore_fn():
        calls.append(1)
        raise AssertionError("rollback must not fire for a stale lag")

    s = _warmed_sentry("rollback", restore_fn=restore_fn)
    v = s.observe(
        _WARM_BOUNDARY, np.array([[1.0] * 3, [5.0] * 3]), {},
        arrived=[True, True], worker_rounds=[_WARM_BOUNDARY, 4],
    )
    assert v.ok and not calls
