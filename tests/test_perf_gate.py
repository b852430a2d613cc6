"""Perf-regression gate (``tools/perf_gate.py``): the rules engine over
synthetic artifact sets, the newest-per-family selection, the live-
profile comparison, and the CLI contract."""

import importlib.util
import json
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gate():
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(_REPO, "tools", "perf_gate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(root, name, obj):
    with open(os.path.join(str(root), name), "w") as f:
        json.dump(obj, f)


GOOD_PIPELINE = {
    "value": 1.7, "overlap_efficiency": 0.95,
    "pipelined_round_ms": 900.0, "serial_round_ms": 1600.0,
}
GOOD_PROFILE = {
    "overhead_profiled_pct": 0.4, "straggler_attributed": True,
    "hidden_frac_h2d_p50": 0.99, "flops_cross_check_ratio": 2.5,
    "profiled_round_ms": 1000.0,
}


def test_newest_artifact_per_family_wins(tmp_path):
    g = _gate()
    _write(tmp_path, "PIPELINE_r08.json", GOOD_PIPELINE)
    _write(tmp_path, "PIPELINE_r03.json", {"value": 0.2})  # old history
    _write(tmp_path, "SCALING_r04_googlenet.json", {"value": 50.0})
    _write(tmp_path, "BASELINE.json", {"value": -1})  # not an artifact
    _write(tmp_path, "notes_r99.json", {"value": -1})  # unknown family
    arts = g.find_artifacts(str(tmp_path))
    assert arts["PIPELINE"][0] == 8
    assert [os.path.basename(p) for p in arts["PIPELINE"][1]] == [
        "PIPELINE_r08.json"
    ]
    assert arts["SCALING"][0] == 4  # suffixed variants count in-family
    assert set(arts) == {"PIPELINE", "SCALING"}
    # ALL same-newest-round variants are returned (unsuffixed first) so
    # the gate validates every one, not an arbitrary glob-order pick
    _write(tmp_path, "SCALING_r04.json", {"value": 60.0})
    _write(tmp_path, "SCALING_r04_resnet50.json", {"value": 70.0})
    arts = g.find_artifacts(str(tmp_path))
    assert [os.path.basename(p) for p in arts["SCALING"][1]] == [
        "SCALING_r04.json", "SCALING_r04_googlenet.json",
        "SCALING_r04_resnet50.json",
    ]
    # a regression in ANY same-round variant fails --check
    _write(tmp_path, "SCALING_r04_googlenet.json", {"value": 0})
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["artifact"] == "SCALING_r04_googlenet.json" and not r["ok"]
        for r in rows
    )
    # suffixes with underscores (a model name like cifar10_full) are in-family
    # too — a newer such artifact must supersede and be validated
    _write(tmp_path, "SCALING_r06_cifar10_full.json", {"value": 0})
    arts = g.find_artifacts(str(tmp_path))
    assert arts["SCALING"][0] == 6
    rc, rows = g.check(str(tmp_path))
    assert any(
        r["artifact"] == "SCALING_r06_cifar10_full.json" and not r["ok"]
        for r in rows
    )


def test_check_passes_good_set_and_fails_regressions(tmp_path):
    g = _gate()
    _write(tmp_path, "PIPELINE_r08.json", GOOD_PIPELINE)
    _write(tmp_path, "PROFILE_r11.json", GOOD_PROFILE)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    # the cross-artifact rule ran: live hidden fraction vs offline eff
    assert any(r["family"] == "PROFILE x PIPELINE" for r in rows)
    # regress the pipeline below the bar -> nonzero
    _write(
        tmp_path, "PIPELINE_r09.json",
        dict(GOOD_PIPELINE, value=0.9, pipelined_round_ms=1700.0),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    fails = [r for r in rows if not r["ok"]]
    assert any("value" in r["detail"] for r in fails)
    assert any("pipelined_round_ms" in r["detail"] for r in fails)


def test_check_fails_on_collapsed_live_hidden_fraction(tmp_path):
    """The cross-artifact band: a PROFILE artifact whose live hidden
    fraction collapsed must fail against the committed PIPELINE
    efficiency even if its own fields look self-consistent."""
    g = _gate()
    _write(tmp_path, "PIPELINE_r08.json", GOOD_PIPELINE)
    _write(
        tmp_path, "PROFILE_r11.json",
        dict(GOOD_PROFILE, hidden_frac_h2d_p50=0.1),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    bad = [r for r in rows if not r["ok"]]
    assert any(r["family"] == "PROFILE x PIPELINE" for r in bad)


GOOD_DATACACHE = {
    "value": 20.0, "warm_epoch_fetches": 0, "cold_epoch_fetches": 6,
    "nocache_epoch2_fetches": 6, "bytes_identical": True,
    "minibatches_identical": True,
}


def test_datacache_family_rules(tmp_path):
    """The DATACACHE family (ISSUE 8): warm-epoch network fetches must
    be EXACTLY zero and byte identity must hold — a single warm fetch
    or a bytes mismatch fails --check."""
    g = _gate()
    _write(tmp_path, "DATACACHE_r12.json", GOOD_DATACACHE)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    _write(
        tmp_path, "DATACACHE_r13.json",
        dict(GOOD_DATACACHE, warm_epoch_fetches=1),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        "warm_epoch_fetches" in r["detail"] for r in rows if not r["ok"]
    )
    _write(
        tmp_path, "DATACACHE_r13.json",
        dict(GOOD_DATACACHE, bytes_identical=False),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        "bytes_identical" in r["detail"] for r in rows if not r["ok"]
    )


GOOD_SANITIZE = {
    "value": 6, "rounds_guarded": 6, "disallowed_transfers": 0,
    "recompiles_post_warmup": 0, "guard_armed": True,
    "leak_check_ok": True, "lint_new_findings": 0,
    "annotated_sync_count": 17,
}


def test_sanitize_family_rules(tmp_path):
    """The SANITIZE family (ISSUE 9): zero disallowed transfers, zero
    post-warmup recompiles, >= 5 guarded rounds, an armed guard, and a
    clean lint — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "SANITIZE_r13.json", GOOD_SANITIZE)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("disallowed_transfers", 1),
        ("recompiles_post_warmup", 2),
        ("guard_armed", False),       # vacuous zero: guard never bit
        ("leak_check_ok", False),
        ("lint_new_findings", 3),
        ("rounds_guarded", 4),        # under the >= 5 steady-round bar
        ("annotated_sync_count", 0),  # empty inventory = unaudited
    ):
        _write(
            tmp_path, "SANITIZE_r14.json",
            dict(GOOD_SANITIZE, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)


GOOD_FLEET = {
    "overhead_shipped_pct": 0.4, "hosts": 2,
    "straggler_attributed": True, "dead_detection_exact": True,
    "clock_offset_bounded": True,
    "trace_interleaves_after_correction": True,
    "overhead_lost_events": 0, "outage_push_failures": 3,
    "outage_replayed_events": 150, "outage_lost_events": 0,
    "outage_dropped_events": 0,
    "value": 0.4,
}


def test_fleet_family_rules(tmp_path):
    """The FLEET family (ISSUE 11): shipper overhead < 2%, exact
    dead/straggler attribution, bounded clock correction, and a
    zero-loss outage replay — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "FLEET_r14.json", GOOD_FLEET)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("overhead_shipped_pct", 3.5),     # shipping cost out of band
        ("straggler_attributed", False),   # wrong/no late host named
        ("dead_detection_exact", False),   # wrong host or round
        ("clock_offset_bounded", False),   # skew not recovered
        ("trace_interleaves_after_correction", False),
        ("overhead_lost_events", 2),       # lossy steady-state shipping
        ("outage_push_failures", 0),       # vacuous: outage never bit
        ("outage_replayed_events", 0),     # nothing buffered/replayed
        ("outage_lost_events", 5),         # the replay lost events
        ("outage_dropped_events", 1),      # buffer overflowed
        ("hosts", 1),                      # not actually a fleet
    ):
        _write(
            tmp_path, "FLEET_r15.json",
            dict(GOOD_FLEET, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)


GOOD_DELIVERY = {
    "value": 180.0, "scaling_ratio_modeled": 1.45,
    "shed_invariant_ok": True, "promote_ok": True,
    "promote_dropped_inflight": 0, "promote_bit_identical": True,
    "rollback_exact": True, "rollback_dropped_inflight": 0,
    "incumbent_held_after_rollback": True, "replica_kill_ok": True,
    "replica_kill_client_errors": 0,
}


def test_delivery_family_rules(tmp_path):
    """The DELIVERY family (ISSUE 12): modeled fleet scaling, the
    shed-invariance contract, zero-drop promotes with bit identity,
    exact-named rollbacks, and replica-kill recovery — any one
    regressing fails --check."""
    g = _gate()
    _write(tmp_path, "DELIVERY_r15.json", GOOD_DELIVERY)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("scaling_ratio_modeled", 1.0),       # fleet didn't scale
        ("shed_invariant_ok", False),         # admission bound drifted
        ("promote_ok", False),                # wrong snapshot promoted
        ("promote_dropped_inflight", 3),      # promote dropped requests
        ("promote_bit_identical", False),     # reload changed outputs
        ("rollback_exact", False),            # wrong publish named
        ("rollback_dropped_inflight", 2),     # rollback dropped requests
        ("incumbent_held_after_rollback", False),
        ("replica_kill_ok", False),           # kill not recovered
        ("replica_kill_client_errors", 1),    # kill leaked client errors
    ):
        _write(
            tmp_path, "DELIVERY_r16.json",
            dict(GOOD_DELIVERY, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)


GOOD_ELASTIC = {
    "value": 4.0, "flat_bit_identical": True,
    "departure_detected_exact": True, "rejoin_completed": True,
    "views_monotonic": True, "loss_band_ok": True,
    "cross_bytes_ratio": 4.0, "cross_slice_every": 4,
}


def test_elastic_family_rules(tmp_path):
    """The ELASTIC family (ISSUE 13): flat-spec bit identity, exact
    departure detection at the round boundary, completed rejoin with
    monotonic view epochs, loss in the no-fault band, and the ~K x
    cross-slice byte reduction — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "ELASTIC_r16.json", GOOD_ELASTIC)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("flat_bit_identical", False),     # flat spec drifted bitwise
        ("departure_detected_exact", False),  # leave landed off-boundary
        ("rejoin_completed", False),       # roster never fully live again
        ("views_monotonic", False),        # epochs went backwards
        ("loss_band_ok", False),           # preemption cost accuracy
        ("cross_bytes_ratio", 2.0),        # two-tier stopped amortizing
    ):
        _write(
            tmp_path, "ELASTIC_r17.json",
            dict(GOOD_ELASTIC, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)
    # the K-relative extra rule: a ratio far under the artifact's OWN
    # K fails even if it clears the static 3.9 floor
    _write(
        tmp_path, "ELASTIC_r17.json",
        dict(GOOD_ELASTIC, cross_slice_every=8, cross_bytes_ratio=4.0,
             value=4.0),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        "cross_slice_every" in r["detail"] for r in rows if not r["ok"]
    )


GOOD_RECOVER = {
    "value": 6, "killpoints_total": 6, "killpoints_survived": 6,
    "bit_identical_all": True, "max_replayed_rounds": 1,
    "no_journal_diverged": True, "journal_bit_neutral": True,
    "journal_overhead_pct": 0.4,
    "stale": {
        "survived": True, "bit_identical": True,
        "replayed_rounds": 1, "stale_bound": 2,
    },
}


def test_recover_family_rules(tmp_path):
    """The RECOVER family (ISSUE 14): every kill-point survived
    bit-identically with at most one replayed round, the no-journal
    control diverged (non-vacuous zero), the ledger bit-neutral, and
    its overhead inside the noise floor — any one regressing fails
    --check."""
    g = _gate()
    _write(tmp_path, "RECOVER_r17.json", GOOD_RECOVER)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("bit_identical_all", False),    # a resume drifted bitwise
        ("max_replayed_rounds", 2),      # exactly-once broke
        ("no_journal_diverged", False),  # the zero went vacuous
        ("journal_bit_neutral", False),  # the ledger perturbed the math
        ("journal_overhead_pct", 7.5),   # the ledger got expensive
        ("killpoints_total", 4),         # the sweep lost coverage
    ):
        _write(
            tmp_path, "RECOVER_r18.json",
            dict(GOOD_RECOVER, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)
    # the survival extra rule: survived must equal total even when
    # both clear their static floors
    _write(
        tmp_path, "RECOVER_r18.json",
        dict(GOOD_RECOVER, killpoints_total=7, value=6),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        "killpoints_survived" in r["detail"] for r in rows if not r["ok"]
    )
    # the stale kill-leg (ISSUE 17): a failed survival, a drifted
    # resume, or a replay past the artifact's OWN stale_bound fails
    # even with the flat sweep perfect
    for bad_stale, needle in (
        (dict(GOOD_RECOVER["stale"], survived=False),
         "stale.survived"),
        (dict(GOOD_RECOVER["stale"], bit_identical=False),
         "stale.bit_identical"),
        (dict(GOOD_RECOVER["stale"], replayed_rounds=3),
         "replayed_rounds"),
        (dict(GOOD_RECOVER["stale"], stale_bound=0),
         "stale_bound"),
    ):
        _write(
            tmp_path, "RECOVER_r18.json",
            dict(GOOD_RECOVER, stale=bad_stale),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, needle
        assert any(
            needle in r["detail"] for r in rows if not r["ok"]
        ), (needle, rows)
    # a RECOVER artifact missing the stale leg entirely is a failure,
    # not a silent pass
    bad = dict(GOOD_RECOVER)
    del bad["stale"]
    _write(tmp_path, "RECOVER_r18.json", bad)
    rc, rows = g.check(str(tmp_path))
    assert rc == 1


def test_missing_key_is_a_failure_not_a_pass(tmp_path):
    g = _gate()
    _write(tmp_path, "OBS_r09.json", {"overhead_traced_pct": 0.5})
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any("MISSING" in r["detail"] for r in rows if not r["ok"])


def test_live_summary_vs_baselines(tmp_path):
    g = _gate()
    _write(tmp_path, "PIPELINE_r08.json", GOOD_PIPELINE)
    _write(tmp_path, "PROFILE_r11.json", GOOD_PROFILE)
    # a RoundProfiler.summary() dump, healthy
    live = {
        "rounds": 10,
        "hidden_frac_h2d": {"p50": 0.98, "min": 0.0, "max": 1.0},
        "round_ms": {"p50": 1100.0, "max": 1400.0},
        "straggler_rounds": 1,
    }
    _write(tmp_path, "live.json", live)
    rc, rows = g.check_live(
        os.path.join(str(tmp_path), "live.json"), str(tmp_path)
    )
    assert rc == 0, rows
    # collapsed overlap -> fail
    _write(
        tmp_path, "live_bad.json",
        dict(live, hidden_frac_h2d={"p50": 0.1, "min": 0, "max": 0.2}),
    )
    rc, rows = g.check_live(
        os.path.join(str(tmp_path), "live_bad.json"), str(tmp_path)
    )
    assert rc == 1
    # round time blown past tolerance -> fail
    _write(
        tmp_path, "live_slow.json",
        dict(live, round_ms={"p50": 1000.0 * 1.6, "max": 2000.0}),
    )
    rc, _ = g.check_live(
        os.path.join(str(tmp_path), "live_slow.json"), str(tmp_path),
        tolerance=0.5,
    )
    assert rc == 1
    # a standing straggler (every round flagged) -> fail
    _write(
        tmp_path, "live_strag.json", dict(live, straggler_rounds=10),
    )
    rc, rows = g.check_live(
        os.path.join(str(tmp_path), "live_strag.json"), str(tmp_path)
    )
    assert rc == 1
    assert any("standing straggler" in r["detail"] for r in rows)
    # a serial-feed / bare-solver run (no producer spans at all) carries
    # hidden_frac_h2d: null — nothing to compare, NOT a regression (a
    # collapsed pipeline reads ~0.0, not null, and fails the band above)
    _write(
        tmp_path, "live_serial.json", dict(live, hidden_frac_h2d=None),
    )
    rc, rows = g.check_live(
        os.path.join(str(tmp_path), "live_serial.json"), str(tmp_path)
    )
    assert rc == 0, rows
    assert any("skipped" in r["detail"] for r in rows)
    # a PROFILE_* bench artifact's straggler counter comes from its
    # deliberately SEEDED leg — never a "standing straggler" verdict
    _write(
        tmp_path, "live_seeded.json",
        dict(
            live, hidden_frac_h2d_p50=0.98, rounds=2,
            straggler_rounds=2, straggler_seeded_worker=1,
        ),
    )
    rc, rows = g.check_live(
        os.path.join(str(tmp_path), "live_seeded.json"), str(tmp_path)
    )
    assert rc == 0, rows


def test_cli_contract(tmp_path, capsys):
    g = _gate()
    _write(tmp_path, "PIPELINE_r08.json", GOOD_PIPELINE)
    rc = g.main(["--check", "--root", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "perf gate:" in out and "0 failure(s)" in out
    _write(tmp_path, "PIPELINE_r09.json", {"value": 0.5})
    assert g.main(["--check", "--root", str(tmp_path)]) == 1
    capsys.readouterr()
    # --json emits machine rows
    rc = g.main(["--check", "--root", str(tmp_path), "--json"])
    assert rc == 1
    rows = json.loads(capsys.readouterr().out)
    assert isinstance(rows, list) and any(not r["ok"] for r in rows)
    with pytest.raises(SystemExit):
        g.main([])  # neither --check nor --live is an error


GOOD_LM = {
    "value": 30000.0, "sp": 2, "rounds": 12,
    "sp_tolerance": 5e-4, "sp_max_abs_param_diff": 2.4e-7,
    "sp_trajectory_ok": True, "loss_strictly_decreasing": True,
    "ring_hop_bytes_per_round": 4194304, "tokens_per_round": 2048,
}


def test_lm_family_rules(tmp_path):
    """The LM family (ISSUE 15): the sp=2 ring-attention run must
    reproduce the sp=1 dense run within the pinned associativity
    tolerance, the seeded run must actually learn (strictly
    decreasing loss), and a real sp>1 mesh with modeled ring bytes
    must have been measured — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "LM_r18.json", GOOD_LM)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("sp_trajectory_ok", False),       # ring drifted off dense
        ("loss_strictly_decreasing", False),  # the LM stopped learning
        ("sp", 1),                         # the ring leg never ran
        ("ring_hop_bytes_per_round", 0),   # no modeled exchange
        ("rounds", 2),                     # too short to mean anything
    ):
        _write(
            tmp_path, "LM_r19.json", dict(GOOD_LM, **{bad_field: bad_value})
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)
    # the tolerance extra rule: a measured diff past the artifact's
    # OWN pin fails even with sp_trajectory_ok mistakenly True
    _write(
        tmp_path, "LM_r19.json",
        dict(GOOD_LM, sp_max_abs_param_diff=1e-2),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        "sp_tolerance" in r["detail"] for r in rows if not r["ok"]
    )
    # a missing diff field is a failure, not a silent pass
    bad = dict(GOOD_LM)
    del bad["sp_max_abs_param_diff"]
    _write(tmp_path, "LM_r19.json", bad)
    rc, rows = g.check(str(tmp_path))
    assert rc == 1


GOOD_GENSERVE = {
    "value": 11000.0, "continuous_vs_static_ratio": 1.25,
    "ab_tokens_identical": True, "storm_shed_429": 24,
    "storm_errors": 0, "storm_p99_ttft_ms": 2.0,
    "post_warmup_recompiles": 0, "kv_exact": True,
    "kv_blocks_in_use_after_drain": 0, "kv_allocated_total": 8762,
    "kv_freed_total": 8762, "promote_ok": True,
    "promote_dropped_streams": 0, "promote_token_identical": True,
    "promote_max_divergence": 3.6e-7, "divergence_max": 1e-3,
    "rollback_divergence": 15.2, "rollback_exact": True,
    "rollback_dropped_streams": 0,
    "incumbent_held_after_rollback": True,
}


def test_genserve_family_rules(tmp_path):
    """The GENSERVE family (ISSUE 16): continuous batching beats static
    with identical greedy tokens, a real 429 storm with zero errors and
    a bounded TTFT tail, zero recompiles after warmup, exact KV-block
    accounting, zero-drop promotes with a token-identical probe, and
    divergence-named rollbacks — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "GENSERVE_r19.json", GOOD_GENSERVE)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("continuous_vs_static_ratio", 1.0),  # scheduling won nothing
        ("ab_tokens_identical", False),    # batching changed the output
        ("storm_shed_429", 0),             # vacuous: admission never bit
        ("storm_errors", 2),               # shed leaked as errors
        ("storm_p99_ttft_ms", 5000.0),     # first token unbounded
        ("post_warmup_recompiles", 1),     # the serving contract broke
        ("kv_exact", False),               # arena accounting drifted
        ("kv_blocks_in_use_after_drain", 3),  # leaked KV blocks
        ("promote_ok", False),             # wrong snapshot promoted
        ("promote_dropped_streams", 2),    # promote dropped decodes
        ("promote_token_identical", False),  # hot-swap changed tokens
        ("rollback_exact", False),         # wrong publish named
        ("rollback_dropped_streams", 1),   # rollback dropped decodes
        ("incumbent_held_after_rollback", False),
    ):
        _write(
            tmp_path, "GENSERVE_r20.json",
            dict(GOOD_GENSERVE, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)
    # the KV extra rule: allocated must equal freed AND be nonzero —
    # an imbalance or a vacuous zero fails even with kv_exact True
    for kv in (
        {"kv_allocated_total": 8762, "kv_freed_total": 8760},
        {"kv_allocated_total": 0, "kv_freed_total": 0},
    ):
        _write(
            tmp_path, "GENSERVE_r20.json", dict(GOOD_GENSERVE, **kv)
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, kv
        assert any(
            "kv_allocated_total" in r["detail"]
            for r in rows if not r["ok"]
        ), (kv, rows)
    # the divergence extra rule: the canary decision must be decisive
    # against the artifact's OWN pin — a good publish outside the pin,
    # or a poisoned publish inside it, fails even with the flags True
    for div in (
        {"promote_max_divergence": 5e-3},   # good publish out of band
        {"rollback_divergence": 5e-4},      # bad publish inside the pin
    ):
        _write(
            tmp_path, "GENSERVE_r20.json", dict(GOOD_GENSERVE, **div)
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, div
        assert any(
            "divergence_max" in r["detail"] for r in rows if not r["ok"]
        ), (div, rows)


GOOD_CHAOS = {
    "value": 5, "loss_band_ok": True,
    "faults_injected": 5, "faults_survived": 5,
    "slow_slice": {
        "survived": True, "straggler_named_ok": True,
        "loss_band_ok": True, "stale": {"forced_waits": 0},
    },
}


def test_chaos_family_rules(tmp_path):
    """The CHAOS family's slow_slice leg (ISSUE 17): the dotted-path
    rules reach inside the nested A/B — a forced wait, an unnamed
    straggler, or a blown loss band in the slow-slice scenario fails
    --check even with every top-level fault survived."""
    g = _gate()
    _write(tmp_path, "CHAOS_r19.json", GOOD_CHAOS)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    ss = GOOD_CHAOS["slow_slice"]
    for bad_ss, needle in (
        (dict(ss, survived=False), "slow_slice.survived"),
        (dict(ss, straggler_named_ok=False),
         "slow_slice.straggler_named_ok"),
        (dict(ss, loss_band_ok=False), "slow_slice.loss_band_ok"),
        (dict(ss, stale={"forced_waits": 2}),
         "slow_slice.stale.forced_waits"),
    ):
        _write(
            tmp_path, "CHAOS_r20.json",
            dict(GOOD_CHAOS, slow_slice=bad_ss),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, needle
        assert any(
            needle in r["detail"] for r in rows if not r["ok"]
        ), (needle, rows)
    # the survival extra rule still applies alongside the nested leg
    _write(
        tmp_path, "CHAOS_r20.json", dict(GOOD_CHAOS, faults_survived=4)
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        "faults_survived" in r["detail"] for r in rows if not r["ok"]
    )
    # a missing nested leg is a failure, not a silent pass
    bad = dict(GOOD_CHAOS)
    del bad["slow_slice"]
    _write(tmp_path, "CHAOS_r20.json", bad)
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any("MISSING" in r["detail"] for r in rows if not r["ok"])


GOOD_STALE = {
    "value": 1.3, "b0_bit_identical": True,
    "b0_flat_bit_identical": True, "b0_hier_bit_identical": True,
    "stale_straggler_penalty_pct": 1.3, "forced_folds": 0,
    "stale_bound": 4, "loss_band_ok": True,
    "hier_laggiest_ok": True, "hier_finite": True,
    "baseline_round_ms_p50": 2750.0, "tail_s": 2.75,
    "sync_slow_round_ms_p50": 5790.0,
    "stale_slow_round_ms_p50": 2780.0,
}


def test_stale_family_rules(tmp_path):
    """The STALE family (ISSUE 17): B=0 bitwise identical to the sync
    trainer on both topologies, the straggled-round penalty inside the
    pinned band, zero bound-forced folds, the one-sided loss band, and
    the two-tier laggiest attribution — any one regressing fails
    --check."""
    g = _gate()
    _write(tmp_path, "STALE_r20.json", GOOD_STALE)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, rows
    for bad_field, bad_value in (
        ("b0_bit_identical", False),        # B=0 drifted off sync
        ("b0_flat_bit_identical", False),   # the flat pin broke
        ("b0_hier_bit_identical", False),   # the two-tier pin broke
        ("stale_straggler_penalty_pct", 30.0),  # tail leaked back in
        ("forced_folds", 1),                # the bound bit mid-window
        ("stale_bound", 0),                 # vacuous: B=0 is just sync
        ("loss_band_ok", False),            # staleness hurt convergence
        ("hier_laggiest_ok", False),        # wrong slice named laggiest
        ("hier_finite", False),             # two-tier losses blew up
    ):
        _write(
            tmp_path, "STALE_r21.json",
            dict(GOOD_STALE, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)
    # the wall-clock extra rule, self-relative to the artifact's OWN
    # calibrated tail: a stale leg drifting past 1.25x baseline, or a
    # sync control that never actually paid the tail (vacuous split),
    # fails even with the static penalty field inside its band
    for wc in (
        {"stale_slow_round_ms_p50": 3600.0},  # stale leg paid the tail
        {"sync_slow_round_ms_p50": 3000.0},   # control never paid it
        {"tail_s": 0.0},                      # no tail injected at all
    ):
        _write(tmp_path, "STALE_r21.json", dict(GOOD_STALE, **wc))
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, wc
        assert any(
            "stale_slow_round_ms_p50" in r["detail"]
            for r in rows if not r["ok"]
        ), (wc, rows)


GOOD_KERNELS = {
    "value": 3.88, "platform": "cpu",
    "flash_fwd_max_diff": 3e-7, "flash_fwd_tol": 2e-5,
    "flash_fwd_ok": True,
    "flash_grad_max_diff": 1.4e-6, "flash_grad_tol": 5e-5,
    "flash_grad_ok": True,
    "flash_ragged_fwd_max_diff": 2.4e-7,
    "flash_ragged_grad_max_diff": 2.9e-6, "flash_ragged_ok": True,
    "flash_bf16_fwd_max_diff": 6.2e-3, "flash_bf16_fwd_tol": 4e-2,
    "flash_bf16_grad_max_diff": 3.1e-2, "flash_bf16_grad_tol": 6e-2,
    "flash_bf16_ok": True,
    "ring_flash_max_diff": 2.9e-6, "ring_tolerance": 5e-4,
    "ring_flash_ok": True,
    "trainer_ab_bitwise": True, "fused_kernel_launches": 54,
    "int8_loss_gap": 0.0013, "loss_band": 0.08, "loss_band_ok": True,
    "post_warmup_recompiles": 0,
    "attn_hbm_ratio": 3.88, "epilogue_hbm_ratio": 2.24,
    "wallclock_rules_armed": True, "wallclock_measured": False,
}


def test_kernels_family_rules(tmp_path):
    """The KERNELS family (ISSUE 18): flash fwd+bwd pinned against the
    dense reference, ring flash inside the LM tolerance, the fused
    epilogue bitwise through a real trainer with the int8 loss gap in
    band, zero post-warmup recompiles, modeled HBM ratios above 1 —
    any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "KERNELS_r21.json", GOOD_KERNELS)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    for bad_field, bad_value in (
        ("flash_fwd_ok", False),          # forward drifted off dense
        ("flash_grad_ok", False),         # custom_vjp grads drifted
        ("flash_ragged_ok", False),       # the auto-pad path broke
        ("flash_bf16_ok", False),         # bf16 out of its band
        ("ring_flash_ok", False),         # per-shard flash off the ring
        ("trainer_ab_bitwise", False),    # fused epilogue moved params
        ("fused_kernel_launches", 0),     # the fused path never ran
        ("loss_band_ok", False),          # int8 leg out of band
        ("post_warmup_recompiles", 2),    # kernel retraces in the step
        ("attn_hbm_ratio", 0.9),          # modeled bytes went backwards
        ("epilogue_hbm_ratio", 0.8),
        ("wallclock_rules_armed", False),  # someone disarmed the gate
    ):
        _write(
            tmp_path, "KERNELS_r22.json",
            dict(GOOD_KERNELS, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)
    # the pins extra rule: a measured diff past the artifact's OWN pin
    # fails even with the ok flag mistakenly True
    for diff_field, pin_field in (
        ("flash_grad_max_diff", "flash_grad_tol"),
        ("ring_flash_max_diff", "ring_tolerance"),
        ("int8_loss_gap", "loss_band"),
    ):
        _write(
            tmp_path, "KERNELS_r22.json",
            dict(GOOD_KERNELS, **{diff_field: 1.0}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, diff_field
        assert any(
            diff_field in r["detail"] for r in rows if not r["ok"]
        ), (diff_field, rows)
    # a missing diff field is a failure, not a silent pass
    bad = dict(GOOD_KERNELS)
    del bad["ring_flash_max_diff"]
    _write(tmp_path, "KERNELS_r22.json", bad)
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    # wall-clock: off-chip must DISCLOSE (wallclock_measured False);
    # an on-chip artifact must actually carry a >1 speedup
    _write(
        tmp_path, "KERNELS_r22.json",
        dict(GOOD_KERNELS, wallclock_measured=True),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1  # CPU artifact claiming a measured wall-clock
    _write(
        tmp_path, "KERNELS_r22.json",
        dict(GOOD_KERNELS, platform="tpu", wallclock_measured=True,
             wallclock_attn_speedup=2.3),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    _write(
        tmp_path, "KERNELS_r22.json",
        dict(GOOD_KERNELS, platform="tpu", wallclock_measured=True,
             wallclock_attn_speedup=0.8),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1


def test_kernels_cross_rules(tmp_path):
    """KERNELS x LM and KERNELS x COMM: the ring-flash diff must sit
    inside LM's OWN sp_tolerance and the int8 loss gap inside COMM's
    OWN loss_band — the kernels bench cannot pick itself easier pins
    than the committed workload artifacts."""
    g = _gate()
    good_comm = {
        "overlap_vs_ideal": 1.04, "bytes_ratio_int8": 4.0,
        "bytes_ratio_bf16": 2.0, "loss_band_ok": True,
        "loss_band": 0.08,
    }
    _write(tmp_path, "KERNELS_r21.json", GOOD_KERNELS)
    _write(tmp_path, "LM_r18.json", GOOD_LM)
    _write(tmp_path, "COMM_r11.json", good_comm)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    assert any(r["family"] == "KERNELS x LM" for r in rows)
    assert any(r["family"] == "KERNELS x COMM" for r in rows)
    # ring diff past the LM pin fails the cross rule (the family's own
    # ring_tolerance is looser here — exactly the drift being caught)
    _write(
        tmp_path, "KERNELS_r21.json",
        dict(GOOD_KERNELS, ring_flash_max_diff=2e-3, ring_tolerance=1e-2),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "KERNELS x LM" and not r["ok"] for r in rows
    )
    # loss gap past the COMM band likewise
    _write(
        tmp_path, "KERNELS_r21.json",
        dict(GOOD_KERNELS, int8_loss_gap=0.5, loss_band=1.0),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "KERNELS x COMM" and not r["ok"] for r in rows
    )


GOOD_SERVEOBS = {
    "value": 0.9, "overhead_pct": 0.9, "noise_floor_pct": 3.0,
    "traced_requests": 240, "post_warmup_recompiles": 0,
    "stages_covered": 5, "shed_cause_header": "kv_reserve",
    "healthz_has_profile": True, "metrics_has_req_series": True,
    "kv_squeeze_attributed": 1, "slow_replica_correct": 1,
    "replica_skew": 24.4, "tpot_p50_ms": 0.7,
    "traced_tokens_per_s": 4500.0,
}


def test_serveobs_family_rules(tmp_path):
    """The SERVEOBS family (ISSUE 19): tracing overhead inside the <2%
    acceptance, zero recompiles with the instrumentation live, all
    five stages covered through a real server, the 429 naming its shed
    cause, the seeded KV squeeze attributed kv-bound, and the seeded
    slow replica named exactly — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "SERVEOBS_r22.json", GOOD_SERVEOBS)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    for bad_field, bad_value in (
        ("overhead_pct", 4.5),             # tracing got expensive
        ("traced_requests", 0),            # vacuous: nothing folded
        ("post_warmup_recompiles", 1),     # instrumentation recompiled
        ("stages_covered", 4),             # a stage stopped emitting
        ("shed_cause_header", None),       # the 429 lost its cause
        ("healthz_has_profile", False),    # /healthz block vanished
        ("metrics_has_req_series", False),  # /metrics series vanished
        ("kv_squeeze_attributed", 0),      # the squeeze misattributed
        ("slow_replica_correct", 0),       # wrong/no replica named
        ("replica_skew", 1.0),             # skew fold went flat
    ):
        _write(
            tmp_path, "SERVEOBS_r23.json",
            dict(GOOD_SERVEOBS, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)


def test_serveobs_cross_rules(tmp_path):
    """SERVEOBS x GENSERVE: the profiler's decode-attributed TPOT must
    agree with genserve's independently measured continuous throughput
    (within the 4x occupancy/mix allowance), and the traced leg must
    keep >=25% of the genserve rate — a broken fold or a tracing
    slowdown fails even when each family passes alone."""
    g = _gate()
    genserve = dict(GOOD_GENSERVE, continuous_tokens_per_s=11000.0,
                    decode_slots=4)
    _write(tmp_path, "SERVEOBS_r22.json", GOOD_SERVEOBS)
    _write(tmp_path, "GENSERVE_r19.json", genserve)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    crosses = [r for r in rows if r["family"] == "SERVEOBS x GENSERVE"]
    assert len(crosses) == 2, crosses
    # a TPOT fold wildly off the genserve-implied per-slot token time
    # (4 slots / 11000 tok/s ~= 0.36 ms) fails the consistency rule
    _write(
        tmp_path, "SERVEOBS_r22.json",
        dict(GOOD_SERVEOBS, tpot_p50_ms=5.0),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "SERVEOBS x GENSERVE" and not r["ok"]
        and "tpot" in r["detail"] for r in rows
    ), rows
    # a traced throughput collapse fails the retention rule
    _write(
        tmp_path, "SERVEOBS_r22.json",
        dict(GOOD_SERVEOBS, traced_tokens_per_s=500.0),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "SERVEOBS x GENSERVE" and not r["ok"]
        and "traced_tokens_per_s" in r["detail"] for r in rows
    ), rows


GOOD_SLO = {
    "value": 0.2, "latency_alert_fired": True, "shed_alert_fired": True,
    "latency_detect_delay_s": 60.0, "shed_detect_delay_s": 60.0,
    "control_false_alarms": 0, "control_evals": 5,
    "tsdb_under_budget": True, "tsdb_dropped_series": 0,
    "downsample_agree": True, "signals_match": True,
    "endpoints_ok": True,
    "ttft_threshold_ms": 500, "hosts": 3, "round_rate_hosts": 3,
}


def test_slo_family_rules(tmp_path):
    """The SLO family (ISSUE 20): both seeded faults detected within
    one burn window, the healthy control silent across real
    evaluations, the store under budget with zero dropped series,
    rollups agreeing with raw, /signals matching recomputation, and
    the HTTP surface answering — any one regressing fails --check."""
    g = _gate()
    _write(tmp_path, "SLO_r23.json", GOOD_SLO)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    for bad_field, bad_value in (
        ("value", 1.5),                    # detection slower than a window
        ("latency_alert_fired", False),    # TTFT fault missed entirely
        ("shed_alert_fired", False),       # shed storm missed entirely
        ("latency_detect_delay_s", 600.0),  # detection crawled
        ("shed_detect_delay_s", 301.0),
        ("control_false_alarms", 2),       # healthy replay paged someone
        ("control_evals", 0),              # control silence was vacuous
        ("tsdb_under_budget", False),      # retention blew its budget
        ("tsdb_dropped_series", 4),        # series refused at budget
        ("downsample_agree", False),       # rollups diverged from raw
        ("signals_match", False),          # /signals unfaithful to /query
        ("endpoints_ok", False),           # HTTP surface broke
    ):
        _write(
            tmp_path, "SLO_r24.json",
            dict(GOOD_SLO, **{bad_field: bad_value}),
        )
        rc, rows = g.check(str(tmp_path))
        assert rc == 1, bad_field
        assert any(
            bad_field in r["detail"] for r in rows if not r["ok"]
        ), (bad_field, rows)


def test_slo_cross_rules(tmp_path):
    """SLO x SERVEOBS: the TTFT objective must be achievable on this
    box (threshold >= serveobs' measured p95) or the control-leg
    silence is vacuous.  SLO x FLEET: /signals is only as trustworthy
    as the fleet plane under it — proven dead-host detection, bounded
    clock offset, and a round-rate entry for every simulated host."""
    g = _gate()
    serveobs = dict(GOOD_SERVEOBS, ttft_p95_ms=420.5)
    fleet = dict(GOOD_FLEET, dead_detected=True)
    _write(tmp_path, "SLO_r23.json", GOOD_SLO)
    _write(tmp_path, "SERVEOBS_r22.json", serveobs)
    _write(tmp_path, "FLEET_r14.json", fleet)
    rc, rows = g.check(str(tmp_path))
    assert rc == 0, [r for r in rows if not r["ok"]]
    assert any(r["family"] == "SLO x SERVEOBS" for r in rows)
    assert any(r["family"] == "SLO x FLEET" for r in rows)
    # an objective the hardware cannot meet: threshold under the
    # independently measured p95 pages forever -> cross rule fails
    _write(
        tmp_path, "SLO_r23.json", dict(GOOD_SLO, ttft_threshold_ms=300)
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "SLO x SERVEOBS" and not r["ok"]
        and "ttft_threshold_ms" in r["detail"] for r in rows
    ), rows
    # a host missing from /signals round rates fails the FLEET cross
    _write(
        tmp_path, "SLO_r23.json", dict(GOOD_SLO, round_rate_hosts=2)
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "SLO x FLEET" and not r["ok"] for r in rows
    ), rows
    # an unproven fleet plane (no dead-host detection) likewise
    _write(tmp_path, "SLO_r23.json", GOOD_SLO)
    _write(
        tmp_path, "FLEET_r14.json",
        dict(GOOD_FLEET, dead_detected=False),
    )
    rc, rows = g.check(str(tmp_path))
    assert rc == 1
    assert any(
        r["family"] == "SLO x FLEET" and not r["ok"]
        and "dead_detected" in r["detail"] for r in rows
    ), rows
