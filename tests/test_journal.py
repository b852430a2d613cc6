"""Run journal (``io/journal.py``) + full-job-state snapshots
(``io/checkpoint.py`` extra_state / journal-guided restore): the
crash-consistency primitives behind ``runtime/recover.py``.

Unit-level proofs: CRC framing + torn-tail truncation, fsync policy,
intent/commit reconciliation (exactly-once rules), the jobstate
companion riding the CRC manifest, ledger-vs-snapshot reconciliation
(uncommitted snapshots ignored), the ``_atomic`` crash seam, and the
CLI surface."""

import json
import os

import numpy as np
import pytest

from sparknet_tpu import config
from sparknet_tpu.io import checkpoint
from sparknet_tpu.io import journal as journal_mod
from sparknet_tpu.io.journal import RunJournal, scan
from sparknet_tpu.solver import Solver

NET = """
name: "jr_net"
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
    return Solver(sp, net_param=config.parse_net_prototxt(NET))


def _batches(tau, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": rng.randn(tau, 8, 6).astype(np.float32),
        "label": rng.randint(0, 4, (tau, 8)).astype(np.float32),
    }


class _Boom(BaseException):
    pass


def _boom():
    raise _Boom()


# ---------------------------------------------------------------------------
# framing + durability


def test_append_scan_roundtrip(tmp_path):
    p = str(tmp_path / "r.journal")
    j = RunJournal(p)
    j.begin_round(0, iter=0, cursor=0, view_epoch=0)
    j.commit_round(0, iter=2, snapshot="s_iter_2.solverstate.npz")
    j.close()
    recs, torn = scan(p)
    assert torn == 0
    assert [r["kind"] for r in recs] == ["intent", "commit"]
    assert recs[0]["round"] == 0 and recs[0]["cursor"] == 0
    assert recs[1]["snapshot"] == "s_iter_2.solverstate.npz"
    # reopen resumes the same record list and keeps appending
    j2 = RunJournal(p)
    assert len(j2.records) == 2
    j2.begin_round(1, iter=2)
    j2.close()
    assert len(scan(p)[0]) == 3


def test_torn_tail_truncated_on_open(tmp_path):
    """A kill mid-append leaves half a frame; the partial record fails
    its CRC, open() truncates it, and later appends extend a clean
    ledger — the record being written never half-exists."""
    p = str(tmp_path / "r.journal")
    j = RunJournal(p)
    j.begin_round(0, iter=0)
    j.commit_round(0, iter=2, snapshot="s")
    j.crash_hook = _boom
    with pytest.raises(_Boom):
        j.begin_round(1, iter=2)
    j.close()
    size_torn = os.path.getsize(p)
    recs, torn = scan(p)
    assert len(recs) == 2 and torn > 0
    j2 = RunJournal(p)
    assert j2.truncated_bytes == torn
    assert os.path.getsize(p) == size_torn - torn
    assert [r["kind"] for r in j2.records] == ["intent", "commit"]
    # the healed ledger appends cleanly
    j2.begin_round(1, iter=2)
    j2.close()
    recs, torn = scan(p)
    assert torn == 0 and len(recs) == 3


def test_garbage_tail_is_unreachable_not_fatal(tmp_path):
    p = str(tmp_path / "r.journal")
    j = RunJournal(p)
    j.commit_round(3, iter=8, snapshot="s")
    j.close()
    with open(p, "ab") as f:
        f.write(b"\x00garbage that is not a frame")
    recs, torn = scan(p)
    assert len(recs) == 1 and torn > 0
    j2 = RunJournal(p)
    assert j2.last_committed_round == 3


def test_fsync_policy_validation(tmp_path):
    with pytest.raises(ValueError, match="fsync"):
        RunJournal(str(tmp_path / "x.journal"), fsync="sometimes")
    for ok in ("always", "commit", "never"):
        RunJournal(str(tmp_path / f"{ok}.journal"), fsync=ok).close()


# ---------------------------------------------------------------------------
# reconciliation: the exactly-once rules


def test_reconcile_clean_vs_in_flight(tmp_path):
    j = RunJournal(str(tmp_path / "r.journal"))
    assert j.reconcile()["resume_round"] == 0
    assert j.last_committed_round is None
    j.begin_round(0, iter=0)
    # intent with no commit: round 0 in flight, re-execute it
    rec = j.reconcile()
    assert rec["in_flight_round"] == 0 and rec["resume_round"] == 0
    j.commit_round(0, iter=2, snapshot="s0")
    rec = j.reconcile()
    assert rec["last_committed_round"] == 0
    assert rec["in_flight_round"] is None
    assert rec["resume_round"] == 1  # never re-execute a committed round
    assert rec["snapshot"] == "s0" and rec["commit_iter"] == 2
    j.begin_round(1, iter=2)
    rec = j.reconcile()
    # round 1 in flight == the resume round: never skipped
    assert rec["in_flight_round"] == 1 == rec["resume_round"]
    j.close()


def test_reconcile_snapshot_ref_walks_past_undurable_commits(tmp_path):
    """Cadenced snapshots: commits without a ref are progress markers;
    the rewind target is the newest commit WITH a snapshot."""
    j = RunJournal(str(tmp_path / "r.journal"))
    j.commit_round(0, iter=2, snapshot="s0")
    j.commit_round(1, iter=4, durable=False)
    rec = j.reconcile()
    assert rec["snapshot"] == "s0"
    assert rec["commit_iter"] == 4  # the newest commit's boundary
    j.close()


# ---------------------------------------------------------------------------
# jobstate companion + manifest integration


def _job_state():
    return {
        "comm": {
            "compress": "int8",
            "resid": {"0": np.arange(6, dtype=np.float32)},
        },
        "sentry": {"ema": 1.25, "seen": 3, "cooldown": 0},
        "cursor": {"next_round": 4},
    }


def test_snapshot_with_extra_state_roundtrips(tmp_path):
    solver = _solver()
    state = solver.init_state(seed=0)
    state, _ = solver.step(state, _batches(2))
    prefix = str(tmp_path / "ck")
    model_path, state_path = checkpoint.snapshot(
        solver, state, prefix, extra_state=_job_state()
    )
    jpath = checkpoint.jobstate_path_for(state_path)
    assert os.path.exists(jpath)
    # the manifest vouches for the jobstate file too
    with open(checkpoint.manifest_path_for(state_path)) as f:
        manifest = json.load(f)
    assert os.path.basename(jpath) in manifest["files"]
    checkpoint.verify_snapshot(state_path)
    js = checkpoint.load_job_state(state_path)
    assert js["sentry"]["ema"] == 1.25 and js["sentry"]["seen"] == 3
    assert js["cursor"]["next_round"] == 4
    assert js["comm"]["compress"] == "int8"
    np.testing.assert_array_equal(
        js["comm"]["resid"]["0"], np.arange(6, dtype=np.float32)
    )
    # a plain snapshot has no jobstate: load returns None
    model2, state2 = checkpoint.snapshot(
        solver, state._replace(iter=np.asarray(99, np.int32)),
        prefix,
    )
    assert checkpoint.load_job_state(state2) is None


def test_corrupt_jobstate_fails_manifest_and_quarantines(tmp_path):
    solver = _solver()
    state = solver.init_state(seed=0)
    prefix = str(tmp_path / "ck")
    checkpoint.snapshot(solver, state, prefix)  # older, clean
    state, _ = solver.step(state, _batches(2))
    _, state_path = checkpoint.snapshot(
        solver, state, prefix, extra_state=_job_state()
    )
    jpath = checkpoint.jobstate_path_for(state_path)
    with open(jpath, "r+b") as f:
        f.seek(os.path.getsize(jpath) // 2)
        f.write(b"\xa5\xa5\xa5\xa5")
    with pytest.raises(checkpoint.SnapshotCorrupt):
        checkpoint.verify_snapshot(state_path)
    # the fallback scan quarantines ALL of it (jobstate included) and
    # restores the older clean snapshot
    st, used = checkpoint.restore_newest_valid(solver, prefix)
    assert used != state_path
    assert os.path.exists(jpath + ".corrupt")
    assert not os.path.exists(jpath)


# ---------------------------------------------------------------------------
# journal-guided restore (ledger vs snapshot reconciliation)


def test_journaled_restore_ignores_uncommitted_snapshot(tmp_path):
    """A snapshot published for a round whose commit never landed (kill
    between the publish and the journal append) must NOT be restored:
    its round is uncommitted and re-executes from the previous
    boundary."""
    solver = _solver()
    state = solver.init_state(seed=0)
    prefix = str(tmp_path / "ck")
    j = RunJournal(str(tmp_path / "r.journal"))
    # round 0 committed at iter 2
    state, _ = solver.step(state, _batches(2, seed=0))
    _, sp0 = checkpoint.snapshot(solver, state, prefix)
    j.commit_round(0, iter=2, snapshot=os.path.basename(sp0))
    # round 1: snapshot published, commit NEVER lands
    j.begin_round(1, iter=2)
    state, _ = solver.step(state, _batches(2, seed=1))
    checkpoint.snapshot(solver, state, prefix)
    st, used, js, info = checkpoint.restore_newest_valid_journaled(
        solver, prefix, j
    )
    assert os.path.basename(used) == os.path.basename(sp0)
    assert int(np.asarray(st.iter)) == 2
    assert info["resume_round"] == 1 == info["in_flight_round"]
    j.close()


def test_journaled_restore_quarantines_corrupt_ref_and_falls_back(
    tmp_path,
):
    solver = _solver()
    state = solver.init_state(seed=0)
    prefix = str(tmp_path / "ck")
    j = RunJournal(str(tmp_path / "r.journal"))
    state, _ = solver.step(state, _batches(2, seed=0))
    _, sp0 = checkpoint.snapshot(solver, state, prefix)
    j.commit_round(0, iter=2, snapshot=os.path.basename(sp0))
    state, _ = solver.step(state, _batches(2, seed=1))
    _, sp1 = checkpoint.snapshot(solver, state, prefix)
    j.commit_round(1, iter=4, snapshot=os.path.basename(sp1))
    # the committed ref corrupts on disk -> quarantined, fall back
    with open(sp1, "r+b") as f:
        f.seek(10)
        f.write(b"\xa5\xa5\xa5\xa5")
    st, used, js, info = checkpoint.restore_newest_valid_journaled(
        solver, prefix, j
    )
    assert os.path.basename(used) == os.path.basename(sp0)
    assert os.path.exists(sp1 + ".corrupt")
    j.close()


def test_journaled_restore_no_commits_raises_filenotfound(tmp_path):
    solver = _solver()
    j = RunJournal(str(tmp_path / "r.journal"))
    j.begin_round(0, iter=0)
    with pytest.raises(FileNotFoundError):
        checkpoint.restore_newest_valid_journaled(
            solver, str(tmp_path / "ck"), j
        )
    j.close()


# ---------------------------------------------------------------------------
# the _atomic crash seam (snapshot-mid-write kill point)


def test_atomic_crash_hook_fires_before_publish(tmp_path):
    target = str(tmp_path / "out.bin")
    seen = []

    def hook(path):
        seen.append(path)
        raise _Boom()

    checkpoint.set_crash_hook(hook)
    try:
        with pytest.raises(_Boom):
            checkpoint._atomic(
                lambda p: open(p, "wb").write(b"data"), target
            )
    finally:
        checkpoint.set_crash_hook(None)
    assert seen == [target]
    assert not os.path.exists(target)  # never published
    assert os.listdir(str(tmp_path)) == []  # tmp cleanly abandoned


# ---------------------------------------------------------------------------
# CLI surface


class _Args:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_journal_from_args_auto_rule(tmp_path):
    path = str(tmp_path / "p_run.journal")
    # fresh run, auto default: off
    assert journal_mod.journal_from_args(_Args(journal=None), path) is None
    # explicit --no_journal: off even when a ledger exists
    RunJournal(path).close()
    assert (
        journal_mod.journal_from_args(
            _Args(journal=False), path, resuming=True
        )
        is None
    )
    # resume + existing ledger: consumed automatically
    j = journal_mod.journal_from_args(
        _Args(journal=None), path, resuming=True
    )
    assert j is not None and j.path == path
    j.close()
    # explicit --journal: on for fresh runs too (and honors the
    # fsync/path overrides)
    other = str(tmp_path / "other.journal")
    j = journal_mod.journal_from_args(
        _Args(journal=True, journal_path=other, journal_fsync="never"),
        path,
    )
    assert j.path == other and j.fsync == "never"
    j.close()


def test_add_cli_args_surface(tmp_path):
    import argparse

    p = argparse.ArgumentParser()
    journal_mod.add_cli_args(p)
    a = p.parse_args([])
    assert a.journal is None and a.journal_fsync == "commit"
    assert p.parse_args(["--journal"]).journal is True
    assert p.parse_args(["--no_journal"]).journal is False
    with pytest.raises(SystemExit):
        p.parse_args(["--journal", "--no_journal"])  # mutually exclusive
