"""Transformer-LM workload tests (ISSUE 15): the byte-level text data
plane, the LM's Solver "net protocol" integration, the sp=1 vs sp=2
trajectory identity on the averaging trainer, composition with the
comm plane / hierarchy / health audit, and the journal-guided
bit-identical resume of a full ``apps/lm_app.py`` run (the text cursor
never skips or replays a window)."""

import glob
import hashlib
import json
import os

import numpy as np
import pytest
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from sparknet_tpu.config import parse_solver_prototxt
from sparknet_tpu.data.round_feed import stack_windows
from sparknet_tpu.data.text import (
    ByteTokenizer,
    TextWindowSampler,
    load_corpus,
    write_synthetic_corpus,
)
from sparknet_tpu.models.transformer_lm import TransformerLM
from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh
from sparknet_tpu.solver import Solver

SOLVER_TXT = (
    'base_lr: 0.1 lr_policy: "fixed" momentum: 0.9 '
    "weight_decay: 0.0001 average_loss: 20"
)
T, B, TAU, DP = 32, 4, 2, 2


def _solver_param():
    return parse_solver_prototxt(SOLVER_TXT)


@pytest.fixture(scope="module")
def docs(tmp_path_factory):
    d = tmp_path_factory.mktemp("corpus")
    write_synthetic_corpus(str(d), num_docs=4, words_per_doc=200, seed=0)
    return load_corpus(str(d))


def _build(sp, docs_or_none=None, **solver_kw):
    lm = TransformerLM(
        dim=32, depth=2, heads=2, seq_len=T,
        sp_axis="sp" if sp > 1 else None, sp_size=sp,
    )
    solver = Solver(_solver_param(), net=lm, **solver_kw)
    return lm, solver


def _mesh(sp):
    axes = {"dp": DP, "sp": sp} if sp > 1 else {"dp": DP}
    return make_mesh(axes, devices=jax.devices()[: DP * sp])


def _batch_spec(sp):
    if sp <= 1:
        return None
    spec = P("dp", None, None, "sp")
    return {"tokens": spec, "targets": spec}


def _place(host, mesh, sp):
    spec = P("dp", None, None, "sp") if sp > 1 else P("dp")
    s = NamedSharding(mesh, spec)
    return jax.device_put(host, {k: s for k in host})


def _run_rounds(sp, docs, rounds=2, **trainer_kw):
    lm, solver = _build(sp)
    mesh = _mesh(sp)
    trainer = ParameterAveragingTrainer(
        solver, mesh, batch_spec=_batch_spec(sp), **trainer_kw
    )
    state = trainer.init_state(seed=0)
    samplers = [
        TextWindowSampler(docs, T, B, seed=0, worker=w) for w in range(DP)
    ]
    all_losses = []
    for r in range(rounds):
        host = stack_windows([s.window_for_round(r, TAU) for s in samplers])
        out = trainer.round(state, _place(host, mesh, sp), round_index=r)
        state, losses = out[0], out[1]
        all_losses.append(np.asarray(jax.device_get(losses)))
    return jax.device_get(state), np.stack(all_losses), trainer


# ---------------------------------------------------------------------------
# text data plane
# ---------------------------------------------------------------------------


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    for s in ("hello world", "sparknet éµ"):
        ids = tok.encode(s)
        assert ids.dtype == np.uint8
        assert tok.decode(ids) == s
    assert tok.vocab_size == 256
    # bytes in, bytes' values out
    assert tok.encode(b"\x00\xff").tolist() == [0, 255]


def test_synthetic_corpus_seeded_and_cache_identical(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    write_synthetic_corpus(str(a), num_docs=3, seed=5)
    write_synthetic_corpus(str(b), num_docs=3, seed=5)
    da = load_corpus(str(a))
    db = load_corpus(str(b))
    assert da == db  # seeded: byte-identical corpora
    # the object_store + chunk-cache path serves the SAME bytes as the
    # direct read (verified fetch, file:// store)
    dc = load_corpus("file://" + str(a), cache_dir=str(tmp_path / "cc"))
    assert dc == da
    # and the cache now holds verified entries (a second load hits)
    dc2 = load_corpus("file://" + str(a), cache_dir=str(tmp_path / "cc"))
    assert dc2 == da


def test_empty_corpus_rejected(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_corpus(str(tmp_path))


def test_text_sampler_absolute_iter_cursor(docs):
    s = TextWindowSampler(docs, T, B, seed=3, worker=1)
    w5 = s.window_at(5)
    assert w5["tokens"].shape == (B, T) and w5["targets"].shape == (B, T)
    # pure in the absolute iter: a fresh sampler (a resumed process)
    # re-draws the identical window — the cursor IS the iter
    s2 = TextWindowSampler(docs, T, B, seed=3, worker=1)
    for k in w5:
        np.testing.assert_array_equal(w5[k], s2.window_at(5)[k])
    # distinct iters/workers decorrelate
    assert not np.array_equal(w5["tokens"], s.window_at(6)["tokens"])
    s3 = TextWindowSampler(docs, T, B, seed=3, worker=2)
    assert not np.array_equal(w5["tokens"], s3.window_at(5)["tokens"])
    # next-token supervision: targets are tokens shifted by one
    np.testing.assert_array_equal(
        w5["tokens"][:, 1:], w5["targets"][:, :-1]
    )


def test_text_sampler_round_window_stacks_iters(docs):
    s = TextWindowSampler(docs, T, B, seed=0, worker=0)
    win = s.window_for_round(3, TAU)
    assert win["tokens"].shape == (TAU, B, T)
    for t in range(TAU):
        np.testing.assert_array_equal(
            win["tokens"][t], s.window_at(3 * TAU + t)["tokens"]
        )


def test_text_sampler_cursor_verification(docs):
    s = TextWindowSampler(docs, T, B, seed=0, worker=0)
    cur = s.cursor_for_iter(7)
    s.verify_cursor(cur)  # self-consistent
    with pytest.raises(ValueError, match="seq_len"):
        TextWindowSampler(docs, 16, B).verify_cursor(cur)
    with pytest.raises(ValueError, match="seed"):
        TextWindowSampler(docs, T, B, seed=9).verify_cursor(cur)


def test_text_sampler_too_small_corpus_rejected():
    with pytest.raises(ValueError, match="seq_len"):
        TextWindowSampler([b"tiny"], 128, 2)


# ---------------------------------------------------------------------------
# the model + solver net protocol
# ---------------------------------------------------------------------------


def test_lm_blob_plan_matches_init():
    lm = TransformerLM(dim=32, depth=2, heads=2, seq_len=T)
    params, stats = lm.init(0)
    assert stats == {}
    lr, decay = lm.param_multipliers()
    for group, shapes in lm._blob_plan():
        assert [tuple(b.shape) for b in params[group]] == shapes
        assert len(lr[group]) == len(decay[group]) == len(shapes)
        for s, d in zip(shapes, decay[group]):
            # matrices decay, LN gains/biases and biases do not
            assert d == (1.0 if len(s) > 1 else 0.0)
    assert lm.num_params() == sum(
        int(np.prod(b.shape)) for bs in params.values() for b in bs
    )
    # checkpoint protocol: every group's refs line up with its blobs
    for layer in lm.layers:
        refs = lm._blob_refs[layer.name]
        assert [r.index for r in refs] == list(range(len(refs)))
        assert all(r.owner == layer.name for r in refs)


def test_lm_rejects_bad_geometry():
    with pytest.raises(ValueError, match="divisible"):
        TransformerLM(dim=30, heads=4)
    with pytest.raises(ValueError, match="sp"):
        TransformerLM(seq_len=30, sp_axis="sp", sp_size=4)
    with pytest.raises(ValueError, match="sp_axis"):
        TransformerLM(sp_size=2)


def test_lm_causal_logits():
    # causality: perturbing future tokens must not change earlier
    # logits (the dense sp=1 path; the ring path is pinned against it)
    lm = TransformerLM(dim=32, depth=2, heads=2, seq_len=16)
    params, _ = lm.init(0)
    rng = np.random.RandomState(0)
    t1 = rng.randint(0, 256, (2, 16)).astype(np.int32)
    t2 = t1.copy()
    t2[:, 10:] = (t2[:, 10:] + 17) % 256
    l1 = np.asarray(lm.forward_logits(params, t1))
    l2 = np.asarray(lm.forward_logits(params, t2))
    np.testing.assert_allclose(l1[:, :10], l2[:, :10], atol=1e-5)
    assert not np.allclose(l1[:, 10:], l2[:, 10:])


def test_solver_accepts_net_object(docs):
    lm, solver = _build(1)
    assert solver.net is lm
    state = solver.init_state(seed=0)
    s = TextWindowSampler(docs, T, B, seed=0, worker=0)
    win = s.window_for_round(0, TAU)
    state, losses = solver.step(state, win)
    vals = np.asarray(jax.device_get(losses))
    assert vals.shape == (TAU,) and np.all(np.isfinite(vals))
    # a second window trains further (the loss moves)
    state, losses2 = solver.step(state, s.window_for_round(1, TAU))
    assert float(np.mean(np.asarray(jax.device_get(losses2)))) < float(
        np.mean(vals)
    )
    # no prototxt TEST view behind a net object
    with pytest.raises(ValueError, match="net object"):
        solver.test_net
    # net= and net_param= are mutually exclusive
    from sparknet_tpu import models

    with pytest.raises(ValueError, match="not both"):
        Solver(
            _solver_param(), net=lm,
            net_param=models.load_model("cifar10_quick"),
        )


def test_lm_snapshot_restore_roundtrip(tmp_path, docs):
    """The LM rides the existing checkpoint machinery: snapshot a
    trained state, restore it, bit-identical params/history/iter."""
    from sparknet_tpu.io import checkpoint

    lm, solver = _build(1)
    state = solver.init_state(seed=0)
    s = TextWindowSampler(docs, T, B, seed=0, worker=0)
    state, _ = solver.step(state, s.window_for_round(0, TAU))
    prefix = str(tmp_path / "lm_ck")
    checkpoint.snapshot(solver, state, prefix, fmt="BINARYPROTO")
    restored, used = checkpoint.restore_newest_valid(solver, prefix)
    got = jax.device_get(restored)
    want = jax.device_get(state)
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sequence parallelism on the averaging trainer
# ---------------------------------------------------------------------------


def test_sp_trajectory_matches_dense(docs):
    """The tentpole identity: dp=2/sp=2 ring-attention rounds
    reproduce the dp=2 dense-attention rounds up to float
    associativity (same seeded init, same windows, same tau)."""
    st1, l1, _ = _run_rounds(1, docs, rounds=2)
    st2, l2, _ = _run_rounds(2, docs, rounds=2)
    assert np.max(np.abs(l1 - l2)) < 5e-5
    for a, b in zip(
        jax.tree_util.tree_leaves(st1.params),
        jax.tree_util.tree_leaves(st2.params),
    ):
        assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) < 5e-5


def test_sp_round_with_audit_and_mask(docs):
    """The health sentry's in-graph audit composes onto the sp round
    unchanged: stats ride the jitted program, loss/grad norms finite,
    and the live_mask epilogue still renormalizes."""
    lm, solver = _build(2)
    solver.audit = True
    mesh = _mesh(2)
    trainer = ParameterAveragingTrainer(
        solver, mesh, batch_spec=_batch_spec(2)
    )
    state = trainer.init_state(seed=0)
    samplers = [
        TextWindowSampler(docs, T, B, seed=0, worker=w) for w in range(DP)
    ]
    host = stack_windows([s.window_for_round(0, TAU) for s in samplers])
    live = np.array([1.0, 1.0], np.float32)
    state, losses, stats = trainer.round(
        state, _place(host, mesh, 2), live_mask=live, round_index=0
    )
    got = jax.device_get(stats)
    assert np.all(np.isfinite(np.asarray(got["grad_norm"])))
    assert int(np.sum(np.asarray(got["nonfinite_grads"]))) == 0
    assert np.asarray(got["masked"]).shape == (DP,)
    assert np.all(np.isfinite(np.asarray(jax.device_get(losses))))


def test_sp_composes_with_comm_and_hierarchy(docs):
    """int8 delta averaging + a 2-slice K=2 hierarchy on the sp=2 LM:
    the generalized batch_spec threads through the comm plane's local
    program and the slice round, losses stay finite and decrease."""
    from sparknet_tpu.parallel.hierarchy import HierarchySpec

    spec = HierarchySpec.grouped(DP, 2, cross_slice_every=2)
    _, losses, trainer = _run_rounds(
        2, docs, rounds=4, compress="int8", hierarchy=spec
    )
    assert trainer._comm is not None and trainer._two_tier
    assert np.all(np.isfinite(losses))
    assert losses[-1].mean() < losses[0].mean()


def test_ring_hop_bytes_model():
    lm1 = TransformerLM(dim=32, depth=2, heads=2, seq_len=T)
    assert lm1.ring_hop_bytes_per_iter(B) == 0  # no ring at sp=1
    lm2 = lm1.with_sp("sp", 2)
    expect = 2 * 2 * (B * (T // 2) * 32 * 4) * (2 * 1) * 2
    assert lm2.ring_hop_bytes_per_iter(B) == expect


# ---------------------------------------------------------------------------
# the app: full surface + journal-guided bit-identical resume
# ---------------------------------------------------------------------------

_APP_COMMON = [
    "--tau", str(TAU), "--batch", str(B), "--seq_len", str(T),
    "--dim", "32", "--workers", str(DP), "--log_every", "50",
]


def _final_snapshot_digest(prefix):
    """sha256 over the newest snapshot's jobstate + solverstate +
    caffemodel bytes — bit-identity of two runs == equal digests."""
    js = sorted(
        glob.glob(prefix + "_iter_*.jobstate.npz"),
        key=lambda p: int(p.split("_iter_")[-1].split(".")[0]),
    )[-1]
    h = hashlib.sha256()
    with np.load(js, allow_pickle=False) as z:
        for k in sorted(z.files):
            h.update(k.encode())
            h.update(np.asarray(z[k]).tobytes())
    with np.load(js.replace(".jobstate.npz", ".solverstate.npz")) as z:
        for k in sorted(z.files):
            h.update(k.encode())
            h.update(np.asarray(z[k]).tobytes())
    with open(js.replace(".jobstate.npz", ".caffemodel"), "rb") as f:
        h.update(f.read())
    return os.path.basename(js), h.hexdigest()


def test_lm_app_journal_resume_bit_identical(tmp_path):
    """The acceptance e2e: an LM run (sp=2, health audit on, journal +
    per-round snapshots) interrupted after round 2 and journal-resumed
    to round 5 produces EXACTLY the uninterrupted run's final job
    state — params, per-worker momentum, comm-free history, sentry
    EMA and the text cursor all bit-identical, windows never skipped
    or replayed."""
    from sparknet_tpu.apps import lm_app

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    common = _APP_COMMON + [
        "--sp", "2", "--corpus", str(corpus), "--health", "warn",
        "--journal", "--snapshot_every", "1",
    ]
    pa = str(tmp_path / "a" / "ck")
    os.makedirs(os.path.dirname(pa))
    assert lm_app.main(
        ["--rounds", "5", "--snapshot_prefix", pa] + common
    ) == 0
    pb = str(tmp_path / "b" / "ck")
    os.makedirs(os.path.dirname(pb))
    assert lm_app.main(
        ["--rounds", "2", "--snapshot_prefix", pb] + common
    ) == 0
    assert lm_app.main(
        ["--rounds", "5", "--snapshot_prefix", pb, "--resume"] + common
    ) == 0
    na, da = _final_snapshot_digest(pa)
    nb, db = _final_snapshot_digest(pb)
    assert na == nb  # same final boundary
    assert da == db  # bit-identical full job state

    # the ledger carries the text cursor and proves exactly-once
    # window consumption: every round 0..4 has exactly one intent and
    # one commit across the interrupted+resumed ledger, cursors in
    # absolute-iter order with no gaps or repeats
    from sparknet_tpu.io import journal as journal_mod

    records, _ = journal_mod.scan(
        journal_mod.default_journal_path(pb)
    )
    intents = [r for r in records if r.get("kind") == "intent"]
    commits = [r for r in records if r.get("kind") == "commit"]
    assert [r["round"] for r in intents] == list(range(5))
    assert [r["round"] for r in commits] == list(range(5))
    assert [r["cursor"]["text_iter"] for r in intents] == [
        r * TAU for r in range(5)
    ]


def test_lm_app_resume_with_sparse_snapshots_never_skips(tmp_path):
    """Regression: with --snapshot_every 2 the rounds BETWEEN
    snapshot boundaries stay UNCOMMITTED in the ledger (a progress
    commit the restore path cannot rewind to would make --resume skip
    them).  An interrupted run resumed mid-gap must re-execute the
    uncommitted rounds and land bit-identical to the uninterrupted
    control."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.io import journal as journal_mod

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    common = _APP_COMMON + [
        "--sp", "1", "--corpus", str(corpus),
        "--journal", "--snapshot_every", "2",
    ]
    pa = str(tmp_path / "a" / "ck")
    os.makedirs(os.path.dirname(pa))
    assert lm_app.main(
        ["--rounds", "6", "--snapshot_prefix", pa] + common
    ) == 0
    # interrupt after round 2 — one round PAST the last boundary
    # (snapshot_every=2 commits at rounds 1, 3, 5)
    pb = str(tmp_path / "b" / "ck")
    os.makedirs(os.path.dirname(pb))
    assert lm_app.main(
        ["--rounds", "3", "--snapshot_prefix", pb] + common
    ) == 0
    records, _ = journal_mod.scan(journal_mod.default_journal_path(pb))
    commits = [r["round"] for r in records if r["kind"] == "commit"]
    assert commits == [1]  # round 2 deliberately uncommitted
    assert lm_app.main(
        ["--rounds", "6", "--snapshot_prefix", pb, "--resume"] + common
    ) == 0
    # round 2 re-executed (never skipped): its window re-drawn off the
    # absolute-iter cursor, and the final state bit-identical
    records, _ = journal_mod.scan(journal_mod.default_journal_path(pb))
    intents = [r["round"] for r in records if r["kind"] == "intent"]
    assert intents == [0, 1, 2, 2, 3, 4, 5]  # one replay, no gaps
    assert [
        r["round"] for r in records if r["kind"] == "commit"
    ] == [1, 3, 5]
    na, da = _final_snapshot_digest(pa)
    nb, db = _final_snapshot_digest(pb)
    assert na == nb and da == db


def test_lm_app_resume_journal_without_snapshots_starts_fresh(tmp_path):
    """Regression: --journal with a --snapshot_prefix but
    --snapshot_every 0 (no snapshots ever published) must leave every
    round UNCOMMITTED — a progress commit here would make --resume
    crash claiming durable work vanished (the reconciler treats every
    commit as a durable boundary, and there is no snapshot to rewind
    to).  Resume reconciles to a clean fresh start instead."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.io import journal as journal_mod

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    prefix = str(tmp_path / "ck" / "ck")
    os.makedirs(os.path.dirname(prefix))
    common = _APP_COMMON + [
        "--sp", "1", "--corpus", str(corpus), "--journal",
        "--snapshot_prefix", prefix,
    ]
    assert lm_app.main(["--rounds", "2"] + common) == 0
    records, _ = journal_mod.scan(
        journal_mod.default_journal_path(prefix)
    )
    assert [r["round"] for r in records if r["kind"] == "intent"] == [
        0, 1,
    ]
    assert not [r for r in records if r["kind"] == "commit"]
    # resume consumes the ledger, finds no committed boundary, and
    # starts fresh at round 0 (no SnapshotCorrupt, no skipped rounds)
    assert lm_app.main(["--rounds", "2", "--resume"] + common) == 0


def test_lm_app_elastic_hierarchy_surface(tmp_path):
    """The LM app runs the --slices/--cross_slice_every/--elastic +
    --obs surface end to end (two-tier schedule over the dp axis with
    the sp ring inside each worker; the telemetry sidecar on an
    ephemeral port)."""
    from sparknet_tpu.apps import lm_app

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    rc = lm_app.main(
        _APP_COMMON
        + [
            "--rounds", "4", "--sp", "2", "--corpus", str(corpus),
            "--slices", "2", "--cross_slice_every", "2", "--elastic",
            "--obs", "--obs_port", "0",
        ]
    )
    assert rc == 0
    # the LM series actually counted (the obs run enabled metrics)
    from sparknet_tpu import obs as _obs

    tm = _obs.training_metrics()
    assert tm is not None
    assert tm.lm_tokens.value >= 4 * DP * TAU * B * T
    assert tm.lm_ring_bytes.value > 0


def test_lm_app_rejects_bad_geometry():
    from sparknet_tpu.apps import lm_app

    with pytest.raises(SystemExit, match="seq_len"):
        lm_app.main(["--seq_len", "30", "--sp", "4"])
    with pytest.raises(SystemExit, match="snapshot_prefix"):
        lm_app.main(["--resume"])


def test_lm_app_resume_missing_prefix_fails_loudly(tmp_path):
    """A --resume pointing at a prefix with no ledger and no snapshots
    (a typo, moved files) must fail loudly — the
    imagenet_run_db_app contract — instead of silently retraining the
    whole run from round 0 under the wrong prefix."""
    from sparknet_tpu.apps import lm_app

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    with pytest.raises(SystemExit, match="no ledger and no snapshots"):
        lm_app.main(
            _APP_COMMON
            + [
                "--rounds", "2", "--corpus", str(corpus), "--resume",
                "--snapshot_prefix", str(tmp_path / "nope" / "ck"),
            ]
        )


def test_lm_app_resume_uncommitted_ledger_starts_fresh(tmp_path):
    """A ledger whose first boundary never committed (crash between
    the snapshot publish and the commit append) must resume as a
    FRESH start at round 0 — never consuming a snapshot the ledger
    does not vouch for — and still complete bit-identically to an
    uninterrupted run."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.io import journal as journal_mod

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    common = _APP_COMMON + [
        "--sp", "1", "--corpus", str(corpus),
        "--journal", "--snapshot_every", "2",
    ]
    pa = str(tmp_path / "a" / "ck")
    os.makedirs(os.path.dirname(pa))
    assert lm_app.main(
        ["--rounds", "2", "--snapshot_prefix", pa] + common
    ) == 0
    # the torn first boundary: a ledger holding one dangling intent
    pb = str(tmp_path / "b" / "ck")
    os.makedirs(os.path.dirname(pb))
    with journal_mod.RunJournal(
        journal_mod.default_journal_path(pb)
    ) as jr:
        jr.begin_round(0, iter=0)
    assert lm_app.main(
        ["--rounds", "2", "--snapshot_prefix", pb, "--resume"] + common
    ) == 0
    na, da = _final_snapshot_digest(pa)
    nb, db = _final_snapshot_digest(pb)
    assert na == nb and da == db  # round 0 re-executed, nothing skipped


def test_cli_train_lm_dispatch(tmp_path):
    """``cli train --lm`` hands the line to the LM driver (no
    prototxt --solver required)."""
    from sparknet_tpu.tools import cli

    corpus = tmp_path / "corpus"
    write_synthetic_corpus(str(corpus), num_docs=4, seed=0)
    rc = cli.main(
        ["train", "--lm", "--rounds", "2", "--corpus", str(corpus)]
        + _APP_COMMON
        + ["--sp", "1"]
    )
    assert rc == 0


@pytest.mark.parametrize("lowers, seq_len, sp, fused", [
    (True, 1024, 1, 1.0),
    (True, 65536, 1, 0.0),  # dk/dv over 65,536 keys: 64 MiB of VMEM
    (True, 65536, 8, 1.0),  # a ring shard's 8,192
    (False, 1024, 1, 0.0),
])
def test_the_attention_backward_gauge_names_the_one_pass(
        monkeypatch, lowers, seq_len, sp, fused):
    """``sparknet_kernel_path{kernel="attention_backward"}``: 1 where the
    byte LM's attention takes the kernels and their backward is the one
    pass, by ``pallas_attention.backward_path`` at its shapes."""
    import argparse

    from sparknet_tpu import obs
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.ops import pallas_attention

    monkeypatch.setattr(pallas_attention, "lowerable", lambda: lowers)
    tm = obs.enable_training_metrics()
    args = argparse.Namespace(
        dim=256, depth=1, heads=2, seq_len=seq_len, base_lr=0.1,
        momentum=0.9, weight_decay=0.0)
    lm_app.build_lm_solver(args, sp)
    assert tm.kernel_path.labels("attention").value == float(lowers)
    assert tm.kernel_path.labels("attention_backward").value == fused
