"""LMApp — sequence-parallel language modeling on the averaging stack.

The first non-CNN workload (ROADMAP scenario diversity): a byte-level
decoder-only transformer (``models/transformer_lm.py``) trained by the
SAME ``ParameterAveragingTrainer`` / RoundFeed / obs / health /
journal / elastic machinery every CIFAR/ImageNet app uses — proving
the stack is SparkNet-class for sequence models, not just Caffe-era
convnets.

Mesh layout: ``dp x sp``.  The ``dp`` axis is the familiar worker
axis (tau local steps, then parameter averaging); ``--sp N`` addition-
ally shards every worker's SEQUENCE dimension N ways — attention runs
the ``parallel/ring_attention.py`` construction inside the round's
``shard_map`` (KV rotating one ICI hop per ring step), the gradients
of the sp-replicated params are summed over the ring by ``shard_map``'s
varying-axes typing, and the trajectory matches the sp=1 run up to
float associativity
(``tests/test_lm.py::test_sp_trajectory_matches_dense``).

Data: documents fetched through ``object_store`` + ``ChunkCache``
(``data/text.py``), windows drawn by absolute-iteration cursor — the
journal's round intents carry the text cursor, ``.jobstate.npz``
carries it beside the per-worker momentum stacks, and ``--resume`` is
journal-guided and BIT-IDENTICAL (the window sequence never skips or
replays; ``tests/test_lm.py`` kills and resumes to prove it).

Run:
    python -m sparknet_tpu.apps.lm_app --rounds 20 --sp 2
(synthesizes a seeded corpus and serves it through a file:// chunk
cache when --corpus is omitted)
"""

from __future__ import annotations

import argparse
import functools
import os
import tempfile

import numpy as np

TAU = 4


def add_lm_model_args(parser) -> None:
    parser.add_argument(
        "--model_config", default=None,
        help="a configuration file (benchmark/configs/*.json: the keys of a "
        "published config.json plus experts_held and compute_dtype): trains "
        "models/hybrid_lm.HybridMoELM with ADAM at the file's solver "
        "settings instead of the byte-level TransformerLM; --dim / --depth / "
        "--heads and the SGD flags are then unused, --sp must be 1",
    )
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--heads", type=int, default=2)
    parser.add_argument("--base_lr", type=float, default=0.1)
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--weight_decay", type=float, default=1e-4)


def build_hybrid_lm_solver(config: dict):
    """(HybridMoELM, Solver) from a configuration dict: ADAM at the
    configuration's ``solver`` settings, the configuration's
    ``compute_dtype`` handed to the model through the ``Solver``.  Shared
    with the benchmark's ``lm-train-resident`` kind."""
    from sparknet_tpu.config import parse_solver_prototxt
    from sparknet_tpu.models.hybrid_lm import HybridMoELM
    from sparknet_tpu.solver import Solver

    lm = HybridMoELM(config)
    s = config.get("solver", {})
    solver_param = parse_solver_prototxt(
        'type: "ADAM" lr_policy: "fixed" '
        f"base_lr: {s.get('base_lr', 3e-4)} "
        f"momentum: {s.get('momentum', 0.9)} "
        f"momentum2: {s.get('momentum2', 0.95)} "
        f"delta: {s.get('delta', 1e-8)} "
        f"weight_decay: {s.get('weight_decay', 0.0)} "
        f"iter_size: {s.get('iter_size', 1)} "
        "average_loss: 20"
    )
    solver = Solver(
        solver_param, net=lm, compute_dtype=config.get("compute_dtype"))
    return lm, solver


def set_routing_gauges(lm, stacked_params, tokens, stacked_stats=None):
    """Where one batch of ``(B, T)`` tokens goes, per layer: assignments to
    the held experts a token and the largest held expert's load over the
    mean (``models/hybrid_lm.routing_gauges``), and the held assignments
    over the grouped expert path's rows (``ops/moe.fast_rows_for``: the share
    of those rows the grouped products still run where they stop at the
    held assignments, over 1 where the layer runs in token chunks), set as
    the gauges ``sparknet_lm_held_assignments_per_token`` /
    ``sparknet_lm_held_load_skew`` / ``sparknet_lm_grouped_rows_used_share``
    where training metrics are on, and returned.  Outside the timed loop: it
    runs a forward pass.  ``stacked_params`` are the trainer's (worker-major);
    worker 0 is sliced inside the jit, so no copy of the weights is made.
    ``stacked_stats`` carry the routers' selection biases, where the model
    has them; without, the selection is on the unbiased scores."""
    import jax

    from sparknet_tpu import obs
    from sparknet_tpu.models.hybrid_lm import routing_gauges
    from sparknet_tpu.ops import moe
    from sparknet_tpu.parallel import first_worker

    counts = jax.jit(lambda p, s: lm.routing_counts(
        first_worker(p), tokens, first_worker(s)))(
            stacked_params, stacked_stats or {})
    n_tokens = int(np.prod(tokens.shape))
    gauges = routing_gauges(counts, tokens=n_tokens)
    rows = moe.fast_rows_for(
        n_tokens, lm.config["num_experts_per_tok"], lm.config["num_experts"],
        lm.experts_held[1])
    gauges["grouped_rows_used_share"] = list(
        np.asarray(counts, np.float64).sum(axis=1) / rows)
    tm = obs.training_metrics()
    if tm is not None:
        for i, per_token, skew, used in zip(
                lm.routed_layers, gauges["held_assignments_per_token"],
                gauges["held_load_skew"], gauges["grouped_rows_used_share"]):
            tm.lm_held_assignments.labels(str(i)).set(per_token)
            tm.lm_held_load_skew.labels(str(i)).set(skew)
            tm.lm_grouped_rows_used.labels(str(i)).set(used)
    return gauges


@functools.lru_cache(maxsize=4)
def selection_probe(lm):
    """``(stacked params, stacked stats, tokens (B, T)) ->
    models/hybrid_lm.selection_readings`` of worker 0 (sliced inside the jit,
    so no copy of the weights is made), jitted once a model: every batch of
    one shape runs the one compiled forward pass."""
    import jax

    from sparknet_tpu.parallel import first_worker

    return jax.jit(lambda p, s, tokens: lm.selection_readings(
        first_worker(p), tokens, first_worker(s)))


def set_selection_gauges(lm, stacked_params, tokens, stacked_stats=None,
                         probe=None):
    """Of one batch of ``(B, T)`` tokens, per selected-key attention layer
    (``models/hybrid_lm.selection_readings``): the alignment loss and the
    share of the dense attention's probability the selected keys hold, set
    as the gauges ``sparknet_lm_indexer_loss`` / ``sparknet_lm_selection_mass``
    (and ``sparknet_kernel_path{kernel="sparse_attention"}``: 1 where
    ``masked_attention`` takes the flash kernels for this batch's shapes, 0
    on its XLA pass; its twin ``{kernel="alignment_loss"}``: 1, the loss
    hands back its gradient with its value on every differentiated trace,
    whatever the shapes) where training metrics are on,
    and returned with the pass's ``held_counts``; ``{}`` for a model without
    such a layer.  Outside the timed loop: a forward pass,
    ``selection_probe(lm)`` or the caller's compiled ``probe`` of it."""
    from sparknet_tpu import obs
    from sparknet_tpu.ops import sparse_attention

    layers = [i for i, kind in enumerate(lm.config["mixers"])
              if kind == "dsa_attention"]
    if not layers:
        return {}
    readings = (probe or selection_probe(lm))(
        stacked_params, stacked_stats or {}, tokens)
    gauges = {k: np.asarray(v).tolist() for k, v in readings.items()}
    tm = obs.training_metrics()
    if tm is not None:
        c = lm.config
        tm.kernel_path.labels("sparse_attention").set(float(
            not sparse_attention.kernels_refuse(
                tokens.shape[-1], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"],
                lm.compute_dtype or np.float32)))
        tm.kernel_path.labels("alignment_loss").set(1.0)
        for i, loss, mass in zip(
                layers, gauges["indexer_loss"], gauges["selection_mass"]):
            tm.lm_indexer_loss.labels(str(i)).set(loss)
            tm.lm_selection_mass.labels(str(i)).set(mass)
    return gauges


def build_lm_solver(args, sp: int):
    """(model, Solver) from parsed args — shared with ``cli train --lm``.
    ``--model_config`` selects the hybrid model."""
    from sparknet_tpu import models
    from sparknet_tpu.config import parse_solver_prototxt
    from sparknet_tpu.solver import Solver

    if getattr(args, "model_config", None):
        from sparknet_tpu.models.hybrid_lm import load_config

        if sp != 1:
            raise SystemExit("lm: --model_config trains at --sp 1 only")
        return build_hybrid_lm_solver(load_config(args.model_config))
    lm = models.build_transformer_lm(
        dim=args.dim,
        depth=args.depth,
        heads=args.heads,
        seq_len=args.seq_len,
        sp_axis="sp" if sp > 1 else None,
        sp_size=sp,
    )
    solver_param = parse_solver_prototxt(
        f"base_lr: {args.base_lr} "
        'lr_policy: "fixed" '
        f"momentum: {args.momentum} "
        f"weight_decay: {args.weight_decay} "
        "average_loss: 20"
    )
    solver = Solver(solver_param, net=lm)
    from sparknet_tpu import obs
    from sparknet_tpu.ops import pallas_attention

    tm = obs.training_metrics()
    if tm is not None:
        on_kernel = pallas_attention.lowerable()
        tm.kernel_path.labels("attention").set(1.0 if on_kernel else 0.0)
        # a ring shard's keys, or the whole sequence's
        fused = on_kernel and pallas_attention.backward_path(
            args.seq_len // sp, args.dim // args.heads)[0] == "fused"
        tm.kernel_path.labels("attention_backward").set(float(fused))
    return lm, solver


def lm_batch_spec(sp: int):
    """The round-batch partition specs: worker-major over dp, sequence
    over the sp ring — the trainers' ``batch_spec`` generalization."""
    from jax.sharding import PartitionSpec as P

    if sp <= 1:
        return None
    spec = P("dp", None, None, "sp")
    return {"tokens": spec, "targets": spec}


def lm_batch_sharding(mesh, sp: int):
    """Matching placement pytree for RoundFeed's producer-thread put."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P("dp", None, None, "sp") if sp > 1 else P("dp")
    s = NamedSharding(mesh, spec)
    return {"tokens": s, "targets": s}


def resume_lm_job(solver, trainer, mesh, prefix, jr, sampler=None,
                  tau: int = TAU):
    """Journal-guided full-job-state resume (the recover.py recipe on
    the LM): rewind to the last COMMITTED boundary, broadcast the
    consensus params, put back per-worker momentum stacks, comm EF
    residuals and the sentry EMA from ``.jobstate.npz``, and verify
    the text cursor's corpus geometry.  Returns ``(state, start_round,
    job_state, info)`` — state None means nothing restorable (start
    fresh at round 0)."""
    import jax

    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.parallel import restore_worker_history

    state = js = info = None
    if jr is not None:
        if jr.last_committed_round is None:
            # a ledger with no committed boundary: the reconciler's
            # rule says round 0 (and any snapshot a torn first
            # boundary published for an UNCOMMITTED round) must be
            # ignored — start fresh and re-execute from round 0,
            # never consume a snapshot the ledger does not vouch for
            return None, 0, None, jr.reconcile()
        st, used, js, info = checkpoint.restore_newest_valid_journaled(
            solver, prefix, jr
        )
    else:
        try:
            st, used = checkpoint.restore_newest_valid(solver, prefix)
        except FileNotFoundError:
            return None, 0, None, None
    state = trainer.broadcast_state(st)  # resets the comm plane
    start_round = (
        info["resume_round"]
        if info is not None
        else int(np.asarray(jax.device_get(st.iter))) // max(1, tau)
    )
    if js:
        if "comm" in js:
            trainer.restore_comm_state(js["comm"])
        if "workers" in js:
            # per-worker momentum: the consensus snapshot carries
            # worker 0's history only; the true stacks ride jobstate
            state = restore_worker_history(state, js["workers"], mesh)
        if sampler is not None and "cursor" in js and isinstance(
            js["cursor"], dict
        ) and "text_iter" in js["cursor"]:
            sampler.verify_cursor(js["cursor"])
    return state, start_round, js, info


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--corpus", default=None,
        help="text corpus root: a directory or any object-store URL "
        "(gs:// s3:// http(s):// file://) — *.txt documents are "
        "fetched through the chunk cache; omitted = a seeded "
        "synthetic corpus served through a file:// cache",
    )
    parser.add_argument(
        "--cache_dir", default=None,
        help="chunk-cache root for an object-store --corpus; pass a "
        "STABLE path to make re-runs I/O-free (default: a temp dir — "
        "verified fetches, but no cross-run reuse)",
    )
    parser.add_argument("--cache_bytes", default=0)
    parser.add_argument(
        "--workers", type=int, default=0,
        help="dp worker count (0 = devices // sp)",
    )
    parser.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel ring width: each dp worker's sequence "
        "dim shards --sp ways and attention runs the ring "
        "construction (parallel/ring_attention.py).  Needs "
        "workers x sp devices and seq_len %% sp == 0",
    )
    parser.add_argument("--rounds", type=int, default=40)
    parser.add_argument("--tau", type=int, default=TAU)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--log_every", type=int, default=5)
    parser.add_argument(
        "--snapshot_prefix", default=None,
        help="snapshot path prefix; with --snapshot_every, every k-th "
        "round boundary publishes a full-job-state snapshot "
        "(params + per-worker momentum + comm residuals + sentry + "
        "text cursor) the journal's commit references",
    )
    parser.add_argument("--snapshot_every", type=int, default=0)
    parser.add_argument(
        "--resume", action="store_true",
        help="journal-guided resume from --snapshot_prefix: rewind to "
        "the last committed round, restore the full job state, "
        "continue bit-identically (windows never skip or replay)",
    )
    add_lm_model_args(parser)
    from sparknet_tpu import obs
    from sparknet_tpu.io import journal as journal_mod
    from sparknet_tpu.parallel import comm, hierarchy

    obs.add_cli_args(parser)
    comm.add_cli_args(parser)
    hierarchy.add_cli_args(parser)
    journal_mod.add_cli_args(parser)
    args = parser.parse_args(argv)

    from sparknet_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()

    import jax

    from sparknet_tpu.data import (
        RoundFeed,
        TextWindowSampler,
        load_corpus,
        stack_windows,
        write_synthetic_corpus,
    )
    from sparknet_tpu.io import checkpoint
    from sparknet_tpu.obs import health as health_mod
    from sparknet_tpu.parallel import first_worker, make_mesh
    from sparknet_tpu.utils import SignalHandler, SolverAction, TrainingLog

    sp = max(1, args.sp)
    if args.seq_len % sp:
        raise SystemExit(
            f"lm: --seq_len {args.seq_len} must divide by --sp {sp} "
            "(the ring rotates equal sequence shards)"
        )
    if args.resume and not args.snapshot_prefix:
        raise SystemExit("lm: --resume needs --snapshot_prefix")
    n_workers = args.workers or max(1, jax.local_device_count() // sp)
    need = n_workers * sp
    if jax.local_device_count() < need:
        raise SystemExit(
            f"lm: dp={n_workers} x sp={sp} needs {need} devices, jax "
            f"sees {jax.local_device_count()}"
        )
    log = TrainingLog(tag="lm")
    axes = {"dp": n_workers, "sp": sp} if sp > 1 else {"dp": n_workers}
    mesh = make_mesh(axes, devices=jax.devices()[:need])
    log.log(f"mesh: dp={n_workers} sp={sp} ({need} devices)")

    corpus_root = args.corpus
    if corpus_root is None:
        synth = tempfile.mkdtemp(prefix="lm_synth_corpus_")
        write_synthetic_corpus(synth, seed=args.seed)
        # even the synthetic corpus goes through object_store + the
        # chunk cache: the LM data path IS the verified-fetch path
        corpus_root = "file://" + synth
        log.log(f"synthesized corpus at {corpus_root}")
    docs = load_corpus(
        corpus_root, cache_dir=args.cache_dir, cache_bytes=args.cache_bytes
    )
    log.log(f"corpus: {len(docs)} documents, "
            f"{sum(len(d) for d in docs)} bytes")

    lm, solver = build_lm_solver(args, sp)
    if args.model_config:
        log.log(
            f"model: {args.model_config} experts_held="
            f"{list(lm.experts_held)} compute_dtype={lm.compute_dtype} "
            f"seq_len={args.seq_len} ({lm.num_params()} params)"
        )
    else:
        log.log(
            f"model: dim={args.dim} depth={args.depth} heads={args.heads} "
            f"seq_len={args.seq_len} ({lm.num_params()} params)"
        )
    prefix = args.snapshot_prefix
    sentry = health_mod.sentry_from_args(args, solver, echo=log.log)
    spec = hierarchy.spec_from_args(args, n_workers)
    trainer = hierarchy.averaging_trainer_from_args(
        args, solver, mesh, n_workers,
        hierarchy=spec, batch_spec=lm_batch_spec(sp),
    )
    if sentry is not None and prefix:
        sentry.restore_fn = health_mod.make_restore_fn(
            solver, prefix, trainer=trainer
        )

    # --elastic: membership views drive the round's live_mask; SIGTERM
    # marks this process's slice leaving at the next boundary and
    # AutoRejoin requests readmission (the cifar_app contract, riding
    # the LM unchanged)
    membership_ctl = None
    auto_rejoin = None
    if args.elastic:
        from sparknet_tpu.runtime import membership as membership_mod

        membership_ctl = membership_mod.MembershipController(
            spec
            if spec is not None
            else hierarchy.HierarchySpec.flat(n_workers),
            echo=log.log,
        )
        my_slice = int(
            os.environ.get(
                "SPARKNET_SLICE_ID", membership_ctl.spec.num_slices - 1
            )
        )
        membership_ctl.sigterm_marks(my_slice)
        auto_rejoin = membership_mod.AutoRejoin(
            membership_ctl, args.rejoin_after
        )
        obs.set_membership(membership_ctl)

    # one joined corpus stream, shared by every dp worker's cursor
    base_sampler = TextWindowSampler(
        docs, args.seq_len, args.batch, seed=args.seed, worker=0
    )
    samplers = [base_sampler.for_worker(w) for w in range(n_workers)]
    run_obs = obs.start_from_args(args, echo=log.log)
    jr = journal_mod.journal_from_args(
        args,
        (journal_mod.default_journal_path(prefix)
         if prefix else "lm_run.journal"),
        resuming=args.resume,
    )

    start_round = 0
    state = None
    if args.resume:
        if jr is None and not checkpoint.find_snapshots(prefix):
            # the imagenet_run_db_app loud-failure contract: a typo'd
            # prefix must not silently retrain the whole run from 0
            raise SystemExit(
                f"lm: --resume found no ledger and no snapshots under "
                f"{prefix!r}"
            )
        state, start_round, js, info = resume_lm_job(
            solver, trainer, mesh, prefix, jr, sampler=samplers[0],
            tau=args.tau,
        )
        if state is not None:
            if sentry is not None and js and "sentry" in js:
                sentry.load_state(js["sentry"])
            if membership_ctl is not None and js and "membership" in js:
                # the epoch clock never rewinds across restart (the
                # journaled-state inventory invariant): the restored
                # roster keeps departed slots walking the rejoin path
                membership_ctl.load_state(js["membership"])
            log.log(
                f"resumed at round {start_round} "
                f"(iter {start_round * args.tau})"
            )
            if info is not None and info.get("in_flight_round") is not None:
                tm = obs.training_metrics()
                if tm is not None:
                    tm.recover_replayed.inc()
                log.log(
                    "journal: round %d was in flight at the crash — it "
                    "re-executes" % info["in_flight_round"]
                )
        else:
            # a ledger with no committed boundary: the reconciled
            # decision IS a fresh start (round 0 re-executes; any
            # snapshot from a torn first boundary stays ignored)
            log.log(
                "journal: no committed round — starting fresh at "
                "round 0"
            )
    if state is None:
        trainer.reset_comm_state()
        state = trainer.init_state(seed=args.seed)
    if start_round >= args.rounds:
        log.log(f"run already complete at round {start_round}")
        if membership_ctl is not None:
            membership_ctl.detach()
        run_obs.close()
        if jr is not None:
            jr.close()
        log.close()
        return 0

    if args.model_config:
        # where the first round's tokens go, by layer: gauges, set once
        first = samplers[0].window_for_round(start_round, 1)["tokens"][0]
        gauges = set_routing_gauges(lm, state.params, first, state.stats)
        log.log(f"routing of the first minibatch, by layer: {gauges}")
        gauges = set_selection_gauges(lm, state.params, first, state.stats)
        if gauges:
            log.log(f"selection of the first minibatch, by layer: {gauges}")

    tokens_per_round = n_workers * args.tau * args.batch * args.seq_len
    ring_bytes_per_round = (
        lm.ring_hop_bytes_per_iter(args.batch) * args.tau * n_workers
    )

    def assemble(r, out):
        # the per-round draw is a pure function of the absolute round
        # (resume-aware cursors); the span makes text sampling visible
        # in traces beside assemble/h2d
        with obs.span("sample_text", cat="data", round=r):
            windows = obs.profile.timed_worker_windows(
                r,
                [
                    (lambda s=s: s.window_for_round(r, args.tau))
                    for s in samplers
                ],
            )
        return stack_windows(windows, out)

    feed = RoundFeed(
        assemble,
        sharding=lm_batch_sharding(mesh, sp),
        start_round=start_round,
        num_rounds=args.rounds - start_round,
    )

    def job_extra(r: int):
        it = (r + 1) * args.tau
        import jax as _jax

        from sparknet_tpu.parallel import export_worker_history

        host_state = _jax.device_get(state)
        extra = {
            "cursor": samplers[0].cursor_for_iter(it),
            # per-worker momentum stacks — the shared jobstate recipe
            # (one implementation with runtime/recover.py)
            "workers": export_worker_history(host_state),
        }
        if sentry is not None:
            extra["sentry"] = sentry.export_state()
        if membership_ctl is not None:
            extra["membership"] = membership_ctl.export_state()
        comm_state = trainer.export_comm_state()
        if comm_state is not None:
            extra["comm"] = comm_state
        return extra, first_worker(host_state)

    try:
        with SignalHandler(
            sigint_effect=SolverAction.NONE,
            sighup_effect=SolverAction.NONE,
            sigterm_hooks=membership_ctl is not None,
        ):
            for r in range(start_round, args.rounds):
                if jr is not None:
                    jr.begin_round(
                        r,
                        iter=r * args.tau,
                        cursor=samplers[0].cursor_for_iter(r * args.tau),
                        view_epoch=(
                            membership_ctl.view.epoch
                            if membership_ctl is not None
                            else 0
                        ),
                    )
                mask = None
                if membership_ctl is not None:
                    membership_ctl.advance(r)
                    auto_rejoin.on_round(r)
                    if membership_ctl.pending_joiners():
                        from sparknet_tpu.runtime import (
                            membership as membership_mod,
                        )

                        state, _ = membership_mod.readmit_from_survivors(
                            trainer, state, membership_ctl, r,
                            echo=log.log,
                        )
                    mask = membership_ctl.live_mask()
                    if not mask.any():
                        log.log(
                            f"round {r}: no live workers in the "
                            "membership view; stopping"
                        )
                        break
                if sentry is not None:
                    state, _ = sentry.guarded_round(
                        trainer, state, feed.next_round(r),
                        live_mask=mask, round_index=r,
                    )
                else:
                    state, _ = trainer.round(
                        state, feed.next_round(r),
                        live_mask=mask, round_index=r,
                    )
                tm = obs.training_metrics()
                if tm is not None:
                    # elastic degradation shows up in the counters: a
                    # masked (departed) worker trains no tokens and
                    # moves no ring bytes this round
                    frac = (
                        1.0
                        if mask is None
                        else float(np.sum(mask)) / n_workers
                    )
                    tm.lm_tokens.inc(int(tokens_per_round * frac))
                    if ring_bytes_per_round:
                        tm.lm_ring_bytes.inc(
                            int(ring_bytes_per_round * frac)
                        )
                if r % max(1, args.log_every) == 0 or r == args.rounds - 1:
                    log.log(
                        f"round {r} smoothed_loss "
                        f"{solver.smoothed_loss:.4f}"
                    )
                snapshots_armed = bool(prefix and args.snapshot_every)
                snap_due = (
                    snapshots_armed
                    and (r + 1) % args.snapshot_every == 0
                )
                if snap_due:
                    extra, consensus = job_extra(r)
                    _, state_path = checkpoint.snapshot(
                        solver, consensus, prefix,
                        fmt="BINARYPROTO", extra_state=extra,
                    )
                    if jr is not None:
                        jr.commit_round(
                            r,
                            iter=(r + 1) * args.tau,
                            snapshot=os.path.basename(state_path),
                        )
                elif jr is not None and not prefix:
                    # progress-only ledger (NO snapshot prefix — the
                    # cifar_app contract, resume impossible by
                    # construction): commits mark in-memory completion
                    # for postmortems.  With a prefix set, rounds
                    # without a published snapshot must stay
                    # UNCOMMITTED: the reconciler treats every commit
                    # as a durable boundary, so a commit the restore
                    # path cannot rewind to would make --resume SKIP
                    # rounds (snapshot_every > 1) or crash claiming
                    # durable work vanished (snapshot_every == 0) —
                    # uncommitted rounds instead re-execute
                    # deterministically off the absolute-iter cursor.
                    jr.commit_round(
                        r, iter=(r + 1) * args.tau, durable=False
                    )
        state = trainer.finalize(state)
        log.log(f"final smoothed_loss {solver.smoothed_loss:.4f}")
        return 0
    except health_mod.SentryHalt as e:
        log.log(f"training halted by the health sentry: {e}")
        return 1
    finally:
        if membership_ctl is not None:
            membership_ctl.detach()
        if jr is not None:
            jr.close()
        feed.stop()
        run_obs.close()
        log.close()


if __name__ == "__main__":
    raise SystemExit(main())
