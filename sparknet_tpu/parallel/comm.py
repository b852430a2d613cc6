"""Communication-efficient parameter averaging: the comm plane.

The regime SparkNet's tau exists to amortize is a round whose averaging
collective costs more than its local compute.  This module attacks the
wire directly, three ways:

1. **Delta quantization.**  Workers average bf16/int8-quantized
   *deltas from the round-start broadcast params* (``theta_end -
   theta_0``), never raw weights: deltas are small and centered, so a
   bf16/int8 grid loses far less than quantizing the weights
   themselves, and the round-start params are already known on every
   worker (the previous round's average) — only the delta has to cross
   the wire.  A per-worker **error-feedback residual** carries the
   quantization error into the next round's delta so the bias never
   accumulates (the EF-SGD contract).

2. **Chunked collectives.**  The param pytree is flattened and split
   into ``chunks`` byte-balanced groups; the collective dispatches per
   chunk, so it can interleave with compute instead of being one
   monolithic barrier, and peak payload memory is bounded by the chunk
   size, not the model size.

3. **Overlap with the next round's compute.**  With ``overlap=True``
   round r's chunk collectives run on a comm thread while the main
   thread runs the first ``overlap_steps`` local steps of round r+1;
   when they land, every worker applies the *correction*
   ``mean(delta) - dequant(own delta)`` to both its params and its
   anchor — the RoundFeed (PR 3) overlap trick, applied to the network
   instead of H2D.  Wall-clock per round approaches
   ``max(collective, local)`` instead of their sum.  The first
   ``overlap_steps`` of a round therefore run one average *stale*
   (delayed averaging — disclosed in PERF.md); the ``compress=none,
   overlap off`` default path never enters this module and stays
   bit-identical to the fused round.

Masking composes: the survivor/sentry mask (``live_mask`` x in-graph
finite audit) applies **per chunk** through ``where()`` — a dead or
poisoned worker's delta contributes exactly zero to every chunk, its
slot receives the survivor consensus ``anchor + mean``, and its
error-feedback residual resets on rejoin (mirroring the momentum-
zeroing rejoin contract of the fused round).  When any worker is
masked in an overlapped round, that round degrades to the barriered
apply — overlap is a healthy-path optimization; the fault path keeps
the strict semantics.

Bytes accounting (``sparknet_collective_bytes_total``): a ring
all-reduce moves ~2x the payload per worker, so the counter charges
``2 x payload_nbytes`` per round, where the payload is the compressed
representation (int8 = 1 B/elem + one f32 max-abs scale per tensor,
bf16 = 2 B/elem, fp32 = 4 B/elem).  On the virtual CPU mesh
collectives are shared-memory copies — the counter models what a
bandwidth-bound interconnect would carry, which is exactly the
quantity compression changes.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from sparknet_tpu import obs

tree_map = jax.tree_util.tree_map

# CLI-facing compression modes; "fp32" is additionally accepted by the
# trainer for benchmarks/tests that want the comm-plane *structure*
# (chunked delta averaging) with an uncompressed payload.
CLI_COMPRESS_MODES = ("none", "bf16", "int8")
COMPRESS_MODES = ("none", "fp32", "bf16", "int8")

DEFAULT_CHUNKS = 4
DEFAULT_OVERLAP_STEPS = 1

# The pinned bit-accuracy band (PR-5 audit style): over the reference
# A/B protocol (same seed, same data, cifar10_quick-class model, tens
# of rounds), the final smoothed loss of a bf16/int8 delta-averaged
# run must land within this absolute band of the fp32 collective's.
# Pinned here, held by tests/test_comm.py.
LOSS_BAND = 0.08

_ELEM_NBYTES = {"fp32": 4, "none": 4, "bf16": 2, "int8": 1}
# ring all-reduce moves ~2x(N-1)/N x payload per worker; charge 2x
_RING_FACTOR = 2


def add_cli_args(parser) -> None:
    """``--compress {none,bf16,int8}`` / ``--overlap_avg`` — the comm
    plane's CLI surface, shared by the parameter-averaging apps."""
    parser.add_argument(
        "--compress", choices=CLI_COMPRESS_MODES, default="none",
        help="delta-quantized parameter averaging: workers average "
        "bf16/int8 deltas from the round-start params (error-feedback "
        "residual carried per worker); 'none' keeps the fp32 fused "
        "collective, bit-identical to the classic round",
    )
    parser.add_argument(
        "--overlap_avg", action="store_true",
        help="overlap the averaging collective with the next round's "
        "first local steps (chunked comm on a background thread; the "
        "overlapped steps run one average stale — PERF.md "
        "'Communication-efficient averaging')",
    )


def comm_kwargs_from_args(args) -> Dict[str, object]:
    """Trainer kwargs for the comm plane from parsed CLI args."""
    return {
        "compress": getattr(args, "compress", "none"),
        "overlap_avg": bool(getattr(args, "overlap_avg", False)),
    }


def _per_worker_nbytes(leaf, mode: str) -> int:
    """Modeled payload bytes ONE worker contributes for ``leaf`` (leaf
    is worker-stacked: shape (num_workers, ...)): compressed elements
    plus the per-tensor f32 scale int8 carries."""
    per_worker_elems = int(np.prod(leaf.shape[1:], dtype=np.int64))
    nb = per_worker_elems * _ELEM_NBYTES[mode]
    if mode == "int8":
        nb += 4  # one f32 max-abs scale per tensor per worker
    return nb


def fused_round_payload_bytes(state, average_stats: bool = True) -> int:
    """Modeled per-round collective bytes of the classic fused fp32
    round (params + averaged BN stats, ring factor applied) — what
    ``sparknet_collective_bytes_total{compress="none"}`` charges when
    the comm plane is off.  ``state`` is the worker-stacked TrainState."""
    leaves = jax.tree_util.tree_leaves(state.params)
    if average_stats:
        leaves = leaves + jax.tree_util.tree_leaves(state.stats)
    return _RING_FACTOR * sum(_per_worker_nbytes(x, "fp32") for x in leaves)


class CommPlane:
    """The chunked, delta-quantized, optionally-overlapped averaging
    engine behind ``ParameterAveragingTrainer``.  Built once per
    trainer when ``compress != 'none'`` or ``overlap_avg`` is set."""

    def __init__(
        self,
        solver,
        mesh: Mesh,
        axis: str,
        compress: str = "fp32",
        overlap: bool = False,
        chunks: int = DEFAULT_CHUNKS,
        overlap_steps: int = DEFAULT_OVERLAP_STEPS,
        average_stats: bool = True,
        mask_nonfinite: bool = True,
        batch_spec=None,
        fused: Optional[bool] = None,
    ):
        if compress not in COMPRESS_MODES:
            raise ValueError(
                f"compress={compress!r}: expected one of {COMPRESS_MODES}"
            )
        if overlap and jax.process_count() > 1:
            # two threads enqueueing programs race the cross-process
            # program order multi-controller jax requires — a deadlock,
            # not a slowdown.  Barriered compression is still fine.
            raise ValueError(
                "overlap_avg needs a single-process runtime (multi-host "
                "program order must be deterministic); use barriered "
                "compression instead"
            )
        self.solver = solver
        self.mesh = mesh
        self.axis = axis
        self.num_workers = mesh.shape[axis]
        # "none" reaching the plane means overlap-only: fp32 payload
        self.compress = "fp32" if compress == "none" else compress
        self.overlap = bool(overlap)
        self.chunks = max(1, int(chunks))
        self.overlap_steps = max(1, int(overlap_steps))
        self.average_stats = bool(average_stats)
        self.audit = bool(getattr(solver, "audit", False))
        self.mask_nonfinite = bool(mask_nonfinite) and self.audit
        # fused Pallas epilogue (ops/pallas_comm.py): delta-encode +
        # quantize + EF-residual in one kernel per chunk, and the
        # apply/correction likewise.  None routes on the shared
        # lowerable() gate (TPU native); True forces the kernels
        # (interpreter mode off-TPU — the test/bench pin); False keeps
        # the unfused jitted closures.  Both paths are bit-identical
        # by construction (same per-element op order).
        from sparknet_tpu.ops.pallas_attention import lowerable

        self.fused = lowerable() if fused is None else bool(fused)

        # ---- per-round carried state (device, worker-stacked) ----
        # anchor: what deltas are measured against — the round-start
        # broadcast params (barriered: re-seeded from the round entry
        # each round; overlap: persisted and corrected in lockstep
        # with the params, consistent across workers up to the
        # error-feedback residual drift)
        self._anchor: Optional[list] = None
        self._resid: Optional[list] = None  # error-feedback residuals
        self._treedefs = None  # (params_treedef, stats_treedef, nparams)
        self._chunk_slices: Optional[List[slice]] = None
        self._modes: Optional[List[str]] = None  # per comm leaf
        self._modes_static: Tuple[str, ...] = ()
        self._payload_bytes_per_round = 0  # modeled, set at _setup
        self._pending = None  # in-flight overlapped round
        self._pending_err = None  # dispatched quant-error readout
        # journaled residuals restored before the first round (consumed
        # by _setup in place of the zero init — the resume path)
        self._resid_restore: Optional[list] = None

        audit = self.audit
        mask_nf = self.mask_nonfinite
        solver_ref = solver

        def local_body(state, batches, rng, live):
            # per-worker local steps (tau or an overlap segment) — the
            # fused round_body minus the averaging epilogue; alive/bad
            # ride out so the chunked collective can mask per chunk.
            st = tree_map(lambda x: x[0], state)
            bt = tree_map(lambda x: x[0], batches)
            widx = jax.lax.axis_index(axis)
            lrng = jax.random.fold_in(rng, widx)
            st, out = solver_ref._step_tau(st, bt, lrng)
            if audit:
                losses, astats = out
            else:
                losses = out
            alive = live[0]
            bad = jnp.zeros(())
            if mask_nf:
                bad_flag = (
                    jnp.sum(astats["nonfinite_grads"])
                    + jnp.sum(astats["nonfinite_params"])
                ) > 0
                ok = jnp.where(bad_flag, 0.0, 1.0)
                alive = alive * ok
                bad = 1.0 - ok
                astats = dict(astats, masked=bad)
            outs = (
                tree_map(lambda x: x[None], st),
                losses[None],
                alive[None],
                bad[None],
            )
            if audit:
                outs = outs + (tree_map(lambda x: x[None], astats),)
            return outs

        out_specs = (P(axis), P(axis), P(axis), P(axis))
        if audit:
            out_specs = out_specs + (P(axis),)
        # NO donation: the round-entry params double as the delta
        # anchor, so their buffers must outlive the local program (the
        # fused default path keeps its donating round; delta averaging
        # inherently carries one extra param copy — PERF.md).
        # batch_spec: the trainer's generalized batch partitioning
        # (sequence parallelism) — same in_spec as the fused round
        batch_in_spec = P(axis) if batch_spec is None else batch_spec
        # the local window is the plane's one large program: an
        # obs.Program, keyed on its batches (obs/program.py).  The chunk
        # programs below stay bare jits: one name would hold a record a
        # chunk, and a second record would not mean a recompile
        self._local = obs.Program("comm_local", jax.jit(
            shard_map(
                local_body,
                mesh=mesh,
                in_specs=(P(axis), batch_in_spec, P(), P(axis)),
                out_specs=out_specs,
            )
        ), watch=(1,), devices=mesh.local_devices)

        def _dequant(q, scale, mode: str):
            if mode == "int8":
                sc = scale.reshape((-1,) + (1,) * (q.ndim - 1))
                return q.astype(jnp.float32) * sc
            if mode == "bf16":
                return q.astype(jnp.float32)
            return q  # fp32

        def encode_fn(leaves, anchors, resids, modes_idx, with_err):
            # delta = theta_end - anchor (+ error-feedback residual);
            # quantize per tensor.  Pure per-worker compute: GSPMD
            # keeps every op local to the worker's shard.  with_err
            # (static) additionally folds the quantization-error
            # readout (max |err|, |delta|^2, |err|^2 for the live SNR
            # gauge) into the SAME program — the residual IS the error,
            # so the reductions fuse with work already being done
            # instead of paying a second full-model dequant pass.
            qs, scales, new_resids = [], [], []
            max_abs = jnp.zeros(())
            err_sq = jnp.zeros(())
            delta_sq = jnp.zeros(())
            for x, a, r, mi in zip(leaves, anchors, resids, modes_idx):
                mode = self._modes_static[mi]
                delta = (x - a) + r
                zero_scale = jnp.zeros((x.shape[0],), jnp.float32)
                if mode == "bf16":
                    q = delta.astype(jnp.bfloat16)
                    scale = zero_scale
                elif mode == "int8":
                    red = tuple(range(1, delta.ndim))
                    amax = (
                        jnp.max(jnp.abs(delta), axis=red)
                        if red else jnp.abs(delta)
                    )
                    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
                    sc = scale.reshape((-1,) + (1,) * (delta.ndim - 1))
                    q = jnp.clip(
                        jnp.rint(delta / sc), -127, 127
                    ).astype(jnp.int8)
                else:  # fp32
                    q = delta
                    scale = zero_scale
                qs.append(q)
                scales.append(scale)
                err = delta - _dequant(q, scale, mode)
                new_resids.append(err)
                if with_err:
                    max_abs = jnp.maximum(max_abs, jnp.max(jnp.abs(err)))
                    err_sq = err_sq + jnp.sum(jnp.square(err))
                    delta_sq = delta_sq + jnp.sum(jnp.square(delta))
            err_out = (max_abs, delta_sq, err_sq) if with_err else None
            return tuple(qs), tuple(scales), tuple(new_resids), err_out

        self._encode = jax.jit(encode_fn, static_argnums=(3, 4))

        def allreduce_fn(qs, scales, alive, modes_idx):
            # masked mean of the dequantized deltas over the dp axis.
            # where(), not multiplication: a dead replica's NaN delta
            # must not leak through 0*NaN into the reduce.  The sum
            # over the sharded leading axis IS the collective.
            denom0 = jnp.sum(jnp.where(alive > 0, 1.0, 0.0))
            denom = jnp.maximum(denom0, 1.0)
            means = []
            for q, scale, mi in zip(qs, scales, modes_idx):
                dq = _dequant(q, scale, self._modes_static[mi])
                am = alive.reshape((-1,) + (1,) * (q.ndim - 1))
                contrib = jnp.where(am > 0, dq, jnp.zeros_like(dq))
                means.append(jnp.sum(contrib, axis=0) / denom)
            return tuple(means), denom0

        self._allreduce = jax.jit(allreduce_fn, static_argnums=(3,))

        def apply_barriered_fn(own, anchors, means, resids, alive, bad,
                               denom0):
            # consensus apply: every worker lands on anchor + mean —
            # the masked slot receives the survivor consensus exactly
            # like the fused round's wmean overwrite, and its error-
            # feedback residual resets on rejoin.  If NO worker is
            # finite, keep own params so the host sentry sees the
            # damage (the fused-round contract).
            have = denom0 > 0
            rejoin = jnp.logical_and(alive <= 0, have)
            new_leaves, new_resids = [], []
            for x, a, m, r in zip(own, anchors, means, resids):
                rm = rejoin.reshape((-1,) + (1,) * (x.ndim - 1))
                new_leaves.append(jnp.where(have, a + m, x))
                new_resids.append(jnp.where(rm, jnp.zeros_like(r), r))
            return tuple(new_leaves), tuple(new_resids)

        self._apply_barriered = jax.jit(apply_barriered_fn)

        def zero_bad_history_fn(history, bad, denom0):
            # an audit-masked worker's momentum still holds the
            # poisoned window — zero it, mirroring the fused round's
            # rejoin contract (bad == 0 selects the original leaves
            # exactly, so healthy rounds are untouched)
            rejoined = jnp.logical_and(bad > 0, denom0 > 0)

            def zero(h):
                rm = rejoined.reshape((-1,) + (1,) * (h.ndim - 1))
                return jnp.where(rm, jnp.zeros_like(h), h)

            return tree_map(zero, history)

        self._zero_bad_history = jax.jit(zero_bad_history_fn)

        def apply_correction_fn(own, anchors, qs, scales, means,
                                modes_idx):
            # overlapped healthy-path apply: every worker already
            # advanced overlap_steps past the encode point, so add the
            # consensus-minus-own-contribution correction to params AND
            # anchor — local progress since the encode is preserved,
            # and anchors stay consistent up to residual drift.
            new_leaves, new_anchors = [], []
            for x, a, q, scale, m, mi in zip(
                own, anchors, qs, scales, means, modes_idx
            ):
                corr = m - _dequant(q, scale, self._modes_static[mi])
                new_leaves.append(x + corr)
                new_anchors.append(a + corr)
            return tuple(new_leaves), tuple(new_anchors)

        self._apply_correction = jax.jit(
            apply_correction_fn, static_argnums=(5,)
        )

    # ------------------------------------------------------------------
    # comm-leaf plumbing: params leaves + (optionally) stats leaves form
    # one flat list; stats always ride fp32 (tiny next to params)
    def _setup(self, state) -> None:
        params_leaves, params_def = jax.tree_util.tree_flatten(state.params)
        stats_leaves, stats_def = jax.tree_util.tree_flatten(state.stats)
        if not self.average_stats:
            stats_leaves = []
        self._treedefs = (params_def, stats_def, len(params_leaves))
        modes = (
            [self.compress] * len(params_leaves)
            + ["fp32"] * len(stats_leaves)
        )
        self._modes = modes
        self._modes_static = tuple(modes)
        leaves = params_leaves + stats_leaves
        # byte-balanced contiguous chunking of the comm leaves
        sizes = [_per_worker_nbytes(x, m) for x, m in zip(leaves, modes)]
        total = sum(sizes)
        k = min(self.chunks, len(leaves))
        target = total / k if k else total
        slices, start, acc = [], 0, 0
        for i, s in enumerate(sizes):
            acc += s
            if acc >= target and len(slices) < k - 1:
                slices.append(slice(start, i + 1))
                start, acc = i + 1, 0
        slices.append(slice(start, len(leaves)))
        self._chunk_slices = [s for s in slices if s.stop > s.start]
        self._payload_bytes_per_round = _RING_FACTOR * total
        tm = obs.training_metrics()
        if tm is not None:
            tm.kernel_path.labels("epilogue").set(
                1.0 if self.fused else 0.0
            )
        restore, self._resid_restore = self._resid_restore, None
        if restore is not None:
            # journaled EF residuals restored before the first round
            if len(restore) != len(leaves) or any(
                tuple(r.shape) != tuple(x.shape)
                for r, x in zip(restore, leaves)
            ):
                raise ValueError(
                    "restored jobstate residuals do not match this "
                    "plane's comm leaves (model/worker-count drift?)"
                )
            self._resid = [jnp.asarray(r) for r in restore]
        else:
            self._resid = [jnp.zeros_like(x) for x in leaves]

    # ------------------------------------------------------------------
    # epilogue routing: the same three program contracts as the jitted
    # unfused closures, but one Pallas kernel per comm chunk on the
    # fused path (ops/pallas_comm.py) — delta + quantize + EF residual
    # (and dequant + apply + anchor) each a single pass over the chunk
    # instead of an op chain round-tripping full-model intermediates
    # through HBM.  Bit-identical by construction; routing is decided
    # once at __init__ (self.fused).
    def _count_fused(self, stage: str) -> None:
        tm = obs.training_metrics()
        if tm is not None:
            tm.kernel_fused_chunks.labels(stage).inc(
                len(self._chunk_slices)
            )

    def _encode_all(self, leaves, with_err):
        if not self.fused:
            idx = tuple(range(len(leaves)))
            return self._encode(
                tuple(leaves), tuple(self._anchor), tuple(self._resid),
                idx, with_err,
            )
        from sparknet_tpu.ops import pallas_comm

        qs: list = []
        scales: list = []
        new_resids: list = []
        errs: list = []
        for sl in self._chunk_slices:
            q, sc, nr, err = pallas_comm.fused_encode(
                tuple(leaves[sl]), tuple(self._anchor[sl]),
                tuple(self._resid[sl]), self._modes_static[sl],
                with_err, None,
            )
            qs.extend(q)
            scales.extend(sc)
            new_resids.extend(nr)
            if with_err:
                errs.append(err)
        self._count_fused("encode")
        err_out = None
        if with_err:
            allv = jnp.stack(errs)  # (chunks, workers, 3)
            err_out = (
                jnp.max(allv[..., 0]),
                jnp.sum(allv[..., 1]),
                jnp.sum(allv[..., 2]),
            )
        return tuple(qs), tuple(scales), tuple(new_resids), err_out

    def _apply_barriered_all(self, leaves, means, alive, bad, denom0):
        if not self.fused:
            return self._apply_barriered(
                tuple(leaves), tuple(self._anchor), tuple(means),
                tuple(self._resid), alive, bad, denom0,
            )
        from sparknet_tpu.ops import pallas_comm

        new_leaves: list = []
        new_resids: list = []
        for sl in self._chunk_slices:
            nl, nr = pallas_comm.fused_apply_barriered(
                tuple(leaves[sl]), tuple(self._anchor[sl]),
                tuple(means[sl]), tuple(self._resid[sl]),
                alive, denom0, None,
            )
            new_leaves.extend(nl)
            new_resids.extend(nr)
        self._count_fused("apply")
        return tuple(new_leaves), tuple(new_resids)

    def _apply_correction_all(self, leaves, q, scales, means):
        if not self.fused:
            idx = tuple(range(len(leaves)))
            return self._apply_correction(
                tuple(leaves), tuple(self._anchor), tuple(q),
                tuple(scales), tuple(means), idx,
            )
        from sparknet_tpu.ops import pallas_comm

        new_leaves: list = []
        new_anchors: list = []
        for sl in self._chunk_slices:
            nl, na = pallas_comm.fused_apply_correction(
                tuple(leaves[sl]), tuple(self._anchor[sl]),
                tuple(q[sl]), tuple(scales[sl]), tuple(means[sl]),
                self._modes_static[sl], None,
            )
            new_leaves.extend(nl)
            new_anchors.extend(na)
        self._count_fused("apply")
        return tuple(new_leaves), tuple(new_anchors)

    def _comm_leaves(self, state) -> list:
        leaves = list(jax.tree_util.tree_leaves(state.params))
        if self.average_stats:
            leaves += list(jax.tree_util.tree_leaves(state.stats))
        return leaves

    def _rebuild(self, state, leaves, history=None):
        params_def, stats_def, nparams = self._treedefs
        params = jax.tree_util.tree_unflatten(params_def, leaves[:nparams])
        stats = (
            jax.tree_util.tree_unflatten(stats_def, leaves[nparams:])
            if self.average_stats
            else state.stats
        )
        return type(state)(
            params, stats,
            state.history if history is None else history,
            state.iter,
        )

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Drop carried comm state — the rollback/rejoin/broadcast
        entry: a restored state has no valid anchor, residual, or
        in-flight collective (a stale correction applied onto restored
        params would corrupt them)."""
        p = self._pending
        if p is not None and p["thread"] is not None:
            try:
                p["thread"].join()
            except Exception:  # pragma: no cover - defensive
                pass
        self._pending = None
        self._pending_err = None
        self._anchor = None
        self._resid_restore = None  # a stale pre-broadcast restore dies too
        if self._resid is not None:
            self._resid = [jnp.zeros_like(r) for r in self._resid]

    def export_state(self) -> Optional[dict]:
        """Host copy of the carried error-feedback residuals — the
        comm-plane half of a full-job-state snapshot (``io/checkpoint``
        ``extra_state``).  A resumed run that does NOT restore this
        silently resets the EF bias correction and diverges from the
        uninterrupted trajectory
        (``tests/test_recover.py::test_no_journal_resume_diverges``).
        Call at a round boundary with no in-flight overlapped
        collective (``finalize()`` first)."""
        if self._resid is None:
            return None
        if self._pending is not None:
            raise RuntimeError(
                "export_state with an overlapped collective in flight — "
                "finalize() the round first"
            )
        return {
            "compress": self.compress,
            "resid": {
                str(i): np.asarray(jax.device_get(r))
                for i, r in enumerate(self._resid)
            },
        }

    def restore_state(self, exported: dict) -> None:
        """Load residuals exported by ``export_state``.  Call AFTER the
        restore path's ``reset()`` (``broadcast_state`` triggers it) —
        the restore order is: place the snapshot params, then put the
        journaled residuals back.  A compress-mode or shape mismatch
        fails loudly: silently training on wrong residuals is exactly
        the bug this state exists to prevent."""
        if exported.get("compress") != self.compress:
            raise ValueError(
                "jobstate residuals were recorded under compress=%r, "
                "this plane runs %r"
                % (exported.get("compress"), self.compress)
            )
        resid = exported["resid"]
        leaves = [resid[str(i)] for i in range(len(resid))]
        if self._resid is not None:
            if len(leaves) != len(self._resid):
                raise ValueError(
                    f"jobstate has {len(leaves)} residual leaves, plane "
                    f"carries {len(self._resid)}"
                )
            for got, want in zip(leaves, self._resid):
                if tuple(got.shape) != tuple(want.shape):
                    raise ValueError(
                        f"residual shape {got.shape} != {want.shape}"
                    )
            self._resid = [jnp.asarray(l) for l in leaves]
        else:
            # first round hasn't run: _setup consumes these instead of
            # zeros (shape-checked there against the real comm leaves)
            self._resid_restore = [np.asarray(l) for l in leaves]

    def _join_pending(self) -> dict:
        """Wait for the in-flight chunk collectives; re-raise comm-
        thread errors on the caller."""
        p = self._pending
        # sparknet: join-ok(bounded by the in-flight chunk collectives: _pace_chunks always terminates, storing errors instead of raising)
        p["thread"].join()
        holder = p["holder"]
        if holder.get("error") is not None:
            self._pending = None
            raise holder["error"]
        return holder

    @property
    def payload_bytes_per_round(self) -> int:
        return self._payload_bytes_per_round

    @property
    def has_pending(self) -> bool:
        return self._pending is not None

    # ------------------------------------------------------------------
    def _dispatch_chunks(self, q, scales, alive):
        """Dispatch every chunk's collective from the CALLING thread —
        the device queue executes programs in dispatch order, so the
        chunks land right behind this round's encode and run as soon as
        the deltas exist, BEFORE the next round's local window the
        caller dispatches afterwards.  (Dispatching from the comm
        thread instead would race that window into the queue ahead of
        the chunks and serialize the 'overlapped' collective behind a
        full local window — measured, not hypothetical.)"""
        outs = []
        denom0 = None
        for sl in self._chunk_slices:
            idx = tuple(range(sl.start, sl.stop))
            nbytes = _RING_FACTOR * sum(
                _per_worker_nbytes(x, self._modes[i])
                for i, x in zip(idx, q[sl])
            )
            m, d0 = self._allreduce(
                tuple(q[sl]), tuple(scales[sl]), alive, idx
            )
            outs.append((sl, m, nbytes))
            denom0 = d0
        return outs, denom0

    def _pace_chunks(self, q, outs, denom0, holder) -> None:
        """Wait for the already-dispatched chunks in order (comm thread
        in overlap mode, inline in barriered mode).  Each chunk's span
        covers the block on its mean — the span times the wire, not the
        dispatch."""
        try:
            # the wire cannot carry a delta before it exists: wait for
            # the encode (and the local window it depends on) before
            # pacing chunks — in overlap mode this is the comm thread
            # parking until round r's window is done, in barriered mode
            # it keeps the round an honest local-then-collective sum
            # sparknet: sync-ok(the wire wait: comm thread parks until the encode lands — overlapped in overlap mode, the deliberate barrier otherwise)
            jax.block_until_ready(q)
            means: list = [None] * len(q)
            for sl, m, nbytes in outs:
                with obs.span("allreduce", chunk=sl.start, nbytes=nbytes):
                    # sparknet: sync-ok(chunk landing: the span times the wire, not the dispatch — comm-thread side of the overlap)
                    jax.block_until_ready(m)
                means[sl] = list(m)
            holder["means"] = means
            holder["denom0"] = denom0
        except BaseException as e:  # re-raised at the next join
            holder["error"] = e

    def _apply_pending_correction(self, state, stage: str):
        """Land the joined pending collective as the overlap
        correction on ``state`` (and the anchor)."""
        p = self._pending
        holder = p["holder"]
        with obs.span("dequantize", stage=stage):
            leaves = self._comm_leaves(state)
            new_leaves, new_anchor = self._apply_correction_all(
                leaves, p["q"], p["scales"], holder["means"]
            )
            state = self._rebuild(state, list(new_leaves))
            self._anchor = list(new_anchor)
        self._pending = None
        return state

    def _local_call(self, state, batches, rng, live):
        with obs.span("execute"):
            return self._local(state, batches, rng, live)

    # ------------------------------------------------------------------
    def flush_quant_error(self) -> Optional[dict]:
        """Land the previous round's dispatched quantization-error
        readout into the gauges (values are ready by now — no stall).
        Returns the readout dict, or None when nothing is pending."""
        pending = self._pending_err
        if pending is None:
            return None
        self._pending_err = None
        from sparknet_tpu import obs as _obs

        max_abs, delta_sq, err_sq = (
            # sparknet: sync-ok(3-scalar readout dispatched with LAST round's encode — ready by now, fetched without stalling the dispatch path)
            float(v) for v in jax.device_get(pending)
        )
        if err_sq > 0:
            # sparknet: sync-ok(host floats fetched above — pure host math)
            snr_db = 10.0 * float(np.log10(max(delta_sq, 1e-45) / err_sq))
        else:
            snr_db = 300.0  # error underflowed to exactly 0
        tm = _obs.training_metrics()
        if tm is not None:
            tm.quant_error.labels(self.compress).set(max_abs)
            tm.quant_snr_db.labels(self.compress).set(round(snr_db, 3))
        return {
            "compress": self.compress,
            "max_abs_err": max_abs,
            "snr_db": round(snr_db, 3),
        }

    def round(self, state, batches, rng, live, live_host):
        """One comm-plane averaging round.  ``live`` is the placed
        (num_workers,) mask, ``live_host`` its host value.  Returns the
        fused round's contract: ``(state, losses[, astats])``."""
        if self._treedefs is None:
            self._setup(state)
        self.flush_quant_error()  # last round's readout (ready: no sync)

        tau = jax.tree_util.tree_leaves(batches)[0].shape[1]
        astats = None

        if self._pending is not None:
            # overlapped steady state: the first overlap_steps of THIS
            # round run while round r-1's collective is in flight, then
            # the correction lands and the window finishes
            s = min(self.overlap_steps, tau)
            seg1 = tree_map(lambda x: x[:, :s], batches)
            out = self._local_call(state, seg1, rng, live)
            state, losses, alive, bad = out[:4]
            if self.audit:
                astats = out[4]
            self._join_pending()
            state = self._apply_pending_correction(state, "correction")
            if tau - s > 0:
                seg2 = tree_map(lambda x: x[:, s:], batches)
                out2 = self._local_call(state, seg2, rng, live)
                state = out2[0]
                losses = jnp.concatenate([losses, out2[1]], axis=1)
                alive = alive * out2[2]
                bad = jnp.maximum(bad, out2[3])
                if self.audit:
                    # per-iter stat leaves ((w, s, ...)) concatenate
                    # along the window; per-window flags (masked,
                    # (w,)) combine as max
                    astats = tree_map(
                        lambda a, b: (
                            jnp.concatenate([a, b], axis=1)
                            if a.ndim >= 2 else jnp.maximum(a, b)
                        ),
                        astats, out2[4],
                    )
        else:
            # first round, or barriered steady state: the round-entry
            # params ARE the broadcast anchor
            self._anchor = self._comm_leaves(state)
            out = self._local_call(state, batches, rng, live)
            state, losses, alive, bad = out[:4]
            if self.audit:
                astats = out[4]

        # ---- encode this round's deltas ----
        leaves = self._comm_leaves(state)
        # per-round quantization-error telemetry (delta max-abs-err +
        # SNR, labeled by compress mode like the payload family): the
        # PR-6 bit-accuracy band, observable in LIVE runs.  The
        # 3-scalar readout is folded into the encode program itself
        # (static with_err leg — the residual IS the error, so the
        # reductions fuse with work already being done) and fetched one
        # round later by flush_quant_error, so the gauge never adds a
        # sync or a second model pass to the dispatch path.
        # compress="none" (the overlap-only plane) quantizes nothing —
        # skip the readout entirely; fp32 keeps its deliberate
        # exactly-zero/300 dB export (pinned in test_comm) as the
        # bit-accuracy control.
        tm = obs.training_metrics()
        with_err = tm is not None and self.compress != "none"
        with obs.span("quantize", compress=self.compress):
            q, scales, new_resid, err = self._encode_all(leaves, with_err)
        q, scales = list(q), list(scales)
        self._resid = list(new_resid)

        if tm is not None:
            tm.collective_bytes.labels(self.compress).inc(
                self._payload_bytes_per_round
            )
            if with_err:
                self._pending_err = err

        # Overlap only on the all-alive path: a masked/dead worker
        # forces the strict barriered apply (consensus overwrite,
        # residual reset, momentum zeroing).  The decision is host-
        # side: live_host is host data already; the in-graph audit
        # verdict costs one tiny (num_workers,) read — the same
        # per-round D2H budget the host sentry already pays.
        # sparknet: sync-ok(live_host is the host-side mask, never a device array)
        all_alive = bool(np.all(np.asarray(live_host) > 0))
        if all_alive and self.mask_nonfinite:
            # sparknet: sync-ok(one tiny (num_workers,) audit-verdict read — the same per-round D2H budget the host sentry pays; documented above)
            all_alive = not bool(np.any(np.asarray(jax.device_get(bad)) > 0))

        outs, denom0 = self._dispatch_chunks(q, scales, alive)
        if self.overlap and all_alive:
            holder: dict = {}
            th = threading.Thread(
                target=self._pace_chunks,
                args=(q, outs, denom0, holder),
                name="comm-averaging",
                daemon=True,
            )
            self._pending = {
                "q": q, "scales": scales, "holder": holder, "thread": th,
            }
            # from here deltas are measured against the encode point
            self._anchor = leaves
            th.start()
        else:
            holder = {}
            self._pace_chunks(q, outs, denom0, holder)
            if holder.get("error") is not None:
                raise holder["error"]
            with obs.span("dequantize", stage="barriered"):
                new_leaves, new_resid2 = self._apply_barriered_all(
                    leaves, holder["means"], alive, bad, holder["denom0"]
                )
                self._resid = list(new_resid2)
                history = state.history
                if self.mask_nonfinite:
                    history = self._zero_bad_history(
                        history, bad, holder["denom0"]
                    )
                state = self._rebuild(state, list(new_leaves), history)
            self._anchor = None  # re-seeded from the next round's entry

        if self.audit:
            return state, losses, astats
        return state, losses

    # ------------------------------------------------------------------
    def finalize(self, state):
        """Land the in-flight overlapped collective into ``state`` —
        call before an eval or at the end of training so the last
        round's average is applied.  No-op when nothing is pending."""
        self.flush_quant_error()  # the last round's gauges land too
        if self._pending is None:
            return state
        self._join_pending()
        return self._apply_pending_correction(state, "finalize")
