"""The two reference data-parallel training modes on a device mesh.

1. ``ParameterAveragingTrainer`` — SparkNet's algorithm (reference driver
   loop ``CifarApp.scala:95-136``): every worker keeps its own full replica
   of params *and solver history*, runs tau local SGD iterations with no
   communication, then parameters (only) are averaged across workers:
   ``psum(theta)/N``.  History is never averaged — the reference's
   ``getWeights`` reads param blobs only (``Net.scala:151-171``).  The whole
   round is ONE jitted program: the Spark driver hop, java serialization,
   and float-by-float JNA copies all vanish into an XLA collective.

2. ``AllReduceTrainer`` — the engine's in-node P2PSync mode
   (``parallel.cpp:287-380``): synchronous per-iteration gradient summing.
   Expressed as pjit sharding: params replicated, batch sharded over ``dp``;
   XLA inserts the gradient all-reduce automatically.  Optional tensor
   parallelism: a sharding policy places large param blobs over the ``mp``
   axis and GSPMD propagates.

Both run unchanged on the 8-device CPU simulation, a real TPU slice, or a
multi-host pod (see ``mesh.initialize_distributed``).
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparknet_tpu import obs
from sparknet_tpu.obs import profile as obs_profile
from sparknet_tpu.parallel.hierarchy import HierarchySpec
from sparknet_tpu.solver import Solver, TrainState
from sparknet_tpu.utils.rngs import default_train_key

tree_map = jax.tree_util.tree_map


# Sharding cache, keyed on MESH IDENTITY: the per-mesh dict lives on
# the mesh object itself, so its lifetime is exactly the mesh's — a
# process that recreates meshes (every test file does) can never grow a
# module-level cache monotonically, and an equal mesh (jax interns
# Mesh, so equal specs ARE the same object) reuses the same shardings.
# A module-level lru keyed on Mesh would instead pin every mesh it ever
# saw (NamedSharding holds the mesh strongly, so even a weak-key dict
# can't evict).  Fallback for a Mesh that rejects attributes: a small
# bounded dict, cleared on overflow like ``_place_live``'s.
_SHARDING_ATTR = "_sparknet_shardings"
_sharding_fallback: Dict = {}


def _mesh_sharding_cache(mesh: Mesh) -> Dict:
    cache = getattr(mesh, _SHARDING_ATTR, None)
    if cache is None:
        cache = {}
        try:
            setattr(mesh, _SHARDING_ATTR, cache)
        except (AttributeError, TypeError):  # pragma: no cover
            if len(_sharding_fallback) >= 64:
                _sharding_fallback.clear()
            cache = _sharding_fallback.setdefault(mesh, {})
    return cache


def leading_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """The leading-axis placement ``NamedSharding(mesh, P(axis))``,
    built ONCE per (mesh, axis) — the training loops place a batch with
    this every round, and rebuilding the sharding object per round is
    avoidable host work on the hot path.  Cached ON the mesh object
    (mesh identity), so repeated trainer/mesh construction cannot grow
    a global cache."""
    cache = _mesh_sharding_cache(mesh)
    key = ("lead", axis)
    s = cache.get(key)
    if s is None:
        s = cache.setdefault(key, NamedSharding(mesh, P(axis)))
    return s


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement ``NamedSharding(mesh, P())``, cached
    like ``leading_sharding``."""
    cache = _mesh_sharding_cache(mesh)
    s = cache.get("repl")
    if s is None:
        s = cache.setdefault("repl", NamedSharding(mesh, P()))
    return s


def replicate(tree, mesh: Mesh):
    """Place a pytree fully replicated over the mesh (no new axes; the
    inverse is a no-op — just use the tree)."""
    return jax.device_put(tree, replicated_sharding(mesh))


def export_worker_history(host_state) -> Dict:
    """Per-worker momentum stacks as a ``.jobstate.npz`` fragment
    (the ``workers`` key of the journaled-state inventory): the
    consensus snapshot keeps worker 0's history only — broadcast
    would replicate it over every worker — so the true stacks ride
    beside it.  One implementation shared by every journaled driver
    (``runtime/recover.py``, ``apps/lm_app.py``)."""
    return {
        "history": {
            str(i): np.asarray(l)
            for i, l in enumerate(
                jax.tree_util.tree_leaves(host_state.history)
            )
        }
    }


def restore_worker_history(state, workers_fragment, mesh: Mesh,
                           axis: str = "dp"):
    """Put journaled per-worker momentum stacks back onto a
    broadcast-restored state (the inverse of
    ``export_worker_history``); shape mismatches fail loudly — the
    jobstate belongs to a different trainer geometry."""
    hd = workers_fragment["history"]
    cur, treedef = jax.tree_util.tree_flatten(state.history)
    leaves = [np.asarray(hd[str(i)]) for i in range(len(cur))]
    if any(
        tuple(l.shape) != tuple(c.shape) for l, c in zip(leaves, cur)
    ):
        raise ValueError(
            "jobstate worker history does not match this trainer's "
            "shapes"
        )
    return state._replace(
        history=shard_leading(
            jax.tree_util.tree_unflatten(treedef, leaves), mesh, axis
        )
    )


def first_worker(stacked_tree):
    """Slice worker 0 out of a *worker-stacked* tree (leaves carry a leading
    ``num_workers`` axis — the ParameterAveragingTrainer state layout).  Not
    for ``replicate()`` output, which has no stacking axis."""
    return tree_map(lambda x: x[0], stacked_tree)


def shard_leading(tree, mesh: Mesh, axis: str = "dp"):
    """Shard every leaf's leading dimension over ``axis`` (the per-worker
    stacking used by the averaging trainer and for per-worker batches)."""
    return jax.device_put(tree, leading_sharding(mesh, axis))


def local_worker_slice(mesh: Mesh, axis: str = "dp") -> slice:
    """This process's contiguous block of the ``axis`` dimension (worker
    indices whose mesh position lands on local devices).  The host-side
    data-sharding rule of a multi-host run: each host loads/feeds only
    its own workers — the Spark-partitions-per-executor analog."""
    devs = np.moveaxis(mesh.devices, mesh.axis_names.index(axis), 0)
    pos = [
        i
        for i in range(mesh.shape[axis])
        if all(
            d.process_index == jax.process_index()
            for d in np.atleast_1d(devs[i]).flat
        )
    ]
    if not pos:
        raise ValueError("this process owns no workers on the mesh")
    if pos != list(range(pos[0], pos[-1] + 1)):
        raise ValueError(f"non-contiguous local worker block {pos}")
    return slice(pos[0], pos[-1] + 1)


def shard_leading_global(tree_local, mesh: Mesh, axis: str = "dp"):
    """Multi-host ``shard_leading``: every process passes only its LOCAL
    workers' leading block (see ``local_worker_slice``); the result is one
    global array spanning all hosts.  Single-process it expects the full
    leading dim and degrades to ``shard_leading``."""
    if jax.process_count() == 1:
        return shard_leading(tree_local, mesh, axis)
    sharding = leading_sharding(mesh, axis)
    n = mesh.shape[axis]

    def mk(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sharding, x, (n,) + tuple(x.shape[1:])
        )

    return tree_map(mk, tree_local)


def replicate_global(tree, mesh: Mesh):
    """Fully-replicated placement that also works multi-host (every process
    passes the same host value — the initial weight broadcast semantics)."""
    sharding = replicated_sharding(mesh)
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)

    def mk(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        )

    return tree_map(mk, tree)


class ParameterAveragingTrainer:
    """tau-step local SGD + parameter averaging over the ``dp`` axis."""

    # placed-live-mask LRU bound (masks are small; the bound exists so
    # churning membership views can never grow the cache monotonically)
    _LIVE_CACHE_MAX = 64

    def __init__(
        self,
        solver: Solver,
        mesh: Mesh,
        axis: str = "dp",
        average_stats: bool = True,
        mask_nonfinite: bool = True,
        compress: str = "none",
        overlap_avg: bool = False,
        comm_chunks: Optional[int] = None,
        overlap_steps: Optional[int] = None,
        comm_fused: Optional[bool] = None,
        hierarchy: Optional[HierarchySpec] = None,
        batch_spec=None,
    ):
        """``compress``/``overlap_avg`` engage the comm plane
        (``parallel/comm.py``): delta-quantized (bf16/int8) chunked
        collectives, optionally overlapped with the next round's first
        local steps.  The default (``compress='none'``,
        ``overlap_avg=False``) keeps the classic fused round,
        bit-identical to the pre-comm-plane trainer.

        With the solver's numerics audit on (``solver.audit`` — set it
        BEFORE constructing the trainer; the audit arity is baked into
        the shard_map output spec), ``round`` returns a third value:
        the per-worker audit stats tree.  ``mask_nonfinite`` then also
        arms the IN-GRAPH sentry mask: a worker whose local window
        produced any non-finite grad/param is excluded from this
        round's average before the ``psum`` — the poison never reaches
        the survivors, and the masked slot is overwritten with the
        survivor mean (it rejoins healthy next round).  If NO worker is
        finite the round keeps each worker's own (poisoned) params so
        the host sentry sees the damage and escalates, instead of a
        silent all-zero average.

        ``hierarchy`` (``parallel/hierarchy.py``) declares the two-tier
        averaging schedule: rounds where ``(r + 1) %
        cross_slice_every != 0`` average WITHIN each slice only (pass
        ``round_index`` to ``round()`` so resumed runs keep the
        absolute schedule); every K-th round runs the ordinary GLOBAL
        round — the same jitted program as today, so compression and
        overlap compose unchanged on the cross-slice tier.  A flat
        spec (one slice, or K == 1) yields the single-tier schedule
        and is bit-identical to ``hierarchy=None`` by construction.

        ``batch_spec`` generalizes the round's batch partitioning
        beyond the worker-major CNN layout: a ``PartitionSpec`` (or a
        pytree of them matching the batch dict) used as the shard_map
        in_spec for ``batches`` — e.g. the transformer LM passes
        ``{"tokens": P("dp", None, None, "sp"), ...}`` so each round's
        (num_workers, tau, B, T) token arrays shard their sequence
        dim over the ``sp`` ring while the leading dim keeps the dp
        worker split.  ``None`` keeps today's ``P(axis)`` (every CNN
        app, bit-identical)."""
        self.solver = solver
        self.mesh = mesh
        self.axis = axis
        self.num_workers = mesh.shape[axis]
        self.audit = bool(getattr(solver, "audit", False))
        self.mask_nonfinite = bool(mask_nonfinite) and self.audit
        self.average_stats = bool(average_stats)
        # batch pytree partitioning: P(axis) (worker-major, the CNN
        # apps) unless the caller declares per-leaf specs (sequence
        # parallelism)
        self.batch_spec = batch_spec
        batch_in_spec = P(axis) if batch_spec is None else batch_spec

        # the comm plane (parallel/comm.py): engaged for compressed
        # and/or overlapped averaging; None on the default path, which
        # keeps the fused round below bit-identical to the classic
        # trainer
        from sparknet_tpu.parallel import comm as _comm

        if compress not in _comm.COMPRESS_MODES:
            raise ValueError(
                f"compress={compress!r}: expected one of "
                f"{_comm.COMPRESS_MODES}"
            )
        self.compress = compress
        self._comm = None
        if compress != "none" or overlap_avg:
            self._comm = _comm.CommPlane(
                solver, mesh, axis,
                compress=compress,
                overlap=overlap_avg,
                chunks=(
                    _comm.DEFAULT_CHUNKS
                    if comm_chunks is None else comm_chunks
                ),
                overlap_steps=(
                    _comm.DEFAULT_OVERLAP_STEPS
                    if overlap_steps is None else overlap_steps
                ),
                average_stats=average_stats,
                mask_nonfinite=mask_nonfinite,
                batch_spec=batch_spec,
                # fused Pallas epilogue routing (None = the shared
                # lowerable() gate; True forces the kernels, as
                # tests/test_pallas_comm.py does)
                fused=comm_fused,
            )
        self._fused_payload_bytes: Optional[int] = None

        # two-tier hierarchical averaging (parallel/hierarchy.py): the
        # spec's slice grouping + K.  Flat specs never build the slice
        # program — every round is the global round (bit-identity).
        if hierarchy is not None and hierarchy.num_workers != self.num_workers:
            raise ValueError(
                f"hierarchy spec covers {hierarchy.num_workers} workers, "
                f"mesh has {self.num_workers}"
            )
        self.hierarchy = hierarchy
        self._two_tier = hierarchy is not None and not hierarchy.is_flat()
        # schedule fallback when round() isn't handed an absolute
        # round_index: counts this trainer's own round() calls
        self._auto_round = 0

        audit = self.audit
        mask_nf = self.mask_nonfinite

        def round_body(state, batches, rng, live):
            # shard_map hands each worker a leading axis of size 1
            st = tree_map(lambda x: x[0], state)
            bt = tree_map(lambda x: x[0], batches)
            widx = jax.lax.axis_index(axis)
            lrng = jax.random.fold_in(rng, widx)
            st, out = solver._step_tau(st, bt, lrng)
            if audit:
                losses, astats = out
            else:
                losses = out
            # averaging round: params (and BN stats) only, never history.
            # Survivor-aware: the average is a masked weighted mean over
            # LIVE workers — psum(where(live, theta, 0))/psum(live) — so
            # a dead dp worker's replica is excluded instead of
            # poisoning every survivor, and the dead slot itself is
            # overwritten with the survivor mean (it rejoins healthy).
            # where(), not multiplication: a dead replica holding
            # NaN/Inf garbage (diverged or interrupted step) must not
            # leak through 0*NaN=NaN into the psum.  With live == ones
            # this is exactly psum(theta)/N, the original pmean.
            alive = live[0]
            if mask_nf:
                # in-graph sentry mask: this worker's window produced a
                # non-finite grad or param -> drop it from the average
                bad = (
                    jnp.sum(astats["nonfinite_grads"])
                    + jnp.sum(astats["nonfinite_params"])
                ) > 0
                ok = jnp.where(bad, 0.0, 1.0)
                alive = alive * ok
                astats = dict(astats, masked=1.0 - ok)
            # the scope the device trace reads the cost of averaging from
            with jax.named_scope("average"):
                denom0 = jax.lax.psum(alive, axis)
                denom = jnp.maximum(denom0, 1.0)

                def wmean(w):
                    contrib = jnp.where(alive > 0, w, jnp.zeros_like(w))
                    m = jax.lax.psum(contrib, axis) / denom.astype(w.dtype)
                    if mask_nf:
                        # no finite worker at all: keep own params (the
                        # host sentry escalates) instead of an all-zero
                        # "average" that would read as healthy
                        return jnp.where(denom0 > 0, m, w)
                    return m

                avg_params = tree_map(wmean, st.params)
                avg_stats = (
                    tree_map(wmean, st.stats) if average_stats else st.stats
                )
            history = st.history
            if mask_nf:
                # the masked slot's params are replaced by the survivor
                # mean, but its momentum history still holds the
                # poisoned window — zero it too, or momentum replays the
                # non-finite update next round and the worker re-
                # diverges (staying masked forever off one bad batch).
                # bad=False selects the original leaves exactly, so
                # healthy rounds keep the bit-identity contract.
                rejoined = jnp.logical_and(bad, denom0 > 0)
                history = tree_map(
                    lambda h: jnp.where(rejoined, jnp.zeros_like(h), h),
                    history,
                )
            st = TrainState(avg_params, avg_stats, history, st.iter)
            if audit:
                return (
                    tree_map(lambda x: x[None], st),
                    losses[None],
                    tree_map(lambda x: x[None], astats),
                )
            return tree_map(lambda x: x[None], st), losses[None]

        # state AND batches are donated: the consumed round's batch
        # buffers are recycled on device (XLA reuses them as scratch /
        # for outputs) instead of coexisting with round r+1's incoming
        # batch — with the pipelined RoundFeed keeping a batch in
        # flight, that halves steady-state batch memory.  Callers pass
        # host numpy batches (safe to reuse: the jit places a fresh
        # device buffer and donates THAT) or a freshly-placed device
        # batch per round (the apps/RoundFeed pattern); a device batch
        # is deleted by the round that consumes it.
        out_specs = (
            (P(axis), P(axis), P(axis)) if audit else (P(axis), P(axis))
        )
        # every program below is an obs.Program: built (lowered, compiled,
        # accounted for: obs.programs()) on a batch it has not seen, then
        # the jax.jit it wraps is called.  A call is keyed on the batches
        # alone; the marks read the memory of this mesh's own devices
        self._devices = mesh.local_devices
        self._round = obs.Program("round", jax.jit(
            shard_map(
                round_body,
                mesh=mesh,
                in_specs=(P(axis), batch_in_spec, P(), P(axis)),
                out_specs=out_specs,
            ),
            donate_argnums=(0, 1),
        ), watch=(1,), devices=self._devices)
        # per-mask placed live masks, cached: the chaos/degraded loops
        # pass the SAME mask for many consecutive rounds, and the
        # all-alive default mask is placed exactly once.  A true LRU
        # (move-to-front on hit, evict-oldest at the bound): elastic
        # membership churns a fresh mask per view epoch, and the old
        # clear-the-world overflow dropped the hot all-alive entry
        # along with the churn.
        self._live_cache: "OrderedDict[bytes, jax.Array]" = OrderedDict()

        # intra-slice averaging program (two-tier schedule only): the
        # same local window, but the averaging epilogue is a PER-SLICE
        # masked weighted mean.  Expressed as a stacked per-slice psum
        # (each worker selects its own slice's row) because this jax
        # build's shard_map doesn't lower psum(axis_index_groups=...);
        # on the virtual mesh collectives are shared-memory copies
        # either way, and the tier byte accounting below models the
        # ICI-vs-DCN split (hierarchy.py module docstring).
        self._slice_round = None
        if self._two_tier:
            slice_ids = jnp.asarray(hierarchy.slice_ids(), jnp.int32)
            num_slices = hierarchy.num_slices

            def slice_body(state, batches, rng, live):
                st = tree_map(lambda x: x[0], state)
                bt = tree_map(lambda x: x[0], batches)
                widx = jax.lax.axis_index(axis)
                lrng = jax.random.fold_in(rng, widx)
                st, out = solver._step_tau(st, bt, lrng)
                if audit:
                    losses, astats = out
                else:
                    losses = out
                alive = live[0]
                if mask_nf:
                    bad = (
                        jnp.sum(astats["nonfinite_grads"])
                        + jnp.sum(astats["nonfinite_params"])
                    ) > 0
                    ok = jnp.where(bad, 0.0, 1.0)
                    alive = alive * ok
                    astats = dict(astats, masked=1.0 - ok)
                sid = slice_ids[widx]
                onehot = (
                    jnp.arange(num_slices, dtype=jnp.int32) == sid
                ).astype(jnp.float32)
                # per-slice live counts, visible to every worker; each
                # worker reads its OWN slice's count
                with jax.named_scope("average"):
                    denom0_all = jax.lax.psum(onehot * alive, axis)
                    denom0 = jnp.take(denom0_all, sid)
                    denom = jnp.maximum(denom0, 1.0)

                    def smean(w):
                        contrib = jnp.where(alive > 0, w, jnp.zeros_like(w))
                        stacked = (
                            onehot.reshape((num_slices,) + (1,) * w.ndim)
                            * contrib[None]
                        )
                        sums = jax.lax.psum(stacked, axis)
                        m = jnp.take(
                            sums, sid, axis=0
                        ) / denom.astype(w.dtype)
                        # a fully-departed slice keeps its own params (its
                        # slots are stale until readmission broadcasts) —
                        # unlike the global round there may be NO live
                        # worker in this group even on a healthy fleet
                        return jnp.where(denom0 > 0, m, w)

                    avg_params = tree_map(smean, st.params)
                    avg_stats = (
                        tree_map(smean, st.stats)
                        if average_stats else st.stats
                    )
                history = st.history
                if mask_nf:
                    # audit-masked worker rejoining its slice mean:
                    # zero its momentum (the fused round's contract)
                    rejoined = jnp.logical_and(bad, denom0 > 0)
                    history = tree_map(
                        lambda h: jnp.where(
                            rejoined, jnp.zeros_like(h), h
                        ),
                        history,
                    )
                st = TrainState(avg_params, avg_stats, history, st.iter)
                if audit:
                    return (
                        tree_map(lambda x: x[None], st),
                        losses[None],
                        tree_map(lambda x: x[None], astats),
                    )
                return tree_map(lambda x: x[None], st), losses[None]

            self._slice_round = obs.Program("slice_round", jax.jit(
                shard_map(
                    slice_body,
                    mesh=mesh,
                    in_specs=(P(axis), batch_in_spec, P(), P(axis)),
                    out_specs=out_specs,
                ),
                donate_argnums=(0, 1),
            ), watch=(1,), devices=self._devices)

        def eval_body(state, batches, counts):
            # heterogeneous partitions: every worker's batches are padded
            # to the max count; only its own first `counts[w]` batches
            # score (equal partitions just pass counts == nb everywhere)
            st = tree_map(lambda x: x[0], state)
            bt = tree_map(lambda x: x[0], batches)
            scores = solver._forward_test(
                st.params, st.stats, bt, count=counts[0]
            )
            # global accumulation (the RDD reduce of test scores,
            # CifarApp.scala:113)
            return {k: jax.lax.psum(v, axis) for k, v in scores.items()}

        self._eval = obs.Program("eval", jax.jit(
            shard_map(
                eval_body,
                mesh=mesh,
                in_specs=(P(axis), P(axis), P(axis)),
                out_specs=P(),
            )
        ), watch=(1, 2), devices=self._devices)

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0) -> TrainState:
        """All workers start from identical weights (the initial broadcast,
        CifarApp.scala:92-97); per-worker slots stacked on axis 0 and
        sharded over ``dp``.  Host time to return, as ``average`` is: the
        span adds no sync; the two memory marks around it bracket what the
        replicas hold (``in_use`` after, less before)."""
        obs.program.mark_memory("init_state:enter", self._devices)
        with obs.span("init_state", cat="build", workers=self.num_workers):
            state = self._init_state(seed)
        obs.program.mark_memory("init_state", self._devices)
        return state

    def _init_state(self, seed: int) -> TrainState:
        st = self.solver.init_state(seed)
        n = self.num_workers
        sharding = leading_sharding(self.mesh, self.axis)
        if n == 1:
            # one worker: the stack is the replica itself under a leading
            # axis of 1.  Donated, so the buffers are the same ones: the
            # host path below holds the replica AND its stacked copy on the
            # one device until it returns, twice a state of gigabytes
            # (PERF.md section 6, PR 27).  Several workers take the host
            # path as before
            return obs.Program("stack_state", jax.jit(
                lambda tree: tree_map(lambda x: x[None], tree),
                out_shardings=sharding, donate_argnums=(0,),
            ), devices=self._devices)(st)

        # identical init in every process; each device's shard is cut
        # from a broadcast VIEW of the one host replica, so the n-fold
        # stack never exists on the host or on any single device
        def mk(x):
            x = np.asarray(x)
            full = np.broadcast_to(x, (n,) + x.shape)
            return jax.make_array_from_callback(
                full.shape, sharding, lambda idx: full[idx]
            )

        return tree_map(mk, st)

    def broadcast_state(self, st: TrainState) -> TrainState:
        """Re-place a SINGLE-replica TrainState (a snapshot restore)
        onto the mesh: every worker slot gets the same value — the
        reference's restore-on-every-executor semantics.  The resume
        entry for ``imagenet_run_db_app --resume``, the chaos harness,
        and the sentry's rollback path."""
        if self._comm is not None:
            # a restored state invalidates the comm plane's carried
            # anchor/residual and any in-flight collective — a stale
            # correction applied onto restored params would corrupt
            # them (the residual reset mirrors the momentum-zeroing
            # rejoin contract)
            self._comm.reset()
        n = self.num_workers
        stacked = tree_map(
            lambda x: np.broadcast_to(
                np.asarray(x), (n,) + np.asarray(x).shape
            ).copy(),
            jax.device_get(st),
        )
        if jax.process_count() == 1:
            return shard_leading(stacked, self.mesh, self.axis)
        return shard_leading_global(
            tree_map(
                lambda x: x[local_worker_slice(self.mesh, self.axis)],
                stacked,
            ),
            self.mesh,
            self.axis,
        )

    # --- full job state (crash consistency, io/checkpoint extra_state)
    def export_comm_state(self):
        """The comm plane's carried error-feedback residuals as a
        host-side jobstate fragment, or None on the classic fused
        round (no carried state).  Snapshot this beside params so a
        resumed run continues the EF-SGD trajectory bit-identically
        (``runtime/recover.py``)."""
        if self._comm is None:
            return None
        return self._comm.export_state()

    def restore_comm_state(self, exported) -> None:
        """Load residuals exported by ``export_comm_state`` — call
        AFTER ``broadcast_state`` (which resets the plane) so the
        journaled residuals land on the freshly placed params."""
        if exported is None:
            return
        if self._comm is None:
            raise ValueError(
                "jobstate carries comm residuals but this trainer runs "
                "the classic fused round (compress/overlap off)"
            )
        self._comm.restore_state(exported)

    def reset_comm_state(self) -> None:
        """Drop carried comm state (fresh-run entry for a reused
        trainer: in-process chaos/recover harnesses)."""
        if self._comm is not None:
            self._comm.reset()

    def _place_live(self, live_mask) -> jax.Array:
        """Place a host (num_workers,) 0/1 mask over the dp axis.
        Cached per distinct mask value — the loops pass the same mask
        round after round (all-alive, or one fixed fault pattern), so
        the placement happens once, not once per round."""
        # sparknet: sync-ok(live_mask is a host 0/1 array, never a device value; placement cached per mask)
        live = np.asarray(live_mask, np.float32).reshape(-1)
        if live.shape[0] != self.num_workers:
            raise ValueError(
                f"live_mask has {live.shape[0]} entries, mesh has "
                f"{self.num_workers} workers"
            )
        key = live.tobytes()
        cached = self._live_cache.get(key)
        if cached is not None:
            # LRU hit: keep hot masks (the all-alive default, a standing
            # fault pattern) resident while membership churn turns over
            self._live_cache.move_to_end(key)
            return cached
        sharding = leading_sharding(self.mesh, self.axis)
        if jax.process_count() > 1:
            placed = jax.make_array_from_callback(
                live.shape, sharding, lambda idx: live[idx]
            )
        else:
            placed = jax.device_put(live, sharding)
        while len(self._live_cache) >= self._LIVE_CACHE_MAX:
            # evict the coldest entry only: a churning mask stream
            # (every membership view epoch is a new mask value) stays
            # bounded WITHOUT dropping the hot entries alongside it
            self._live_cache.popitem(last=False)
        self._live_cache[key] = placed
        return placed

    def compile_round(self, state: TrainState, batches: Dict[str, jax.Array]):
        """Build the fused round program for a state and batches like
        these (``obs.Program.build``: lowered, compiled and accounted for,
        running nothing and donating nothing): ``round`` with the default
        key and an all-alive mask then finds it compiled.  For a caller's
        thread, while its data loads or its other programs compile (jax's
        compile releases the interpreter)."""
        live = self._place_live(np.ones((self.num_workers,), np.float32))
        self._round.build(state, batches, default_train_key(0), live)

    def round(
        self,
        state: TrainState,
        batches: Dict[str, jax.Array],
        rng=None,
        live_mask=None,
        round_index: Optional[int] = None,
    ):
        """One averaging round: ``batches[blob]`` is (num_workers, tau, ...)
        — worker-major, tau-deep.  Returns (state, losses (workers, tau)).

        ``live_mask`` (num_workers,) of 0/1 marks which dp workers
        survive this round: dead workers are excluded from the average
        (masked weighted mean) and receive the survivor mean — a lost
        partition degrades throughput, never the weights.  ``None``
        means all alive (identical numerics to the unmasked round).

        ``round_index`` is the ABSOLUTE round — only the two-tier
        hierarchy schedule consumes it (which rounds cross slices);
        omitted, the trainer counts its own calls, which is correct
        for fresh runs but loses the absolute schedule across resumes.

        With the solver's numerics audit on, returns ``(state, losses,
        stats)`` where ``stats`` is the per-worker audit tree (leaves
        (num_workers, tau); plus ``masked`` (num_workers,) when the
        in-graph non-finite mask is armed)."""
        rng = rng if rng is not None else default_train_key(0)
        # sparknet: sync-ok(round_index is a host int from the driver loop, never a device value)
        r = self._auto_round if round_index is None else int(round_index)
        self._auto_round = r + 1
        # two-tier schedule: intra-slice rounds between cross-slice
        # (global) ones; flat specs and hierarchy=None are always cross
        intra = self._two_tier and not self.hierarchy.is_cross_round(r)
        # "average" is the whole averaging round (this method IS one
        # round of the SparkNet algorithm); "execute" nests inside it as
        # the fused XLA program's dispatch/execution.  Span timing stays
        # dispatch-honest: no extra device sync is added here.
        astats = None
        with obs.span("average", round=r):
            if live_mask is None:
                live_mask = np.ones((self.num_workers,), np.float32)
            live = self._place_live(live_mask)  # cached per mask value
            if intra:
                # a pending overlapped CROSS-slice collective lands at
                # this round boundary (its correction is global
                # consensus — applying it after a slice-local average
                # would de-synchronize slices); with K > 1 the overlap
                # window is the boundary gap, disclosed in PERF.md
                if self._comm is not None:
                    state = self._comm.finalize(state)
                with obs.span("execute"):
                    if self.audit:
                        state, losses, astats = self._slice_round(
                            state, batches, rng, live
                        )
                    else:
                        state, losses = self._slice_round(
                            state, batches, rng, live
                        )
                tm = obs.training_metrics()
                if tm is not None:
                    tm.collective_bytes.labels("none").inc(
                        self._payload_bytes(state)
                    )
            elif self._comm is not None:
                # comm plane: delta-quantized chunked collectives,
                # optionally overlapped with the next round's compute
                out = self._comm.round(
                    state, batches, rng, live, live_mask
                )
                if self.audit:
                    state, losses, astats = out
                else:
                    state, losses = out
            else:
                with obs.span("execute"):
                    if self.audit:
                        state, losses, astats = self._round(
                            state, batches, rng, live
                        )
                    else:
                        state, losses = self._round(
                            state, batches, rng, live
                        )
                tm = obs.training_metrics()
                if tm is not None:
                    # the fused fp32 collective's modeled wire bytes
                    # (ring factor x params+stats payload) — computed
                    # once, charged per round
                    tm.collective_bytes.labels("none").inc(
                        self._payload_bytes(state)
                    )
            # tier-split byte/round accounting for hierarchy runs: the
            # intra series models the ICI (in-slice) fabric, the cross
            # series the DCN — the quantity the two-tier schedule
            # divides by K
            # (tests/test_membership.py::test_hierarchy_tier_metrics_charged)
            tm = obs.training_metrics()
            if tm is not None and self.hierarchy is not None:
                tier = "intra" if intra else "cross"
                payload = self._payload_bytes(state)
                if not intra and self._comm is not None:
                    payload = self._comm.payload_bytes_per_round or payload
                tm.hierarchy_rounds.labels(tier).inc()
                tm.hierarchy_bytes.labels(tier).inc(payload)
            # recorded lazily: smoothed_loss pulls the worker-mean of the
            # addressable shards on read (Solver._drain_losses) — no
            # device->host sync in the round loop
            self.solver.note_losses(losses)
        tm = obs.training_metrics()
        if tm is not None:
            tm.rounds.inc()
            tm.iters.inc(losses.shape[-1])  # tau (shape read: no sync)
        prof = obs_profile.active()
        if prof is not None:
            # round-anatomy profiler (--profile): static work sizes once,
            # then the per-shard execute probe + round finalize.  Outside
            # the average span so the probe's sync never inflates it.
            self._note_profile_work(prof, int(losses.shape[-1]), state)
            prof.observe_round(losses)
        obs.report_healthy()  # a completed round clears /healthz
        if obs.recording():
            # only where a sink keeps it: memory_stats() is a call into
            # the runtime, and an unobserved round makes none
            obs.program.mark_memory("round", self._devices, keep=False)
        if self.audit:
            return state, losses, astats
        return state, losses

    def _payload_bytes(self, state) -> int:
        """Modeled per-round fp32 collective payload bytes (ring factor
        x params+stats), computed once per trainer from the state's
        shapes."""
        if self._fused_payload_bytes is None:
            from sparknet_tpu.parallel import comm as _comm

            self._fused_payload_bytes = _comm.fused_round_payload_bytes(
                state, self.average_stats
            )
        return self._fused_payload_bytes

    def _note_profile_work(self, prof, tau: int, state) -> None:
        """Hand the profiler this trainer's modeled per-round work: MXU
        FLOPs (analytic shape walk) and collective payload bytes (comm
        plane when engaged, else the fused fp32 model)."""
        # memo: a WEAKREF to the noting trainer lives on the profiler —
        # id()-based keys on either side collide when a fresh object
        # recycles a freed address, silently starving the new one of
        # its work sizes
        noted = getattr(prof, "_work_noted_by", None)
        if noted is not None and noted[0]() is self and noted[1] == tau:
            return
        prof._work_noted_by = (weakref.ref(self), tau)
        flops = None
        try:
            from sparknet_tpu.utils.flops import train_flops

            flops = train_flops(self.solver.net) * tau * self.num_workers
        except Exception:  # a net without static shapes stays unmodeled
            pass
        if self._comm is not None:
            payload = self._comm.payload_bytes_per_round or None
            compress = self._comm.compress
        else:
            self._payload_bytes(state)
            payload = self._fused_payload_bytes
            compress = "none"
        prof.note_round_work(
            flops_per_round=flops,
            comm_bytes_per_round=payload,
            compress=compress,
            num_workers=self.num_workers,
        )

    def finalize(self, state: TrainState) -> TrainState:
        """Land any in-flight overlapped averaging collective into
        ``state`` (``--overlap_avg``): call before an eval or at the
        end of training so the last round's average is applied.
        No-op on the default (fused) path and when nothing is
        pending."""
        if self._comm is not None:
            return self._comm.finalize(state)
        return state

    def test_and_store_result(
        self, state: TrainState, batches: Dict[str, jax.Array], counts=None
    ) -> Dict[str, float]:
        """Distributed eval: ``batches[blob]`` is (num_workers, nb, ...);
        returns accumulated scores over ALL workers' batches.  With
        heterogeneous test partitions, pad every worker to the same nb and
        pass ``counts`` (num_workers,) int32 — each worker scores only its
        own first ``counts[w]`` batches (the reference's per-partition
        full-pass sampler, CifarApp.scala:103-106)."""
        if counts is None:
            nb = (
                next(iter(batches.values())).shape[1]
                if jax.process_count() > 1
                else len(next(iter(batches.values()))[0])
            )
            counts = np.full((self.num_workers,), nb, np.int32)
        counts = np.asarray(counts, np.int32)
        if jax.process_count() > 1 and counts.shape[0] == self.num_workers:
            # pass the GLOBAL counts on every host; place like the state
            sharding = leading_sharding(self.mesh, self.axis)
            counts_arr = jax.make_array_from_callback(
                counts.shape, sharding, lambda idx: counts[idx]
            )
        else:
            counts_arr = jnp.asarray(counts, jnp.int32)
        out = self._eval(state, batches, counts_arr)
        return {k: float(v) for k, v in jax.device_get(out).items()}

    @staticmethod
    def pad_partitions(parts):
        """Stack per-worker {blob: (nb_w, ...)} dicts of UNEQUAL nb_w into
        ({blob: (N, nb_max, ...)} zero-padded, counts (N,)) for
        ``test_and_store_result`` — the pad-and-mask layout."""
        keys = parts[0].keys()
        counts = np.array(
            [len(next(iter(p.values()))) for p in parts], np.int32
        )
        nb_max = int(counts.max())
        stacked = {}
        for k in keys:
            ref = parts[0][k]
            out = np.zeros((len(parts), nb_max) + ref.shape[1:], ref.dtype)
            for w, p in enumerate(parts):
                out[w, : len(p[k])] = p[k]
            stacked[k] = out
        return stacked, counts


class AllReduceTrainer:
    """Synchronous gradient all-reduce DP (the P2PSync replacement), with
    optional tensor-parallel param placement over ``mp``."""

    def __init__(
        self,
        solver: Solver,
        mesh: Mesh,
        dp_axis: str = "dp",
        mp_axis: Optional[str] = None,
    ):
        self.solver = solver
        self.mesh = mesh
        self.dp_axis = dp_axis
        if mp_axis is not None and mp_axis not in mesh.axis_names:
            raise ValueError(
                f"mp_axis {mp_axis!r} is not a mesh axis {mesh.axis_names}"
            )
        self.mp_axis = mp_axis

        repl = NamedSharding(mesh, P())
        # batches are (tau, global_batch, ...): shard the batch dim over dp
        batch_sharding = NamedSharding(mesh, P(None, dp_axis))
        # structure/shapes only — no RNG or device memory spent
        params0, stats0 = jax.eval_shape(solver.net.init, 0)
        param_shardings = self._param_shardings(params0)
        # history mirrors each param blob's placement; stats replicated
        if solver.method in ("ADADELTA", "ADAM"):
            history_shardings = (param_shardings, param_shardings)
        else:
            history_shardings = param_shardings
        state_shardings = TrainState(
            params=param_shardings,
            stats=tree_map(lambda _: repl, stats0),
            history=history_shardings,
            iter=repl,
        )
        self._state_shardings = state_shardings
        self._devices = mesh.local_devices
        self._jit_round = obs.Program("sync_round", jax.jit(
            solver._step_tau,
            donate_argnums=(0,),
            in_shardings=(state_shardings, batch_sharding, repl),
            out_shardings=(state_shardings, repl),
        ), watch=(1,), devices=self._devices)
        self._batch_sharding = batch_sharding

    @property
    def batch_sharding(self):
        """The (tau, global_batch) placement ``step()`` applies — public
        for feeds that issue the put on a producer thread (RoundFeed);
        ``step()`` on an already-so-placed batch re-puts as a no-op."""
        return self._batch_sharding

    def _param_shardings(self, params):
        """TP policy: shard the output-channel dim of large param blobs over
        ``mp`` when divisible; everything else replicated.  GSPMD inserts
        the activation collectives."""
        mesh = self.mesh

        def place(x):
            if (
                self.mp_axis
                and x.ndim >= 2
                and x.shape[0] % mesh.shape[self.mp_axis] == 0
                and x.size >= 4096
            ):
                return NamedSharding(
                    mesh, P(self.mp_axis, *([None] * (x.ndim - 1)))
                )
            return NamedSharding(mesh, P())

        return tree_map(place, params)

    def init_state(self, seed: int = 0) -> TrainState:
        obs.program.mark_memory("init_state:enter", self._devices)
        with obs.span("init_state", cat="build", workers=self.mesh.size):
            st = self.solver.init_state(seed)
            state = jax.device_put(st, self._state_shardings)
        obs.program.mark_memory("init_state", self._devices)
        return state

    def shard_state(self, state: TrainState) -> TrainState:
        """Place an existing (host or single-device) TrainState onto the
        mesh — the resume/warm-start entry (``Solver::Restore`` before
        ``P2PSync::Run``, tools/caffe.cpp:207-216)."""
        return jax.device_put(state, self._state_shardings)

    def step(self, state: TrainState, batches: Dict[str, jax.Array], rng=None):
        """tau synchronous steps on a globally-sharded batch
        (batches[blob]: (tau, global_B, ...)).  With the solver's
        numerics audit on (readable here at step time — the jit's
        output sharding is a pytree prefix, so no rebuild is needed),
        returns ``(state, losses, stats)``."""
        rng = rng if rng is not None else default_train_key(0)
        audit = bool(getattr(self.solver, "audit", False))
        stats = None
        with obs.span("execute"):
            batches = jax.device_put(batches, self._batch_sharding)
            state, out = self._jit_round(state, batches, rng)
            if audit:
                losses, stats = out
            else:
                losses = out
            self.solver.note_losses(losses)
        tm = obs.training_metrics()
        if tm is not None:
            tm.rounds.inc()
            tm.iters.inc(losses.shape[0])  # tau (shape read: no sync)
        # --profile: finalize the profiled round (losses are replicated
        # here, so no per-worker shard probe — phases/skew come from the
        # span stream and the feed's worker hooks)
        obs_profile.observe_round_if_active(losses)
        obs.report_healthy()
        if audit:
            return state, losses, stats
        return state, losses
