"""Pipelined round feed: overlap host batch assembly + H2D with the round.

The SparkNet reference keeps PREFETCH_COUNT=3 batches in flight on an
InternalThread precisely so the data plane never serializes with the
solver (``base_data_layer.cpp:70-101``); until round 8 every app and
``cli train`` did per-round host ``np.stack`` assembly -> blocking
sharded ``device_put`` -> ``trainer.round``, fully serial, so on a
machine with a spare core the host work was pure added wall-clock per
round (PERF.md names input-pipeline skew, not the collective, as the
realistic threat to >=0.9 scaling at dp=32).

``RoundFeed`` is the reusable executor behind all of those loops now:

- round r+1's worker-stacked tau-deep batch dict is **assembled on a
  producer thread** (the ``Prefetcher`` bounded-queue/stall-watchdog
  machinery underneath, so ``PrefetchStall`` and the
  stop()-and-``restart()`` recovery pattern compose unchanged),
- the dp-sharded ``device_put`` is issued from that thread too, so
  assembly AND the H2D copy of round r+1 run under round r's execute,
- the placement (``NamedSharding``) is built **once** and cached, not
  rebuilt per round,
- host buffers are **recycled** between rounds (``assemble`` receives
  its previous output dict back and refills it in place — e.g. via
  ``stack_windows(windows, out)`` — instead of fresh ``np.stack``
  allocations each round)... except on the ``cpu`` backend, where a
  sharded ``device_put`` zero-copies aligned host buffers (the device
  shards ALIAS the numpy memory — measured on this jax build), so
  reusing the buffer would scribble over a round still in flight;
  there ``assemble`` is handed ``out=None`` every round and the
  orphaned allocation is the (free) zero-copy source.

``pipelined=False`` is the **serial mode**: assemble and put run on
the consumer, between rounds, with identical numerics.  The training
loops run pipelined; the serial mode is the reference the tests hold
the pipelined feed to.

Determinism contract: ``assemble`` is called exactly once per round, in
round order, from a single thread — a stateful sampler draws the same
sequence under the pipelined and serial modes, and the trained
``TrainState`` is bit-identical between them
(``tests/test_round_feed.py``).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import numpy as np

from sparknet_tpu import obs
from sparknet_tpu.obs import profile as obs_profile
from sparknet_tpu.data.prefetch import (  # noqa: F401  (re-exported)
    PREFETCH_COUNT,
    Prefetcher,
    PrefetchStall,
)


def _host_nbytes(host) -> int:
    """Byte size of a host batch pytree (the H2D payload the h2d span
    carries so the profiler can report achieved transfer bandwidth)."""
    try:
        return int(
            sum(
                int(v.nbytes)
                for v in jax.tree_util.tree_leaves(host)
                if hasattr(v, "nbytes")
            )
        )
    except (AttributeError, TypeError):
        return 0

Assemble = Callable[[int, Optional[Dict[str, np.ndarray]]],
                    Dict[str, np.ndarray]]


def stack_windows(windows, out=None):
    """Stack per-worker batch pytrees ``{blob: (tau, ...)}`` (flat
    dicts — the CNN apps — or ANY nested pytree: token/target dicts,
    tuples, dicts of dicts) into the worker-major round layout
    ``{blob: (num_workers, tau, ...)}``, leaf by leaf.  All windows
    must share one tree structure.  With ``out`` (a RoundFeed-recycled
    buffer of the same structure) the stack writes in place instead of
    allocating fresh arrays each round."""
    if out is None:
        return jax.tree_util.tree_map(
            lambda *leaves: np.stack(leaves), *windows
        )
    jax.tree_util.tree_map(
        lambda buf, *leaves: np.stack(leaves, out=buf), out, *windows
    )
    return out


def sharded_put_may_alias() -> bool:
    """Whether ``jax.device_put`` with a sharding may return device
    shards that ALIAS the source numpy buffer (zero-copy).  True on the
    cpu backend (measured on this jax build: the sharded put aliases,
    the plain put does not — we gate on the platform, conservatively);
    every non-cpu backend copies across the host->device link."""
    return jax.devices()[0].platform == "cpu"


class RoundFeed:
    """Pipelined per-round batch executor for the training loops.

    ``assemble(r, out)`` builds absolute round ``r``'s host batch dict:
    when ``out`` is None it allocates and returns a fresh dict; when
    ``out`` is the dict a previous call returned, it MAY refill it in
    place and return it (buffer recycling — opt in via
    ``stack_windows(windows, out)``; returning a fresh dict is always
    correct, just unrecycled).

    Placement, most specific wins: ``place`` (a callable
    ``host_dict -> device_batch`` — the multi-host loops pass
    ``shard_leading_global``), else ``sharding`` (used as
    ``jax.device_put(host, sharding)`` — a single sharding broadcast
    over every leaf, or a pytree of shardings matching the batch
    structure, e.g. the LM's per-blob dp x sp placement), else
    ``mesh``/``axis`` (the cached ``NamedSharding(mesh, P(axis))`` —
    the single-host default), else a plain ``jax.device_put``.

    The consumer calls ``next_round(r)`` with consecutive absolute round
    indices; on a ``PrefetchStall`` it calls ``restart(r)`` and retries
    (the chaos-harness recovery pattern).  ``stop()`` tears the producer
    down (idempotent, reports whether the thread died)."""

    def __init__(
        self,
        assemble: Assemble,
        *,
        mesh=None,
        axis: str = "dp",
        sharding=None,
        place: Optional[Callable] = None,
        pipelined: bool = True,
        depth: int = PREFETCH_COUNT - 1,
        stall_timeout_s: Optional[float] = None,
        start_round: int = 0,
        num_rounds: Optional[int] = None,
        recycle: Optional[bool] = None,
    ):
        if sharding is None and mesh is not None:
            from sparknet_tpu.parallel.trainers import leading_sharding

            sharding = leading_sharding(mesh, axis)
        self._assemble = assemble
        self._sharding = sharding  # built once; never per round
        self._place = place if place is not None else self._default_place
        self._pipelined = bool(pipelined)
        self._depth = max(1, int(depth))
        self._stall_timeout_s = stall_timeout_s
        self._start = int(start_round)
        self._end = (
            self._start + int(num_rounds) if num_rounds is not None else None
        )
        # recycling is only safe when the device batch cannot alias the
        # host buffer (see sharded_put_may_alias); a custom `place` gets
        # the conservative default too unless the caller vouches.  The
        # serial fallback never recycles by default: its point is to
        # restore the old async put-and-dispatch loop verbatim, and
        # recycling's block_until_ready would add a per-round H2D wait
        # the serial path never had (allocation is off the critical
        # path there — one batch at a time).
        self._recycle = (
            bool(recycle) if recycle is not None
            else (pipelined and not sharded_put_may_alias())
        )
        self._buf: Optional[Dict[str, np.ndarray]] = None
        self._next_r = self._start
        self._pf: Optional[Prefetcher] = None
        if self._pipelined:
            self._spawn(self._start)

    # ------------------------------------------------------------------
    def _default_place(self, host):
        if self._sharding is not None:
            return jax.device_put(host, self._sharding)
        return jax.device_put(host)

    def _produce_one(self, r: int):
        # spans land on the PRODUCER thread when pipelined, so a trace
        # shows round r+1's assemble/h2d bars interleaving under the
        # consumer thread's execute bar — the overlap, visually
        with obs.span("assemble", round=r):
            host = self._assemble(r, self._buf if self._recycle else None)
        with obs.span("h2d", round=r, nbytes=_host_nbytes(host)):
            dev = self._place(host)
            if self._recycle:
                # the H2D copy must complete before the buffer is
                # refilled; blocking HERE keeps the wait on the producer
                # thread, still fully overlapped with the consumer's
                # round execute
                # sparknet: sync-ok(recycle handback: the H2D must land before the buffer refills; waits on the producer thread, overlapped under consumer execute)
                jax.block_until_ready(dev)
                self._buf = host  # adopt (first round) / keep the buffer
        return dev

    def _spawn(self, start_r: int):
        # the round cursor is LOCAL to this producer generation: a
        # thread that outlives stop() (wedged inside assemble past the
        # reap timeout) keeps bumping ITS cursor, never the rebuilt
        # generation's — the chaos-harness ordering guarantee
        cur = [start_r]

        def produce():
            r = cur[0]
            if self._end is not None and r >= self._end:
                return None
            dev = self._produce_one(r)
            cur[0] += 1
            return dev

        self._pf = Prefetcher(
            produce,
            depth=self._depth,
            device_put=False,  # the put happens in produce, sharded
            stall_timeout_s=self._stall_timeout_s,
        )

    # ------------------------------------------------------------------
    def next_round(self, r: int):
        """The placed device batch for absolute round ``r``.  Rounds
        must be requested consecutively (``restart`` rewinds).  Raises
        ``PrefetchStall`` when the producer goes silent past
        ``stall_timeout_s`` and ``StopIteration`` past ``num_rounds``."""
        if r != self._next_r:
            raise ValueError(
                f"RoundFeed is at round {self._next_r}, asked for {r} "
                "(rounds are consumed in order; use restart() to rewind)"
            )
        if self._end is not None and r >= self._end:
            raise StopIteration
        if not self._pipelined:
            out = self._produce_one(r)
        else:
            if self._pf is None:
                self._spawn(r)
            out = next(self._pf)
        tm = obs.training_metrics()
        if tm is not None and self._pf is not None:
            tm.feed_queue_depth.set(self._pf.qsize())
        # the profiler keys its round records by the ABSOLUTE round the
        # consumer is about to train on (resume replays re-key correctly)
        obs_profile.note_consumed_round(r)
        self._next_r = r + 1
        return out

    def restart(self, r: int) -> bool:
        """Reap the current producer generation and respawn from
        absolute round ``r`` — the post-``PrefetchStall`` recovery (and
        the resume-replay rewind).  Returns whether the old producer
        thread actually died; if it did not, the recycled buffer is
        abandoned (the wedged thread may still write into it)."""
        exited = True
        if self._pf is not None:
            exited = self._pf.stop()
            if not exited:
                self._buf = None  # never share a buffer with a zombie
        self._next_r = r
        if self._pipelined:
            self._spawn(r)
        return exited

    def stop(self, timeout: float = 5.0) -> bool:
        """Stop the producer and reap its thread (idempotent)."""
        if self._pf is None:
            return True
        return self._pf.stop(timeout)

    close = stop
