"""Async host->device prefetch.

Reference: ``BasePrefetchingDataLayer`` keeps PREFETCH_COUNT=3 batches in
flight on an InternalThread with an async H2D push (``base_data_layer.cpp:
70-101``); ``BlockingQueue`` provides the handshake.  Here the same
double-buffering is a producer thread + bounded queue, and the device push
is ``jax.device_put`` (which on TPU overlaps with compute because transfers
are async until the buffer is used).

``device_put=False`` makes the producer deliver host batches only; the
consumer then issues ``jax.device_put`` itself between steps.

Fault tolerance: ``stall_timeout_s`` arms a consumer-side watchdog — if
the producer delivers nothing for that long (storage wedged past the
retry layer's budget, dead pipeline thread), ``__next__`` raises
``PrefetchStall`` instead of hanging the training loop forever; the
driver can tear the prefetcher down (``stop()`` is idempotent and
reports whether the thread actually died) and rebuild it — the pattern
``runtime/chaos.py`` proves out.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Callable, Dict, Iterator, Optional

import jax
import numpy as np

from sparknet_tpu import obs

PREFETCH_COUNT = 3  # reference: data_layers.hpp PREFETCH_COUNT

_log = logging.getLogger(__name__)


class PrefetchStall(RuntimeError):
    """The producer went silent past ``stall_timeout_s`` — the loop gets
    a diagnosable error instead of an unbounded ``queue.get`` hang."""


class Prefetcher:
    """Wraps a batch-producing callable in a background thread with a
    bounded queue (the InternalThread + BlockingQueue pair)."""

    def __init__(
        self,
        produce: Callable[[], Dict[str, np.ndarray]],
        depth: int = PREFETCH_COUNT,
        device_put: bool = True,
        sharding=None,
        stall_timeout_s: Optional[float] = None,
    ):
        self._produce = produce
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._stopped = False
        self._thread_exited: Optional[bool] = None
        self._error: Optional[BaseException] = None
        self._device_put = device_put
        self._sharding = sharding
        self._stall_timeout_s = stall_timeout_s
        # named so traced producer spans get a labeled Perfetto track
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="prefetch-producer"
        )
        self._thread.start()

    def qsize(self) -> int:
        """Batches currently buffered (the feed-queue-depth gauge)."""
        return self._q.qsize()

    def _put_politely(self, item) -> bool:
        """Bounded-queue put that keeps checking the stop flag — the
        producer must never block unkillably, not even on the final
        ``None`` sentinel."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            while not self._stop.is_set():
                batch = self._produce()
                if batch is None:
                    self._put_politely(None)
                    return
                if self._device_put:
                    batch = (
                        jax.device_put(batch, self._sharding)
                        if self._sharding is not None
                        else jax.device_put(batch)
                    )
                self._put_politely(batch)
        except BaseException as e:  # surfaced on next __next__
            self._error = e
            self._put_politely(None)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._stopped:
            # stop() drained the queue and nothing more is coming: the
            # stream is over NOW.  Without this, a stall_timeout_s
            # consumer would wait out the whole watchdog window and then
            # raise a misleading PrefetchStall on a deliberately-stopped
            # prefetcher.
            raise StopIteration
        if self._done:
            if self._error is not None:
                raise self._error
            raise StopIteration
        if self._stall_timeout_s is None:
            item = self._q.get()
        else:
            try:
                item = self._q.get(timeout=self._stall_timeout_s)
            except queue.Empty:
                msg = (
                    "prefetch producer delivered nothing for %.1fs "
                    "(thread %s)"
                    % (
                        self._stall_timeout_s,
                        "alive" if self._thread.is_alive() else "DEAD",
                    )
                )
                # telemetry: the stall counter ticks, the trace gets a
                # tagged instant, and /healthz goes unhealthy until the
                # next round completes (obs.report_healthy)
                tm = obs.training_metrics()
                if tm is not None:
                    tm.feed_stalls.inc()
                obs.instant("prefetch_stall", cat="fault", msg=msg)
                obs.report_unhealthy("prefetch_stall: " + msg)
                # a stall is a postmortem moment: dump the flight ring
                # (no-op unless --flight_recorder armed one)
                obs.flight.dump_if_active(
                    "prefetch_stall", extra={"msg": msg}
                )
                raise PrefetchStall(msg) from None
        if item is None:
            self._done = True  # sticky: keep raising after exhaustion/error
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item

    def stop(self, timeout: float = 5.0) -> bool:
        """Stop the producer and reap its thread.  Idempotent; returns
        True iff the thread is actually dead (repeated calls return the
        recorded outcome).  Drains the queue CONTINUOUSLY while joining —
        a single drain pass lets a producer blocked in ``put`` re-fill
        the queue and outlive the join."""
        if self._stopped:
            if self._thread_exited is False and not self._thread.is_alive():
                self._thread_exited = True  # late exit after first report
            return bool(self._thread_exited)
        self._stopped = True
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        self._thread_exited = not self._thread.is_alive()
        if not self._thread_exited:
            _log.warning(
                "Prefetcher.stop: producer thread still alive after "
                "%.1fs (blocked in produce()?)",
                timeout,
            )
        return self._thread_exited


def device_prefetch(iterator, depth: int = 2, sharding=None):
    """Prefetch an existing host iterator onto device: the idiomatic
    flax-style device prefetch for feeding jitted steps without stalls."""
    it = iter(iterator)

    def produce():
        try:
            return next(it)
        except StopIteration:
            return None

    return Prefetcher(produce, depth=depth, sharding=sharding)
