"""Round-span tracing: Chrome trace-event JSON + a structured JSONL log.

A ``Tracer`` collects *complete* events (``ph: "X"``) from ``span()``
context managers and *instant* events (``ph: "i"``) from ``instant()``,
each stamped with the real OS thread id — so when the pipelined
``RoundFeed`` assembles round r+1 on its producer thread while round r
executes on the consumer, the two span tracks interleave **visually**
in Perfetto (chrome://tracing loads the same file).  Thread-name
metadata events label each track ("roundfeed-producer" vs
"MainThread").

Alongside the Chrome JSON (written once, at ``save()``), every event
can stream to a JSONL run log as it completes — one self-contained JSON
object per line, crash-durable (flushed per line), greppable, and
parseable by ``tools/parse_log.py`` (the structured successor to the
flat ``training_log_<ts>.txt``).

Cost discipline: the module-level ``span()``/``instant()`` fast path is
a shared no-op when no tracer is installed (one global read), so
instrumented hot paths pay ~nothing by default; with tracing on, a span
is two ``perf_counter`` reads and one list append under a lock.

On the profiler's clock: a span that any sink is installed for also
opens a ``jax.profiler.TraceAnnotation(name, **args)``, so while
``jax.profiler.trace`` runs over a job its ``assemble`` / ``h2d`` /
``average`` / ``execute`` sit on the host plane of the same
``.xplane.pb`` as the device's operations, on the thread that ran them
(ARCHITECTURE.md "Telemetry reference").  Outside a profiler trace the
annotation is a no-op; with no sink installed none is made.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional


class Tracer:
    """Collects trace events; thread-safe; bounded (``max_events``
    guards a runaway run — the newest events win a dropped-count note)."""

    def __init__(
        self,
        jsonl_path: Optional[str] = None,
        max_events: int = 500_000,
    ):
        self._t0 = time.perf_counter()
        self._epoch = time.time()
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._dropped = 0
        self._max_events = int(max_events)
        self._thread_names: Dict[int, str] = {}
        self._pid = os.getpid()
        # truncate: one Tracer = one run's log, exactly like save()
        # rewrites the Chrome JSON — re-tracing to the same --trace_out
        # must not interleave two runs' records in one .jsonl
        self._jsonl = open(jsonl_path, "w") if jsonl_path else None
        self.jsonl_path = jsonl_path

    # ------------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _note_thread(self, tid: int) -> None:
        # called under self._lock
        if tid not in self._thread_names:
            name = threading.current_thread().name
            self._thread_names[tid] = name
            self._events.append({
                "name": "thread_name", "ph": "M", "pid": self._pid,
                "tid": tid, "args": {"name": name},
            })

    def _emit(self, ev: dict, jsonl_rec: dict) -> None:
        with self._lock:
            if len(self._events) >= self._max_events:
                self._dropped += 1
            else:
                self._note_thread(ev["tid"])
                self._events.append(ev)
            f = self._jsonl
        if f is not None:
            # one self-contained object per line, flushed — the run log
            # survives a crash up to the last completed event
            try:
                f.write(json.dumps(jsonl_rec) + "\n")
                f.flush()
            except ValueError:  # closed mid-shutdown: drop, don't die
                pass

    # ------------------------------------------------------------------
    def complete(self, name: str, cat: str, t_start_us: float,
                 dur_us: float, args: Optional[dict] = None) -> None:
        """Record a finished span (chrome ``ph: "X"``)."""
        tid = threading.get_ident()
        ev = {
            "name": name, "cat": cat, "ph": "X",
            "ts": t_start_us, "dur": dur_us,
            "pid": self._pid, "tid": tid,
        }
        if args:
            ev["args"] = args
        rec = {
            "kind": "span", "name": name, "cat": cat,
            "ts_s": round(t_start_us / 1e6, 6),
            "dur_ms": round(dur_us / 1e3, 4),
            "thread": threading.current_thread().name,
        }
        if args:
            rec["args"] = args
        self._emit(ev, rec)

    def instant(self, name: str, cat: str = "event",
                args: Optional[dict] = None) -> None:
        """Record a point event (chrome ``ph: "i"``, thread-scoped) —
        fault injections, retries, recoveries."""
        ts = self._now_us()
        tid = threading.get_ident()
        ev = {
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": ts, "pid": self._pid, "tid": tid,
        }
        if args:
            ev["args"] = args
        rec = {
            "kind": "instant", "name": name, "cat": cat,
            "ts_s": round(ts / 1e6, 6),
            "thread": threading.current_thread().name,
        }
        if args:
            rec["args"] = args
        self._emit(ev, rec)

    # ------------------------------------------------------------------
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def save(self, path: str) -> str:
        """Write the Chrome trace-event JSON (object form, Perfetto- and
        chrome://tracing-loadable)."""
        with self._lock:
            doc = {
                "traceEvents": list(self._events),
                "displayTimeUnit": "ms",
                "otherData": {
                    "producer": "sparknet_tpu.obs",
                    "epoch_unix_s": self._epoch,
                    "dropped_events": self._dropped,
                },
            }
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def close(self) -> None:
        with self._lock:
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None


# ----------------------------------------------------------------------
# module-level fast path: install_tracer() flips span()/instant() from
# shared no-ops to recording — instrumented code never holds a Tracer

_tracer: Optional[Tracer] = None
# observes (name, dur_s) of phase-cat spans into the metrics layer when
# training metrics are enabled (set by obs/__init__; None = off)
_phase_observer = None
# observes EVERY completed span with its interval and thread —
# fn(name, cat, t0_s, t1_s, thread_name, args) where t0/t1 are
# perf_counter values (comparable across threads in one process).  The
# RoundProfiler (obs/profile.py) installs itself here to fold the span
# stream into per-round phase/overlap accounting; None = off
_span_observer = None
# the installed FlightRecorder's event ring (obs/flight.py; None = off)
# — spans/instants feed it even when no Tracer is recording
_flight = None
# the installed fleet Shipper (obs/ship.py; None = off) — spans/
# instants feed its bounded buffer the same way, stamped with wall
# time so the collector can clock-align N hosts' records
_ship = None


def install_tracer(tracer: Tracer) -> Tracer:
    global _tracer
    _tracer = tracer
    return tracer


def uninstall_tracer() -> Optional[Tracer]:
    global _tracer
    t, _tracer = _tracer, None
    return t


def get_tracer() -> Optional[Tracer]:
    return _tracer


def set_phase_observer(fn) -> None:
    global _phase_observer
    _phase_observer = fn


def set_span_observer(fn) -> None:
    """Point span() completions at a profiler (obs/profile.py owns the
    install/uninstall lifecycle).  ``fn(name, cat, t0_s, t1_s,
    thread_name, args)`` runs on the thread that closed the span."""
    global _span_observer
    _span_observer = fn


def set_flight(recorder) -> None:
    """Point span()/instant() at a flight-recorder ring (obs/flight.py
    owns the install/uninstall lifecycle)."""
    global _flight
    _flight = recorder


def set_ship(shipper) -> None:
    """Point span()/instant() at a fleet shipper's buffer (obs/ship.py;
    the ObsRun owns the install/uninstall lifecycle)."""
    global _ship
    _ship = shipper


class _NullSpan:
    """The disabled-path span: one shared instance, no state."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "_t0", "_annotation")

    def __init__(self, name: str, cat: str, args: Optional[dict]):
        # imported where a span is made, so that importing obs stays free
        # of jax and the off path (span() -> _NULL_SPAN) never touches it
        from jax.profiler import TraceAnnotation

        self.name, self.cat, self.args = name, cat, args
        # the same span on the profiler's clock: while jax.profiler traces,
        # a host event ``name`` (its args as stats) on this thread's line
        # of the .xplane.pb, beside the device's; otherwise a no-op
        self._annotation = TraceAnnotation(name, **(args or {}))

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        dur_s = t1 - self._t0
        t = _tracer
        if t is not None:
            t.complete(
                self.name, self.cat,
                (self._t0 - t._t0) * 1e6, dur_s * 1e6, self.args,
            )
        f = _flight
        if f is not None:
            rec = {
                "kind": "span", "name": self.name, "cat": self.cat,
                "t_s": round(time.time(), 3),
                "dur_ms": round(dur_s * 1e3, 4),
                "thread": threading.current_thread().name,
            }
            if self.args:
                rec["args"] = self.args
            f.record_event(rec)
        sh = _ship
        if sh is not None:
            # full-resolution wall START time: the collector subtracts
            # the per-host clock offset from t_s when merging, so the
            # span lands on the fleet timeline where it began
            rec = {
                "kind": "span", "name": self.name, "cat": self.cat,
                "t_s": time.time() - dur_s,
                "dur_ms": round(dur_s * 1e3, 4),
                "thread": threading.current_thread().name,
            }
            if self.args:
                rec["args"] = self.args
            sh.record_event(rec)
        obs = _phase_observer
        if obs is not None and self.cat == "phase":
            obs(self.name, dur_s)
        so = _span_observer
        if so is not None:
            so(
                self.name, self.cat, self._t0, t1,
                threading.current_thread().name, self.args,
            )
        return False


def span(name: str, cat: str = "phase", **args):
    """Context manager timing one phase of work.  ``cat="phase"`` spans
    also feed the per-phase latency histogram when training metrics are
    enabled.  Near-free when tracing, metrics AND flight recording are
    off."""
    if (
        _tracer is None
        and _phase_observer is None
        and _flight is None
        and _span_observer is None
        and _ship is None
    ):
        return _NULL_SPAN
    return _Span(name, cat, args or None)


def recording() -> bool:
    """Whether a sink that keeps instants is installed: what a caller asks
    before it READS something only to report it (a per-round memory mark)."""
    return _tracer is not None or _flight is not None or _ship is not None


def instant(name: str, cat: str = "event", **args) -> None:
    """Record a tagged point event (no-op when tracing and flight
    recording are off)."""
    t = _tracer
    if t is not None:
        t.instant(name, cat, args or None)
    f = _flight
    if f is not None:
        rec = {
            "kind": "instant", "name": name, "cat": cat,
            "t_s": round(time.time(), 3),
            "thread": threading.current_thread().name,
        }
        if args:
            rec["args"] = args
        f.record_event(rec)
    sh = _ship
    if sh is not None:
        rec = {
            "kind": "instant", "name": name, "cat": cat,
            "t_s": time.time(),
            "thread": threading.current_thread().name,
        }
        if args:
            rec["args"] = args
        sh.record_event(rec)


def jsonl_path_for(trace_out: str) -> str:
    """``run.trace.json`` -> ``run.trace.jsonl`` (the structured run
    log that rides along with every Chrome trace)."""
    if trace_out.endswith(".json"):
        return trace_out[: -len(".json")] + ".jsonl"
    return trace_out + ".jsonl"
