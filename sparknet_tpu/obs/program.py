"""Every program the trainer builds keeps its own account: what it cost
to build and what it holds on the chip.

``Program(name, jax.jit(...))`` is the one place a training program is
built.  Called with arguments it has not seen, it lowers and compiles
ahead of the call (``build``) and writes ONE record::

    {"program": "round", "trace_lower_s": ..., "compile_s": ...,
     "cache": "hit" | "miss" | "off",
     "temp_bytes", "argument_bytes", "output_bytes", "alias_bytes",
     "code_bytes": ...,        # Compiled.memory_analysis(), per device
     "shapes": ("uint8[1,10,256,3,256,256]@PartitionSpec('dp',)", ...)}

then calls the ``jax.jit`` it wraps, which finds the program compiled
(this jax keeps the lowering and its executable for the arguments it was
lowered with), so a program compiles once and the call a round makes is
the one it always made.  A second record under one name IS a recompile,
and the two records' ``shapes`` say why.

The records and the build-time memory marks are kept in memory whether
or not a sink is installed (``obs.programs()``, ``obs.memory_marks()``):
they are written once per program and signature, never per round.  Where
a sink is installed the same build is a ``build`` span (cat ``build``)
with children ``trace_lower`` and ``compile``, and every mark a
``memory`` instant (cat ``memory``); with training metrics on, the
``sparknet_program_*`` families (ARCHITECTURE.md "Telemetry reference").

A memory mark is ``device.memory_stats()`` of the fullest of the
program's addressable devices: no sync, no walk over live arrays.  Where
the backend reports nothing (the CPU) it carries nulls.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from sparknet_tpu.obs import trace

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# memory_stats() keys behind each kind of a mark and of the gauge
# sparknet_device_memory_bytes{kind}
MEMORY_KINDS = {
    "in_use": "bytes_in_use",
    "peak_in_use": "peak_bytes_in_use",
    "reserved": "bytes_reserved",
    "peak_reserved": "peak_bytes_reserved",
    "limit": "bytes_limit",
}
# Compiled.memory_analysis() fields behind each kind of a record and of
# the gauge sparknet_program_bytes{program,kind}
BYTE_KINDS = {
    "temp": "temp_size_in_bytes",
    "argument": "argument_size_in_bytes",
    "output": "output_size_in_bytes",
    "alias": "alias_size_in_bytes",
    "code": "generated_code_size_in_bytes",
}

_lock = threading.Lock()
# bounded: a process that builds trainers for ever (a test session, a
# bench) must not grow these monotonically
_records: "deque" = deque(maxlen=1024)
_marks: "deque" = deque(maxlen=1024)
# the build in flight on THIS thread counts jax's cache hits: a compile
# fires its events on the thread that asked for it, so another thread's
# compiles (a check compiled beside the round) are not this build's
_building = threading.local()
_listening = False
# the process's TrainingMetrics once enabled (obs.enable_training_metrics)
_metrics = None


def set_metrics(tm) -> None:
    global _metrics
    _metrics = tm


def programs() -> list:
    """Every build record of this process, newest last."""
    with _lock:
        return list(_records)


def memory_marks() -> list:
    """The build-time memory marks (``init_state:enter``, ``init_state``,
    ``built:<program>``), newest last; ``t_s`` is ``perf_counter``."""
    with _lock:
        return list(_marks)


def _reset_for_tests() -> None:
    with _lock:
        _records.clear()
        _marks.clear()


def _stats(device) -> dict:
    return device.memory_stats() or {}


def device_memory(devices=None) -> dict:
    """``MEMORY_KINDS`` of the fullest of ``devices`` (default: this
    process's), fullest by the peak the benchmark reports: live buffers'
    peak plus the reserved peak.  None where the backend reports nothing."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    fullest = max(
        (_stats(d) for d in devices),
        key=lambda s: s.get("peak_bytes_in_use", 0)
        + s.get("peak_bytes_reserved", 0),
    )
    return {kind: fullest.get(key) for kind, key in MEMORY_KINDS.items()}


def mark_memory(at: str, devices=None, keep: bool = True) -> None:
    """One ``memory`` instant; ``keep`` also files it beside the records."""
    reading = device_memory(devices)
    if keep:
        with _lock:
            _marks.append({"at": at, "t_s": time.perf_counter(), **reading})
    trace.instant("memory", cat="memory", at=at, **reading)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT and getattr(_building, "on", False):
        _building.hits += 1


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_listener(_on_event)


def _cache_verdict(hits: int) -> str:
    import jax

    if hits:
        return "hit"
    on = jax.config.jax_enable_compilation_cache
    return "miss" if on and jax.config.jax_compilation_cache_dir else "off"


def _leaf_key(x):
    shape = getattr(x, "shape", None)
    if shape is None:
        return x  # a static argument: the value itself
    return shape, x.dtype, getattr(x, "sharding", None)


def _describe(x) -> str:
    shape = getattr(x, "shape", None)
    if shape is None:
        return repr(x)
    sharding = getattr(x, "sharding", None)
    where = "host" if sharding is None else getattr(sharding, "spec", None)
    if where is None:
        where = type(sharding).__name__
    return f"{x.dtype}[{','.join(map(str, shape))}]@{where}"


class Program:
    """A jitted program that accounts for its builds.  ``watch`` names
    the positional arguments a call is keyed on (a round: its batches;
    the state's shapes are the net's, fixed when the trainer is built),
    so that a round pays for two leaves, not for the state's hundreds;
    None keys on them all.  ``devices``: whose memory the marks read
    (default: this process's).  Everything else (``lower``, ``trace``,
    ``_cache_size``) is the ``jax.jit``'s own."""

    def __init__(self, name: str, jitted, watch=None, devices=None):
        self.name = name
        self.jit = jitted
        self.watch = watch
        self.devices = devices
        self._seen = set()
        import jax  # here, so that importing obs stays free of jax

        self._leaves = jax.tree_util.tree_leaves

    def __getattr__(self, attr):
        if attr == "jit":  # not built yet (a copy in the making)
            raise AttributeError(attr)
        return getattr(self.jit, attr)

    def _watched(self, args):
        if self.watch is None:
            return args
        return [args[i] for i in self.watch]

    def _key(self, args):
        return tuple([_leaf_key(x) for x in self._leaves(self._watched(args))])

    def __call__(self, *args):
        if self._key(args) not in self._seen:
            self.build(*args)
        return self.jit(*args)

    def build(self, *args):
        """Lower and compile for arguments like these, running nothing and
        donating nothing, and write the record; returns the ``Compiled``.
        For a caller's thread too, while its data loads or its other
        programs compile (jax's compile releases the interpreter)."""
        _listen()
        name = self.name
        shapes = tuple(
            _describe(x) for x in self._leaves(self._watched(args))
        )
        with trace.span("build", cat="build", program=name) as sp:
            t0 = time.perf_counter()
            with trace.span("trace_lower", cat="build", program=name):
                lowered = self.jit.lower(*args)
            t1 = time.perf_counter()
            _building.on, _building.hits = True, 0
            try:
                with trace.span("compile", cat="build", program=name):
                    compiled = lowered.compile()
            finally:
                _building.on = False
            t2 = time.perf_counter()
            analysis = compiled.memory_analysis()
            held = {
                "cache": _cache_verdict(_building.hits),
                **{
                    kind + "_bytes": getattr(analysis, field, None)
                    for kind, field in BYTE_KINDS.items()
                },
            }
            if hasattr(sp, "args"):  # not the shared no-op span
                # known only now; the sinks read a span's arguments when
                # it closes (the profiler's annotation keeps ``program``)
                sp.args.update(held)
        record = {
            "program": name, "trace_lower_s": t1 - t0, "compile_s": t2 - t1,
            **held, "shapes": shapes,
        }
        with _lock:
            _records.append(record)
        self._seen.add(self._key(args))
        _note_metrics(record)
        mark_memory("built:" + name, self.devices)
        return compiled


def _note_metrics(record: dict) -> None:
    tm = _metrics
    if tm is None:
        return
    name = record["program"]
    tm.program_builds.labels(name, record["cache"]).inc()
    for stage in ("trace_lower", "compile"):
        tm.program_build_seconds.labels(name, stage).set(
            record[stage + "_s"]
        )
    for kind in BYTE_KINDS:
        tm.program_bytes.labels(name, kind).set(
            record[kind + "_bytes"] or 0
        )


def memory_gauge(kind: str) -> float:
    """``sparknet_device_memory_bytes{kind}`` at scrape; guarded — a
    backend that reports nothing (or a runtime mid-teardown) reads 0
    rather than poisoning a scrape."""
    try:
        return float(device_memory()[kind] or 0)
    except Exception:
        return 0.0
