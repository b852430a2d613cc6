"""Framework-wide metrics: counters/gauges/histograms + Prometheus text.

Promoted from ``serve/metrics.py`` (round 6) to the shared observability
layer: training, the data plane, and serving all register series on ONE
``MetricsRegistry`` shape, and ``obs/exporter.py`` gives any run a
``/metrics`` endpoint.  ``serve.metrics`` remains a thin re-export so
nothing in the serving stack changed call sites.

Stdlib-only (no prometheus_client in the image): each metric is a small
lock-guarded accumulator, and ``MetricsRegistry.render()`` emits the
Prometheus text exposition format (``# HELP``/``# TYPE`` + samples).
Histograms keep cumulative buckets (the Prometheus ``le`` convention)
plus a bounded reservoir of recent observations so p50/p95/p99 can be
reported without a scrape-side quantile engine.

Labels: ``registry.histogram(name, labels=("phase",))`` returns a
FAMILY; ``family.labels("execute")`` returns the child instrument for
that label value (created once, cached).  Per-phase latency is one
histogram family — ``sparknet_phase_latency_seconds{phase="h2d"}`` —
not N ad-hoc instruments.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

# default latency buckets (seconds): 1 ms .. 30 s, roughly log-spaced
LATENCY_BUCKETS_S = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)


def _fmt(v: float) -> str:
    """Prometheus sample value: integers print bare, floats as repr."""
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class Counter:
    """Monotonic counter (``requests_total`` style)."""

    TYPE = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name, self.help = name, help
        self.labelstr = ""  # e.g. 'phase="execute"' for family children
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def samples(self) -> List[Tuple[str, float]]:
        name = (
            f"{self.name}{{{self.labelstr}}}" if self.labelstr else self.name
        )
        return [(name, self.value)]


class Gauge:
    """Set-to-current-value metric (``queue_depth`` style); ``fn`` makes
    it a callback gauge sampled at render time."""

    TYPE = "gauge"

    def __init__(self, name: str, help: str = "", fn=None):
        self.name, self.help = name, help
        self.labelstr = ""
        self._lock = threading.Lock()
        self._value = 0.0
        self._fn = fn

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        if self._fn is not None:
            return float(self._fn())
        with self._lock:
            return self._value

    def samples(self) -> List[Tuple[str, float]]:
        name = (
            f"{self.name}{{{self.labelstr}}}" if self.labelstr else self.name
        )
        return [(name, self.value)]


class Histogram:
    """Cumulative-bucket histogram + bounded reservoir for quantiles.

    The reservoir is a ring of the last ``reservoir`` observations —
    quantiles are over the recent window, which is what a serving
    dashboard wants (steady-state p99, not cold-start-polluted
    all-time p99).

    ``window_quantile`` narrows further to a TIME window: observations
    also enter a timestamped ring (same ``reservoir`` bound), and the
    quantile is taken over only the last ``window_s`` seconds — a long
    run's p99 stops diluting a fresh regression (the count-bounded
    reservoir of a month-old server still remembers last week).  The
    SLO latency evaluator (``obs/slo.py``) reads this view.
    """

    TYPE = "histogram"

    def __init__(
        self,
        name: str,
        help: str = "",
        buckets: Sequence[float] = LATENCY_BUCKETS_S,
        reservoir: int = 4096,
    ):
        self.name, self.help = name, help
        self.labelstr = ""
        self.buckets = tuple(sorted(buckets))
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.buckets) + 1)  # +inf tail
        self._sum = 0.0
        self._count = 0
        self._ring: List[float] = []
        self._ring_cap = int(reservoir)
        self._ring_pos = 0
        # (t_mono, v) pairs for the sliding TIME window; maxlen shares
        # the reservoir bound so memory stays fixed either way
        self._timed: deque = deque(maxlen=self._ring_cap)
        # sorted view of the ring, built lazily on the first quantile
        # read and kept until the next observation — a scrape reading
        # p50/p95/p99 sorts ONCE, not once per quantile
        self._sorted: Optional[List[float]] = None

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            i = 0
            for i, le in enumerate(self.buckets):
                if v <= le:
                    break
            else:
                i = len(self.buckets)
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if len(self._ring) < self._ring_cap:
                self._ring.append(v)
            else:
                self._ring[self._ring_pos] = v
                self._ring_pos = (self._ring_pos + 1) % self._ring_cap
            self._timed.append((time.monotonic(), v))
            self._sorted = None  # invalidate the cached sorted view

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def mean(self) -> float:
        with self._lock:
            return self._sum / self._count if self._count else 0.0

    def quantile(self, q: float) -> float:
        """q in [0, 1] over the recent-observation reservoir (0.0 when
        empty); nearest-rank on the sorted window.  The sort happens at
        most once per observation batch: consecutive quantile reads
        (p50/p95/p99 in one scrape) share the cached sorted view, which
        ``observe`` invalidates."""
        with self._lock:
            if self._sorted is None:
                self._sorted = sorted(self._ring)
            window = self._sorted  # replaced, never mutated, on observe
        if not window:
            return 0.0
        idx = min(len(window) - 1, max(0, int(q * len(window))))
        return window[idx]

    def window_quantile(
        self, q: float, window_s: float = 60.0,
        now: Optional[float] = None,
    ) -> float:
        """Nearest-rank quantile over only the observations of the
        last ``window_s`` seconds (0.0 when none) — the sliding-window
        view the SLO latency evaluator reads.  ``now`` overrides the
        clock for tests; observations older than the window are
        dropped from the timed ring on the way."""
        now = time.monotonic() if now is None else now
        cutoff = now - float(window_s)
        with self._lock:
            while self._timed and self._timed[0][0] < cutoff:
                self._timed.popleft()
            vals = sorted(v for _t, v in self._timed)
        if not vals:
            return 0.0
        idx = min(len(vals) - 1, max(0, int(q * len(vals))))
        return vals[idx]

    def window_count(self, window_s: float = 60.0,
                     now: Optional[float] = None) -> int:
        """Observations inside the sliding time window."""
        now = time.monotonic() if now is None else now
        cutoff = now - float(window_s)
        with self._lock:
            while self._timed and self._timed[0][0] < cutoff:
                self._timed.popleft()
            return len(self._timed)

    def samples(self) -> List[Tuple[str, float]]:
        with self._lock:
            counts, total, s = list(self._counts), self._count, self._sum
        pre = f"{self.labelstr}," if self.labelstr else ""
        suf = f"{{{self.labelstr}}}" if self.labelstr else ""
        out: List[Tuple[str, float]] = []
        cum = 0
        for le, c in zip(self.buckets, counts):
            cum += c
            out.append((f'{self.name}_bucket{{{pre}le="{_fmt(le)}"}}', cum))
        out.append((f'{self.name}_bucket{{{pre}le="+Inf"}}', total))
        out.append((f"{self.name}_sum{suf}", s))
        out.append((f"{self.name}_count{suf}", total))
        return out


class MetricFamily:
    """A labeled family of one instrument class: ``labels(v1, ...)``
    returns the child instrument for that label-value tuple (created on
    first use, cached).  Renders as ONE ``# TYPE`` block whose samples
    carry the label set — the Prometheus family convention."""

    def __init__(self, cls, name: str, help: str,
                 label_names: Sequence[str], fn=None, **kwargs):
        if not label_names:
            raise ValueError("a MetricFamily needs at least one label name")
        self._cls = cls
        # a callback family: each child samples fn(*its label values)
        self._fn = fn
        self.TYPE = cls.TYPE
        self.name, self.help = name, help
        self._label_names = tuple(label_names)
        self._kwargs = kwargs
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}

    def labels(self, *values):
        key = tuple(str(v) for v in values)
        if len(key) != len(self._label_names):
            raise ValueError(
                f"{self.name}: expected labels {self._label_names}, "
                f"got {len(key)} value(s)"
            )
        with self._lock:
            child = self._children.get(key)
            if child is None:
                kwargs = self._kwargs
                if self._fn is not None:
                    kwargs = {**kwargs, "fn": functools.partial(self._fn, *key)}
                child = self._cls(self.name, self.help, **kwargs)
                child.labelstr = ",".join(
                    f'{n}="{_escape_label(v)}"'
                    for n, v in zip(self._label_names, key)
                )
                self._children[key] = child
        return child

    def children(self) -> List[object]:
        with self._lock:
            return list(self._children.values())

    def samples(self) -> List[Tuple[str, float]]:
        out: List[Tuple[str, float]] = []
        for child in self.children():
            out.extend(child.samples())
        return out


class MetricsRegistry:
    """Holds the process's metrics and renders the /metrics payload."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}

    def register(self, metric):
        with self._lock:
            if metric.name in self._metrics:
                raise ValueError(f"duplicate metric {metric.name!r}")
            self._metrics[metric.name] = metric
        return metric

    def counter(
        self, name: str, help: str = "",
        labels: Optional[Sequence[str]] = None,
    ):
        if labels:
            return self.register(MetricFamily(Counter, name, help, labels))
        return self.register(Counter(name, help))

    def gauge(
        self, name: str, help: str = "", fn=None,
        labels: Optional[Sequence[str]] = None,
    ):
        if labels:
            # a labeled callback gauge hands its children's label values
            # to fn; the children exist once labels(...) has named them
            return self.register(
                MetricFamily(Gauge, name, help, labels, fn=fn)
            )
        return self.register(Gauge(name, help, fn=fn))

    def histogram(
        self, name: str, help: str = "",
        buckets: Sequence[float] = LATENCY_BUCKETS_S,
        labels: Optional[Sequence[str]] = None,
    ):
        if labels:
            return self.register(
                MetricFamily(Histogram, name, help, labels, buckets=buckets)
            )
        return self.register(Histogram(name, help, buckets=buckets))

    def get(self, name: str) -> Optional[object]:
        with self._lock:
            return self._metrics.get(name)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """One point-in-time read of every sample, keyed by the full
        sample name (labels inline — ``m{kind="x"}`` — so label
        families survive the round trip): ``{"counters": {...},
        "gauges": {...}}``.  Histogram samples (buckets/sum/count) are
        cumulative and fold under ``counters``.  This is the shipper's
        read side (``obs/ship.py``): two snapshots + ``counter_deltas``
        give the increment to push."""
        with self._lock:
            metrics = list(self._metrics.values())
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        for m in metrics:
            target = counters if m.TYPE in ("counter", "histogram") else gauges
            for name, value in m.samples():
                target[name] = float(value)
        return {"counters": counters, "gauges": gauges}

    def render(self) -> str:
        """Prometheus text exposition format, version 0.0.4."""
        with self._lock:
            metrics = list(self._metrics.values())
        lines: List[str] = []
        for m in metrics:
            if m.help:
                lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.TYPE}")
            for sample_name, value in m.samples():
                lines.append(f"{sample_name} {_fmt(value)}")
        return "\n".join(lines) + "\n"


def counter_deltas(
    prev: Dict[str, float], cur: Dict[str, float]
) -> Tuple[Dict[str, float], List[str]]:
    """Monotonic-counter deltas between two ``snapshot()["counters"]``
    reads, with Prometheus counter-reset semantics: a sample whose
    value DROPPED restarted from zero (process restart, fresh
    registry), so the new value IS the increment — history is never
    un-counted.  Returns ``(deltas, reset_sample_names)``; zero deltas
    are omitted (a quiet fleet ships empty payloads, not every name
    every push).  Samples present in ``prev`` but missing from ``cur``
    are ignored (a swapped registry's old families just stop
    shipping)."""
    deltas: Dict[str, float] = {}
    resets: List[str] = []
    for name, value in cur.items():
        before = prev.get(name, 0.0)
        if value < before:
            resets.append(name)
            d = value
        else:
            d = value - before
        if d:
            deltas[name] = d
    return deltas, resets
