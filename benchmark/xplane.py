"""From the profiler's ``.xplane.pb`` to intervals, and from intervals to
busy time, idle gaps, top operations and collective time.

Read with ``jax.profiler.ProfileData`` and nothing else.  What the trace of
this machine holds (looked at by hand, PERF.md "Where the time goes"): one
plane ``/device:TPU:<n>`` per chip whose line ``XLA Ops`` has one event per
executed HLO operation, named by its whole HLO line (a ``while`` holds the
operations of its body, so events nest), whose line ``XLA Modules`` has one
event per executed program (``jit_round_body(<id>)``) and whose line ``Async
XLA Ops`` spans each asynchronous operation from start to done; one plane
``/host:CPU`` with a line per thread, where ``jax.profiler.TraceAnnotation``
spans appear under their names.  All times are nanoseconds on one clock.
"""

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
HOST_PLANE = "/host:CPU"
# an operation's event is named by its whole HLO line,
# "%fusion.483 = (f32[96,3,11,11]{...}, ...) fusion(...), kind=kOutput, ..."
HLO = re.compile(r"^%?([\w.\-]+) = (?:\(.*?\)|\S+?) ([\w\-]+)\(")
SHAPE = re.compile(r" = \(?(\w+\[[\d,]*\])")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)


def short_name(hlo):
    """``fusion.483 f32[96,3,11,11]`` from the HLO line: the operation's name
    and the type of its (first) result, which is what tells fusions apart
    until the program names its layers in the trace."""
    m, shape = HLO.match(hlo), SHAPE.search(hlo)
    if not m:
        return hlo[:80]
    return m.group(1) + (" " + shape.group(1) if shape else "")


def opcode(hlo):
    m = HLO.match(hlo)
    return m.group(2) if m else ""


def newest_xplane(trace_dir):
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(paths, key=os.path.getmtime) if paths else None


class Events:
    """Events of one line: names (as the profiler prints them) and [start,
    end) in nanoseconds, by start and, among equal starts, the longer first."""

    def __init__(self, names=(), start=(), end=()):
        start = np.asarray(start, np.float64)
        end = np.asarray(end, np.float64)
        order = np.lexsort((-end, start))
        self.names = [names[i] for i in order]
        self.start, self.end = start[order], end[order]

    def __len__(self):
        return len(self.names)

    def select(self, keep):
        keep = np.asarray(keep, bool)
        return Events(
            [n for n, k in zip(self.names, keep) if k],
            self.start[keep], self.end[keep],
        )

    def intervals(self):
        return np.stack([self.start, self.end], axis=1)


def _events(events):
    rows = [(ev.name, ev.start_ns, ev.duration_ns) for ev in events]
    names, start, dur = zip(*rows) if rows else ((), (), ())
    start = np.asarray(start, np.float64)
    return Events(names, start, start + np.asarray(dur, np.float64))


class Trace:
    """``devices[n]`` has ``ops``, ``modules`` and ``async`` (the spans from an
    asynchronous operation's start to its done); ``host[name]`` is every host
    event of that name, whatever its thread."""

    def __init__(self, path):
        from jax.profiler import ProfileData

        self.devices, host = {}, []
        for plane in ProfileData.from_file(path).planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE in lines:
                    self.devices[int(m.group(1))] = {
                        key: _events(lines[name].events if name in lines else [])
                        for key, name in (("ops", OPS_LINE),
                                          ("modules", MODULES_LINE),
                                          ("async", ASYNC_LINE))
                    }
            elif plane.name == HOST_PLANE:
                host = _events(ev for ln in plane.lines for ev in ln.events)
        names = np.array(host.names if host else [], object)
        self.host = {
            name: host.select(names == name) for name in set(names.tolist())
        }


# -- interval arithmetic -------------------------------------------------
def merged(intervals, window=None):
    """The union of ``intervals`` (n, 2) as sorted disjoint intervals, cut to
    ``window`` = (t0, t1)."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    if window is not None:
        iv = np.clip(iv, window[0], window[1])
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0])]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    last = np.concatenate([first[1:], [True]])
    return np.stack([iv[first, 0], reach[last]], axis=1)


def covered(intervals, window=None):
    """Nanoseconds in which at least one of ``intervals`` is open."""
    m = merged(intervals, window)
    return float(np.sum(m[:, 1] - m[:, 0]))


def gaps(intervals, window):
    """The parts of ``window`` no interval covers, as (n, 2)."""
    m = merged(intervals, window)
    edges = np.concatenate([[window[0]], m.reshape(-1), [window[1]]])
    g = edges.reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def intersect(a, b):
    """The parts both ``a`` and ``b`` cover."""
    a, b = merged(a), merged(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, np.float64).reshape(-1, 2)


def minus(a, b):
    """``a`` without the parts ``b`` covers."""
    a = merged(a)
    if not len(a):
        return a
    return intersect(a, gaps(b, (a[0, 0], a[-1, 1])))


def leaf_mask(ops):
    """Which events hold no other event (``ops`` is sorted by start, longer
    first, so a holder is followed at once by something that starts inside)."""
    inside = ops.start[1:] < ops.end[:-1]
    return ~np.concatenate([inside, [False]])


def self_seconds_by_name(ops, window):
    """Seconds per operation name inside ``window``, each nanosecond counted
    once: an operation that holds others (a ``while`` and its body) gets only
    the time in which none of its children runs."""
    order = np.argsort(ops.start, kind="stable")
    totals, stack = {}, []  # stack of [name, end, cursor]

    def credit(name, t0, t1):
        t0, t1 = max(t0, window[0]), min(t1, window[1])
        if t1 > t0:
            totals[name] = totals.get(name, 0.0) + (t1 - t0) / 1e9

    def close_until(t):
        while stack and stack[-1][1] <= t:
            name, end, cursor = stack.pop()
            credit(name, cursor, end)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for i in order:
        s, e = ops.start[i], ops.end[i]
        close_until(s)
        if stack:
            credit(stack[-1][0], stack[-1][2], s)
            stack[-1][2] = max(stack[-1][2], s)
        stack.append([ops.names[i], e, s])
    close_until(np.inf)
    return totals


def top(totals, k=10):
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, seconds] for name, seconds in rows]


def idle_by_host_activity(device_ops, window, host_spans, short_ns=50e3):
    """Seconds of device idle time inside ``window`` by what the host was
    doing: each gap of at least ``short_ns`` goes to the first of
    ``host_spans`` (an ordered ``{name: (n, 2) intervals}``, most telling
    first) that is open in it, piece by piece, and to ``other`` where none is;
    shorter gaps are the spaces between one program's operations."""
    out = {}
    g = gaps(device_ops, window)
    long_gaps = g[(g[:, 1] - g[:, 0]) >= short_ns]
    short = float(np.sum(g[:, 1] - g[:, 0])) - float(
        np.sum(long_gaps[:, 1] - long_gaps[:, 0])
    )
    left = long_gaps
    for name, spans in host_spans.items():
        if not len(left):
            break
        rest = minus(left, spans)
        took = covered(left) - covered(rest)
        if took > 0:
            out[name] = took / 1e9
        left = rest
    if len(left):
        out["other"] = covered(left) / 1e9
    if short > 0:
        out[f"between ops (<{short_ns / 1e3:.0f} us)"] = short / 1e9
    return out
