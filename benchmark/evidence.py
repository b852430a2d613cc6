"""The traced run: a ``jax.profiler`` trace of the window, the program's own
spans kept in memory, and the harness's marks around its calls into the
program, all brought onto the trace's clock.  Per-layer readers
(``benchmark/reducers``) take their numbers from what ``collect`` returns."""

import contextlib
import shutil
import time

import numpy as np

from benchmark import xplane

# a trace of a few seconds of the steady window: traces are large (150,000
# operations a second for CaffeNet), what comes back from the chip is capped,
# and tracing slows the host
TRACE_SECONDS = 4.0
# the window opens at the third of the harness's first marks (its third round),
# when two rounds are in flight
SKIP = 2
# which host activity an idle gap goes to, most telling first: what the feed's
# producer thread was doing, then where the loop's own thread was
HOST_ORDER = ("h2d", "assemble", "feed.next_round", "trainer.round",
              "wait.losses", "drain")


class Marks:
    """The harness's spans around its calls into the program.  On, each is a
    ``TraceAnnotation`` (so it lands in the profiler's trace) and is kept with
    its ``perf_counter`` start (so the two clocks can be tied); off, nothing."""

    def __init__(self, on):
        self.on, self.starts = on, []

    @contextlib.contextmanager
    def __call__(self, name):
        if not self.on:
            yield
            return
        import jax

        self.starts.append((name, time.perf_counter()))
        with jax.profiler.TraceAnnotation(name):
            yield

    def names(self):
        return list(dict.fromkeys(name for name, _ in self.starts))


def traced(measure, trace_dir):
    """Run ``measure()`` under the profiler with the program's tracer
    installed; returns (its result, the program's spans as (name, t0, t1) in
    ``perf_counter`` seconds)."""
    import jax
    from sparknet_tpu import obs

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # no event per Python call: it slows the loop
    born = time.perf_counter()  # Tracer stamps spans relative to its birth
    tracer = obs.install_tracer(obs.Tracer())
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        result = measure()
    finally:
        jax.profiler.stop_trace()
        obs.uninstall_tracer()
    spans = [
        (e["name"], born + e["ts"] / 1e6, born + (e["ts"] + e["dur"]) / 1e6)
        for e in tracer.events() if e.get("ph") == "X"
    ]
    return result, spans


def collect(path, mark_names, window, peaks, mark_starts=(), program_spans=()):
    """Everything the readers need, or None when the trace holds no device or
    none of the harness's marks (a reader that finds nothing returns nothing).

    ``mark_names`` are the harness's marks in the order of their first use;
    the window runs from the start of the first name's third occurrence to the
    end of the last mark.  ``mark_starts`` ((name, perf_counter seconds), as
    ``Marks`` keeps them) tie ``program_spans`` to the trace's clock."""
    trace = xplane.Trace(path)
    first = trace.host.get(mark_names[0]) if mark_names else None
    if not trace.devices or first is None or len(first) <= SKIP:
        return None
    marks = {n: trace.host[n].intervals() for n in mark_names if n in trace.host}
    t0 = float(first.start[SKIP])
    t1 = float(max(iv[:, 1].max() for iv in marks.values()))
    spans = {}
    perf = np.array([t for n, t in mark_starts if n == mark_names[0]])
    if len(perf) == len(first):
        # the k-th annotation is the k-th mark: the median difference ties
        # perf_counter to the trace's clock
        offset = float(np.median(first.start - perf * 1e9))
        for name, s0, s1 in program_spans:
            spans.setdefault(name, []).append(
                (s0 * 1e9 + offset, s1 * 1e9 + offset))
        spans = {k: np.array(v, np.float64) for k, v in spans.items()}
    devices = sorted(trace.devices)[: window["workers"]]
    rounds = {}
    for d in devices:
        # the round is the program that takes most of the window; count its
        # executions inside it, whole and part
        by_name = {}
        mods = trace.devices[d]["modules"]
        for name, s, e in zip(mods.names, mods.start, mods.end):
            part = max(0.0, min(e, t1) - max(s, t0))
            if part > 0:
                total, count = by_name.get(name, (0.0, 0.0))
                by_name[name] = (total + part, count + part / (e - s))
        if not by_name:
            return None
        rounds[d] = max(by_name.values())[1]
    busy = {
        d: xplane.covered(trace.devices[d]["ops"].intervals(), (t0, t1)) / 1e9
        for d in devices
    }
    return {
        "trace": trace, "window_ns": (t0, t1), "devices": devices,
        "rounds_on_device": rounds, "busy_s": busy,
        "spans": spans, "marks": marks,
        "tau": window["tau"], "workers": window["workers"],
        "flops_per_round": window["flops_per_round_and_worker"],
        "peaks": peaks,
    }


def ops_of(ev, device):
    return ev["trace"].devices[device]["ops"]


def device_line(ev):
    """``busy_s`` and ``window_s`` of the result's ``device``: busy is the
    union of the operations' intervals, averaged over the chips used."""
    t0, t1 = ev["window_ns"]
    return {
        "busy_s": float(np.mean(list(ev["busy_s"].values()))),
        "window_s": (t1 - t0) / 1e9,
    }


def breakdown(ev):
    """The ten device operations with most time (self time, summed over the
    window on the first chip) and the device's idle time by what the host was
    doing."""
    ops = ops_of(ev, ev["devices"][0])
    known = {**ev["marks"], **ev["spans"]}
    host = {n: known[n] for n in HOST_ORDER if n in known}
    host.update({n: iv for n, iv in ev["marks"].items() if n not in host})
    idle = xplane.idle_by_host_activity(ops.intervals(), ev["window_ns"], host)
    totals = {}
    for hlo, seconds in xplane.self_seconds_by_name(ops, ev["window_ns"]).items():
        name = xplane.short_name(hlo)
        totals[name] = totals.get(name, 0.0) + seconds
    return {"device_ops": xplane.top(totals), "idle_gaps": xplane.top(idle)}
