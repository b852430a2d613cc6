"""Device time by the scopes the round program names (PR 24).

The program opens ``jax.named_scope``s inside its round (ARCHITECTURE.md
"Telemetry reference"): ``transform``, one ``<Type>:<name>`` per layer,
``update`` and ``average``; autodiff writes a layer's backward operations as
``transpose(jvp(<Type>:<name>))``.  The compiler keeps each operation's name
stack as its ``op_name``.  Where that lands in this machine's trace (looked
at by hand, PERF.md section 6, PR 24): not in the event's name (the HLO line
carries no ``metadata={...}``) and not among the event's own stats (three of
timing), but in the stat ``tf_op`` of the event's *metadata*
(``XEventMetadata.stats``), as ``<op_name>:<HLO category or nothing>``.
``jax.profiler.ProfileData`` shows an event's own stats only and
``xplane.Trace`` keeps names and times, so this module reads those few
fields from the run's ``.xplane.pb`` itself, by the wire format of ``XSpace``
(nothing to import; the lines, nearly all of the file, are stepped over).

The reduction works per whole execution of the round program: every
nanosecond of the ``XLA Ops`` line inside one ``XLA Modules`` event goes to
one operation (self time, ``xplane.self_seconds_by_name``'s rule) and every
operation to one (phase, layer type, layer name).  An execution whose events
cover less than ``MIN_COVERAGE`` of its interval is dropped and counted: a
trace that lost events must not pass as a faster step.  A fusion carries one
``op_name`` (its root's, as a rule), so a ReLU fused into the convolution
before it is the convolution's time: PERF.md names the fusions that span two
scopes.
"""

import functools
import os
import re

import numpy as np

from benchmark import files, xplane

PHASE_SCOPES = ("transform", "update", "average")
FORWARD, BACKWARD, UNSCOPED = "forward", "backward", "unscoped"
OP_NAME_STAT = "tf_op"
MIN_COVERAGE = 0.99
# jvp(Convolution:conv1), transpose(jvp(Convolution:conv1)): what a
# transformation wraps around a scope's name
WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
LAYER_SCOPE = re.compile(r"^([A-Za-z_]\w*):(.+)$")
# "<op_name>:<category>", the category often empty
STAT_TAIL = re.compile(r":[\w.\- ]*$")

_cache = {}  # (path, window, devices) -> what ``table`` returned


def log(message):
    print(f"[bench] {message}", flush=True)


# -- the name stacks, from the file ---------------------------------------
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of the protobuf message in ``buf[start:end]``: an
    int for a varint, (start, end) for a length-delimited field, None for a
    fixed-width one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in {number}")
        yield number, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(path):
    """{chip: {event name: op_name}} of the operations of every plane
    ``/device:TPU:<chip>`` of the file.

    ``XSpace.planes`` = 1; ``XPlane.name`` = 2, ``.event_metadata`` = 4 and
    ``.stat_metadata`` = 5 (maps: key = 1, value = 2); ``XEventMetadata.name``
    = 2, ``.stats`` = 5; ``XStatMetadata.id`` = 1, ``.name`` = 2;
    ``XStat.metadata_id`` = 1, ``.str_value`` = 5, ``.ref_value`` = 7 (the id
    of a stat metadata whose name is the string).  An operation without the
    stat is left out."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        parts = list(_fields(buf, *plane))
        chip = next((xplane.DEVICE_PLANE.match(_text(buf, v))
                     for n, v in parts if n == 2), None)
        if not chip:
            continue
        values = lambda field: (
            dict(_fields(buf, *entry)).get(2) for n, entry in parts
            if n == field)
        stat_names = {}
        for value in values(5):
            meta = dict(_fields(buf, *value)) if value else {}
            if 1 in meta and 2 in meta:
                stat_names[meta[1]] = _text(buf, meta[2])
        wanted = {i for i, name in stat_names.items() if name == OP_NAME_STAT}
        names = out.setdefault(int(chip.group(1)), {})
        for value in values(4):
            name = op_name = None
            for m, v in _fields(buf, *value) if value else ():
                if m == 2:
                    name = _text(buf, v)
                elif m == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in wanted:
                        if 5 in stat:
                            op_name = _text(buf, stat[5])
                        elif 7 in stat:
                            op_name = stat_names.get(stat[7])
            if name is not None and op_name:
                names.setdefault(name, STAT_TAIL.sub("", op_name))
    return out


# -- from a name stack to (phase, type, name) -----------------------------
@functools.lru_cache(maxsize=None)
def classify(op_name):
    """(phase, layer type, layer name) of one operation: the outermost scope
    of the program on its name stack decides.  ``transform``, ``update`` and
    ``average`` have no layer; a layer scope is ``forward`` outside
    ``transpose(`` and ``backward`` inside it; anything else is ``unscoped``.
    Of an ``a;b`` (operations the compiler merged) the first counts."""
    for component in op_name.split(";")[0].split("/"):
        wrappers, inner = [], component
        while True:
            m = WRAPPER.match(inner)
            if not m:
                break
            wrappers.append(m.group(1))
            inner = m.group(2)
        if "jit" in wrappers or "pjit" in wrappers:
            continue  # a function's name, not a scope
        if inner in PHASE_SCOPES:
            return inner, None, None
        m = LAYER_SCOPE.match(inner)
        if m:
            phase = BACKWARD if "transpose" in wrappers else FORWARD
            return phase, m.group(1), m.group(2)
    return UNSCOPED, None, None


# -- per whole execution of the round program -----------------------------
def round_program(mods, window):
    """The program that takes most of ``window`` (``evidence.collect`` counts
    the same one's executions)."""
    totals = {}
    for name, s, e in zip(mods.names, mods.start, mods.end):
        part = min(e, window[1]) - max(s, window[0])
        if part > 0:
            totals[name] = totals.get(name, 0.0) + part
    return max(totals, key=totals.get) if totals else None


def executions(trace, device, window):
    """One row per execution of the round program that lies wholly inside
    ``window`` on ``device``: ``start``, ``end``, ``ops`` {event name: self
    nanoseconds}, ``coverage`` (the share of its interval its operations'
    events cover) and ``gap_before`` (ns since the end of the program before
    it, whichever that was; None for the trace's first)."""
    ops, mods = trace.devices[device]["ops"], trace.devices[device]["modules"]
    program = round_program(mods, window)
    rows, reach = [], None
    for name, s, e in zip(mods.names, mods.start, mods.end):
        if name == program and s >= window[0] and e <= window[1]:
            rows.append({
                "start": float(s), "end": float(e), "ops": {},
                "gap_before": None if reach is None else float(s - reach)})
        reach = e if reach is None else max(reach, e)
    if not rows or not len(ops):
        return rows
    starts = np.array([r["start"] for r in rows])
    ends = np.array([r["end"] for r in rows])
    # an operation belongs to the execution it starts in
    at = np.searchsorted(starts, ops.start, side="right") - 1
    inside = np.nonzero((at >= 0) & (ops.start < ends[at.clip(0)]))[0]
    labelled = xplane.Events(
        [(int(at[i]), ops.names[i]) for i in inside],
        ops.start[inside], ops.end[inside])
    for (i, hlo), seconds in xplane.self_seconds_by_name(
            labelled, (starts[0], ends[-1])).items():
        rows[i]["ops"][hlo] = seconds * 1e9
    for row in rows:
        row["coverage"] = float(
            sum(row["ops"].values()) / (row["end"] - row["start"]))
    return rows


def by_scope(row, stacks):
    """{(phase, type, name): nanoseconds} of one execution."""
    out = {}
    for hlo, ns in row["ops"].items():
        key = classify(stacks.get(hlo, ""))
        out[key] = out.get(key, 0.0) + ns
    return out


def trace_path(ev):
    """The run's own trace: ``evidence.traced`` empties the cell's directory
    before it starts the profiler, so the newest file under ``.bench_out`` is
    this run's.  A test names a recorded file under ``xplane_path``."""
    return ev.get("xplane_path") or xplane.newest_xplane(
        os.path.join(files.ROOT, ".bench_out", "*", "trace"))


def table(ev):
    """{chip: [{(phase, type, name): nanoseconds} of each kept execution]} for
    the chips of the window, or None where there is nothing to read by scope;
    says which it was on a ``[bench]`` line and prints the tables, once a
    process for each trace and window."""
    path = trace_path(ev)
    key = (path, ev["window_ns"], tuple(ev["devices"]))
    if key not in _cache:
        try:
            _cache[key] = _table(ev, path)
        except Exception as exc:  # a reader never fails the run
            log(f"by scope: nothing read, {type(exc).__name__}: {exc}")
            _cache[key] = None
    return _cache[key]


def _table(ev, path):
    if not path:
        log("by scope: nothing read, no .xplane.pb to take the op_names from")
        return None
    stacks = op_names(path)
    kept, tables, notes = {}, {}, []
    for chip in ev["devices"]:
        rows = executions(ev["trace"], chip, ev["window_ns"])
        kept[chip] = [r for r in rows if r["coverage"] >= MIN_COVERAGE]
        rest = [round(r["coverage"], 4) for r in rows
                if r["coverage"] < MIN_COVERAGE]
        notes.append(f"chip {chip}: {len(kept[chip])} of {len(rows)}"
                     + (f", the rest cover {rest}" if rest else ""))
        if chip == ev["devices"][0]:
            print_executions(ev, chip, rows)
        tables[chip] = [by_scope(r, stacks.get(chip, {})) for r in kept[chip]]
    log(f"by scope: whole executions of the round program kept (events cover "
        f">= {MIN_COVERAGE:.0%} of the interval): " + "; ".join(notes))
    if not any(tables.values()):
        log("by scope: nothing read, no whole execution of the round program "
            "in the window is covered by its events")
        return None
    if all(key[0] == UNSCOPED for t in tables.values() for x in t for key in x):
        log("by scope: nothing read, no operation of the round program "
            "carries a scope: this executable was compiled before the scopes "
            "(jax's compile cache keys a program without its debug info, so "
            "a cached one keeps the names it was compiled with)")
        return None
    chip = next(c for c in ev["devices"] if tables[c])
    print_tables(ev, chip, tables[chip])
    print_largest_operations(ev, chip, kept[chip], stacks.get(chip, {}))
    print_spans_on_one_clock(ev)
    return tables


def per_execution(scoped, phases=None, types=None, name=None):
    """Nanoseconds of each execution under ``phases`` (all where None), in
    layers of ``types`` (all, and what has no layer, where None) and in the
    layer ``name`` (any where None)."""
    return [
        sum(ns for (phase, kind, layer), ns in row.items()
            if (phases is None or phase in phases)
            and (types is None or kind in types)
            and (name is None or layer == name))
        for row in scoped
    ]


# -- what goes on [bench] lines -------------------------------------------
def print_executions(ev, chip, rows):
    t0 = ev["window_ns"][0]
    ms = lambda ns: None if ns is None else round(ns / 1e6, 3)
    log(f"executions of the round program on chip {chip} (start ms into the "
        f"window, ms, coverage by events, ms since the program before): "
        + str([(ms(r["start"] - t0), ms(r["end"] - r["start"]),
                round(r["coverage"], 5), ms(r["gap_before"]))
               for r in rows]))


def print_tables(ev, chip, scoped):
    """Median over the kept executions, device ms a local step: by phase, by
    layer type, and the fifteen largest layers, forward and backward."""
    tau = ev["tau"]
    ms = lambda *only: round(
        float(np.median(per_execution(scoped, *only))) / tau / 1e6, 4)
    phases = {p: ms((p,)) for p in
              (*PHASE_SCOPES, FORWARD, BACKWARD, UNSCOPED)}
    log(f"device ms a step by phase on chip {chip}, median of {len(scoped)} "
        f"executions (`average` too is shown a step: x tau {tau} for a "
        f"round): {phases}; sum {round(sum(phases.values()), 4)}, all "
        f"operations {ms()}")
    layers = sorted({key[1:] for row in scoped for key in row if key[1]})
    both = (FORWARD, BACKWARD)
    by_type = {k: [ms((p,), (k,)) for p in both]
               for k in sorted({k for k, _ in layers})}
    log("device ms a step by layer type [forward, backward]: " + str(dict(
        sorted(by_type.items(), key=lambda kv: -sum(kv[1])))))
    by_name = {f"{k}:{n}": [ms((p,), (k,), n) for p in both]
               for k, n in layers}
    largest = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))[:15]
    log("device ms a step by layer [forward, backward], the fifteen largest "
        f"of {len(by_name)}: " + str(dict(largest)))


def print_largest_operations(ev, chip, rows, stacks, k=12):
    """The operations with most self time and the scope each landed in, and
    the largest of those in no scope."""
    names = {hlo for r in rows for hlo in r["ops"]}
    ms = {hlo: float(np.median([r["ops"].get(hlo, 0.0) for r in rows]))
          / ev["tau"] / 1e6 for hlo in names}
    scope = lambda hlo: ":".join(
        str(x) for x in classify(stacks.get(hlo, "")) if x)
    order = sorted(ms, key=ms.get, reverse=True)
    log(f"largest operations on chip {chip}, ms a step and scope: " + str(
        [(xplane.short_name(h), round(ms[h], 4), scope(h)) for h in order[:k]]))
    bare = [h for h in order if scope(h) == UNSCOPED][:k]
    log("largest operations in no scope, ms a step and op_name: " + str(
        [(xplane.short_name(h), round(ms[h], 4), stacks.get(h, ""))
         for h in bare]))


def print_spans_on_one_clock(ev):
    """The program's spans as the profiler's trace has them (``obs.span``
    opens a ``TraceAnnotation``) against where the harness's clock offset
    puts the same spans: the largest difference of starts, span by span."""
    out = {}
    for name, tied in ev["spans"].items():
        host = ev["trace"].host.get(name)
        if host is None or not len(tied):
            out[name] = f"not in the trace, {len(tied)} from the tracer"
            continue
        # each of the tracer's spans against the nearest event of its name
        starts = np.sort(host.start)
        at = np.searchsorted(starts, tied[:, 0]).clip(1, len(starts) - 1)
        diff = np.minimum(np.abs(starts[at] - tied[:, 0]),
                          np.abs(starts[at - 1] - tied[:, 0]))
        out[name] = (f"{len(starts)} in the trace, {len(tied)} from the "
                     f"tracer, starts differ by at most "
                     f"{float(diff.max()) / 1e6:.4f} ms")
    if out:
        log(f"the program's spans on the profiler's clock against the "
            f"harness's offset: {out}")
