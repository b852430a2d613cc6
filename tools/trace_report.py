"""Fold a round-span trace into the per-phase table PERF.md cites.

Input: a Chrome trace-event JSON written by ``--trace_out`` /
``obs.Tracer.save`` (or its sibling ``.jsonl`` structured run log —
both carry the same spans).  Output: one row per span name with count,
total/mean/p50/max milliseconds and the share of run wall time, plus an
instant-event summary (faults, retries, quarantines), the **measured
producer hidden-fraction** — how much of the RoundFeed's assemble+h2d
time ran under a different thread's execute/average spans, overall and
per round (the offline sibling of ``obs/profile.py``'s live number) —
and the compressed-collective breakdown (the PR-6 ``quantize`` /
``allreduce`` / ``dequantize`` comm spans with their ``chunk=`` /
``stage=`` / ``compress=`` arguments).

    python tools/trace_report.py RUN.trace.json
    python tools/trace_report.py RUN.trace.jsonl --json   # machine form
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# one vocabulary for the comm-span triple: the canonical registry the
# lint's registry audit holds the EMITTERS to (stdlib-only import)
from sparknet_tpu.analysis.registry import COMM_SPANS  # noqa: E402


def load_events(path: str) -> List[dict]:
    """Chrome-JSON or JSONL -> a uniform event list: spans as
    {name, ts (us), dur (us), tid/thread, args}, instants as
    {name, ts}.  Multi-host bundles (the fleet collector's merged
    ``/runlog`` JSONL or ``/trace`` Chrome JSON — obs/fleet.py) carry a
    ``host`` per record: the host rides on each event and its thread
    lane is host-qualified, so two hosts' "MainThread"s never fold into
    one lane."""
    if path.endswith(".jsonl"):
        events = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                host = rec.get("host")
                thread = rec.get("thread", "?")
                ev = {
                    "name": rec["name"],
                    "ph": "X" if rec.get("kind") == "span" else "i",
                    "ts": float(rec.get("ts_s", 0.0)) * 1e6,
                    "tid": f"{host}/{thread}" if host else thread,
                }
                if host:
                    ev["host"] = host
                if rec.get("kind") == "span":
                    ev["dur"] = float(rec.get("dur_ms", 0.0)) * 1e3
                if rec.get("args"):
                    ev["args"] = rec["args"]
                events.append(ev)
        return events
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    for ev in events:
        host = (ev.get("args") or {}).get("host")
        if host and "host" not in ev:
            ev["host"] = host
    return events


def _merge_intervals(spans) -> List[tuple]:
    """Sorted, non-overlapping (t0, t1) union of span intervals.
    Consumer traces NEST execute inside average on one thread — summing
    pairwise coverage over both would double-count, inflating the
    hidden fraction up to 2x."""
    ivs = sorted(
        (s["ts"], s["ts"] + s["dur"]) for s in spans if s.get("dur")
    )
    merged: List[tuple] = []
    for t0, t1 in ivs:
        if merged and t0 <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], t1))
        else:
            merged.append((t0, t1))
    return merged


def _overlap_us(span, merged) -> float:
    """Microseconds of ``span`` covered by ``merged`` (non-overlapping
    sorted intervals from ``_merge_intervals`` — sum is exact)."""
    a0, a1 = span["ts"], span["ts"] + span["dur"]
    cov = 0.0
    for o0, o1 in merged:
        lo, hi = max(a0, o0), min(a1, o1)
        if hi > lo:
            cov += hi - lo
    return min(cov, a1 - a0)


def _hidden_fraction(by_name: Dict[str, List[dict]]) -> Dict[str, object]:
    """Measured producer hidden-fraction: the share of assemble+h2d span
    time overlapping a DIFFERENT thread's execute/average spans —
    overall, and folded per round (``round=`` span args) into
    p50/min/max.  Rounds whose producer work ran in the open (round 0,
    the startup prefetch lead, a serial feed) honestly read 0.  In a
    merged multi-host bundle the overlap is judged WITHIN each host's
    lane set: host A's assembly under host B's execute is coincidence,
    not pipelining, and must not count as hidden (nor double-count one
    producer across N hosts' consumers)."""
    producers = by_name.get("assemble", []) + by_name.get("h2d", [])
    consumers = by_name.get("execute", []) + by_name.get("average", [])
    if not producers:
        return {"producer_hidden_fraction": None,
                "producer_hidden_fraction_per_round": None}
    total = 0.0
    hidden = 0.0
    per_round: Dict[object, List[float]] = {}
    merged_by_lane: Dict[object, List[tuple]] = {}
    for p in producers:
        lane = (p.get("host"), p.get("tid"))
        if lane not in merged_by_lane:
            merged_by_lane[lane] = _merge_intervals(
                c for c in consumers
                if c.get("host") == lane[0] and c.get("tid") != lane[1]
            )
        dur = p.get("dur", 0.0)
        cov = _overlap_us(p, merged_by_lane[lane]) if dur else 0.0
        total += dur
        hidden += cov
        r = (p.get("args") or {}).get("round")
        acc = per_round.setdefault((p.get("host"), r), [0.0, 0.0])
        acc[0] += dur
        acc[1] += cov
    overall = hidden / total if total > 0 else None
    fracs = sorted(
        cov / dur for dur, cov in per_round.values() if dur > 0
    )
    per = None
    if fracs:
        per = {
            "rounds": len(fracs),
            "p50": round(fracs[len(fracs) // 2], 4),
            "min": round(fracs[0], 4),
            "max": round(fracs[-1], 4),
        }
    return {
        "producer_hidden_fraction": (
            round(overall, 4) if overall is not None else None
        ),
        "producer_hidden_fraction_per_round": per,
    }


def _comm_section(by_name: Dict[str, List[dict]]) -> Dict[str, object]:
    """The compressed-collective breakdown (PR-6 comm spans), absent
    (None) for traces that predate the comm plane."""
    if not any(by_name.get(n) for n in COMM_SPANS):
        return {"comm": None}
    out: Dict[str, object] = {}
    ar = by_name.get("allreduce", [])
    if ar:
        chunks = sorted(
            {(e.get("args") or {}).get("chunk") for e in ar}
            - {None}
        )
        out["allreduce"] = {
            "count": len(ar),
            "total_ms": round(sum(e["dur"] for e in ar) / 1e3, 3),
            "chunks": chunks,
            "nbytes_total": int(sum(
                (e.get("args") or {}).get("nbytes", 0) for e in ar
            )),
            "threads": sorted({str(e.get("tid")) for e in ar}),
        }
    qz = by_name.get("quantize", [])
    if qz:
        out["quantize"] = {
            "count": len(qz),
            "total_ms": round(sum(e["dur"] for e in qz) / 1e3, 3),
            "compress": sorted(
                {(e.get("args") or {}).get("compress") for e in qz}
                - {None}
            ),
        }
    dq = by_name.get("dequantize", [])
    if dq:
        stages: Dict[str, int] = {}
        for e in dq:
            s = (e.get("args") or {}).get("stage", "?")
            stages[s] = stages.get(s, 0) + 1
        out["dequantize"] = {
            "count": len(dq),
            "total_ms": round(sum(e["dur"] for e in dq) / 1e3, 3),
            "stages": dict(sorted(stages.items())),
        }
    return {"comm": out}


def fold(events: List[dict]) -> Dict[str, object]:
    spans = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    by_name: Dict[str, List[dict]] = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    wall_us = 0.0
    if spans:
        t0 = min(e["ts"] for e in spans)
        t1 = max(e["ts"] + e["dur"] for e in spans)
        wall_us = max(1e-9, t1 - t0)
    phases = {}
    for name, evs in sorted(by_name.items()):
        durs = sorted(e["dur"] for e in evs)
        total = sum(durs)
        phases[name] = {
            "count": len(durs),
            "total_ms": round(total / 1e3, 3),
            "mean_ms": round(total / len(durs) / 1e3, 3),
            "p50_ms": round(durs[len(durs) // 2] / 1e3, 3),
            "max_ms": round(durs[-1] / 1e3, 3),
            "pct_of_wall": round(100.0 * total / wall_us, 1),
            "threads": sorted({str(e["tid"]) for e in evs}),
        }
    inst_counts: Dict[str, int] = {}
    for e in instants:
        inst_counts[e["name"]] = inst_counts.get(e["name"], 0) + 1
    rep = {
        "wall_ms": round(wall_us / 1e3, 3),
        "phases": phases,
        "instants": dict(sorted(inst_counts.items())),
    }
    hosts = sorted({
        str(e["host"]) for e in spans + instants if e.get("host")
    })
    rep["hosts"] = hosts or None
    # per-host straggler verdicts (the round profiler's per-round
    # `profile` instants): a merged bundle NAMES the host so "worker 3
    # was slow" becomes "worker 3 of host-b was slow"
    stragglers = []
    for e in instants:
        a = e.get("args") or {}
        if e.get("name") == "profile" and a.get("straggler"):
            stragglers.append({
                "host": e.get("host"),
                "round": a.get("round"),
                "worker": a.get("worst_worker"),
                "skew": a.get("skew"),
            })
    rep["stragglers"] = stragglers
    rep.update(_hidden_fraction(by_name))
    # back-compat boolean: derived from the measured
    # fraction instead of a separate any-overlap scan
    hf = rep["producer_hidden_fraction"]
    rep["producer_overlap_observed"] = bool(hf is not None and hf > 0)
    rep.update(_comm_section(by_name))
    return rep


def format_report(rep: Dict[str, object]) -> str:
    lines = [
        "%-12s %7s %12s %10s %10s %10s %8s"
        % ("phase", "count", "total (ms)", "mean", "p50", "max", "% wall")
    ]
    for name, p in rep["phases"].items():
        lines.append(
            "%-12s %7d %12.1f %10.2f %10.2f %10.2f %8.1f"
            % (
                name, p["count"], p["total_ms"], p["mean_ms"],
                p["p50_ms"], p["max_ms"], p["pct_of_wall"],
            )
        )
    lines.append("wall: %.1f ms" % rep["wall_ms"])
    if rep.get("hosts"):
        lines.append("hosts: " + ", ".join(rep["hosts"]))
    if rep["instants"]:
        lines.append(
            "instants: "
            + ", ".join(f"{k} x{v}" for k, v in rep["instants"].items())
        )
    for s in rep.get("stragglers") or ():
        lines.append(
            "straggler: round %s worker %s%s (skew %s)"
            % (
                s["round"], s["worker"],
                " on host %s" % s["host"] if s["host"] else "",
                s["skew"],
            )
        )
    hf = rep.get("producer_hidden_fraction")
    per = rep.get("producer_hidden_fraction_per_round")
    if hf is None:
        lines.append("producer assembly/h2d hidden under execute: n/a")
    else:
        lines.append(
            "producer assembly/h2d hidden under execute: %.1f%%%s"
            % (
                100.0 * hf,
                " (per round: p50 %.2f, min %.2f, max %.2f over %d)"
                % (per["p50"], per["min"], per["max"], per["rounds"])
                if per else "",
            )
        )
    comm = rep.get("comm")
    if comm:
        ar = comm.get("allreduce")
        if ar:
            lines.append(
                "compressed collective: allreduce x%d %.1f ms over "
                "chunks %s (%d B modeled)"
                % (
                    ar["count"], ar["total_ms"], ar["chunks"],
                    ar["nbytes_total"],
                )
            )
        for name in ("quantize", "dequantize"):
            sec = comm.get(name)
            if sec:
                extra = (
                    " modes %s" % sec["compress"]
                    if name == "quantize"
                    else " stages %s" % sec["stages"]
                )
                lines.append(
                    "  %s x%d %.1f ms%s"
                    % (name, sec["count"], sec["total_ms"], extra)
                )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="Chrome trace .json or run-log .jsonl")
    ap.add_argument("--json", action="store_true",
                    help="emit the folded report as JSON")
    args = ap.parse_args(argv)
    rep = fold(load_events(args.trace))
    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        print(format_report(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
