"""Crash-consistent run journal: the write-ahead round ledger.

The driver is the one process the fault-tolerance stack never covered:
a snapshot carries params/history/iter, but not *where the loop was* —
which round was in flight when the process died, which rounds' effects
are durable, and the carried driver-side state (CommPlane error-feedback
residuals, sentry EMA, shuffle cursors) that a restart silently resets.

``RunJournal`` is an append-only, CRC-framed record file the training
loop writes *around* every round:

- ``begin_round(r, ...)`` appends an **intent** record before any of
  round ``r``'s work (round index, view epoch, shuffle cursor, RNG key
  path, iter),
- ``commit_round(r, ...)`` appends a **commit** record only after the
  round's effects are durable (the snapshot+jobstate published for this
  boundary rides along as a ref).

Restart reads the ledger and knows exactly where the crash landed:

- last record is a **commit** for ``r`` -> round ``r`` is done; resume
  at ``r + 1`` (never re-execute a committed round),
- last record is an **intent** for ``r`` -> round ``r`` was in flight;
  rewind to the last committed boundary and execute ``r`` (never skip
  an uncommitted round),
- the tail is **torn** (a kill mid-append) -> the partial frame fails
  its CRC and is truncated on open; the record it was replacing never
  existed, so the rule above still applies to the last *whole* record.

Frame format (little-endian): ``b"SNJ1" | len:u32 | crc32:u32 |
payload`` where payload is one JSON object.  Each append is a single
``os.write`` on an ``O_APPEND`` descriptor; durability follows the
``fsync`` policy flag: ``"always"`` (every record), ``"commit"``
(commit records only — the default: an intent lost to the page cache
only costs re-detecting an uncommitted round), ``"never"`` (tests /
throwaway runs).

``io/checkpoint.restore_newest_valid_journaled`` reconciles this ledger
against the on-disk snapshots; ``runtime/recover.py`` is the journaled
driver loop ``tests/test_recover.py`` kills at every phase boundary and
holds to bit-identical recovery.
"""

from __future__ import annotations

import json
import os
import struct
import time
import zlib
from typing import Callable, Dict, List, Optional, Tuple

MAGIC = b"SNJ1"
_HEADER = struct.Struct("<II")  # payload length, payload crc32
FSYNC_POLICIES = ("always", "commit", "never")

INTENT = "intent"
COMMIT = "commit"


def _crc(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF


def scan(path: str) -> Tuple[List[Dict], int]:
    """Read-only frame scan: ``(records, torn_bytes)``.  ``torn_bytes``
    is the size of the unparseable tail (0 for a clean ledger); the
    scan stops at the first bad magic/length/CRC — everything after a
    torn frame is unreachable by construction (frames carry no resync
    marker; the writer never starts a frame before finishing the last).
    """
    records: List[Dict] = []
    if not os.path.exists(path):
        return records, 0
    with open(path, "rb") as f:
        blob = f.read()
    off = 0
    n = len(blob)
    while off < n:
        frame_start = off
        if blob[off : off + 4] != MAGIC or n - off < 4 + _HEADER.size:
            return records, n - frame_start
        length, crc = _HEADER.unpack_from(blob, off + 4)
        body_start = off + 4 + _HEADER.size
        body = blob[body_start : body_start + length]
        if len(body) < length or _crc(body) != crc:
            return records, n - frame_start
        try:
            rec = json.loads(body.decode("utf-8"))
        except ValueError:
            return records, n - frame_start
        records.append(rec)
        off = body_start + length
    return records, 0


class RunJournal:
    """Append-only CRC-framed round ledger (open-or-create).

    Opening an existing ledger scans it and TRUNCATES a torn tail (a
    kill mid-append) so the file is clean for this run's appends; the
    truncated byte count is exported on
    ``sparknet_journal_truncated_total``.  ``crash_hook`` is the chaos
    seam: when set, the next append writes *half* its frame, fsyncs,
    and calls the hook (which SIGKILLs in the kill sweep, or raises in
    in-process tests) — producing exactly the torn tail the open-time
    truncation must heal."""

    def __init__(self, path: str, fsync: str = "commit"):
        if fsync not in FSYNC_POLICIES:
            raise ValueError(
                f"fsync={fsync!r}: expected one of {FSYNC_POLICIES}"
            )
        self.path = path
        self.fsync = fsync
        self.crash_hook: Optional[Callable[[], None]] = None
        self.records, torn = scan(path)
        self.truncated_bytes = torn
        if torn:
            # heal the torn tail in place: later appends must extend a
            # valid frame sequence, never a partial frame
            good = os.path.getsize(path) - torn
            with open(path, "r+b") as f:
                f.truncate(good)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._fd = os.open(
            path, os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644
        )
        from sparknet_tpu import obs as _obs

        tm = _obs.training_metrics()
        if tm is not None and torn:
            tm.journal_truncated.inc()

    # ------------------------------------------------------------------
    def append(self, kind: str, **fields) -> Dict:
        """Append one record (single ``os.write``; fsync per policy)."""
        rec = {"kind": kind, "t_s": time.time(), **fields}
        body = json.dumps(rec, default=str).encode("utf-8")
        frame = MAGIC + _HEADER.pack(len(body), _crc(body)) + body
        if self.crash_hook is not None:
            # the chaos seam: half a frame lands durably, then the
            # "process dies" (SIGKILL in the sweep, an exception in
            # in-process tests).  A hook that returns is a harness bug.
            hook, self.crash_hook = self.crash_hook, None
            os.write(self._fd, frame[: max(5, len(frame) // 2)])
            os.fsync(self._fd)
            hook()
            raise RuntimeError(
                "journal crash_hook returned instead of dying"
            )
        os.write(self._fd, frame)
        if self.fsync == "always" or (
            self.fsync == "commit" and kind == COMMIT
        ):
            os.fsync(self._fd)
        self.records.append(rec)
        from sparknet_tpu import obs as _obs

        tm = _obs.training_metrics()
        if tm is not None:
            tm.journal_records.labels(kind).inc()
        return rec

    def begin_round(self, round_index: int, **meta) -> Dict:
        """The round's WRITE-AHEAD intent: appended before any of the
        round's work so a crash anywhere inside it is attributable."""
        return self.append(INTENT, round=int(round_index), **meta)

    def commit_round(self, round_index: int, **meta) -> Dict:
        """The round's commit: append ONLY after the round's effects
        are durable (pass ``snapshot=<state-file basename>`` when this
        boundary published one — the reconciler's rewind target)."""
        return self.append(COMMIT, round=int(round_index), **meta)

    # ------------------------------------------------------------------
    @property
    def last_committed_round(self) -> Optional[int]:
        for rec in reversed(self.records):
            if rec.get("kind") == COMMIT:
                return int(rec["round"])
        return None

    @property
    def in_flight_round(self) -> Optional[int]:
        """The intent round with no matching commit (None = clean)."""
        for rec in reversed(self.records):
            kind = rec.get("kind")
            if kind == COMMIT:
                return None
            if kind == INTENT:
                return int(rec["round"])
        return None

    def last_commit(self) -> Optional[Dict]:
        for rec in reversed(self.records):
            if rec.get("kind") == COMMIT:
                return rec
        return None

    def reconcile(self) -> Dict:
        """The restart decision, in one dict:

        - ``resume_round``: the first round to EXECUTE on restart —
          ``last_committed_round + 1`` (which equals the in-flight
          round when the crash landed mid-round), or 0 for a ledger
          with no commits.
        - ``snapshot``: the newest committed snapshot ref (state-file
          basename) at or before the committed boundary — the state
          ``restore_newest_valid_journaled`` rewinds to.
        - ``commit_iter``: the committed boundary's iter (snapshots
          beyond it belong to uncommitted rounds and are ignored).
        - ``worker_rounds``: the committed boundary's per-worker round
          vector (bounded-staleness runs journal it on every record;
          None for synchronous ledgers) — what a stale resume replays
          from, <= stale_bound rounds.
        """
        last = self.last_committed_round
        snapshot = None
        commit_iter = None
        worker_rounds = None
        for rec in reversed(self.records):
            if rec.get("kind") != COMMIT:
                continue
            if commit_iter is None and "iter" in rec:
                commit_iter = int(rec["iter"])
            if worker_rounds is None and rec.get("worker_rounds"):
                worker_rounds = [int(v) for v in rec["worker_rounds"]]
            if rec.get("snapshot"):
                snapshot = str(rec["snapshot"])
                break
        return {
            "last_committed_round": last,
            "in_flight_round": self.in_flight_round,
            "resume_round": 0 if last is None else last + 1,
            "snapshot": snapshot,
            "commit_iter": commit_iter,
            "worker_rounds": worker_rounds,
            "records": len(self.records),
            "truncated_bytes": self.truncated_bytes,
        }

    def close(self) -> None:
        if self._fd is not None:
            fd, self._fd = self._fd, None
            try:
                if self.fsync != "never":
                    os.fsync(fd)
            except OSError:  # pragma: no cover - fd already gone
                pass
            os.close(fd)

    def __enter__(self) -> "RunJournal":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


# ----------------------------------------------------------------------
# CLI surface (shared by cli train + the four averaging apps)


def default_journal_path(prefix: str) -> str:
    """One naming rule for the ledger that rides a snapshot prefix."""
    return prefix + "_run.journal"


def add_cli_args(parser) -> None:
    g = parser.add_mutually_exclusive_group()
    g.add_argument(
        "--journal", dest="journal", action="store_true", default=None,
        help="journal round intent/commit records to a CRC-framed "
        "write-ahead ledger beside the snapshots "
        "(<prefix>_run.journal): restart knows exactly which round "
        "was in flight, never re-executes a committed round, never "
        "skips an uncommitted one (io/journal.py).  Default: off for "
        "fresh runs; a resume that FINDS a ledger consumes it "
        "automatically",
    )
    g.add_argument(
        "--no_journal", dest="journal", action="store_false",
        help="disable the run journal even on resume (the resumed "
        "trajectory may silently diverge from an uninterrupted one: "
        "EF residuals / sentry state reset)",
    )
    parser.add_argument(
        "--journal_path", default=None,
        help="override the ledger path (default <prefix>_run.journal)",
    )
    parser.add_argument(
        "--journal_fsync", choices=FSYNC_POLICIES, default="commit",
        help="journal durability: fsync every record / commit records "
        "only (default) / never",
    )


def journal_from_args(
    args, default_path: str, resuming: bool = False
) -> Optional[RunJournal]:
    """Build (or skip) the run journal from parsed CLI args.  The auto
    default (neither ``--journal`` nor ``--no_journal``): a RESUME that
    finds an existing ledger consumes it; fresh runs stay unjournaled
    unless asked."""
    want = getattr(args, "journal", None)
    path = getattr(args, "journal_path", None) or default_path
    if want is False:
        return None
    if want is None and not (resuming and os.path.exists(path)):
        return None
    return RunJournal(
        path, fsync=getattr(args, "journal_fsync", "commit")
    )
