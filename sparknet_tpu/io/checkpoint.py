"""Checkpoint/resume for the full TrainState.

Reference semantics (``solver.cpp:446-519``, ``sgd_solver.cpp:242-290``):
a snapshot is the model weights (.caffemodel) plus SolverState (iter,
current_step, history blobs); ``Restore`` resumes training exactly.  Both
reference snapshot formats are supported, chosen by
``SolverParameter.snapshot_format`` (``solver.cpp:459-476``):

- BINARYPROTO (default): ``{prefix}_iter_{N}.caffemodel`` (binary-
  compatible with the reference wire format) +
  ``{prefix}_iter_{N}.solverstate.npz`` (iter + flattened history pytree),
- HDF5: ``{prefix}_iter_{N}.caffemodel.h5`` +
  ``{prefix}_iter_{N}.solverstate.h5`` in the Net::ToHDF5 /
  SnapshotSolverStateToHDF5 layouts (``io/hdf5.py``).

``snapshot()``/``restore()`` round-trip bitwise in either format; restore
and warm-start detect the format from the file extension.

Integrity + recovery (the fault-tolerance layer): every snapshot also
publishes ``{prefix}_iter_{N}.manifest.json`` with the CRC32 and size of
each file.  ``restore()`` verifies the manifest when present and raises
``SnapshotCorrupt`` on mismatch; ``restore_newest_valid()`` walks
snapshots newest-first, QUARANTINES corrupt/truncated ones (renamed with
a ``.corrupt`` suffix so the next resume doesn't trip on them again) and
falls back to the newest snapshot that verifies — preemption mid-write
or bit-rot degrades to an older restore point instead of killing the
resume (``imagenet_run_db_app --resume`` / ``cli train --resume``;
chaos-proved by ``runtime/chaos.py``).

Full job state (the crash-consistency layer): ``snapshot(...,
extra_state=...)`` serializes DRIVER-side state the TrainState never
carried — CommPlane error-feedback residuals, sentry EMA/cooldown,
membership epoch, data-plane cursors — as
``{prefix}_iter_{N}.jobstate.npz`` beside the model/state files, listed
in the same CRC manifest (``load_job_state`` reads it back).
``restore_newest_valid_journaled()`` reconciles the run journal
(``io/journal.py``) against the snapshot set: it rewinds to the last
COMMITTED round boundary — a snapshot published for a round whose
commit never landed is ignored, so restart never re-executes a
committed round nor skips an uncommitted one.  Proven bit-identical
at every phase boundary of ``runtime/recover.py``'s driver loop by
``tests/test_recover.py``.
"""

from __future__ import annotations

import glob as _glob
import json
import logging
import os
import zlib
from typing import List, Optional, Tuple

_log = logging.getLogger(__name__)

_STATE_SUFFIXES = (".solverstate.npz", ".solverstate.h5")
_JOBSTATE_SUFFIX = ".jobstate.npz"


class SnapshotCorrupt(RuntimeError):
    """A snapshot failed CRC/size verification or could not be decoded."""

import numpy as np

from sparknet_tpu import obs
from sparknet_tpu.io import caffemodel

# jax and the Solver stack import LAZILY (inside the functions that
# touch live state): the read-only manifest/CRC helpers below are shared
# with the data plane (``data/chunk_cache.py``) and the serving delivery
# watcher (``serve/delivery.py``), which must be able to verify a
# published snapshot WITHOUT pulling jax or constructing a solver.


def _flatten_history(history):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(history)
    return leaves, treedef


# chaos/test seam: called with the DESTINATION path after the temp file
# is fully written but before the atomic publish rename — the window a
# preemption mid-write lands in.  The kill sweep's SIGKILL here leaves
# an unpublished ``*.tmp-<pid>`` (never a torn published file);
# in-process tests raise instead, exercising the clean-abandon path.
_CRASH_HOOK = None


def set_crash_hook(hook) -> None:
    global _CRASH_HOOK
    _CRASH_HOOK = hook


def _atomic(write_fn, path: str) -> None:
    """Write through a temp file + rename so a kill mid-write never
    leaves a file ``restore()`` would accept."""
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        write_fn(tmp)
        if _CRASH_HOOK is not None:
            _CRASH_HOOK(path)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def crc32_bytes(data: bytes) -> int:
    """The framework's one checksum convention (manifest ``crc32``
    fields, chunk-cache sidecars): masked ``zlib.crc32``."""
    return zlib.crc32(data) & 0xFFFFFFFF


def crc32_file(path: str) -> Tuple[int, int]:
    """Streaming (crc32, size) of a file."""
    crc = 0
    size = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return crc & 0xFFFFFFFF, size
            crc = zlib.crc32(chunk, crc)
            size += len(chunk)


_crc32_file = crc32_file  # pre-round-15 private name, kept for callers


def manifest_path_for(path: str) -> str:
    """``.../p_iter_N.<anything>`` -> ``.../p_iter_N.manifest.json``."""
    base = path
    for suf in _STATE_SUFFIXES + (
        _JOBSTATE_SUFFIX, ".caffemodel.h5", ".caffemodel"
    ):
        if base.endswith(suf):
            base = base[: -len(suf)]
            break
    return base + ".manifest.json"


def jobstate_path_for(state_path: str) -> str:
    """``.../p_iter_N.solverstate.*`` -> ``.../p_iter_N.jobstate.npz``."""
    base = state_path
    for suf in _STATE_SUFFIXES:
        if base.endswith(suf):
            base = base[: -len(suf)]
            break
    return base + _JOBSTATE_SUFFIX


def _write_manifest(it: int, fmt: str, paths) -> str:
    """CRC/size manifest over every published snapshot file (model,
    state, and — when present — the jobstate companion).  The state
    path sits at index 1; extra files follow."""
    mpath = manifest_path_for(paths[1])
    entries = {}
    for p in paths:
        crc, size = _crc32_file(p)
        entries[os.path.basename(p)] = {"crc32": crc, "size": size}

    def _dump(tmp):
        with open(tmp, "w") as f:
            json.dump(
                {"iter": int(it), "format": fmt, "files": entries}, f
            )

    _atomic(_dump, mpath)
    return mpath


def read_manifest(mpath: str) -> dict:
    """Decode a snapshot manifest — read-only, no solver, no jax.
    OSError (transient I/O on flaky storage — the very environment this
    layer targets) propagates as-is: only DECODE failure of the manifest
    is evidence of corruption.  ``restore_newest_valid`` treats plain
    OSError as non-corruption and leaves the snapshot intact."""
    with open(mpath) as f:
        raw = f.read()
    return parse_manifest(raw, label=mpath)


def parse_manifest(raw, label: str = "<manifest>") -> dict:
    """Manifest bytes/text -> dict, raising ``SnapshotCorrupt`` on
    garbage (the delivery watcher feeds this bytes fetched through an
    object store / chunk cache rather than a local path)."""
    try:
        if isinstance(raw, bytes):
            raw = raw.decode("utf-8")
        manifest = json.loads(raw)
        if not isinstance(manifest["files"], dict):
            raise TypeError("'files' is not a mapping")
    except (ValueError, KeyError, TypeError) as e:
        raise SnapshotCorrupt(f"{label}: unreadable manifest: {e}") from e
    return manifest


def verify_file_entry(path: str, want: dict) -> None:
    """CRC32/size-check ONE on-disk file against its manifest entry."""
    if not os.path.exists(path):
        raise SnapshotCorrupt(f"{path}: listed in manifest but missing")
    crc, size = crc32_file(path)
    if size != int(want["size"]):
        raise SnapshotCorrupt(
            f"{path}: truncated ({size} bytes, manifest says "
            f"{want['size']})"
        )
    if crc != int(want["crc32"]):
        raise SnapshotCorrupt(
            f"{path}: CRC32 mismatch ({crc:#x} vs manifest "
            f"{int(want['crc32']):#x})"
        )


def verify_bytes_entry(name: str, data: bytes, manifest: dict) -> None:
    """CRC32/size-check fetched BYTES against the manifest's entry for
    ``name`` — the delivery watcher's verify, where the file arrived
    through an object store and never touched the local disk under its
    published name."""
    want = manifest["files"].get(os.path.basename(name))
    if want is None:
        raise SnapshotCorrupt(f"{name}: not listed in the manifest")
    if len(data) != int(want["size"]):
        raise SnapshotCorrupt(
            f"{name}: truncated ({len(data)} bytes, manifest says "
            f"{want['size']})"
        )
    crc = crc32_bytes(data)
    if crc != int(want["crc32"]):
        raise SnapshotCorrupt(
            f"{name}: CRC32 mismatch ({crc:#x} vs manifest "
            f"{int(want['crc32']):#x})"
        )


def verify_manifest(mpath: str) -> Optional[dict]:
    """Read-only verify of every file a manifest lists (no solver, no
    jax — shared by ``restore()``, the chunk cache's snapshot staging,
    and the serving delivery watcher).  Returns the decoded manifest,
    or None when no manifest exists (pre-manifest snapshots pass).
    Raises ``SnapshotCorrupt`` on truncation/mismatch/missing files."""
    if not os.path.exists(mpath):
        return None
    manifest = read_manifest(mpath)
    d = os.path.dirname(mpath)
    for name, want in manifest["files"].items():
        verify_file_entry(os.path.join(d, name), want)
    return manifest


def verify_snapshot(state_path: str) -> None:
    """CRC32/size-check every file the snapshot's manifest lists.
    Raises ``SnapshotCorrupt`` on truncation/mismatch/missing files; a
    snapshot with NO manifest (pre-manifest format) passes — decode
    errors are still caught by ``restore_newest_valid``."""
    verify_manifest(manifest_path_for(state_path))


# ----------------------------------------------------------------------
# full job state: the driver-side state a TrainState never carried
# (CommPlane EF residuals, sentry EMA/cooldown, membership epoch,
# data-plane cursors), serialized beside params under the same CRC
# manifest.  The payload is a NESTED dict whose leaves are numpy arrays
# (stored as npz entries keyed by their "/"-joined path) or JSON-able
# scalars/lists (stored together in one __json__ entry).


def _flatten_job_state(d: dict, prefix: str = ""):
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _flatten_job_state(v, key + "/")
        else:
            yield key, v


def _unflatten_job_state(flat: dict) -> dict:
    out: dict = {}
    for key, v in flat.items():
        parts = key.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    return out


def _dump_job_state(path: str, extra_state: dict) -> None:
    import json as _json

    arrays = {}
    scalars = {}
    for key, v in _flatten_job_state(extra_state):
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            arrays[f"a:{key}"] = np.asarray(v)
        else:
            scalars[key] = v

    def _savez(p):
        with open(p, "wb") as f:
            np.savez(
                f,
                __json__=np.frombuffer(
                    _json.dumps(scalars).encode("utf-8"), np.uint8
                ),
                **arrays,
            )

    _atomic(_savez, path)


def load_job_state(state_path: str):
    """The jobstate companion of a snapshot (pass the solverstate
    path), or None when the snapshot predates the job-state format.
    Read-only; the manifest check happens in ``restore()``/``verify``.
    """
    import json as _json

    jpath = jobstate_path_for(state_path)
    if not os.path.exists(jpath):
        return None
    flat: dict = {}
    with np.load(jpath) as z:
        for name in z.files:
            if name == "__json__":
                flat.update(
                    _json.loads(bytes(z[name].tobytes()).decode("utf-8"))
                )
            elif name.startswith("a:"):
                flat[name[2:]] = z[name]
    return _unflatten_job_state(flat)


def _write_snapshot(
    fmt: str, prefix: str, it: int, blobs, leaves, net_name: str,
    extra_state=None,
) -> Tuple[str, str]:
    """Host-side file writes of one snapshot (shared by the sync path
    and the AsyncCheckpointer worker); all files publish atomically."""
    with obs.span("snapshot", iter=int(it), fmt=fmt):
        return _write_snapshot_inner(
            fmt, prefix, it, blobs, leaves, net_name, extra_state
        )


def _write_snapshot_inner(
    fmt: str, prefix: str, it: int, blobs, leaves, net_name: str,
    extra_state=None,
) -> Tuple[str, str]:
    os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)
    if fmt == "HDF5":
        from sparknet_tpu.io import hdf5

        model_path = f"{prefix}_iter_{it}.caffemodel.h5"
        state_path = f"{prefix}_iter_{it}.solverstate.h5"
        _atomic(lambda p: hdf5.save_weights_hdf5(blobs, p), model_path)
        _atomic(
            lambda p: hdf5.save_state_hdf5(
                p, it, [np.asarray(l) for l in leaves]
            ),
            state_path,
        )
    else:
        model_path = f"{prefix}_iter_{it}.caffemodel"
        state_path = f"{prefix}_iter_{it}.solverstate.npz"
        _atomic(
            lambda p: caffemodel.save_weights(blobs, p, net_name=net_name),
            model_path,
        )

        def _savez(p):
            with open(p, "wb") as f:
                np.savez(
                    f,
                    iter=np.asarray(it, np.int64),
                    **{f"h{i}": np.asarray(l) for i, l in enumerate(leaves)},
                )

        _atomic(_savez, state_path)
    paths = (model_path, state_path)
    if extra_state:
        jpath = jobstate_path_for(state_path)
        _dump_job_state(jpath, extra_state)
        paths = paths + (jpath,)
    # manifest publishes LAST: a kill between the data files and here
    # leaves a manifest-less (pre-format) snapshot, never a manifest
    # that vouches for half-written data
    _write_manifest(it, fmt, paths)
    tm = obs.training_metrics()
    if tm is not None:
        tm.snapshots.inc()
    return model_path, state_path


def _host_snapshot_args(solver, state, fmt: str):
    import jax

    fmt = (fmt or solver.param.snapshot_format or "BINARYPROTO").upper()
    it = int(jax.device_get(state.iter))
    # net_blobs np.asarray()s every blob — the host transfer happens
    # here, on the caller's thread, against the live buffers
    blobs = caffemodel.net_blobs(solver.net, state.params, state.stats)
    leaves = [
        np.asarray(l)
        for l in _flatten_history(jax.device_get(state.history))[0]
    ]
    return fmt, it, blobs, leaves


def snapshot(
    solver, state, prefix: str, fmt: str = None, extra_state=None
) -> Tuple[str, str]:
    """Write model + solver state; returns (model_path, state_path).
    ``fmt`` overrides ``solver.param.snapshot_format``.
    ``extra_state`` (a nested dict of numpy arrays / JSON-ables)
    publishes as the ``.jobstate.npz`` companion under the same CRC
    manifest — the full-job-state snapshot (``load_job_state``)."""
    fmt, it, blobs, leaves = _host_snapshot_args(solver, state, fmt)
    return _write_snapshot(
        fmt, prefix, it, blobs, leaves, solver.net.name or "net",
        extra_state,
    )


class AsyncCheckpointer:
    """Background snapshots for preemption tolerance (the role Orbax
    async checkpointing plays in TPU stacks; the reference's analog is
    restart-from-snapshot fault tolerance, SURVEY §5).

    ``save()`` pulls the state to host on the caller's thread (the only
    part that must see the live buffers — training continues immediately
    since updates are functional), then serializes and writes on a
    worker thread.  Files publish atomically, one snapshot is in flight
    at a time (a new ``save`` waits for the previous write), and worker
    errors re-raise on the next ``save()``/``wait()``.

    Preemption contract: the worker is a daemon thread, so WITHOUT a
    drain an interpreter exit (or a SIGTERM the driver acts on before
    calling ``wait()``) could abandon the in-flight write — the round's
    snapshot silently skipped, a ``*.tmp-<pid>`` left behind, while
    ``_atomic`` guarantees nothing half-written ever PUBLISHES.  The
    checkpointer therefore registers a bounded drain on BOTH exits: the
    ``utils/signals.py`` SIGTERM hook registry (the orchestrator's
    preemption notice) and ``atexit`` (which runs before daemon threads
    are killed).  A write still wedged past ``drain_timeout_s`` is
    abandoned cleanly — the previous snapshot stays the newest valid
    restore point (regression-tested with a real SIGKILL mid-write)."""

    def __init__(self, drain_timeout_s: float = 30.0) -> None:
        import atexit

        from sparknet_tpu.utils import signals as _signals

        self._thread = None
        self._exc: Optional[BaseException] = None
        self._last_paths: Optional[Tuple[str, str]] = None
        self.drain_timeout_s = float(drain_timeout_s)
        _signals.add_sigterm_hook(self._drain)
        atexit.register(self._drain)
        self._detach = lambda: (
            _signals.remove_sigterm_hook(self._drain),
            atexit.unregister(self._drain),
        )

    def save(
        self, solver, state, prefix: str, fmt: str = None,
        extra_state=None,
    ) -> None:
        import threading

        self.wait()
        fmt, it, blobs, leaves = _host_snapshot_args(solver, state, fmt)
        net_name = solver.net.name or "net"

        def work():
            try:
                self._last_paths = _write_snapshot(
                    fmt, prefix, it, blobs, leaves, net_name, extra_state
                )
            except BaseException as e:  # noqa: BLE001 — re-raised on wait
                self._exc = e

        self._thread = threading.Thread(
            target=work, name="sparknet-async-ckpt", daemon=True
        )
        self._thread.start()

    def wait(self) -> Optional[Tuple[str, str]]:
        """Block until the in-flight snapshot (if any) is published;
        returns its (model_path, state_path).  Call before process exit
        and on STOP signals."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc
        return self._last_paths

    @property
    def last_paths(self) -> Optional[Tuple[str, str]]:
        """Paths of the newest PUBLISHED snapshot (None until the
        first write completes) — journaling drivers commit the
        previous async boundary once its publish is confirmed."""
        return self._last_paths

    def _drain(self) -> None:
        """Bounded flush of the in-flight write (SIGTERM hook + atexit
        — both may fire in teardown contexts, so this never raises:
        errors surface on the next explicit ``wait()``, a wedged write
        is abandoned with the previous snapshot intact)."""
        t = self._thread
        if t is None:
            return
        try:
            t.join(timeout=self.drain_timeout_s)
            if not t.is_alive():
                self._thread = None
        except Exception:  # noqa: BLE001 — signal/teardown context
            pass

    def close(self) -> None:
        """Flush and detach the exit hooks (idempotent)."""
        self._drain()
        detach, self._detach = self._detach, lambda: None
        detach()


def _load_model_blobs(model_path: str):
    if model_path.endswith(".h5"):
        from sparknet_tpu.io import hdf5

        return hdf5.load_weights_hdf5(model_path)
    return caffemodel.load_weights(model_path)


def restore(
    solver,
    prefix_or_state_path: str,
    seed: int = 0,
    verify: bool = True,
):
    """Rebuild a TrainState from a snapshot (``Solver::Restore`` +
    ``restore_solver_from_file``, ccaffe.cpp:271-273).  Accepts either a
    ``.solverstate.npz`` or ``.solverstate.h5`` path.  When the snapshot
    carries a manifest, its CRC32s are checked first (``verify=False``
    opts out, e.g. for forensics on a quarantined file)."""
    with obs.span(
        "restore", path=os.path.basename(prefix_or_state_path)
    ):
        state = _restore_impl(solver, prefix_or_state_path, seed, verify)
    tm = obs.training_metrics()
    if tm is not None:
        tm.restores.inc()
    return state


def _restore_impl(
    solver,
    prefix_or_state_path: str,
    seed: int = 0,
    verify: bool = True,
):
    import jax

    from sparknet_tpu.solver import TrainState

    state_path = prefix_or_state_path
    if verify:
        with obs.span("verify", path=os.path.basename(state_path)):
            verify_snapshot(state_path)
    fresh = solver.init_state(seed)
    leaves, treedef = _flatten_history(jax.device_get(fresh.history))
    if state_path.endswith(".solverstate.h5"):
        from sparknet_tpu.io import hdf5

        model_path = state_path[: -len(".solverstate.h5")] + ".caffemodel.h5"
        it, _step, new_leaves = hdf5.load_state_hdf5(state_path)
        if len(new_leaves) != len(leaves):
            raise ValueError(
                f"{state_path}: {len(new_leaves)} history blobs, solver "
                f"has {len(leaves)}"
            )
    elif state_path.endswith(".solverstate.npz"):
        model_path = state_path[: -len(".solverstate.npz")] + ".caffemodel"
        with np.load(state_path) as z:
            it = int(z["iter"])
            new_leaves = [z[f"h{i}"] for i in range(len(leaves))]
    else:
        raise ValueError("pass a .solverstate.npz or .solverstate.h5 path")
    loaded = _load_model_blobs(model_path)
    params, stats = caffemodel.apply_blobs(
        solver.net, jax.device_get(fresh.params), jax.device_get(fresh.stats), loaded
    )
    history = jax.tree_util.tree_unflatten(treedef, new_leaves)
    return TrainState(
        params=jax.device_put(params),
        stats=jax.device_put(stats),
        history=jax.device_put(history),
        iter=np.asarray(it, np.int32),
    )


def find_snapshots(prefix: str) -> List[str]:
    """All non-quarantined solverstate paths for ``prefix``, sorted by
    iteration ascending (the resume scan)."""
    out = [
        p
        for p in _glob.glob(prefix + "_iter_*.solverstate*")
        if p.endswith(_STATE_SUFFIXES)
    ]
    return sorted(out, key=lambda p: int(p.split("_iter_")[-1].split(".")[0]))


def _quarantine(state_path: str) -> List[str]:
    """Rename every file of a corrupt snapshot (model, state, manifest)
    with a ``.corrupt`` suffix so resume scans skip it but forensics can
    still read it."""
    mpath = manifest_path_for(state_path)
    for suf in _STATE_SUFFIXES:
        if state_path.endswith(suf):
            base = state_path[: -len(suf)]
            break
    else:  # pragma: no cover - callers always pass a state path
        base = os.path.splitext(state_path)[0]
    moved = []
    for p in (
        state_path,
        base + ".caffemodel",
        base + ".caffemodel.h5",
        base + _JOBSTATE_SUFFIX,
        mpath,
    ):
        if os.path.exists(p):
            os.replace(p, p + ".corrupt")
            moved.append(p + ".corrupt")
    tm = obs.training_metrics()
    if tm is not None:
        tm.quarantined.inc()
    obs.instant(
        "quarantine", cat="fault", snapshot=os.path.basename(state_path)
    )
    return moved


def restore_newest_valid(
    solver,
    prefix: str,
    seed: int = 0,
    quarantine: bool = True,
):
    """Resume from the newest snapshot that VERIFIES — the fault-
    tolerant ``--resume`` path.  Walks ``find_snapshots(prefix)`` newest
    first; a snapshot that fails its manifest check or cannot be decoded
    is quarantined (renamed ``*.corrupt``) and the scan falls back to
    the next-older one.  Returns ``(state, state_path)``; raises
    ``FileNotFoundError`` when no snapshots exist at all and
    ``SnapshotCorrupt`` when every candidate is bad."""
    candidates = find_snapshots(prefix)
    if not candidates:
        raise FileNotFoundError(f"no {prefix}_iter_*.solverstate* snapshots")
    return _restore_first_valid(
        solver, list(reversed(candidates)), seed, quarantine,
        label="restore_newest_valid", prefix=prefix,
    )


def _restore_first_valid(
    solver, ordered, seed: int, quarantine: bool, label: str, prefix: str
):
    """Walk ``ordered`` candidate state paths (preferred first) and
    restore the first that verifies — the one fallback/quarantine loop
    behind BOTH the plain and the journal-guided resume.  Quarantines
    ONLY evidence of file corruption: a failed manifest check, or (for
    manifest-less legacy snapshots) a truncated/garbage container.
    Anything else — solver mismatch, transient I/O — is a
    caller/environment problem: renaming healthy snapshots for it
    would destroy the very restore points this function protects."""
    import zipfile

    failures = []
    for state_path in ordered:
        try:
            return restore(solver, state_path, seed=seed), state_path
        except (ImportError, ModuleNotFoundError):
            raise  # missing h5py etc: environment problem, not corruption
        except Exception as e:  # noqa: BLE001 — classified below
            failures.append(f"{state_path}: {e}")
            is_corrupt = isinstance(
                e, (SnapshotCorrupt, zipfile.BadZipFile, EOFError)
            )
            _log.warning(
                "%s: skipping %s (%s)%s",
                label,
                state_path,
                e,
                "; quarantining" if (quarantine and is_corrupt)
                else "; left intact",
            )
            if quarantine and is_corrupt:
                _quarantine(state_path)
    raise SnapshotCorrupt(
        "%s: no valid snapshot under prefix %r; all %d candidates "
        "failed:\n%s"
        % (label, prefix, len(ordered), "\n".join(failures))
    )


def _snapshot_iter(state_path: str) -> int:
    return int(state_path.split("_iter_")[-1].split(".")[0])


def restore_newest_valid_journaled(
    solver,
    prefix: str,
    journal,
    seed: int = 0,
    quarantine: bool = True,
):
    """Journal-guided resume: reconcile the run ledger
    (``io/journal.RunJournal``) against the snapshot set and rewind to
    the last COMMITTED round boundary.

    Rules (the exactly-once contract):

    - the ledger's newest committed snapshot ref is the restore target;
      if it fails verification it is quarantined and the scan falls
      back to the next-older candidate,
    - a snapshot NEWER than the committed boundary (published for a
      round whose commit never landed — a kill between the snapshot
      publish and the journal append) is IGNORED: its round is
      uncommitted and must be re-executed, not skipped,
    - a ledger with no commits means round 0 never completed:
      ``FileNotFoundError`` (the caller starts fresh at round 0).
      That is the ONLY FileNotFoundError case — commits whose
      snapshots have vanished raise ``SnapshotCorrupt`` instead:
      training fresh weights while resuming at a committed round
      would silently skip every round the ledger vouches for.

    Returns ``(state, state_path, job_state, info)`` where
    ``job_state`` is the restored snapshot's jobstate companion (None
    for plain snapshots) and ``info`` is ``journal.reconcile()``.
    """
    info = journal.reconcile()
    if info["last_committed_round"] is None:
        raise FileNotFoundError(
            f"journal {journal.path}: no committed round — nothing to "
            "resume (start fresh at round 0)"
        )
    commit_iter = info["commit_iter"]
    candidates = find_snapshots(prefix)
    if commit_iter is not None:
        eligible = [
            p for p in candidates if _snapshot_iter(p) <= commit_iter
        ]
        skipped = len(candidates) - len(eligible)
        if skipped:
            _log.warning(
                "journaled resume: ignoring %d snapshot(s) beyond the "
                "committed boundary (iter %d) — their rounds never "
                "committed and will re-execute",
                skipped, commit_iter,
            )
        candidates = eligible
    if not candidates:
        # the journal vouches for committed work whose durable state is
        # GONE — a fresh init here would silently skip those rounds, so
        # this is a corruption-class failure, never a quiet fresh start
        raise SnapshotCorrupt(
            f"journaled resume: no snapshot at or before the committed "
            f"boundary under {prefix!r} (journal says round "
            f"{info['last_committed_round']} committed)"
        )
    # prefer the exact committed ref, then fall back newest-first
    ref = info["snapshot"]
    ordered = sorted(candidates, key=_snapshot_iter)
    if ref is not None:
        exact = [p for p in ordered if os.path.basename(p) == ref]
        ordered = [p for p in ordered if os.path.basename(p) != ref] + exact
    state, state_path = _restore_first_valid(
        solver, list(reversed(ordered)), seed, quarantine,
        label="journaled resume", prefix=prefix,
    )
    return state, state_path, load_job_state(state_path), info


def load_weights_into_state(solver, state, model_path: str):
    """Warm start from a .caffemodel or .caffemodel.h5 only (the
    ``--weights=`` / ``loadWeightsFromFile`` path, Net.scala:238-240):
    history and iter keep their current values."""
    import jax

    loaded = _load_model_blobs(model_path)
    params, stats = caffemodel.apply_blobs(
        solver.net, jax.device_get(state.params), jax.device_get(state.stats), loaded
    )
    return state._replace(
        params=jax.device_put(params), stats=jax.device_put(stats)
    )
