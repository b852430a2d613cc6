"""ctypes bindings for the native runtime (record DB + data pipeline).

The native side (``native/sparknet_runtime/runtime.cpp``) replaces the
reference's C++ data plane: db::DB over LevelDB/LMDB, BlockingQueue,
DataReader's reader thread and DataTransformer.  The library is built
from the tracked sources at first use (``make -C native``; make's
timestamp rule replaces one older than ``runtime.cpp``).  A pure-Python
fallback keeps the record DB and pipeline working where it cannot be
built; ``native_available()`` reports which path is active.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import queue as _queue
from typing import Dict, Iterator, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libsparknet_runtime.so")
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native")

_lib = None
_lib_error: Optional[str] = None


def _load():
    global _lib, _lib_error
    if _lib is not None or _lib_error is not None:
        return _lib
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR], check=True, capture_output=True
        )
        lib = ctypes.CDLL(_LIB_PATH)
    except subprocess.CalledProcessError as e:
        _lib_error = e.stderr.decode("utf-8", "replace") or str(e)
        return None
    except OSError as e:  # no make, or an unloadable library
        _lib_error = str(e)
        return None
    lib.sn_last_error.restype = ctypes.c_char_p
    lib.sndb_open.restype = ctypes.c_void_p
    lib.sndb_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.sndb_put.restype = ctypes.c_int
    lib.sndb_put.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.sndb_commit.argtypes = [ctypes.c_void_p]
    lib.sndb_num_records.restype = ctypes.c_long
    lib.sndb_num_records.argtypes = [ctypes.c_void_p]
    lib.sndb_read.restype = ctypes.c_long
    lib.sndb_read.argtypes = [
        ctypes.c_void_p,
        ctypes.c_long,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_void_p,
        ctypes.c_size_t,
    ]
    lib.sndb_close.argtypes = [ctypes.c_void_p]
    lib.snpipe_create2.restype = ctypes.c_void_p
    lib.snpipe_create2.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_float,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
        ctypes.c_uint,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.snpipe_next2.restype = ctypes.c_int
    lib.snpipe_next2.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
    ]
    lib.snpipe_out_h.restype = ctypes.c_int
    lib.snpipe_out_h.argtypes = [ctypes.c_void_p]
    lib.snpipe_out_w.restype = ctypes.c_int
    lib.snpipe_out_w.argtypes = [ctypes.c_void_p]
    lib.snpipe_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def build() -> bool:
    """(Re)try the native build + load; True when the library is active."""
    global _lib_error
    _lib_error = None
    return _load() is not None


def native_available() -> bool:
    return _load() is not None


def _err(lib) -> str:
    return lib.sn_last_error().decode("utf-8", "replace")


# ---------------------------------------------------------------------------
# RecordDB
# ---------------------------------------------------------------------------


class RecordDB:
    """Record store with transaction-style commits (the ``db::DB`` role;
    the CreateDB path commits explicitly like CreateDB.scala:13-51)."""

    MAGIC = b"SNDB1\x00\x00\x00"

    def __init__(self, path: str, mode: str = "r"):
        self.path = path
        self.mode = mode
        self._lib = _load()
        self._handle = None
        self._py_records = None
        self._py_pending = []
        self._py_out = None
        if self._lib is not None:
            self._handle = self._lib.sndb_open(
                path.encode(), 1 if mode == "w" else 0
            )
            if not self._handle:
                raise IOError(f"sndb_open failed: {_err(self._lib)}")
        elif mode == "w":
            self._py_out = open(path, "wb")
            self._py_out.write(self.MAGIC)
        else:
            self._py_records = self._py_scan(path)

    @classmethod
    def _py_scan(cls, path):
        records = []
        with open(path, "rb") as f:
            if f.read(8) != cls.MAGIC:
                raise IOError(f"bad magic in {path}")
            while True:
                head = f.read(4)
                if not head:
                    break
                klen = int.from_bytes(head, "little")
                key = f.read(klen)
                vlen = int.from_bytes(f.read(4), "little")
                value = f.read(vlen)
                if len(value) != vlen:
                    raise IOError(f"truncated record in {path}")
                records.append((key, value))
        return records

    def put(self, key: bytes, value: bytes):
        if self._handle is not None:
            rc = self._lib.sndb_put(self._handle, key, len(key), value, len(value))
            if rc:
                raise IOError(_err(self._lib))
        else:
            self._py_pending.append((key, value))

    def commit(self):
        if self._handle is not None:
            if self._lib.sndb_commit(self._handle):
                raise IOError(_err(self._lib))
        else:
            for key, value in self._py_pending:
                self._py_out.write(len(key).to_bytes(4, "little"))
                self._py_out.write(key)
                self._py_out.write(len(value).to_bytes(4, "little"))
                self._py_out.write(value)
            self._py_pending.clear()
            self._py_out.flush()

    def __len__(self) -> int:
        if self._handle is not None:
            return int(self._lib.sndb_num_records(self._handle))
        if self._py_records is None:
            return 0
        return len(self._py_records)

    def read(self, idx: int):
        if self._handle is not None:
            size = self._lib.sndb_read(self._handle, idx, None, 0, None, 0)
            if size < 0:
                raise IndexError(_err(self._lib))
            keybuf = ctypes.create_string_buffer(4096)
            buf = ctypes.create_string_buffer(int(size))
            self._lib.sndb_read(self._handle, idx, keybuf, 4096, buf, size)
            return keybuf.value, buf.raw
        return self._py_records[idx]

    def close(self):
        if self._handle is not None:
            self._lib.sndb_close(self._handle)
            self._handle = None
        if self._py_out is not None:
            self._py_out.close()
            self._py_out = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def write_datum_db(
    path: str,
    images: np.ndarray,
    labels: np.ndarray,
    commit_every: int = 1000,
) -> None:
    """Write (N, C, H, W) uint8 images + labels as Datum-style records
    (label + pixel bytes), committing every ``commit_every`` puts like
    the reference's CreateDB.  The label is 1 byte when every label fits
    (CIFAR-scale) or 2 little-endian bytes otherwise (1000-class
    ImageNet); readers infer the width from record length vs the known
    image size."""
    images = np.ascontiguousarray(images, dtype=np.uint8)
    labels = np.asarray(labels)
    if len(labels) and not (
        0 <= int(labels.min()) and int(labels.max()) <= 0xFFFF
    ):
        raise ValueError(
            f"labels outside [0, 65535]: min {labels.min()}, "
            f"max {labels.max()}"
        )
    width = 1 if (len(labels) == 0 or int(labels.max()) <= 0xFF) else 2
    with RecordDB(path, "w") as db:
        for i in range(len(labels)):
            value = int(labels[i]).to_bytes(width, "little") + images[i].tobytes()
            db.put(b"%08d" % i, value)
            if (i + 1) % commit_every == 0:
                db.commit()
        db.commit()


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------

_U64 = (1 << 64) - 1


def _record_rng_stream(seed: int, seq: int):
    """The counter-based splitmix64 stream the native pipeline draws
    per-record crop/mirror randomness from (runtime.cpp splitmix64):
    keyed on (seed, global record sequence number), so output is
    identical for any worker count and both implementations."""
    s = ((seed * 0x9E3779B97F4A7C15) ^ (seq * 0xBF58476D1CE4E5B9)) & _U64

    def next_u64():
        nonlocal s
        s = (s + 0x9E3779B97F4A7C15) & _U64
        z = s
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _U64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _U64
        return z ^ (z >> 31)

    return next_u64


class DataPipeline:
    """Threaded DB -> transformed batches (one reader + N transform
    workers + ordered delivery in native code; Python thread fallback
    otherwise).

    Two output modes:

    - float (default): full DataTransformer semantics on the host —
      ``next()`` returns ``(data f32 (B,C,oh,ow), labels f32 (B,))``.
    - ``u8_output=True``: the host applies only crop *geometry* (uint8
      row copies — the cheap part) and ships the arithmetic to the
      device where it fuses into the training step; ``next()`` returns
      ``(data u8, labels, h_offs i32, w_offs i32, flips u8)``.  Finish
      on device with ``data.transforms.finish_host_crops``.  This is
      the low-byte path for weak host->device links (5x fewer bytes
      than float full-frames).
    """

    def __init__(
        self,
        db_path: str,
        batch_size: int,
        shape: Sequence[int],  # (C, H, W) of stored records
        crop: int = 0,
        mirror: bool = False,
        train: bool = True,
        scale: float = 1.0,
        mean: Optional[np.ndarray] = None,
        seed: int = 0,
        prefetch: int = 3,
        workers: int = 0,  # 0 = cores-1 (native); fallback always 1
        u8_output: bool = False,
    ):
        self.batch_size = batch_size
        c, h, w = (int(x) for x in shape)
        self.c, self.h, self.w = c, h, w
        self.out_h = crop if crop else h
        self.out_w = crop if crop else w
        self.u8_output = bool(u8_output)
        self._lib = _load()
        mean_arr = (
            np.ascontiguousarray(mean, dtype=np.float32).reshape(-1)
            if mean is not None
            else None
        )
        if self._lib is not None:
            mean_ptr = (
                mean_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
                if mean_arr is not None
                else None
            )
            self._handle = self._lib.snpipe_create2(
                db_path.encode(),
                batch_size,
                c,
                h,
                w,
                crop,
                int(mirror),
                int(train),
                scale,
                mean_ptr,
                0 if mean_arr is None else mean_arr.size,
                seed,
                prefetch,
                workers,
                int(u8_output),
            )
            if not self._handle:
                raise IOError(f"snpipe_create failed: {_err(self._lib)}")
        else:
            self._handle = None
            self._py_init(db_path, crop, mirror, train, scale, mean_arr, seed, prefetch)

    # -- python fallback ------------------------------------------------
    def _py_init(self, db_path, crop, mirror, train, scale, mean, seed, prefetch):
        db = RecordDB(db_path, "r")
        if len(db) == 0:
            raise IOError("empty db")
        record_bytes = 1 + self.c * self.h * self.w
        self._py_q: "_queue.Queue" = _queue.Queue(maxsize=prefetch)
        self._py_stop = threading.Event()
        u8 = self.u8_output

        def run():
            idx = 0
            seq = 0
            n = len(db)
            while not self._py_stop.is_set():
                dtype = np.uint8 if u8 else np.float32
                data = np.empty(
                    (self.batch_size, self.c, self.out_h, self.out_w), dtype
                )
                labels = np.empty(self.batch_size, np.float32)
                h_offs = np.zeros(self.batch_size, np.int32)
                w_offs = np.zeros(self.batch_size, np.int32)
                flips = np.zeros(self.batch_size, np.uint8)
                for i in range(self.batch_size):
                    _, value = db.read(idx)
                    idx = (idx + 1) % n
                    if len(value) not in (record_bytes, record_bytes + 1):
                        self._py_q.put(
                            IOError(
                                f"record size mismatch: got {len(value)}, "
                                f"want {record_bytes} or {record_bytes + 1}"
                            )
                        )
                        return
                    # label width (1 or 2 bytes) inferred from length
                    lw = len(value) - (record_bytes - 1)
                    labels[i] = int.from_bytes(value[:lw], "little")
                    img = np.frombuffer(value, np.uint8, offset=lw).reshape(
                        self.c, self.h, self.w
                    )
                    draw = _record_rng_stream(seed, seq)
                    seq += 1
                    ho = wo = 0
                    if crop:
                        if train:
                            ho = draw() % (self.h - crop + 1)
                            wo = draw() % (self.w - crop + 1)
                        else:
                            ho = (self.h - crop) // 2
                            wo = (self.w - crop) // 2
                    flip = bool(mirror and train and (draw() & 1))
                    window = (
                        img[:, ho : ho + crop, wo : wo + crop] if crop else img
                    )
                    if u8:
                        data[i] = window
                        h_offs[i], w_offs[i], flips[i] = ho, wo, flip
                        continue
                    out = window.astype(np.float32)
                    if mean is not None and mean.size == self.c * self.h * self.w:
                        m = mean.reshape(self.c, self.h, self.w)
                        out = out - m[:, ho : ho + self.out_h, wo : wo + self.out_w]
                    elif mean is not None and mean.size == self.c:
                        out = out - mean.reshape(self.c, 1, 1)
                    if flip:
                        out = out[:, :, ::-1]
                    data[i] = out * scale
                item = (
                    (data, labels, h_offs, w_offs, flips)
                    if u8
                    else (data, labels)
                )
                while not self._py_stop.is_set():
                    try:
                        self._py_q.put(item, timeout=0.1)
                        break
                    except _queue.Full:
                        continue

        self._py_thread = threading.Thread(
            target=run, name="snpipe-producer", daemon=True
        )
        self._py_thread.start()

    def next(self):
        """float mode: ``(data f32, labels)``; u8 mode: ``(data u8,
        labels, h_offs, w_offs, flips)``."""
        if self._handle is not None:
            dtype = np.uint8 if self.u8_output else np.float32
            data = np.empty(
                (self.batch_size, self.c, self.out_h, self.out_w), dtype
            )
            labels = np.empty(self.batch_size, np.float32)
            if self.u8_output:
                h_offs = np.empty(self.batch_size, np.int32)
                w_offs = np.empty(self.batch_size, np.int32)
                flips = np.empty(self.batch_size, np.uint8)
                rc = self._lib.snpipe_next2(
                    self._handle,
                    data.ctypes.data_as(ctypes.c_void_p),
                    labels.ctypes.data_as(ctypes.c_void_p),
                    h_offs.ctypes.data_as(ctypes.c_void_p),
                    w_offs.ctypes.data_as(ctypes.c_void_p),
                    flips.ctypes.data_as(ctypes.c_void_p),
                )
                if rc:
                    raise IOError(_err(self._lib))
                return data, labels, h_offs, w_offs, flips
            rc = self._lib.snpipe_next2(
                self._handle,
                data.ctypes.data_as(ctypes.c_void_p),
                labels.ctypes.data_as(ctypes.c_void_p),
                None,
                None,
                None,
            )
            if rc:
                raise IOError(_err(self._lib))
            return data, labels
        item = self._py_q.get()
        if isinstance(item, Exception):
            raise item
        return item

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        return self.next()

    def close(self):
        if self._handle is not None:
            self._lib.snpipe_destroy(self._handle)
            self._handle = None
        elif hasattr(self, "_py_stop"):
            self._py_stop.set()
            try:
                while True:
                    self._py_q.get_nowait()
            except _queue.Empty:
                pass
            self._py_thread.join(timeout=5)
