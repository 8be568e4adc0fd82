"""ctypes bindings to the native runtime library (native/*.cpp).

The reference's ingest decoding and host memory management lived in
native/JVM code outside its repo (the spark-cassandra-connector JAR and
Spark's executor memory manager, reference Dockerfile:5,
submit-heatmap:14-15). Here they are in-repo C++:

- ``parse_csv_batches`` — threaded CSV point decoder with batch
  prefetch (native/pointcodec.cpp). Parsing of batch N+1 overlaps the
  caller's device work on batch N.
- ``StagingPool`` — bounded pool of page-aligned host buffers for
  host->device staging (native/staging.cpp).

The library auto-builds on first import (``make`` in native/) when a
toolchain is present; set ``HEATMAP_TPU_NO_NATIVE_BUILD=1`` to disable.
When the library is unavailable this module still imports, but the
accelerated names are absent — ``from heatmap_tpu.native import
parse_csv_batches`` raises ImportError, which callers (io.sources)
treat as "use the pure-Python path".
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
from typing import Iterator

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_LIB_NAME = "libheatmap_native.so"


def _lib_candidates():
    env = os.environ.get("HEATMAP_TPU_NATIVE_LIB")
    if env:
        yield env
    yield os.path.join(_NATIVE_DIR, "build", _LIB_NAME)
    yield os.path.join(os.path.dirname(__file__), _LIB_NAME)


def build(quiet: bool = True) -> str | None:
    """Build the native library via make; returns its path or None.

    Processes that import this module at once (parallel test workers)
    build one at a time, under an exclusive lock on a file in the build
    directory: the first builds, the others find the library up to date.
    The Makefile links into a temporary file and renames it into place,
    so a reader never sees a half-written library.
    """
    if not os.path.isdir(_NATIVE_DIR):
        return None
    out = subprocess.DEVNULL if quiet else None
    build_dir = os.path.join(_NATIVE_DIR, "build")
    try:
        os.makedirs(build_dir, exist_ok=True)
        with open(os.path.join(build_dir, ".build.lock"), "a") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)  # released when closed
            rc = subprocess.call(["make", "-C", _NATIVE_DIR],
                                 stdout=out, stderr=out)
    except OSError:
        return None
    path = os.path.join(build_dir, _LIB_NAME)
    return path if rc == 0 and os.path.exists(path) else None


def _open_first() -> ctypes.CDLL | None:
    for path in _lib_candidates():
        if os.path.exists(path):
            try:
                return ctypes.CDLL(path)
            except OSError:
                continue
    return None


def _load() -> ctypes.CDLL | None:
    lib = _open_first()
    if lib is not None or os.environ.get("HEATMAP_TPU_NO_NATIVE_BUILD"):
        return lib
    # Build (or wait for another process's build), then look again.
    build()
    return _open_first()


_lib = _load()

if _lib is not None:
    _lib.hm_csv_open.restype = ctypes.c_void_p
    _lib.hm_csv_open.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
    ]
    _lib.hm_csv_peek.restype = ctypes.c_int64
    _lib.hm_csv_peek.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    _lib.hm_csv_take.restype = ctypes.c_int
    _lib.hm_csv_take.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_char_p,
    ]
    _lib.hm_csv_error.restype = ctypes.c_char_p
    _lib.hm_csv_error.argtypes = [ctypes.c_void_p]
    _lib.hm_csv_close.restype = None
    _lib.hm_csv_close.argtypes = [ctypes.c_void_p]
    _lib.hm_ts_missing.restype = ctypes.c_int64

    _lib.hm_pool_create.restype = ctypes.c_void_p
    _lib.hm_pool_create.argtypes = [ctypes.c_int64, ctypes.c_int]
    _lib.hm_pool_acquire.restype = ctypes.c_int
    _lib.hm_pool_acquire.argtypes = [ctypes.c_void_p]
    _lib.hm_pool_try_acquire.restype = ctypes.c_int
    _lib.hm_pool_try_acquire.argtypes = [ctypes.c_void_p]
    _lib.hm_pool_release.restype = None
    _lib.hm_pool_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
    _lib.hm_pool_buffer.restype = ctypes.c_void_p
    _lib.hm_pool_buffer.argtypes = [ctypes.c_void_p, ctypes.c_int]
    _lib.hm_pool_buf_bytes.restype = ctypes.c_int64
    _lib.hm_pool_buf_bytes.argtypes = [ctypes.c_void_p]
    _lib.hm_pool_size.restype = ctypes.c_int
    _lib.hm_pool_size.argtypes = [ctypes.c_void_p]
    _lib.hm_pool_destroy.restype = None
    _lib.hm_pool_destroy.argtypes = [ctypes.c_void_p]

    TS_MISSING = int(_lib.hm_ts_missing())
    # The C sentinel must agree with the canonical Python-side one
    # (pipeline.timespan.TS_MISSING, INT64_MIN) or fast-path missing
    # timestamps would silently stop being detected.
    from heatmap_tpu.pipeline.timespan import TS_MISSING as _PY_TS_MISSING

    assert TS_MISSING == int(_PY_TS_MISSING), (
        f"native TS_MISSING {TS_MISSING} != canonical {_PY_TS_MISSING}"
    )

    def _arena_to_list(buf: bytes, rows: int) -> list:
        # NUL-separated fields, one per row, each NUL-terminated.
        if rows == 0:
            return []
        return buf[:-1].decode("utf-8", "replace").split("\x00")

    def parse_csv_batches(path: str, batch_size: int,
                          queue_depth: int = 3,
                          fast: bool = False,
                          n_workers: int | None = None) -> Iterator[dict]:
        """Columnar batches from a CSV file via the native decoder.

        Default (compat) mode yields the heatmap_tpu.io.sources batch
        layout, with timestamps as Python ints (or None where
        missing/blank) — the pure csv path keeps raw strings;
        downstream never reads them (reference carries but ignores
        timestamp, heatmap.py:33 and SURVEY.md §8 quirk 7).

        ``fast=True`` keeps everything integer — no per-row Python
        objects at all. Batches carry ``latitude``/``longitude`` (f64),
        ``timestamp`` (i64, TS_MISSING sentinel), ``background`` (bool;
        reference heatmap.py:28-29), ``routed`` (i32 ids into the
        reader's routed-group name table, -1 = excluded x-user;
        reference heatmap.py:64-70) and ``new_group_names`` — names the
        consumer hasn't seen yet, in id order, so consumers extend
        their table with ``names += new_group_names``.

        ``n_workers`` defaults to 1 in compat mode (batch order then
        matches the pure-Python reader byte-for-byte) and to the CPU
        count (capped at 8) in fast mode, where the file is parsed in
        parallel byte-range shards and batch order is nondeterministic
        (the aggregation is order-invariant).
        """
        import csv as _csv

        with open(path, newline="") as f:
            header = next(_csv.reader(f), None)
        if header is None:  # zero-byte file: nothing to yield
            return

        def col(name):
            try:
                return header.index(name)
            except ValueError:
                return -1

        lat_c, lon_c = col("latitude"), col("longitude")
        if lat_c < 0 or lon_c < 0:
            raise ValueError(f"{path}: missing latitude/longitude columns")
        if n_workers is None:
            n_workers = min(8, os.cpu_count() or 1) if fast else 1
        handle = _lib.hm_csv_open(
            path.encode(), batch_size, lat_c, lon_c,
            col("user_id"), col("source"), col("timestamp"), queue_depth,
            0 if fast else 1, n_workers,
        )
        if not handle:
            raise OSError(f"native csv open failed for {path}")
        c_dbl = ctypes.POINTER(ctypes.c_double)
        c_i64 = ctypes.POINTER(ctypes.c_int64)
        c_i32 = ctypes.POINTER(ctypes.c_int32)
        c_u8 = ctypes.POINTER(ctypes.c_uint8)
        try:
            while True:
                uid_b = ctypes.c_int64()
                src_b = ctypes.c_int64()
                names_b = ctypes.c_int64()
                rows = _lib.hm_csv_peek(
                    handle, ctypes.byref(uid_b), ctypes.byref(src_b),
                    ctypes.byref(names_b),
                )
                if rows == 0:
                    return
                if rows < 0:
                    err = _lib.hm_csv_error(handle)
                    raise OSError(
                        f"native csv parse failed for {path}: "
                        f"{(err or b'').decode()}"
                    )
                lat = np.empty(rows, np.float64)
                lon = np.empty(rows, np.float64)
                ts = np.empty(rows, np.int64)
                if fast:
                    routed = np.empty(rows, np.int32)
                    bg = np.empty(rows, np.uint8)
                    names_arena = ctypes.create_string_buffer(
                        max(1, names_b.value)
                    )
                    rc = _lib.hm_csv_take(
                        handle,
                        lat.ctypes.data_as(c_dbl),
                        lon.ctypes.data_as(c_dbl),
                        ts.ctypes.data_as(c_i64),
                        None, None,
                        routed.ctypes.data_as(c_i32),
                        bg.ctypes.data_as(c_u8),
                        names_arena,
                    )
                    if rc != 0:
                        raise OSError(
                            "native csv take failed (no pending batch)"
                        )
                    n_new = names_arena.raw[: names_b.value]
                    yield {
                        "latitude": lat,
                        "longitude": lon,
                        "timestamp": ts,
                        "background": bg.astype(bool),
                        "routed": routed,
                        "new_group_names": _arena_to_list(
                            n_new, 1 if names_b.value else 0
                        ),
                    }
                    continue
                uid_arena = ctypes.create_string_buffer(max(1, uid_b.value))
                src_arena = ctypes.create_string_buffer(max(1, src_b.value))
                rc = _lib.hm_csv_take(
                    handle,
                    lat.ctypes.data_as(c_dbl),
                    lon.ctypes.data_as(c_dbl),
                    ts.ctypes.data_as(c_i64),
                    uid_arena,
                    src_arena,
                    None, None, None,
                )
                if rc != 0:
                    raise OSError("native csv take failed (no pending batch)")
                if (ts == TS_MISSING).any():
                    stamps = [None if t == TS_MISSING else int(t)
                              for t in ts.tolist()]
                else:
                    stamps = ts.tolist()
                yield {
                    "latitude": lat,
                    "longitude": lon,
                    "user_id": _arena_to_list(uid_arena.raw[: uid_b.value], rows),
                    "source": _arena_to_list(src_arena.raw[: src_b.value], rows),
                    "timestamp": stamps,
                }
        finally:
            _lib.hm_csv_close(handle)

    class StagingPool:
        """Bounded pool of page-aligned host staging buffers.

        ``acquire(shape, dtype)`` returns ``(id, array)`` where the
        array is a zero-copy numpy view of a pooled buffer; release the
        id once the data has been handed to the device. Blocks when all
        buffers are in flight (back-pressure against compute).

        Views alias pool memory: ``close()`` refuses (raises) while ids
        are outstanding, since freeing under a live view would be a
        use-after-free. Release everything before closing.
        """

        def __init__(self, buf_bytes: int, n_bufs: int = 2):
            self._h = _lib.hm_pool_create(buf_bytes, n_bufs)
            if not self._h:
                raise MemoryError("staging pool allocation failed")
            self.buf_bytes = int(_lib.hm_pool_buf_bytes(self._h))
            self.n_bufs = int(_lib.hm_pool_size(self._h))
            self._outstanding = set()

        def acquire(self, shape, dtype, block: bool = True):
            dtype = np.dtype(dtype)
            need = int(np.prod(shape)) * dtype.itemsize
            if need > self.buf_bytes:
                raise ValueError(
                    f"requested {need} bytes > pool buffer {self.buf_bytes}"
                )
            if block:
                bid = _lib.hm_pool_acquire(self._h)
            else:
                bid = _lib.hm_pool_try_acquire(self._h)
                if bid < 0:
                    return None
            base = _lib.hm_pool_buffer(self._h, bid)
            raw = (ctypes.c_char * self.buf_bytes).from_address(base)
            arr = np.frombuffer(raw, dtype=dtype, count=int(np.prod(shape)))
            self._outstanding.add(bid)
            return bid, arr.reshape(shape)

        def release(self, bid: int):
            self._outstanding.discard(bid)
            _lib.hm_pool_release(self._h, bid)

        def close(self, force: bool = False):
            if getattr(self, "_h", None):
                if self._outstanding and not force:
                    raise RuntimeError(
                        f"staging pool closed with buffers "
                        f"{sorted(self._outstanding)} still acquired — "
                        f"their numpy views would dangle; release them "
                        f"first (or close(force=True) if they are dead)"
                    )
                _lib.hm_pool_destroy(self._h)
                self._h = None

        def __del__(self):
            try:
                self.close(force=True)
            except Exception:
                pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.close()


if _lib is not None:
    _lib.hm_format_blob_bodies.restype = ctypes.c_int64
    _lib.hm_format_blob_bodies.argtypes = [
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    _lib.hm_blobfmt_free.restype = None
    _lib.hm_blobfmt_free.argtypes = [ctypes.c_char_p]

    def format_blob_bodies(rows, cols, values, is_start, zoom: int,
                           n_threads: int | None = None) -> list:
        """NUL-separated '{...}' JSON documents for one sorted level.

        Contract of the numpy join/split path in
        pipeline.cascade.json_blobs_from_level_arrays: one document per
        blob start, aggregate order preserved. ``values`` MUST be
        integral doubles with |v| < 1e15 (the caller checks; cascade
        counts always satisfy it — "%lld.0" is then exactly
        repr(float)).
        """
        import numpy as np

        n = len(rows)
        if n == 0:
            return []
        rows = np.ascontiguousarray(rows, np.int64)
        cols = np.ascontiguousarray(cols, np.int64)
        values = np.ascontiguousarray(values, np.float64)
        starts = np.ascontiguousarray(is_start, np.uint8)
        if n_threads is None:
            n_threads = min(8, os.cpu_count() or 1)
        out = ctypes.c_char_p()
        length = _lib.hm_format_blob_bodies(
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            starts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n, zoom, n_threads, ctypes.byref(out),
        )
        if length < 0:
            raise MemoryError("native blob formatter allocation failed")
        try:
            buf = ctypes.string_at(out, length)
        finally:
            _lib.hm_blobfmt_free(out)
        return buf.decode("ascii").split("\x00")
else:
    format_blob_bodies = None


if _lib is not None:
    _lib.hm_format_blob_ids.restype = ctypes.c_int64
    _lib.hm_format_blob_ids.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int32,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_char_p),
    ]

    def _name_table(names):
        """UTF-8 concat buffer + int64 offsets for a small name array."""
        import numpy as np

        encoded = [str(s).encode("utf-8") for s in names]
        offs = np.zeros(len(encoded) + 1, np.int64)
        np.cumsum([len(b) for b in encoded], out=offs[1:])
        return b"".join(encoded), offs

    def format_blob_ids(user_idx, ts_idx, coarse_row, coarse_col,
                        coarse_zoom: int, user_names, ts_names,
                        n_threads: int | None = None) -> list:
        """'user|timespan|z_r_c' blob id strings, dictionary-decoded
        and formatted in one threaded C pass (the numpy np.char chain
        this replaces was the dominant cost of reference-format JSON
        egress; reference key codec heatmap.py:54-55)."""
        import numpy as np

        n = len(user_idx)
        if not (len(ts_idx) == len(coarse_row) == len(coarse_col) == n):
            raise ValueError(
                f"column length mismatch: user_idx={n} ts_idx={len(ts_idx)} "
                f"coarse_row={len(coarse_row)} coarse_col={len(coarse_col)}"
            )
        if n == 0:
            return []
        user_idx = np.ascontiguousarray(user_idx, np.int32)
        ts_idx = np.ascontiguousarray(ts_idx, np.int32)
        coarse_row = np.ascontiguousarray(coarse_row, np.int32)
        coarse_col = np.ascontiguousarray(coarse_col, np.int32)
        ubuf, uoffs = _name_table(user_names)
        tbuf, toffs = _name_table(ts_names)
        if n_threads is None:
            n_threads = min(8, os.cpu_count() or 1)
        out = ctypes.c_char_p()
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        length = _lib.hm_format_blob_ids(
            user_idx.ctypes.data_as(i32p),
            ts_idx.ctypes.data_as(i32p),
            coarse_row.ctypes.data_as(i32p),
            coarse_col.ctypes.data_as(i32p),
            n, coarse_zoom,
            ubuf, uoffs.ctypes.data_as(i64p), len(user_names),
            tbuf, toffs.ctypes.data_as(i64p), len(ts_names),
            n_threads, ctypes.byref(out),
        )
        if length == -1:
            raise MemoryError("native blob-id formatter allocation failed")
        if length == -2:
            raise ValueError(
                "blob-id dictionary index out of range for its name table"
            )
        if length < 0:
            raise ValueError(f"coarse_zoom out of range: {coarse_zoom}")
        try:
            buf = ctypes.string_at(out, length)
        finally:
            _lib.hm_blobfmt_free(out)
        return buf.decode("utf-8").split("\x00")[:-1]
else:
    format_blob_ids = None


if _lib is not None:
    _lib.hm_decode_keys.restype = ctypes.c_int32
    _lib.hm_decode_keys.argtypes = [
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
    ]

    def decode_keys(keys, code_bits: int, n_threads: int | None = None,
                    morton_only: bool = False):
        """Split composite cascade keys -> (slot, code, row, col).

        One fused multithreaded pass replacing the numpy
        shift/mask/Morton-compact chain in pipeline.cascade
        (decode_level_keys + tilemath.morton.morton_decode_np). With
        ``morton_only=True`` the slot/code columns are neither
        allocated nor written (returned as None) — the Morton-decode
        fast path for tilemath.morton.morton_decode_np.
        """
        import numpy as np

        keys = np.ascontiguousarray(keys, np.int64)
        if keys.ndim != 1:
            raise ValueError(f"keys must be 1-D, got shape {keys.shape}")
        n = len(keys)
        slot = None if morton_only else np.empty(n, np.int32)
        code = None if morton_only else np.empty(n, np.int64)
        row = np.empty(n, np.int32)
        col = np.empty(n, np.int32)
        if n:
            if n_threads is None:
                n_threads = min(8, os.cpu_count() or 1)
            i32p = ctypes.POINTER(ctypes.c_int32)
            rc = _lib.hm_decode_keys(
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                n, code_bits,
                None if slot is None else slot.ctypes.data_as(i32p),
                None if code is None else code.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int64)),
                row.ctypes.data_as(i32p),
                col.ctypes.data_as(i32p),
                n_threads,
            )
            if rc != 0:
                raise ValueError(
                    f"hm_decode_keys rejected code_bits={code_bits}"
                )
        return slot, code, row, col
else:
    decode_keys = None


def available() -> bool:
    """True when the native library loaded (accelerated paths active)."""
    return _lib is not None
