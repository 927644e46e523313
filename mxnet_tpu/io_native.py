"""ctypes binding to the native IO library (src/recordio.cc).

Reference: the C++ data pipeline (`src/io/iter_prefetcher.h` +
dmlc-core recordio) — here a small C++ shared library with a background
prefetch thread and a bounded queue, auto-built on first use (make -C src)
and loaded via ctypes (the environment has no pybind11; SURVEY §7 native
policy).  Falls back cleanly when no compiler is available — callers
check :func:`available`.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import time

import numpy as np

from . import telemetry
from .telemetry import ioview as _ioview

# per-record counters for the native reader (source label separates it
# from the pure-python recordio path)
_NAT_READS = telemetry.counter(
    "mxtpu_io_records_total").labels(source="native")
_NAT_BAD = telemetry.counter(
    "mxtpu_io_bad_records_total").labels(source="native")

_LIB = None
_TRIED = False
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
_LIB_PATH = os.path.join(_SRC_DIR, "libmxtpu_io.so")


def _load():
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    # the library is not tracked by git: make builds it where it is
    # missing and rebuilds it where a source is newer (a no-op
    # otherwise), so a stale binary is never loaded.  One process at a
    # time: test workers start together
    try:
        with open(os.path.join(_SRC_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(["make", "-C", _SRC_DIR, "libmxtpu_io.so"],
                           check=True, capture_output=True)
    except (OSError, subprocess.CalledProcessError):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    lib.MXTPURecordIOReaderCreate.restype = ctypes.c_void_p
    lib.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p,
                                              ctypes.c_int64]
    lib.MXTPURecordIOReaderFree.argtypes = [ctypes.c_void_p]
    lib.MXTPURecordIOReaderNext.restype = ctypes.c_int64
    lib.MXTPURecordIOReaderNext.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
    lib.MXTPURecordIOReadFloatBatch.restype = ctypes.c_int64
    lib.MXTPURecordIOReadFloatBatch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int64]
    lib.MXTPUImagePipelineHasJpeg.restype = ctypes.c_int
    lib.MXTPUImagePipelineCreate.restype = ctypes.c_void_p
    lib.MXTPUImagePipelineCreate.argtypes = [ctypes.c_char_p] + \
        [ctypes.c_int64] * 10
    lib.MXTPUImagePipelineFree.argtypes = [ctypes.c_void_p]
    lib.MXTPUImagePipelineNextBatch.restype = ctypes.c_int64
    lib.MXTPUImagePipelineNextBatch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
    _LIB = lib
    return lib


def available():
    return _load() is not None


def jpeg_available():
    lib = _load()
    return bool(lib and lib.MXTPUImagePipelineHasJpeg())


class NativeRecordIOReader:
    """Threaded-prefetch sequential reader over the reference .rec format.

    ``skip_bad_records`` (or ``MXNET_TPU_BAD_RECORD_QUOTA``) mirrors the
    pure-python ``MXRecordIO`` tolerant mode: records the native reader
    rejects (oversized / negative return) are counted on ``bad_records``
    and skipped under the quota instead of surfacing as hard errors, and
    the ``recordio.read`` fault seam fires per read so chaos specs cover
    the native path too."""

    def __init__(self, path, queue_cap=64, max_record=1 << 24,
                 skip_bad_records=None):
        lib = _load()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self._path = path
        if skip_bad_records is None:
            from . import config
            skip_bad_records = config.get_int("MXNET_TPU_BAD_RECORD_QUOTA")
        self._bad_quota = int(skip_bad_records)
        self.bad_records = 0
        self._handle = lib.MXTPURecordIOReaderCreate(
            path.encode(), queue_cap)
        if not self._handle:
            raise IOError("cannot open %s" % path)
        self._buf = (ctypes.c_uint8 * max_record)()
        self._max_record = max_record
        self.records_read = 0

    def _note_bad_record(self, exc):
        if self._bad_quota <= 0:
            raise exc
        self.bad_records += 1
        _NAT_BAD.inc()
        if self.bad_records > self._bad_quota:
            raise IOError(
                "%s: bad-record quota exhausted (%d > %d); last "
                "error: %s" % (self._path, self.bad_records,
                               self._bad_quota, exc)) from exc
        import logging
        logging.warning("%s: skipping bad record (%d/%d under quota): "
                        "%s", self._path, self.bad_records,
                        self._bad_quota, exc)

    def read(self):
        """Next record bytes, or None at EOF."""
        from . import resilience
        t0 = time.perf_counter()
        while True:
            dropped = False
            try:
                resilience.fault_point("recordio.read")
            except resilience.FaultInjected as e:
                # the injected fault corrupted this record: count it
                # once and drop it after the (shared) validity checks
                self._note_bad_record(e)
                dropped = True
            n = self._lib.MXTPURecordIOReaderNext(self._handle, self._buf,
                                                  self._max_record)
            if n == 0:
                return None
            if n < 0 or n > self._max_record:
                # the native side returns the FULL record size but only
                # memcpy's min(n, buf_size) bytes: an oversized record
                # would otherwise be returned silently truncated.  Count
                # it against the quota (the record was already consumed)
                # unless the injected fault already claimed it
                if not dropped:
                    self._note_bad_record(IOError(
                        "%s: record of %d bytes exceeds the %d-byte "
                        "staging buffer (or native error)"
                        % (self._path, n, self._max_record)))
                continue
            if dropped:
                continue
            _NAT_READS.inc()
            self.records_read += 1
            _ioview.account("read", time.perf_counter() - t0, items=1,
                            nbytes=int(n))
            return bytes(bytearray(self._buf[:n]))

    def position(self):
        """Advisory reader position (records read by the CONSUMER — the
        native thread's read-ahead never shows here, so this is already
        the next-undelivered record)."""
        return {"offset": self.records_read,
                "bad_records": self.bad_records}

    def state(self):
        from . import io_resume
        return {"v": io_resume.STATE_VERSION, "kind": "native_recordio",
                "offset": self.records_read}

    def restore(self, state):
        """Recreate the native handle and skip forward ``offset``
        records (the native reader is sequential — no byte-seek ABI).
        Validate-then-commit: the skip runs on a fresh handle and the
        old one is only replaced when the cursor landed."""
        from . import io_resume
        from .base import MXNetError
        io_resume.check_state(state, "native_recordio")
        offset = int(state["offset"])
        if offset < 0:
            raise MXNetError("native recordio offset %d < 0" % offset)
        handle = self._lib.MXTPURecordIOReaderCreate(
            self._path.encode(), 64)
        if not handle:
            raise MXNetError("cannot reopen %s for restore" % self._path)
        try:
            for i in range(offset):
                n = self._lib.MXTPURecordIOReaderNext(
                    handle, self._buf, self._max_record)
                if n == 0:
                    raise MXNetError(
                        "%s has only %d records; state expects >= %d — "
                        "the file shrank since the checkpoint"
                        % (self._path, i, offset))
        except BaseException:  # mxlint: allow-broad-except(frees the native reader handle before re-raising — the open iterator is left untouched)
            self._lib.MXTPURecordIOReaderFree(handle)
            raise
        self.close()
        self._handle = handle
        self.records_read = offset

    def read_float_batch(self, batch, record_floats):
        """Parse ``batch`` records of IRHeader+float32 payload into
        (labels, data) numpy arrays in one native call."""
        t0 = time.perf_counter()
        labels = np.zeros(batch, np.float32)
        data = np.zeros((batch, record_floats), np.float32)
        n = self._lib.MXTPURecordIOReadFloatBatch(
            self._handle,
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            record_floats, batch)
        if n > 0:
            _NAT_READS.inc(int(n))
            self.records_read += int(n)
            _ioview.account("read", time.perf_counter() - t0,
                            items=int(n),
                            nbytes=int(n) * (record_floats * 4 + 4))
        return int(n), labels, data

    def close(self):
        if self._handle:
            self._lib.MXTPURecordIOReaderFree(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # mxlint: allow-broad-except(__del__ at interpreter teardown must never raise)
            pass


class ImageRecordIter:
    """High-throughput JPEG .rec iterator — the reference's
    ``ImageRecordIter`` (src/io/iter_image_recordio_2.cc
    ImageRecordIOParser2): a native reader thread + ``preprocess_threads``
    libjpeg decoders + bilinear resize feed whole uint8 batches across the
    C ABI; Python only normalizes and transposes per BATCH, never per
    image.

    Emits (data, label) DataBatches with data float32 NCHW shaped
    ``(batch_size,) + data_shape`` after optional mean/std/scale
    normalization (reference mean_r/g/b, std_r/g/b, scale params).
    Partial tail batches are zero-padded with ``pad`` set, like the
    reference's round_batch handling.
    """

    def __init__(self, path_imgrec, data_shape, batch_size,
                 preprocess_threads=4, mean_r=0.0, mean_g=0.0, mean_b=0.0,
                 std_r=1.0, std_g=1.0, std_b=1.0, scale=1.0, queue_cap=512,
                 raw_uint8=False, shuffle=False, shuffle_buffer=1024,
                 rand_crop=False, rand_mirror=False, seed=0,
                 num_parts=1, part_index=0, round_batch=True, **kwargs):
        if kwargs:
            # fail loudly instead of silently dropping reference options
            # (mean_img, rand_gray, ... are not implemented)
            raise TypeError("ImageRecordIter: unsupported options %s"
                            % sorted(kwargs))
        lib = _load()
        if lib is None or not lib.MXTPUImagePipelineHasJpeg():
            raise RuntimeError("native JPEG pipeline unavailable "
                               "(libmxtpu_io.so without libjpeg)")
        if len(data_shape) != 3 or data_shape[0] != 3:
            raise ValueError("data_shape must be (3, H, W)")
        self._lib = lib
        self._path = path_imgrec
        self.data_shape = tuple(data_shape)
        self.batch_size = batch_size
        self._threads = preprocess_threads
        self._queue_cap = queue_cap
        self._mean = np.array([mean_r, mean_g, mean_b], np.float32)
        self._std = np.array([std_r, std_g, std_b], np.float32)
        self._scale = float(scale)
        # raw_uint8: skip ALL host-side numpy work and emit (N, H, W, 3)
        # uint8 — the TPU fast path (normalize/cast/transpose fuse into
        # the device program; host stays at decode speed)
        self._raw = bool(raw_uint8)
        self._shuffle_buffer = int(shuffle_buffer) if shuffle else 0
        self._rand_crop = bool(rand_crop)
        self._rand_mirror = bool(rand_mirror)
        self._seed = int(seed)
        self._num_parts = int(num_parts)
        self._part_index = int(part_index)
        self._round = bool(round_batch)
        self._epoch = 0
        self._consumed = 0
        self._handle = None
        self._open()
        from .io import DataDesc
        h, w = self.data_shape[1], self.data_shape[2]
        shp = (batch_size, h, w, 3) if self._raw \
            else (batch_size,) + self.data_shape
        self.provide_data = [DataDesc("data", shp)]
        self.provide_label = [DataDesc("softmax_label", (batch_size,))]

    def _open(self):
        self.close()
        h, w = self.data_shape[1], self.data_shape[2]
        # vary aug/shuffle randomness across epochs, deterministically
        self._handle = self._lib.MXTPUImagePipelineCreate(
            self._path.encode(), h, w, self._threads, self._queue_cap,
            self._num_parts, self._part_index,
            int(self._rand_crop), int(self._rand_mirror),
            self._seed + self._epoch * 9973, self._shuffle_buffer)
        if not self._handle:
            raise IOError("cannot open %s" % self._path)

    def __iter__(self):
        return self

    def reset(self):
        self._epoch += 1
        self._consumed = 0
        self._open()

    def position(self):
        """{"epoch", "shard", "num_shards", "offset"} — records consumed
        by the python side (the native decoder threads run ahead of
        this, but only CONSUMED records count: this is already the
        next-undelivered offset; see ``telemetry.ioview``)."""
        return {"epoch": self._epoch, "shard": self._part_index,
                "num_shards": self._num_parts, "offset": self._consumed}

    def state(self):
        from . import io_resume
        return {"v": io_resume.STATE_VERSION, "kind": "image_record",
                "epoch": self._epoch, "shard": self._part_index,
                "num_shards": self._num_parts,
                "offset": int(self._consumed)}

    def restore(self, state):
        """Reopen the native pipeline at the recorded epoch (the seed
        is derived from seed+epoch, so shuffle/augment order reproduces
        exactly) and skip forward to the recorded offset.  The skip
        requests exactly the missing record counts, so offsets off a
        batch boundary restore exactly too."""
        from . import io_resume
        from .base import MXNetError
        io_resume.check_state(state, "image_record")
        if int(state["shard"]) != self._part_index or \
                int(state["num_shards"]) != self._num_parts:
            raise MXNetError(
                "image_record state is for shard %s/%s, iterator is "
                "%d/%d — elastic resharding of the native pipeline is "
                "not supported (use ShardedLedgerIter for elastic "
                "resume)" % (state["shard"], state["num_shards"],
                             self._part_index, self._num_parts))
        offset = int(state["offset"])
        if offset < 0:
            raise MXNetError("image_record offset %d < 0" % offset)
        self._epoch = int(state["epoch"])
        self._consumed = 0
        self._open()
        import ctypes as ct
        h, w = self.data_shape[1], self.data_shape[2]
        labels = np.zeros(self.batch_size, np.float32)
        raw = np.zeros((self.batch_size, h, w, 3), np.uint8)
        while self._consumed < offset:
            want = min(self.batch_size, offset - self._consumed)
            n = self._lib.MXTPUImagePipelineNextBatch(
                self._handle,
                labels.ctypes.data_as(ct.POINTER(ct.c_float)),
                raw.ctypes.data_as(ct.POINTER(ct.c_uint8)), want)
            if n <= 0:
                raise MXNetError(
                    "%s: epoch has only %d records in this shard; "
                    "state expects >= %d — the file shrank since the "
                    "checkpoint" % (self._path, self._consumed, offset))
            self._consumed += int(n)

    def next(self):
        from .io import DataBatch
        from .ndarray import array as nd_array
        h, w = self.data_shape[1], self.data_shape[2]
        labels = np.zeros(self.batch_size, np.float32)
        raw = np.zeros((self.batch_size, h, w, 3), np.uint8)
        import ctypes as ct
        t0 = time.perf_counter()
        n = self._lib.MXTPUImagePipelineNextBatch(
            self._handle, labels.ctypes.data_as(ct.POINTER(ct.c_float)),
            raw.ctypes.data_as(ct.POINTER(ct.c_uint8)), self.batch_size)
        if n <= 0:
            raise StopIteration
        n = int(n)
        _NAT_READS.inc(n)
        self._consumed += n
        # the native pipeline reads + JPEG-decodes behind one call:
        # account it as the decode stage (read is not separable here)
        _ioview.account("decode", time.perf_counter() - t0, items=n,
                        nbytes=int(raw.nbytes))
        if n < self.batch_size and self._round:
            # pad the tail by wrapping real samples (reference round_batch
            # pads with wrapped data, never zero images); pad count lets
            # predict/score slice them off
            for i in range(n, self.batch_size):
                raw[i] = raw[i % n]
                labels[i] = labels[i % n]
        if self._raw:
            return DataBatch(data=[nd_array(raw)], label=[nd_array(labels)],
                             pad=self.batch_size - int(n))
        t1 = time.perf_counter()
        data = raw.astype(np.float32)
        data = (data - self._mean) / self._std * self._scale
        data = np.ascontiguousarray(data.transpose(0, 3, 1, 2))  # NCHW
        # host-side normalize + NCHW transpose is batch-assembly work
        _ioview.account("batch", time.perf_counter() - t1, items=n,
                        nbytes=int(data.nbytes))
        return DataBatch(data=[nd_array(data)], label=[nd_array(labels)],
                         pad=self.batch_size - int(n))

    def __next__(self):
        return self.next()

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.MXTPUImagePipelineFree(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # mxlint: allow-broad-except(__del__ at interpreter teardown must never raise)
            pass
