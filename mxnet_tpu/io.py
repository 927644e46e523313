"""Data iterators.

Reference: ``python/mxnet/io.py`` (859 L: DataDesc/DataBatch/DataIter,
NDArrayIter, ResizeIter, PrefetchingIter) plus the C++ iterators in
``src/io/`` (MNIST: iter_mnist.cc, CSV: iter_csv.cc; the RecordIO image
pipeline lives in :mod:`mxnet_tpu.image`).  TPU-native notes: batches are
host numpy until Module scatters them to devices; PrefetchingIter overlaps
host IO with device compute (the role of dmlc::ThreadedIter,
iter_prefetcher.h).
"""
from __future__ import annotations

import gzip
import os
import struct
import threading
import time
from collections import namedtuple

import numpy as np

from .base import MXNetError
from . import ndarray
from . import resilience
from . import telemetry
from .telemetry import ioview as _ioview
from .ndarray import NDArray, array

__all__ = ["DataDesc", "DataBatch", "DataIter", "DevicePrefetchIter",
           "ResizeIter",
           "PrefetchingIter", "NDArrayIter", "MNISTIter", "CSVIter"]


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Name + shape (+ dtype/layout) of one input (reference io.py:19-75)."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype,
                                          self.layout)

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch:
    """One mini-batch (reference io.py DataBatch)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Base iterator (reference io.py DataIter)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError

    def position(self):
        """Advisory iterator position for the data-plane observability
        layer (``telemetry.ioview``): a JSON-able dict — by convention
        ``{"epoch", "shard", "num_shards", "offset", "resyncs"}``, any
        subset — or None when the iterator tracks nothing.  Rides each
        sampled step's telemetry JSONL record and the checkpoint
        manifest meta.  Wrappers MUST report the next-UNDELIVERED
        sample, not the inner reader's read-ahead position: a
        prefetcher holding staged-but-undelivered batches reports the
        position captured BEFORE those batches were fetched (the
        ``restore(state()) => identical remaining stream`` contract
        depends on it)."""
        return None

    def state(self):
        """Durable iterator state (``mxnet_tpu.io_resume``): a
        JSON-able versioned dict ``{"v", "kind", ...}`` describing the
        next-undelivered sample, or None when this iterator declares no
        durable state.  ``restore(state())`` into a compatible iterator
        must reproduce the identical remaining sample stream.  Wrappers
        delegate inward, compensating for any prefetched-but-
        undelivered batches they hold."""
        return None

    def restore(self, state):
        """Restore a ``state()`` dict.  Validate-then-commit: a
        rejected or failing restore must leave the iterator restartable
        from the same state (the ``io.resume`` chaos seam in
        ``io_resume.restore_iterator`` tests exactly that).  The base
        accepts only None (nothing to restore)."""
        if state is None:
            return
        raise MXNetError(
            "%s declares no durable state and cannot restore %r — "
            "resume with the iterator class that produced the state"
            % (type(self).__name__, state.get("kind")
               if isinstance(state, dict) else state))


class ResizeIter(DataIter):
    """Resize another iterator to ``size`` batches per epoch
    (reference io.py ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad

    def position(self):
        return self.data_iter.position()

    def state(self):
        from . import io_resume
        return {"v": io_resume.STATE_VERSION, "kind": "resize",
                "cur": self.cur, "inner": self.data_iter.state()}

    def restore(self, state):
        from . import io_resume
        io_resume.check_state(state, "resize")
        cur = int(state["cur"])
        if not 0 <= cur <= self.size:
            raise MXNetError("resize cursor %d out of range [0, %d]"
                             % (cur, self.size))
        # inner first (it validates its own state), cursor commits last
        self.data_iter.restore(state["inner"])
        self.cur = cur
        self.current_batch = None


def _safe_state(it):
    """``it.state()`` when the duck-type fits, else None (raw values
    that are not dicts are advisory noise, not durable state)."""
    fn = getattr(it, "state", None)
    st = fn() if callable(fn) else None
    return st if isinstance(st, dict) else None


def _safe_position(it):
    fn = getattr(it, "position", None)
    pos = fn() if callable(fn) else None
    return pos if isinstance(pos, dict) else None


class PrefetchingIter(DataIter):
    """Thread-prefetch over one or more iterators (reference io.py:319;
    C++ analogue iter_prefetcher.h)."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0].shape[0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]
        self.prefetch_errors = [None for _ in range(self.n_iter)]
        # inner state/position captured BEFORE each fetch: while the
        # fetched batch is staged-but-undelivered, the wrapper's
        # state()/position() must describe that batch (the next
        # UNDELIVERED sample), not the reader's read-ahead point
        self.next_state = [None for _ in range(self.n_iter)]
        self.next_position = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                # producer-starved time: this thread is idle because the
                # consumer has not taken the previous batch — a slow
                # consumer must not be misread as a healthy pipeline
                # (the consumer-bound half of the bottleneck verdict)
                t_wait = time.perf_counter()
                self.data_taken[i].wait()
                _ioview.note_starved("host",
                                     time.perf_counter() - t_wait)
                if not self.started:
                    break
                try:
                    self.next_state[i] = _safe_state(self.iters[i])
                    self.next_position[i] = _safe_position(self.iters[i])
                except Exception:  # mxlint: allow-broad-except(advisory capture from arbitrary user iterators must not kill the producer thread and hang the consumer on data_ready)
                    self.next_state[i] = None
                    self.next_position[i] = None
                try:
                    # the io.prefetch fault seam: injected faults retry
                    # with backoff (transient-read semantics); a real —
                    # or exhausted — error is surfaced on the consumer
                    # in iter_next instead of killing this thread and
                    # hanging the consumer on data_ready forever.  The
                    # host_prefetch stage is this window EXCLUSIVE of
                    # the inner stages the upstream next() accounts on
                    # this same thread (read/decode/augment/batch) —
                    # charging them twice would make host_prefetch >=
                    # their sum by construction, so the slowest-stage
                    # verdict could never name the real culprit.  A
                    # kind=delay seam fault (a seeded slow stage) is
                    # outside the inner stages and lands here
                    t_work = time.perf_counter()
                    inner0 = _ioview.thread_accounted()
                    resilience.retry_call(
                        resilience.fault_point, args=("io.prefetch",),
                        retries=2, base_delay=0.01, max_delay=0.1,
                        exceptions=(resilience.FaultInjected,),
                        name="io.prefetch")
                    self.next_batch[i] = self.iters[i].next()
                    inner = _ioview.thread_accounted() - inner0
                    _ioview.account(
                        "host_prefetch",
                        max(0.0, time.perf_counter() - t_work - inner),
                        items=1)
                except StopIteration:
                    self.next_batch[i] = None
                except BaseException as e:  # mxlint: allow-broad-except(stored and re-raised on the consumer thread, not swallowed)
                    self.next_batch[i] = None
                    self.prefetch_errors[i] = e
                self.data_taken[i].clear()
                self.data_ready[i].set()
                # a composite batch counts as staged once EVERY slot is
                # ready; the occupancy tracker owns the depth value (the
                # consumer zeroes it when it takes the batch) and holds
                # it between iter_next calls so scrapes/snapshots see it
                if all(e.is_set() for e in self.data_ready):
                    _ioview.queue_tracker("host").set_depth(1)

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)]
        for t in self.prefetch_threads:
            t.start()

    def __del__(self):
        self.started = False
        for e in self.data_taken:
            e.set()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        for e in self.data_ready:
            e.wait()
        for i in self.iters:
            i.reset()
        for i in range(self.n_iter):
            # pre-fetch captures are from the finished epoch; position()
            # falls back to the live inner until the first new fetch
            self.next_state[i] = None
            self.next_position[i] = None
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        # the staged composite (if any) was discarded above
        _ioview.queue_tracker("host").set_depth(0)

    def iter_next(self):
        # consumer stall: time blocked on the prefetch threads — nonzero
        # totals mean the pipeline (not the device) bounds throughput
        t0 = time.perf_counter()
        for e in self.data_ready:
            e.wait()
        _ioview.note_stall("host", time.perf_counter() - t0)
        errs = [e for e in self.prefetch_errors if e is not None]
        if errs:
            # re-arm EVERY slot before raising so a caller that treats
            # the error as transient can keep iterating: the whole
            # composite batch is dropped (re-arming only the errored
            # slot would leave the other iterators one batch ahead —
            # silently mismatched data/labels for the rest of the epoch)
            for i in range(self.n_iter):
                self.prefetch_errors[i] = None
                self.next_state[i] = None
                self.next_position[i] = None
                self.data_ready[i].clear()
                self.data_taken[i].set()
            raise errs[0]
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iters"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Number of entry mismatches between iters"
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad, self.next_batch[0].index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        for e in self.data_ready:
            e.clear()
        # the captures described the batch just taken; until the
        # producer re-captures, the live inner position IS the next
        # undelivered sample.  Nulled BEFORE data_taken re-arms the
        # producer, so a fresh capture is never clobbered
        for i in range(self.n_iter):
            self.next_state[i] = None
            self.next_position[i] = None
        for e in self.data_taken:
            e.set()
        _ioview.queue_tracker("host").set_depth(0)
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad

    def position(self):
        """Position of the next-UNDELIVERED batch: the first wrapped
        iterator's position captured BEFORE the staged (or in-flight)
        fetch — the producer thread runs one batch ahead of the
        consumer, so the live inner position would over-report by that
        batch.  Falls back to the live inner position before the first
        fetch of an epoch (nothing is staged then)."""
        pos = self.next_position[0]
        return pos if pos is not None else self.iters[0].position()

    def state(self):
        """Durable state of the next-undelivered batch: the inner
        state(s) captured before the staged fetch.  Quiesces first
        (waits for the producers to finish staging, like ``reset``), so
        the captures are stable."""
        from . import io_resume
        for e in self.data_ready:
            e.wait()
        if self.n_iter == 1:
            return self.next_state[0]
        return {"v": io_resume.STATE_VERSION, "kind": "prefetch",
                "inner": list(self.next_state)}

    def restore(self, state):
        """Restore the wrapped iterator(s) and discard any staged
        batch (it belongs to the abandoned stream).  The producer
        threads then refetch from the restored state."""
        from . import io_resume
        if state is None:
            return
        if self.n_iter == 1:
            states = [state]
        else:
            io_resume.check_state(state, "prefetch")
            states = list(state["inner"])
            if len(states) != self.n_iter:
                raise MXNetError(
                    "prefetch state has %d inner entries, wrapper has "
                    "%d iterators" % (len(states), self.n_iter))
        for e in self.data_ready:
            e.wait()                 # quiesce: producers are parked
        for it, st in zip(self.iters, states):
            it.restore(st)           # each tier validates-then-commits
        for i in range(self.n_iter):
            self.prefetch_errors[i] = None
            self.next_state[i] = None
            self.next_position[i] = None
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        # the staged composite (if any) was discarded above
        _ioview.queue_tracker("host").set_depth(0)


def _init_data(data, allow_empty, default_name):
    """Normalize input data to a list of (name, numpy) (reference io.py)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {default_name + "_%d" % i: d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of "
                        "them or dict with them as values")
    out = {}
    for k, v in data.items():
        out[k] = v.asnumpy() if isinstance(v, NDArray) else np.asarray(v)
    return list(sorted(out.items()))


class DevicePrefetchIter:
    """Stage up to ``depth`` batches AHEAD onto the devices.

    The reference prefetcher's role (``src/io/iter_prefetcher.h:27-130``)
    extended across the device boundary: a background thread pulls host
    batches from ``it`` and runs ``stage_fn`` — typically
    ``ShardedTrainer.put_batch`` (device-side transpose/normalize +
    transfer) — so JPEG decode AND host→device transfer overlap the
    previous step's compute instead of serializing with it.  Iterating
    yields whatever ``stage_fn`` returns (a dict of staged device
    arrays, feedable straight to ``trainer.step``).
    """

    def __init__(self, it, stage_fn, depth=2):
        import queue as _queue
        self._it = it
        self._stage = stage_fn
        self._depth = max(1, int(depth))
        self._queue = _queue.Queue(maxsize=self._depth)
        self._thread = None
        self._stop = False
        self._exhausted = False
        # (state, position) of the inner iterator captured BEFORE each
        # fetched-but-undelivered batch, oldest first: the wrapper's
        # state()/position() report pending[0] — the next UNDELIVERED
        # sample — never the inner reader's read-ahead point
        from collections import deque
        self._pending = deque()
        self._plock = threading.Lock()
        self._start()

    def depth(self):
        """Current staging-queue depth bound (a backpressure knob)."""
        return self._depth

    def set_depth(self, depth):
        """Retune the staging depth at runtime — the backpressure
        controller's actuator (io_resume.BackpressureController).
        Raising it lets the worker run further ahead; lowering it takes
        effect as the consumer drains below the new bound (staged
        batches are never discarded)."""
        depth = max(1, int(depth))
        with self._queue.mutex:
            self._queue.maxsize = depth
            self._queue.not_full.notify_all()
        self._depth = depth

    def _to_host_dict(self, batch):
        out = {}
        for desc, arr in zip(self._it.provide_data, batch.data):
            out[desc[0] if not hasattr(desc, "name") else desc.name] = \
                arr.asnumpy()
        for desc, arr in zip(self._it.provide_label or [], batch.label):
            out[desc[0] if not hasattr(desc, "name") else desc.name] = \
                arr.asnumpy()
        return out

    def _put(self, item):
        """Bounded put that gives up when reset() cancels the worker."""
        import queue as _queue
        while not self._stop:
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def _try_put(self, item):
        """Non-blocking put; False when the queue is full (the caller
        holds the item in its double-buffer slot instead)."""
        import queue as _queue
        try:
            self._queue.put_nowait(item)
            return True
        except _queue.Full:
            return False

    def _start(self):
        self._stop = False
        self._exhausted = False

        def worker():
            # payloads are tagged, so a stage_fn returning None or a
            # tuple is never mistaken for a control message.
            #
            # DOUBLE-BUFFERED staging (ISSUE 15): the worker holds up
            # to one staged batch ASIDE of the bounded queue, so when
            # the queue is full (backpressure) the NEXT batch's decode
            # + H2D staging dispatch still proceeds instead of waiting
            # behind the blocked put — the transfer overlaps the
            # current step's compute, and the moment the consumer takes
            # a batch the replacement is already staged (no pipeline
            # bubble of one decode+transfer per take).  An empty queue
            # flushes immediately, so consumer-bound pipelines see no
            # added latency.
            tracker = _ioview.queue_tracker("device")
            held = []        # staged, tracked, awaiting queue space
            # MXNET_TPU_OVERLAP=0 restores the strictly serial
            # decode -> stage -> blocking-put worker (held_cap 0)
            import os as _os
            held_cap = 0 if _os.environ.get(
                "MXNET_TPU_OVERLAP", "1") in ("0", "false", "False") \
                else 1
            try:
                src = iter(self._it)
                while True:
                    if self._stop:
                        return
                    # speculative pre-capture: every fetched-but-
                    # undelivered batch must have its BEFORE-state on
                    # the pending deque while it is in flight, or a
                    # state() read during the fetch would skip it; the
                    # entry is popped right back off when the fetch
                    # turns out to be the end of the epoch
                    pre = (_safe_state(self._it),
                           _safe_position(self._it))
                    with self._plock:
                        self._pending.append(pre)
                    try:
                        batch = next(src)
                    except StopIteration:
                        with self._plock:
                            self._pending.pop()
                        break
                    # opportunistic flush: hand over anything the
                    # consumer made room for, without blocking
                    while held and self._try_put(held[0]):
                        held.pop(0)
                    # io.prefetch fault seam: injected staging faults
                    # retry with backoff; exhaustion surfaces on the
                    # consumer like any other staging error (a
                    # kind=delay fault is a seeded slow device_stage)
                    t_work = time.perf_counter()
                    resilience.retry_call(
                        resilience.fault_point, args=("io.prefetch",),
                        retries=2, base_delay=0.01, max_delay=0.1,
                        exceptions=(resilience.FaultInjected,),
                        name="io.prefetch")
                    host = self._to_host_dict(batch)
                    nbytes = sum(getattr(v, "nbytes", 0)
                                 for v in host.values())
                    staged = self._stage(host)
                    _ioview.account("device_stage",
                                    time.perf_counter() - t_work,
                                    items=1, nbytes=nbytes)
                    # the tracker owns the depth counter: the old
                    # producer/consumer set(qsize()) pair raced and the
                    # exported depth flapped (ISSUE 14 satellite).
                    # Increment BEFORE the put: the consumer decrements
                    # after its take, so depth transiently over-reads by
                    # one instead of under-reading — an underflow would
                    # hit the tracker's 0-clamp and leave a permanent +1
                    # offset (a put that loses the race to a cancelled
                    # reset is settled by reset's set_depth(0))
                    tracker.adjust(+1)
                    held.append(("item", staged))
                    # hand the fresh batch over NOW if the queue has
                    # room — holding it until the next upstream fetch
                    # would add one upstream-production latency to
                    # every take on a producer-bound pipeline
                    while held and self._try_put(held[0]):
                        held.pop(0)
                    # block only once BOTH double-buffer slots are
                    # occupied; the blocked time is producer-starved —
                    # the consumer (the training step) is the slow side
                    while len(held) > held_cap:
                        t_put = time.perf_counter()
                        if not self._put(held[0]):
                            return
                        held.pop(0)
                        _ioview.note_starved(
                            "device", time.perf_counter() - t_put)
                while held:
                    if not self._put(held[0]):
                        return
                    held.pop(0)
            except BaseException as e:  # mxlint: allow-broad-except(surfaced on the consumer via the error queue item)
                # deliver any already-staged batch first: the serial
                # path (held_cap 0) put it before the failing fetch,
                # so the double-buffer must not silently drop it
                while held:
                    if not self._put(held[0]):
                        return
                    held.pop(0)
                self._put(("error", e))
                return
            self._put(("end", None))
        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration     # iterator protocol: stays exhausted
        t0 = time.perf_counter()
        kind, val = self._queue.get()
        _ioview.note_stall("device", time.perf_counter() - t0)
        if kind == "end":
            self._exhausted = True
            raise StopIteration
        if kind == "error":
            self._exhausted = True
            raise val
        # only staged items count toward occupancy (end/error control
        # messages were never tracked in)
        _ioview.queue_tracker("device").adjust(-1)
        with self._plock:
            if self._pending:
                self._pending.popleft()
        return val

    next = __next__

    def position(self):
        """Position of the next-UNDELIVERED batch: the inner position
        captured before the oldest staged (or in-flight) batch — the
        worker runs up to ``depth``+held batches ahead of the consumer,
        so the live inner position would over-report by that much.
        Falls back to the live inner position when nothing is staged."""
        with self._plock:
            if self._pending:
                return self._pending[0][1]
        return self._it.position() if hasattr(self._it, "position") \
            else None

    def state(self):
        """Durable state of the next-undelivered batch (pending[0]'s
        pre-fetch capture), compensating for every staged batch the
        worker ran ahead."""
        with self._plock:
            if self._pending:
                return self._pending[0][0]
        return _safe_state(self._it)

    def restore(self, state):
        """Cancel the worker, discard staged batches (they belong to
        the abandoned stream — a stale worker error goes with them),
        restore the wrapped iterator, restart.  The inner restore
        validates-then-commits, so a failure here leaves the wrapped
        iterator restorable from the same state (the worker is simply
        stopped; a follow-up restore or reset revives it)."""
        if state is None:
            return
        self._cancel_worker()
        if not callable(getattr(self._it, "restore", None)):
            raise MXNetError(
                "%s wraps %s, which has no restore()"
                % (type(self).__name__, type(self._it).__name__))
        self._it.restore(state)
        self._exhausted = False
        self._start()

    def _cancel_worker(self):
        """Stop the worker and drain the queue (staged batches are
        discarded); returns a worker error the consumer never saw."""
        import queue as _queue
        self._stop = True
        pending_error = None
        while self._thread.is_alive() or not self._queue.empty():
            try:
                kind, val = self._queue.get(timeout=0.1)
                if kind == "error":
                    pending_error = val
            except _queue.Empty:
                pass
        self._thread.join()
        with self._plock:
            self._pending.clear()
        _ioview.queue_tracker("device").set_depth(0)
        return pending_error

    def reset(self):
        """Cancel the worker (at most ``depth`` staged batches are
        discarded — a mid-epoch reset must not decode the rest of the
        epoch), rewind the wrapped iterator, restart.  A worker error
        that the consumer never saw is re-raised here rather than
        silently dropped."""
        pending_error = self._cancel_worker()
        if pending_error is not None:
            self._exhausted = True
            raise pending_error
        self._it.reset()
        self._start()


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (reference io.py NDArrayIter)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)

        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            np.random.shuffle(self.idx)
            self.data = [(k, v[self.idx]) for k, v in self.data]
            self.label = [(k, v[self.idx]) for k, v in self.label]

        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - \
                self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]

        self.data_list = [x[1] for x in self.data] + \
            [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle
        self._epochs = 0

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype)
                for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        self._epochs += 1
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + \
                (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def position(self):
        """{"epoch", "offset"}: samples consumed this epoch (advisory —
        see :meth:`DataIter.position`)."""
        return {"epoch": self._epochs,
                "offset": int(min(max(0, self.cursor + self.batch_size),
                                  self.num_data))}

    def state(self):
        """Durable state.  NOTE: a ``shuffle=True`` iterator permutes
        ONCE at construction from the global numpy RNG — an exact
        restore into a fresh process requires seeding ``np.random``
        identically before reconstructing (the order is part of the
        arrays, not of this state)."""
        from . import io_resume
        pos = self.position()
        return {"v": io_resume.STATE_VERSION, "kind": "ndarray",
                "epoch": pos["epoch"], "offset": pos["offset"],
                "num_data": int(self.num_data)}

    def restore(self, state):
        from . import io_resume
        io_resume.check_state(state, "ndarray")
        if int(state["num_data"]) != int(self.num_data):
            raise MXNetError(
                "ndarray state is for %s samples, iterator has %d"
                % (state["num_data"], self.num_data))
        offset = int(state["offset"])
        if not 0 <= offset <= self.num_data:
            raise MXNetError("ndarray offset %d out of range [0, %d]"
                             % (offset, self.num_data))
        self._epochs = int(state["epoch"])
        self.cursor = offset - self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None)
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [array(x[1][self.cursor:self.cursor + self.batch_size])
                    for x in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [array(np.concatenate((x[1][self.cursor:], x[1][:pad]),
                                     axis=0)) for x in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


def _read_idx_file(path, expect_magic):
    """Read an MNIST idx-ubyte file, optionally gzipped
    (reference src/io/iter_mnist.cc:71-150)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    magic, = struct.unpack(">i", buf[:4])
    ndim = magic % 256
    dims = struct.unpack(">" + "i" * ndim, buf[4:4 + 4 * ndim])
    data = np.frombuffer(buf, dtype=np.uint8, offset=4 + 4 * ndim)
    return data.reshape(dims)


class MNISTIter(DataIter):
    """MNIST idx-ubyte reader (reference src/io/iter_mnist.cc:21-254)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128,
                 shuffle=True, flat=False, seed=0, silent=False,
                 num_parts=1, part_index=0, **kwargs):
        super().__init__(batch_size)
        img = _read_idx_file(image, 2051).astype(np.float32) / 255.0
        lab = _read_idx_file(label, 2049).astype(np.float32)
        if num_parts > 1:  # sharded read for data-parallel workers
            n = img.shape[0] // num_parts
            img = img[part_index * n:(part_index + 1) * n]
            lab = lab[part_index * n:(part_index + 1) * n]
        if shuffle:
            rng = np.random.RandomState(seed)
            order = rng.permutation(img.shape[0])
            img, lab = img[order], lab[order]
        if flat:
            img = img.reshape(img.shape[0], -1)
        else:
            img = img.reshape(img.shape[0], 1, img.shape[1], img.shape[2])
        self._part_index = int(part_index)
        self._num_parts = int(num_parts)
        self._inner = NDArrayIter(img, lab, batch_size=batch_size,
                                  last_batch_handle="discard")

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()

    def position(self):
        pos = self._inner.position()
        pos.update(shard=self._part_index, num_shards=self._num_parts)
        return pos

    def state(self):
        return self._inner.state()

    def restore(self, state):
        self._inner.restore(state)


class CSVIter(DataIter):
    """CSV reader (reference src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32,
                          ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros(data.shape[0], dtype=np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size=batch_size,
            last_batch_handle="pad" if round_batch else "discard")

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()

    def position(self):
        return self._inner.position()

    def state(self):
        return self._inner.state()

    def restore(self, state):
        self._inner.restore(state)


def ImageRecordIter(*args, **kwargs):
    """Native JPEG record iterator (reference io.ImageRecordIter,
    src/io/iter_image_recordio_2.cc) — see
    :class:`mxnet_tpu.io_native.ImageRecordIter`.  Requires the native
    library built with libjpeg; use :class:`mxnet_tpu.image.ImageIter`
    as the pure-python fallback."""
    from .io_native import ImageRecordIter as _Native
    return _Native(*args, **kwargs)
