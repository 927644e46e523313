"""Span tracer: one record per finished span, on ``time.perf_counter()``.

``telemetry.span("fwd")`` is a context manager and a decorator.  A
finished span is ONE measurement (start and end on
``time.perf_counter()``, the clock a benchmark's own samples use) with
several sinks, all fed from the same ``__exit__``:

* **the record ring** — a :class:`Record` (name, start, end, id, the id
  of the span that was open on this thread at entry, thread id,
  attributes) appended to a bounded in-memory ring; :func:`records`
  reads it, :func:`self_time` is a span's duration less what its
  children cover, :func:`uncovered` the stretches of an interval that
  no record of a thread covers.  Always on: it costs a
  ``deque.append``.
* the ``mxtpu_span_seconds`` histogram (the per-phase breakdown
  ``report()`` prints) and the per-step sum the JSONL step-log drains;
* the Chrome trace (:func:`mxnet_tpu.profiler.record_event`) while
  ``profiler_set_state('run')`` is on, so spans and the reference-parity
  operator events land in ONE trace file;
* the active ``telemetry.tracing`` trace, as a child span;
* the device profiler: while a ``jax.profiler`` session is running the
  span is also a ``jax.profiler.TraceAnnotation("mxtpu:<name>")``, so
  the host side of the program lies on the device trace's clock.

Spans nest freely (executor.forward inside module.forward inside a fit
step); the parent is whatever span this thread had open, and the trace
event carries the thread id so concurrent prefetcher/consumer spans
render on separate trace rows.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

from .. import profiler
from . import tracing
from .registry import histogram

__all__ = ["span", "Record", "Gap", "record", "records", "clear",
           "self_time", "uncovered", "drain_step_spans",
           "step_span_totals", "RING_SIZE"]

#: records the ring keeps (the newest): some ten a dispatch, so hours of
#: a chained loop and minutes of a per-batch ``Module.fit``
RING_SIZE = 65536


class Record(NamedTuple):
    """One finished span.  ``start``/``end`` are ``time.perf_counter()``
    seconds; ``parent`` is the ``id`` of the span open on the same
    thread at entry (None at a root); ``attrs`` is the dict given at
    entry, or None."""
    name: str
    start: float
    end: float
    id: int
    parent: int | None
    thread: int
    attrs: dict | None


_ring = deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_tls = threading.local()   # .open: this thread's open spans, outermost first
_SPAN, _ID, _START, _SUMMED = range(4)   # an open span's entry, see __enter__
_hist_children = {}        # span name -> its bound mxtpu_span_seconds child
_step_lock = threading.Lock()
_step_spans = {}           # name -> [total_seconds, count] since last step


def _observe(name, dur):
    child = _hist_children.get(name)
    if child is None:   # bound lazily: the catalog import has settled by now
        child = _hist_children[name] = \
            histogram("mxtpu_span_seconds").labels(span=name)
    child.observe(dur)


def _add_step_sum(name, dur):
    with _step_lock:
        acc = _step_spans.get(name)
        if acc is None:
            _step_spans[name] = [dur, 1]
        else:
            acc[0] += dur
            acc[1] += 1


class span:
    """Time a scope::

        with telemetry.span("program.compile", program="trainer.step"):
            ...

    or decorate a function::

        @telemetry.span("data.fetch")
        def next_batch(): ...

    Keyword arguments beyond ``category`` are the record's attributes.
    One instance may be shared (the decorator form re-enters it from
    many threads): enter state lives on the entering thread's stack of
    open spans, not on the instance.
    """

    def __init__(self, name, category="span", **attrs):
        self.name = name
        self.category = category
        self.attrs = attrs or None

    def __enter__(self):
        stack = getattr(_tls, "open", None)
        if stack is None:
            stack = _tls.open = []
        # active trace? this span becomes a child span of it; the cost
        # without a trace is ONE thread-local read (tracing.current)
        ctx = tracing.current()
        if ctx is not None:
            tracing.attach(ctx.child())
        ann = None
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation("mxtpu:" + self.name)
            ann.__enter__()
        # [span, id, start, already in a step's sum, parent id, trace
        #  parent, annotation]
        stack.append([self, next(_ids), time.perf_counter(), False,
                      stack[-1][_ID] if stack else None, ctx, ann])
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        stack = _tls.open
        at = len(stack) - 1
        while stack[at][_SPAN] is not self:
            # exits out of order (a span held open across a generator's
            # yield): take this span's own entry, innermost first
            at -= 1
        _self, sid, start, summed, parent, ctx, ann = stack.pop(at)
        if ann is not None:
            ann.__exit__(*exc)
        _ring.append(Record(self.name, start, end, sid, parent,
                            threading.get_ident(), self.attrs))
        dur = end - start
        if ctx is not None:
            child = tracing.current()
            tracing.detach(ctx)
            if child is not None:
                tracing.record_span(
                    ctx, self.name, tracing.epoch_of(start), dur,
                    span_id=child.span_id,
                    status="error" if exc and exc[0] is not None
                    else None)
        _observe(self.name, dur)
        if not summed:
            _add_step_sum(self.name, dur)
        if profiler.is_running():
            profiler.record_event(
                self.name, profiler.us_of(start), dur * 1e6,
                category=self.category,
                tid=threading.get_ident() % (1 << 31))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with self:
                return fn(*args, **kwargs)
        return wrapper


def record(name, start, end, **attrs):
    """Append a record for an interval its caller timed on
    ``time.perf_counter()`` where no ``span`` could be open round it:
    before this module was imported (``mxnet_tpu.import``), or inside
    JAX (the ``jax.*`` records of ``telemetry.compile``).  Its parent is
    the span open on the calling thread.  Feeds the ring only."""
    stack = getattr(_tls, "open", None)
    _ring.append(Record(name, start, end, next(_ids),
                        stack[-1][_ID] if stack else None,
                        threading.get_ident(), attrs or None))


def clear():
    """Empty the record ring (``telemetry.reset``)."""
    _ring.clear()


def records(prefix=None, since=None, until=None):
    """The ring's records, oldest first: those whose name starts with
    ``prefix``, that started at or after ``since`` and ended at or
    before ``until`` (``time.perf_counter()`` seconds)."""
    return [r for r in list(_ring)
            if (prefix is None or r.name.startswith(prefix))
            and (since is None or r.start >= since)
            and (until is None or r.end <= until)]


def self_time(rec, recs):
    """``rec``'s duration less the part of it its children in ``recs``
    cover (the union of their intervals, so overlapping children on
    other threads count once)."""
    covered, upto = 0.0, rec.start
    for c in sorted((c for c in recs if c.parent == rec.id),
                    key=lambda c: c.start):
        lo, hi = max(c.start, upto), min(c.end, rec.end)
        if hi > lo:
            covered += hi - lo
            upto = hi
    return (rec.end - rec.start) - covered


class Gap(NamedTuple):
    """A stretch no record covers, with the names of the record that
    ends where it starts and of the one that starts where it ends
    (None at the interval's own bounds)."""
    start: float
    end: float
    before: str | None
    after: str | None


def uncovered(since, until, thread=None):
    """The stretches of ``[since, until]`` that no record of ``thread``
    (default: the calling thread) covers, longest first: "why did my
    job take a minute to start" is ``uncovered(t0, now)[:3]`` beside
    the longest records."""
    if thread is None:
        thread = threading.get_ident()
    gaps, upto, before = [], since, None
    for r in sorted((r for r in list(_ring) if r.thread == thread
                     and r.end > since and r.start < until),
                    key=lambda r: r.start):
        if r.start > upto:
            gaps.append(Gap(upto, r.start, before, r.name))
        if r.end > upto:
            upto, before = r.end, r.name
    if until > upto:
        gaps.append(Gap(upto, until, before, None))
    return sorted(gaps, key=lambda g: g.start - g.end)


def drain_step_spans():
    """Spans accumulated since the last drain, as
    ``{name: {"total_s": s, "count": n}}`` — consumed by the JSONL
    step-log so each record carries that step's phase timings.  A span
    still open on the calling thread (``trainer.run_steps`` round its
    own ``step_end``) goes into THIS drain with the time it has run so
    far, and not into the next step's."""
    now = time.perf_counter()
    for entry in getattr(_tls, "open", ()):
        if not entry[_SUMMED]:
            entry[_SUMMED] = True
            _add_step_sum(entry[_SPAN].name, now - entry[_START])
    with _step_lock:
        out = {name: {"total_s": v[0], "count": v[1]}
               for name, v in _step_spans.items()}
        _step_spans.clear()
    return out


def step_span_totals():
    """Non-draining view of the current per-step accumulator."""
    with _step_lock:
        return {name: {"total_s": v[0], "count": v[1]}
                for name, v in _step_spans.items()}
