"""JAX's trace, lowering and compile events, as counters and span records.

A Learned Performance Model for TPUs (PAPERS.md) treats compile count /
time as first-class run facts: an unexpected recompile per step is the
single most common TPU performance bug.  JAX reports every jaxpr trace,
every lowering to MLIR and every backend compile (the persistent
cache's lookup is inside it, so a cache load is one too) to
``jax.monitoring`` with its wall-clock start and end and the function's
name, whoever called ``jit``.  ONE process-wide time-span listener

* feeds ``mxtpu_compile_total`` / ``mxtpu_compile_seconds_total`` and a
  ``compile`` flight event from each backend compile, and
* appends a ``jax.trace`` / ``jax.lower`` / ``jax.compile`` record with
  the attribute ``fun_name`` to the span ring (``telemetry.spans``), on
  the ``perf_counter`` clock, its parent the span open on the compiling
  thread: a ``jax.compile`` under a ``trainer.run_steps`` record names
  the function that recompiled and the dispatch it happened in.

Two one-line listeners hand it what the time span does not say: a
``jax.compile`` record's ``cache_hit`` (the persistent cache reported a
hit on this thread inside the interval), and how deep in traces the
thread is, because a trace inside a trace (``jax.numpy``'s own jitted
helpers, some thousands a program) leaves no record: it lies in the
outermost one's interval, and the ring is for the run's records.
"""
from __future__ import annotations

import threading
import time

from . import flight, spans
from .registry import counter

__all__ = ["install", "installed"]

_RECORD_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# .hit: a cache hit since the thread's last compile; .depth: traces open
_tls = threading.local()
_installed = False


def install():
    """Register the jax.monitoring listeners once per process.  Returns
    True."""
    global _installed
    if _installed:
        return True
    from jax import monitoring
    c_total = counter("mxtpu_compile_total")
    c_secs = counter("mxtpu_compile_seconds_total")

    def _on_time_span(event, start_time, end_time, fun_name=None, **kwargs):
        name = _RECORD_OF.get(event)
        if name is None:
            return
        if name == "jax.trace":
            _tls.depth = depth = max(0, getattr(_tls, "depth", 1) - 1)
            if depth:
                return
        # JAX's times are time.time(): the interval is laid back from now
        end = time.perf_counter()
        dur = float(end_time - start_time)
        attrs = {"fun_name": fun_name}
        if name == "jax.compile":
            c_total.inc()
            c_secs.inc(dur)
            flight.record("compile", duration_s=round(dur, 6))
            attrs["cache_hit"] = _tls.__dict__.pop("hit", False)
        spans.record(name, end - dur, end, **attrs)

    def _on_event(event, **kwargs):
        if event == _CACHE_HIT:
            _tls.hit = True

    def _on_scalar(event, value, **kwargs):   # JAX says a timed scope opens
        if _RECORD_OF.get(event) == "jax.trace":
            _tls.depth = getattr(_tls, "depth", 0) + 1

    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_scalar)
    _installed = True
    return True


def installed():
    """True when the jax.monitoring listeners are active."""
    return bool(_installed)
