"""XLA compile-event capture.

A Learned Performance Model for TPUs (PAPERS.md) treats compile count /
time as first-class run facts: an unexpected recompile per step is the
single most common TPU performance bug.  Two capture modes:

* **jax.monitoring** (preferred): JAX emits a
  ``/jax/core/compile/backend_compile_duration`` duration event per
  backend compile; a process-wide listener feeds
  ``mxtpu_compile_total`` / ``mxtpu_compile_seconds_total``.
* **first-call heuristic** (fallback when the listener API is absent):
  ``report()`` classifies steps whose wall time dwarfs the steady-state
  median as compile-inflated — see
  :func:`mxnet_tpu.telemetry.exporters.report`.
"""
from __future__ import annotations

from .registry import counter

__all__ = ["install", "installed"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_installed = False


def install():
    """Register the jax.monitoring duration listener once per process
    (until then report() uses the step-time heuristic).  Returns True."""
    global _installed
    if _installed:
        return True
    from jax import monitoring
    c_total = counter("mxtpu_compile_total")
    c_secs = counter("mxtpu_compile_seconds_total")

    def _on_duration(name, dur, **kwargs):
        if name == _COMPILE_EVENT:
            c_total.inc()
            c_secs.inc(float(dur))
            from . import flight
            flight.record("compile", duration_s=round(float(dur), 6))

    monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True
    return True


def installed():
    """True when the jax.monitoring listener is active."""
    return bool(_installed)
