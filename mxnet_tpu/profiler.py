"""Profiler: Chrome-trace dump + XLA trace capture.

Reference: ``src/engine/profiler.{h,cc}`` + ``python/mxnet/profiler.py``
(SURVEY §5.1) — per-op timing accumulated per device, dumped as
Chrome trace-event JSON.  TPU-native design: two layers.

* Python-level events (executor forward/backward, imperative op dispatch)
  recorded here and dumped in the same Chrome trace-event JSON format the
  reference emits (``Profiler::DumpProfile``, profiler.h:60-117) — so
  existing trace-viewer workflows port unchanged.
* Device-level detail comes from ``jax.profiler`` (xprof) traces started /
  stopped alongside; set ``MXNET_PROFILER_XLA_DIR`` to capture.

Env parity: ``MXNET_PROFILER_AUTOSTART`` honored at import (reference
initialize.cc:40-48 dumps at exit).
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "record_event", "is_running", "now_us", "us_of"]

_state = {
    "mode": "symbolic",      # 'symbolic' | 'all'
    "filename": "profile.json",
    "running": False,
    "events": [],
    "xla_dir": os.environ.get("MXNET_PROFILER_XLA_DIR"),
    "xla_active": False,
}
_lock = threading.Lock()
_t0 = time.perf_counter()


def us_of(t):
    """A ``time.perf_counter()`` reading as microseconds on the
    profiler's clock (trace-event timebase).  Public so the telemetry
    span tracer puts its records on the same axis as the operator
    events recorded here."""
    return (t - _t0) * 1e6


def now_us():
    """Microseconds on the profiler's clock, now."""
    return us_of(time.perf_counter())


_now_us = now_us


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Reference MXSetProfilerConfig (c_api.cc:79-95)."""
    if mode not in ("symbolic", "all", "imperative"):
        raise ValueError("invalid profiler mode %r" % mode)
    # _lock guards ALL _state mutation: config can race span callbacks
    # (telemetry spans fire from prefetcher threads) and dump_profile
    with _lock:
        _state["mode"] = mode
        _state["filename"] = filename


def profiler_set_state(state="stop"):
    """Reference MXSetProfilerState: 'run' | 'stop'."""
    if state == "run":
        with _lock:
            _state["running"] = True
            start_xla = _state["xla_dir"] and not _state["xla_active"]
            if start_xla:
                # claim the slot under the lock (a racing 'run' must
                # not double-start); rolled back below if start fails
                _state["xla_active"] = True
        if start_xla:
            import jax
            try:
                jax.profiler.start_trace(_state["xla_dir"])
            except BaseException:  # mxlint: allow-broad-except(rollback-and-reraise: the flag must not claim a trace that never started)
                with _lock:
                    _state["xla_active"] = False
                raise
    elif state == "stop":
        with _lock:
            _state["running"] = False
            stop_xla = _state["xla_active"]
            if stop_xla:
                _state["xla_active"] = False
        if stop_xla:
            import jax
            jax.profiler.stop_trace()
    else:
        raise ValueError("invalid profiler state %r" % state)


def is_running(imperative=False):
    if not _state["running"]:
        return False
    if imperative and _state["mode"] == "symbolic":
        # reference kOnlySymbolic skips imperative ops
        # (threaded_engine.cc:289-295)
        return False
    return True


def record_event(name, start_us, dur_us, category="operator", tid=0):
    """Append one complete ('X') trace event."""
    with _lock:
        _state["events"].append({
            "name": name, "cat": category, "ph": "X",
            "ts": start_us, "dur": dur_us, "pid": 0, "tid": tid,
        })


class record_scope:
    """Context manager timing a scope into the profile."""

    def __init__(self, name, category="operator", imperative=False):
        self.name = name
        self.category = category
        self.imperative = imperative

    def __enter__(self):
        self.active = is_running(self.imperative)
        self.start = _now_us() if self.active else 0
        return self

    def __exit__(self, *exc):
        if self.active:
            record_event(self.name, self.start, _now_us() - self.start,
                         self.category)


def dump_profile(finished=True):
    """Write Chrome trace-event JSON (reference MXDumpProfile)."""
    with _lock:
        events = list(_state["events"])
        if finished:
            _state["events"] = []
        filename = _state["filename"]
    with open(filename, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return filename


if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    profiler_set_config(mode="all",
                        filename=os.environ.get("MXNET_PROFILER_FILENAME",
                                                "profile.json"))
    profiler_set_state("run")
    atexit.register(dump_profile)
