"""Telemetry: unified metrics, tracing spans, and run reports.

The process-wide observability subsystem.  The reference framework's
only runtime window is the engine profiler's Chrome-trace dump
(``src/engine/profiler.{h,cc}``, SURVEY §5.1); this package keeps that
trace (spans feed it — see :mod:`mxnet_tpu.profiler`) and adds the
aggregation layer every TPU optimization decision needs: per-step cost
attribution, compile accounting, and the scattered robustness counters
(bad-record skips, retries, prefetch stalls, kvstore traffic, watchdog
restarts) absorbed into one registry.

Three engines:

* **metrics registry** (:mod:`.registry`) — thread-safe counters,
  gauges, and fixed-bucket histograms with label support; every metric
  is declared in :data:`CATALOG` (:mod:`.catalog`), and creation of an
  undeclared name raises at the emit site;
* **span tracer** (:mod:`.spans`) — ``telemetry.span("fwd")`` context
  manager/decorator recording wall time per phase, wired through the
  executor, Module, both trainers, and the IO stack; every finished
  span is one record on ``time.perf_counter()`` in a bounded ring
  (``telemetry.spans.records()``), mirrored into the Chrome trace when
  the profiler is running and onto a ``jax.profiler`` trace's host
  plane as ``mxtpu:<name>``;
* **distributed tracing** (:mod:`.tracing`) — W3C-traceparent trace
  context (thread-local + explicitly attachable) giving every serving
  request and training step ONE causal trace: spans entered under an
  active trace record into it, batch fan-in is expressed with span
  links (one dispatch, many parents), retention is tail-sampled
  (errors/sheds + the slow tail always kept, the rest at
  ``MXNET_TPU_TRACE_SAMPLE``), kept traces export as ``mxtpu-trace/1``
  JSONL per rank (``MXNET_TPU_TRACE_DIR``), and latency histograms
  carry per-bucket trace-id exemplars; ``tools/trace_top.py`` ranks,
  reconstructs waterfalls, and attributes the critical path;
* **exporters** (:mod:`.exporters`) — a JSONL step-log
  (``MXNET_TPU_TELEMETRY_JSONL``), Prometheus text format
  (:func:`render_prom`, served on ``MXNET_TPU_TELEMETRY_PORT``), and
  the end-of-run :func:`report` dict ``bench.py`` emits;
* **memory observability** (:mod:`.memory`) — static XLA memory plans
  per compiled program (``memory_analysis``/``cost_analysis`` gauges),
  live ``device.memory_stats()`` sampling at step boundaries, a
  pre-dispatch budget check (``MXNET_TPU_MEMORY_BUDGET``), and
  ``RESOURCE_EXHAUSTED`` annotation with plan + live-buffer forensics;
* **input-pipeline view** (:mod:`.ioview`) — per-stage accounting of
  the data plane (read/decode/augment/batch/host prefetch/device
  staging: wall, items, bytes), time-weighted prefetch-queue occupancy,
  a per-window bottleneck classifier (producer-bound naming the slow
  stage / consumer-bound / balanced), and iterator ``position()``
  tracking riding step records and checkpoint manifests;
  ``tools/io_top.py`` renders the stream;
* **flight recorder** (:mod:`.flight`) — a bounded ring of recent
  structured events dumped to a JSON black box
  (``MXNET_TPU_FLIGHT_DIR``) on MXNetError/OOM/SIGTERM/crash;
  ``tools/flight_read.py`` pretty-prints a dump;
* **cost database** (:mod:`.costdb`) — persistent op/block cost
  records (``MXNET_TPU_COSTDB``, schema ``mxtpu-costdb/1``) joining
  measured wall time, flops/bytes, and fused-block identity into
  MFU/roofline attribution; ``tools/perf_top.py`` ranks the worst
  blocks, ``tools/bench_diff.py`` guards the BENCH trajectory;
* **training-health numerics** (:mod:`.numerics`) — jit-safe in-graph
  tensor stats sampled every ``MXNET_TPU_NUMERICS_EVERY`` steps
  (param/grad/fused-block norms, non-finite counts, value digests,
  global grad norm), anomaly rules with NaN/Inf provenance and a
  strict-mode stop, and the per-step divergence ledger
  ``tools/numdiff.py`` bisects;
* **SLO engine / healthd** (:mod:`.slo`) — the judge over every sensor
  above: a declared rule catalog (threshold / multi-window burn-rate /
  absence / anomaly-passthrough, ``MXNET_TPU_SLO_RULES`` overrides)
  evaluated by an in-process ticker, an alert state machine (pending →
  firing → resolved with debounce) emitting ``mxtpu_alert_*`` metrics
  and ``alert`` flight events, the per-rank ``health()`` verdict
  behind the serving tier's deep ``/healthz``, and fleet-scope rules
  evaluated over the run timeline by ``launch.py``;
  ``tools/health_top.py`` renders live and postmortem views.

Compile events come from one ``jax.monitoring`` listener
(:mod:`.compile`), which also leaves every trace, lowering and compile
of the process in the span ring as a ``jax.*`` record.

See ``docs/api/telemetry.md`` for the full metric catalog, env knobs,
and exporter formats.
"""
from __future__ import annotations

import os as _os

from .catalog import CATALOG, selfcheck
from .registry import (REGISTRY, Registry, Counter, Gauge, Histogram,
                       counter, gauge, histogram)
from . import tracing
from .spans import span, drain_step_spans, step_span_totals
from . import flight
from . import memory
from . import distview
from . import ioview
from . import costdb
from . import numerics
from . import slo
from .exporters import (step_end, jsonl_event, render_prom, report,
                        start_http_server, jsonl_path, env_port, reset,
                        reset_steps)
from . import compile as compile_events
from .exporters import _init_env_state

__all__ = [
    "CATALOG", "selfcheck",
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram",
    "span", "drain_step_spans", "step_span_totals",
    "step_end", "jsonl_event", "render_prom", "report",
    "start_http_server", "jsonl_path", "env_port", "reset",
    "reset_steps", "compile_events",
    "flight", "memory", "distview", "ioview", "costdb", "numerics",
    "slo", "tracing",
]

# process-wide init: compile listener (jax.monitoring) and
# env-derived gauges.  Both are cheap and dependency-light; the http
# endpoint starts only when MXNET_TPU_TELEMETRY_PORT is set.
compile_events.install()
_init_env_state()
# black-box mode: an uncaught crash must leave a flight dump for the
# launch.py watchdog to collect
if flight.dump_dir():
    flight.install_excepthook()
# on-demand live capture: SIGUSR1 (relayed fleet-wide by tools/launch.py
# --capture) writes a bounded profiler window + flight snapshot
if distview.capture_dir():
    distview.install_capture_handler()
# the per-process index offset (env_port) keeps co-located multi-process
# workers from racing to bind ONE fixed port
_port = env_port()
if _port > 0:
    try:
        start_http_server(_port)
    except (OSError, OverflowError, ValueError):
        # OverflowError: out-of-range port (socket.bind raises it, not
        # OSError) — an env typo must not break `import mxnet_tpu`
        import logging as _logging
        _logging.getLogger(__name__).warning(
            "MXNET_TPU_TELEMETRY_PORT=%s: cannot bind the metrics "
            "endpoint; telemetry continues without it",
            _os.environ["MXNET_TPU_TELEMETRY_PORT"])
