"""HBM accounting: static memory plans, live stats, OOM forensics.

"A Learned Performance Model for TPUs" (PAPERS.md) treats per-program
memory and FLOP/byte cost as primary observables, and XLA already
computes both on every compile — ``compiled.memory_analysis()`` and
``compiled.cost_analysis()``.  This module promotes them into the
telemetry catalog and builds the OOM story on top:

* **version-tolerant accessors** — :func:`memory_analysis_of` /
  :func:`cost_analysis_of` normalize the jax 0.4.x API drift (attribute
  objects vs dicts, list-of-dict cost tables, ``None`` backends) into
  plain dicts; ``examples/memcost/memcost.py`` and
  ``tools/profile_step.py`` use them instead of private copies;
* **memory plans** — :func:`plan_of` + :func:`register_plan` record a
  compiled program's argument/output/temp/generated-code bytes and
  FLOPs/bytes-accessed in the ``mxtpu_memory_plan_bytes`` /
  ``mxtpu_program_flops`` / ``mxtpu_program_bytes_accessed`` gauges and
  a process-wide plan registry the exporters and the flight recorder
  snapshot;
* **live stats** — :func:`sample_live_memory` reads
  ``device.memory_stats()`` (bytes_in_use / peak_bytes_in_use; absent
  on CPU) into the ``mxtpu_hbm_*`` gauges at step boundaries;
* **budget check** — :func:`check_budget` compares a plan against
  device capacity BEFORE the program is dispatched and raises a
  descriptive :class:`~mxnet_tpu.base.MXNetError` with the per-category
  breakdown and remat/batch-size advice, instead of burning a
  dispatch-then-OOM cycle;
* **OOM annotation** — :func:`annotate_oom` catches a backend
  ``RESOURCE_EXHAUSTED`` and re-raises :class:`HbmOomError` carrying
  the plan, the live-bytes snapshot, and the largest live buffers;
* **planned dispatch** — :func:`planned_executable` AOT-compiles a
  jitted function once (no double compile: callers dispatch through
  the returned executable), registering its plan and budget-checking
  it before the first execution.

Knobs: ``MXNET_TPU_MEMORY_BUDGET`` (fraction of capacity the static
plan may use, default 1.0; <=0 disables), ``MXNET_TPU_HBM_LIMIT_BYTES``
(capacity override for backends without ``memory_stats``, e.g. tests
on CPU).  See docs/api/telemetry.md.
"""
from __future__ import annotations

import os
import threading

from ..base import MXNetError
from .registry import counter, gauge
from . import flight
from .spans import span

__all__ = [
    "HbmOomError", "MemoryPlan",
    "memory_analysis_of", "cost_analysis_of", "plan_of",
    "register_plan", "get_plan", "plans_dict", "clear_plans",
    "device_memory_stats", "device_capacity_bytes", "sample_live_memory",
    "budget_fraction", "check_budget", "planned_executable",
    "dispatch_planned",
    "is_oom_error", "annotate_oom", "largest_live_buffers",
]

#: plan byte categories, in breakdown display order
CATEGORIES = ("argument", "output", "temp", "alias", "generated_code")


class HbmOomError(MXNetError):
    """A backend ``RESOURCE_EXHAUSTED`` annotated with the static
    memory plan, the live-bytes snapshot, and the largest live buffers
    (raised by :func:`annotate_oom`; the original error is chained)."""


# ------------------------------------------------- version-tolerant accessors

def memory_analysis_of(compiled):
    """``compiled.memory_analysis()`` as a plain dict of bytes per
    category (:data:`CATEGORIES` keys), or None when the backend does
    not report one.  Tolerates the jax 0.4.x drift: attribute objects
    (``CompiledMemoryStats`` with ``*_size_in_bytes``), plain dicts,
    and ``None`` returns."""
    fn = getattr(compiled, "memory_analysis", None)
    if fn is None:
        return None
    try:
        ma = fn()
    except Exception:  # mxlint: allow-broad-except(memory_analysis availability and failure modes are backend-dependent; absence degrades to no plan, never to a crash)
        return None
    if ma is None:
        return None
    if isinstance(ma, dict):
        src = dict(ma)
    else:
        src = {c: getattr(ma, "%s_size_in_bytes" % c, None)
               for c in CATEGORIES}
    out = {}
    for c in CATEGORIES:
        v = src.get(c, src.get("%s_size_in_bytes" % c))
        if v is not None:
            out[c] = int(v)
    return out or None


def cost_analysis_of(compiled):
    """``compiled.cost_analysis()`` as a plain dict (``flops``,
    ``bytes_accessed``, ``transcendentals`` where reported), or None.
    Tolerates list-of-dict (jax <= 0.4.x), plain-dict (0.5+), and
    absent/None returns."""
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        ca = fn()
    except Exception:  # mxlint: allow-broad-except(cost_analysis availability and failure modes are backend-dependent; absence degrades to no plan, never to a crash)
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for key, names in (("flops", ("flops",)),
                       ("bytes_accessed", ("bytes accessed",
                                           "bytes_accessed")),
                       ("transcendentals", ("transcendentals",))):
        for n in names:
            if n in ca:
                out[key] = float(ca[n])
                break
    return out or None


# ----------------------------------------------------------------- the plan

class MemoryPlan:
    """One compiled program's static footprint: bytes per category from
    ``memory_analysis()`` plus FLOPs / bytes-accessed from
    ``cost_analysis()``."""

    def __init__(self, program, memory=None, cost=None):
        self.program = program
        self.memory = dict(memory or {})
        self.cost = dict(cost or {})

    @property
    def total_bytes(self):
        """Peak HBM the program needs live at once: arguments + outputs
        + temporaries + generated code, minus aliased (donated) bytes
        counted on both sides."""
        m = self.memory
        total = sum(m.get(c, 0) for c in
                    ("argument", "output", "temp", "generated_code"))
        return max(0, total - m.get("alias", 0))

    def as_dict(self):
        d = {"program": self.program,
             "total_bytes": self.total_bytes}
        d.update({"%s_bytes" % c: self.memory[c] for c in CATEGORIES
                  if c in self.memory})
        d.update(self.cost)
        return d

    def breakdown(self):
        """Human-readable per-category byte breakdown, one line."""
        parts = ["%s=%s" % (c, _fmt_bytes(self.memory[c]))
                 for c in CATEGORIES if c in self.memory]
        parts.append("total=%s" % _fmt_bytes(self.total_bytes))
        if "flops" in self.cost:
            parts.append("flops=%.3g" % self.cost["flops"])
        return ", ".join(parts)

    def __repr__(self):
        return "MemoryPlan(%r: %s)" % (self.program, self.breakdown())


def _fmt_bytes(n):
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return ("%.1f %s" if unit != "B" else "%.0f %s") % (n, unit)
        n /= 1024.0


def plan_of(compiled, program):
    """Build a :class:`MemoryPlan` from a compiled executable, or None
    when the backend reports neither memory nor cost analysis."""
    mem = memory_analysis_of(compiled)
    cost = cost_analysis_of(compiled)
    if mem is None and cost is None:
        return None
    return MemoryPlan(program, memory=mem, cost=cost)


_plans_lock = threading.Lock()
_PLANS = {}
_STATIC = {}   # program -> analysis.memlive prediction dict


def note_static_prediction(program, info):
    """Record a bind-time static liveness prediction for ``program``
    (pushed by :func:`mxnet_tpu.analysis.memlive.record_prediction` —
    the dependency points this way so the telemetry layer never imports
    the analysis package).  The budget check and :class:`annotate_oom`
    fold it into their reports, and :func:`register_plan` publishes the
    MXG018 drift gauge once both peaks are known."""
    with _plans_lock:
        _STATIC[program] = dict(info)
        plan = _PLANS.get(program)
    if plan is not None:
        _publish_drift(program, info, plan)


def static_prediction(program):
    """The recorded static prediction for a program name, or None."""
    with _plans_lock:
        return _STATIC.get(program)


def _publish_drift(program, info, plan):
    """``mxtpu_memlive_drift_ratio{program}`` — (static - plan)/plan."""
    peak = int(info.get("peak_bytes") or 0)
    total = int(plan.total_bytes or 0)
    if total > 0:
        gauge("mxtpu_memlive_drift_ratio").labels(program=program).set(
            (peak - total) / float(total))


def register_plan(plan):
    """Record a plan in the process registry and the catalog gauges
    (``mxtpu_memory_plan_bytes{program,category}`` per category plus
    ``total``, ``mxtpu_program_flops``, ``mxtpu_program_bytes_accessed``)
    and note it in the flight ring.  Re-registering a program name
    overwrites (a rebind IS a new plan)."""
    with _plans_lock:
        _PLANS[plan.program] = plan
        static = _STATIC.get(plan.program)
    if static is not None:
        _publish_drift(plan.program, static, plan)
    g = gauge("mxtpu_memory_plan_bytes")
    for c in CATEGORIES:
        if c in plan.memory:
            g.labels(program=plan.program, category=c).set(plan.memory[c])
    g.labels(program=plan.program, category="total").set(plan.total_bytes)
    if "flops" in plan.cost:
        gauge("mxtpu_program_flops").labels(
            program=plan.program).set(plan.cost["flops"])
    if "bytes_accessed" in plan.cost:
        gauge("mxtpu_program_bytes_accessed").labels(
            program=plan.program).set(plan.cost["bytes_accessed"])
    flight.record("memory_plan", program=plan.program,
                  total_bytes=plan.total_bytes, **plan.cost)
    return plan


def get_plan(program):
    """The registered plan for a program name, or None."""
    with _plans_lock:
        return _PLANS.get(program)


def plans_dict():
    """{program: plan dict} snapshot — the report()/flight-dump block."""
    with _plans_lock:
        return {name: p.as_dict() for name, p in sorted(_PLANS.items())}


def clear_plans():
    """Forget every registered plan and static prediction
    (telemetry.reset calls this)."""
    with _plans_lock:
        _PLANS.clear()
        _STATIC.clear()


# ------------------------------------------------------------ live memory

def device_memory_stats(device=None):
    """``device.memory_stats()`` as a dict, or None when the backend
    does not report live memory (CPU, some PJRT plugins).  Default
    device: first local device."""
    try:
        if device is None:
            import jax
            devs = jax.local_devices()
            if not devs:
                return None
            device = devs[0]
        stats = getattr(device, "memory_stats", None)
        stats = stats() if callable(stats) else None
    except Exception:  # mxlint: allow-broad-except(memory_stats is backend-dependent and may raise; live sampling degrades to None, never to a crash)
        return None
    return dict(stats) if stats else None


def device_capacity_bytes(device=None):
    """Usable device memory in bytes: ``memory_stats()['bytes_limit']``
    when the backend reports it, else the ``MXNET_TPU_HBM_LIMIT_BYTES``
    override (tests, CPU), else None (capacity unknown — the budget
    check stays inert)."""
    stats = device_memory_stats(device)
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    try:
        env = int(os.environ.get("MXNET_TPU_HBM_LIMIT_BYTES", "0"))
    except ValueError:
        env = 0
    return env or None


def sample_live_memory():
    """Read every local device's ``memory_stats`` into the
    ``mxtpu_hbm_bytes_in_use`` / ``mxtpu_hbm_peak_bytes`` gauges
    (label: ``platform:id``).  Returns the first device's stats dict,
    or None when no backend reports live memory.  Called at step
    boundaries by ``telemetry.step_end``; cheap when unsupported (one
    None-returning call per device)."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:  # mxlint: allow-broad-except(device enumeration can fail during interpreter teardown or before backend init; sampling degrades to None)
        return None
    first = None
    in_use = gauge("mxtpu_hbm_bytes_in_use")
    peak = gauge("mxtpu_hbm_peak_bytes")
    for d in devs:
        stats = device_memory_stats(d)
        if not stats:
            continue
        label = "%s:%d" % (getattr(d, "platform", "dev"),
                           getattr(d, "id", 0))
        if "bytes_in_use" in stats:
            in_use.labels(device=label).set(stats["bytes_in_use"])
        if "peak_bytes_in_use" in stats:
            peak.labels(device=label).set(stats["peak_bytes_in_use"])
        if first is None:
            first = stats
    return first


# ------------------------------------------------------------ budget check

def budget_fraction():
    """``MXNET_TPU_MEMORY_BUDGET``: fraction of device capacity the
    static plan may use before dispatch raises (default 1.0; a value
    <= 0 disables the check)."""
    try:
        return float(os.environ.get("MXNET_TPU_MEMORY_BUDGET", "1.0"))
    except ValueError:
        return 1.0


def check_budget(plan, capacity=None, fraction=None, device=None):
    """Raise a descriptive :class:`~mxnet_tpu.base.MXNetError` when the
    plan's total bytes exceed ``fraction * capacity`` — BEFORE the
    program is dispatched, so the failure costs no device OOM cycle.
    Inert when capacity is unknown or the check is disabled."""
    if fraction is None:
        fraction = budget_fraction()
    if fraction <= 0 or plan is None:
        return
    if capacity is None:
        capacity = device_capacity_bytes(device)
    if not capacity:
        return
    budget = int(capacity * fraction)
    if plan.total_bytes <= budget:
        return
    flight.record("budget_exceeded", program=plan.program,
                  total_bytes=plan.total_bytes, budget_bytes=budget)
    raise MXNetError(
        "memory budget check: compiled program %r needs %s of device "
        "memory but only %s is budgeted (capacity %s x "
        "MXNET_TPU_MEMORY_BUDGET=%.2f).  Plan breakdown: %s.%s  "
        "Options: reduce the per-device batch size, enable "
        "rematerialization (MXNET_BACKWARD_DO_MIRROR=1), shard more "
        "state over the mesh (tp_rules / pipeline_stages), or raise "
        "the budget fraction if the headroom is intentional."
        % (plan.program, _fmt_bytes(plan.total_bytes),
           _fmt_bytes(budget), _fmt_bytes(capacity), fraction,
           plan.breakdown(), _static_summary(plan.program)))


def _static_summary(program):
    """One sentence comparing the bind-time static prediction with the
    registered XLA plan — both peaks come from the same predictor
    (analysis.memlive), so budget failures name where the bytes go."""
    info = static_prediction(program)
    if not info:
        return ""
    parts = ["  Static liveness prediction: peak %s at %s"
             % (_fmt_bytes(info.get("peak_bytes", 0)),
                info.get("peak_node", "?"))]
    bd = info.get("breakdown") or {}
    cats = ", ".join("%s=%s" % (c, _fmt_bytes(v))
                     for c, v in bd.items() if v)
    if cats:
        parts.append(" (%s)" % cats)
    remats = info.get("remat_candidates") or ()
    if remats:
        r = remats[0]
        parts.append("; top remat candidate %s frees %s at peak"
                     % (r.get("node"),
                        _fmt_bytes(r.get("bytes_freed", 0))))
    zero = int(info.get("zero_saving_per_rank") or 0)
    if zero > 0:
        parts.append("; ZeRO-sharding replicated optimizer state "
                     "would save %s per rank" % _fmt_bytes(zero))
    return "".join(parts) + "."


# ------------------------------------------------------- planned dispatch

def planned_executable(program, fn, args):
    """AOT-compile a jitted function for ``args`` ONCE, register its
    memory plan, budget-check it, and return the executable to dispatch
    through (callers cache it — jax shares no compile cache between
    ``lower().compile()`` and ordinary jit calls, so dispatching the
    returned object is what keeps this a single compile).

    Anything that prevents planning (no ``lower``, lowering failure, a
    backend without analyses) degrades to returning ``fn`` unchanged — the
    plan is observability, only the budget check is allowed to raise."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return fn
    try:
        # the one seam every program of the trainer and the executor
        # compiles through: trace + lower (the graph passes run at
        # trace time) apart from backend compile / cache load
        with span("program.lower", program=program):
            lowered = lower(*args)
        with span("program.compile", program=program):
            compiled = lowered.compile()
    except MXNetError:
        raise
    except Exception as e:  # mxlint: allow-broad-except(AOT lowering is an optimization for plan capture; any backend/tracing failure falls back to the ordinary jit dispatch path)
        import logging
        logging.getLogger(__name__).debug(
            "planned_executable(%s): AOT lowering unavailable (%s: "
            "%s); dispatching via jit without a memory plan",
            program, type(e).__name__, e)
        return fn
    # with the two spans above and the caller's .launch, a first
    # dispatch has no stretch without a record
    with span("program.plan", program=program):
        plan = plan_of(compiled, program)
        if plan is not None:
            register_plan(plan)
            check_budget(plan)
    return compiled


def dispatch_planned(cache, program, fn, args):
    """Dispatch ``fn(*args)`` through its cached AOT executable —
    THE shared hot-path pattern for Executor and ShardedTrainer.

    First call per ``(program, id(fn))``: AOT-compile via
    :func:`planned_executable` (plan registered + budget-checked) and
    cache the executable in the caller-owned ``cache`` dict.  If the
    cached executable later rejects the arguments (aval drift, e.g. a
    partial tail batch), the entry is permanently downgraded to the jit
    wrapper for that fn — jax's own cache then serves every shape with
    no per-call raise/catch — and the registered plan keeps describing
    the first-seen (steady-state) program.

    The call of the executable alone, up to its return, is a
    ``<program>.launch`` span (telemetry.spans)."""
    key = (program, id(fn))
    exe = cache.get(key)
    if exe is None:
        exe = planned_executable(program, fn, args)
        cache[key] = exe
    launch = span(program + ".launch")
    try:
        with launch:
            return exe(*args)
    except TypeError:
        if exe is fn:
            raise
        cache[key] = fn
        flight.record("plan_fallback", program=program)
        with launch:
            return fn(*args)


# ----------------------------------------------------------- OOM forensics

def is_oom_error(exc):
    """True when an exception is a backend (device) out-of-memory: an
    ``XlaRuntimeError``-shaped error whose message carries
    ``RESOURCE_EXHAUSTED`` / out-of-memory markers.  Matched on the
    message, not the type — the concrete error class moved between
    jaxlib versions.  A host-side :class:`MemoryError` is deliberately
    NOT matched: annotating host-RAM exhaustion with HBM advice would
    send the postmortem in the wrong direction."""
    if isinstance(exc, MemoryError):
        return False
    msg = str(exc)
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


def largest_live_buffers(n=8):
    """The ``n`` largest live device arrays as
    ``(nbytes, shape, dtype)`` tuples, largest first — the "what is
    actually occupying HBM" part of an OOM report.  Empty on API
    drift."""
    try:
        import jax
        arrs = jax.live_arrays()
    except Exception:  # mxlint: allow-broad-except(live_arrays is a debugging API that may be absent or raise mid-teardown; forensics degrade to an empty list)
        return []
    sized = []
    for a in arrs:
        try:
            sized.append((int(a.nbytes), tuple(a.shape), str(a.dtype)))
        except Exception:  # mxlint: allow-broad-except(deleted/donated arrays raise on attribute access while still listed; skip them)
            continue
    sized.sort(key=lambda t: -t[0])
    return sized[:n]


class annotate_oom:
    """Context manager around a dispatch: a backend
    ``RESOURCE_EXHAUSTED`` is re-raised as :class:`HbmOomError` whose
    message carries the program's static memory plan, the live-bytes
    snapshot, and the largest live buffers; the event is counted
    (``mxtpu_oom_total``) and recorded in the flight ring.  Non-OOM
    errors pass through untouched.

    ::

        with memory.annotate_oom("trainer.step"):
            out = compiled(*args)
    """

    def __init__(self, program):
        self.program = program

    def __enter__(self):
        return self

    def __exit__(self, etype, exc, tb):
        if exc is None or isinstance(exc, HbmOomError) \
                or not is_oom_error(exc):
            return False
        counter("mxtpu_oom_total").labels(program=self.program).inc()
        plan = get_plan(self.program)
        live = device_memory_stats()
        buffers = largest_live_buffers()
        flight.record(
            "oom", program=self.program,
            plan_total_bytes=plan.total_bytes if plan else None,
            bytes_in_use=(live or {}).get("bytes_in_use"),
            peak_bytes_in_use=(live or {}).get("peak_bytes_in_use"))
        lines = [
            "device out of memory (RESOURCE_EXHAUSTED) while running "
            "%r." % self.program,
        ]
        if plan is not None:
            lines.append("static memory plan: %s." % plan.breakdown())
        else:
            lines.append("static memory plan: none registered for this "
                         "program.")
        if live:
            lines.append(
                "live device memory: bytes_in_use=%s, peak=%s, limit=%s."
                % (_fmt_bytes(live.get("bytes_in_use", 0)),
                   _fmt_bytes(live.get("peak_bytes_in_use", 0)),
                   _fmt_bytes(live["bytes_limit"])
                   if live.get("bytes_limit") else "unknown"))
        else:
            lines.append("live device memory: backend reports no "
                         "memory_stats.")
        if buffers:
            lines.append("largest live buffers: %s." % "; ".join(
                "%s %s %s" % (_fmt_bytes(b), shape, dtype)
                for b, shape, dtype in buffers))
        static = _static_summary(self.program)
        if static:
            lines.append(static.strip())
        lines.append(
            "Advice: reduce the per-device batch size, enable "
            "rematerialization (MXNET_BACKWARD_DO_MIRROR=1), or shard "
            "more state (tp_rules / pipeline_stages).  A flight-recorder "
            "dump of the final seconds is written when "
            "MXNET_TPU_FLIGHT_DIR is set.")
        raise HbmOomError(" ".join(lines)) from exc
