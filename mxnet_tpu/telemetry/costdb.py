"""Persistent op/block cost database: the perf ground truth layer.

ROADMAP item 2 (TVM-style autotuner + learned cost model,
arXiv:1802.04799, arXiv:2008.01040) needs *measured-not-inferred*
training data, and until now every measured signal was ephemeral —
spans die with the process, xprof captures are one-off files, and the
MemoryPlan flops/bytes gauges reset on restart.  This module joins the
three existing-but-disconnected signals into durable records:

* **measured wall time** — the span tracer's dispatch timing (sampled
  at the Executor/ShardedTrainer dispatch seam, synchronized via
  ``jax.block_until_ready`` so the number is device-complete, not
  async-dispatch time);
* **flops + bytes_accessed** — the PR 4 :mod:`.memory` accessors
  (``cost_analysis`` of the compiled program) for program records, and
  analytic shape-derived estimates for fused-block / Pallas-kernel
  records (registered at trace time, when the shapes are in hand);
* **block identity** — the PR 6 ``FusionPlan`` block kind plus the
  Pallas block configuration (``block_q``/``block_k``), so the
  2176-style block-shape cliffs become queryable by (op, shape).

Each record derives **MFU** (``flops / wall_s / peak_flops``) and
**arithmetic intensity** (``flops / bytes_accessed``) against a
per-backend peak table (env-overridable ``MXNET_TPU_PEAK_FLOPS`` /
``MXNET_TPU_PEAK_BW``), yielding a roofline classification:
``bound="compute"`` when AI >= ridge (``peak_flops/peak_bw``), else
``"bandwidth"``.  Block wall time is *attributed*: the measured program
wall is split across the program's fused blocks proportionally to each
block's roofline-attainable time (``max(flops/peak_flops,
bytes/peak_bw)``), so bandwidth-bound blocks surface with exactly the
depressed MFU the roofline predicts — the targeting input
``tools/perf_top.py`` ranks for the future autotuner.

**Collection flow** (all observability — a costdb failure never fails
the dispatch it observes):

1. trace time: :func:`note_block` (``analysis.fusion.apply_block``) and
   :func:`note_kernel` (``ops/pallas_kernels.py``, ``ops/fused.py``)
   register *pending signatures* with shapes/dtypes/flops estimates;
2. dispatch time: :func:`begin_dispatch`/:func:`end_dispatch` around
   ``Executor._dispatch`` / ``ShardedTrainer._launch`` bind
   pending signatures to the program whose compile traced them, and on
   *sampled* dispatches (``MXNET_TPU_COSTDB_SAMPLE``, default every
   16th; the first post-compile dispatch is always sampled; ``0``
   disables measurement) measure a synchronized wall time and record
   the program + its blocks/kernels;
3. persistence: :func:`flush` appends the aggregated records as JSONL
   (schema ``mxtpu-costdb/1``, one record per line) under the
   ``MXNET_TPU_COSTDB`` directory (auto-flushed at interpreter exit
   when the knob is set) and notes a ``costdb_flush`` flight event;
   :func:`read_records` loads/validates a file or directory back.

Metrics: ``mxtpu_block_mfu{block}`` (latest derived MFU per fused
block / kernel) and ``mxtpu_costdb_records_total{kind}`` (records
created in the in-memory database).

Consumers: ``tools/perf_top.py`` (worst-MFU ranking with bound-ness),
``bench.py`` (roll-up embedded in BENCH JSON via :func:`summary`),
``ShardedTrainer.cost_summary()``.  See docs/api/telemetry.md.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import threading
import time

__all__ = [
    "SCHEMA", "CostDB", "DB",
    "db_dir", "sample_every", "backend_name",
    "peak_flops", "peak_bandwidth", "roofline",
    "note_block", "note_kernel", "begin_dispatch", "end_dispatch",
    "bind_pending", "next_scope", "drop_scope",
    "record", "records", "summary", "flush", "reset", "read_records",
]

SCHEMA = "mxtpu-costdb/1"

#: per-backend (peak_flops/s, peak_bytes/s).  ``tpu`` is one TPU v5e
#: chip: 197 TFLOP/s bf16, 819 GB/s HBM (source: Google Cloud
#: documentation, "TPU v5e").  ``cpu`` is a nominal many-core host that
#: anchors the *ratios* the CPU tests read; it is not a measurement.
#: A backend with no row has no peak: its MFU/roofline fields are None.
PEAKS = {
    "tpu": (197e12, 819e9),
    "cpu": (5e11, 1e11),
}

#: accelerator ``device_kind`` -> PEAKS row.  The ``tpu`` row applies
#: only to the kind it was published for; any other accelerator keeps
#: its own kind as backend name and so has no peak.
DEVICE_KIND_ROWS = {"TPU v5 lite": "tpu"}


def db_dir():
    """Persistence directory (``MXNET_TPU_COSTDB``), or None when the
    database is in-memory only (flush becomes a no-op)."""
    return os.environ.get("MXNET_TPU_COSTDB") or None


def sample_every():
    """``MXNET_TPU_COSTDB_SAMPLE``: measure every Nth post-compile
    dispatch per program (default 16; the first post-compile dispatch
    is always measured; ``0`` disables measurement — signatures are
    still collected)."""
    try:
        n = int(os.environ.get("MXNET_TPU_COSTDB_SAMPLE", "16"))
    except ValueError:
        n = 16
    return max(0, n)


_SCOPES = itertools.count(1)


def next_scope():
    """A process-unique dispatch-scope token.  Executor/ShardedTrainer
    take one at construction — and a fresh one on every rebuild — and
    pass ``key=(scope, id(fn))`` to :func:`begin_dispatch`: ``id(fn)``
    alone is reused by the allocator once a discarded function is
    collected, which would let a rebuilt instance's compile dispatch
    masquerade as post-warm and get its multi-second compile timed as
    dispatch wall."""
    return next(_SCOPES)


def backend_name():
    """The peak-table key of the live jax backend: ``cpu`` on the host
    platform, on an accelerator the :data:`DEVICE_KIND_ROWS` row of its
    ``device_kind`` (``tpu`` for a v5e) or, for a kind with no row, the
    kind itself — which has no peak.  ``cpu`` when the backend cannot
    be probed (costdb must never raise)."""
    try:
        import jax
        dev = jax.devices()[0]
    except Exception:  # mxlint: allow-broad-except(backend probing can fail before init or mid-teardown; cost attribution degrades to the cpu peak table)
        return "cpu"
    if dev.platform == "cpu":
        return "cpu"
    return DEVICE_KIND_ROWS.get(dev.device_kind, dev.device_kind)


def _env_float(name):
    v = os.environ.get(name)
    if not v:
        return None
    try:
        return float(v)
    except ValueError:
        return None


def peak_flops(backend=None):
    """Peak FLOPs/s for ``backend`` (default: the live jax backend),
    None for a backend with no :data:`PEAKS` row.
    ``MXNET_TPU_PEAK_FLOPS`` overrides the table — set it when the
    chip generation differs from the baked-in defaults."""
    env = _env_float("MXNET_TPU_PEAK_FLOPS")
    if env and env > 0:
        return env
    return PEAKS.get(backend or backend_name(), (None, None))[0]


def peak_bandwidth(backend=None):
    """Peak memory bytes/s for ``backend`` (default: the live jax
    backend), None for a backend with no :data:`PEAKS` row;
    ``MXNET_TPU_PEAK_BW`` overrides the table."""
    env = _env_float("MXNET_TPU_PEAK_BW")
    if env and env > 0:
        return env
    return PEAKS.get(backend or backend_name(), (None, None))[1]


def roofline(flops, bytes_accessed, wall_s, backend=None):
    """Derive the roofline fields for one record: ``mfu``,
    ``ai`` (arithmetic intensity, flops/byte), ``bound``
    (``compute``/``bandwidth`` by AI vs the ridge point),
    ``attainable_s`` (the roofline-model lower bound on wall time) and
    ``attained_frac`` (attainable/measured — 1.0 means running at the
    roofline).  Fields that cannot be derived are None; never raises."""
    pf = peak_flops(backend)
    pbw = peak_bandwidth(backend)
    out = {"mfu": None, "ai": None, "bound": None,
           "attainable_s": None, "attained_frac": None,
           "peak_flops": pf, "peak_bw": pbw}
    flops = None if flops is None else float(flops)
    bytes_accessed = None if bytes_accessed is None \
        else float(bytes_accessed)
    if flops is not None and wall_s and wall_s > 0 and pf:
        out["mfu"] = flops / wall_s / pf
    if flops is not None and bytes_accessed:
        out["ai"] = flops / bytes_accessed
        if pf and pbw:
            out["bound"] = ("compute" if out["ai"] >= pf / pbw
                            else "bandwidth")
    att = _attainable_s(flops, bytes_accessed, pf, pbw)
    if att is not None:
        out["attainable_s"] = att
        if wall_s and wall_s > 0:
            out["attained_frac"] = min(1.0, att / wall_s)
    return out


def _attainable_s(flops, bytes_accessed, pf, pbw):
    """Roofline lower bound: max(compute time, memory time); None
    where the peaks are unknown."""
    parts = []
    if flops is not None and pf:
        parts.append(flops / pf)
    if bytes_accessed is not None and pbw:
        parts.append(bytes_accessed / pbw)
    return max(parts) if parts else None


def _sig_hash(payload):
    blob = json.dumps(payload, sort_keys=True, default=repr)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:12]


def _shapes_of(args, limit=4):
    """Compact (shapes, dtypes, n_leaves, digest) signature of a
    dispatch's argument pytree — the first ``limit`` leaves spelled
    out for display, plus a digest over EVERY leaf's shape+dtype that
    the record key includes.  Trainer args lead with the params tree,
    so without the full digest a partial-final-batch dispatch (whose
    batch leaf sits past ``limit``) would collapse into the full-batch
    record and corrupt its min-wall MFU."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
    except Exception:  # mxlint: allow-broad-except(signature capture is best-effort observability over arbitrary caller pytrees)
        return [], [], 0, None
    shapes, dtypes = [], []
    for leaf in leaves[:limit]:
        shapes.append(list(getattr(leaf, "shape", ()) or ()))
        dtypes.append(str(getattr(leaf, "dtype", type(leaf).__name__)))
    h = hashlib.sha1()
    for leaf in leaves:
        h.update(repr((tuple(getattr(leaf, "shape", ()) or ()),
                       str(getattr(leaf, "dtype",
                                   type(leaf).__name__)))).encode())
    return shapes, dtypes, len(leaves), h.hexdigest()[:12]


class CostDB:
    """The in-memory aggregate store + pending-signature registry.

    One module-level instance (:data:`DB`) serves the process; tests
    build private ones.  All methods are thread-safe and never raise
    out of the observation path.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._records = {}         # (kind, name, sig) -> record dict
        self._pending = []         # unbound trace-time signatures
        self._bound = {}           # program -> [signature, ...]
        self._counts = {}          # program -> dispatches observed

    # ------------------------------------------------ trace-time notes
    def note_block(self, name, block_kind, shapes, dtypes, flops=None,
                   bytes_accessed=None, block_config=None, layout=None,
                   graph=None, plan=None):
        """Register a fused block traced right now (pending until the
        surrounding program's dispatch binds it).  Called from
        ``analysis.fusion.apply_block`` with trace-time shapes.
        ``graph``/``plan``: the owning graph's structural digest and
        the dispatched plan identity (``greedy`` or a searched
        ``plan-*`` id) — ``tools/perf_top.py --suggest`` joins them
        against the ``graph_plan`` tuning-cache entries.  Never raises
        — it runs inside a jit trace, which must not pay for
        observability."""
        try:
            self._note({
                "kind": "block", "name": str(name),
                "block_kind": block_kind,
                "shapes": [list(s) for s in shapes],
                "dtypes": [str(d) for d in dtypes],
                "flops": None if flops is None else float(flops),
                "bytes_accessed": None if bytes_accessed is None
                else float(bytes_accessed),
                "block_config": dict(block_config) if block_config
                else None,
                "layout": layout, "pallas": False,
                "graph": graph, "plan": plan,
            })
        except MemoryError:  # pragma: no cover - never mask resource exhaustion
            raise
        except Exception:  # mxlint: allow-broad-except(signature capture inside a jit trace; any failure must not fail the compile)
            pass

    def note_kernel(self, op, shapes, dtypes, flops=None,
                    bytes_accessed=None, block_config=None):
        """Register a Pallas kernel instantiation (its chosen block
        shapes keyed by the problem shape — the queryable form of the
        block-size cliffs).  Never raises (jit-trace context, as
        :meth:`note_block`)."""
        try:
            self._note({
                "kind": "kernel", "name": str(op), "block_kind": None,
                "shapes": [list(s) for s in shapes],
                "dtypes": [str(d) for d in dtypes],
                "flops": None if flops is None else float(flops),
                "bytes_accessed": None if bytes_accessed is None
                else float(bytes_accessed),
                "block_config": dict(block_config) if block_config
                else None,
                "layout": None, "pallas": True,
            })
        except MemoryError:  # pragma: no cover - never mask resource exhaustion
            raise
        except Exception:  # mxlint: allow-broad-except(signature capture inside a jit trace; any failure must not fail the compile)
            pass

    @staticmethod
    def _sig_ident(sig):
        """Identity of a trace-time signature: kind + name + shapes +
        block config.  Shapes/config are part of it so two
        instantiations of the same kernel in ONE program (e.g. cross-
        and self-attention flash calls at different seq lengths) both
        survive; a retrace of the SAME instantiation refreshes in
        place."""
        return (sig["kind"], sig["name"],
                json.dumps(sig["shapes"]),
                json.dumps(sig["block_config"], sort_keys=True))

    def _note(self, sig):
        ident = self._sig_ident(sig)
        with self._lock:
            for i, p in enumerate(self._pending):
                if self._sig_ident(p) == ident:
                    self._pending[i] = sig
                    return
            self._pending.append(sig)

    # -------------------------------------------------- dispatch seam
    def begin_dispatch(self, program, key=None):
        """Mark a dispatch of ``program`` beginning; returns the
        observation token :func:`end_dispatch` consumes.  ``key``
        (callers pass ``id(fn)``) scopes the dispatch counter to ONE
        compiled function — program names are fixed strings shared by
        every Executor/Trainer instance, and without the key a second
        instance's compile dispatch would look post-warm and get
        timed.  The first observed dispatch per (program, key) is the
        compile and is never timed; afterwards every Nth
        (``MXNET_TPU_COSTDB_SAMPLE``) is, starting with the first
        post-compile one."""
        ckey = (program, key)
        with self._lock:
            count = self._counts.get(ckey, 0)
            self._counts[ckey] = count + 1
        n = sample_every()
        sampled = (n > 0 and count > 0
                   and (n <= 1 or count % n == 1))
        return (program, key,
                time.perf_counter() if sampled else None)

    def end_dispatch(self, obs, out=None, args=None, mesh=None,
                     failed=False, steps=1):
        """Close a dispatch observation: bind any signatures the
        compile just traced to this program, and on sampled dispatches
        synchronize on ``out`` and record the program + its bound
        blocks/kernels.  ``steps``: how many training steps the ONE
        dispatch executed (``run_steps`` chains N inside one program
        while the trace — whose flops the signatures carry — covers a
        single step; the measured wall is divided by it so per-step
        flops meet per-step wall).  ``failed=True`` (the dispatch
        raised) still binds — otherwise the signatures would dangle
        and bind to whatever program dispatches next — but never
        times.  Swallows every failure — observability must not fail
        the train step."""
        try:
            self._end_dispatch(obs, out, args, mesh, failed, steps)
        except Exception:  # mxlint: allow-broad-except(cost recording is observability wrapped around the training hot path; any failure here must never fail the dispatch it measured)
            pass

    def bind_pending(self, program, key=None):
        """Bind every pending trace-time signature to the (program,
        key) dispatch scope — ``key`` is the caller's ``id(fn)``, so
        two Executor/Trainer instances sharing the fixed program-name
        strings cannot cross-attribute each other's blocks.  One drain
        is one compile's burst: for each (kind, name) present in the
        burst, the burst's instantiation set REPLACES the previously
        bound set of that (kind, name) — so a retrace with new shapes
        (partial final batch) cannot stack a second shape variant that
        would forever split the attributed wall, while a single trace
        carrying several instantiations of one kernel (different seq
        lengths) keeps them all.  Multi-process dispatch paths call
        this directly (bind-only, no timing)."""
        with self._lock:
            if not self._pending:
                return
            burst_names = {(s["kind"], s["name"]) for s in self._pending}
            bound = self._bound.setdefault((program, key), [])
            bound[:] = [s for s in bound
                        if (s["kind"], s["name"]) not in burst_names]
            bound.extend(self._pending)
            self._pending = []

    def _end_dispatch(self, obs, out, args, mesh, failed=False,
                      steps=1):
        program, key, t0 = obs
        self.bind_pending(program, key=key)
        if t0 is None or failed:
            return
        import jax
        from .spans import span
        # the one host sync this module adds to a dispatch, as a record
        # of its own: counting ``<program>.sync`` records counts syncs
        with span(program + ".sync", category="costdb"):
            jax.block_until_ready(out)
        # per-step wall: a run_steps chain is `steps` full updates in
        # one dispatch, and the bound signatures carry ONE step's flops
        wall = (time.perf_counter() - t0) / max(1, int(steps))
        backend = backend_name()
        mesh_d = dict(mesh) if mesh else None
        shapes, dtypes, n_leaves, digest = _shapes_of(args)
        from . import memory as memory_mod
        plan = memory_mod.get_plan(program)
        cost = plan.cost if plan is not None else {}
        # the compiled chain's cost_analysis covers all `steps` too:
        # scale both sides so per-step flops meet per-step wall
        scale = 1.0 / max(1, int(steps))
        self.record(
            "program", program, wall_s=wall,
            flops=None if cost.get("flops") is None
            else cost["flops"] * scale,
            bytes_accessed=None if cost.get("bytes_accessed") is None
            else cost["bytes_accessed"] * scale,
            shapes=shapes, dtypes=dtypes, n_leaves=n_leaves,
            leaves_digest=digest,
            mesh=mesh_d, backend=backend, program=program)
        with self._lock:
            sigs = list(self._bound.get((program, key), ()))
        if not sigs:
            return
        # attribute the measured wall across the program's blocks by
        # roofline-attainable share: a bandwidth-bound block's MFU then
        # lands exactly where the roofline predicts it
        pf, pbw = peak_flops(backend), peak_bandwidth(backend)
        atts = [_attainable_s(s["flops"], s["bytes_accessed"], pf, pbw)
                or 0.0 for s in sigs]
        total_att = sum(atts)
        for sig, att in zip(sigs, atts):
            wall_b = (wall * att / total_att) if total_att > 0 else None
            self.record(
                sig["kind"], sig["name"], wall_s=wall_b,
                flops=sig["flops"],
                bytes_accessed=sig["bytes_accessed"],
                shapes=sig["shapes"], dtypes=sig["dtypes"],
                mesh=mesh_d, backend=backend, program=program,
                block_kind=sig["block_kind"],
                block_config=sig["block_config"],
                layout=sig["layout"], pallas=sig["pallas"],
                graph=sig.get("graph"), plan=sig.get("plan"),
                source="span+roofline-attribution")

    # ------------------------------------------------------- records
    def record(self, kind, name, wall_s=None, flops=None,
               bytes_accessed=None, shapes=(), dtypes=(), n_leaves=None,
               leaves_digest=None,
               mesh=None, backend=None, program=None, block_kind=None,
               block_config=None, layout=None, pallas=None,
               graph=None, plan=None,
               source="span"):
        """Upsert one aggregate record.  The record key is (kind, name,
        signature-hash of shapes/dtypes/mesh/backend/block config) —
        re-observations of the same key aggregate (count, min/mean
        wall) and the roofline fields are re-derived from the *minimum*
        observed wall (the least-noise estimate, the convention
        benchmarking uses).  ``graph``/``plan`` (block records) name
        the owning graph digest and the dispatched fusion-plan
        identity; the latest observation wins — they annotate, and do
        not split, the record key."""
        backend = backend or backend_name()
        key_payload = {
            "shapes": [list(s) for s in shapes],
            "dtypes": [str(d) for d in dtypes],
            "n_leaves": n_leaves, "leaves_digest": leaves_digest,
            "mesh": mesh, "backend": backend,
            "block_config": block_config, "block_kind": block_kind,
        }
        sig = _sig_hash(key_payload)
        key = (kind, str(name), sig)
        wall_s = None if wall_s is None else float(wall_s)
        with self._lock:
            rec = self._records.get(key)
            created = rec is None
            if created:
                rec = {
                    "schema": SCHEMA, "kind": kind, "name": str(name),
                    "sig": sig, "program": program,
                    "block_kind": block_kind,
                    "block_config": block_config,
                    "layout": layout, "pallas": pallas,
                    "shapes": key_payload["shapes"],
                    "dtypes": key_payload["dtypes"],
                    "n_leaves": n_leaves,
                    "leaves_digest": leaves_digest,
                    "mesh": mesh, "backend": backend,
                    "graph": graph, "plan": plan,
                    "count": 0, "wall_s": None, "mean_wall_s": None,
                    "total_wall_s": 0.0,
                    "flops": None, "bytes_accessed": None,
                    "source": source,
                }
                self._records[key] = rec
            if flops is not None:
                rec["flops"] = float(flops)
            if bytes_accessed is not None:
                rec["bytes_accessed"] = float(bytes_accessed)
            if graph is not None:
                rec["graph"] = graph
            if plan is not None:
                rec["plan"] = plan
            if program is not None:
                rec["program"] = program
            rec["ts"] = round(time.time(), 6)
            if wall_s is not None:
                rec["count"] += 1
                rec["total_wall_s"] += wall_s
                rec["wall_s"] = wall_s if rec["wall_s"] is None \
                    else min(rec["wall_s"], wall_s)
                rec["mean_wall_s"] = rec["total_wall_s"] / rec["count"]
            rec.update(roofline(rec["flops"], rec["bytes_accessed"],
                                rec["wall_s"], backend))
            mfu = rec["mfu"]
        self._emit_metrics(kind, name, created, mfu)
        return key

    def _emit_metrics(self, kind, name, created, mfu):
        try:
            from .registry import counter, gauge
            if created:
                counter("mxtpu_costdb_records_total").labels(
                    kind=kind).inc()
            if mfu is not None and kind in ("block", "kernel"):
                gauge("mxtpu_block_mfu").labels(block=str(name)).set(mfu)
        except Exception:  # mxlint: allow-broad-except(metric emission is observability; a registry failure must not fail the recording path)
            pass

    def records(self):
        """Snapshot of every aggregate record (copies, JSON-ready)."""
        with self._lock:
            return [dict(r) for r in self._records.values()]

    def summary(self, top=5):
        """Roll-up dict for reports: record/kind counts, per-program
        measured wall + MFU, and the ``top`` worst-MFU blocks/kernels
        — the block the autotuner should look at first leads."""
        recs = self.records()
        by_kind = {}
        for r in recs:
            by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
        programs = {}
        for r in recs:
            if r["kind"] != "program" or r["wall_s"] is None:
                continue
            programs[r["name"]] = {
                "wall_s": round(r["wall_s"], 6),
                "flops": r["flops"],
                "bytes_accessed": r["bytes_accessed"],
                "mfu": None if r["mfu"] is None else round(r["mfu"], 4),
                "bound": r["bound"],
                "count": r["count"],
            }
        ranked = sorted(
            (r for r in recs if r["kind"] in ("block", "kernel")
             and r["mfu"] is not None),
            key=lambda r: r["mfu"])
        worst = [{
            "name": r["name"], "kind": r["kind"],
            "block_kind": r["block_kind"],
            "mfu": round(r["mfu"], 4), "bound": r["bound"],
            "block_config": r["block_config"],
        } for r in ranked[:top]]
        return {
            "schema": SCHEMA,
            "records": len(recs),
            "by_kind": by_kind,
            "backend": backend_name(),
            "peak_flops": peak_flops(),
            "peak_bw": peak_bandwidth(),
            "programs": programs,
            "worst_mfu": worst,
        }

    # --------------------------------------------------- persistence
    def flush(self, directory=None):
        """Append the current aggregates to
        ``<dir>/costdb-<pid>.jsonl`` (``directory`` defaults to
        ``MXNET_TPU_COSTDB``; no directory -> no-op returning None).
        Each line is one self-describing ``mxtpu-costdb/1`` record;
        repeated flushes append snapshots and the reader keeps the
        last occurrence per key.  Notes a ``costdb_flush`` flight
        event.  Never raises."""
        directory = directory or db_dir()
        if not directory:
            return None
        recs = self.records()
        if not recs:
            return None
        path = os.path.join(directory, "costdb-%d.jsonl" % os.getpid())
        try:
            os.makedirs(directory, exist_ok=True)
            with open(path, "a") as f:
                for r in recs:
                    f.write(json.dumps(r, sort_keys=True, default=repr)
                            + "\n")
        except OSError as e:
            import logging
            logging.getLogger(__name__).warning(
                "costdb: cannot write %r: %s", path, e)
            return None
        try:
            from . import flight
            flight.record("costdb_flush", path=path, records=len(recs))
        except Exception:  # mxlint: allow-broad-except(flight noting is observability-of-observability; never let it mask a successful flush)
            pass
        return path

    def drop_scope(self, scope):
        """Prune the dispatch counts and bindings of a retired scope
        token (a rebuilt trainer calls this for its OLD scope so
        long-running rebuild loops do not grow the maps without
        bound).  Aggregate records are kept — they are the product."""
        with self._lock:
            stale = [k for k in self._counts
                     if isinstance(k[1], tuple) and k[1]
                     and k[1][0] == scope]
            for k in stale:
                del self._counts[k]
            stale = [k for k in self._bound
                     if isinstance(k[1], tuple) and k[1]
                     and k[1][0] == scope]
            for k in stale:
                del self._bound[k]

    def reset(self):
        """Forget every record, pending signature, binding, and
        dispatch count (telemetry.reset calls this)."""
        with self._lock:
            self._records.clear()
            self._pending = []
            self._bound.clear()
            self._counts.clear()


#: the process-wide database (module-level helpers below)
DB = CostDB()


def note_block(*args, **kwargs):
    """Register a traced fused block — see :meth:`CostDB.note_block`."""
    return DB.note_block(*args, **kwargs)


def note_kernel(*args, **kwargs):
    """Register a Pallas kernel choice — :meth:`CostDB.note_kernel`."""
    return DB.note_kernel(*args, **kwargs)


def begin_dispatch(program, key=None):
    """Open a dispatch observation — :meth:`CostDB.begin_dispatch`."""
    return DB.begin_dispatch(program, key=key)


def sampled(obs):
    """Whether ``obs`` (what :func:`begin_dispatch` returned) is a
    dispatch :func:`end_dispatch` synchronizes on: after it the
    program's outputs are on hand and reading them waits for nothing."""
    return obs is not None and obs[2] is not None


def bind_pending(program, key=None):
    """Bind pending signatures only — :meth:`CostDB.bind_pending`.
    Never raises (multi-process dispatch paths call it from a
    ``finally``, where an error would mask the step's real result)."""
    try:
        DB.bind_pending(program, key=key)
    except Exception:  # mxlint: allow-broad-except(observability on the dispatch hot path; a binding failure must never mask the dispatch result propagating through the caller's finally)
        pass


def drop_scope(scope):
    """Prune a retired scope's counters — :meth:`CostDB.drop_scope`.
    Never raises (called from rebuild paths)."""
    try:
        DB.drop_scope(scope)
    except Exception:  # mxlint: allow-broad-except(scope pruning is bookkeeping; a failure must not break the rebuild that triggered it)
        pass


def end_dispatch(obs, out=None, args=None, mesh=None, failed=False,
                 steps=1):
    """Close a dispatch observation — :meth:`CostDB.end_dispatch`."""
    return DB.end_dispatch(obs, out=out, args=args, mesh=mesh,
                           failed=failed, steps=steps)


def record(*args, **kwargs):
    """Upsert one record on the default DB — :meth:`CostDB.record`."""
    return DB.record(*args, **kwargs)


def records():
    """Snapshot of the default DB's records."""
    return DB.records()


def summary(top=5):
    """Roll-up of the default DB — :meth:`CostDB.summary`."""
    return DB.summary(top=top)


def flush(directory=None):
    """Persist the default DB — :meth:`CostDB.flush`."""
    return DB.flush(directory=directory)


def reset():
    """Clear the default DB (telemetry.reset calls this)."""
    DB.reset()


# ------------------------------------------------------------- reader

_REQUIRED_FIELDS = ("schema", "kind", "name", "sig")


def _validate(rec, where):
    if not isinstance(rec, dict):
        raise ValueError("%s: record is not an object" % where)
    for f in _REQUIRED_FIELDS:
        if f not in rec:
            raise ValueError("%s: record missing %r" % (where, f))
    if rec["schema"] != SCHEMA:
        raise ValueError("%s: schema %r != %r"
                         % (where, rec["schema"], SCHEMA))
    if rec["kind"] not in ("program", "block", "kernel", "op"):
        raise ValueError("%s: unknown record kind %r"
                         % (where, rec["kind"]))
    return rec


def read_records(path, strict=False):
    """Load cost records from a ``costdb-*.jsonl`` file or a directory
    of them.  Duplicate (kind, name, sig) keys — repeated flush
    snapshots, multiple runs sharing the directory — dedup to the most
    RECENT record by its ``ts`` field (file order breaks ties; lexical
    filename order alone would let an old run's pid win).
    ``strict=True`` raises :class:`ValueError` on the first malformed
    line / wrong-schema record; the default skips bad lines and
    reports them in the returned ``(records, skipped)`` tuple."""
    if os.path.isdir(path):
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.startswith("costdb") and f.endswith(".jsonl"))
        if not files and strict:
            raise ValueError("no costdb-*.jsonl files under %r" % path)
    else:
        files = [path]
    out, skipped = {}, 0
    for fp in files:
        try:
            fh = open(fp)
        except OSError as e:
            if strict:
                raise ValueError("cannot read %r: %s" % (fp, e))
            skipped += 1
            continue
        with fh:
            for i, line in enumerate(fh, 1):
                line = line.strip()
                if not line:
                    continue
                where = "%s:%d" % (os.path.basename(fp), i)
                try:
                    rec = _validate(json.loads(line), where)
                except ValueError:
                    if strict:
                        raise
                    skipped += 1
                    continue
                key = (rec["kind"], rec["name"], rec["sig"])
                prev = out.get(key)
                if prev is None or _rec_ts(rec) >= _rec_ts(prev):
                    out[key] = rec
    return list(out.values()), skipped


def _rec_ts(rec):
    ts = rec.get("ts")
    return float(ts) if isinstance(ts, (int, float)) else float("-inf")


# auto-persist: a run that armed MXNET_TPU_COSTDB keeps its ground
# truth even when the training script never calls flush() itself.
# Registered unconditionally — flush() re-reads the env and no-ops
# when the knob is unset, so a script that sets MXNET_TPU_COSTDB
# AFTER importing still gets the documented exit-time flush.
import atexit
atexit.register(flush)
