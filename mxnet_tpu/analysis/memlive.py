"""Static memory-liveness analyzer: bind-time peak-HBM prediction.

PR 4's :mod:`mxnet_tpu.telemetry.memory` budget checks only observe
*after* XLA compiles — an over-budget model pays a full trace+compile
before it learns it cannot run, and nothing can say *which* activations
to rematerialize or *which* optimizer slots to shard.  This pass does
the memory planning Glow performs during lowering (arXiv:1805.00907,
liveness intervals in view before codegen) with the analytic per-node
features the learned-TPU-cost-model line showed are accurate enough to
rank decisions (arXiv:2008.01040): a topological interval analysis over
the composed train step — forward activations, autodiff residuals,
backward cotangents, optimizer state — byte-accurate via the verifier's
shape pass, fusion-plan-aware (interior edges of a
:class:`~.fusion.FusedBlock` never materialize) and donation/sharding-
aware (donated state is updated in place; sharded dims divide by their
mesh axis size).

Timeline model (train): forward node ``i`` of ``N`` executes at ``t=i``;
its backward executes at ``t = 2N-1-i`` (reverse topo order); the
optimizer update runs at ``t = 2N``.  A residual saved for the backward
of its *earliest* forward consumer is therefore the longest-lived — the
classic reason remat targets early, cheap-to-recompute chains.

Rule catalog (emitted by :func:`check_memory`; all opt-in — plain
``verify_symbol`` runs none of them):

========  ========  ====================================================
rule      severity  meaning
========  ========  ====================================================
MXG017    error     predicted peak HBM exceeds the armed budget at bind
                    time — names the peak node and top live buffers,
                    before any compile
MXG018    warning   prediction drift: analytic peak vs the XLA
                    ``memory_analysis`` total outside
                    ``MXNET_TPU_MEMLIVE_TOL`` (keeps these formulas
                    honest the way MXG010 is calibrated)
MXG019    warning   remat candidate: residual-heavy fusion chain ranked
                    by bytes-freed-at-peak per recompute FLOP
MXG020    warning   ZeRO-shardable: replicated optimizer-state bytes a
                    ``reshard.py`` rule table could shard over the data
                    axis, with the projected per-rank saving
MXG021    warning   donation: a step input is dead after its first use
                    but not donated, so XLA cannot reuse its buffer
========  ========  ====================================================

Entry points: :func:`analyze` (the engine), :func:`check_memory` (rule
emission into a verifier :class:`~.verifier.Report`),
``verify_symbol(..., memory=...)`` / ``Symbol.verify(memory=...)``,
``python -m mxnet_tpu.analysis --memory`` and ``tools/mem_top.py``.
Predictions are pushed to
:func:`mxnet_tpu.telemetry.memory.note_static_prediction` so the budget
check and ``HbmOomError`` report both the static and the XLA peak from
one predictor.
"""
from __future__ import annotations

__all__ = ["Buffer", "LivenessAnalysis", "analyze", "analyze_memory",
           "check_memory", "record_prediction", "CATEGORIES",
           "memlive_tolerance"]

# per-category taxonomy of the watermark breakdown
CATEGORIES = ("params", "activations", "residuals", "optimizer",
              "workspace")

_ADVICE_CAP = 3        # MXG019/021 diagnostics emitted per report
_TOP_BUFFERS = 5       # live buffers named in MXG017 messages


def memlive_tolerance(default=0.25):
    """MXG018 relative drift tolerance (``MXNET_TPU_MEMLIVE_TOL``).

    The default is calibrated against the model zoo: forward-plan
    drift vs ``memory_analysis`` measures within +-7% on every zoo
    model (worst: resnext's grouped convs at -6.7%; docs/api/
    memlive.md has the table and what the gate takes out of XLA:CPU's
    plan), so 25% flags real formula regressions without tripping on
    XLA's temp-buffer scheduling freedom."""
    import os
    raw = os.environ.get("MXNET_TPU_MEMLIVE_TOL", "").strip()
    if not raw:
        return float(default)
    return float(raw)


def _fmt_bytes(n):
    from ..telemetry.memory import _fmt_bytes as fmt
    return fmt(int(n))


class Buffer:
    """One materialized allocation with its liveness interval.

    ``start``/``end`` are inclusive timeline positions (see the module
    docstring for the schedule).  ``node`` is the defining node name,
    ``category`` one of :data:`CATEGORIES`, ``first_use`` the first
    consumer's timeline position (inputs only — the donation audit asks
    whether the interval closes right there), ``is_input`` marks step
    inputs (data/label variables).
    """
    __slots__ = ("name", "node", "category", "nbytes", "start", "end",
                 "shape", "dtype", "is_input", "first_use")

    def __init__(self, name, node, category, nbytes, start, end,
                 shape=None, dtype=None, is_input=False, first_use=None):
        self.name = name
        self.node = node
        self.category = category
        self.nbytes = int(nbytes)
        self.start = int(start)
        self.end = int(end)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = str(dtype) if dtype is not None else None
        self.is_input = bool(is_input)
        self.first_use = first_use

    @property
    def span(self):
        return self.end - self.start + 1

    def as_dict(self):
        return {"name": self.name, "node": self.node,
                "category": self.category, "bytes": self.nbytes,
                "start": self.start, "end": self.end,
                "shape": list(self.shape) if self.shape else None,
                "dtype": self.dtype}

    def __repr__(self):
        return ("<Buffer %s %s [%d,%d] %s>"
                % (self.name, self.category, self.start, self.end,
                   _fmt_bytes(self.nbytes)))


class LivenessAnalysis:
    """Result of :func:`analyze`: buffers, intervals, and the peak."""

    def __init__(self, buffers, n_nodes, is_train, program=None,
                 mesh=None, n_slots=0, donate=frozenset(),
                 remat_chains=(), skipped_bytes=0, peak_names=None):
        self.buffers = list(buffers)
        self.n_nodes = int(n_nodes)
        self.is_train = bool(is_train)
        self.program = program
        self.mesh = dict(mesh or {})
        self.n_slots = int(n_slots)
        self.donate = frozenset(donate)
        self._remat_chains = list(remat_chains)
        self.skipped_bytes = int(skipped_bytes)  # never-materialized (fused)
        self._names = list(peak_names or ())     # topo node names
        self.peak_bytes = 0
        self.peak_pos = 0
        self.breakdown = {c: 0 for c in CATEGORIES}
        self.category_totals = {c: 0 for c in CATEGORIES}
        self._sweep()

    # ------------------------------------------------------------ peak

    def _sweep(self):
        """Event sweep over buffer intervals: running per-category sums,
        recording the watermark and its timeline position."""
        events = {}
        for b in self.buffers:
            self.category_totals[b.category] += b.nbytes
            events.setdefault(b.start, []).append((b.nbytes, b.category))
            events.setdefault(b.end + 1, []).append((-b.nbytes,
                                                     b.category))
        live = {c: 0 for c in CATEGORIES}
        total = 0
        for t in sorted(events):
            for delta, cat in events[t]:
                live[cat] += delta
                total += delta
            if total > self.peak_bytes:
                self.peak_bytes = total
                self.peak_pos = t
                self.breakdown = dict(live)

    @property
    def timeline_len(self):
        return (2 * self.n_nodes + 1) if self.is_train else self.n_nodes

    def node_at(self, t):
        """Underlying graph-node name for timeline position ``t`` (no
        phase decoration; None for the optimizer-update slot)."""
        n = self.n_nodes
        if self.is_train and t >= 2 * n:
            return None
        i = (2 * n - 1 - t) if (self.is_train and t >= n) else t
        if 0 <= i < len(self._names):
            return self._names[i]
        return None

    def describe_pos(self, t):
        """Human name for timeline position ``t`` with its phase."""
        n = self.n_nodes
        if self.is_train and t >= 2 * n:
            return "<optimizer update>"
        raw = self.node_at(t) or ("#%d" % t)
        if self.is_train and t >= n:
            return "bwd(%s)" % raw
        return raw

    @property
    def peak_node(self):
        return self.describe_pos(self.peak_pos)

    def live_at(self, t):
        return sorted((b for b in self.buffers if b.start <= t <= b.end),
                      key=lambda b: -b.nbytes)

    @property
    def live_at_peak(self):
        return self.live_at(self.peak_pos)

    # ---------------------------------------------------------- advice

    def residual_peak_pos(self):
        """Timeline position where the most residual bytes are live —
        where rematerialization frees the most (may differ from the
        global peak, e.g. when the watermark is in the update phase)."""
        events = {}
        for b in self.buffers:
            if b.category != "residuals":
                continue
            events.setdefault(b.start, []).append(b.nbytes)
            events.setdefault(b.end + 1, []).append(-b.nbytes)
        best_pos, best, live = self.peak_pos, 0, 0
        for t in sorted(events):
            live += sum(events[t])
            if live > best:
                best, best_pos = live, t
        return best_pos

    def remat_candidates(self):
        """Residual-heavy chains ranked by bytes-freed-at-peak per
        recompute FLOP (MXG019).  Each record:
        ``{node, members, bytes_freed, recompute_flops, score}``.
        Bytes-freed are measured at the residual watermark."""
        out = []
        peak = self.residual_peak_pos()
        owner = {}
        for b in self.buffers:
            if b.category == "residuals" and b.start <= peak <= b.end:
                owner.setdefault(b.node, []).append(b)
        for terminal, members, flops in self._remat_chains:
            freed = sum(b.nbytes for m in members
                        for b in owner.get(m, ()))
            if freed <= 0:
                continue
            out.append({"node": terminal, "members": list(members),
                        "bytes_freed": int(freed),
                        "recompute_flops": int(flops),
                        "score": freed / float(flops + 1)})
        out.sort(key=lambda r: (-r["score"], -r["bytes_freed"],
                                r["node"]))
        return out

    def zero_audit(self):
        """Replicated optimizer-state audit (MXG020): slots for params
        without a model-parallel rule are replicated over the data axis;
        sharding them ZeRO-style saves ``bytes * (1 - 1/data)``/rank."""
        data = int(self.mesh.get("data", 1) or 1)
        if not self.is_train or self.n_slots <= 0 or data <= 1:
            return []
        out = []
        for b in self.buffers:
            if b.category != "optimizer":
                continue
            saving = int(b.nbytes * (1.0 - 1.0 / data))
            if saving > 0:
                out.append({"param": b.node, "slot_bytes": b.nbytes,
                            "saving_per_rank": saving,
                            "data_size": data})
        out.sort(key=lambda r: (-r["saving_per_rank"], r["param"]))
        return out

    def donation_audit(self):
        """Step inputs dead after their first use but not donated
        (MXG021): ``{input, bytes, last_use}`` records."""
        out = []
        for b in self.buffers:
            if not b.is_input or b.name in self.donate:
                continue
            if b.first_use is None:
                continue
            # "dead after first use": the interval closes at the first
            # consumer — no later forward reader, no backward residual
            if b.end == b.first_use:
                out.append({"input": b.name, "bytes": b.nbytes,
                            "last_use": b.end})
        out.sort(key=lambda r: (-r["bytes"], r["input"]))
        return out

    def as_dict(self):
        return {
            "program": self.program,
            "is_train": self.is_train,
            "peak_bytes": int(self.peak_bytes),
            "peak_node": self.peak_node,
            "breakdown": {c: int(v) for c, v in self.breakdown.items()},
            "category_totals": {c: int(v)
                                for c, v in self.category_totals.items()},
            "skipped_bytes": int(self.skipped_bytes),
            "n_buffers": len(self.buffers),
        }


# --------------------------------------------------------------- engine

def _prod(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _shard_div(shape, dim, size):
    """Bytes divisor for sharding ``dim`` of ``shape`` over ``size``
    ranks (1 when the dim does not divide evenly — stays replicated)."""
    if size <= 1 or not shape or dim is None or dim >= len(shape):
        return 1
    return size if int(shape[dim]) % size == 0 else 1


def analyze(sym, shapes=None, types=None, *, is_train=True, mesh=None,
            tp_rules=None, n_slots=0, donate=(), fuse=None,
            layout="NCHW", inputs=None, program=None,
            topo=None, structs=None):
    """Run the static liveness pass; returns a :class:`LivenessAnalysis`.

    ``shapes``/``types``: as ``verify_symbol`` (input name -> shape /
    dtype).  ``is_train`` models the full fwd+bwd+update schedule with
    residuals, cotangents and ``n_slots`` float32 optimizer slots per
    parameter.  ``mesh`` ({axis: size}) divides batch-sharded dims by
    the ``data`` axis size and ``tp_rules``-sharded parameter dims by
    the ``model`` axis size.  ``donate`` is a collection of donated
    input names, or True for the trainer convention (params + optimizer
    + aux donated, updated in place).  ``fuse``/``layout`` control the
    fusion plan (None follows the ``MXNET_FUSE_BLOCKS`` default);
    interior edges of fused blocks never materialize.  ``inputs`` names
    the step inputs (defaults to the keys of ``shapes``); everything
    else in ``list_arguments()`` is a parameter.  ``topo``/``structs``
    accept the verifier's already-traced shape pass to avoid re-running
    it.
    """
    from ..symbol import _classify_vars
    from .verifier import Report, _shape_pass, _topo_from_entries
    from .fusion import _consumers
    from .perf import node_cost_estimate

    shapes = dict(shapes or {})
    entries = sym._entries
    if topo is None:
        topo = _topo_from_entries(entries)
    if structs is None:
        _, structs = _shape_pass(sym, topo, shapes, dict(types or {}),
                                 Report())

    mesh = dict(mesh or {})
    tp_rules = dict(tp_rules or {})
    data_size = int(mesh.get("data", 1) or 1)
    model_size = int(mesh.get("model", 1) or 1)

    input_names = set(inputs) if inputs is not None else set(shapes)
    arg_nodes, aux_nodes = _classify_vars(topo)
    param_nodes = [v for v in arg_nodes if v.name not in input_names]
    input_nodes = [v for v in arg_nodes if v.name in input_names]

    if donate is True:
        donate_set = ({v.name for v in param_nodes}
                      | {v.name for v in aux_nodes})
        state_donated = True
    else:
        donate_set = set(donate or ())
        state_donated = bool(param_nodes) and all(
            v.name in donate_set for v in param_nodes)

    # fusion plan: interior edges never materialize
    skip, chains = set(), []
    if fuse is None:
        from .. import config as _config
        fuse = _config.get_bool("MXNET_FUSE_BLOCKS")
    if fuse:
        try:
            from .fusion import plan_block_fusion
            plan = plan_block_fusion(topo, entries, layout=layout,
                                     is_train=is_train)
            skip = set(plan.skip)
            for blk in plan.blocks.values():
                # FusedBlock.chain holds member NAMES (strings)
                chains.append((blk.name,
                               tuple(nd if isinstance(nd, str)
                                     else nd.name
                                     for nd in blk.chain)))
        except Exception:  # mxlint: allow-broad-except(fusion planning is advisory; an unplannable graph falls back to the unfused liveness model)
            skip, chains = set(), []

    pos = {id(nd): i for i, nd in enumerate(topo)}
    n = len(topo)
    end = (2 * n) if is_train else (n - 1)
    consumers = _consumers(topo, entries)

    def measure(node):
        """(nbytes, elems, shape, dtype) of a node's materialized
        outputs, sharding-aware; None when shapes are unresolved."""
        sts = structs.get(id(node))
        if not sts:
            return None
        nbytes = elems = 0
        shape0 = dtype0 = None
        for st in sts:
            shp = tuple(int(d) for d in st.shape)
            if shape0 is None:
                shape0, dtype0 = shp, st.dtype
            if node.is_variable:
                if node.name in tp_rules:
                    div = _shard_div(shp, tp_rules[node.name],
                                     model_size)
                elif node.name in input_names:
                    div = _shard_div(shp, 0, data_size)
                else:
                    div = 1  # replicated state
            else:
                div = _shard_div(shp, 0, data_size)  # batch-sharded
            e = _prod(shp) // div
            elems += e
            nbytes += e * st.dtype.itemsize
        return nbytes, elems, shape0, dtype0

    def flops_of(node):
        sts = structs.get(id(node)) or ()
        in_shapes = []
        for (src, oi) in node.inputs:
            s = structs.get(id(src))
            if s and oi < len(s):
                in_shapes.append(tuple(int(d) for d in s[oi].shape))
        out_shapes = [tuple(int(d) for d in st.shape) for st in sts]
        est = node_cost_estimate(node, in_shapes, out_shapes)
        return est[0] if est else 0

    buffers = []
    skipped_bytes = 0
    residual_owners = set()

    # ---- long-lived state: params, aux, optimizer slots
    for v in list(param_nodes) + list(aux_nodes):
        m = measure(v)
        if m is None:
            continue
        nbytes, elems, shp, dt = m
        buffers.append(Buffer(v.name, v.name, "params", nbytes, 0, end,
                              shp, dt))
        if is_train and n_slots > 0 and v in param_nodes:
            # slots are float32, sharded like the parameter they track
            buffers.append(Buffer(v.name + ".opt", v.name, "optimizer",
                                  elems * 4 * n_slots, 0, end, shp,
                                  "float32"))

    # ---- step inputs (data / labels)
    for v in input_nodes:
        m = measure(v)
        if m is None:
            continue
        nbytes, _elems, shp, dt = m
        cs = [c for (c, _s) in consumers.get(id(v), ()) if c is not None]
        if not cs:
            continue  # dead input — MXG003's finding, not a buffer
        first = min(pos[id(c)] for c in cs)
        last = max(pos[id(c)] for c in cs)
        if is_train:
            # inputs are residuals for the weight-gradient computation
            last = max(last, 2 * n - 1 - first)
        buffers.append(Buffer(v.name, v.name, "activations", nbytes,
                              0, last, shp, dt, is_input=True,
                              first_use=first))

    # ---- forward activations / residuals + backward cotangents
    for node in topo:
        if node.is_variable:
            continue
        m = measure(node)
        if m is None:
            continue
        nbytes, _elems, shp, dt = m
        if id(node) in skip:
            skipped_bytes += nbytes
            continue
        p = pos[id(node)]
        cons = consumers.get(id(node), ())
        op_cons = [c for (c, _s) in cons if c is not None]
        is_head = any(c is None for (c, _s) in cons)
        ends = [p]
        if op_cons:
            ends.append(max(pos[id(c)] for c in op_cons))
        if is_head:
            # head outputs persist to the end of the step
            ends.append(end)
        if is_train and op_cons:
            # saved for the backward of the earliest consumer
            ends.append(2 * n - 1 - min(pos[id(c)] for c in op_cons))
        last = max(ends)
        cat = ("residuals" if (is_train and last >= n and not is_head)
               else "activations")
        if cat == "residuals":
            residual_owners.add(node.name)
        buffers.append(Buffer(node.name, node.name, cat, nbytes, p,
                              last, shp, dt))

        if is_train:
            # cotangent of this output: born when the latest forward
            # consumer's backward runs (the earliest backward step),
            # consumed at this node's own backward
            t_own = 2 * n - 1 - p
            if op_cons:
                born = 2 * n - 1 - max(pos[id(c)] for c in op_cons)
            else:
                born = n  # loss head seeds the backward
            born = min(born, t_own)
            buffers.append(Buffer("d(%s)" % node.name, node.name,
                                  "workspace", nbytes, born, t_own,
                                  shp, dt))

    # ---- parameter gradients: accumulate over the backward, consumed
    # by the optimizer update
    if is_train:
        for v in param_nodes:
            m = measure(v)
            if m is None:
                continue
            nbytes, _elems, shp, dt = m
            cs = [c for (c, _s) in consumers.get(id(v), ())
                  if c is not None]
            if not cs:
                continue
            born = 2 * n - 1 - max(pos[id(c)] for c in cs)
            buffers.append(Buffer("d(%s)" % v.name, v.name, "workspace",
                                  nbytes, born, 2 * n, shp, dt))
        if not state_donated:
            # un-donated state: the update writes fresh output buffers
            # instead of reusing the inputs
            for v in param_nodes:
                m = measure(v)
                if m is None:
                    continue
                nbytes, elems, shp, dt = m
                buffers.append(Buffer(v.name + "'", v.name, "workspace",
                                      nbytes, 2 * n, 2 * n, shp, dt))
                if n_slots > 0:
                    buffers.append(Buffer(v.name + ".opt'", v.name,
                                          "workspace",
                                          elems * 4 * n_slots,
                                          2 * n, 2 * n, shp, "float32"))

    # ---- remat chains: fusion blocks when planned, else each
    # residual-owning op is its own single-member chain
    name2node = {nd.name: nd for nd in topo}
    remat_chains = []
    if chains:
        for terminal, members in chains:
            fl = sum(flops_of(name2node[mname]) for mname in members
                     if mname in name2node)
            remat_chains.append((terminal, members, fl))
    else:
        for mname in sorted(residual_owners):
            nd = name2node.get(mname)
            if nd is None:
                continue
            remat_chains.append((mname, (mname,), flops_of(nd)))

    return LivenessAnalysis(
        buffers, n, is_train, program=program, mesh=mesh,
        n_slots=n_slots, donate=donate_set, remat_chains=remat_chains,
        skipped_bytes=skipped_bytes,
        peak_names=[nd.name for nd in topo])


# ------------------------------------------------------------- reporting

def record_prediction(analysis, program=None):
    """Publish a prediction: CATALOG gauges, a ``memlive`` flight event,
    and the :mod:`~mxnet_tpu.telemetry.memory` static-prediction slot
    (so budget checks and ``HbmOomError`` report it)."""
    prog = program or analysis.program or "memlive"
    remats = analysis.remat_candidates()
    zeros = analysis.zero_audit()
    info = analysis.as_dict()
    info["program"] = prog
    info["remat_candidates"] = remats[:_ADVICE_CAP]
    info["zero_saving_per_rank"] = sum(z["saving_per_rank"]
                                       for z in zeros)
    try:
        from ..telemetry import flight, gauge
        g = gauge("mxtpu_predicted_peak_bytes")
        g.labels(program=prog, category="total").set(
            analysis.peak_bytes)
        for cat, val in analysis.breakdown.items():
            g.labels(program=prog, category=cat).set(val)
        gauge("mxtpu_remat_candidate_bytes").labels(program=prog).set(
            sum(r["bytes_freed"] for r in remats))
        flight.record("memlive", program=prog,
                      peak_bytes=int(analysis.peak_bytes),
                      peak_node=analysis.peak_node,
                      **{c: int(v)
                         for c, v in analysis.breakdown.items()})
    except Exception:  # mxlint: allow-broad-except(prediction accounting is observability; a metric failure must never mask the analysis)
        pass
    try:
        from ..telemetry import memory as _tmem
        _tmem.note_static_prediction(prog, info)
    except Exception:  # mxlint: allow-broad-except(same — the memory-module slot is advisory)
        pass
    return info


def check_memory(sym, shapes=None, types=None, report=None, *,
                 budget_bytes=None, plan_total=None, tol=None,
                 advice=True, record=False, program=None,
                 topo=None, structs=None, **opts):
    """Run :func:`analyze` and emit MXG017-021 into ``report``.

    ``budget_bytes``: peak budget for MXG017 (default: armed device
    budget ``device_capacity_bytes() * budget_fraction()`` when known,
    else the check is skipped).  ``plan_total``: an XLA
    ``MemoryPlan.total_bytes`` (or the plan itself) to drift-check
    against (MXG018) under ``tol`` / ``MXNET_TPU_MEMLIVE_TOL``.
    ``advice`` emits MXG019/020/021.  ``record`` publishes gauges, the
    ``memlive`` flight event and the static-prediction slot.  Remaining
    ``opts`` go to :func:`analyze`.  Returns the
    :class:`LivenessAnalysis` (the report carries the findings).
    """
    from .verifier import Report
    if report is None:
        report = Report()
    analysis = analyze(sym, shapes, types, program=program, topo=topo,
                       structs=structs, **opts)
    peak = analysis.peak_bytes
    peak_node_raw = analysis.node_at(analysis.peak_pos)

    if budget_bytes is None:
        try:
            from ..telemetry import memory as _tmem
            cap = _tmem.device_capacity_bytes()
            frac = _tmem.budget_fraction()
            if cap and frac > 0:
                budget_bytes = int(cap * frac)
        except Exception:  # mxlint: allow-broad-except(no budget signal means the MXG017 leg is simply not armed)
            budget_bytes = None

    if budget_bytes and peak > budget_bytes:
        top = ", ".join("%s (%s, %s)" % (b.name, b.category,
                                         _fmt_bytes(b.nbytes))
                        for b in analysis.live_at_peak[:_TOP_BUFFERS])
        bd = ", ".join("%s=%s" % (c, _fmt_bytes(v))
                       for c, v in analysis.breakdown.items() if v)
        report.add(
            "MXG017", "error",
            "predicted peak HBM %s at %s exceeds the memory budget %s "
            "(%.0f%%) before any compile; breakdown: %s; top live "
            "buffers: %s"
            % (_fmt_bytes(peak), analysis.peak_node,
               _fmt_bytes(budget_bytes), 100.0 * peak / budget_bytes,
               bd, top),
            node=peak_node_raw or analysis.peak_node,
            advice={"peak_bytes": int(peak),
                    "budget_bytes": int(budget_bytes),
                    "peak_node": analysis.peak_node,
                    "breakdown": {c: int(v) for c, v
                                  in analysis.breakdown.items()}})

    if plan_total is not None:
        total = getattr(plan_total, "total_bytes", plan_total)
        total = int(total)
        if total > 0:
            tolerance = memlive_tolerance() if tol is None else float(tol)
            drift = (peak - total) / float(total)
            try:
                from ..telemetry import gauge
                gauge("mxtpu_memlive_drift_ratio").labels(
                    program=program or "memlive").set(drift)
            except Exception:  # mxlint: allow-broad-except(drift gauge is observability only)
                pass
            if abs(drift) > tolerance:
                report.add(
                    "MXG018", "warning",
                    "static peak prediction %s drifts %.0f%% from the "
                    "XLA memory_analysis total %s (tolerance %.0f%%); "
                    "the liveness formulas need recalibration for this "
                    "graph shape"
                    % (_fmt_bytes(peak), 100.0 * drift,
                       _fmt_bytes(total), 100.0 * tolerance),
                    node=peak_node_raw,
                    advice={"static_peak_bytes": int(peak),
                            "plan_total_bytes": total,
                            "drift": drift, "tolerance": tolerance})

    if advice:
        for rec in analysis.remat_candidates()[:_ADVICE_CAP]:
            report.add(
                "MXG019", "warning",
                "remat candidate: chain %s frees %s at the predicted "
                "peak for ~%s recompute FLOPs (score %.3g bytes/FLOP); "
                "MXNET_BACKWARD_DO_MIRROR=1 or a jax.checkpoint over "
                "the chain trades this memory for compute"
                % (rec["node"], _fmt_bytes(rec["bytes_freed"]),
                   "{:,}".format(rec["recompute_flops"]),
                   rec["score"]),
                node=rec["node"], advice=dict(rec, kind="remat"))
        zeros = analysis.zero_audit()
        if zeros:
            total_saving = sum(z["saving_per_rank"] for z in zeros)
            total_slots = sum(z["slot_bytes"] for z in zeros)
            top = ", ".join("%s (%s)" % (z["param"],
                                         _fmt_bytes(z["slot_bytes"]))
                            for z in zeros[:_TOP_BUFFERS])
            report.add(
                "MXG020", "warning",
                "%s of optimizer state is replicated across the "
                "data axis (size %d); sharding it ZeRO-style via a "
                "reshard.py rule table would save %s per rank — "
                "largest slots: %s"
                % (_fmt_bytes(total_slots), zeros[0]["data_size"],
                   _fmt_bytes(total_saving), top),
                node=zeros[0]["param"],
                advice={"kind": "zero", "params": zeros,
                        "total_slot_bytes": int(total_slots),
                        "total_saving_per_rank": int(total_saving)})
        for rec in analysis.donation_audit()[:_ADVICE_CAP]:
            report.add(
                "MXG021", "warning",
                "step input %r (%s) is dead after its first use at "
                "t=%d but not donated; donating it would let XLA reuse "
                "the buffer for the step's outputs"
                % (rec["input"], _fmt_bytes(rec["bytes"]),
                   rec["last_use"]),
                node=rec["input"], advice=dict(rec, kind="donate"))

    if record:
        record_prediction(analysis, program=program)
    return analysis


# package-level alias: the generic name ``analyze`` stays local to this
# module; ``mxnet_tpu.analysis.analyze_memory`` is the public spelling
analyze_memory = analyze
