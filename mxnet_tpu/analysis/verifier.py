"""Static graph verifier: pre-compile defect detection for Symbol graphs.

The reference surfaces shape/dtype mismatches only at bind/execute time
(``GraphExecutor::Init`` runs InferShape/InferType and throws mid-bind);
the TPU build additionally pays an XLA compile before the first error can
appear.  This pass walks the DAG *abstractly* — per-node
``jax.eval_shape`` over each op's registered fcompute — so every defect
is caught before any device time is spent and is attributed to the
offending node, in the spirit of the typed, verifiable IR passes of TVM
(arXiv:1802.04799) and Relay (arXiv:1810.00952).

Check catalog (rule IDs are stable; docs/api/analysis.md documents them):

========  ========  ====================================================
rule      severity  meaning
========  ========  ====================================================
MXG001    error     cycle in the graph (names the nodes on the cycle)
MXG002    error     duplicate node name (name-keyed binding would alias)
MXG003    warning   dead node / unused input (unreachable from any head,
                    or a head variable no op consumes)
MXG004    error     op with parameter inputs but no param-shape rule in
                    ``ops.shapes`` and no explicit ``__shape__``
MXG005    error     shape/attr inconsistency — the op's fcompute rejects
                    its input shapes (message carries the op error)
MXG006    warning   implicit dtype promotion (mixed float widths feeding
                    one op) or unresolvable input dtypes
MXG007    error     sharded-graph coverage: a shardable parameter gets no
                    rule from ``parallel.tp_rules`` and carries no
                    explicit ``__tp__ = 'replicate'`` annotation
MXG008    error     registry self-check finding (alias/hook/rule drift)
MXG009    warning   shape underdetermined — a rule exists but could not
                    produce the parameter's shape from what is known
MXG010    warning   predicted-slow node: the learned cost model
                    (``mxnet_tpu.autotune``) predicts a wall time more
                    than ``slow_factor`` x the node's roofline-
                    attainable time (opt-in: runs only when a
                    ``cost_model`` is supplied; see :mod:`.perf`)
MXG017    error     predicted peak HBM exceeds the armed memory budget
                    at bind time, before any compile (opt-in via
                    ``memory=``; see :mod:`.memlive`)
MXG018    warning   static-peak vs XLA ``memory_analysis`` drift beyond
                    ``MXNET_TPU_MEMLIVE_TOL`` (:mod:`.memlive`)
MXG019    warning   remat candidate: residual-heavy chain ranked by
                    bytes-freed-at-peak per recompute FLOP
MXG020    warning   ZeRO-shardable replicated optimizer state with the
                    projected per-rank saving
MXG021    warning   step input dead after first use but not donated
========  ========  ====================================================

MXG011-016 (distributed/SPMD) live in :mod:`.spmd`; MXG017-021 (memory
liveness, all opt-in via ``memory=``) in :mod:`.memlive`.

Entry points: :func:`verify_symbol` (the engine), :meth:`Symbol.verify`,
``Symbol.bind(..., strict=True)``, :func:`verify_json` (adds real
unreachable-node detection over the serialized layout), and
``python -m mxnet_tpu.analysis``.
"""
from __future__ import annotations

import json

from ..base import MXNetError

__all__ = ["Diagnostic", "Report", "verify_symbol", "verify_json",
           "verify_model", "infer_node_shapes"]

_SEVERITIES = ("error", "warning")


class Diagnostic:
    """One verifier finding, attributed to a node where possible."""
    __slots__ = ("rule", "severity", "node", "op", "message", "advice")

    def __init__(self, rule, severity, message, node=None, op=None,
                 advice=None):
        assert severity in _SEVERITIES, severity
        self.rule = rule
        self.severity = severity
        self.message = message
        self.node = node          # offending node name (str | None)
        self.op = op              # op name (str | None)
        self.advice = advice      # machine-readable payload (dict | None)

    def as_dict(self):
        """JSON-ready form (``python -m mxnet_tpu.analysis --json``)."""
        d = {"rule": self.rule, "severity": self.severity,
             "node": self.node, "op": self.op, "message": self.message}
        if self.advice is not None:
            d["advice"] = self.advice
        return d

    def __repr__(self):
        return "<Diagnostic %s %s>" % (self.rule, self.node or "<graph>")

    def __str__(self):
        where = self.node or "<graph>"
        if self.op:
            where += " (op %s)" % self.op
        return "%s [%s] %s: %s" % (self.rule, self.severity, where,
                                   self.message)


class Report:
    """Verification result: an ordered list of diagnostics."""

    def __init__(self, diagnostics=()):
        self.diagnostics = list(diagnostics)

    @property
    def errors(self):
        return [d for d in self.diagnostics if d.severity == "error"]

    @property
    def warnings(self):
        return [d for d in self.diagnostics if d.severity == "warning"]

    @property
    def ok(self):
        return not self.errors

    def __bool__(self):
        return self.ok

    def __iter__(self):
        return iter(self.diagnostics)

    def __len__(self):
        return len(self.diagnostics)

    def __str__(self):
        if not self.diagnostics:
            return "verify: OK (no findings)"
        lines = ["verify: %d error(s), %d warning(s)"
                 % (len(self.errors), len(self.warnings))]
        lines.extend("  " + str(d) for d in self.diagnostics)
        return "\n".join(lines)

    def add(self, *args, **kwargs):
        d = Diagnostic(*args, **kwargs)
        self.diagnostics.append(d)
        try:
            from ..telemetry import counter
            counter("mxtpu_verify_findings_total").labels(
                rule=d.rule).inc()
        except Exception:  # mxlint: allow-broad-except(finding accounting is observability; a metric failure must never mask the diagnostic itself)
            pass

    def raise_if_errors(self, context=""):
        if self.ok:
            return self
        head = "graph verification failed"
        if context:
            head += " (%s)" % context
        raise MXNetError(head + ":\n" + "\n".join(
            "  " + str(d) for d in self.errors))


# ------------------------------------------------------------ graph walking

def _collect_nodes(entries):
    """Every node reachable from ``entries`` — tolerates cycles (unlike
    Symbol._topo, which assumes a DAG and would not terminate)."""
    nodes, seen = [], set()
    stack = [n for (n, _i) in entries]
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        nodes.append(node)
        stack.extend(src for (src, _i) in node.inputs)
    return nodes


def _find_cycle(entries):
    """Iterative three-color DFS; returns the node list of one cycle, or
    None.  Runs before any topo-order work — a cycle makes Symbol._topo
    spin forever, so this check gates everything else."""
    WHITE, GRAY, BLACK = 0, 1, 2
    color, parent = {}, {}
    for root, _i in entries:
        if color.get(id(root), WHITE) != WHITE:
            continue
        stack = [(root, iter([s for (s, _) in root.inputs]))]
        color[id(root)] = GRAY
        while stack:
            node, it = stack[-1]
            advanced = False
            for src in it:
                c = color.get(id(src), WHITE)
                if c == GRAY:
                    # walk parent chain back to src to name the cycle
                    cyc, cur = [node], node
                    while id(cur) != id(src):
                        cur = parent[id(cur)]
                        cyc.append(cur)
                    return list(reversed(cyc))
                if c == WHITE:
                    color[id(src)] = GRAY
                    parent[id(src)] = node
                    stack.append((src, iter([s for (s, _) in src.inputs])))
                    advanced = True
                    break
            if not advanced:
                color[id(node)] = BLACK
                stack.pop()
    return None


def _topo_from_entries(entries):
    from ..symbol import _topo_order
    return _topo_order(entries)


# -------------------------------------------------------------- the checks

def _check_duplicate_names(nodes, report):
    by_name = {}
    for n in nodes:
        by_name.setdefault(n.name, []).append(n)
    for name, group in sorted(by_name.items()):
        if len(group) > 1:
            kinds = ["variable" if n.is_variable else n.op.name
                     for n in group]
            report.add("MXG002", "error",
                       "%d distinct nodes share the name %r (%s); "
                       "name-keyed binding (arg_dict, checkpoints) would "
                       "silently alias them" % (len(group), name,
                                                ", ".join(kinds)),
                       node=name)


def _check_dead_entries(entries, nodes, report):
    """Head variables nothing consumes, and duplicate head entries."""
    consumed = set()
    for n in nodes:
        for (src, _i) in n.inputs:
            consumed.add(id(src))
    seen_entries = set()
    for node, idx in entries:
        if (id(node), idx) in seen_entries:
            report.add("MXG003", "warning",
                       "output %r is listed more than once in the heads"
                       % node.output_names()[idx], node=node.name)
        seen_entries.add((id(node), idx))
        if node.is_variable and id(node) not in consumed:
            report.add("MXG003", "warning",
                       "input variable %r is consumed by no operator and "
                       "is returned unchanged (dead input)" % node.name,
                       node=node.name)


def _auto_param_names(node):
    """The auto-created parameter/aux variable inputs of an op node:
    variables named ``<node>_<slot>`` (the Symbol._create convention)."""
    names = node.arg_names() + node.aux_names()
    out = []
    for slot, (src, _i) in zip(names, node.inputs):
        if src.is_variable and src.name == "%s_%s" % (node.name, slot):
            out.append((slot, src))
    return out


def _shape_pass(sym, topo, known_shapes, type_overrides, report):
    """Per-node abstract interpretation: ``symbol._shape_walk``, the walk
    ``Symbol.infer_shape`` makes, with each thing that stopped it
    localized to its node as a diagnostic instead of raised.  Returns
    ``({var_name: shape}, {id(node): tuple(ShapeDtypeStruct)})`` — the
    resolved variable shapes feed the TP pass, the per-node structs
    feed MXG010 (:mod:`.perf`) and the autotuner's zoo mode.
    """
    import jax.numpy as jnp
    from ..ops import shapes as _shapes
    from ..symbol import _input_structs, _shape_walk

    var_shapes, structs, faults = _shape_walk(topo, known_shapes,
                                              type_overrides)
    var_reported = set()  # variables already attributed to a diagnostic
    for node, stage, detail in faults:
        op = node.op.name
        if stage == "hook":
            report.add("MXG005", "error",
                       "param-shape rule for op %s raised: %s" % (op, detail),
                       node=node.name, op=op)
        elif stage == "op":
            msg = str(detail).strip().splitlines()
            report.add("MXG005", "error",
                       "op %s rejects input shapes %s: %s"
                       % (op, [tuple(st.shape) for st
                               in _input_structs(node, structs)],
                          msg[0] if msg else repr(detail)),
                       node=node.name, op=op)
        else:
            missing = [nm for nm, src in detail
                       if id(src) not in var_reported]
            var_reported.update(id(src) for _nm, src in detail)
            auto_params = {nm for nm, _src in _auto_param_names(node)}
            auto_missing = [nm for nm in missing if nm in auto_params]
            if _shapes.get_param_shapes(op) is None and auto_missing:
                report.add(
                    "MXG004", "error",
                    "op %s auto-created parameter input(s) %s but has no "
                    "param-shape rule registered in ops.shapes and no "
                    "explicit __shape__; their shapes cannot be inferred"
                    % (op, auto_missing), node=node.name, op=op)
            elif missing:
                report.add(
                    "MXG009", "warning",
                    "shapes of input(s) %s of op %s are underdetermined "
                    "(provide them via infer kwargs or __shape__)"
                    % (missing, op), node=node.name, op=op)

    # dtype-promotion audit: mixed float widths feeding one op.
    # issubdtype (not .kind == 'f') so bfloat16 — an ml_dtypes
    # extension type with kind 'V', and THE TPU compute dtype —
    # is covered.
    for node in topo:
        if node.is_variable:
            continue
        ins = _input_structs(node, structs) or ()
        f_dtypes = sorted({jnp.dtype(st.dtype).name for st in ins
                           if jnp.issubdtype(st.dtype, jnp.floating)})
        if len(f_dtypes) > 1:
            report.add("MXG006", "warning",
                       "inputs of op %s mix float dtypes %s; XLA will "
                       "promote implicitly (check intended precision)"
                       % (node.op.name, f_dtypes),
                       node=node.name, op=node.op.name)
    resolved = {n.name: var_shapes[id(n)] for n in topo
                if n.is_variable and id(n) in var_shapes}
    return resolved, structs


def _check_tp_coverage(topo, arg_shapes, tp_size, report):
    """Sharded-graph coverage: every shardable parameter must either get
    a rule from ``derive_tp_rules`` or carry an explicit replicate
    annotation (``__tp__ = 'replicate'`` on the owning op node or the
    parameter variable)."""
    from ..parallel.tp_rules import derive_tp_rules, _weight_of
    rules = derive_tp_rules(topo, arg_shapes, tp_size)
    for node in topo:
        if node.is_variable or node.op is None:
            continue
        if node.op.name not in ("FullyConnected", "Convolution"):
            continue
        w, _b = _weight_of(node)
        if w is None or w in rules:
            continue
        ann = node.raw_attr.get("__tp__")
        if ann is None:
            for (src, _i) in node.inputs:
                if src.is_variable and src.name == w:
                    ann = src.raw_attr.get("__tp__")
                    break
        if ann == "replicate":
            continue
        if ann is not None:
            report.add("MXG007", "error",
                       "op %s has unknown __tp__ annotation %r (expected "
                       "'replicate')" % (node.op.name, ann),
                       node=node.name, op=node.op.name)
            continue
        shp = arg_shapes.get(w)
        report.add(
            "MXG007", "error",
            "parameter %r of op %s (shape %s) gets no tensor-parallel "
            "rule for tp_size=%d and carries no explicit "
            "__tp__='replicate' annotation; it would be silently "
            "replicated on every model shard"
            % (w, node.op.name, shp, tp_size),
            node=node.name, op=node.op.name)


def _registry_diagnostics(report):
    from ..ops import registry as _registry
    for problem in _registry.selfcheck():
        report.add("MXG008", "error", problem)


# ------------------------------------------------------------- entry points

def verify_symbol(sym, shapes=None, types=None, tp_size=1,
                  check_registry=False, report=None, cost_model=None,
                  slow_factor=3.0, plan=False, plan_layout="NCHW",
                  mesh=None, parallel=None, memory=None):
    """Verify a Symbol graph; returns a :class:`Report`.

    ``shapes``: {input_name: shape} (same keys as ``infer_shape`` kwargs;
    optional — without them only structural checks and __shape__-seeded
    inference run).  ``types``: {input_name: dtype}.  ``tp_size`` > 1
    additionally runs the sharding-coverage check against
    ``parallel.tp_rules``.  ``check_registry`` folds the op-registry
    self-check into the report.  ``cost_model`` (a fitted
    ``mxnet_tpu.autotune.CostModel`` or a saved-model path)
    additionally runs MXG010: nodes whose predicted wall exceeds their
    roofline-attainable time by more than ``slow_factor`` are named
    before any compile (:mod:`.perf`).  ``plan=True`` switches MXG010
    to plan mode: predictions for the COMMITTED fusion/layout plan
    (the ``graph_plan`` tuning-cache entry at ``plan_layout``; greedy
    on miss) instead of the default per-node lowering.  ``mesh``
    ({axis: size} descriptor) additionally runs the distributed-
    correctness pass (:mod:`.spmd`, MXG011-016) with ``parallel`` — a
    :func:`.spmd.build_config` dict describing the composed step.
    ``memory`` (True or a dict of :func:`.memlive.check_memory`
    options) additionally runs the static memory-liveness pass
    (MXG017-021), reusing this call's shape pass; like MXG010 it is
    opt-in and never runs on a plain verify.
    """
    report = report if report is not None else Report()
    shapes = dict(shapes or {})
    types = dict(types or {})

    if check_registry:
        _registry_diagnostics(report)

    entries = sym._entries
    cycle = _find_cycle(entries)
    if cycle is not None:
        report.add("MXG001", "error",
                   "graph contains a cycle through nodes [%s]; no "
                   "execution order exists"
                   % " -> ".join(n.name for n in cycle),
                   node=cycle[0].name)
        # everything below needs a topo order — stop here
        return report

    nodes = _collect_nodes(entries)
    _check_duplicate_names(nodes, report)
    _check_dead_entries(entries, nodes, report)

    topo = _topo_from_entries(entries)
    arg_shapes, structs = _shape_pass(sym, topo, shapes, types, report)

    if tp_size and tp_size > 1:
        _check_tp_coverage(topo, arg_shapes, tp_size, report)
    if mesh:
        from . import spmd as _spmd
        cfg = parallel if parallel is not None else _spmd.build_config()
        if not cfg.get("data_shapes") and shapes:
            cfg = dict(cfg)
            cfg["data_shapes"] = {k: v for k, v in shapes.items()
                                  if not k.endswith("_label")}
            cfg["label_shapes"] = {k: v for k, v in shapes.items()
                                   if k.endswith("_label")}
        # hand the pass the per-node shapes _shape_pass already traced
        # — re-inferring would run jax.eval_shape over the whole graph
        # a second time
        node_shapes = {}
        for nid, sts in structs.items():
            if sts is None:
                continue
            for i, st in enumerate(sts):
                node_shapes[(nid, i)] = tuple(int(d) for d in st.shape)
        _spmd.verify_spmd(sym, mesh, cfg, report=report,
                          shapes=node_shapes, arg_shapes=arg_shapes)
    if cost_model is not None:
        if plan:
            from .perf import check_predicted_plan
            check_predicted_plan(topo, sym._entries, structs,
                                 cost_model, factor=slow_factor,
                                 report=report, layout=plan_layout)
        else:
            from .perf import check_predicted_slow
            check_predicted_slow(topo, structs, cost_model,
                                 factor=slow_factor, report=report)
    if memory:
        from . import memlive as _memlive
        mopts = dict(memory) if isinstance(memory, dict) else {}
        if mesh and "mesh" not in mopts:
            mopts["mesh"] = dict(mesh)
        # hand over this call's shape pass — memlive would otherwise
        # re-trace the whole graph
        _memlive.check_memory(sym, shapes, types, report=report,
                              topo=topo, structs=structs, **mopts)
    return report


def infer_node_shapes(sym, shapes=None, types=None):
    """Per-node output shapes via the verifier's abstract-
    interpretation pass, without diagnostics: ``(topo,
    {id(node): tuple(shape tuples)})``.  Nodes whose shapes could not
    be resolved are absent.  Feeds the autotuner's zoo-model mode
    (``tools/autotune.py --model``)."""
    entries = sym._entries
    topo = _topo_from_entries(entries)
    scratch = Report()
    _resolved, structs = _shape_pass(sym, topo, dict(shapes or {}),
                                     dict(types or {}), scratch)
    out = {}
    for nid, sts in structs.items():
        if sts is None:
            continue
        out[nid] = tuple(tuple(int(d) for d in st.shape) for st in sts)
    return topo, out


def verify_json(json_str, shapes=None, types=None, tp_size=1,
                check_registry=False, cost_model=None,
                slow_factor=3.0, plan=False, plan_layout="NCHW",
                mesh=None, parallel=None, memory=None):
    """Verify a serialized symbol (the reference JSON graph layout).

    Runs every :func:`verify_symbol` check *plus* true dead-node
    detection: nodes present in the file but unreachable from any head —
    the defect class hand-edited or generator-produced checkpoints hit,
    which an in-memory Symbol cannot represent (it only holds what its
    heads reach).
    """
    from .. import symbol as _symbol
    report = Report()
    try:
        data = json.loads(json_str)
        raw_nodes = data.get("nodes", [])
        heads = [h[0] for h in data.get("heads", [])]

        # reachability over the flat node table
        reachable, stack = set(), list(heads)
        while stack:
            i = stack.pop()
            if i in reachable or i >= len(raw_nodes):
                continue
            reachable.add(i)
            stack.extend(inp[0] for inp in raw_nodes[i].get("inputs", []))
        for i, entry in enumerate(raw_nodes):
            if i not in reachable:
                report.add("MXG003", "warning",
                           "node %r (op %s) is unreachable from every "
                           "head (dead node)"
                           % (entry.get("name", "#%d" % i),
                              entry.get("op", "?")),
                           node=entry.get("name"))
    except (ValueError, TypeError, AttributeError, KeyError,
            IndexError) as e:
        # not the reference JSON layout at all — one diagnostic, not a
        # traceback (the CLI contract)
        report.add("MXG005", "error",
                   "graph does not parse as the symbol JSON layout: "
                   "%s" % e)
        return report

    try:
        sym = _symbol.load_json(json_str)
    except (MXNetError, ValueError, TypeError, KeyError, IndexError) as e:
        report.add("MXG005", "error",
                   "graph does not deserialize: %s" % e)
        return report
    return verify_symbol(sym, shapes=shapes, types=types, tp_size=tp_size,
                         check_registry=check_registry, report=report,
                         cost_model=cost_model, slow_factor=slow_factor,
                         plan=plan, plan_layout=plan_layout,
                         mesh=mesh, parallel=parallel, memory=memory)


# default verification inputs per model-zoo entry: (data kwargs)
_MODEL_SHAPES = {
    "mlp": {"data": (2, 784)},
    "lenet": {"data": (2, 1, 28, 28)},
}
_DEFAULT_IMAGE = {"data": (2, 3, 224, 224)}


def verify_model(name, batch=2, tp_size=1, num_classes=10,
                 cost_model=None, slow_factor=3.0, plan=False,
                 plan_layout="NCHW", mesh=None, parallel=None,
                 memory=None, **model_kwargs):
    """Build a model-zoo symbol and verify it with its canonical input
    shape.  Returns (symbol, Report).  ``cost_model`` additionally
    runs the MXG010 predicted-slow check (:mod:`.perf`); ``plan=True``
    switches it to committed-plan mode; ``mesh``/``parallel`` run the
    distributed-correctness pass (:mod:`.spmd`)."""
    from .. import models
    net = models.get_model(name, num_classes=num_classes, **model_kwargs)
    shapes = dict(_MODEL_SHAPES.get(name, _DEFAULT_IMAGE))
    shapes = {k: (batch,) + tuple(v[1:]) for k, v in shapes.items()}
    shapes["softmax_label"] = (batch,)
    return net, verify_symbol(net, shapes=shapes, tp_size=tp_size,
                              cost_model=cost_model,
                              slow_factor=slow_factor, plan=plan,
                              plan_layout=plan_layout,
                              mesh=mesh, parallel=parallel,
                              memory=memory)
