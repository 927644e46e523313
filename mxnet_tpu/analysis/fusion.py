"""Block-granularity fusion + layout planning (ROADMAP item 1).

The Glow-style lowering pass (PAPERS.md: arXiv:1805.00907) that turns
the instrumentation of PRs 3-5 into throughput: conv->BN->ReLU and
matmul->bias->activation chains — the blocks that dominate ResNet-style
graphs — are pattern-matched over the Symbol DAG in topo order and each
match is emitted as ONE fused region (`mxnet_tpu.ops.fused`
``fused_block_*``: a single custom-vjp XLA region).  Because every
region carries a hand-written backward, training keeps one fused
dispatch per block in BOTH directions; the plan runs wherever
:func:`mxnet_tpu.symbol.eval_graph` traces — forward, the executor's
vjp backward, and the trainer's fused step.

**Layout planning.**  Each region boundary is pinned to an explicit
activation layout (the trace-time ``image_layout``, NHWC on the TPU
path).  Interior edges of a fused block — conv->BN, BN->act — no longer
cross a region boundary, so the materialization/relayout XLA would
schedule there disappears; when two fused blocks are adjacent (one's
terminal feeds the other's input) the plan additionally pins both sides
of the shared boundary to the same layout, eliminating the relayout
between them.  Both counts are reported as
``mxtpu_fusion_relayouts_eliminated_total``.

**Chains matched** (see docs/api/fusion.md for the full rule catalog):

=============  =====================================================
kind           pattern (every interior node single-consumer)
=============  =====================================================
conv_bn_act    Convolution(2-d) -> BatchNorm -> Activation(relu)
conv_bn        Convolution(2-d) -> BatchNorm (no relu consumer)
bn_act         BatchNorm -> Activation(relu), producer not a fusable
               conv (pre-activation ResNet is full of these)
fc_act         FullyConnected -> Activation(relu|sigmoid|tanh)
=============  =====================================================

BatchNorm nodes must use the reference channel axis (``axis=1``) and
not request ``output_mean_var``; ineligible candidates are recorded as
fallbacks (``mxtpu_fusion_fallback_total{reason=...}``) and evaluated
unfused — the pass degrades, never refuses a graph.

Enabled per-trace by ``ops.fused.block_fusion`` (the
``MXNET_FUSE_BLOCKS`` env default), wired through
``Executor`` (bind-time capture) and ``ShardedTrainer(fuse_blocks=...)``.
"""
from __future__ import annotations

import hashlib
import json

__all__ = ["FusedBlock", "FusionPlan", "plan_block_fusion",
           "apply_block", "last_plan_summary", "FC_FUSABLE_ACTS",
           "graph_digest", "decisions_id", "plan_decisions",
           "active_decisions", "CHAIN_CHOICES"]

FC_FUSABLE_ACTS = ("relu", "sigmoid", "tanh")

#: per-chain-kind decision alternatives the plan search explores
#: (analysis.plansearch).  "fuse" is the greedy behavior; "conv_bn" /
#: "bn_act" split a conv_bn_act chain at its BN boundary; "off" leaves
#: the whole chain unfused.
CHAIN_CHOICES = {
    "conv_bn_act": ("fuse", "conv_bn", "bn_act", "off"),
    "conv_bn": ("fuse", "off"),
    "bn_act": ("fuse", "off"),
    "fc_act": ("fuse", "off"),
}

# summary of the most recent recorded plan (bench.py / fit.py surface
# it; plans are computed at trace time inside jit, so a module-level
# snapshot is the only host-side handle)
_LAST_SUMMARY = None

# the active plan-decision overrides (analysis.plansearch): tri-state
# like ops.fused's trace flags — None means "greedy", a dict is the
# searched decision vector a committed graph_plan cache entry carries.
# Executor/ShardedTrainer enter the context around every eval_graph
# trace so forward, backward, and the fused step lower identically.
_DECISIONS = {"v": None}


class plan_decisions:
    """Context manager activating a plan-decision vector for the traces
    inside it (``None``/``{}`` -> the greedy plan).  See
    docs/api/plansearch.md for the decision schema."""

    def __init__(self, decisions):
        self.decisions = decisions

    def __enter__(self):
        self._prev = _DECISIONS["v"]
        _DECISIONS["v"] = self.decisions
        return self

    def __exit__(self, *exc):
        _DECISIONS["v"] = self._prev


def active_decisions():
    """The decision vector the current trace context activated, or
    None (greedy)."""
    return _DECISIONS["v"]


def graph_digest(topo, entries):
    """Stable 12-hex identity of the graph STRUCTURE — op names, attrs,
    input wiring, and head entries; node *names* excluded, so two
    processes (or two builds in one process, whose auto-naming counters
    differ) constructing the same architecture share one digest.  The
    plan-search tuning-cache entries (``analysis.plansearch``) are
    keyed by it, together with mesh + backend."""
    idx = {id(n): i for i, n in enumerate(topo)}
    items = []
    for n in topo:
        if n.is_variable:
            items.append("var")
            continue
        items.append([
            n.op.name,
            sorted((str(k), repr(v)) for k, v in n.attrs.items()),
            [[idx[id(src)], int(i)] for (src, i) in n.inputs],
        ])
    items.append([[idx[id(n)], int(i)] for (n, i) in entries])
    blob = json.dumps(items, sort_keys=True, default=repr)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:12]


def decisions_id(decisions):
    """Short identity of one decision vector ("greedy" for the empty /
    absent one) — the plan identity costdb records and flight events
    carry."""
    if not decisions:
        return "greedy"
    blob = json.dumps(decisions, sort_keys=True, default=repr)
    return "plan-" + hashlib.sha1(blob.encode("utf-8")).hexdigest()[:10]


class FusedBlock:
    """One matched chain: the member nodes and how to emit them."""
    __slots__ = ("kind", "terminal", "conv", "bn", "fc", "act",
                 "layout", "chain", "graph", "plan_id")

    def __init__(self, kind, terminal, conv=None, bn=None, fc=None,
                 act=None, layout="NCHW", chain=None,
                 graph=None, plan_id=None):
        self.kind = kind
        self.terminal = terminal      # the node whose value the region yields
        self.conv = conv
        self.bn = bn
        self.fc = fc
        self.act = act                # act_type string or None
        self.layout = layout
        self.chain = chain            # stable chain id (greedy-terminal
        self.graph = graph            # topo index), graph digest, and
        self.plan_id = plan_id        # plan identity, for costdb/cache

    @property
    def name(self):
        return self.terminal.name

    def interior(self):
        """Member nodes other than the terminal (skipped at eval)."""
        members = [n for n in (self.conv, self.bn, self.fc)
                   if n is not None and n is not self.terminal]
        return members


class FusionPlan:
    """The pass output: blocks keyed by terminal node id, the interior
    node-id skip set, fallback records, and the layout plan."""

    def __init__(self, layout, is_train, decisions=None, graph=None):
        self.layout = layout
        self.is_train = bool(is_train)
        self.decisions = decisions    # plan-search overrides (or None)
        self.graph = graph            # graph digest (None when unhashed)
        self.plan_id = decisions_id(decisions)
        self.blocks = {}          # id(terminal) -> FusedBlock
        self.skip = set()         # interior node ids
        self.fallbacks = []       # (node_name, reason)
        self.interior_edges = 0   # relayout boundaries removed in-block
        self.adjacent_edges = 0   # same-layout block-to-block boundaries
        self.relayout_edges_added = 0  # explicit boundary transposes a
        self.overrides = 0             # layout override inserts (2/block)

    @property
    def relayouts_eliminated(self):
        return self.interior_edges + self.adjacent_edges

    def add(self, block):
        block.graph = self.graph
        block.plan_id = self.plan_id
        self.blocks[id(block.terminal)] = block
        interior = block.interior()
        for n in interior:
            self.skip.add(id(n))
        self.interior_edges += len(interior)
        if block.kind != "fc_act" and block.layout != self.layout:
            # an overridden-layout region transposes its input in and
            # its output back out (apply_block) — 2 explicit relayouts.
            # Plan-time accounting is shape-free, so this is an upper
            # bound: a non-4d activation transposes nothing (the
            # search never offers layout moves for those — plansearch.
            # chain_moves filters on the inferred shapes)
            self.relayout_edges_added += 2

    def fallback(self, node, reason):
        self.fallbacks.append((node.name, reason))

    def summary(self):
        kinds = {}
        for blk in self.blocks.values():
            kinds[blk.kind] = kinds.get(blk.kind, 0) + 1
        reasons = {}
        for _name, reason in self.fallbacks:
            reasons[reason] = reasons.get(reason, 0) + 1
        return {
            "layout": self.layout,
            "is_train": self.is_train,
            "blocks": len(self.blocks),
            "kinds": kinds,
            # no block lowers to a kernel; the benchmark's reader
            # (layer_metrics/pallas_blocks.py) still asks for the key
            "pallas_blocks": 0,
            "relayouts_eliminated": self.relayouts_eliminated,
            "relayout_edges_added": self.relayout_edges_added,
            "fallbacks": reasons,
            "graph": self.graph,
            "plan_id": self.plan_id,
            "searched": bool(self.decisions),
            "overrides": self.overrides,
        }


def _consumers(topo, entries):
    """id(node) -> list of (consumer node, input slot); graph heads
    count as consumers (a head output must stay visible)."""
    out = {}
    for node in topo:
        for slot, (src, _idx) in enumerate(node.inputs):
            out.setdefault(id(src), []).append((node, slot))
    for (node, _i) in entries:
        out.setdefault(id(node), []).append((None, -1))
    return out


def _single_consumer(consumers, node):
    """The unique (consumer, slot) of ``node``, or None."""
    cs = consumers.get(id(node), ())
    if len(cs) != 1 or cs[0][0] is None:
        return None
    return cs[0]


def _is_op(node, name):
    return (not node.is_variable and node.op is not None
            and node.op.name == name)


def _bn_fusable(bn, plan):
    """BatchNorm eligibility shared by every BN-bearing chain."""
    if bn.attrs.get("output_mean_var"):
        plan.fallback(bn, "bn_output_mean_var")
        return False
    if int(bn.attrs.get("axis", 1)) != 1:
        plan.fallback(bn, "bn_axis")
        return False
    return True


def _conv_fusable(conv, layout, plan, claimed):
    """Convolution eligibility as the head of a conv_bn* chain."""
    if id(conv) in claimed:
        plan.fallback(conv, "claimed_by_other_pass")
        return False
    if len(tuple(conv.attrs.get("kernel") or ())) != 2:
        plan.fallback(conv, "conv_ndim")
        return False
    if conv.attrs.get("layout") and conv.attrs["layout"] != layout:
        plan.fallback(conv, "conv_layout_pinned")
        return False
    return True


def _apply_decision(blk, cid, decisions, plan):
    """Transform one greedy-matched block by the plan-search decision
    vector (``decisions``): per-chain fuse/split/off and per-region
    layout.  ``cid`` is the chain's stable id (the GREEDY terminal's
    topo index, as a string) — the key every committed ``graph_plan``
    cache entry uses.  Returns the
    block to plan (possibly a shorter chain) or None (chain unfused).
    Unknown/ineligible choices read as "fuse" — a stale entry must
    degrade, never break a trace."""
    if not decisions:
        blk.chain = cid
        return blk
    choice = str((decisions.get("chains") or {}).get(cid, "fuse"))
    if choice not in CHAIN_CHOICES.get(blk.kind, ("fuse",)):
        choice = "fuse"
    if choice == "off":
        plan.overrides += 1
        return None
    if choice == "conv_bn" and blk.kind == "conv_bn_act":
        blk = FusedBlock("conv_bn", terminal=blk.bn, conv=blk.conv,
                         bn=blk.bn, act=None, layout=blk.layout)
        plan.overrides += 1
    elif choice == "bn_act" and blk.kind == "conv_bn_act":
        blk = FusedBlock("bn_act", terminal=blk.terminal, bn=blk.bn,
                         act=blk.act, layout=blk.layout)
        plan.overrides += 1
    layout = (decisions.get("layouts") or {}).get(cid)
    if layout in ("NCHW", "NHWC") and layout != blk.layout \
            and blk.kind != "fc_act":
        blk.layout = layout
        plan.overrides += 1
    blk.chain = cid
    return blk


def plan_block_fusion(topo, entries, layout="NCHW", is_train=True,
                      exclude=(), record=True, decisions=None):
    """Match fusable chains over ``topo`` and return a
    :class:`FusionPlan`.  ``exclude``: node ids already claimed by
    another trace-time pass (stem s2d, dX elision) — chains
    touching them fall back.  ``record`` emits the ``mxtpu_fusion_*``
    metrics and a ``fusion_plan`` flight event (one per trace).
    ``decisions``: plan-search overrides (analysis.plansearch; default:
    the :class:`plan_decisions` context, i.e. the committed cache
    entry Executor/ShardedTrainer activated — None means greedy)."""
    if decisions is None:
        decisions = active_decisions()
    digest = graph_digest(topo, entries) if (record or decisions) \
        else None
    plan = FusionPlan(layout, is_train, decisions=decisions,
                      graph=digest)
    consumers = _consumers(topo, entries)
    claimed = set(exclude)
    topo_index = {id(n): i for i, n in enumerate(topo)}

    def conv_chain(bn, act_node, act_type):
        """Try conv->bn(->act); returns the block or None."""
        src, idx = bn.inputs[0]
        if not _is_op(src, "Convolution") or idx != 0:
            return None
        nxt = _single_consumer(consumers, src)
        if nxt is None or nxt[0] is not bn:
            plan.fallback(src, "conv_multi_consumer")
            return None
        if not _conv_fusable(src, layout, plan, claimed):
            return None
        return FusedBlock("conv_bn_act" if act_node is not None
                          else "conv_bn",
                          terminal=act_node if act_node is not None
                          else bn,
                          conv=src, bn=bn, act=act_type, layout=layout)

    for node in topo:
        if node.is_variable or node.op is None or id(node) in claimed:
            continue
        blk = None
        if _is_op(node, "Activation"):
            act_type = node.attrs.get("act_type", "relu")
            src, idx = node.inputs[0]
            if src.is_variable or src.op is None or idx != 0 \
                    or id(src) in claimed or id(src) in plan.skip \
                    or id(src) in plan.blocks:
                continue
            one = _single_consumer(consumers, src)
            if one is None or one[0] is not node:
                continue
            if _is_op(src, "BatchNorm") and act_type == "relu":
                if not _bn_fusable(src, plan):
                    continue
                blk = conv_chain(src, node, act_type)
                if blk is None:
                    blk = FusedBlock("bn_act", terminal=node, bn=src,
                                     act=act_type, layout=layout)
            elif _is_op(src, "FullyConnected") \
                    and act_type in FC_FUSABLE_ACTS:
                blk = FusedBlock("fc_act", terminal=node, fc=src,
                                 act=act_type, layout=layout)
            elif _is_op(src, "BatchNorm"):
                plan.fallback(node, "act_type")
        elif _is_op(node, "BatchNorm"):
            if id(node) in plan.skip or id(node) in plan.blocks:
                continue
            # BN whose single consumer is a fusable relu is deferred to
            # the Activation visit above (the longer chain wins)
            one = _single_consumer(consumers, node)
            if one is not None and _is_op(one[0], "Activation") \
                    and one[0].attrs.get("act_type") == "relu" \
                    and one[1] == 0:
                continue
            if not _bn_fusable(node, plan):
                continue
            blk = conv_chain(node, None, None)
        if blk is not None:
            # the chain id is the GREEDY terminal's topo position, so a
            # committed decision vector survives rebuilds whose auto-
            # generated node names differ
            blk = _apply_decision(blk, str(topo_index[id(node)]),
                                  decisions, plan)
        if blk is not None:
            # a block's members must not collide with earlier claims
            members = blk.interior() + [blk.terminal]
            if any(id(m) in plan.skip or id(m) in plan.blocks
                   for m in members):
                continue
            plan.add(blk)

    # layout plan: adjacent fused regions sharing an IMAGE-layout
    # boundary keep one pinned layout — no relayout between them.  The
    # credit needs image activations on BOTH sides: an fc_act block
    # neither carries an image layout out (its terminal is a 2-d
    # activation) nor reads one in (FullyConnected flattens its input,
    # paying that materialization regardless of any pinning), so FC
    # boundaries never count — crediting them overstated the
    # mxtpu_fusion_relayouts_eliminated_total metric.  Both sides must
    # also sit in the AMBIENT layout: an overridden-layout region
    # round-trips through the ambient layout at every boundary
    # (apply_block), so two adjacent NHWC-overridden regions in an
    # NCHW trace still pay their transposes — their boundary
    # eliminates nothing (relayout_edges_added counts what they pay).
    image_terminal = {tid: b.layout for tid, b in plan.blocks.items()
                      if b.kind != "fc_act"}
    for blk in plan.blocks.values():
        if blk.fc is not None or blk.layout != plan.layout:
            continue
        first = blk.conv or blk.bn
        src, _idx = first.inputs[0]
        if image_terminal.get(id(src)) == plan.layout:
            plan.adjacent_edges += 1

    if record:
        _record(plan)
    return plan


def _record(plan):
    """Emit the plan's metrics + flight event and snapshot the summary
    (runs at trace time — host-side python, once per compile)."""
    global _LAST_SUMMARY
    s = plan.summary()
    _LAST_SUMMARY = s
    try:
        from .. import telemetry
        from ..telemetry import flight
        telemetry.counter("mxtpu_fusion_plans_total").inc()
        for kind, n in s["kinds"].items():
            telemetry.counter("mxtpu_fusion_blocks_total").labels(
                kind=kind).inc(n)
        if s["relayouts_eliminated"]:
            telemetry.counter(
                "mxtpu_fusion_relayouts_eliminated_total").inc(
                s["relayouts_eliminated"])
        for reason, n in s["fallbacks"].items():
            telemetry.counter("mxtpu_fusion_fallback_total").labels(
                reason=reason).inc(n)
        flight.record("fusion_plan", **s)
    except MemoryError:  # pragma: no cover - observability must not kill a trace
        raise
    except Exception:  # mxlint: allow-broad-except(metric emission is observability; a telemetry failure must not fail the trace that is being fused)
        pass


def last_plan_summary():
    """Summary dict of the most recent recorded plan in this process
    (None before any fused trace).  See :meth:`FusionPlan.summary`."""
    return _LAST_SUMMARY


def _relayout(x, dst_layout):
    """Explicit boundary transpose into ``dst_layout`` for a 4-d image
    activation (the relayout edge an overridden-layout region pays —
    plan.relayout_edges_added counts them, and the plan-search
    objective costs them at peak bandwidth)."""
    if x is None or getattr(x, "ndim", 0) != 4:
        return x
    import jax.numpy as jnp
    return jnp.transpose(x, (0, 2, 3, 1) if dst_layout == "NHWC"
                         else (0, 3, 1, 2))


def apply_block(blk, vals, is_train):
    """Evaluate one planned block from the eval_graph value map.
    Returns (out, bn_node_or_None, [new_mm, new_mv] or None); the
    caller threads the BN aux updates exactly as the unfused op would.

    A block whose ``layout`` differs from the ambient trace layout (a
    plan-search per-region override) transposes its image activation
    into the region layout on entry and back on exit — the weight path
    is layout-independent (reference OIHW, dimension numbers derived
    inside the region).
    """
    from ..ops import fused as _fused
    from ..ops.nn import current_image_layout

    def val(node, slot):
        src, idx = node.inputs[slot]
        return vals[id(src)][idx]

    ambient = current_image_layout()

    if blk.kind in ("conv_bn_act", "conv_bn"):
        conv, bn = blk.conv, blk.bn
        x, w = val(conv, 0), val(conv, 1)
        b = None if conv.attrs.get("no_bias") else val(conv, 2)
        gamma, beta = val(bn, 1), val(bn, 2)
        mm, mv = val(bn, 3), val(bn, 4)
        if blk.layout != ambient:
            x = _relayout(x, blk.layout)
        out, new_mm, new_mv = _fused.fused_block_conv_bn_act(
            conv.attrs, bn.attrs, blk.layout, is_train, blk.act,
            x, w, b, gamma, beta, mm, mv)
        _note_block_cost(blk, out, x, w)
        _note_block_numerics(blk, out)
        if blk.layout != ambient:
            out = _relayout(out, ambient)
        return out, bn, [new_mm, new_mv]
    if blk.kind == "bn_act":
        bn = blk.bn
        x = val(bn, 0)
        if blk.layout != ambient:
            x = _relayout(x, blk.layout)
        ch = 3 if (blk.layout == "NHWC" and x.ndim == 4) else 1
        out, new_mm, new_mv = _fused.fused_block_bn_act(
            bn.attrs, ch, is_train, blk.act, x, val(bn, 1), val(bn, 2),
            val(bn, 3), val(bn, 4))
        _note_block_cost(blk, out, x, None)
        _note_block_numerics(blk, out)
        if blk.layout != ambient:
            out = _relayout(out, ambient)
        return out, bn, [new_mm, new_mv]
    if blk.kind == "fc_act":
        fc = blk.fc
        x, w = val(fc, 0), val(fc, 1)
        b = None if fc.attrs.get("no_bias") else val(fc, 2)
        out = _fused.fused_block_fc_act(fc.attrs, blk.act, x, w, b)
        _note_block_cost(blk, out, x, w)
        _note_block_numerics(blk, out)
        return out, None, None
    raise ValueError("unknown fused block kind %r" % (blk.kind,))


def _note_block_numerics(blk, out):
    """Feed the block's output into an active numerics collection
    window (telemetry.numerics.block_stats) — zero added trace work
    outside the trainer's sampled stats variant."""
    from ..telemetry import numerics as _numerics
    _numerics.note_block(blk.name, out)


def _note_block_cost(blk, out, x, w):
    """Register the applied block as a pending cost-database signature
    (telemetry.costdb) with analytic flops/bytes estimates from the
    trace-time shapes — runs host-side inside the trace, once per
    compile.  The dispatch that owns this compile binds the signature
    and attributes measured wall time to it.
    Observability: any failure is swallowed, the trace must never pay
    for it."""
    try:
        from ..telemetry import costdb
        import numpy as _np

        def _nbytes(a):
            return int(a.size) * _np.dtype(a.dtype).itemsize

        shapes = [tuple(x.shape)] + ([tuple(w.shape)]
                                     if w is not None else [])
        dtypes = [str(x.dtype)] + ([str(w.dtype)]
                                   if w is not None else [])
        if w is not None:
            # conv and FC share one formula: every output element costs
            # (w.size / n_out) MACs — C*R*S for a conv, the input width
            # for an FC — plus the ~10 flops/element BN/act epilogue.
            # n_out comes from the op attrs (num_filter / num_hidden).
            node = blk.conv if blk.conv is not None else blk.fc
            n_out = int(node.attrs.get("num_filter")
                        or node.attrs.get("num_hidden")
                        or w.shape[0])
            flops = 2.0 * int(out.size) * int(w.size) / n_out \
                + 10.0 * int(out.size)
            bytes_ = _nbytes(x) + _nbytes(w) + _nbytes(out)
        else:
            # bn_act: pure elementwise normalize/scale/shift/act
            flops = 10.0 * int(out.size)
            bytes_ = _nbytes(x) + _nbytes(out)
        costdb.note_block(
            blk.name, blk.kind, shapes, dtypes, flops=flops,
            bytes_accessed=bytes_, layout=blk.layout,
            graph=blk.graph, plan=blk.plan_id)
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(cost-signature capture is observability inside a jit trace; any failure must not fail the compile)
        pass
