"""Cost-model-guided whole-graph plan search (ROADMAP item 3).

`analysis.fusion` is a greedy fixed-pattern matcher: the first longest
chain wins and layout choice is purely local.  The
costdb roofline (PR 7) *measures* the MFU gap those local choices leave
behind but nothing acts on it globally.  This module closes the loop
Relay/TVM-style (PAPERS.md: arXiv:1810.00952, arXiv:1802.04799):

* **search space** — one decision vector over the greedy plan's chain
  candidates: per-chain ``fuse``/``conv_bn``/``bn_act``/``off``
  (``fusion.CHAIN_CHOICES`` — splits the chains the greedy
  longest-chain-wins rule forecloses) and per-region layout
  (``NCHW``/``NHWC``, with the explicit boundary relayouts
  ``fusion.apply_block`` inserts costed at peak bandwidth).  Chains
  are keyed by the greedy terminal's topo index, so a committed vector
  survives rebuilds whose auto-generated node names differ;
* **objective** — predicted step wall from the learned cost model
  (:mod:`mxnet_tpu.autotune.model`, arXiv:2008.01040) over analytic
  flops/bytes per unit (the same formulas the trace-time costdb notes
  use: ``fusion._note_block_cost`` for fused regions,
  ``analysis.perf.node_cost_estimate`` for the unfused heavies), with
  the roofline-attainable bound as the model-free fallback;
* **search** — deterministic beam search over single-decision
  neighbor moves, the greedy plan always seeded into the population,
  so the searched predicted wall can never regress the greedy one;
* **measurement** — the top-k candidates (plus greedy, always) are
  measured for real with :func:`mxnet_tpu.autotune.measure` on a
  traced forward+backward step of the actual graph, each candidate's
  decisions active at trace time;
* **commit** — the measured winner persists as a ``graph_plan`` entry
  in the ``mxtpu-tunecache/1`` tuning cache, keyed by graph digest
  (``fusion.graph_digest`` — structure, not names) + trace layout +
  mesh + backend.  ``Executor``/``ShardedTrainer`` consult the entry
  at bind time (:func:`committed_decisions`) and activate it around
  every trace, so a tuned plan is picked up on every later run with
  zero search cost — greedy on miss, exactly like kernel configs.

Driver: ``tools/plan_search.py`` (``--model resnet50 --budget N``).
Feedback loop: ``tools/perf_top.py --suggest`` emits ``plan`` rows for
worst-MFU blocks whose graph has an untuned/stale entry, and
``python -m mxnet_tpu.analysis --plan`` reports MXG010 predictions for
the *committed* plan rather than the default lowering.  Env:
``MXNET_TPU_PLAN_SEARCH`` (off|cache), ``MXNET_TPU_PLAN_BUDGET``,
``MXNET_TPU_PLAN_BEAM``.  See docs/api/plansearch.md.
"""
from __future__ import annotations

import json
import os
import threading

from . import fusion as _fusion

__all__ = [
    "OP", "plan_mode", "plan_budget", "plan_beam",
    "lookup_entry", "committed_decisions", "stats", "reset_stats",
    "predict_plan_wall", "chain_moves", "search_plan",
    "build_step_values", "measure_decisions", "search_and_commit",
]

#: the tuning-cache op name of a graph-level plan entry
OP = "graph_plan"

_MODES = ("off", "cache")


def plan_mode():
    """``MXNET_TPU_PLAN_SEARCH``: ``off`` (no bind-time lookups) |
    ``cache`` (default — consult the tuning cache at bind time, greedy
    on miss).  Unknown values read as ``cache``; searching never
    happens implicitly (it is an offline driver / CI action)."""
    v = os.environ.get("MXNET_TPU_PLAN_SEARCH", "cache").strip().lower()
    return v if v in _MODES else "cache"


def _env_int(name, default):
    try:
        return max(1, int(os.environ.get(name, "") or default))
    except ValueError:
        return default


def plan_budget():
    """``MXNET_TPU_PLAN_BUDGET``: max candidate plans the beam search
    scores with the cost model (default 64)."""
    return _env_int("MXNET_TPU_PLAN_BUDGET", 64)


def plan_beam():
    """``MXNET_TPU_PLAN_BEAM``: beam width (default 8)."""
    return _env_int("MXNET_TPU_PLAN_BEAM", 8)


# ------------------------------------------------------- cache lookup

_STATS_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0}


def reset_stats():
    """Zero the bind-time hit/miss counters (tests)."""
    with _STATS_LOCK:
        _STATS.update(hits=0, misses=0)


def stats():
    """Bind-time plan-lookup counters for this process."""
    with _STATS_LOCK:
        return dict(_STATS)


def lookup_entry(graph, layout, mesh=None):
    """Raw ``graph_plan`` tuning-cache entry for (graph digest, trace
    layout, mesh, backend), or None — no mode gate, no metrics."""
    from .. import autotune
    return autotune.lookup(OP, [], [], mesh=mesh,
                           extra={"graph": str(graph),
                                  "layout": str(layout)})


def committed_decisions(topo, entries, layout, mesh=None):
    """The bind-time consult: the committed plan's decision vector for
    this graph (``{}`` when the committed winner IS the greedy plan),
    or None on miss/off — the caller traces greedy either way, but a
    dict (even empty) means a cache entry owns the plan.  Emits
    ``mxtpu_plan_cache_{hit,miss}_total`` and a ``plan_lookup`` flight
    event carrying the graph digest + plan identity, so the dispatched
    plan is attributable in costdb/flight postmortems.  Never raises —
    a broken cache must not break a bind."""
    try:
        if plan_mode() == "off":
            return None
        graph = _fusion.graph_digest(topo, entries)
        entry = lookup_entry(graph, layout, mesh=mesh)
        hit = entry is not None
        decisions = None
        if hit:
            cfg = entry.get("config") or {}
            decisions = cfg.get("decisions")
            decisions = dict(decisions) if isinstance(decisions, dict) \
                else {}
        with _STATS_LOCK:
            _STATS["hits" if hit else "misses"] += 1
        try:
            from ..telemetry import counter, flight
            name = ("mxtpu_plan_cache_hit_total" if hit
                    else "mxtpu_plan_cache_miss_total")
            counter(name).inc()
            flight.record("plan_lookup", graph=graph, layout=str(layout),
                          hit=hit,
                          plan=_fusion.decisions_id(decisions)
                          if hit else None)
        except Exception:  # mxlint: allow-broad-except(lookup accounting is observability at bind time; a metric failure must not fail the bind)
            pass
        return decisions
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(the bind-time plan lookup is advisory; any failure reads as a plain miss and the trace falls back to the greedy plan)
        return None


# -------------------------------------------------------- the objective

def _size(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _out_shape(node_shapes, node, idx=0):
    sh = node_shapes.get(id(node))
    if not sh or len(sh) <= idx:
        return None
    return tuple(int(d) for d in sh[idx])


def _in_shape(node_shapes, node, slot):
    src, idx = node.inputs[slot]
    return _out_shape(node_shapes, src, idx)


def _block_cost(blk, node_shapes, itemsize=4):
    """Analytic (flops, bytes) of one fused block at shape-inference
    time — the same formulas ``fusion._note_block_cost`` feeds the
    costdb at trace time, so the objective and the measured ground
    truth describe the same quantity.  None when shapes are
    unresolved."""
    out = _out_shape(node_shapes, blk.terminal)
    if out is None:
        return None
    out_size = _size(out)
    if blk.kind == "bn_act":
        x = _in_shape(node_shapes, blk.bn, 0)
        if x is None:
            return None
        return (10.0 * out_size,
                float(itemsize) * (_size(x) + out_size))
    head = blk.conv if blk.conv is not None else blk.fc
    x = _in_shape(node_shapes, head, 0)
    w = _in_shape(node_shapes, head, 1)
    if x is None or w is None:
        return None
    n_out = int(head.attrs.get("num_filter")
                or head.attrs.get("num_hidden") or w[0])
    flops = 2.0 * out_size * _size(w) / max(1, n_out) + 10.0 * out_size
    bytes_ = float(itemsize) * (_size(x) + _size(w) + out_size)
    return flops, max(bytes_, float(itemsize))


def predict_plan_wall(topo, entries, plan, node_shapes, model=None,
                      backend=None):
    """Predicted step wall of one candidate plan: the cost model's
    prediction (roofline-attainable fallback when ``model`` is None or
    cannot predict) summed over every fused block and every unfused
    heavy node, plus the explicit boundary-relayout traffic of
    overridden-layout regions at peak bandwidth.  Returns ``(total_s,
    units)`` — one unit dict per costed block/node, the breakdown
    MXG010's ``--plan`` mode and the driver report render."""
    from ..telemetry import costdb
    from .perf import node_cost_estimate

    backend = backend or costdb.backend_name()
    pf = costdb.peak_flops(backend)
    pbw = costdb.peak_bandwidth(backend)
    units = []
    total = 0.0

    def predicted(flops, bytes_):
        att = costdb._attainable_s(flops, bytes_ or None, pf, pbw)
        pred = None
        if model is not None:
            pred = model.predict(flops=flops, bytes_accessed=bytes_,
                                 backend=backend)
        return (pred if pred is not None else att), att

    for node in topo:
        if node.is_variable or node.op is None:
            continue
        if id(node) in plan.skip:
            continue
        blk = plan.blocks.get(id(node))
        if blk is not None:
            cost = _block_cost(blk, node_shapes)
            if cost is None:
                continue
            flops, bytes_ = cost
            pred, att = predicted(flops, bytes_)
            relayout_s = 0.0
            if blk.kind != "fc_act" and blk.layout != plan.layout:
                x = _in_shape(node_shapes,
                              blk.conv or blk.bn, 0)
                out = _out_shape(node_shapes, blk.terminal)
                # apply_block's _relayout only transposes 4-d image
                # activations — a non-4d block pays nothing
                if x is not None and out is not None and pbw \
                        and len(x) == 4 and len(out) == 4:
                    # one transpose in, one out: read+write each
                    relayout_s = 2.0 * 4.0 * (_size(x) + _size(out)) \
                        / pbw
            if pred is not None:
                total += pred + relayout_s
                units.append({
                    "unit": "block", "name": blk.name,
                    "kind": blk.kind, "chain": blk.chain,
                    "layout": blk.layout,
                    "flops": flops, "bytes": bytes_,
                    "attainable_s": att, "predicted_s": pred,
                    "relayout_s": relayout_s,
                })
            continue
        # unfused node: only the heavies the analytic estimator models
        out_shapes = []
        sh = node_shapes.get(id(node))
        if sh:
            out_shapes = [tuple(int(d) for d in s) for s in sh]
        in_shapes = []
        ok = True
        for slot in range(len(node.inputs)):
            s = _in_shape(node_shapes, node, slot)
            if s is None:
                ok = False
                break
            in_shapes.append(s)
        if not ok or not out_shapes:
            continue
        est = node_cost_estimate(node, in_shapes, out_shapes)
        if est is None:
            if node.op.name == "Activation":
                # the act a split/off decision pushes OUT of a fused
                # region: one extra elementwise pass (read + write)
                # over the activation — exactly the traffic fusing it
                # into the epilogue eliminates.  Without this term
                # every split scores tied-with-greedy and the
                # measurement budget fills with candidates that are
                # strictly worse in reality.
                out_size = _size(out_shapes[0])
                est = (float(out_size), 8.0 * out_size)
            else:
                continue
        flops, bytes_ = est
        pred, att = predicted(flops, bytes_)
        if pred is not None:
            total += pred
            units.append({
                "unit": "node", "name": node.name,
                "kind": node.op.name, "chain": None,
                "layout": None,
                "flops": flops, "bytes": bytes_,
                "attainable_s": att, "predicted_s": pred,
                "relayout_s": 0.0,
            })
    return total, units


# ------------------------------------------------------------ search

def chain_moves(topo, entries, layout, is_train=True,
                node_shapes=None):
    """The single-decision neighbor moves of this graph's search space,
    derived from the greedy plan: per chain the non-greedy
    ``CHAIN_CHOICES`` and a layout flip for image chains.  With
    ``node_shapes``, layout flips are only offered for chains whose
    activation is actually 4-d (``apply_block`` transposes nothing
    else, so the move would be a no-op with phantom accounting).
    Returns ``(greedy_plan, moves)`` with each move a ``(category,
    chain_id, value)`` triple."""
    greedy = _fusion.plan_block_fusion(topo, entries, layout=layout,
                                      is_train=is_train, record=False,
                                      decisions={})
    moves = []
    other = "NCHW" if layout == "NHWC" else "NHWC"
    for blk in greedy.blocks.values():
        cid = blk.chain
        for choice in _fusion.CHAIN_CHOICES.get(blk.kind, ()):
            if choice != "fuse":
                moves.append(("chains", cid, choice))
        if blk.kind != "fc_act":
            x = None
            if node_shapes is not None:
                x = _in_shape(node_shapes, blk.conv or blk.bn, 0)
            if node_shapes is None or (x is not None and len(x) == 4):
                moves.append(("layouts", cid, other))
    return greedy, moves


def _with_move(decisions, cat, cid, val):
    """Decision vector with one move applied (re-applying the same
    value toggles it back off — the beam can retreat toward greedy)."""
    nd = {k: dict(v) for k, v in decisions.items()}
    cur = nd.get(cat, {}).get(cid)
    if cur == val:
        del nd[cat][cid]
        if not nd[cat]:
            del nd[cat]
    else:
        nd.setdefault(cat, {})[cid] = val
    return nd


def _canon(decisions):
    return json.dumps(decisions, sort_keys=True)


def search_plan(topo, entries, layout="NHWC", is_train=True,
                node_shapes=None, model=None, budget=None, beam=None):
    """Beam search over whole-graph plan decisions, scored by
    :func:`predict_plan_wall`.  The greedy plan (empty decision
    vector) is always seeded into the population, so the returned
    best candidate's predicted wall is <= the greedy plan's by
    construction.  Returns candidates sorted best-predicted-first:
    ``{"decisions", "plan_id", "predicted_s", "blocks", "units"}``."""
    if node_shapes is None:
        raise ValueError("search_plan needs node_shapes (use "
                         "analysis.infer_node_shapes)")
    budget = int(budget or plan_budget())
    beam = int(beam or plan_beam())
    _greedy_plan, moves = chain_moves(topo, entries, layout,
                                      is_train=is_train,
                                      node_shapes=node_shapes)
    evaluated = {}

    def score(decisions):
        key = _canon(decisions)
        if key in evaluated:
            return evaluated[key]
        plan = _fusion.plan_block_fusion(
            topo, entries, layout=layout, is_train=is_train,
            record=False, decisions=dict(decisions) if decisions
            else {})
        total, units = predict_plan_wall(topo, entries, plan,
                                         node_shapes, model=model)
        res = {"decisions": decisions,
               "plan_id": _fusion.decisions_id(decisions),
               "predicted_s": total, "blocks": len(plan.blocks),
               "units": units}
        evaluated[key] = res
        return res

    score({})
    frontier = [{}]
    while len(evaluated) < budget and moves:
        fresh = []
        for d in frontier:
            for (cat, cid, val) in moves:
                nd = _with_move(d, cat, cid, val)
                if _canon(nd) not in evaluated:
                    fresh.append(nd)
                    score(nd)
                    if len(evaluated) >= budget:
                        break
            if len(evaluated) >= budget:
                break
        if not fresh:
            break
        ranked = sorted(evaluated.values(),
                        key=lambda r: (r["predicted_s"], r["plan_id"]))
        new_frontier = [r["decisions"] for r in ranked[:beam]]
        if [_canon(d) for d in new_frontier] == \
                [_canon(d) for d in frontier]:
            break
        frontier = new_frontier
    return sorted(evaluated.values(),
                  key=lambda r: (r["predicted_s"], r["plan_id"]))


# -------------------------------------------------------- measurement

def build_step_values(symbol, data_shapes, layout="NHWC", seed=0):
    """Deterministic argument/aux value arrays for measuring a
    training step of ``symbol`` at ``data_shapes`` (reference NCHW
    global shapes; 4-d data inputs are transposed to NHWC when the
    trace layout asks, exactly like the trainer's ingest).  Returns
    ``(arg_nodes, aux_nodes, vals)`` with ``vals`` ordered args then
    aux — the layout :func:`measure_decisions`'s step fn expects."""
    import numpy as np
    from ..symbol import _classify_vars

    topo = symbol._topo()
    arg_nodes, aux_nodes = _classify_vars(topo)
    arg_shapes, _out, aux_shapes = symbol.infer_shape(**data_shapes)
    rng = np.random.RandomState(seed)
    vals = []
    for node, shape in zip(arg_nodes, arg_shapes):
        name = node.name
        if name in data_shapes and "label" in name:
            v = rng.randint(0, 2, shape).astype(np.float32)
        elif name in data_shapes:
            v = rng.uniform(-1, 1, shape).astype(np.float32)
            if layout == "NHWC" and len(shape) == 4:
                v = np.transpose(v, (0, 2, 3, 1)).copy()
        elif "gamma" in name or "var" in name:
            v = rng.uniform(0.5, 1.5, shape).astype(np.float32)
        else:
            v = (rng.uniform(-0.5, 0.5, shape) * 0.2).astype(np.float32)
        vals.append(v)
    # aux: moving mean ~0, moving var ~1 keeps BN numerics tame
    for node, shape in zip(aux_nodes, aux_shapes):
        if "var" in node.name:
            vals.append(np.ones(shape, np.float32))
        else:
            vals.append(np.zeros(shape, np.float32))
    return arg_nodes, aux_nodes, vals


def measure_decisions(symbol, data_shapes, decisions, layout="NHWC",
                      repeats=2, seed=0, values=None):
    """Measured wall seconds of ONE forward+backward training step of
    ``symbol`` with ``decisions`` active at trace time — the real A/B
    leg ``autotune.measure`` times (synchronized, min-of-N, compile
    excluded).  ``values``: reuse :func:`build_step_values` output so
    every candidate measures on identical inputs."""
    import jax
    import jax.numpy as jnp
    from .. import autotune
    from ..symbol import eval_graph
    from ..ops.nn import image_layout
    from ..ops.fused import block_fusion

    topo, entries = symbol._topo(), symbol._entries
    if values is None:
        values = build_step_values(symbol, data_shapes, layout=layout,
                                   seed=seed)
    arg_nodes, aux_nodes, vals = values
    var_ids = [id(n) for n in arg_nodes + aux_nodes]
    n_args = len(arg_nodes)
    head_is_loss = [bool(n.op is not None and n.op.is_loss)
                    for (n, _i) in entries]
    # labels are not differentiated (their central role is indexing)
    diff_idx = tuple(i for i, n in enumerate(arg_nodes)
                     if "label" not in n.name)
    decisions = dict(decisions) if decisions else {}

    def step(*all_vals):
        diff_vals = tuple(all_vals[i] for i in diff_idx)

        def f(diff):
            full = list(all_vals)
            for j, i in enumerate(diff_idx):
                full[i] = diff[j]
            var_values = dict(zip(var_ids, full))
            bsz = full[0].shape[0] if full and full[0].ndim else None
            with image_layout(layout), block_fusion(True), \
                    _fusion.plan_decisions(decisions):
                heads, _aux = eval_graph(
                    topo, entries, var_values, is_train=True,
                    key=jax.random.PRNGKey(0), batch_size=bsz)
            return heads

        heads, vjp = jax.vjp(f, diff_vals)
        cot = [jnp.ones_like(h) if il else jnp.zeros_like(h)
               for h, il in zip(heads, head_is_loss)]
        (grads,) = vjp(list(cot))
        return heads, grads

    return autotune.measure(step, tuple(vals), repeats=repeats)


# ------------------------------------------------- search-and-commit

def search_and_commit(symbol, data_shapes, layout="NHWC", model=None,
                      budget=None, beam=None, topk=3, repeats=2,
                      mesh=None, commit=True, cache=None, force=False,
                      measure=True, node_shapes=None, say=None):
    """The full loop the driver / ci_check / bench run: search, measure
    the top-k predicted candidates (greedy ALWAYS measured alongside —
    the committed winner can never be worse than greedy on the
    measured run), commit the winner to the tuning cache keyed by
    (graph digest, layout, mesh, backend).  A pre-existing entry short-
    circuits everything unless ``force`` (the all-hit second run is
    the CI contract).  Returns the report doc."""
    from .. import autotune
    from .verifier import infer_node_shapes

    say = say or (lambda s: None)
    topo, entries = symbol._topo(), symbol._entries
    graph = _fusion.graph_digest(topo, entries)
    doc = {"schema": "mxtpu-plansearch/1", "graph": graph,
           "layout": layout, "mesh": dict(mesh) if mesh else None,
           "cached": False, "searched": 0, "measured": 0}

    if cache is not None:
        existing = cache.lookup(OP, [], [], mesh=mesh,
                                extra={"graph": graph,
                                       "layout": str(layout)})
    else:
        existing = lookup_entry(graph, layout, mesh=mesh)
    if existing is not None and not force:
        cfg = existing.get("config") or {}
        say("plan_search: graph %s cached (plan %s, wall %.3g ms)"
            % (graph, cfg.get("plan_id"),
               1e3 * (existing.get("wall_s") or 0)))
        doc.update(cached=True, entry=existing,
                   plan_id=cfg.get("plan_id"),
                   predicted_s=cfg.get("predicted_s"),
                   greedy_predicted_s=cfg.get("greedy_predicted_s"),
                   wall_s=existing.get("wall_s"),
                   greedy_wall_s=existing.get("heuristic_wall_s"))
        return doc

    if node_shapes is None:
        _topo2, node_shapes = infer_node_shapes(symbol, data_shapes)
    ranked = search_plan(topo, entries, layout=layout,
                         node_shapes=node_shapes, model=model,
                         budget=budget, beam=beam)
    doc["searched"] = len(ranked)
    greedy = next(r for r in ranked if not r["decisions"])
    best_pred = ranked[0]
    say("plan_search: graph %s — %d candidate(s) scored; greedy "
        "predicted %.3g ms, best predicted %.3g ms (%s)"
        % (graph, len(ranked), 1e3 * greedy["predicted_s"],
           1e3 * best_pred["predicted_s"], best_pred["plan_id"]))

    # measurement set: greedy + the top-k predicted, RESTRICTED to
    # candidates the objective scores at least as well as greedy — a
    # predicted-worse plan is never committed (the CI contract:
    # committed predicted <= greedy predicted), so measuring one is
    # wasted budget
    bar = greedy["predicted_s"] * (1.0 + 1e-9)
    candidates, seen = [], set()
    for r in [greedy] + [r for r in ranked[:max(1, int(topk))]
                         if r["predicted_s"] <= bar]:
        key = _canon(r["decisions"])
        if key not in seen:
            seen.add(key)
            candidates.append(r)

    winner = best_pred
    greedy_wall = None
    if measure:
        values = build_step_values(symbol, data_shapes, layout=layout)
        measured = []
        for r in candidates:
            try:
                wall = measure_decisions(symbol, data_shapes,
                                         r["decisions"], layout=layout,
                                         repeats=repeats, values=values)
            except Exception as e:  # mxlint: allow-broad-except(a candidate plan that fails to trace/compile is simply not a winner; the search continues with the rest of the measured set)
                say("plan_search:   %-14s FAILED: %s"
                    % (r["plan_id"], str(e)[:120]))
                continue
            say("plan_search:   %-14s predicted %.3g ms measured "
                "%.3g ms" % (r["plan_id"], 1e3 * r["predicted_s"],
                             1e3 * wall))
            measured.append(dict(r, wall_s=wall))
        doc["measured"] = len(measured)
        if not measured:
            doc["error"] = "no candidate plan measured"
            return doc
        greedy_row = next((m for m in measured if not m["decisions"]),
                          None)
        if greedy_row is None:
            # without a measured greedy there is no A/B — committing a
            # searched plan here would void the "never worse than
            # greedy on the measured run" guarantee the entry carries
            doc["error"] = ("greedy leg failed to measure — nothing "
                            "committed")
            return doc
        greedy_wall = greedy_row["wall_s"]
        winner = min(measured, key=lambda m: m["wall_s"])
        doc["candidates"] = [
            {k: m[k] for k in ("plan_id", "predicted_s", "wall_s")}
            for m in measured]
    else:
        winner = dict(best_pred, wall_s=None)

    doc.update(plan_id=winner["plan_id"],
               predicted_s=winner["predicted_s"],
               greedy_predicted_s=greedy["predicted_s"],
               wall_s=winner.get("wall_s"), greedy_wall_s=greedy_wall)
    if commit:
        c = cache if cache is not None else autotune.CACHE
        entry = c.put(
            OP, [], [],
            config={"decisions": winner["decisions"],
                    "plan_id": winner["plan_id"],
                    "predicted_s": winner["predicted_s"],
                    "greedy_predicted_s": greedy["predicted_s"]},
            wall_s=winner.get("wall_s"), mesh=mesh,
            extra={"graph": graph, "layout": str(layout)},
            heuristic_config={"decisions": {}, "plan_id": "greedy"},
            heuristic_wall_s=greedy_wall,
            candidates=doc.get("measured") or doc["searched"],
            source="plan-search")
        doc["entry"] = entry
        say("plan_search: committed %s for graph %s (measured "
            "%.3g ms%s)"
            % (winner["plan_id"], graph,
               1e3 * (winner.get("wall_s") or 0),
               ", greedy %.3g ms" % (1e3 * greedy_wall)
               if greedy_wall else ""))
    return doc
