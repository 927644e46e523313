"""Cost-model-guided whole-graph plan search (analysis.plansearch +
fusion decision hooks): digest/identity stability, decision
application, objective, beam search (greedy-seeded, never regresses),
measurement + cache commit, bind-time pickup by Executor and
ShardedTrainer, searched-vs-greedy numerical parity, the perf_top
plan-suggestion rows, and MXG010's --plan mode.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune, models, telemetry
from mxnet_tpu.analysis import fusion, infer_node_shapes, plansearch
from mxnet_tpu.ops.fused import block_fusion

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_plan_cache():
    """The process-wide tuning cache keeps merged entries across env
    changes; a committed plan from one test must not be consulted by
    another test's (or suite's) bind."""
    autotune.CACHE.clear()
    plansearch.reset_stats()
    yield
    autotune.CACHE.clear()
    plansearch.reset_stats()


def _conv_net(num_classes=10):
    """conv3x3+BN+relu -> conv1x1+BN+relu -> FC+relu
    -> FC head: one chain of every matchable kind but bn_act."""
    d = mx.sym.Variable("data")
    n = mx.sym.Convolution(d, kernel=(3, 3), pad=(1, 1), num_filter=8,
                           no_bias=True, name="c0")
    n = mx.sym.BatchNorm(n, name="b0", fix_gamma=False)
    n = mx.sym.Activation(n, act_type="relu", name="r0")
    n = mx.sym.Convolution(n, kernel=(1, 1), num_filter=8,
                           no_bias=True, name="c1")
    n = mx.sym.BatchNorm(n, name="b1", fix_gamma=False)
    n = mx.sym.Activation(n, act_type="relu", name="r1")
    n = mx.sym.FullyConnected(mx.sym.Flatten(n), num_hidden=16,
                              name="fc0")
    n = mx.sym.Activation(n, act_type="relu", name="fa0")
    n = mx.sym.FullyConnected(n, num_hidden=num_classes, name="fc1")
    return mx.sym.SoftmaxOutput(n, name="softmax")


def _greedy_plan(sym, layout="NCHW"):
    return fusion.plan_block_fusion(sym._topo(), sym._entries,
                                    layout=layout, record=False,
                                    decisions={})


def _chain_of(plan, kind, terminal=None):
    for b in plan.blocks.values():
        if b.kind == kind and (terminal is None or b.name == terminal):
            return b.chain
    raise AssertionError("no %s block in plan" % kind)


# --------------------------------------------------- digest / identity
def test_graph_digest_stable_across_rebuilds():
    """Two builds of one architecture (different auto-generated node
    names) share a digest; an attr change breaks it."""
    first = _conv_net()
    d1 = fusion.graph_digest(first._topo(), first._entries)
    a, b = _conv_net(), _conv_net()
    assert fusion.graph_digest(a._topo(), a._entries) == \
        fusion.graph_digest(b._topo(), b._entries) == d1
    c = _conv_net(num_classes=11)
    assert fusion.graph_digest(c._topo(), c._entries) != d1


def test_graph_digest_shared_across_batch_sizes():
    """The digest hashes structure, not shapes — one committed plan
    serves every batch size of the graph."""
    net = models.get_model("mlp", num_classes=10)
    assert fusion.graph_digest(net._topo(), net._entries) == \
        fusion.graph_digest(net._topo(), net._entries)


def test_decisions_id():
    assert fusion.decisions_id(None) == "greedy"
    assert fusion.decisions_id({}) == "greedy"
    d = {"chains": {"3": "off"}}
    assert fusion.decisions_id(d).startswith("plan-")
    assert fusion.decisions_id(d) == fusion.decisions_id(dict(d))
    assert fusion.decisions_id(d) != \
        fusion.decisions_id({"chains": {"3": "conv_bn"}})


# ----------------------------------------------- decision application
def test_decision_off_unfuses_chain():
    sym = _conv_net()
    g = _greedy_plan(sym)
    cid = _chain_of(g, "conv_bn_act", "r0")
    p = fusion.plan_block_fusion(sym._topo(), sym._entries,
                                 record=False,
                                 decisions={"chains": {cid: "off"}})
    kinds = sorted(b.kind for b in p.blocks.values())
    assert kinds == ["conv_bn_act", "fc_act"]
    assert p.overrides == 1 and p.plan_id.startswith("plan-")


def test_decision_conv_bn_split():
    """conv_bn_act -> conv_bn: the act leaves the region, the terminal
    moves to the BN, the chain id stays the greedy terminal's."""
    sym = _conv_net()
    g = _greedy_plan(sym)
    cid = _chain_of(g, "conv_bn_act", "r0")
    p = fusion.plan_block_fusion(
        sym._topo(), sym._entries, record=False,
        decisions={"chains": {cid: "conv_bn"}})
    blk = next(b for b in p.blocks.values() if b.kind == "conv_bn")
    assert blk.name == "b0" and blk.chain == cid and blk.act is None
    # a split of the 1x1 chain under NHWC is a conv_bn block too
    g_nhwc = _greedy_plan(sym, layout="NHWC")
    cid1 = _chain_of(g_nhwc, "conv_bn_act", "r1")
    p2 = fusion.plan_block_fusion(
        sym._topo(), sym._entries, layout="NHWC", record=False,
        decisions={"chains": {cid1: "conv_bn"}})
    blk2 = next(b for b in p2.blocks.values() if b.kind == "conv_bn")
    assert blk2.name == "b1" and blk2.chain == cid1


def test_decision_bn_act_split():
    """conv_bn_act -> bn_act: the conv leaves the region (evaluates
    unfused), the bn+act half still fuses."""
    sym = _conv_net()
    g = _greedy_plan(sym)
    cid = _chain_of(g, "conv_bn_act", "r0")
    p = fusion.plan_block_fusion(
        sym._topo(), sym._entries, record=False,
        decisions={"chains": {cid: "bn_act"}})
    blk = next(b for b in p.blocks.values() if b.kind == "bn_act")
    assert blk.name == "r0" and blk.conv is None


def test_decision_layout_override_accounting_and_pallas():
    """A region pinned to a non-ambient layout pays 2 explicit
    relayout edges and loses adjacency credit; the summary still
    counts no kernel block."""
    sym = _conv_net()
    g = _greedy_plan(sym, layout="NCHW")
    assert g.adjacent_edges == 1 and g.relayout_edges_added == 0
    cid = _chain_of(g, "conv_bn_act", "r1")   # the 1x1 chain
    p = fusion.plan_block_fusion(
        sym._topo(), sym._entries, layout="NCHW", record=False,
        decisions={"layouts": {cid: "NHWC"}})
    blk = next(b for b in p.blocks.values() if b.chain == cid)
    assert blk.layout == "NHWC"
    assert p.relayout_edges_added == 2
    assert p.adjacent_edges == 0      # boundary layouts now differ
    s = p.summary()
    assert s["relayout_edges_added"] == 2 and s["searched"]
    assert s["pallas_blocks"] == 0


def test_stale_decisions_degrade_to_fuse():
    """Unknown chain ids, ineligible choices and a decision category
    this tree no longer has (an entry committed before PR 28 may carry
    ``pallas``) read as greedy — a stale committed entry must never
    break a plan."""
    sym = _conv_net()
    g = _greedy_plan(sym)
    fc_cid = _chain_of(g, "fc_act")
    p = fusion.plan_block_fusion(
        sym._topo(), sym._entries, record=False,
        decisions={"chains": {"9999": "off", fc_cid: "conv_bn"},
                   "pallas": {_chain_of(g, "conv_bn_act", "r1"): 0}})
    assert sorted(b.kind for b in p.blocks.values()) == \
        sorted(b.kind for b in g.blocks.values())
    assert p.overrides == 0


def test_adjacent_overridden_regions_claim_no_elimination():
    """Two adjacent regions both overridden to NHWC in an NCHW trace
    still round-trip through the ambient layout at their shared
    boundary (apply_block) — crediting adjacency there would
    contradict the 4 relayout edges they demonstrably pay."""
    sym = _conv_net()
    g = _greedy_plan(sym, layout="NCHW")
    cids = sorted(b.chain for b in g.blocks.values()
                  if b.kind == "conv_bn_act")
    p = fusion.plan_block_fusion(
        sym._topo(), sym._entries, layout="NCHW", record=False,
        decisions={"layouts": {cids[0]: "NHWC", cids[1]: "NHWC"}})
    assert p.relayout_edges_added == 4
    assert p.adjacent_edges == 0


# ---------------------------------------------------------- objective
def test_predict_plan_wall_greedy_covers_blocks_and_heavies():
    sym = _conv_net()
    shapes = {"data": (4, 3, 8, 8), "softmax_label": (4,)}
    topo, node_shapes = infer_node_shapes(sym, shapes)
    plan = fusion.plan_block_fusion(topo, sym._entries, record=False,
                                    decisions={})
    total, units = plansearch.predict_plan_wall(topo, sym._entries,
                                                plan, node_shapes)
    assert total > 0
    kinds = {(u["unit"], u["kind"]) for u in units}
    assert ("block", "conv_bn_act") in kinds
    assert ("block", "fc_act") in kinds
    assert ("node", "FullyConnected") in kinds    # the unfused fc1 head


def test_predict_plan_wall_costs_layout_override_relayouts():
    sym = _conv_net()
    shapes = {"data": (4, 3, 8, 8), "softmax_label": (4,)}
    topo, node_shapes = infer_node_shapes(sym, shapes)
    g = fusion.plan_block_fusion(topo, sym._entries, record=False,
                                 decisions={})
    cid = _chain_of(g, "conv_bn_act", "r0")
    p = fusion.plan_block_fusion(topo, sym._entries, record=False,
                                 decisions={"layouts": {cid: "NHWC"}})
    t_g, _ = plansearch.predict_plan_wall(topo, sym._entries, g,
                                          node_shapes)
    t_o, units = plansearch.predict_plan_wall(topo, sym._entries, p,
                                              node_shapes)
    blk = next(u for u in units if u["chain"] == cid)
    assert blk["relayout_s"] > 0
    assert t_o > t_g


def test_predict_plan_wall_sees_split_off_activation_cost():
    """A split/off decision pushes the act OUT of the fused epilogue:
    the objective must charge that extra elementwise pass, or every
    split scores tied with greedy and the measurement budget fills
    with candidates that are strictly worse in reality."""
    sym = _conv_net()
    shapes = {"data": (4, 3, 8, 8), "softmax_label": (4,)}
    topo, node_shapes = infer_node_shapes(sym, shapes)
    g = fusion.plan_block_fusion(topo, sym._entries, record=False,
                                 decisions={})
    cid = _chain_of(g, "conv_bn_act", "r0")
    t_g, _ = plansearch.predict_plan_wall(topo, sym._entries, g,
                                          node_shapes)
    for choice in ("conv_bn", "off"):
        p = fusion.plan_block_fusion(
            topo, sym._entries, record=False,
            decisions={"chains": {cid: choice}})
        t_s, _ = plansearch.predict_plan_wall(topo, sym._entries, p,
                                              node_shapes)
        assert t_s > t_g, choice


def test_search_plan_greedy_seeded_and_never_regressed():
    sym = _conv_net()
    shapes = {"data": (4, 3, 8, 8), "softmax_label": (4,)}
    topo, node_shapes = infer_node_shapes(sym, shapes)
    ranked = plansearch.search_plan(topo, sym._entries, layout="NHWC",
                                    node_shapes=node_shapes,
                                    budget=12, beam=4)
    assert 1 <= len(ranked) <= 12
    greedy = next(r for r in ranked if not r["decisions"])
    assert greedy["plan_id"] == "greedy"
    assert ranked[0]["predicted_s"] <= greedy["predicted_s"]


@pytest.mark.parametrize("name", ["resnet", "inception_resnet_v2"])
def test_search_plan_zoo_predicted_never_worse(name):
    """The ROADMAP targets: on resnet50 and inception_resnet_v2 the
    searched plan's predicted wall is <= the greedy plan's (greedy is
    seeded, so this holds by construction — the test pins it)."""
    kwargs = {"num_layers": 50} if name == "resnet" else {}
    net = models.get_model(name, num_classes=10, **kwargs)
    shapes = {"data": (2, 3, 224, 224)} if name != "resnet" else \
        {"data": (2, 3, 32, 32)}
    shapes["softmax_label"] = (2,)
    topo, node_shapes = infer_node_shapes(net, shapes)
    ranked = plansearch.search_plan(topo, net._entries, layout="NHWC",
                                    node_shapes=node_shapes,
                                    budget=6, beam=2)
    greedy = next(r for r in ranked if not r["decisions"])
    assert greedy["blocks"] > 0
    assert ranked[0]["predicted_s"] <= greedy["predicted_s"]


# ------------------------------------------- measure / commit / lookup
def test_search_and_commit_contract(tmp_path, monkeypatch):
    """One loop: winner committed; predicted <= greedy predicted AND
    measured <= greedy measured; the second run is a pure cache hit
    with zero search."""
    net = models.get_model("mlp", num_classes=10)
    data_shapes = {"data": (4, 784), "softmax_label": (4,)}
    cache = autotune.TuneCache()
    doc = plansearch.search_and_commit(net, data_shapes, layout="NCHW",
                                       budget=8, beam=4, topk=2,
                                       repeats=1, cache=cache)
    assert doc["predicted_s"] <= doc["greedy_predicted_s"] * (1 + 1e-9)
    assert doc["wall_s"] <= doc["greedy_wall_s"] * (1 + 1e-9)
    assert doc["measured"] >= 1 and len(cache) == 1
    entry = cache.entries()[0]
    assert entry["op"] == "graph_plan"
    assert entry["extra"]["graph"] == doc["graph"]
    doc2 = plansearch.search_and_commit(net, data_shapes,
                                        layout="NCHW", cache=cache)
    assert doc2["cached"] and doc2["searched"] == 0
    assert doc2["plan_id"] == doc["plan_id"]


def test_committed_decisions_roundtrip(tmp_path, monkeypatch):
    """Entry -> persistent cache -> fresh merged view -> bind-time
    lookup returns the decision vector, bumping the hit counter and
    dropping a plan_lookup flight event; mode=off skips everything."""
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    autotune.reload_cache()
    sym = _conv_net()
    topo, entries = sym._topo(), sym._entries
    graph = fusion.graph_digest(topo, entries)
    g = _greedy_plan(sym)
    decisions = {"chains": {_chain_of(g, "fc_act"): "off"}}
    autotune.put(plansearch.OP, [], [],
                 config={"decisions": decisions,
                         "plan_id": fusion.decisions_id(decisions)},
                 wall_s=1e-3, extra={"graph": graph, "layout": "NCHW"},
                 source="plan-search")
    autotune.reload_cache()
    plansearch.reset_stats()
    h0 = telemetry.counter("mxtpu_plan_cache_hit_total").get()
    got = plansearch.committed_decisions(topo, entries, "NCHW")
    assert got == decisions
    assert plansearch.stats() == {"hits": 1, "misses": 0}
    assert telemetry.counter("mxtpu_plan_cache_hit_total").get() == \
        h0 + 1
    # a different layout key misses
    assert plansearch.committed_decisions(topo, entries, "NHWC") is None
    assert plansearch.stats()["misses"] == 1
    # mode off: no lookup, no counters
    monkeypatch.setenv("MXNET_TPU_PLAN_SEARCH", "off")
    plansearch.reset_stats()
    assert plansearch.committed_decisions(topo, entries, "NCHW") is None
    assert plansearch.stats() == {"hits": 0, "misses": 0}


def test_executor_bind_picks_up_committed_plan(tmp_path, monkeypatch):
    """The acceptance loop: commit an entry, reload the cache (a fresh
    process's merged view), bind an Executor on a REBUILT graph
    (different node names) — the searched plan must dispatch, visible
    in last_plan_summary's plan identity."""
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    autotune.reload_cache()
    sym = _conv_net()
    g = _greedy_plan(sym)
    decisions = {"chains": {_chain_of(g, "conv_bn_act", "r0"):
                            "conv_bn"}}
    plan_id = fusion.decisions_id(decisions)
    autotune.put(plansearch.OP, [], [],
                 config={"decisions": decisions, "plan_id": plan_id},
                 wall_s=1e-3,
                 extra={"graph": fusion.graph_digest(sym._topo(),
                                                     sym._entries),
                        "layout": "NCHW"},
                 source="plan-search")
    autotune.reload_cache()
    rebuilt = _conv_net()
    with block_fusion(True):
        ex = rebuilt.simple_bind(mx.cpu(), data=(4, 3, 8, 8),
                                 softmax_label=(4,))
    assert ex._plan_decisions == decisions
    rng = np.random.RandomState(0)
    for name, arr in ex.arg_dict.items():
        arr[:] = (rng.randint(0, 10, arr.shape)
                  if name == "softmax_label"
                  else rng.uniform(-0.5, 0.5, arr.shape)) \
            .astype(np.float32)
    ex.forward(is_train=True)
    s = fusion.last_plan_summary()
    assert s["plan_id"] == plan_id and s["searched"]
    assert "conv_bn" in s["kinds"]


def test_executor_searched_vs_greedy_parity():
    """Forward + backward parity of a decision-transformed plan (chain
    split + per-region layout override) against greedy —
    the plan search may only change WHERE the math runs, never what it
    computes."""
    sym = _conv_net()
    g = _greedy_plan(sym)
    decisions = {
        "chains": {_chain_of(g, "conv_bn_act", "r0"): "bn_act"},
        "layouts": {_chain_of(g, "conv_bn_act", "r1"): "NHWC"},
    }
    shapes = {"data": (4, 3, 8, 8), "softmax_label": (4,)}

    def run(dec):
        with block_fusion(True), fusion.plan_decisions(dec):
            ex = sym.simple_bind(mx.cpu(), **shapes)
        assert ex._plan_decisions == dec     # ambient capture at bind
        rng = np.random.RandomState(0)
        for name, arr in ex.arg_dict.items():
            arr[:] = (rng.randint(0, 10, arr.shape)
                      if name == "softmax_label"
                      else rng.uniform(-0.5, 0.5, arr.shape)) \
                .astype(np.float32)
        ex.forward(is_train=True)
        out = ex.outputs[0].asnumpy()
        ex.backward()
        return out, {k: v.asnumpy() for k, v in ex.grad_dict.items()
                     if v is not None}

    o_ref, g_ref = run(None)
    o_alt, g_alt = run(decisions)
    np.testing.assert_allclose(o_ref, o_alt, rtol=2e-5, atol=2e-6)
    for k in g_ref:
        np.testing.assert_allclose(g_ref[k], g_alt[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_trainer_picks_up_mesh_keyed_plan(tmp_path, monkeypatch):
    """ShardedTrainer consults the entry keyed by ITS mesh axis sizes
    and the step stays finite under the searched plan."""
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    autotune.reload_cache()
    net = models.get_model("mlp", num_classes=10)
    mesh = build_mesh(tp=1)
    mesh_d = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    g = fusion.plan_block_fusion(net._topo(), net._entries,
                                 record=False, decisions={})
    decisions = {"chains": {sorted(b.chain for b in
                                   g.blocks.values())[0]: "off"}}
    autotune.put(plansearch.OP, [], [],
                 config={"decisions": decisions,
                         "plan_id": fusion.decisions_id(decisions)},
                 wall_s=1e-3, mesh=mesh_d,
                 extra={"graph": fusion.graph_digest(net._topo(),
                                                     net._entries),
                        "layout": "NCHW"},
                 source="plan-search")
    autotune.reload_cache()
    t = ShardedTrainer(net, mesh, data_shapes={"data": (8, 784)},
                       label_shapes={"softmax_label": (8,)},
                       fuse_blocks=True, learning_rate=0.1)
    assert t._plan_decisions == decisions
    rng = np.random.RandomState(0)
    b = t.put_batch({
        "data": rng.uniform(-1, 1, (8, 784)).astype(np.float32),
        "softmax_label": rng.randint(0, 10, 8).astype(np.float32)})
    assert np.isfinite(float(t.step(b)))
    assert fusion.last_plan_summary()["plan_id"] == \
        fusion.decisions_id(decisions)


# ----------------------------------------------- perf_top integration
def _perf_top(args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "perf_top.py")]
        + args, capture_output=True, text=True, timeout=120, env=e)


def _write_costdb(path, graph="abc123def456", plan="greedy",
                  layout=None):
    from mxnet_tpu.telemetry import costdb
    db = costdb.CostDB()
    db.record("block", "r0", wall_s=1e-3, flops=1e6,
              bytes_accessed=1e6, shapes=[(4, 8, 8, 8)],
              dtypes=["float32"], block_kind="conv_bn_act",
              layout=layout, graph=graph, plan=plan, source="test")
    p = db.flush(str(path))
    assert p
    return p


def test_perf_top_suggest_plan_untuned_row(tmp_path):
    db = tmp_path / "db"
    cache = tmp_path / "cache"
    db.mkdir(), cache.mkdir()
    _write_costdb(db)
    # a cache with SOME entry (not graph_plan) so --cache is readable
    c = autotune.TuneCache()
    c.put("flash_attention_fwd", [(1, 256, 1, 32)], ["float32"],
          {"block_q": 64}, wall_s=1e-4, persist=False)
    with open(cache / "tunecache-1.jsonl", "w") as f:
        f.write(json.dumps(c.entries()[0], default=repr) + "\n")
    res = _perf_top([str(db), "--suggest", "--cache", str(cache),
                     "--json"])
    assert res.returncode == 0, res.stderr
    doc = json.loads(res.stdout)
    rows = [r for r in doc["suggestions"] if r["kind"] == "plan"]
    assert len(rows) == 1
    assert rows[0]["status"] == "plan-untuned"
    assert rows[0]["name"] == "abc123def456"
    assert rows[0]["worst_block"] == "r0"


def test_perf_top_suggest_plan_stale_row(tmp_path):
    db = tmp_path / "db"
    cache = tmp_path / "cache"
    db.mkdir(), cache.mkdir()
    _write_costdb(db, plan="greedy")     # run dispatched greedy...
    c = autotune.TuneCache()
    c.put(plansearch.OP, [], [],
          {"decisions": {"chains": {"3": "off"}},
           "plan_id": "plan-deadbeef00"},
          wall_s=1e-3, extra={"graph": "abc123def456",
                              "layout": "NHWC"}, persist=False)
    with open(cache / "tunecache-1.jsonl", "w") as f:
        f.write(json.dumps(c.entries()[0], default=repr) + "\n")
    res = _perf_top([str(db), "--suggest", "--cache", str(cache),
                     "--json"])
    assert res.returncode == 0, res.stderr
    doc = json.loads(res.stdout)
    rows = [r for r in doc["suggestions"] if r["kind"] == "plan"]
    assert len(rows) == 1 and rows[0]["status"] == "plan-stale"
    assert rows[0]["committed_plan"] == "plan-deadbeef00"
    assert rows[0]["dispatched_plan"] == "greedy"


def test_perf_top_suggest_layout_mismatch_reads_untuned(tmp_path):
    """An entry committed at a DIFFERENT trace layout is not this
    record's plan — the row must read plan-untuned, not plan-stale."""
    db = tmp_path / "db"
    cache = tmp_path / "cache"
    db.mkdir(), cache.mkdir()
    _write_costdb(db, plan="greedy", layout="NCHW")
    c = autotune.TuneCache()
    c.put(plansearch.OP, [], [],
          {"decisions": {"chains": {"3": "off"}},
           "plan_id": "plan-deadbeef00"},
          wall_s=1e-3, extra={"graph": "abc123def456",
                              "layout": "NHWC"}, persist=False)
    with open(cache / "tunecache-1.jsonl", "w") as f:
        f.write(json.dumps(c.entries()[0], default=repr) + "\n")
    res = _perf_top([str(db), "--suggest", "--cache", str(cache),
                     "--json"])
    assert res.returncode == 0, res.stderr
    doc = json.loads(res.stdout)
    rows = [r for r in doc["suggestions"] if r["kind"] == "plan"]
    assert len(rows) == 1 and rows[0]["status"] == "plan-untuned"


def test_perf_top_suggest_bad_cache_is_usage_error(tmp_path):
    """--cache pointing at a nonexistent or corrupt file exits 2 with
    a usage error instead of silently rendering zero suggestions."""
    db = tmp_path / "db"
    db.mkdir()
    _write_costdb(db)
    res = _perf_top([str(db), "--suggest", "--cache",
                     str(tmp_path / "nope")])
    assert res.returncode == 2
    assert "does not exist" in res.stderr
    corrupt = tmp_path / "tunecache-bad.jsonl"
    corrupt.write_text("this is not json\n{\"also\": \"bad\"}\n")
    res = _perf_top([str(db), "--suggest", "--cache", str(corrupt)])
    assert res.returncode == 2
    assert "no readable" in res.stderr
    # the ambient env cache stays LENIENT: the directory is created
    # lazily by the first tune write, so a fresh machine must read as
    # all-untuned (with a stderr note), not as a tool failure
    res = _perf_top([str(db), "--suggest"],
                    env={"MXNET_TPU_TUNE_CACHE":
                         str(tmp_path / "gone")})
    assert res.returncode == 0
    assert "does not exist yet" in res.stderr


# --------------------------------------------------- MXG010 --plan mode
def _tiny_cost_model():
    recs = [{"wall_s": 10.0 ** (-6 + i % 3), "flops": 10.0 ** (6 + i),
             "bytes_accessed": 10.0 ** (5 + i),
             "block_config": {"block_q": 2 ** (3 + i % 4)}}
            for i in range(12)]
    return autotune.CostModel().fit(recs)


def test_mxg010_plan_mode_names_plan_identity(tmp_path, monkeypatch):
    from mxnet_tpu.analysis import verify_model
    model = _tiny_cost_model()
    path = str(tmp_path / "cm.json")
    model.save(path)
    monkeypatch.setenv("MXNET_TPU_PLAN_SEARCH", "off")  # greedy plan
    _net, report = verify_model("lenet", cost_model=path, plan=True,
                                plan_layout="NCHW")
    msgs = [d.message for d in report if d.rule == "MXG010"]
    # the tiny synthetic model predicts wildly — what matters is that
    # plan-mode diagnostics run clean through the committed-plan path
    # and name the plan identity that owns each prediction
    for m in msgs:
        assert "committed plan greedy" in m


def test_analysis_cli_plan_flag(tmp_path):
    model = _tiny_cost_model()
    path = str(tmp_path / "cm.json")
    model.save(path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_PLAN_SEARCH="off")
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", "--model", "mlp",
         "--cost-model", path, "--plan", "--layout", "NCHW"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=_ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", "--model", "mlp",
         "--plan"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=_ROOT)
    assert res.returncode == 2        # --plan needs --cost-model
    assert "cost-model" in res.stderr


# ------------------------------------------------ measured zoo A/B
@pytest.mark.slow
def test_resnet_measured_ab_never_worse(tmp_path):
    """Measured top-k A/B on a (reduced-image) resnet50: the committed
    winner is never worse than greedy on the measured run."""
    net = models.get_model("resnet", num_layers=50, num_classes=10,
                           image_shape="3,32,32")
    data_shapes = {"data": (2, 3, 32, 32), "softmax_label": (2,)}
    cache = autotune.TuneCache()
    doc = plansearch.search_and_commit(net, data_shapes, layout="NHWC",
                                       budget=6, beam=2, topk=1,
                                       repeats=1, cache=cache)
    assert doc["wall_s"] <= doc["greedy_wall_s"] * (1 + 1e-9)
    assert doc["predicted_s"] <= doc["greedy_predicted_s"] * (1 + 1e-9)
