"""SDAR under block-diffusion training (PR 40): the graph built from the
configuration, its noising against the reference's, the toy model through
``ShardedTrainer`` against the plain reference
(``benchmark/references/sdar-30b-a3b-chat.py``), the loss head's monitored
value, that the mask leaks nothing, the readers, and that the neighbours'
graphs are the parent's, all at toy size on the CPU.
"""
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import sdar_moe
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import ShardedTrainer, build_mesh, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
#: the benchmark's harness (``benchmark/run.py``) and the plain reference,
#: set for this file's tests by ``_benchmark_modules``
run = REF = None
SEED = 2 ** 31 + 40
DOC, BLOCK = 64, 4


@pytest.fixture(scope="module", autouse=True)
def _benchmark_modules():
    """The benchmark's modules, importable while this file's tests run and
    gone after them (``tests/test_lfm2_moe.py`` has why)."""
    global run, REF
    path, before = list(sys.path), dict(sys.modules)
    shadowed = {name: sys.modules.pop(name) for name in ("common", "run")
                if name in sys.modules}
    sys.path[:0] = [BENCH, os.path.join(BENCH, "references")]
    import run as harness
    run = harness
    REF = harness.load_module("references", "sdar-30b-a3b-chat")
    yield
    sys.path[:] = path
    for name, mod in list(sys.modules.items()):
        if name not in before and \
                (getattr(mod, "__file__", None) or "").startswith(BENCH):
            del sys.modules[name]
    sys.modules.update(shadowed)


def _toy_bench():
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"))
    bench["configs"] = [{"name": "smoke-sdar",
                         "file": "benchmark/configs/smoke-sdar.json"}]
    bench["workloads"] = [{"name": "smoke-sdar", "config": "smoke-sdar",
                           "traffic": "smoke-bd-s64-b1-chain2", "chips": 1}]
    return bench


@pytest.fixture(scope="module")
def toy_cell(_benchmark_modules):
    return run.Cell("smoke-sdar", _toy_bench())


@pytest.fixture(scope="module")
def both_sides(toy_cell):
    """One run of the toy cell through the harness (``run.run_cell`` on the
    CPU: the reference's and the program's first 1 + chain steps of two layers
    at width 64, a document of 64 tokens in blocks of 4, float32, 4 of 16
    experts held, from the same seeded weights and the same batch, then a
    short window), with what the harness compared kept.  The reference's
    attention rows are cut so that its blocking is exercised."""
    import check
    from mxnet_tpu.telemetry import spans
    cell, kept = toy_cell, {}
    compare = check.compare

    def keeping(prog, ref, limits, say=print):
        kept.update(prog=prog, ref=ref)
        return compare(prog, ref, limits, say)

    rows, cell.refmod.ATTENTION_ROWS = cell.refmod.ATTENTION_ROWS, 32
    check.compare = keeping
    try:
        result = run.run_cell(cell, seed=SEED, seconds=0.3, trace=0,
                              on_chip=False)
    finally:
        check.compare = compare
        cell.refmod.ATTENTION_ROWS = rows
    built = [r.attrs for r in spans.records("model.build")]
    return (kept["ref"], kept["prog"], moe.last_plan_summary(),
            sdar_moe.last_plan_summary(), result, built)


def _batch(cell, seed=SEED):
    import traffic
    return traffic.host_batch(cell.cfg, cell.mix, 1, seed)


def test_model_symbol_is_built_from_the_configuration(toy_cell):
    net, data, label = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    assert data == {"data": (1, 2 * DOC + DOC // BLOCK)}
    shapes = dict(zip(net.list_arguments(), net.infer_shape(**data)[0]))
    del shapes["data"]
    assert "softmax_label" not in shapes             # read by nothing
    assert shapes == toy_cell.refmod.param_shapes(toy_cell.cfg)
    assert all(name.endswith(("_weight", "_gamma")) for name in shapes)
    assert net.list_auxiliary_states() == ["layer0_moe_load", "layer1_moe_load"]
    nodes = json.loads(net.tojson())["nodes"]
    ops = [n["op"] for n in nodes]
    assert (ops.count("_contrib_TopKMoE"), ops.count("_contrib_FlashAttention"),
            ops.count("Embedding"), ops.count("MakeLoss"),
            ops.count("SoftmaxOutput")) == (2, 2, 2, 1, 0)
    assert ops.count("RMSNorm") == 2 * 4 + 1    # in, q, k, post a layer; last
    assert ops.count("_contrib_RotaryEmbedding") == 2 * 2
    for n in nodes:
        if n["op"] == "_contrib_TopKMoE":
            assert (n["attrs"]["score_func"], n["attrs"]["use_expert_bias"],
                    n["attrs"]["routed_scaling_factor"],
                    n["attrs"]["norm_topk_prob"]) == ("softmax", "False",
                                                      "1.0", "True")
        if n["op"] == "_contrib_FlashAttention":
            assert (n["attrs"]["diffusion_block"], n["attrs"]["causal"]) == \
                (str(BLOCK), "False")
            assert n["attrs"]["window"] == "0"
    assert net.infer_shape(**data)[1] == [(1,)]      # one loss a document
    # Module binds such a Symbol too
    mod = mx.mod.Module(net, context=mx.cpu(), label_names=None)
    mod.bind(data_shapes=[("data", data["data"])])
    mod.init_params(mx.init.Normal(0.02))
    mod.forward(mx.io.DataBatch([mx.nd.array(_batch(toy_cell)["data"])], []),
                is_train=False)
    out = mod.get_outputs()[0].asnumpy()
    assert out.shape == (1,) and 0 < out[0] < 50


@pytest.mark.parametrize("change,says", [
    ({"block_length": 5}, "no whole number of blocks of 5"),
    ({"tie_word_embeddings": True}, "a tied head"),
    ({"mlp_only_layers": [0]}, "dense layers are not built"),
    ({"mask_token_id": 128}, "lies outside the 128 ids"),
    ({"max_position_embeddings": 32}, "64 positions, the model declares 32")])
def test_model_refuses_what_it_does_not_build(toy_cell, change, says):
    with pytest.raises(MXNetError, match=says):
        sdar_moe.get_symbol(dict(toy_cell.cfg, **change), DOC)


def test_build_says_what_seq_a_document_needs(toy_cell):
    with pytest.raises(ValueError, match="seq == 2 \\* document \\+ document / "
                                         "block_length = 144"):
        toy_cell.cfgmod.build(toy_cell.cfg, dict(toy_cell.mix, seq=128), 1)
    assert toy_cell.cfgmod.units_per_step(toy_cell.cfg, toy_cell.mix, 1) == DOC
    assert toy_cell.cfgmod.layer_rows(toy_cell.cfg, toy_cell.mix, 1) == 2 * DOC


def test_noising_in_the_graph_is_the_references(toy_cell):
    """The graph's three arrays against ``noise`` of the reference on the same
    batch; a token the noising left alone keeps its id and has zero weight; a
    masked one holds ``mask_token_id`` and weighs ``1 / t``."""
    cfg = toy_cell.cfg
    data = _batch(toy_cell)["data"]
    group = mx.sym.Group(list(sdar_moe.noised(mx.sym.Variable("data"), cfg, DOC)))
    doc, ids, weight = (o.asnumpy()[0] for o in group.bind(
        mx.cpu(), {"data": mx.nd.array(data)}).forward())
    x0, xt, m, t = (np.asarray(a) for a in REF.noise(jnp.asarray(data[0]), cfg))
    np.testing.assert_array_equal(doc, x0)
    np.testing.assert_array_equal(ids, xt)
    assert 0 < m.sum() < DOC
    np.testing.assert_array_equal(weight == 0, ~m)
    np.testing.assert_array_equal(ids[~m], x0[~m])
    np.testing.assert_array_equal(ids[m], cfg["mask_token_id"])
    np.testing.assert_allclose(weight[m], 1.0 / t[m], rtol=1e-6)
    assert np.all(t.reshape(-1, BLOCK) == t.reshape(-1, BLOCK)[:, :1])
    assert cfg["noise_eps"] <= t.min() and t.max() <= 1.0


@pytest.mark.parametrize("number,tolerance", [
    ("loss_gap", 1e-4), ("grad_sample_err", 1e-4), ("grad_norm_gap", 1e-4),
    ("delta_norm_gap", 1e-4)])
def test_model_through_sharded_trainer_follows_the_reference(both_sides, number,
                                                            tolerance):
    """Float32 on both sides: three losses (the trainer's monitored loss is
    the reference's weighted loss), the first gradient element by element and
    by leaf, and the parameters' change after two more Adam steps."""
    import check
    ref, prog = both_sides[:2]
    assert len(ref["losses"]) == len(prog["losses"]) == 3
    values = {n: v for n, v, _ in check.numbers(prog, ref)}
    assert values[number] <= tolerance, values
    assert set(prog["grad_norms"]) == set(ref["grad_norms"])
    worst = max(check.leaf_gaps(prog["grad_norms"], ref["grad_norms"]).values())
    assert worst <= 1e-3, worst
    assert prog["losses"][2] < prog["losses"][1] < prog["losses"][0]


def test_monitored_loss_is_the_weighted_loss(toy_cell, both_sides):
    """What ``run_steps`` returns for a ``MakeLoss`` head is the head's own
    value: the reference's ``(1 / (b L)) sum m / t * nll`` on the same weights,
    not 0 and not a cross-entropy over the unread label."""
    cfg = toy_cell.cfg
    key, _offset = run.seed_key(SEED)
    params = REF.init_params(cfg, key)
    batch = {k: jnp.asarray(v) for k, v in _batch(toy_cell).items()}
    total, mean = REF.loss(params, batch, cfg)
    assert float(total) == pytest.approx(float(mean))      # one document
    assert both_sides[1]["losses"][0] == pytest.approx(float(mean), rel=1e-5)
    # by hand from the reference's logits
    x0, xt, m, t = REF.noise(batch["data"][0], cfg)
    logp = jax.nn.log_softmax(REF.logits(params, x0, xt, cfg), axis=-1)
    nll = -np.asarray(logp)[np.arange(DOC), np.asarray(x0)]
    by_hand = float(np.sum(np.where(np.asarray(m), nll / np.asarray(t), 0)) / DOC)
    assert float(mean) == pytest.approx(by_hand, rel=1e-5)


def _toy_trainer(cell, rows=1, **kw):
    net, data, label = cell.cfgmod.build(cell.cfg, cell.mix, rows)
    mesh = build_mesh(devices=jax.devices()[:1], tp=1)
    opt = dict(cell.cfg["optimizer"])
    return ShardedTrainer(net, mesh, data_shapes=data, label_shapes=label,
                          optimizer=opt.pop("optimizer"), seed=7, **opt,
                          **dict(cell.cfg["trainer"], **kw))


def test_trainer_stages_a_label_that_no_node_reads(toy_cell):
    """``put_batch`` takes every key the trainer was given shapes for: the
    harness stages ``softmax_label`` with every token batch, and the graph
    reads ``data`` alone.  Two documents: the monitored loss is the mean of
    the head's two values; the label's contents move nothing."""
    t = _toy_trainer(toy_cell, rows=2)
    import traffic
    hb = traffic.host_batch(toy_cell.cfg, toy_cell.mix, 2, SEED)
    staged = t.put_batch(hb)
    assert sorted(staged) == ["data", "softmax_label"]
    assert staged["data"].dtype == jnp.float32      # ids, not bfloat16
    first = np.asarray(t.run_steps(staged, 1))
    t2 = _toy_trainer(toy_cell, rows=2)
    other = t2.put_batch(dict(hb, softmax_label=hb["softmax_label"] * 0))
    np.testing.assert_array_equal(first, np.asarray(t2.run_steps(other, 1)))
    params = {k: np.asarray(v) for k, v in t2.params.items()}
    # the head's two values, one a document, from the reference on the
    # trainer's own weights after its step: their mean is what is monitored
    second = float(np.asarray(t2.run_steps(other, 1))[0])
    per_doc = [float(REF.loss({k: jnp.asarray(v) for k, v in params.items()},
                              {"data": jnp.asarray(hb["data"][i:i + 1])},
                              toy_cell.cfg)[1]) for i in range(2)]
    assert second == pytest.approx(np.mean(per_doc), rel=1e-5)
    # a bfloat16 trainer keeps the ids exact: the first lookup reads the
    # batch's ids through a slice alone
    low = _toy_trainer(toy_cell, dtype="bfloat16")
    assert "data" in low._int_inputs
    assert low.put_batch(_batch(toy_cell))["data"].dtype == jnp.float32


def test_a_clean_token_leaks_into_no_noised_block_up_to_its_own(toy_cell):
    """Changing the clean token at a masked position of block b (so that the
    noised copy's ids stay as they were) changes no logit of the noised blocks
    <= b and changes those after it: the mask leaks nothing.  On the graph's
    own logits and on the reference's."""
    cfg = toy_cell.cfg
    net = toy_cell.cfgmod.build(cfg, toy_cell.mix, 1)[0]
    logits = net.get_internals()["lm_head_output"]
    key, _offset = run.seed_key(SEED)
    params = REF.init_params(cfg, key)
    data = np.array(_batch(toy_cell)["data"])
    block, pos = 5, 5 * BLOCK + 1
    data[0, DOC + pos] = 0                           # u ~ 0: masked
    data[0, 2 * DOC + block] = cfg["vocab_size"] - 1     # t ~ 1
    changed = data.copy()
    changed[0, pos] = (data[0, pos] + 17) % (cfg["vocab_size"] - 1)
    args = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    aux = {"layer%d_moe_load" % i: mx.nd.zeros((cfg["num_experts"] + 1,))
           for i in range(cfg["num_hidden_layers"])}

    def program(d):
        ex = logits.bind(mx.cpu(), dict(args, data=mx.nd.array(d)),
                         aux_states=aux)
        return ex.forward(is_train=False)[0].asnumpy()

    def reference(d):
        x0, xt, m, _t = REF.noise(jnp.asarray(d[0]), cfg)
        assert bool(m[pos])
        return np.asarray(REF.logits(params, x0, xt, cfg))

    np.testing.assert_allclose(program(data), reference(data), atol=2e-5)
    for f in (program, reference):
        a, b = f(data), f(changed)
        upto = (block + 1) * BLOCK
        np.testing.assert_array_equal(a[:upto], b[:upto])
        later = np.abs(a[upto:] - b[upto:]).max(axis=1)
        assert np.all(later > 0), later


def test_trainer_records_the_plans_and_the_build_span(both_sides):
    experts, model, built = both_sides[2], both_sides[3], both_sides[5]
    assert experts["expert_layers"] == 2
    # 128 rows of 4 assignments, 4 of 16 held: every assignment there can be
    # fits four times the even load, and twice it is the small buffer
    assert {(x["score_func"], x["buffer_rows"], x["small_rows"], x["even_rows"],
             x["products_trained"]) for x in experts["layers"]} == {
        ("softmax", 512, 256, 128.0, 9)}
    assert model == {"doc_len": DOC, "block_length": BLOCK, "layers": 2,
                     "layer_rows": 2 * DOC, "head_rows": DOC}
    assert {"model": "sdar_moe"} in [{"model": b.get("model")} for b in built]


def test_toy_cell_runs_through_the_harness(both_sides):
    result = both_sides[4]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["count"] == 1


def test_new_readers_read_the_plans_and_none_without_them(monkeypatch):
    readers = {name: run.load_module("layer_metrics", name) for name in (
        "blockdiff_attn_layers", "blockdiff_scores_computed_pct",
        "head_rows_pct", "flash_scores_computed_pct", "flash_q_block_rows")}
    monkeypatch.setattr(pk, "last_causal_plan", lambda: {
        "kernels": [], "causal_ranges": 4, "scores_computed_pct": 31.25,
        "q_block_rows": 512, "window_layers": 0,
        "window_scores_computed_pct": None, "diffusion_layers": 5,
        "diffusion_scores_computed_pct": 31.25})
    monkeypatch.setattr(sdar_moe, "_LAST_PLAN", {
        "doc_len": 4096, "block_length": 4, "layers": 5, "layer_rows": 8192,
        "head_rows": 4096})
    assert {n: r.read({}) for n, r in readers.items()} == {
        "blockdiff_attn_layers": 5, "blockdiff_scores_computed_pct": 31.25,
        "head_rows_pct": 50.0, "flash_scores_computed_pct": 31.25,
        "flash_q_block_rows": 512}
    # the parent's plan (no such keys), no plan, no graph, no model
    monkeypatch.setattr(pk, "last_causal_plan", lambda: {
        "kernels": [], "causal_ranges": 4, "scores_computed_pct": 53.125,
        "q_block_rows": 512, "window_layers": 0,
        "window_scores_computed_pct": None})
    monkeypatch.setattr(sdar_moe, "_LAST_PLAN", None)
    assert [readers[n].read({}) for n in (
        "blockdiff_attn_layers", "blockdiff_scores_computed_pct",
        "head_rows_pct")] == [None, None, None]
    monkeypatch.setattr(pk, "last_causal_plan", lambda: None)
    assert readers["blockdiff_attn_layers"].read({}) is None
    assert readers["blockdiff_scores_computed_pct"].read({}) is None
    monkeypatch.setitem(sys.modules, "mxnet_tpu.models.sdar_moe", None)
    assert readers["head_rows_pct"].read({}) is None


def test_step_lowers_to_the_diffusion_kernels_for_the_chip(toy_cell, monkeypatch):
    """The toy model at a document of 1024 tokens, the platform probe patched
    true, the step lowered for the TPU from here: every layer's attention is
    the two new kernels under ``mxtpu.block.bda`` and the plan counts them."""
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    doc = 1024
    cell = run.Cell("smoke-sdar", _toy_bench())
    cell.mix = dict(cell.mix, document=doc, seq=2 * doc + doc // BLOCK)
    t = _toy_trainer(cell)
    spec = lambda tree: jax.tree.map(                       # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    args = (spec(t.params), spec(t.opt_state), spec(t.aux),
            {k: jax.ShapeDtypeStruct((1, cell.mix["seq"]), jnp.float32)
             for k in ("data", "softmax_label")},
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    text = jax.jit(t._py_step).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count('kernel_name = "mxtpu_flash_fwd_blockdiff"') == 2
    assert text.count('kernel_name = "mxtpu_flash_bwd_blockdiff"') == 2
    assert "mxtpu.block.bda)/mxtpu_flash_fwd_blockdiff/pallas_call" in text
    assert "mxtpu_flash_fwd_stream" not in text
    plan = pk.last_causal_plan()
    assert plan["diffusion_layers"] == 2
    from mxnet_tpu.ops import flash_blockdiff as bd
    assert plan["diffusion_scores_computed_pct"] == bd.scores_computed_pct(
        2 * doc, 512, 1024, BLOCK, pk._causal_plan(512, 1024))
    assert {(k["block_q"], k["block_k"]) for k in plan["kernels"]} == {(512, 1024)}


#: sha256 of the neighbours' toy Symbols (``tojson``, keys sorted, the
#: operator nodes' names apart: unnamed ones are numbered by a counter of the
#: process) as the parent commit (a3ca9a0) built them.  The two ops' new
#: parameters are
#: serialised at their defaults (``NEW_DEFAULTS``) and taken out first:
#: nothing else of their graphs, attributes included, has changed
PARENT_GRAPHS = {"smoke-lfm2": "c37b93222543fad4",
                 "smoke-kimi": "74b0b010e305169f",
                 "smoke-trinity": "4468539b19727671",
                 "smoke-nemotron": "8613002c0208e9ed",
                 "smoke-opt": "730cb35b36a99072"}
NEW_DEFAULTS = {"diffusion_block": "0", "score_func": "sigmoid"}
#: what PR 44's shared latent-attention builder writes on Kimi Linear's one
#: such block and on nothing else: the block's scope on its nodes, the
#: layer's plan note on its attention call
BLOCK_META = ("__scope__", "__plan_note__")


@pytest.mark.parametrize("name", sorted(PARENT_GRAPHS))
def test_the_neighbours_graphs_are_the_parents(name):
    cfg = run.load_json(run.ROOT, "benchmark/configs/%s.json" % name)
    mix = run.load_json(run.HERE, "traffic", "smoke-s32-b2-chain2.json"
                        if name == "smoke-opt" else "smoke-s64-b1-chain2.json")
    net = run.load_module("configs", cfg["code"]).build(cfg, mix, 1)[0]
    graph = json.loads(net.tojson())
    carried = 0
    for node in graph["nodes"]:
        if node["op"] != "null":
            node.pop("name", None)
        for key, value in NEW_DEFAULTS.items():
            if node.get("attrs", {}).get(key) == value:
                del node["attrs"][key]
                carried += 1
        meta = [key for key in BLOCK_META if key in node.get("attrs", {})]
        assert not meta or (name == "smoke-kimi"
                            and node["attrs"]["__scope__"] == "mxtpu.block.mla")
        for key in meta:
            del node["attrs"][key]
        if meta and not node["attrs"]:
            del node["attrs"]
    assert carried > 0
    assert hashlib.sha256(json.dumps(graph, sort_keys=True).encode()) \
        .hexdigest()[:16] == PARENT_GRAPHS[name]


def test_cell_configuration_keeps_every_published_width():
    cfg = run.load_json(run.ROOT, "benchmark/configs/sdar-30b-a3b-chat.json")
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 16, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    published = {"hidden_size": 2048, "intermediate_size": 6144,
                 "moe_intermediate_size": 768, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "num_experts_per_tok": 8, "router_num_experts": 128,
                 "rope_theta": 1000000, "rms_norm_eps": 1e-06,
                 "max_position_embeddings": 32768, "norm_topk_prob": True,
                 "decoder_sparse_step": 1, "mlp_only_layers": [],
                 "tie_word_embeddings": False, "attention_bias": False}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["block_length"], cfg["noise_eps"], cfg["mask_token_id"],
            cfg["initializer_range"], cfg["router_trained"]) == (
        4, 1e-3, 18991, 0.02, False)
    for key in ("block_length", "noise_schedule", "unshifted_labels",
                "mask_token_id", "weights"):
        assert key in cfg["assumed"], key
    shapes = REF.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 550984960
    cell = next(w for w in bench["workloads"]
                if w["name"] == "sdar-fused-s4096-bd4")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "bd-s4096-b1-chain2", 1)
    cfgmod = run.load_module("configs", "sdar-30b-a3b-chat")
    mix = run.load_json(run.HERE, "traffic", "bd-s4096-b1-chain2.json")
    assert (mix["seq"], mix["document"]) == (9216, 4096)
    assert cfgmod.units_per_step(cfg, mix, 1) == 4096       # not the 8192 rows
    assert cfgmod.score_pairs(4096, 4) == 16793600
    flops = cfgmod.step_flops(cfg, mix, 1)
    assert 10.85e12 < flops < 10.95e12
    attention = 6 * 5 * 2.0 * 32 * 128 * 16793600
    assert attention / flops == pytest.approx(0.378, abs=0.005)
    head = 6.0 * 2048 * 18992 * 4096
    assert cfgmod.step_flops(dict(cfg, vocab_size=2 * 18992), mix, 1) - flops \
        == pytest.approx(head)
    costs = cfgmod.kernel_costs(cfg, mix)
    assert set(costs) == {"mxtpu_flash_fwd_blockdiff",
                          "mxtpu_flash_bwd_blockdiff", "ragged-dot"}
    assert costs["mxtpu_flash_fwd_blockdiff"]["calls"] == 5
    assert costs["mxtpu_flash_bwd_blockdiff"]["flops"] == pytest.approx(
        2 * costs["mxtpu_flash_fwd_blockdiff"]["flops"])
    assert costs["ragged-dot"]["calls"] == 5 * 9
    # the cell reports the new readers, and the ones the token cells share
    names = {m["name"] for m in bench["per_layer"]
             if "sdar-fused-s4096-bd4" in m.get("workloads", ())}
    assert {"blockdiff_attn_layers.tok", "blockdiff_scores_computed_pct.tok",
            "head_rows_pct.tok", "moe_small_buffer_pct.tok",
            "hbm_peak_gb.tok", "step_roofline_pct.tok"} <= names
    assert not {"attn_window_layers.tok", "kda_kernel_layers.tok",
                "ssd_chunked_layers.tok"} & names
