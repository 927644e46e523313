"""GLM-4.7-Flash (PR 44): latent attention with a query latent and a rotary
part, the multi-token-prediction module that shares the embedding and the
head, the two losses made in the graph, and the toy model through
``ShardedTrainer`` against the plain reference
(``benchmark/references/glm-4.7-flash.py``), all at toy size on the CPU.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import decoder_blocks, glm4_moe_lite, kimi_linear
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import ShardedTrainer, build_mesh, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
#: the benchmark's harness (``benchmark/run.py``) and the plain reference,
#: set for this file's tests by ``_benchmark_modules``
run = REF = None
SEED = 2 ** 31 + 44
T = 64


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
    run, REF = harness, harness.load_module("references", "glm-4.7-flash")
    yield
    sys.path[:] = path
    for name, mod in list(sys.modules.items()):
        if name not in before and \
                (getattr(mod, "__file__", None) or "").startswith(BENCH):
            del sys.modules[name]
    sys.modules.update(shadowed)


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _toy_bench():
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"))
    bench["configs"] = [{"name": "smoke-glm47",
                         "file": "benchmark/configs/smoke-glm47.json"}]
    bench["workloads"] = [{"name": "smoke-glm47", "config": "smoke-glm47",
                           "traffic": "smoke-s64-b1-chain2", "chips": 1}]
    return bench


@pytest.fixture(scope="module")
def toy_cell(_benchmark_modules):
    return run.Cell("smoke-glm47", _toy_bench())


def _through_the_harness(cell, seed=SEED):
    """One run of a toy cell through ``run.run_cell`` on the CPU, with what
    the harness compared kept.  The reference's attention rows are cut so
    that its blocking is exercised."""
    import check
    kept, compare = {}, check.compare

    def keeping(prog, ref, limits, say=print):
        kept.update(prog=prog, ref=ref)
        return compare(prog, ref, limits, say)

    rows, cell.refmod.ATTENTION_ROWS = cell.refmod.ATTENTION_ROWS, 32
    check.compare = keeping
    try:
        result = run.run_cell(cell, seed=seed, seconds=0.3, trace=0,
                              on_chip=False)
    finally:
        check.compare = compare
        cell.refmod.ATTENTION_ROWS = rows
    return kept["ref"], kept["prog"], result


@pytest.fixture(scope="module")
def both_sides(toy_cell):
    """The reference's and the program's first 1 + chain steps of two layers
    (dense, expert) and the module at width 64, 64 positions, float32, 4 of
    16 experts held, from the same seeded weights and batch, then a short
    window."""
    from mxnet_tpu.telemetry import spans
    ref, prog, result = _through_the_harness(toy_cell)
    built = [r.attrs for r in spans.records("model.build")]
    return (ref, prog, moe.last_plan_summary(),
            glm4_moe_lite.last_plan_summary(), pk.last_causal_plan(), result,
            built)


def _batch(cell, seed=SEED, rows=1):
    import traffic
    return traffic.host_batch(cell.cfg, cell.mix, rows, seed)


def _seeded(cell, seed=SEED):
    key, _offset = run.seed_key(seed)
    return REF.init_params(cell.cfg, key)


def _outputs(net, params, batch, names):
    """``{output name: value}`` of the graph's internal outputs ``names`` on
    the reference's weights (plain executor, float32)."""
    internals = net.get_internals()
    group = mx.sym.Group([internals[n] for n in names])
    args = {k: mx.nd.array(np.asarray(v)) for k, v in
            {**params, **batch}.items() if k in group.list_arguments()}
    aux = {k: mx.nd.zeros(s) for k, s in zip(
        group.list_auxiliary_states(),
        group.infer_shape(**{k: v.shape for k, v in batch.items()})[2])}
    outs = group.bind(mx.cpu(), args, aux_states=aux).forward()
    return {n: o.asnumpy() for n, o in zip(names, outs)}


# --------------------------------------------------- the graph as built
def test_model_symbol_is_built_from_the_configuration(toy_cell):
    net, data, label = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    assert data == {"data": (1, T)} and label == {"softmax_label": (1, T)}
    shapes = dict(zip(net.list_arguments(), net.infer_shape(**data, **label)[0]))
    del shapes["data"], shapes["softmax_label"]
    assert shapes == toy_cell.refmod.param_shapes(toy_cell.cfg)
    assert net.list_auxiliary_states() == ["layer1_moe_load", "mtp_moe_load"]
    nodes = json.loads(net.tojson())["nodes"]
    ops = [n["op"] for n in nodes]
    assert (ops.count("_contrib_TopKMoE"), ops.count("_contrib_FlashAttention"),
            ops.count("Embedding"), ops.count("MakeLoss"),
            ops.count("SoftmaxOutput"), ops.count("_contrib_TokenCrossEntropy"),
            ops.count("_contrib_RotaryEmbedding")) == (2, 3, 2, 1, 0, 2, 6)
    # op, q, kv, ffn norms a layer; final; enorm, hnorm, the module's head's
    assert ops.count("RMSNorm") == 3 * 4 + 1 + 3
    # ONE embedding and ONE head, each read by two nodes
    for name, op in (("embed_weight", "Embedding"),
                     ("lm_head_weight", "FullyConnected")):
        index = [i for i, n in enumerate(nodes) if n["name"] == name]
        assert len(index) == 1
        readers = [n for n in nodes if n["op"] == op
                   and any(src == index[0] for src, *_ in n["inputs"])]
        assert len(readers) == 2, name
    for n in nodes:
        if n["op"] == "_contrib_TopKMoE":
            assert (n["attrs"]["score_func"], n["attrs"]["use_expert_bias"],
                    n["attrs"]["routed_scaling_factor"], n["attrs"]["num_experts"],
                    n["attrs"]["experts_held"], n["attrs"]["num_experts_per_tok"],
                    n["attrs"]["router_trained"]) == \
                ("sigmoid", "True", "1.8", "16", "4", "4", "False")
    assert net.infer_shape(**data, **label)[1] == [(1,)]   # one loss a sequence


def test_every_block_op_carries_its_scope(toy_cell):
    """``mxtpu.block.mla`` on every op of a latent-attention block, the main
    path's and the module's; ``mxtpu.block.mtp`` on everything the module
    adds, its layer's blocks inside it; nothing else carries either."""
    net, _d, _l = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    scopes = {n["name"]: n.get("attrs", {}).get("__scope__")
              for n in json.loads(net.tojson())["nodes"] if n["op"] != "null"}
    mla, mtp = decoder_blocks.SCOPE_MLA, glm4_moe_lite.SCOPE_MTP
    for name in ("q_a", "q_norm", "q_b", "q_rope", "kv_a", "kv_norm", "kv_b",
                 "k_rope", "attn", "o"):
        assert scopes["layer0_" + name] == scopes["layer1_" + name] == mla
        assert scopes["mtp_" + name] == mtp + "/" + mla
    for name in ("mtp_embed", "mtp_enorm", "mtp_hnorm", "mtp_eh_proj",
                 "mtp_op_norm", "mtp_ffn_norm", "mtp_moe", "mtp_shared_w1",
                 "mtp_final_norm", "mtp_final_norm_head"):
        assert scopes[name] == mtp, name
    for name in ("embed", "layer0_op_norm", "layer0_w1", "layer1_moe",
                 "final_norm", "final_norm_head", "loss"):
        assert scopes[name] is None, name
    # on the device: the scope is in the lowered step's op names
    trainer = _toy_trainer(toy_cell)
    fn, args = trainer._prepare_run_steps(
        trainer.put_batch(_batch(toy_cell)), 1)
    text = fn.lower(*args).as_text(debug_info=True)
    for inside in ("mxtpu.fwd/jvp(%s)/" % mla, "mxtpu.fwd/jvp(%s/%s)/" % (mtp, mla),
                   "mxtpu.fwd/jvp(%s)/dot_general" % mtp,
                   "mxtpu.fwd/jvp(%s)/%s/" % (mtp, moe.SCOPE_MOE),
                   "mxtpu.bwd/transpose(jvp(%s/%s))/" % (mtp, mla)):
        assert inside in text, inside


@pytest.mark.parametrize("change,says", [
    ({"n_group": 2}, "one expert group"),
    ({"topk_method": "greedy"}, "noaux_tc routing"),
    ({"num_nextn_predict_layers": 2}, "2 multi-token-prediction modules"),
    ({"tie_word_embeddings": True}, "a tied head"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"rope_scaling": {"type": "yarn"}}, "scaled rotary embedding"),
    ({"max_position_embeddings": 32}, "64 positions, the model declares 32")])
def test_model_refuses_what_it_does_not_build(toy_cell, change, says):
    with pytest.raises(MXNetError, match=says):
        glm4_moe_lite.get_symbol(dict(toy_cell.cfg, **change), T)


# ----------------------------------------- one builder for latent attention
def test_kimi_linear_builds_its_latent_attention_through_the_shared_builder(
        monkeypatch):
    """``kimi_linear._mla`` is ``decoder_blocks.latent_attention`` without
    its two options; ``tests/test_afmoe.py`` holds the lowered toy chain to
    the parent's digest (``smoke-kimi``)."""
    cfg = run.load_json(BENCH, "configs", "smoke-kimi.json")
    seen = []
    builder = decoder_blocks.latent_attention

    def spy(x, cfg, prefix):
        seen.append((prefix, cfg.get("q_lora_rank"), cfg["mla_use_nope"]))
        return builder(x, cfg, prefix)

    monkeypatch.setattr(kimi_linear, "latent_attention", spy)
    net = kimi_linear.get_symbol(cfg, T)
    assert seen == [("layer3_", None, True)]
    ops = [n["op"] for n in json.loads(net.tojson())["nodes"]]
    assert ops.count("_contrib_RotaryEmbedding") == 0
    for change in ({"q_lora_rank": 24}, {"mla_use_nope": False}):
        with pytest.raises(MXNetError, match="glm4_moe_lite builds both"):
            kimi_linear.get_symbol(dict(cfg, **change), T)


# ------------------------------------------------------ the rotary part
def _qkv(cell, x, params=None, prefix="layer0_"):
    """The graph's own ``q`` and ``k`` of one layer for the layer input
    ``x`` (1, T, d), and the reference's."""
    cfg = cell.cfg
    params = params or _seeded(cell)
    p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
    data = mx.sym.Variable("x")
    out = decoder_blocks.latent_attention(data, cfg, prefix)
    attn = out.get_internals()[prefix + "attn_output"]
    node = attn._entries[0][0]
    group = mx.sym.Group([mx.sym.Symbol([e]) for e in node.inputs])
    args = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()
            if k in group.list_arguments()}
    args["x"] = mx.nd.array(np.asarray(x))
    got = [o.asnumpy()[0] for o in group.bind(mx.cpu(), args).forward()]
    want = REF.queries_keys_values(jnp.asarray(x[0]), p, cfg)
    return got, [np.asarray(w) for w in want]


def test_rotary_part_turns_only_the_rotary_dims(toy_cell):
    """``q`` and ``k`` of the graph are the reference's; against the same
    layer built without rotation (``mla_use_nope``), the ``qk_nope_head_dim``
    leading dims of every head are bit for bit the same and only the last
    ``qk_rope_head_dim`` differ; position 0 is not turned at all."""
    cfg = toy_cell.cfg
    nope = cfg["qk_nope_head_dim"]
    x = np.asarray(_rand(1, T, cfg["hidden_size"], seed=3))
    got, want = _qkv(toy_cell, x)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    plain = run.Cell("smoke-glm47", _toy_bench())
    plain.cfg = dict(cfg, mla_use_nope=True)
    unturned, _ = _qkv(plain, x)
    for turned, flat in zip(got[:2], unturned[:2]):
        np.testing.assert_array_equal(turned[..., :nope], flat[..., :nope])
        np.testing.assert_array_equal(turned[0], flat[0])
        assert np.abs(turned[1:, :, nope:] - flat[1:, :, nope:]).max() > 1e-3
    np.testing.assert_array_equal(got[2], unturned[2])      # values: never


def test_scores_depend_on_position_differences_only(toy_cell):
    """One token repeated at every position: the score of query ``i`` on key
    ``j`` is then a function of ``i - j`` alone (every diagonal of the score
    matrix is constant), which only a relative rotation of both sides
    gives; and it does vary along a row (the rotation is there)."""
    cfg = toy_cell.cfg
    x = np.tile(np.asarray(_rand(1, 1, cfg["hidden_size"], seed=5)), (1, T, 1))
    (q, k, _v), _want = _qkv(toy_cell, x)
    scores = np.einsum("ihd,jhd->hij", q, k)
    for off in range(0, T, 7):
        diag = np.diagonal(scores, offset=-off, axis1=1, axis2=2)
        np.testing.assert_allclose(diag, diag[:, :1].repeat(diag.shape[1], 1),
                                   rtol=1e-4, atol=1e-5)
    assert np.abs(scores[:, -1, 0] - scores[:, -1, -1]).max() > 1e-4


# --------------------------------------------------- the whole model
#: float32 on both sides, so what separates them is the order of float32
#: sums (the program's grouped products and flash-style attention against
#: the reference's loops): 2e-7 and less on every number.  The same toy in
#: bfloat16 reads 1.4e-4 to 7e-3 (``test_a_bfloat16_run_of_the_toy_fails``)
TOLERANCES = [("loss_gap", 5e-6), ("grad_sample_err", 5e-6),
              ("grad_norm_gap", 5e-6), ("delta_norm_gap", 5e-6)]


@pytest.mark.parametrize("number,tolerance", TOLERANCES)
def test_model_through_sharded_trainer_follows_the_reference(both_sides, number,
                                                            tolerance):
    """Three losses (the trainer's monitored loss is the reference's ``L_main
    + 0.3 L_mtp``), the first gradient element by element and by leaf, and
    the parameters' change after two more Adam steps."""
    import check
    ref, prog = both_sides[:2]
    assert len(ref["losses"]) == len(prog["losses"]) == 3
    values = {n: v for n, v, _ in check.numbers(prog, ref)}
    assert values[number] <= tolerance, values
    assert set(prog["grad_norms"]) == set(ref["grad_norms"])
    worst = max(check.leaf_gaps(prog["grad_norms"], ref["grad_norms"]).values())
    assert worst <= 1e-4, worst
    assert prog["losses"][2] < prog["losses"][1] < prog["losses"][0]
    # the shared parameters and the module's own projection are among the
    # sampled gradient elements: a dropped or doubled term fails above
    assert {"embed_weight", "lm_head_weight", "mtp_eh_proj_weight"} <= \
        set(prog["grad_samples"])


def test_a_bfloat16_run_of_the_toy_fails(toy_cell):
    """The tolerances above tell a lower precision: the same toy with the
    trainer's compute dtype bfloat16 is outside every one of them."""
    import check
    cell = run.Cell("smoke-glm47", _toy_bench())
    cell.cfg = dict(cell.cfg, trainer=dict(cell.cfg["trainer"],
                                           dtype="bfloat16"))
    ref, prog, _result = _through_the_harness(cell)
    values = {n: v for n, v, _ in check.numbers(prog, ref)}
    for number, tolerance in TOLERANCES:
        assert values[number] > 10 * tolerance, values


def test_logits_of_both_heads_and_both_losses_are_the_references(toy_cell):
    cfg = toy_cell.cfg
    params, batch = _seeded(toy_cell), _batch(toy_cell)
    net = toy_cell.cfgmod.build(cfg, toy_cell.mix, 1)[0]
    out = _outputs(net, params, batch, ["final_norm_head_output",
                                        "mtp_final_norm_head_output",
                                        "loss_output"])
    tokens = jnp.asarray(batch["data"][0], jnp.int32)
    labels = jnp.asarray(batch["softmax_label"][0], jnp.int32)
    main, ahead = REF.sequence_logits(params, tokens, labels, cfg)
    np.testing.assert_allclose(out["final_norm_head_output"], main,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out["mtp_final_norm_head_output"], ahead,
                               rtol=1e-4, atol=1e-5)
    l_main, l_mtp = (float(v) for v in
                     REF.sequence_losses(params, tokens, labels, cfg))
    assert out["loss_output"][0] == pytest.approx(l_main + 0.3 * l_mtp, rel=1e-6)
    assert 0.5 < l_mtp / l_main < 2.0 and l_mtp != l_main


def _toy_trainer(cell, params=None, cfg=None, **kw):
    cfg = cfg or cell.cfg
    net, data, label = cell.cfgmod.build(cfg, cell.mix, 1)
    mesh = build_mesh(devices=jax.devices()[:1], tp=1)
    opt = dict(cfg["optimizer"])
    trainer = ShardedTrainer(net, mesh, data_shapes=data, label_shapes=label,
                             optimizer=opt.pop("optimizer"), seed=7, **opt,
                             **dict(cfg["trainer"], **kw))
    if params is not None:
        trainer.params = {k: jnp.array(params[k], copy=True)
                          for k in trainer.params}
    return trainer


def _first_gradient(trainer, batch):
    """``(loss, {leaf: gradient})`` of the trainer's first step, the
    gradient read from Adam's first moment (``m_1 = (1 - beta1) g``)."""
    loss = float(np.asarray(trainer.run_steps(trainer.put_batch(batch), 1))[0])
    return loss, {k: np.asarray(s[0]) / 0.1
                  for k, s in trainer.opt_state.items()}


class _TwoUses(dict):
    """The reference's parameters with a second value for some of them: the
    first read of such a name gives the first value, every later read the
    second (the reference reads the embedding and the head first in the main
    path and then in the module)."""

    def __init__(self, base, second):
        super().__init__(base)
        self.second, self.reads = second, {}

    def __getitem__(self, name):
        if name in self.second:
            self.reads[name] = self.reads.get(name, 0) + 1
            if self.reads[name] > 1:
                return self.second[name]
        return super().__getitem__(name)


def test_mtp_label_at_row_i_is_the_token_after_the_next(toy_cell):
    """By hand from the graph's own logits: ``L_mtp`` is the mean over rows
    ``0 .. T-2`` of ``-log softmax(logits'_i)[softmax_label[i + 1]]``, which
    is ``t_{i+2}``; counting the module's last row in (against the wrapped
    label the graph's shift leaves there) reads a different loss."""
    cfg = toy_cell.cfg
    params, batch = _seeded(toy_cell), _batch(toy_cell)
    # a head fifty times as large: the rows' terms differ by whole units,
    # so one row more or less is told
    params = dict(params, lm_head_weight=params["lm_head_weight"] * 50)
    net = toy_cell.cfgmod.build(cfg, toy_cell.mix, 1)[0]
    out = _outputs(net, params, batch, ["final_norm_head_output",
                                        "mtp_final_norm_head_output",
                                        "loss_output"])
    labels = batch["softmax_label"][0].astype(int)
    ahead = -np.asarray(jax.nn.log_softmax(out["mtp_final_norm_head_output"]))
    main = -np.asarray(jax.nn.log_softmax(out["final_norm_head_output"]))
    l_main = np.mean(main[np.arange(T), labels])
    l_mtp = np.mean(ahead[np.arange(T - 1), labels[1:]])
    assert out["loss_output"][0] == pytest.approx(l_main + 0.3 * l_mtp, rel=1e-5)
    with_last = np.mean(ahead[np.arange(T), np.roll(labels, -1)])
    assert abs(with_last - l_mtp) > 3e-4 * l_mtp
    # and not the next token: that is the main head's label
    assert abs(np.mean(ahead[np.arange(T), labels]) - l_mtp) > 1e-2 * l_mtp


def test_last_row_of_the_module_adds_nothing_to_the_gradient(toy_cell):
    """The program's first gradient of ``W_eh`` is the reference's, whose
    module loss runs over ``logits'[:-1]``; a reference that counts the last
    row in (with the wrapped label) is told from it a thousand times over."""
    cfg = toy_cell.cfg
    params, batch = _seeded(toy_cell), _batch(toy_cell)
    _loss, got = _first_gradient(_toy_trainer(toy_cell, params), batch)
    tokens = jnp.asarray(batch["data"][0], jnp.int32)
    labels = jnp.asarray(batch["softmax_label"][0], jnp.int32)

    def total(p, counted):
        main, ahead = REF.sequence_logits(p, tokens, labels, cfg)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(ahead),
                                   jnp.roll(labels, -1)[:, None], axis=1)[:, 0]
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(main), labels[:, None], axis=1)) \
            + 0.3 * jnp.mean(nll[:counted])

    name = "mtp_eh_proj_weight"
    want = np.asarray(jax.grad(total)(params, T - 1)[name])
    wrong = np.asarray(jax.grad(total)(params, T)[name])
    scale = np.abs(want).max()
    assert np.abs(got[name] - want).max() <= 2e-5 * scale
    assert np.abs(got[name] - wrong).max() >= 2e-2 * scale


def test_shared_parameters_gradients_are_the_sums_of_their_two_uses(toy_cell):
    """``embed_weight`` and ``lm_head_weight``: the trainer holds ONE master
    and ONE pair of Adam slots for each, and the gradient it applies is the
    main path's use plus the module's, each taken apart in the reference by
    giving the second read a value of its own."""
    cfg = toy_cell.cfg
    params, batch = _seeded(toy_cell), _batch(toy_cell)
    trainer = _toy_trainer(toy_cell, params)
    shapes = toy_cell.refmod.param_shapes(cfg)
    assert set(trainer.params) == set(shapes) == set(trainer.opt_state)
    assert not [n for n in trainer.params
                if n.startswith("mtp_") and ("embed" in n or "head" in n)]
    assert all(len(slots) == 2 for slots in trainer.opt_state.values())
    held = sum(int(np.prod(v.shape)) for v in trainer.params.values())
    assert held == sum(int(np.prod(s)) for s in shapes.values())
    loss, got = _first_gradient(trainer, batch)
    tokens = jnp.asarray(batch["data"][0], jnp.int32)
    labels = jnp.asarray(batch["softmax_label"][0], jnp.int32)
    shared = ("embed_weight", "lm_head_weight")

    def total(first, second):
        p = _TwoUses(dict(params, **first), second)
        l_main, l_mtp = REF.sequence_losses(p, tokens, labels, cfg)
        assert p.reads == {"embed_weight": 2, "lm_head_weight": 2}
        return l_main + 0.3 * l_mtp

    both = {n: params[n] for n in shared}
    value, (main_use, module_use) = jax.value_and_grad(total, (0, 1))(both, both)
    assert loss == pytest.approx(float(value), rel=1e-6)
    for n in shared:
        a, b = np.asarray(main_use[n]), np.asarray(module_use[n])
        scale = np.abs(a + b).max()
        assert np.abs(b).max() > 0.05 * scale and np.abs(a).max() > 0.05 * scale
        np.testing.assert_allclose(got[n], a + b, rtol=0, atol=2e-5 * scale)
        # a dropped or a doubled module term is told
        assert np.abs(got[n] - a).max() > 0.02 * scale
        assert np.abs(got[n] - (a + 2 * b)).max() > 0.02 * scale


def test_loss_weight_zero_is_the_graph_without_the_module(toy_cell):
    """``mtp_loss_weight`` 0 with the module built reads, to the bit, the
    main loss of the graph built without one (``num_nextn_predict_layers``
    0), on the same weights; with the weight as shipped the loss is
    larger by ``0.3 L_mtp``."""
    params, batch = _seeded(toy_cell), _batch(toy_cell)
    losses = {}
    for name, change in (("zero", {"mtp_loss_weight": 0.0}),
                         ("none", {"num_nextn_predict_layers": 0}),
                         ("shipped", {})):
        trainer = _toy_trainer(toy_cell, params, dict(toy_cell.cfg, **change))
        assert ("mtp_eh_proj_weight" in trainer.params) == (name != "none")
        losses[name] = np.asarray(trainer.run_steps(trainer.put_batch(batch),
                                                    1))[0]
    assert losses["zero"] == losses["none"]
    assert losses["shipped"] > 1.2 * losses["none"]


# ------------------------------------------------- the shares add up
WHOLE = dict(n_routed_experts=64, router_num_experts=64, num_experts_per_tok=4,
             expert_offset=0, norm_topk_prob=True, routed_scaling_factor=1.8,
             router_trained=True, n_shared_experts=1)


def _moe_params(d=16, ff=24, e=64):
    return {"moe_router_weight": _rand(e, d, seed=1, scale=0.5),
            "moe_expert_bias": _rand(e, seed=3, scale=0.1),
            "moe_w1_weight": _rand(e, d, ff, seed=4, scale=0.2),
            "moe_w3_weight": _rand(e, d, ff, seed=5, scale=0.2),
            "moe_w2_weight": _rand(e, ff, d, seed=6, scale=0.2),
            "shared_w1_weight": _rand(ff, d, seed=7, scale=0.2),
            "shared_w3_weight": _rand(ff, d, seed=8, scale=0.2),
            "shared_w2_weight": _rand(d, ff, seed=9, scale=0.2)}


def _shares_sum(x, p):
    """The eight shares of 8 experts, each without the shared expert, summed,
    plus the shared expert once (every chip computes it alike)."""
    y = 0.0
    for off in range(0, 64, 8):
        y = y + moe.topk_moe(
            x, p["moe_router_weight"], p["moe_expert_bias"],
            p["moe_w1_weight"][off:off + 8], p["moe_w3_weight"][off:off + 8],
            p["moe_w2_weight"][off:off + 8], 4, expert_offset=off,
            norm_topk_prob=True, routed_scaling_factor=1.8)[0]
    shared = jax.nn.silu(x @ p["shared_w1_weight"].T) * (x @ p["shared_w3_weight"].T)
    return y + shared @ p["shared_w2_weight"].T


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Top 4 of 64, gates over their sum times 1.8: what the eight chips of
    the deployment compute, summed, is the uncut reference's layer."""
    x, p = _rand(48, 16), _moe_params()
    uncut = REF.expert_layer(x, p, WHOLE) + REF.shared_expert(x, p)
    np.testing.assert_allclose(_shares_sum(x, p), uncut, rtol=1e-5, atol=1e-6)
    one = moe.topk_moe(x, p["moe_router_weight"], p["moe_expert_bias"],
                       p["moe_w1_weight"][:8], p["moe_w3_weight"][:8],
                       p["moe_w2_weight"][:8], 4, expert_offset=0,
                       norm_topk_prob=True, routed_scaling_factor=1.8)[0]
    np.testing.assert_allclose(
        one, REF.expert_layer(x, {n: (v[:8] if n.startswith("moe_w") else v)
                                  for n, v in p.items()},
                              dict(WHOLE, n_routed_experts=8)),
        rtol=1e-5, atol=1e-6)
    assert float(np.abs(np.asarray(one) - np.asarray(uncut)).max()) > 1e-2


# ------------------------------------------------- the new loss op
def test_token_cross_entropy_is_float32_whatever_the_logits_and_keeps_them():
    """``-log softmax(x)[label]`` a row in float32 from bfloat16 logits; its
    backward keeps the logits as they came (no float32 copy of them is a
    residual) and gives ``softmax - onehot``."""
    from mxnet_tpu.ops.registry import OpContext, get_op
    op = get_op("_contrib_TokenCrossEntropy")
    x = _rand(12, 40, seed=2).astype(jnp.bfloat16)
    labels = jnp.asarray(np.random.RandomState(3).randint(0, 40, 12), jnp.float32)

    def nll(x):
        return op.fcompute({}, OpContext(is_train=True), x, labels)

    out = nll(x)
    assert out.dtype == jnp.float32 and out.shape == (12,)
    logp = jax.nn.log_softmax(x.astype(jnp.float32))
    want = -logp[jnp.arange(12), labels.astype(jnp.int32)]
    np.testing.assert_allclose(out, want, rtol=1e-6)
    grad = jax.grad(lambda x: nll(x).sum())(x)
    onehot = jax.nn.one_hot(labels.astype(jnp.int32), 40)
    np.testing.assert_allclose(grad.astype(jnp.float32), jnp.exp(logp) - onehot,
                               atol=1e-2)
    from jax._src.ad_checkpoint import saved_residuals
    residuals = saved_residuals(lambda x: nll(x).sum(), x)
    assert not [r for r, _ in residuals
                if r.dtype == jnp.float32 and r.shape == x.shape]


# ------------------------------------- the plans, the readers, the harness
def test_trainer_records_the_blocks_plans(both_sides):
    _ref, _prog, moe_plan, plan, flash, _result, built = both_sides
    assert built[-1] == {"model": "glm4_moe_lite"} or \
        {"model": "glm4_moe_lite"} in built
    layer = {"q_lora_rank": 24, "rope_dims": 8, "dk": 24, "dv": 24, "heads": 4}
    assert plan["mla_layers"] == [layer] * 3
    assert plan["mtp"] == {"depth": 1, "layer_rows": T, "head_rows": 2 * T,
                           "loss_weight": 0.3,
                           "shared": ["embed_weight", "lm_head_weight"]}
    assert moe_plan["expert_layers"] == 2
    assert [(la["num_experts"], la["experts_held"], la["score_func"])
            for la in moe_plan["layers"]] == [(16, 4, "sigmoid")] * 2


def test_toy_cell_runs_through_the_harness(both_sides):
    result = both_sides[5]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1


def test_new_readers_read_the_plans_and_none_without_them(monkeypatch, both_sides):
    from mxnet_tpu.telemetry import plan
    mla = {"q_lora_rank": 768, "rope_dims": 64, "dk": 256, "dv": 256, "heads": 20}
    mtp = {"depth": 1, "layer_rows": 4096, "head_rows": 8192, "loss_weight": 0.3,
           "shared": ["embed_weight", "lm_head_weight"]}
    read = lambda name: run.load_module("layer_metrics", name).read({})  # noqa: E731
    monkeypatch.setattr(plan, "_LAST", {
        "mxtpu.block.mla": ([mla] * 6, {}), "mxtpu.block.mtp": ([mtp], {})})
    assert (read("mla_query_latent_layers"), read("mtp_modules"),
            read("mtp_head_rows_pct"), read("mtp_shared_params")) == (6, 1, 200.0, 2)
    # Kimi Linear's layers (neither option) do not count; a step without a
    # module reads 0 modules and no shares
    monkeypatch.setattr(plan, "_LAST", {"mxtpu.block.mla": (
        [dict(mla, q_lora_rank=None, rope_dims=0)], {})})
    assert (read("mla_query_latent_layers"), read("mtp_modules"),
            read("mtp_head_rows_pct"), read("mtp_shared_params")) == \
        (0, 0, None, None)
    # a copy of the head: one name shared
    monkeypatch.setattr(plan, "_LAST", {"mxtpu.block.mtp": (
        [dict(mtp, shared=["embed_weight"])], {})})
    assert read("mtp_shared_params") == 1
    monkeypatch.setattr(plan, "_LAST", {})
    assert [read(n) for n in ("mla_query_latent_layers", "mtp_modules",
                              "mtp_head_rows_pct", "mtp_shared_params")] == [None] * 4


# --------------------------------------------- the flash kernels' rule
#: (positions, dk, dv, query heads a key/value head) of every causal call a
#: cell makes -> (forward blocks, backward blocks, backward calls a group,
#: the backward's ``_vmem_need`` MiB, whether it asks): the values of the
#: parent of PR 44 for the seven token cells, forward and backward the same
#: pair, and the new row: two lane tiles on both sides at 4096 positions,
#: where the backward takes 256 rows (at 512 it spills: 10.12 ms against
#: 3.03 on the chip, PR 44)
CELL_SHAPES = {
    "opt1.3b": ((2048, 64, 64, 1), ((512, 2048), (512, 2048), 1, 15.0, False)),
    "lfm2": ((8192, 64, 64, 4), ((512, 2048), (512, 2048), 1, 33.0, True)),
    "kimi-linear": ((8192, 192, 128, 1),
                    ((512, 2048), (512, 2048), 1, 34.0, True)),
    "trinity-mini": ((8192, 128, 128, 8),
                     ((512, 2048), (512, 2048), 1, 49.0, True)),
    "nemotron": ((8192, 128, 128, 16),
                 ((512, 2048), (512, 2048), 2, 49.0, True)),
    "glm-4.7-flash": ((4096, 256, 256, 1),
                      ((512, 2048), (256, 2048), 1, 25.0, True)),
}


@pytest.mark.parametrize("name", sorted(CELL_SHAPES))
def test_flash_rule_on_every_shape_the_cells_use(name):
    (t, dk, dv, group), (fwd, bwd, parts, need_mib, asks) = CELL_SHAPES[name]
    assert pk._flash_blocks(t, dk, dv, group, True) == fwd
    assert pk._flash_blocks(t, dk, dv, group, True, backward=True) == bwd
    q = jax.ShapeDtypeStruct((1, t, group, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, t, 1, dv), jnp.bfloat16)
    assert pk._select_blocks("flash_attention_fwd", q, True, v, group) == fwd
    assert pk._select_blocks("flash_attention_bwd", q, True, v, group) == bwd
    assert pk._group_parts(t, max(dk, dv), group) == parts
    dq_rows = group // parts * t if t > bwd[1] else 0
    need = pk._vmem_need(max(dk, dv), *bwd, dq_rows)
    assert need == need_mib * 2 ** 20
    assert bool(pk._vmem_params(need)) == asks
    if asks:
        assert pk._vmem_params(need)["compiler_params"].vmem_limit_bytes == \
            need * 3 // 2 <= pk._VMEM_MAX


def test_only_two_lane_tiles_on_both_sides_cap_the_backward_rows():
    for dk, dv in ((256, 256), (192, 192), (129, 256)):
        assert pk._flash_blocks(4096, dk, dv, 1, True, backward=True) == \
            (pk._BWD_ROWS_TWO_TILES, 2048)
        assert pk._flash_blocks(4096, dk, dv, 1, True) == (512, 2048)
    for dk, dv in ((192, 128), (128, 256), (128, 128), (64, 64)):
        assert pk._flash_blocks(4096, dk, dv, 1, True, backward=True) == \
            (512, 2048)
    # not causal: the built-in pair either way
    assert pk._flash_blocks(4096, 256, 256, 1, False, backward=True) == \
        pk._flash_blocks(4096, 256, 256) == (128, 2048)


@pytest.mark.parametrize("fwd_blocks,bwd_blocks", [
    ((32, 64), (16, 64)), ((32, 128), (16, 128))], ids=["stream", "panel"])
def test_two_lane_tiles_on_both_sides_with_a_backward_pair_of_its_own(
        fwd_blocks, bwd_blocks):
    """256-wide scores over 256-wide values in interpret mode, the backward
    at half the forward's Q rows (as the rule now picks for this shape): it
    reads the forward's output and log-sum-exp whatever blocks made them, and
    both match the plain formula."""
    q, k, v, g = (_rand(1, 128, 2, 256, seed=i) for i in (1, 2, 3, 4))
    o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                            blocks=fwd_blocks)
    want, pull = jax.vjp(lambda q, k, v: pk._attention_jnp(q, k, v, True),
                         q, k, v)
    np.testing.assert_allclose(o, want, rtol=2e-5, atol=2e-5)
    grads = pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, True, True,
                                           blocks=bwd_blocks)
    for got, ref in zip(grads, pull(g)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# --------------------------------------------- the cell's configuration
def test_cell_configuration_keeps_every_published_width():
    """``benchmark/configs/glm-4.7-flash.json`` against the catalog's
    ``config`` (``model-configs``' ``architectures.jsonl``, quoted here): only
    the three reduced keys differ, each with its published value beside it."""
    cfg = run.load_json(BENCH, "configs", "glm-4.7-flash.json")
    published = dict(
        attention_bias=False, hidden_act="silu", hidden_size=2048,
        intermediate_size=10240, max_position_embeddings=202752,
        model_type="glm4_moe_lite", moe_intermediate_size=1536,
        topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
        n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
        routed_scaling_factor=1.8, num_experts_per_tok=4,
        first_k_dense_replace=1, num_hidden_layers=47, num_key_value_heads=20,
        num_nextn_predict_layers=1, partial_rotary_factor=1, rms_norm_eps=1e-5,
        rope_scaling=None, rope_theta=1000000, tie_word_embeddings=False,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, vocab_size=154880)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "n_routed_experts", "vocab_size"}
    assert {k: published[k] for k in changed} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["router_num_experts"], cfg["mtp_loss_weight"]) == \
        (5, 8, 19360, 64, 0.3)
    # the floors: one leading dense layer + four expert layers, 8 experts,
    # an eighth of the vocabulary, the module whole
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert set(cfg["limits"]) == {"loss_gap", "grad_sample_err", "grad_norm_gap",
                                  "delta_norm_gap"}
    assert all(v["reason"] and 0 < v["limit"] < 1 for v in cfg["limits"].values())
    cfgmod = run.load_module("configs", "glm-4.7-flash")
    mix = run.load_json(BENCH, "traffic", "s4096-b1-chain2.json")
    assert (mix["seq"], mix["batch_per_chip"], mix["chain"], mix["mesh"],
            mix["runner"]) == (4096, 1, 2, {"tp": 1}, "fused_trainer")
    shapes = REF.param_shapes(cfg)
    # ISSUE 44's count: 706.5M parameters, 21.76M a latent-attention block
    assert sum(int(np.prod(s)) for s in shapes.values()) == 706518848
    assert cfgmod.mla_params(cfg) == 21757952
    assert round(cfgmod.matmul_params_per_token(cfg) / 1e6, 1) == 351.9
    assert cfgmod.units_per_step(cfg, mix, 1) == 4096
    costs = cfgmod.kernel_costs(cfg, mix)
    assert set(costs) == {"mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream",
                          "ragged-dot"}
    assert costs["ragged-dot"]["calls"] == 45
    assert costs["mxtpu_flash_fwd_stream"]["calls"] == 6
    flash = sum(costs[k]["flops"] for k in costs if k.startswith("mxtpu_flash"))
    assert abs(flash / 1e12 - 3.09) < 0.01
    assert abs(cfgmod.step_flops(cfg, mix, 1) / 1e12 - 11.75) < 0.1
