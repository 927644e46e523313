"""Block-granularity fusion + layout planning (analysis.fusion +
ops/fused.py ``fused_block_*``): plan correctness over the model zoo,
fused-vs-unfused numerical parity (forward, gradients, aux updates;
train AND eval BN semantics) on the Executor and the ShardedTrainer,
and graceful fallback when a pattern is ineligible.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu.analysis import fusion
from mxnet_tpu.ops.fused import block_fusion
from mxnet_tpu.parallel import ShardedTrainer, build_mesh


def _plan(sym, layout="NHWC", is_train=True):
    return fusion.plan_block_fusion(sym._topo(), sym._entries,
                                    layout=layout, is_train=is_train,
                                    record=False)


def _resnet_style_net(num_classes=10, act="relu", bn_kwargs=None):
    """conv3x3+BN+act trunk -> conv1x1+BN+act ->
    residual add (the trunk terminal has two consumers) -> FC+relu head."""
    bn_kwargs = bn_kwargs or {}
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                             num_filter=8, no_bias=True, name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0", fix_gamma=False, **bn_kwargs)
    net = mx.sym.Activation(net, act_type=act, name="act0")
    trunk = net
    net = mx.sym.Convolution(net, kernel=(1, 1), num_filter=8,
                             no_bias=True, name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1", fix_gamma=False, **bn_kwargs)
    net = mx.sym.Activation(net, act_type=act, name="act1")
    net = net + trunk
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc0")
    net = mx.sym.Activation(net, act_type="relu", name="fcact")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


# ------------------------------------------------------------- the plan
def test_plan_resnet_style_blocks():
    plan = _plan(_resnet_style_net())
    s = plan.summary()
    assert s["kinds"] == {"conv_bn_act": 2, "fc_act": 1}
    # no block lowers to a kernel; the benchmark reads the key
    assert s["pallas_blocks"] == 0
    by_kind = {b.kind: b for b in plan.blocks.values()}
    assert by_kind["conv_bn_act"].terminal.name in ("act0", "act1")
    # interior edges: 2 per conv_bn_act, 1 per fc_act = 5; plus the
    # act0 -> conv1 block adjacency pinned to one layout = 6
    assert plan.interior_edges == 5
    assert plan.adjacent_edges == 1
    assert s["relayouts_eliminated"] == 6
    assert s["fallbacks"] == {}


def test_plan_longest_chain_wins():
    """conv->BN->relu must match as ONE conv_bn_act, not bn_act."""
    plan = _plan(_resnet_style_net())
    kinds = {b.kind for b in plan.blocks.values()}
    assert "bn_act" not in kinds


def test_plan_conv_multi_consumer_falls_back_to_bn_act():
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                           no_bias=True, name="c")
    bn = mx.sym.BatchNorm(c, name="bn", fix_gamma=False)
    r = mx.sym.Activation(bn, act_type="relu", name="r")
    out = r + c                     # conv consumed by bn AND the add
    plan = _plan(out)
    s = plan.summary()
    assert s["kinds"] == {"bn_act": 1}
    assert s["fallbacks"] == {"conv_multi_consumer": 1}


def test_plan_ineligible_bn_attrs_fall_back():
    # output_mean_var: the region exposes only output + aux updates
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn", output_mean_var=True)
    out = mx.sym.Activation(bn[0], act_type="relu")
    s = _plan(out).summary()
    assert s["blocks"] == 0
    assert s["fallbacks"] == {"bn_output_mean_var": 1}

    # non-reference channel axis
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn", axis=3)
    out = mx.sym.Activation(bn, act_type="relu")
    s = _plan(out).summary()
    assert s["blocks"] == 0 and s["fallbacks"] == {"bn_axis": 1}


def test_plan_non_relu_bn_activation_falls_back():
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn")
    out = mx.sym.Activation(bn, act_type="sigmoid")
    s = _plan(out).summary()
    assert s["blocks"] == 0 and s["fallbacks"] == {"act_type": 1}


def test_plan_respects_exclusions():
    """Nodes claimed by another trace-time pass are off limits."""
    sym = _resnet_style_net()
    topo = sym._topo()
    conv1 = next(n for n in topo if n.name == "conv1")
    plan = fusion.plan_block_fusion(topo, sym._entries, layout="NHWC",
                                    exclude={id(conv1)}, record=False)
    s = plan.summary()
    # conv1's chain degrades to bn_act; conv0's chain still fuses
    assert s["kinds"] == {"conv_bn_act": 1, "bn_act": 1, "fc_act": 1}
    assert s["fallbacks"] == {"claimed_by_other_pass": 1}


# --------------------------- the lowering of the 1x1 conv->BN->ReLU block
# (activation NHWC, filters, dtype): ResNet-50's four bottleneck shapes
# at batch 128 (the flatten round the kernel is a ``reshape`` that moves
# the activation: W of 28, 14, 7, and 64 filters in stage 1), the shapes
# whose flatten is a bitcast in the compiled v5e text (W of whole 8-row
# tiles, 128-lane channels: stage 2's first unit has them, and the A/B
# of PR 25 ran the second), and float32
_ONE_BY_ONE_CASES = [
    ((128, 56, 56, 256), 64, "bfloat16"),
    ((128, 28, 28, 512), 128, "bfloat16"),
    ((128, 14, 14, 1024), 256, "bfloat16"),
    ((128, 7, 7, 2048), 512, "bfloat16"),
    ((128, 56, 56, 256), 128, "bfloat16"),
    ((128, 32, 32, 128), 128, "bfloat16"),
    ((8, 8, 8, 128), 128, "float32"),
]


def one_by_one_block(x_shape, nout, dtype):
    """``(fn, [(shape, dtype), ...])``: the gradient of a 1x1
    conv->BN->ReLU chain (ResNet's conv1->bn2->relu2) on an NHWC
    activation, evaluated as ``eval_graph`` evaluates a training
    step's — planned by the fusion pass, lowered by ``apply_block`` —
    and the arguments it takes (tests/test_chip_compile.py compiles it
    for the chip)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import image_layout
    from mxnet_tpu.symbol import eval_graph

    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(1, 1),
                             num_filter=nout, no_bias=True, name="c")
    net = mx.sym.BatchNorm(net, name="bn", fix_gamma=False)
    out = mx.sym.Activation(net, act_type="relu", name="r")
    topo = out._topo()
    order = [n for n in topo if n.is_variable]
    shapes = {"data": x_shape, "c_weight": (nout, x_shape[3], 1, 1)}

    def loss(*values):
        with block_fusion(True), image_layout("NHWC"):
            heads, aux = eval_graph(
                topo, out._entries,
                {id(n): v for n, v in zip(order, values)}, is_train=True)
        return heads[0].astype(jnp.float32).sum() \
            + sum(a.sum() for a in aux.values())

    return (jax.grad(loss, argnums=(0, 1, 2, 3)),
            [(shapes.get(n.name, (nout,)),
              jnp.dtype(dtype) if n.name in shapes else jnp.float32)
             for n in order])


@pytest.mark.parametrize("x_shape,nout,dtype", _ONE_BY_ONE_CASES, ids=str)
def test_one_by_one_block_is_an_xla_region(monkeypatch, x_shape, nout,
                                           dtype):
    """A training trace of the 1x1 conv->BN->ReLU chain, taken as on
    the TPU, is one fused block with no ``pallas_call`` and no 2-d
    flatten of the activation in it, whatever the shape; the summary
    counts no kernel and names no fallback."""
    import jax
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    fn, args = one_by_one_block(x_shape, nout, dtype)
    text = str(jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(sh, dt) for sh, dt in args]))
    s = fusion.last_plan_summary()
    assert s["kinds"] == {"conv_bn_act": 1} and s["fallbacks"] == {}
    assert s["pallas_blocks"] == 0
    assert "pallas_call" not in text
    assert "conv_general_dilated" in text
    rows = x_shape[0] * x_shape[1] * x_shape[2]
    assert "[%d,%d]" % (rows, nout) not in text


# ------------------------------------ relayout accounting (the matrix)
# Adjacent-same-layout credit across ALL FOUR chain kinds: a boundary
# only counts as an eliminated relayout when an image activation sits
# on both sides — fc_act blocks neither carry an image layout out nor
# read one in (FullyConnected flattens), so FC boundaries never credit.

def _conv_bn(data, i, act=False):
    n = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                           no_bias=True, name="c%d" % i)
    n = mx.sym.BatchNorm(n, name="b%d" % i, fix_gamma=False)
    if act:
        n = mx.sym.Activation(n, act_type="relu", name="r%d" % i)
    return n


def _bn_act(data, i):
    n = mx.sym.BatchNorm(data, name="nb%d" % i, fix_gamma=False)
    return mx.sym.Activation(n, act_type="relu", name="nr%d" % i)


def _fc_act(data, i):
    n = mx.sym.FullyConnected(data, num_hidden=8, name="f%d" % i)
    return mx.sym.Activation(n, act_type="relu", name="fa%d" % i)


def _counts(sym):
    p = _plan(sym)
    return (p.summary()["kinds"], p.interior_edges, p.adjacent_edges,
            p.relayouts_eliminated)


def test_relayout_adjacent_conv_bn_act_chain():
    d = mx.sym.Variable("data")
    sym = _conv_bn(_conv_bn(d, 0, act=True), 1, act=True)
    kinds, interior, adjacent, total = _counts(sym)
    assert kinds == {"conv_bn_act": 2}
    assert (interior, adjacent, total) == (4, 1, 5)


def test_relayout_adjacent_conv_bn_chain():
    d = mx.sym.Variable("data")
    sym = _conv_bn(_conv_bn(d, 0), 1)
    kinds, interior, adjacent, total = _counts(sym)
    assert kinds == {"conv_bn": 2}
    assert (interior, adjacent, total) == (2, 1, 3)


def test_relayout_adjacent_bn_act_chain():
    d = mx.sym.Variable("data")
    sym = _bn_act(_bn_act(d, 0), 1)
    kinds, interior, adjacent, total = _counts(sym)
    assert kinds == {"bn_act": 2}
    assert (interior, adjacent, total) == (2, 1, 3)


def test_relayout_adjacent_conv_into_bn_act():
    d = mx.sym.Variable("data")
    sym = _bn_act(_conv_bn(d, 0, act=True), 1)
    kinds, interior, adjacent, total = _counts(sym)
    assert kinds == {"conv_bn_act": 1, "bn_act": 1}
    assert (interior, adjacent, total) == (3, 1, 4)


def test_relayout_fc_chain_never_credits_adjacency():
    """fc_act -> fc_act: both boundary tensors are 2-d — no image
    relayout exists to eliminate (the credit used to overcount)."""
    d = mx.sym.Variable("data")
    sym = _fc_act(_fc_act(d, 0), 1)
    kinds, interior, adjacent, total = _counts(sym)
    assert kinds == {"fc_act": 2}
    assert (interior, adjacent, total) == (2, 0, 2)


def test_relayout_conv_into_fc_never_credits_adjacency():
    """conv_bn_act -> fc_act (direct, FC flatten=True): the FC flattens
    the image activation, paying that materialization regardless of
    any layout pinning — no credit (used to overcount)."""
    d = mx.sym.Variable("data")
    sym = _fc_act(_conv_bn(d, 0, act=True), 1)
    kinds, interior, adjacent, total = _counts(sym)
    assert kinds == {"conv_bn_act": 1, "fc_act": 1}
    assert (interior, adjacent, total) == (3, 0, 3)


def test_relayout_flatten_between_blocks_no_credit():
    d = mx.sym.Variable("data")
    sym = _fc_act(mx.sym.Flatten(_conv_bn(d, 0, act=True)), 1)
    _kinds, _interior, adjacent, _total = _counts(sym)
    assert adjacent == 0


# the zoo: every net with a fusable pattern must plan >= 1 block.
# googlenet is the documented zero: convs without BN and an FC head
# with no trailing activation offer nothing to fuse.
_ZOO_MIN_BLOCKS = {"googlenet": 0}


@pytest.mark.parametrize("name", models._MODELS)
def test_plan_zoo_model(name):
    net = models.get_model(name, num_classes=10)
    plan = _plan(net)
    s = plan.summary()
    assert s["blocks"] >= _ZOO_MIN_BLOCKS.get(name, 1), s
    # plans must be internally consistent: interiors are skipped, every
    # terminal is outside every skip set
    for blk in plan.blocks.values():
        assert id(blk.terminal) not in plan.skip
        for n in blk.interior():
            assert id(n) in plan.skip
    if s["blocks"]:
        assert s["relayouts_eliminated"] >= s["blocks"]


# --------------------------------------------------- executor parity
def _exec_run(sym, fuse, is_train, shapes, seed=0, aux_seed=None,
              backward=True):
    with block_fusion(fuse):
        ex = sym.simple_bind(mx.cpu(), **shapes)
    rng = np.random.RandomState(seed)
    for name, arr in ex.arg_dict.items():
        if name == "softmax_label":
            arr[:] = rng.randint(0, 10, arr.shape).astype(np.float32)
        else:
            arr[:] = rng.uniform(-0.5, 0.5, arr.shape).astype(np.float32)
    if aux_seed is not None:
        arng = np.random.RandomState(aux_seed)
        for name, arr in ex.aux_dict.items():
            base = arng.uniform(0.1, 1.0, arr.shape).astype(np.float32)
            arr[:] = base
    ex.forward(is_train=is_train)
    outs = [np.asarray(o.asnumpy()) for o in ex.outputs]
    grads = {}
    if backward and is_train:
        ex.backward()
        grads = {k: v.asnumpy() for k, v in ex.grad_dict.items()
                 if v is not None}
    aux = {k: v.asnumpy() for k, v in ex.aux_dict.items()}
    return outs, grads, aux


_SHAPES = {"data": (4, 3, 8, 8), "softmax_label": (4,)}


def test_executor_train_parity():
    """Fused forward+backward (one custom-vjp region per block, both
    directions) matches the unfused graph: outputs, every gradient."""
    sym = _resnet_style_net()
    o_ref, g_ref, _ = _exec_run(sym, False, True, _SHAPES)
    o_fused, g_fused, _ = _exec_run(sym, True, True, _SHAPES)
    for a, b in zip(o_ref, o_fused):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    assert set(g_ref) == set(g_fused)
    for k in g_ref:
        np.testing.assert_allclose(g_ref[k], g_fused[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_executor_eval_parity_uses_global_stats():
    """Eval-mode BN (moving stats) lowers through the same fused region;
    outputs must match the unfused eval graph bit-for-bit semantics."""
    sym = _resnet_style_net()
    o_ref, _, _ = _exec_run(sym, False, False, _SHAPES, aux_seed=11,
                            backward=False)
    o_fused, _, _ = _exec_run(sym, True, False, _SHAPES, aux_seed=11,
                              backward=False)
    for a, b in zip(o_ref, o_fused):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("act", ["sigmoid", "tanh"])
def test_executor_fc_act_parity(act):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=8,
                                name="fc0")
    net = mx.sym.Activation(net, act_type=act, name="a0")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    assert _plan(sym).summary()["kinds"] == {"fc_act": 1}
    o_ref, g_ref, _ = _exec_run(sym, False, True, _SHAPES)
    o_fused, g_fused, _ = _exec_run(sym, True, True, _SHAPES)
    np.testing.assert_allclose(o_ref[0], o_fused[0], rtol=2e-5,
                               atol=2e-6)
    for k in g_ref:
        np.testing.assert_allclose(g_ref[k], g_fused[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_executor_fc_act_flatten_false_parity():
    """FullyConnected(flatten=False) keeps its leading batch dims; the
    fused region's backward must contract ALL of them (review r6)."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, flatten=False,
                                name="fc0")
    net = mx.sym.Activation(net, act_type="relu", name="a0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = {"data": (4, 5, 6), "softmax_label": (4,)}
    o_ref, g_ref, _ = _exec_run(sym, False, True, shapes)
    o_fused, g_fused, _ = _exec_run(sym, True, True, shapes)
    np.testing.assert_allclose(o_ref[0], o_fused[0], rtol=2e-5,
                               atol=2e-6)
    for k in g_ref:
        np.testing.assert_allclose(g_ref[k], g_fused[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_fused_conv_bn_region_bf16_biased_grads():
    """A biased conv under a bf16 compute view: the region's bias
    cotangent must come back in the bias dtype (review r6 — the f32
    accumulator used to fail custom_vjp's aval check)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import fused as F

    conv_attrs = {"kernel": (3, 3), "stride": (1, 1), "dilate": (1, 1),
                  "pad": (1, 1), "num_group": 1}
    bn_attrs = {"eps": 1e-5, "momentum": 0.9}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, (2, 4, 4, 3)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-1, 1, (8, 3, 3, 3)), jnp.bfloat16)
    b = jnp.asarray(rng.uniform(-1, 1, (8,)), jnp.bfloat16)
    gamma = jnp.ones((8,), jnp.float32)
    beta = jnp.zeros((8,), jnp.float32)
    mm = jnp.zeros((8,), jnp.float32)
    mv = jnp.ones((8,), jnp.float32)

    def loss(x, w, b):
        out, _mm, _mv = F.fused_block_conv_bn_act(
            conv_attrs, bn_attrs, "NHWC", True, "relu",
            x, w, b, gamma, beta, mm, mv)
        return jnp.sum(out.astype(jnp.float32))

    dx, dw, db = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    assert db.dtype == jnp.bfloat16 and db.shape == (8,)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype
    assert np.isfinite(np.asarray(db, np.float32)).all()


def test_seeded_partial_graph_never_fuses():
    """Pipeline stages evaluate partial topos with seeded boundary
    values; a chain straddling the boundary reaches nodes outside the
    stage topo, so seeded graphs must not fuse (review r6 — the
    planner used to fuse the out-of-topo conv and die on a KeyError)."""
    import jax.numpy as jnp
    from mxnet_tpu.symbol import eval_graph

    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, kernel=(1, 1), num_filter=4,
                           no_bias=True, name="c")
    bn = mx.sym.BatchNorm(c, name="bn", fix_gamma=False)
    out = mx.sym.Activation(bn, act_type="relu", name="r")
    topo = out._topo()
    conv_node = next(n for n in topo if n.name == "c")
    data_node = next(n for n in topo if n.name == "data")
    # stage-2 topo: the boundary (conv) and its input stay behind
    stage = [n for n in topo if n is not conv_node and n is not data_node]
    rng = np.random.RandomState(0)
    conv_out = jnp.asarray(rng.uniform(-1, 1, (2, 4, 3, 3)), jnp.float32)
    var_values = {
        id(n): jnp.asarray(
            rng.uniform(0.5, 1.0, (4,)) if "gamma" in n.name
            or "var" in n.name else np.zeros(4), jnp.float32)
        for n in stage if n.is_variable}

    def run(fuse):
        with block_fusion(fuse):
            heads, _aux = eval_graph(
                stage, out._entries, dict(var_values), is_train=True,
                seed_vals={id(conv_node): (conv_out,)})
        return np.asarray(heads[0])

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6)


def test_executor_graceful_fallback_runs_unfused():
    """An ineligible pattern (BN axis) under the fused flag must run —
    and match — the unfused graph, never error."""
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn", axis=3)
    net = mx.sym.Activation(bn, act_type="relu")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    o_ref, g_ref, _ = _exec_run(sym, False, True, _SHAPES)
    o_fused, g_fused, _ = _exec_run(sym, True, True, _SHAPES)
    np.testing.assert_allclose(o_ref[0], o_fused[0], rtol=2e-5,
                               atol=2e-6)
    for k in g_ref:
        np.testing.assert_allclose(g_ref[k], g_fused[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


# ---------------------------------------------------- trainer parity
def _make_trainer(fuse, layout="NHWC", dtype="float32", hw=8):
    mesh = build_mesh(tp=1)
    kwargs = dict(
        data_shapes={"data": (8, 3, hw, hw)},
        label_shapes={"softmax_label": (8,)},
        dtype=dtype, seed=3, learning_rate=0.1, momentum=0.9,
        fuse_blocks=fuse)
    if layout is not None:
        kwargs["layout"] = layout
    return ShardedTrainer(_resnet_style_net(), mesh, **kwargs)


def _batch(seed=0, hw=8):
    rng = np.random.RandomState(seed)
    return {
        "data": (rng.uniform(-1, 1, (8, 3, hw, hw)) * 2.0 + 0.25)
        .astype(np.float32),
        "softmax_label": rng.randint(0, 10, 8).astype(np.float32),
    }


@pytest.mark.parametrize("hw", [8, 7])
@pytest.mark.parametrize("layout", ["NHWC", None])
def test_trainer_step_parity(layout, hw):
    """Two full fused-step training updates (fwd + custom-vjp bwd +
    optimizer + BN aux) match the unfused trainer in either layout, at
    a width of whole tiles and at an odd one (ResNet-50's last stage:
    the 1x1 block runs the XLA region there)."""
    t_ref = _make_trainer(False, layout=layout, hw=hw)
    t_fused = _make_trainer(True, layout=layout, hw=hw)
    losses = []
    for t in (t_ref, t_fused):
        b = t.put_batch(_batch(0, hw))
        losses.append((float(t.step(b)), float(t.step(b))))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5,
                               atol=1e-7)
    for n in t_ref.params:
        np.testing.assert_allclose(
            np.asarray(t_ref.params[n]), np.asarray(t_fused.params[n]),
            rtol=2e-4, atol=2e-5, err_msg=n)
    for n in t_ref.aux:
        np.testing.assert_allclose(
            np.asarray(t_ref.aux[n]), np.asarray(t_fused.aux[n]),
            rtol=2e-4, atol=2e-5, err_msg="aux:" + n)


def test_trainer_eval_forward_parity():
    """trainer.forward (eval BN semantics inside the fused regions)
    matches the unfused inference forward after a training step."""
    t_ref = _make_trainer(False)
    t_fused = _make_trainer(True)
    for t in (t_ref, t_fused):
        float(t.step(t.put_batch(_batch(0))))
    feed = {"data": _batch(1)["data"]}
    np.testing.assert_allclose(
        np.asarray(t_ref.forward(feed)[0]),
        np.asarray(t_fused.forward(feed)[0]), rtol=2e-4, atol=2e-5)


def test_trainer_fusion_summary_and_metrics():
    """The plan leaves its host-side traces: fusion_summary(), the
    module-level last_plan_summary snapshot, and the mxtpu_fusion_*
    counters (one batch of increments per trace)."""
    plans0 = telemetry.counter("mxtpu_fusion_plans_total").get()
    t = _make_trainer(True)
    float(t.step(t.put_batch(_batch(0))))
    s = t.fusion_summary()
    assert s is not None and s["blocks"] >= 3
    assert s == fusion.last_plan_summary()
    assert telemetry.counter("mxtpu_fusion_plans_total").get() > plans0
    assert telemetry.counter("mxtpu_fusion_blocks_total").labels(
        kind="conv_bn_act").get() >= 2
    # unfused trainers surface no summary
    assert _make_trainer(False).fusion_summary() is None


# ------------------------------------------- stable names on the device
def _step_args(t):
    import jax
    import jax.numpy as jnp
    spec = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    batch = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
             for n, s in t._input_shapes.items()}
    return (spec(t.params), spec(t.opt_state), spec(t.aux), batch,
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))


def _opcodes(text):
    import collections
    import re
    return collections.Counter(
        re.findall(r"= \"?((?:stablehlo|func|chlo)\.[a-z_]+)", text))


def _kernel_block_trainer():
    """conv3x3+BN+relu -> a conv1x1+BN+relu of the shapes the
    matmul-with-stats kernel was written for (128 lanes in and out, W
    of 8 a whole tile of rows) -> pooled FC+relu head."""
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                             num_filter=128, no_bias=True, name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu", name="act0")
    net = mx.sym.Convolution(net, kernel=(1, 1), num_filter=128,
                             no_bias=True, name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu", name="act1")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=16,
                                name="fc0")
    net = mx.sym.Activation(net, act_type="relu", name="fcact")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc1")
    return ShardedTrainer(
        mx.sym.SoftmaxOutput(net, name="softmax"), build_mesh(n_devices=1),
        data_shapes={"data": (8, 3, 8, 8)},
        label_shapes={"softmax_label": (8,)}, layout="NHWC", seed=3,
        learning_rate=0.1, momentum=0.9, fuse_blocks=True)


def test_train_step_names_its_phases_and_blocks(monkeypatch):
    """The step program carries the names a device trace is read by:
    the forward / backward / optimizer scopes, the fused block's kind
    round the block's convolution, and fixed module names.  Lowered for
    the TPU from here: the 1x1 block is an XLA region there too, so the
    step holds no custom call, by the removed kernel's name or any."""
    import jax
    from mxnet_tpu import context
    from mxnet_tpu.parallel import trainer as trainer_mod
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    t = _kernel_block_trainer()
    assert t._plan_decisions is None   # greedy plan
    text = jax.jit(t._py_step).trace(*_step_args(t)).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "module @jit_mxtpu_train_step" in text
    for name in (trainer_mod.SCOPE_FWD, trainer_mod.SCOPE_BWD,
                 trainer_mod.SCOPE_OPT, "mxtpu.block.conv_bn_act",
                 "mxtpu.block.fc_act"):
        assert name in text, name
    assert "mxtpu_matmul_stats" not in text \
        and "tpu_custom_call" not in text
    assert t.fusion_summary()["pallas_blocks"] == 0
    # the block's convolution sits inside the forward, inside its block
    assert ("mxtpu.fwd/jvp(mxtpu.block.conv_bn_act)/conv_general_dilated"
            in text)
    chain = t._build_multi_step(2)
    assert chain.__wrapped__.__name__ == "mxtpu_train_chain"


def test_names_change_no_arithmetic(monkeypatch):
    """With ``jax.named_scope`` patched to a null context the same two
    steps give bit-identical losses and parameters, and the lowered
    text holds the same count of each opcode."""
    import contextlib
    import jax

    def run():
        t = _make_trainer(True)
        text = jax.jit(t._py_step).lower(*_step_args(t)).as_text(
            debug_info=True)
        b = t.put_batch(_batch(0))
        losses = [np.asarray(t.step(b)), np.asarray(t.step(b))]
        return losses, {k: np.asarray(v) for k, v in t.params.items()}, text

    named = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = run()
    assert "mxtpu.fwd" in named[2] and "mxtpu.fwd" not in plain[2]
    for a, b in zip(named[0], plain[0]):
        assert a.tobytes() == b.tobytes()
    for k in named[1]:
        assert named[1][k].tobytes() == plain[1][k].tobytes(), k
    counts = _opcodes(named[2])
    assert counts and counts == _opcodes(plain[2])
