"""LFM2-MoE support (ISSUE 26): every new op against its lines of the plain
reference (``benchmark/references/lfm2-8b-a1b.py``), grouped-query flash
attention against the repeated-heads form on both kernel routes, the expert
layer's shares adding up to the uncut layer, the whole model through
``ShardedTrainer`` against ``common.follow``, and the harness at toy size.
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
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
#: the benchmark's harness (``benchmark/run.py``) and the plain reference,
#: set for this file's tests by ``_benchmark_modules``
run = REF = None


@pytest.fixture(scope="module", autouse=True)
def _benchmark_modules():
    """The benchmark's modules, importable while this file's tests run and
    gone after them: ``benchmark/references/common.py`` is ``common`` in
    ``sys.modules``, the name ``examples/image-classification/common`` goes
    by in other test files, and which file a worker runs next depends on
    timing."""
    global run, REF
    path, before = list(sys.path), dict(sys.modules)
    shadowed = {name: sys.modules.pop(name) for name in ("common", "run")
                if name in sys.modules}
    sys.path[:0] = [BENCH, os.path.join(BENCH, "references")]
    import run as harness
    run, REF = harness, harness.load_module("references", "lfm2-8b-a1b")
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


def _op(name, *arrays, **attrs):
    """An op through the imperative surface, as jnp."""
    out = getattr(mx.nd, name)(*[mx.nd.array(np.asarray(a)) for a in arrays],
                               **attrs)
    return jnp.asarray(out.asnumpy())


def _fcompute(name, attrs, *arrays):
    """An op's lowering itself (differentiable)."""
    from mxnet_tpu.ops.registry import OpContext, get_op
    op = get_op(name)
    return op.fcompute(op.parse_attrs(attrs), OpContext(is_train=True),
                       *arrays)


# ------------------------------------------------------------ elementwise
def _ref_conv(x, w):
    """The reference's lines for one sequence, over a batch."""
    taps = w.shape[1]

    def one(v):
        vp = jnp.pad(v, ((taps - 1, 0), (0, 0)))
        return sum(w[:, j] * vp[j:j + v.shape[0]] for j in range(taps))
    return jax.vmap(one)(x)


OPS = {
    "rmsnorm": (lambda x, g: _fcompute("RMSNorm", {"eps": 1e-5}, x, g),
                lambda x, g: REF._rms(x, g, 1e-5),
                lambda: (_rand(2, 5, 16), 1 + _rand(16, seed=1, scale=0.3))),
    "rmsnorm_heads": (lambda x, g: _fcompute("RMSNorm", {"eps": 1e-5}, x, g),
                      lambda x, g: REF._rms(x, g, 1e-5),
                      lambda: (_rand(2, 5, 4, 8), 1 + _rand(8, seed=1, scale=0.3))),
    "silu": (lambda x: _fcompute("Activation", {"act_type": "silu"}, x),
             jax.nn.silu, lambda: (_rand(3, 7),)),
    "rotary": (lambda x: _fcompute("_contrib_RotaryEmbedding", {"base": 1e6}, x),
               lambda x: jax.vmap(lambda s: REF._rope(s, 1e6))(x),
               lambda: (_rand(2, 9, 3, 8),)),
    "causal_conv": (lambda x, w: _fcompute("_contrib_CausalConv1D",
                                           {"kernel": 3}, x, w),
                    _ref_conv, lambda: (_rand(2, 11, 6), _rand(6, 3, seed=2))),
}


@pytest.mark.parametrize("name", sorted(OPS))
def test_new_op_matches_reference_values_and_gradients(name):
    op, ref, make = OPS[name]
    args = make()
    np.testing.assert_allclose(op(*args), ref(*args), rtol=2e-6, atol=2e-6)
    cot = _rand(*ref(*args).shape, seed=9)
    got = jax.grad(lambda *a: jnp.sum(op(*a) * cot), range(len(args)))(*args)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * cot), range(len(args)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)


def test_causal_conv_sees_no_future():
    x, w = _rand(1, 8, 4), _rand(4, 3, seed=3)
    y = _op("_contrib_CausalConv1D", x, w, kernel=3)
    y2 = _op("_contrib_CausalConv1D", x.at[:, 5:].set(7.0), w, kernel=3)
    np.testing.assert_array_equal(y[:, :5], y2[:, :5])
    assert not np.allclose(y[:, 5:], y2[:, 5:])


@pytest.mark.parametrize("op,args,attrs,says", [
    ("Activation", [(2, 3)], {"act_type": "swish"},
     ["'swish'", "(2, 3)", "silu", "relu"]),
    ("_contrib_FlashAttention", [(1, 8, 6, 4), (1, 8, 4, 4), (1, 8, 4, 4)], {},
     ["6 query heads", "4 key/value heads", "(1, 8, 6, 4)"]),
    ("_contrib_FlashAttention", [(8, 6, 4), (8, 6, 4), (8, 6, 4)], {},
     ["(batch, seq, heads, head_dim)", "(8, 6, 4)"]),
    ("_contrib_CausalConv1D", [(1, 8, 4), (4, 2)], {"kernel": 3},
     ["(4, 2)", "(channels, 3)"]),
    ("_contrib_RotaryEmbedding", [(1, 8, 4, 5)], {}, ["(1, 8, 4, 5)", "even"]),
])
def test_errors_name_the_offending_value_and_shape(op, args, attrs, says):
    with pytest.raises(MXNetError) as e:
        _op(op, *[_rand(*s) for s in args], **attrs)
    for text in says:
        assert text in str(e.value), (text, str(e.value))


# ------------------------------------------------- grouped-query attention
def _gqa_inputs(b=2, t=256, hq=8, hk=2, d=32):
    return (_rand(b, t, hq, d), _rand(b, t, hk, d, seed=1),
            _rand(b, t, hk, d, seed=2), _rand(b, t, hq, d, seed=3))


# (block_q, block_k) at 256 positions: one K/V panel, and K/V streamed
ROUTES = {"panel": (128, 256), "stream": (64, 64)}


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("causal", [True, False])
def test_grouped_query_flash_matches_repeated_heads(route, causal):
    q, k, v, g = _gqa_inputs()
    want, vjp = jax.vjp(lambda *a: pk._attention_jnp(*a, causal), q, k, v)
    o, lse = pk._flash_attention_fwd_pallas(q, k, v, causal, True,
                                            blocks=ROUTES[route])
    np.testing.assert_allclose(o, want, atol=2e-6)
    grads = pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, causal, True,
                                           blocks=ROUTES[route])
    assert [x.shape for x in grads] == [q.shape, k.shape, v.shape]
    for got, ref in zip(grads, vjp(g)):
        np.testing.assert_allclose(got, ref, atol=1e-5)


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_grouped_query_flash_is_the_ungrouped_kernel_head_by_head(route):
    """A query head reads its shared key/value head through the block maps:
    the same arithmetic as the factor-1 kernels on repeated heads, bit for bit
    (forward and dQ; dK/dV are sums over the group in another order)."""
    q, k, v, g = _gqa_inputs()
    kr, vr = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
    o4, lse4 = pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                              blocks=ROUTES[route])
    o1, lse1 = pk._flash_attention_fwd_pallas(q, kr, vr, True, True,
                                              blocks=ROUTES[route])
    np.testing.assert_array_equal(o4, o1)
    np.testing.assert_array_equal(lse4, lse1)
    dq4, dk4, _ = pk._flash_attention_bwd_pallas(q, k, v, o4, lse4, g, True,
                                                 True, blocks=ROUTES[route])
    dq1, dk1, _ = pk._flash_attention_bwd_pallas(q, kr, vr, o1, lse1, g, True,
                                                 True, blocks=ROUTES[route])
    np.testing.assert_array_equal(dq4, dq1)
    np.testing.assert_allclose(
        dk4, dk1.reshape(dk4.shape[:2] + (2, 4, -1)).sum(axis=3), atol=1e-5)


def test_factor_one_flash_calls_are_unchanged():
    """With as many key/value heads as query heads the kernels are called as
    before grouped queries came: same grid, no group arithmetic in the kernel
    body, no compiler parameters.  Since the causal ranges (PR 27) a streaming
    kernel takes one remainder for a Q block's place on its K/V tile's diagonal
    where that tile holds several Q blocks; the block's place in its own head
    is a second one, and only grouped queries take it."""
    def text(blocks, kv_heads=4):
        q = jax.ShapeDtypeStruct((2, 256, 4, 32), jnp.float32)
        kv = jax.ShapeDtypeStruct((2, 256, kv_heads, 32), jnp.float32)

        def f(q, k, v, g):
            o, lse = pk._flash_attention_fwd_pallas(q, k, v, True, True,
                                                    blocks=blocks)
            return pk._flash_attention_bwd_pallas(q, k, v, o, lse, g, True,
                                                  True, blocks=blocks)
        return str(jax.make_jaxpr(f)(q, kv, kv, q))

    for route, blocks in ROUTES.items():
        t = text(blocks)
        assert " rem " not in t and "vmem_limit" not in t, route
        assert t.count("grid=(8, %d" % (256 // blocks[route == "stream"]))
        assert t.count("name=mxtpu_flash_fwd_%s" % route) == 1
        assert t.count("name=mxtpu_flash_bwd_%s" % route) == 1
    # two Q blocks on a streamed tile's diagonal: forward and backward take
    # one remainder each, and the grouped kernels one more
    assert text((64, 128)).count(" rem ") == 2
    assert text((64, 128), kv_heads=1).count(" rem ") == 4
    # one query head a key/value head at 8192 positions asks for nothing, as
    # it always did; four hold a dQ accumulator of 8 MiB, and that call asks
    assert pk._vmem_params(pk._vmem_need(64, 128, 2048, 8192)) == {}
    assert "compiler_params" in pk._vmem_params(
        pk._vmem_need(64, 128, 2048, 4 * 8192))


# ------------------------------------------------------------ expert layer
MOE = dict(num_experts=2, router_num_experts=8, num_experts_per_tok=2,
           expert_offset=0, use_expert_bias=True, norm_topk_prob=True,
           routed_scaling_factor=1.0)


def _moe_params(d=16, ff=24, e=8, bias=1.5):
    """Deliberately uneven routing: a strong bias sends most tokens to a few
    experts and none to others."""
    rng = np.random.RandomState(4)
    bias_v = rng.randn(e) * bias
    bias_v[3] = -50.0                       # expert 3 gets no token
    return {"moe_router_weight": _rand(e, d, seed=5, scale=0.5),
            "moe_expert_bias": jnp.asarray(bias_v, jnp.float32),
            "moe_w1_weight": _rand(e, d, ff, seed=6, scale=0.2),
            "moe_w3_weight": _rand(e, d, ff, seed=7, scale=0.2),
            "moe_w2_weight": _rand(e, ff, d, seed=8, scale=0.2)}


def _share(p, off, held):
    return {k: (v[off:off + held] if k.endswith(("w1_weight", "w3_weight",
                                                  "w2_weight")) else v)
            for k, v in p.items()}


def _layer(x, p, off):
    return moe.topk_moe(x, p["moe_router_weight"], p["moe_expert_bias"],
                        p["moe_w1_weight"], p["moe_w3_weight"],
                        p["moe_w2_weight"], 2, expert_offset=off)


@pytest.mark.parametrize("off", [0, 2, 4, 6])
def test_topk_moe_share_matches_reference(off):
    x, p = _rand(64, 16, seed=10), _share(_moe_params(), off, 2)
    cfg = dict(MOE, expert_offset=off)
    y, load = _layer(x, p, off)
    np.testing.assert_allclose(y, REF.expert_layer(x, p, cfg), atol=2e-6)
    # the load it reports is the routing's own: counts of the two held
    # experts, tokens with neither
    s = jax.nn.sigmoid(x @ p["moe_router_weight"].T) + p["moe_expert_bias"]
    idx = np.asarray(jax.lax.top_k(s, 2)[1])
    want = [np.sum(idx == off), np.sum(idx == off + 1),
            np.sum(~np.any((idx >= off) & (idx < off + 2), axis=1))]
    np.testing.assert_array_equal(np.asarray(load), want)
    if off == 2:
        assert want[1] == 0 and want[2] > 0   # an idle expert, unrouted tokens
    keys = sorted(p)
    got = jax.grad(lambda x, *w: jnp.sum(jnp.square(
        _layer(x, dict(zip(keys, w)), off)[0])), range(1 + len(keys)))(
            x, *[p[k] for k in keys])
    ref = jax.grad(lambda x, *w: jnp.sum(jnp.square(
        REF.expert_layer(x, dict(zip(keys, w)), cfg))), range(1 + len(keys)))(
            x, *[p[k] for k in keys])
    for name, g, r in zip(["x"] + keys, got, ref):
        np.testing.assert_allclose(g, r, atol=2e-5, err_msg=name)
    # the bias enters the selection only; a share's router gets the held
    # experts' part of its gradient (the op holds no training policy)
    assert not np.any(np.asarray(got[1 + keys.index("moe_expert_bias")]))
    if want[0] + want[1]:
        assert np.any(np.asarray(got[1 + keys.index("moe_router_weight")]))


def test_topk_moe_router_untrained_makes_the_scores_constants():
    """``router_trained=False``: the reference's ``stop_gradient`` on the
    scores; no gradient for the router, and the hidden state's is the
    experts' path alone."""
    x, p = _rand(64, 16, seed=10), _share(_moe_params(), 0, 2)
    cfg = dict(MOE, router_trained=False)
    keys = sorted(p)

    def frozen(x, p):
        return moe.topk_moe(x, p["moe_router_weight"], p["moe_expert_bias"],
                            p["moe_w1_weight"], p["moe_w3_weight"],
                            p["moe_w2_weight"], 2, router_trained=False)[0]

    np.testing.assert_array_equal(frozen(x, p), _layer(x, p, 0)[0])
    got, ref, exact = (jax.grad(lambda x, *w: jnp.sum(jnp.square(
        layer(x, dict(zip(keys, w))))), range(1 + len(keys)))(
            x, *[p[k] for k in keys])
        for layer in (frozen, lambda x, p: REF.expert_layer(x, p, cfg),
                      lambda x, p: _layer(x, p, 0)[0]))
    for name, g, r in zip(["x"] + keys, got, ref):
        np.testing.assert_allclose(g, r, atol=2e-5, err_msg=name)
    assert not np.any(np.asarray(got[1 + keys.index("moe_router_weight")]))
    assert float(jnp.max(jnp.abs(got[0] - exact[0]))) > 1e-4


def test_topk_moe_holding_every_expert_trains_its_router():
    x, p = _rand(64, 16, seed=12), _moe_params()
    cfg = dict(MOE, num_experts=8)
    keys = sorted(p)
    got, ref = (jax.grad(lambda x, *w: jnp.sum(jnp.square(
        layer(x, dict(zip(keys, w))))), range(1 + len(keys)))(
            x, *[p[k] for k in keys])
        for layer in (lambda x, p: _layer(x, p, 0)[0],
                      lambda x, p: REF.expert_layer(x, p, cfg)))
    for name, g, r in zip(["x"] + keys, got, ref):
        np.testing.assert_allclose(g, r, atol=2e-5, err_msg=name)
    assert np.any(np.asarray(got[1 + keys.index("moe_router_weight")]))


def test_the_shares_add_up_to_the_uncut_layer():
    x, p = _rand(64, 16, seed=11), _moe_params()
    whole = REF.expert_layer(x, p, dict(MOE, num_experts=8))
    parts = [_layer(x, _share(p, off, 2), off) for off in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, atol=3e-6)
    counts = np.concatenate([np.asarray(load[:2]) for _, load in parts])
    assert counts.sum() == 64 * 2 and counts[3] == 0   # every assignment, once


@pytest.mark.parametrize("wrt", ["x", "moe_router_weight", "moe_expert_bias"])
def test_the_shares_gradients_add_up_to_the_uncut_layers(wrt):
    """What an expert-parallel deployment sums after its exchange: each share
    returns its true part of the gradient for the hidden state and for the
    router, gates included, and the parts are the whole layer's."""
    x, p = _rand(64, 16, seed=11), _moe_params()
    cot = _rand(64, 16, seed=13)

    def grad(layer):
        def loss(x, shared):
            return jnp.sum(layer(x, dict(p, **shared)) * cot)
        gx, gs = jax.grad(loss, (0, 1))(x, {
            k: p[k] for k in ("moe_router_weight", "moe_expert_bias")})
        return gx if wrt == "x" else gs[wrt]

    whole = grad(lambda x, p: REF.expert_layer(x, p, dict(MOE, num_experts=8)))
    parts = [grad(lambda x, p, off=off: _layer(x, _share(p, off, 2), off)[0])
             for off in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    if wrt == "moe_expert_bias":
        assert not np.any(np.asarray(whole))
    else:
        assert np.any(np.asarray(whole))
        # and no share's part is the whole: each holds a quarter of it
        assert all(float(jnp.max(jnp.abs(g - whole))) > 1e-4 for g in parts)


def test_nothing_is_dropped_when_every_assignment_is_held():
    """The worst imbalance a share can be given: a bias that sends every
    token's two choices to the two held experts fills the sorted buffer to its
    last row, and the result is still the reference's."""
    x, p = _rand(64, 16, seed=14), _share(_moe_params(), 4, 2)
    p["moe_expert_bias"] = jnp.where(
        (jnp.arange(8) >= 4) & (jnp.arange(8) < 6), 10.0, 0.0)
    y, load = _layer(x, p, 4)
    assert np.asarray(load).tolist() == [64, 64, 0]
    np.testing.assert_allclose(
        y, REF.expert_layer(x, p, dict(MOE, expert_offset=4)), atol=2e-6)


def test_topk_moe_op_shapes_aux_and_plan():
    net = mx.sym._contrib_TopKMoE(
        mx.sym.Variable("data"), num_experts=8, experts_held=2,
        expert_offset=4, num_experts_per_tok=2, hidden_size=24, name="moe")
    args, outs, aux = net.infer_shape(data=(2, 8, 16))
    assert dict(zip(net.list_arguments(), args)) == {
        "data": (2, 8, 16), "moe_router_weight": (8, 16),
        "moe_expert_bias": (8,), "moe_w1_weight": (2, 16, 24),
        "moe_w3_weight": (2, 16, 24), "moe_w2_weight": (2, 24, 16)}
    assert outs == [(2, 8, 16)]
    assert dict(zip(net.list_auxiliary_states(), aux)) == {"moe_load": (3,)}
    with pytest.raises(MXNetError, match="experts_held=6"):
        mx.sym._contrib_TopKMoE(
            mx.sym.Variable("data"), num_experts=8, experts_held=6,
            expert_offset=4, num_experts_per_tok=2, hidden_size=24
        ).infer_shape(data=(2, 8, 16))
    with moe.plan_recording():
        net.infer_shape(data=(2, 8, 16))
    plan = moe.last_plan_summary()
    assert plan["expert_layers"] == 1
    assert plan["layers"][0] == {
        "num_experts": 8, "experts_held": 2, "expert_offset": 4,
        "num_experts_per_tok": 2, "hidden_size": 24, "buffer_rows": 32,
            "small_rows": 16, "even_rows": 8.0, "products_trained": 9,
            "score_func": "sigmoid", "token_sum": "scatter_add"}
    # how the products were lowered is read from a compiled program only
    assert plan["grouped_products"] is None and plan["grouped_layers"] is None


class _Text:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_grouped_layers_are_counted_in_the_compiled_programs_text():
    """Instruction lines as XLA:TPU wrote them for the cell's step (a kept
    compile for a described v5e): the products count, their tile maps and the
    references to them do not."""
    lines = (
        ["  %%ragged-dot-none.%d = bf16[32768,1792]{1,0:T(8,128)(2,1)} "
         "custom-call(%%a, %%b), custom_call_target=\"tpu_custom_call\"" % i
         for i in range(1, 18)]
        + ["  %ragged-dot-none = bf16[32768,2048]{1,0} custom-call(%a, %b)",
           "  %ragged-dot-metadata.4 = (s32[9]{0}, s32[71]{0}) custom-call(%g)",
           "  %get-tuple-element.9 = s32[1]{0} get-tuple-element("
           "%ragged-dot-metadata.4), index=3",
           "  %fusion.7 = bf16[8,8]{1,0} fusion(%ragged-dot-none.3)"])
    with moe.plan_recording():
        moe.note_layer(buffer_rows=32768)
        moe.note_layer(buffer_rows=32768)
        moe.note_layer(buffer_rows=32768)
    moe.note_compiled(_Text("\n".join(lines)))
    plan = moe.last_plan_summary()
    assert plan["grouped_products"] == 18 and plan["grouped_layers"] == 2
    moe.note_compiled(_Text("\n".join(lines * 2)))    # an unrolled chain
    assert moe.last_plan_summary()["grouped_layers"] == 3
    # this backend multiplies densely and masks: no such call, and it says so
    x, p = _rand(64, 16, seed=10), _share(_moe_params(), 0, 2)
    with moe.plan_recording():
        exe = jax.jit(lambda x: _layer(x, p, 0)[0]).lower(x).compile()
    moe.note_compiled(exe)
    assert moe.last_plan_summary()["grouped_layers"] == 0


def test_topk_moe_builds_nothing_of_tokens_by_experts_by_capacity():
    """No value of the traced layer, forward or backward, is larger than the
    sorted buffer's hidden state (tokens x k x ff): nothing grows with tokens
    x experts x capacity."""
    t, d, ff, e, held, k = 256, 16, 32, 64, 16, 2
    p = dict(zip("r b w1 w3 w2".split(), (
        _rand(e, d), _rand(e), _rand(held, d, ff), _rand(held, d, ff),
        _rand(held, ff, d))))
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(moe.topk_moe(
        x, p["r"], p["b"], p["w1"], p["w3"], p["w2"], k)[0])))(_rand(t, d))
    biggest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.jaxpr.eqns
                  for v in eqn.outvars)
    assert biggest <= t * k * max(ff, d, held), biggest


# ------------------------------------------------ the model and the harness
def _toy_bench():
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"))
    bench["configs"] = [{"name": "smoke-lfm2",
                         "file": "benchmark/configs/smoke-lfm2.json"}]
    bench["workloads"] = [{"name": "smoke-lfm2", "config": "smoke-lfm2",
                           "traffic": "smoke-s64-b1-chain2", "chips": 1}]
    return bench


SEED = 2 ** 31 + 26


@pytest.fixture(scope="module")
def toy_cell():
    return run.Cell("smoke-lfm2", _toy_bench())


@pytest.fixture(scope="module")
def both_sides(toy_cell):
    """The reference's and the program's first 1 + chain steps of the toy
    configuration (3 layers: dense-conv, expert-attention, expert-conv, width
    64, float32), from the same seeded weights; attention's scores are made 16
    query rows at a time so that the reference's blocks are exercised."""
    import traffic
    cell = toy_cell
    batch = traffic.host_batch(cell.cfg, cell.mix, 1, SEED)
    devices = jax.devices()[:1]
    rows, cell.refmod.ATTENTION_ROWS = cell.refmod.ATTENTION_ROWS, 16
    try:
        ref = run.reference_first_steps(cell, SEED, batch, 3, devices)
    finally:
        cell.refmod.ATTENTION_ROWS = rows
    session = cell.runner.open(
        cell.cfg, cell.cfgmod, cell.mix, devices, SEED,
        lambda key: cell.refmod.init_params(cell.cfg, key), run.seed_key(SEED),
        batch)
    prog = session.first_steps()
    # the chain program's second dispatch is the first the cost database
    # blocks on (its first was the compile)
    session.fetch(session.dispatch())
    plan, samples = moe.last_plan_summary(), moe.load_samples()
    aux = {k: np.asarray(v) for k, v in session.trainer.aux.items()}
    session.close()
    return ref, prog, plan, samples, aux


def test_model_symbol_is_built_from_the_configuration(toy_cell):
    net, data, label = toy_cell.cfgmod.build(toy_cell.cfg, toy_cell.mix, 1)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(**data, **label)[0]))
    del shapes["data"], shapes["softmax_label"]
    assert shapes == toy_cell.refmod.param_shapes(toy_cell.cfg)
    assert net.list_auxiliary_states() == ["layer1_moe_load", "layer2_moe_load"]
    assert net.list_outputs() == ["softmax_output"]
    # Module binds the same Symbol
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", data["data"])],
             label_shapes=[("softmax_label", label["softmax_label"])])
    mod.init_params()
    mod.forward(mx.io.DataBatch([mx.nd.zeros(data["data"])],
                                [mx.nd.zeros(label["softmax_label"])]),
                is_train=False)
    assert mod.get_outputs()[0].shape == (64, toy_cell.cfg["vocab_size"])


@pytest.mark.parametrize("trained", [True, False])
def test_the_router_is_frozen_by_the_configuration_and_only_when_asked(toy_cell, trained):
    """``router_trained: false`` is the configuration's decision, handed by
    the builder to each expert layer; without the key the graph is the
    published one."""
    from mxnet_tpu.models import lfm2_moe
    cfg = {k: v for k, v in toy_cell.cfg.items() if k != "router_trained"}
    net = lfm2_moe.get_symbol(cfg if trained else dict(cfg, router_trained=False), 64)
    layers = [n for n in json.loads(net.tojson())["nodes"]
              if n["op"] == "_contrib_TopKMoE"]
    assert len(layers) == 2
    assert all(str(n.get("attrs", n.get("attr", {})).get(
        "router_trained", "True")) == str(trained) for n in layers)


@pytest.mark.parametrize("number,tolerance", [
    ("loss_gap", 2e-5), ("grad_sample_err", 2e-5), ("grad_norm_gap", 2e-5),
    ("delta_norm_gap", 2e-4)])
def test_model_through_sharded_trainer_follows_the_reference(both_sides, number,
                                                            tolerance):
    """Float32 on both sides: three losses, the first gradient element by
    element and by leaf, and the parameters' change agree to float noise (Adam
    divides by the square root of a tiny second moment: its change is looser)."""
    import check
    ref, prog = both_sides[:2]
    assert len(ref["losses"]) == len(prog["losses"]) == 3
    values = {n: v for n, v, _ in check.numbers(prog, ref)}
    assert values[number] <= tolerance, values
    worst = max(check.leaf_gaps(prog["grad_norms"], ref["grad_norms"]).values())
    assert worst <= 1e-3, worst


def test_the_one_share_cut_trains_neither_expert_bias_nor_router(both_sides):
    ref, prog = both_sides[:2]
    biases = [k for k in ref["delta_norms"]
              if k.endswith(("_expert_bias", "_router_weight"))]
    assert len(biases) == 4
    for side in (ref, prog):
        # the change is measured against the seeded weights made again by
        # another program, whose normal() * 0.1 rounds differently by ~1e-8
        assert all(side["delta_norms"][k] < 1e-7 for k in biases)
        assert all(side["grad_norms"][k] == 0.0 for k in biases)
    for k in biases:
        assert prog["delta_norms"][k] == ref["delta_norms"][k]


def test_trainer_records_plan_and_publishes_loads(both_sides):
    _ref, _prog, plan, samples, aux = both_sides
    # read from the compiled chain program: none grouped on the CPU
    assert plan["expert_layers"] == 2 and plan["grouped_layers"] == 0
    assert {l["buffer_rows"] for l in plan["layers"]} == {64 * 2}
    assert {l["experts_held"] for l in plan["layers"]} == {4}
    assert {l["num_experts"] for l in plan["layers"]} == {8}
    # carried like moving statistics: 4 counts, unrouted tokens
    assert sorted(aux) == ["layer1_moe_load", "layer2_moe_load"]
    for load in aux.values():
        assert load.shape == (5,)
        assert 0 < load[:4].sum() <= 64 * 2
    # published on the dispatch the cost database blocked on, by layer
    assert samples and set(samples[-1][1]) == {"layer1_moe", "layer2_moe"}
    from mxnet_tpu import telemetry
    flat = telemetry.REGISTRY.flat()
    assert any(k.startswith("mxtpu_moe_expert_assignments") for k in flat)


def test_toy_cell_runs_through_the_harness():
    result = run.run_cell(run.Cell("smoke-lfm2", _toy_bench()), seed=SEED + 1,
                          seconds=0.3, trace=0, on_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["count"] == 1


def test_moe_readers_on_hand_made_samples(monkeypatch, toy_cell):
    def read(name, ctx):
        return run.load_module("layer_metrics", name).read(ctx)

    ctx = {"samples": [(100.0, 100.1, 100.8, [1.0]), (100.8, 100.9, 101.6, [1.0])],
           "cell": toy_cell}
    even = {"a": {"assignments": [8.0] * 4, "tokens_unrouted": 3.0},
            "b": {"assignments": [4.0, 12.0, 8.0, 8.0], "tokens_unrouted": 0.0}}
    skew = {"a": {"assignments": [0.0, 32.0, 0.0, 0.0], "tokens_unrouted": 0.0},
            "b": {"assignments": [8.0] * 4, "tokens_unrouted": 0.0}}
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(50.0, skew), (100.5, even),
                                               (101.0, even), (200.0, skew)])
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {
        "grouped_layers": 2, "layers": [{"buffer_rows": 128}] * 2})
    assert read("moe_grouped_layers", ctx) == 2
    assert read("moe_dropped_tokens", ctx) == 0
    # a buffer with a capacity of 30 rows could not have held layer a's 32
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {
        "grouped_layers": None, "layers": [{"buffer_rows": 30}] * 2})
    assert read("moe_dropped_tokens", ctx) == 2 * 2 * 2
    assert read("moe_grouped_layers", ctx) is None
    assert read("moe_load_max_over_mean", ctx) == 1.5      # layer b: 12 / 8
    # 64 tokens x 2 a token = 128 assignments a layer, 32 of them held
    assert read("moe_assignments_held_pct", ctx) == 25.0
    # no sample inside the window: the newest one before it
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(50.0, skew)])
    assert read("moe_load_max_over_mean", ctx) == 4.0
    # a program without the record (the parent of this change): None, no raise
    monkeypatch.delattr(moe, "load_samples")
    monkeypatch.delattr(moe, "last_plan_summary")
    for name in ("moe_grouped_layers", "moe_dropped_tokens",
                 "moe_load_max_over_mean", "moe_assignments_held_pct"):
        assert read(name, ctx) is None


def test_cell_configuration_keeps_every_published_width():
    """``benchmark/configs/lfm2-8b-a1b.json`` against the values this issue
    quotes from the published ``config.json``: only the three reduced keys
    differ, each with its published value beside it."""
    cfg = run.load_json(BENCH, "configs", "lfm2-8b-a1b.json")
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=7168, max_position_embeddings=128000,
        moe_intermediate_size=1792, norm_eps=1e-5, norm_topk_prob=True,
        num_attention_heads=32, num_dense_layers=2, num_experts=32,
        num_experts_per_tok=4, num_hidden_layers=24, num_key_value_heads=8,
        rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True,
        vocab_size=65536)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "num_experts", "vocab_size"}
    assert {k: published[k] for k in changed} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["router_num_experts"]) == (6, 8, 16384, 32)
    assert len(cfg["layer_types"]) == 24
    assert cfg["layer_types"][:6] == ["conv", "conv", "full_attention",
                                      "conv", "conv", "conv"]
    cfgmod = run.load_module("configs", "lfm2-8b-a1b")
    mix = run.load_json(BENCH, "traffic", "s8192-b1-chain2.json")
    shapes = REF.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 568647936
    # ISSUE 26's count: 1.66 GFLOP a token, 13.6 TFLOP a step
    assert abs(cfgmod.step_flops(cfg, mix, 1) / 8192 - 1.66e9) < 0.01e9
    costs = cfgmod.kernel_costs(cfg, mix)
    assert set(costs) == {"mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream",
                          "ragged-dot"}
    assert costs["ragged-dot"]["calls"] == 36
    assert costs["mxtpu_flash_fwd_stream"]["flops"] == 2 * 8192 * 8192 * 2048
