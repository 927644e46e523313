"""Expert-parallel switch MoE (parallel/moe.py): routing correctness
against a per-token reference, gradient flow, and sharded-vs-single
parity on the virtual CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import moe


def _ref_moe(x, p):
    """Per-token loop reference (ample capacity, no drops)."""
    logits = x @ p["router_w"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for i in range(x.shape[0]):
        eidx = probs[i].argmax()
        h = np.maximum(x[i] @ p["w1"][eidx] + p["b1"][eidx], 0)
        out[i] = (h @ p["w2"][eidx] + p["b2"][eidx]) * probs[i, eidx]
    return out


def test_switch_moe_matches_per_token_reference():
    rng = np.random.RandomState(0)
    p = moe.init_moe_params(rng, d=16, ff=32, num_experts=4)
    x = rng.randn(64, 16).astype("f")
    y, aux = moe.switch_moe(jnp.asarray(x), **{k: jnp.asarray(v)
                                               for k, v in p.items()},
                            capacity_factor=4.0)   # no capacity drops
    np.testing.assert_allclose(np.asarray(y), _ref_moe(x, p),
                               rtol=1e-4, atol=1e-4)
    assert float(aux) > 0


def test_switch_moe_capacity_drops_tokens():
    """With capacity 1 token per expert, most tokens fall back to zero
    (the residual path in a real block carries them)."""
    rng = np.random.RandomState(1)
    p = moe.init_moe_params(rng, d=8, ff=16, num_experts=2)
    x = rng.randn(32, 8).astype("f")
    y, _ = moe.switch_moe(jnp.asarray(x), **{k: jnp.asarray(v)
                                             for k, v in p.items()},
                          capacity_factor=2.0 / 16)   # C = 2 per expert
    nonzero_rows = (np.abs(np.asarray(y)).sum(-1) > 1e-7).sum()
    assert nonzero_rows <= 4, nonzero_rows
    assert nonzero_rows < x.shape[0] // 2  # most tokens dropped


def test_switch_moe_gradients_flow():
    rng = np.random.RandomState(2)
    p = {k: jnp.asarray(v) for k, v in
         moe.init_moe_params(rng, d=8, ff=16, num_experts=4).items()}
    x = jnp.asarray(rng.randn(32, 8).astype("f"))

    def loss(params):
        y, aux = moe.switch_moe(x, **params)
        return jnp.sum(y ** 2) + 0.01 * aux

    grads = jax.grad(loss)(p)
    for k, g in grads.items():
        assert np.isfinite(np.asarray(g)).all(), k
    assert float(jnp.abs(grads["router_w"]).max()) > 0
    assert float(jnp.abs(grads["w1"]).max()) > 0


def test_switch_moe_expert_parallel_parity():
    """8-way expert-sharded run equals the unsharded run."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    rng = np.random.RandomState(3)
    p = {k: jnp.asarray(v) for k, v in
         moe.init_moe_params(rng, d=16, ff=32, num_experts=8).items()}
    x = jnp.asarray(rng.randn(64, 16).astype("f"))
    y0, aux0 = jax.jit(lambda x, p: moe.switch_moe(x, **p))(x, p)

    mesh = moe.make_expert_mesh(8)

    @jax.jit
    def sharded(x, p):
        return moe.switch_moe(x, **p, mesh=mesh, expert_axis="expert")

    with mesh:
        y1, aux1 = sharded(x, p)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux1), float(aux0), rtol=1e-5)


def test_switch_moe_symbol_trains_through_module():
    """The _contrib_SwitchMoE op trains a classifier through Module.fit
    (aux load-balance loss attached via MakeLoss)."""
    import mxnet_tpu as mx
    # initializers draw from the global RNGs — pin for run-order
    # independence
    np.random.seed(7)
    mx.random.seed(7)
    rng = np.random.RandomState(0)
    protos = np.random.RandomState(42).randn(8, 16).astype("f")
    yy = rng.randint(0, 8, 1024)
    xx = (protos[yy] + 0.3 * rng.randn(1024, 16)).astype("f")

    data = mx.sym.Variable("data")
    moe_out = mx.sym._contrib_SwitchMoE(data, num_experts=4,
                                        hidden_size=32, name="moe")
    fc = mx.sym.FullyConnected(moe_out[0] + data, num_hidden=8,
                               name="cls")
    sm = mx.sym.SoftmaxOutput(fc, name="softmax")
    balance = mx.sym.MakeLoss(0.01 * moe_out[1], name="balance")
    net = mx.sym.Group([sm, balance])

    class _Acc(mx.metric.EvalMetric):
        """first-output accuracy (the balance head has no label)"""

        def __init__(self):
            super().__init__("acc0")

        def update(self, labels, preds):
            pred = preds[0].asnumpy().argmax(1)
            lab = labels[0].asnumpy()
            self.sum_metric += (pred == lab).sum()
            self.num_inst += len(lab)

    mod = mx.module.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(xx, yy.astype("f"), 64, shuffle=True)
    mod.fit(it, num_epoch=6, initializer=mx.init.Xavier(),
            optimizer="adam", optimizer_params={"learning_rate": 2e-3},
            eval_metric=_Acc())
    acc = mod.score(it, _Acc())[0][1]
    assert acc > 0.9, acc


# ------------------------------------------- topk_moe's two buffer sizes
T, K, D, FF, HELD = 64, 2, 32, 24, 4
#: a quarter held: the bound is every assignment there can be (128 rows, the
#: sorted gather path), the small buffer 64; a sixth held: the bound is four
#: times the even load (88 rows, dropping past it), the small buffer 48
SHARES = {"quarter": 16, "sixth": 24}
EXPERTS = {"gated": True, "ungated": False}


def _topk_params(num_experts, gated):
    rng = np.random.RandomState(3)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
    return {"x": f(T, D), "router_w": f(num_experts, D),
            "w1": f(HELD, D, FF) if gated else f(HELD, FF, D),
            "w3": f(HELD, D, FF) if gated else None, "w2": f(HELD, FF, D)}


def _routed(p, first, second):
    """``p`` with tokens and router made so that token ``i`` chooses the
    experts ``first[i]`` and ``second[i]``, in that order, whatever else."""
    e = p["router_w"].shape[0]
    x = np.array(p["x"]) * 0.05
    x[np.arange(T), first] += 6.0
    x[np.arange(T), second] += 4.0
    return dict(p, x=jnp.asarray(x), router_w=jnp.eye(e, D, dtype=jnp.float32))


def _held_exactly(p, n):
    """Routing that sends exactly ``n`` of the ``T * K`` assignments to the
    held experts ``0 .. HELD - 1``: both choices of the first ``n // 2``
    tokens, one of the next if ``n`` is odd, none of the others'."""
    e = p["router_w"].shape[0]
    i = np.arange(T)
    first = np.where(i < (n + 1) // 2, i % HELD, HELD + i % (e - HELD))
    second = np.where(i < n // 2, (i + 1) % HELD, HELD + (i + 1) % (e - HELD))
    return _routed(p, first, second)


def _layer_and_grads(p, bias, trained):
    names = [n for n in ("x", "w1", "w3", "w2", "router_w")
             if p[n] is not None and (n != "router_w" or trained)]
    mix = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def loss(args):
        q = dict(p, **args)
        y, load = moe.topk_moe(q["x"], q["router_w"], bias, q["w1"], q["w3"],
                               q["w2"], K, router_trained=trained)
        return jnp.sum(y * mix), (y, load)

    (_, (y, load)), grads = jax.value_and_grad(loss, has_aux=True)(
        {n: p[n] for n in names})
    return y, load, grads


def _one_buffer(monkeypatch, p, bias, trained):
    """The layer with the one size it had before it had two."""
    with monkeypatch.context() as m:
        m.setattr(moe, "small_buffer_rows", lambda *a: None)
        return _layer_and_grads(p, bias, trained)


def _assert_same(got, want):
    (y, load, grads), (y0, load0, grads0) = got, want
    np.testing.assert_array_equal(load, load0)
    np.testing.assert_allclose(y, y0, rtol=1e-5, atol=1e-6)
    assert sorted(grads) == sorted(grads0)
    for n in grads:
        assert float(jnp.abs(grads0[n]).max()) > 0, n
        np.testing.assert_allclose(grads[n], grads0[n], rtol=1e-5, atol=1e-6,
                                   err_msg=n)


def _dense(p, bias):
    """Token by token, every held assignment computed: no buffer at all."""
    x = np.asarray(p["x"])
    s = 1.0 / (1.0 + np.exp(-(x @ np.asarray(p["router_w"]).T)))
    idx = np.argsort(-(s + (0 if bias is None else np.asarray(bias))),
                     axis=1, kind="stable")[:, :K]
    out = np.zeros_like(x)
    for t in range(T):
        gates = s[t, idx[t]] / (s[t, idx[t]].sum() + 1e-6)
        for e, g in zip(idx[t], gates):
            if e >= HELD:
                continue
            if p["w3"] is not None:
                a = x[t] @ np.asarray(p["w1"][e])
                h = a / (1.0 + np.exp(-a)) * (x[t] @ np.asarray(p["w3"][e]))
            else:
                h = np.maximum(np.asarray(p["w1"][e]) @ x[t], 0.0) ** 2
            out[t] += g * (h @ np.asarray(p["w2"][e]))
    return out


@pytest.mark.parametrize("trained", [True, False])
@pytest.mark.parametrize("experts", sorted(EXPERTS))
@pytest.mark.parametrize("share", sorted(SHARES))
def test_topk_moe_small_buffer_equals_the_one_buffer(monkeypatch, share,
                                                     experts, trained):
    """Even routing holds about its even share, under the small buffer: the
    branch over ``small_rows`` gives the one-buffer layer's result, loads and
    gradients, and the bufferless sum's result."""
    e = SHARES[share]
    p = _topk_params(e, EXPERTS[experts])
    small = moe.small_buffer_rows(T, K, HELD, e)
    assert small == {16: 64, 24: 48}[e] < moe.buffer_rows(T, K, HELD, e)
    got = _layer_and_grads(p, None, trained)
    assert 0 < float(got[1][:-1].sum()) <= small
    _assert_same(got, _one_buffer(monkeypatch, p, None, trained))
    np.testing.assert_allclose(got[0], _dense(p, None), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("trained", [True, False])
@pytest.mark.parametrize("experts", sorted(EXPERTS))
@pytest.mark.parametrize("share", sorted(SHARES))
def test_topk_moe_past_the_small_buffer_runs_at_the_bound(monkeypatch, share,
                                                          experts, trained):
    """A bias that sends every assignment to the held experts: the branch at
    ``buffer_rows`` runs and is the one-buffer layer.  With a quarter held
    nothing is dropped; with a sixth the assignments past four times the
    even load are, as before (the small buffer would have dropped more)."""
    e = SHARES[share]
    p = _topk_params(e, EXPERTS[experts])
    bias = jnp.where(jnp.arange(e) < HELD, 10.0, 0.0)
    got = _layer_and_grads(p, bias, trained)
    assert float(got[1][:-1].sum()) == T * K > moe.small_buffer_rows(T, K, HELD, e)
    _assert_same(got, _one_buffer(monkeypatch, p, bias, trained))
    dense = _dense(p, bias)
    if share == "quarter":
        np.testing.assert_allclose(got[0], dense, rtol=1e-4, atol=1e-5)
    else:
        assert moe.buffer_rows(T, K, HELD, e) == 88 < T * K
        assert float(np.abs(np.asarray(got[0]) - dense).max()) > 1e-3


@pytest.mark.parametrize("trained", [True, False])
@pytest.mark.parametrize("experts", sorted(EXPERTS))
@pytest.mark.parametrize("over", [0, 1])
def test_topk_moe_at_the_small_buffers_last_row_and_one_past(monkeypatch, over,
                                                             experts, trained):
    """Exactly ``small_rows`` held assignments fill the small buffer to its
    last row and fit; one more goes to the bound's branch.  Either way the
    result is the one-buffer layer's and nothing is dropped: the small buffer
    would have left the one past it out."""
    e = SHARES["quarter"]
    small = moe.small_buffer_rows(T, K, HELD, e)
    p = _held_exactly(_topk_params(e, EXPERTS[experts]), small + over)
    got = _layer_and_grads(p, None, trained)
    assert float(got[1][:-1].sum()) == small + over
    _assert_same(got, _one_buffer(monkeypatch, p, None, trained))
    np.testing.assert_allclose(got[0], _dense(p, None), rtol=1e-4, atol=1e-5)
    # the host's gauge says which branch that step took
    with moe.plan_recording():
        _layer_and_grads(p, None, trained)
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [])
    moe.publish_load({"l": np.asarray(got[1])})
    assert moe.load_samples()[-1][1]["l"]["small_buffer"] == 1.0 - over


@pytest.mark.parametrize("tokens,top_k,held,experts,small", [
    (8192, 4, 8, 32, 16384),     # LFM2's cell: 2 rows a token saved
    (8192, 8, 8, 128, 8192),     # Trinity-Mini's: 1
    (8192, 6, 8, 128, 6144),     # Nemotron's: 0.75
    (8192, 8, 8, 256, None),     # Kimi Linear's: 0.5, and the cond cost more
    (8192, 4, 16, 32, None), (8192, 4, 32, 32, None),   # half and all: nothing
    (64, 2, 4, 32, None)])
def test_small_buffer_rows_only_where_they_save_half_a_row_a_token(
        tokens, top_k, held, experts, small):
    assert moe.small_buffer_rows(tokens, top_k, held, experts) == small
    if small is not None:
        bound = moe.buffer_rows(tokens, top_k, held, experts)
        assert bound - small > moe.SMALL_SAVES_ROWS_A_TOKEN * tokens


@pytest.mark.parametrize("held,conditional", [(4, True), (8, False), (16, False)])
def test_topk_moe_has_two_sizes_only_under_half_the_experts(held, conditional):
    """Half or more of the experts held: twice the even load is every
    assignment there can be, the layer has one size and lowers with no
    conditional; its plan says ``small_rows`` None."""
    p = _topk_params(16, True)
    w = {n: jnp.concatenate([p[n]] * (held // HELD)) for n in ("w1", "w3", "w2")}
    with moe.plan_recording():
        text = jax.jit(lambda x: moe.topk_moe(
            x, p["router_w"], None, w["w1"], w["w3"], w["w2"], K)[0]
        ).lower(p["x"]).as_text()
    assert ("stablehlo.case" in text or "stablehlo.if" in text) == conditional
    plan = moe.last_plan_summary()["layers"][0]
    assert plan["buffer_rows"] == T * K
    assert plan["small_rows"] == (64 if conditional else None)
    assert moe.small_buffer_rows(T, K, held, 16) == plan["small_rows"]


def test_publish_load_says_small_buffer_only_where_the_plan_has_one(monkeypatch):
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [])
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {"layers": [
        {"small_rows": 10}, {"small_rows": None}, {"small_rows": 10}]})
    loads = {"a": np.array([4.0, 6.0, 1.0]), "b": np.array([9.0, 9.0, 0.0]),
             "c": np.array([5.0, 6.0, 0.0])}
    moe.publish_load(loads)
    sample = moe.load_samples()[-1][1]
    assert sample["a"]["small_buffer"] == 1.0 and sample["c"]["small_buffer"] == 0.0
    assert "small_buffer" not in sample["b"]
    from mxnet_tpu import telemetry
    flat = telemetry.REGISTRY.flat()
    assert any(k.startswith("mxtpu_moe_small_buffer") for k in flat)
    # a plan of another step's layers says nothing about these
    monkeypatch.setattr(moe, "last_plan_summary", lambda: {"layers": [{"small_rows": 10}]})
    moe.publish_load(loads)
    assert all("small_buffer" not in v for v in moe.load_samples()[-1][1].values())


@pytest.mark.parametrize("products,two_sizes,layers", [
    (72, True, 4), (71, True, 3), (36, True, 2), (36, False, 4), (144, True, 4)])
def test_grouped_layers_count_both_branches_products(products, two_sizes, layers):
    """A layer with two sizes compiled both branches' grouped products: it is
    covered when the text holds twice its ``products_trained``."""
    with moe.plan_recording():
        for _ in range(4):
            moe.note_layer(buffer_rows=32768, products_trained=9,
                           small_rows=16384 if two_sizes else None)

    class Compiled:
        def as_text(self):
            return "\n".join(
                "  %%ragged-dot-none.%d = bf16[16384,1792]{1,0} custom-call(%%a, "
                "%%b), custom_call_target=\"tpu_custom_call\"" % i
                for i in range(products))

    moe.note_compiled(Compiled())
    plan = moe.last_plan_summary()
    assert (plan["grouped_products"], plan["grouped_layers"]) == (products, layers)


# --------------------------------------------- softmax scores (PR 40)
def _softmax_dense(p, k, offset, held, trained):
    """Every expert of the share on every token, the gates scattered over the
    router's width: softmax over all of it, the ``k`` largest, renormalised
    over the chosen (+1e-6, the layer's)."""
    s = jax.nn.softmax(p["x"] @ p["router_w"].T, axis=-1)
    if not trained:
        s = jax.lax.stop_gradient(s)
    top, idx = jax.lax.top_k(s, k)
    gates = top / (jnp.sum(top, axis=1, keepdims=True) + 1e-6)
    e = p["router_w"].shape[0]
    scattered = jnp.sum(jax.nn.one_hot(idx, e) * gates[:, :, None], axis=1)
    y = jnp.zeros_like(p["x"])
    for j in range(held):
        h = jax.nn.silu(p["x"] @ p["w1"][j]) * (p["x"] @ p["w3"][j])
        y = y + scattered[:, offset + j, None] * (h @ p["w2"][j])
    return y


@pytest.mark.parametrize("trained", [True, False])
@pytest.mark.parametrize("offset,held", [(0, 16), (0, 4), (8, 4)])
def test_topk_moe_softmax_scores_match_a_dense_reference(offset, held, trained):
    rng = np.random.RandomState(5)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)   # noqa: E731
    e, k = 16, 4
    p = {"x": f(T, D), "router_w": f(e, D), "w1": f(held, D, FF),
         "w3": f(held, D, FF), "w2": f(held, FF, D)}
    mix = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def layer(p):
        return moe.topk_moe(p["x"], p["router_w"], None, p["w1"], p["w3"],
                            p["w2"], k, expert_offset=offset,
                            router_trained=trained, score_func="softmax")[0]

    np.testing.assert_allclose(layer(p), _softmax_dense(p, k, offset, held,
                                                        trained),
                               rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda p: jnp.sum(layer(p) * mix))(p)
    want = jax.grad(lambda p: jnp.sum(
        _softmax_dense(p, k, offset, held, trained) * mix))(p)
    for n in p:
        np.testing.assert_allclose(got[n], want[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)
    assert (float(jnp.abs(got["router_w"]).max()) > 0) == trained
    # the scores are the softmax's: they differ from the sigmoid layer's
    sig = moe.topk_moe(p["x"], p["router_w"], None, p["w1"], p["w3"], p["w2"],
                       k, expert_offset=offset, router_trained=trained)[0]
    assert float(jnp.abs(sig - layer(p)).max()) > 1e-3
    with pytest.raises(ValueError, match="neither sigmoid nor softmax"):
        moe.topk_moe(p["x"], p["router_w"], None, p["w1"], p["w3"], p["w2"],
                     k, score_func="tanh")


def test_the_eight_softmax_shares_add_up_to_the_uncut_layer():
    """The share tied to the model (SDAR's cut: one of 8 chips): 8 shares of
    2 of 16 experts, summed, give the uncut layer's result and its gradient
    for the input."""
    rng = np.random.RandomState(9)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)   # noqa: E731
    e, k, each = 16, 4, 2
    x, rw = f(T, D), f(e, D)
    w1, w3, w2 = f(e, D, FF), f(e, D, FF), f(e, FF, D)
    mix = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def share(x, first, n):
        part = slice(first, first + n)
        return moe.topk_moe(x, rw, None, w1[part], w3[part], w2[part], k,
                            expert_offset=first, score_func="softmax")[0]

    whole, whole_grad = jax.value_and_grad(
        lambda x: jnp.sum(share(x, 0, e) * mix))(x)
    parts = [jax.value_and_grad(lambda x, i=i: jnp.sum(
        share(x, i * each, each) * mix))(x) for i in range(e // each)]
    np.testing.assert_allclose(sum(v for v, _g in parts), whole, rtol=1e-5)
    np.testing.assert_allclose(sum(g for _v, g in parts), whole_grad,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        sum(share(x, i * each, each) for i in range(e // each)),
        share(x, 0, e), rtol=1e-5, atol=1e-6)


#: ``(held, experts, gated, trained)`` -> (sha256 of the gradient's jaxpr,
#: its length, sums of |gradient| by argument and the load) of the sigmoid
#: layer at PR 40's parent (a3ca9a0), on the fixed input of ``_pinned``
SIGMOID_AT_PARENT = {
    (4, 16, True, False): ("d567d47f71c55fbe", 55527, [
        280.5479, 0.0, 304.4913, 338.0938, 12.0, 11.0, 6.0, 6.0, 36.0]),
    (8, 16, True, True): ("7debaba37becd620", 16875, [
        400.1437, 17.9823, 488.0611, 520.0387, 12.0, 11.0, 6.0, 6.0, 3.0, 5.0,
        7.0, 9.0, 20.0]),
    (4, 24, False, False): ("07f505dcd927c114", 56561, [
        188.351, 0.0, 296.265, 290.355, 6.0, 0.0, 1.0, 7.0, 50.0])}


def _pinned(held, experts, gated, trained, **kw):
    import hashlib
    import re
    rng = np.random.RandomState(11)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)   # noqa: E731
    x, rw, b = f(T, D), f(experts, D), f(experts) * 0.1
    w1 = f(held, D, FF) if gated else f(held, FF, D)
    w3 = f(held, D, FF) if gated else None
    w2 = f(held, FF, D)
    mix = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def fn(x, rw, w1, w2):
        y, load = moe.topk_moe(x, rw, b, w1, w3, w2, K,
                               router_trained=trained, **kw)
        return jnp.sum(y * mix), load

    grad = jax.grad(lambda *a: fn(*a)[0], (0, 1, 2, 3))
    text = re.sub(r"0x[0-9a-f]+", "0x",
                  str(jax.make_jaxpr(grad)(x, rw, w1, w2)))
    values = [float(jnp.sum(jnp.abs(o))) for o in grad(x, rw, w1, w2)] \
        + [float(v) for v in fn(x, rw, w1, w2)[1]]
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text), values


@pytest.mark.parametrize("case", sorted(SIGMOID_AT_PARENT), ids=str)
def test_sigmoid_layer_lowers_and_computes_as_its_parent_did(case):
    """``score_func`` left the sigmoid path alone: the jaxpr of the layer's
    gradient and its numbers are the parent commit's, whether the argument is
    left out or says ``"sigmoid"``."""
    sha, length, values = SIGMOID_AT_PARENT[case]
    for kw in ({}, {"score_func": "sigmoid"}):
        got = _pinned(*case, **kw)
        assert got[:2] == (sha, length)
        np.testing.assert_allclose(got[2], values, rtol=2e-5, atol=1e-4)
    assert _pinned(*case, score_func="softmax")[0] != sha
