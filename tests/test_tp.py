"""Tensor parallelism breadth: graph-derived sharding rules (Megatron
column/row FC pairing, conv output channels) and tp=2/4 training parity
on transformer and conv nets over the virtual CPU mesh.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import ShardedTrainer, build_mesh
from mxnet_tpu.parallel.tp_rules import derive_tp_rules


def _transformer(seq=8, d=16, layers=2, vocab=16):
    net = mx.sym.Variable("data")
    net = mx.sym.Embedding(net, input_dim=vocab, output_dim=d,
                           name="embed")
    for i in range(layers):
        pre = "l%d_" % i
        ln1 = mx.sym.LayerNorm(net, name=pre + "ln1")
        qkv = mx.sym.FullyConnected(ln1, num_hidden=3 * d, flatten=False,
                                    name=pre + "qkv")
        q = mx.sym.slice_axis(qkv, axis=2, begin=0, end=d)
        k = mx.sym.slice_axis(qkv, axis=2, begin=d, end=2 * d)
        v = mx.sym.slice_axis(qkv, axis=2, begin=2 * d, end=3 * d)
        att = mx.sym.softmax(mx.sym.batch_dot(q, k, transpose_b=True)
                             * (1.0 / np.sqrt(d)), axis=-1)
        proj = mx.sym.FullyConnected(mx.sym.batch_dot(att, v),
                                     num_hidden=d, flatten=False,
                                     name=pre + "proj")
        net = net + proj
        ff = mx.sym.FullyConnected(
            mx.sym.Activation(mx.sym.FullyConnected(
                mx.sym.LayerNorm(net, name=pre + "ln2"),
                num_hidden=4 * d, flatten=False, name=pre + "ff1"),
                act_type="relu"),
            num_hidden=d, flatten=False, name=pre + "ff2")
        net = net + ff
    net = mx.sym.LayerNorm(net, name="ln_f")
    net = mx.sym.Reshape(net, shape=(-1, d))
    net = mx.sym.FullyConnected(net, num_hidden=vocab, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _arg_shapes(sym, **shapes):
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return dict(zip(sym.list_arguments(), arg_shapes))


def test_derive_rules_transformer_megatron_pairing():
    sym = _transformer()
    shapes = _arg_shapes(sym, data=(4, 8), softmax_label=(32,))
    rules = derive_tp_rules(sym._topo(), shapes, tp_size=2)
    # QKV and ff1 column-parallel (+ biases); out-proj and ff2
    # row-parallel (bias replicated — it adds after the psum)
    assert rules["l0_qkv_weight"] == 0 and rules["l0_qkv_bias"] == 0
    assert rules["l0_proj_weight"] == 1
    assert "l0_proj_bias" not in rules
    assert rules["l0_ff1_weight"] == 0
    assert rules["l0_ff2_weight"] == 1
    # the head follows a (replicated) LayerNorm: column-parallel
    assert rules["head_weight"] == 0
    # embedding is not an FC/conv: untouched
    assert "embed_weight" not in rules
    # at tp=4, ff2's output dim (16) is too small to column-shard but
    # its input dim (64) still row-shards — the pairing must not depend
    # on the partner's own output being shardable
    rules4 = derive_tp_rules(sym._topo(), shapes, tp_size=4)
    assert rules4["l0_ff2_weight"] == 1
    assert rules4["l0_ff1_weight"] == 0


def test_derive_rules_conv_channels():
    from mxnet_tpu import models
    net = models.get_model("resnet18", num_classes=10,
                           image_shape="3,32,32")
    shapes = _arg_shapes(net, data=(4, 3, 32, 32), softmax_label=(4,))
    rules = derive_tp_rules(net._topo(), shapes, tp_size=2)
    conv_rules = {k: v for k, v in rules.items() if "conv" in k}
    assert conv_rules and all(v == 0 for v in conv_rules.values())
    # dims not divisible / too small stay unsharded
    rules8 = derive_tp_rules(net._topo(), shapes, tp_size=256)
    assert not rules8


def test_derive_rules_gating_diamonds_linear_time():
    """Chained self-gating diamonds (swish/highway style) must not
    blow up the reachability walk (memoized, not exponential)."""
    import time
    net = mx.sym.Variable("data")
    for _ in range(30):
        net = net * mx.sym.Activation(net, act_type="sigmoid")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = _arg_shapes(net, data=(4, 32), softmax_label=(4,))
    t0 = time.time()
    rules = derive_tp_rules(net._topo(), shapes, 2)
    assert time.time() - t0 < 5
    assert rules.get("fc_weight") == 0


def _tok_batch(bsz, seq, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return {"data": rng.randint(0, vocab, (bsz, seq)).astype("f"),
            "softmax_label":
                rng.randint(0, vocab, (bsz * seq,)).astype("f")}


@pytest.mark.parametrize("tp", [2, 4])
def test_transformer_tp_parity(tp):
    """tp=2/4 transformer training matches tp=1 step for step."""
    bsz, seq, vocab = 8, 8, 16

    def make(tp_):
        # sgd for the parity check: the K-projection bias gradient is
        # mathematically zero (softmax is shift-invariant per query), so
        # adam would amplify tp-reduction-order noise on it into
        # arbitrary-sign updates
        return ShardedTrainer(
            _transformer(seq=seq, vocab=vocab),
            build_mesh(n_devices=max(tp_, 1), tp=tp_),
            data_shapes={"data": (bsz, seq)},
            label_shapes={"softmax_label": (bsz * seq,)},
            learning_rate=0.02, momentum=0.9, seed=3)

    a, b = make(1), make(tp)
    assert b.tp_rules  # the auto rules actually fired
    for i in range(2):
        batch = _tok_batch(bsz, seq, vocab, seed=i)
        la, lb = float(a.step(batch)), float(b.step(batch))
        assert np.isclose(la, lb, rtol=1e-4), (la, lb)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=3e-4, atol=3e-5, err_msg=name)


def test_resnet_tp_parity():
    """Conv-channel tensor parallelism on ResNet-18: tp=2 == tp=1."""
    from mxnet_tpu import models

    def make(tp_):
        net = models.get_model("resnet18", num_classes=10,
                               image_shape="3,32,32")
        return ShardedTrainer(
            net, build_mesh(n_devices=tp_ * 2, tp=tp_),
            data_shapes={"data": (8, 3, 32, 32)},
            label_shapes={"softmax_label": (8,)},
            learning_rate=0.1, momentum=0.9, seed=5, layout="NHWC")

    a, b = make(1), make(2)
    assert any("conv" in k for k in b.tp_rules)
    rng = np.random.RandomState(0)
    batch = {"data": rng.uniform(-1, 1, (8, 3, 32, 32)).astype("f"),
             "softmax_label": rng.randint(0, 10, 8).astype("f")}
    # single step: BN-statistics rsqrt backward amplifies f32
    # reduction-order noise under channel sharding, compounding per step
    la, lb = float(a.step(batch)), float(b.step(batch))
    assert np.isclose(la, lb, rtol=5e-4)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=5e-4, atol=2e-4, err_msg=name)


def test_dp_tp_composition():
    """dp=2 x tp=4 on the transformer: auto rules + batch sharding."""
    bsz, seq, vocab = 16, 8, 16
    tr = ShardedTrainer(
        _transformer(seq=seq, vocab=vocab),
        build_mesh(n_devices=8, tp=4),
        data_shapes={"data": (bsz, seq)},
        label_shapes={"softmax_label": (bsz * seq,)},
        optimizer="adam", learning_rate=0.01, seed=3)
    losses = [float(tr.step(_tok_batch(bsz, seq, vocab, seed=i)))
              for i in range(3)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
