"""Pipeline parallelism on real models through the framework surface:
ShardedTrainer(pipeline_stages=N) — graph cutting, packed-stage GPipe
schedule, dp x pp composition — checked for gradient/training parity
against the plain single-mesh trainer on the virtual CPU mesh.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import ShardedTrainer, build_mesh
from mxnet_tpu.parallel.pipeline import plan_pipeline_stages


def _mlp_tower(depth=4, hidden=32, num_classes=8):
    """A stacked tower: one legal cut between every pair of blocks."""
    net = mx.sym.Variable("data")
    for i in range(depth):
        net = mx.sym.FullyConnected(net, num_hidden=hidden,
                                    name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="relu", name="relu%d" % i)
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="out")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _tiny_transformer(seq=8, d=16, heads=2, layers=2, vocab=16):
    """Embedding -> pre-LN transformer blocks -> head; aux-free and
    dropout-free, so it is pipeline-eligible (GPT-mini shape)."""
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
        att = mx.sym.batch_dot(q, k, transpose_b=True)
        att = mx.sym.softmax(att * (1.0 / np.sqrt(d)), axis=-1)
        ctxv = mx.sym.batch_dot(att, v)
        proj = mx.sym.FullyConnected(ctxv, num_hidden=d, flatten=False,
                                     name=pre + "proj")
        net = net + proj
        ln2 = mx.sym.LayerNorm(net, name=pre + "ln2")
        ff = mx.sym.FullyConnected(ln2, num_hidden=4 * d, flatten=False,
                                   name=pre + "ff1")
        ff = mx.sym.Activation(ff, act_type="relu")
        ff = mx.sym.FullyConnected(ff, num_hidden=d, flatten=False,
                                   name=pre + "ff2")
        net = net + ff
    net = mx.sym.LayerNorm(net, name="ln_f")
    net = mx.sym.Reshape(net, shape=(-1, d))
    net = mx.sym.FullyConnected(net, num_hidden=vocab, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


# ------------------------------------------------------------ planning
def test_plan_cuts_tower_balanced():
    sym = _mlp_tower(depth=4)
    stages = plan_pipeline_stages(sym._topo(), sym._entries, {"data",
                                  "softmax_label"}, 2)
    assert len(stages) == 2
    # every param assigned to exactly one stage, none lost
    all_params = [p for s in stages for p in s["param_names"]]
    assert sorted(all_params) == sorted(set(all_params))
    assert any("fc0" in p for p in stages[0]["param_names"])
    assert any("out" in p for p in stages[1]["param_names"])
    # the label rides to the loss-head stage
    assert "softmax_label" in stages[1]["batch_names"]
    assert stages[1]["boundary_in"] is not None


def test_plan_rejects_batchnorm_aux():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    net = mx.sym.BatchNorm(net, name="bn")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    with pytest.raises(mx.base.MXNetError, match="auxiliary state"):
        plan_pipeline_stages(net._topo(), net._entries,
                             {"data", "softmax_label"}, 2)


def test_plan_rejects_dropout():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    net = mx.sym.Dropout(net, p=0.5)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    with pytest.raises(mx.base.MXNetError, match="stochastic"):
        plan_pipeline_stages(net._topo(), net._entries,
                             {"data", "softmax_label"}, 2)


# ------------------------------------------------- training parity
def _batch(bsz, feat, classes, seed=0):
    rng = np.random.RandomState(seed)
    return {"data": rng.uniform(-1, 1, (bsz, feat)).astype("f"),
            "softmax_label": rng.randint(0, classes, bsz).astype("f")}


def _tok_batch(bsz, seq, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return {"data": rng.randint(0, vocab, (bsz, seq)).astype("f"),
            "softmax_label":
                rng.randint(0, vocab, (bsz * seq,)).astype("f")}


@pytest.mark.parametrize("pp,dp,micro", [(2, 1, 2), (4, 2, 4)])
def test_pipeline_trainer_matches_plain(pp, dp, micro):
    """dp x pp pipelined training == plain single-mesh training, step
    for step (loss and all parameters)."""
    sym_a, sym_b = _mlp_tower(), _mlp_tower()
    bsz = 16

    plain = ShardedTrainer(
        sym_a, build_mesh(n_devices=1, tp=1),
        data_shapes={"data": (bsz, 12)},
        label_shapes={"softmax_label": (bsz,)},
        learning_rate=0.1, momentum=0.9, seed=7)
    piped = ShardedTrainer(
        sym_b, build_mesh(n_devices=dp * pp, pp=pp),
        data_shapes={"data": (bsz, 12)},
        label_shapes={"softmax_label": (bsz,)},
        learning_rate=0.1, momentum=0.9, seed=7,
        pipeline_stages=pp, pipeline_microbatches=micro)

    for i in range(3):
        b = _batch(bsz, 12, 8, seed=i)
        la = float(plain.step(b))
        lb = float(piped.step(b))
        assert np.isclose(la, lb, rtol=1e-4), (i, la, lb)
    for name in plain.params:
        np.testing.assert_allclose(
            np.asarray(plain.params[name]), np.asarray(piped.params[name]),
            rtol=2e-4, atol=2e-5, err_msg=name)


def test_pipeline_transformer_trains():
    """GPT-shaped model through dp x pp: loss decreases on a learnable
    pattern and forward() (inference, non-pipelined) agrees with the
    trained params."""
    seq, vocab = 8, 16
    bsz = 16
    sym = _tiny_transformer(seq=seq, vocab=vocab)
    tr = ShardedTrainer(
        sym, build_mesh(n_devices=8, pp=4),
        data_shapes={"data": (bsz, seq)},
        label_shapes={"softmax_label": (bsz * seq,)},
        optimizer="adam", learning_rate=0.01, seed=11,
        pipeline_stages=4, pipeline_microbatches=4)

    # learnable task: predict the input token (identity LM)
    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, (bsz, seq)).astype("f")
    batch = {"data": x, "softmax_label": x.reshape(-1).copy()}
    losses = [float(tr.step(batch)) for _ in range(80)]
    assert losses[-1] < losses[0] * 0.1, losses[::10]

    probs = np.asarray(tr.forward({"data": x})[0])
    acc = (probs.argmax(1) == x.reshape(-1)).mean()
    assert acc > 0.9, acc


def test_pipeline_transformer_matches_plain():
    """Transformer gradients through the pipeline match the plain path."""
    seq, vocab, bsz = 8, 16, 8
    plain = ShardedTrainer(
        _tiny_transformer(seq=seq, vocab=vocab),
        build_mesh(n_devices=1, tp=1),
        data_shapes={"data": (bsz, seq)},
        label_shapes={"softmax_label": (bsz * seq,)},
        learning_rate=0.2, momentum=0.9, seed=4)
    piped = ShardedTrainer(
        _tiny_transformer(seq=seq, vocab=vocab),
        build_mesh(n_devices=2, pp=2),
        data_shapes={"data": (bsz, seq)},
        label_shapes={"softmax_label": (bsz * seq,)},
        learning_rate=0.2, momentum=0.9, seed=4,
        pipeline_stages=2, pipeline_microbatches=2)
    for i in range(2):
        b = _tok_batch(bsz, seq, vocab, seed=i)
        la, lb = float(plain.step(b)), float(piped.step(b))
        assert np.isclose(la, lb, rtol=1e-4)
    for name in plain.params:
        np.testing.assert_allclose(
            np.asarray(plain.params[name]),
            np.asarray(piped.params[name]),
            rtol=3e-4, atol=3e-5, err_msg=name)


def test_pipeline_requires_pipe_axis():
    with pytest.raises(mx.base.MXNetError, match="pipe"):
        ShardedTrainer(
            _mlp_tower(), build_mesh(n_devices=2, tp=1),
            data_shapes={"data": (8, 12)},
            label_shapes={"softmax_label": (8,)},
            pipeline_stages=2)


def test_pipeline_checkpoint_roundtrip(tmp_path):
    """Pipelined trainer checkpoints stay Module-format (per-name f32
    masters, independent of the packed stage encoding)."""
    sym = _mlp_tower()
    tr = ShardedTrainer(
        sym, build_mesh(n_devices=2, pp=2),
        data_shapes={"data": (8, 12)},
        label_shapes={"softmax_label": (8,)},
        learning_rate=0.1, momentum=0.9, seed=3,
        pipeline_stages=2, pipeline_microbatches=2)
    tr.step(_batch(8, 12, 8))
    prefix = str(tmp_path / "pp")
    tr.save_checkpoint(prefix, 1)
    sym2, arg_p, aux_p = mx.model.load_checkpoint(prefix, 1)
    assert sorted(arg_p) == sorted(tr.params)


def test_pipeline_run_steps_matches_step_loop():
    """run_steps (scan chaining) composes with the pipelined step."""
    sym_a, sym_b = _mlp_tower(), _mlp_tower()
    bsz = 16

    def make(sym):
        return ShardedTrainer(
            sym, build_mesh(n_devices=4, pp=2),
            data_shapes={"data": (bsz, 12)},
            label_shapes={"softmax_label": (bsz,)},
            learning_rate=0.1, momentum=0.9, seed=7,
            pipeline_stages=2, pipeline_microbatches=2)

    a, b = make(sym_a), make(sym_b)
    batch = _batch(bsz, 12, 8, seed=0)
    losses_a = [float(a.step(batch)) for _ in range(3)]
    losses_b = np.asarray(b.run_steps(batch, 3))
    np.testing.assert_allclose(losses_b, losses_a, rtol=1e-5)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=1e-5, atol=1e-6, err_msg=name)
