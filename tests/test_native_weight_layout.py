"""Physical HWIO master storage (round 5, ShardedTrainer
``native_weight_layout``).

Conv weight masters stored HWIO so the canonical layout IS the conv-
preferred one (jit's Layout.AUTO cannot reach lax.scan loop carries —
docs/perf.md).  The graph and all checkpoints still see reference
OIHW, so the feature must be invisible: bit-identical training, the
same checkpoint bytes, and interop in both directions.
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.parallel import ShardedTrainer, build_mesh


def _net():
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           no_bias=True, name="c1")
    b = mx.sym.BatchNorm(c, name="bn1")
    a = mx.sym.Activation(b, act_type="relu")
    c2 = mx.sym.Convolution(a, num_filter=16, kernel=(1, 1),
                            no_bias=True, name="c2")
    p = mx.sym.Pooling(c2, global_pool=True, pool_type="avg",
                       kernel=(1, 1))
    fc = mx.sym.FullyConnected(mx.sym.Flatten(p), num_hidden=5,
                               name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _trainer(native, **kw):
    return ShardedTrainer(
        _net(), build_mesh(tp=1),
        data_shapes={"data": (8, 3, 16, 16)},
        label_shapes={"softmax_label": (8,)},
        learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
        dtype="float32", layout="NHWC", seed=0,
        native_weight_layout=native, **kw)


def _batch():
    rng = np.random.RandomState(0)
    return {"data": rng.uniform(-1, 1, (8, 3, 16, 16)).astype("f"),
            "softmax_label": rng.randint(0, 5, 8).astype("f")}


def test_native_layout_trains_identically(tmp_path):
    batch = _batch()
    losses, params = {}, {}
    for native in (False, True):
        tr = _trainer(native)
        if native:
            assert tr._native_w == {"c1_weight", "c2_weight"}, tr._native_w
            assert tr.params["c1_weight"].shape == (3, 3, 3, 8)
        else:
            assert tr._native_w == frozenset()
        ls = [float(tr.step(tr.put_batch(batch))) for _ in range(4)]
        # the run_steps scan path shares the storage layout
        ls += [float(v) for v in
               np.asarray(tr.run_steps(tr.put_batch(batch), 3))]
        losses[native] = ls
        pre = str(tmp_path / ("ck%d" % native))
        tr.save_checkpoint(pre, 0, save_optimizer_states=True)
        params[native] = {k: np.asarray(v.asnumpy()) for k, v in
                          mx.nd.load(pre + "-0000.params").items()}
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5)
    # checkpoints are reference OIHW from either storage
    assert params[True]["arg:c1_weight"].shape == (8, 3, 3, 3)
    for k in params[False]:
        np.testing.assert_allclose(params[False][k], params[True][k],
                                   rtol=2e-5, atol=1e-6, err_msg=k)


def test_native_layout_checkpoint_interop(tmp_path):
    """native=True resumes a native=False checkpoint and vice versa."""
    batch = _batch()
    t0 = _trainer(False)
    float(t0.step(t0.put_batch(batch)))
    pre = str(tmp_path / "x")
    t0.save_checkpoint(pre, 0, save_optimizer_states=True)
    ref_loss = float(t0.step(t0.put_batch(batch)))

    t1 = _trainer(True)
    t1.load_checkpoint(pre, 0, load_optimizer_states=True)
    got = float(t1.step(t1.put_batch(batch)))
    np.testing.assert_allclose(got, ref_loss, rtol=1e-5)

    pre2 = str(tmp_path / "y")
    t1.save_checkpoint(pre2, 0)
    t2 = _trainer(False)
    t2.load_checkpoint(pre2, 0)
    for k in t0.params:
        a = np.asarray(t2.params[k])
        b = np.asarray(t1.params[k])
        if k in t1._native_w:
            b = b.transpose(3, 2, 0, 1)
        np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=k)


def test_native_layout_shared_weight_excluded():
    """A weight consumed by anything besides Convolution keeps the
    reference layout (shared/tied weights)."""
    d = mx.sym.Variable("data")
    w = mx.sym.Variable("shared_weight")
    c = mx.sym.Convolution(d, weight=w, num_filter=4, kernel=(3, 3),
                           pad=(1, 1), no_bias=True, name="c1")
    # the same w also feeds an elementwise op -> not conv-only
    reg = mx.sym.sum(w * w)
    out = mx.sym.Pooling(c, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    out = mx.sym.FullyConnected(mx.sym.Flatten(out), num_hidden=3,
                                name="fc")
    net = mx.sym.SoftmaxOutput(out + 0.0 * mx.sym.reshape(reg, shape=(1,)),
                               name="softmax")
    tr = ShardedTrainer(
        net, build_mesh(tp=1),
        data_shapes={"data": (4, 2, 8, 8)},
        label_shapes={"softmax_label": (4,)},
        learning_rate=0.05, momentum=0.9, dtype="float32",
        layout="NHWC", native_weight_layout=True)
    assert "shared_weight" not in tr._native_w
