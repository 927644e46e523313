"""Round-3 performance paths: scan-chained multi-step (`run_steps`) and
the input-BN conv backward-data elision (ops/fused.py), both checked for
exact parity against the plain step on the CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import fused
from mxnet_tpu.parallel import ShardedTrainer, build_mesh


# ------------------------------------------------- dx-sum elision math
@pytest.mark.parametrize("cfg", [
    # (H, W, Cin, Cout, kernel, stride, pad_pairs)
    (14, 14, 5, 8, (7, 7), (2, 2), ((3, 3), (3, 3))),
    (12, 12, 12, 16, (4, 4), (1, 1), ((2, 1), (2, 1))),  # s2d stem form
    (9, 9, 4, 6, (3, 3), (1, 1), ((1, 1), (1, 1))),
    (8, 8, 3, 4, (1, 1), (1, 1), ((0, 0), (0, 0))),
    (11, 7, 3, 4, (5, 3), (3, 2), ((2, 2), (0, 0))),
])
def test_elided_conv_channel_sums_exact(cfg):
    """The fake dX's per-channel sums equal the real backward-data's."""
    h, w, cin, cout, kernel, stride, pads = cfg
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, h, w, cin).astype(np.float32))
    wt = jnp.asarray(
        rng.randn(kernel[0], kernel[1], cin, cout).astype(np.float32))

    def conv(xx, ww):
        dn = jax.lax.conv_dimension_numbers(xx.shape, ww.shape,
                                            ("NHWC", "HWIO", "NHWC"))
        return jax.lax.conv_general_dilated(
            xx, ww, window_strides=stride, padding=pads,
            dimension_numbers=dn)

    y, vjp = jax.vjp(conv, x, wt)
    dy = jnp.asarray(rng.randn(*y.shape).astype(np.float32))
    dx_true, dw_true = vjp(dy)

    f = fused._elided_conv(tuple(stride), tuple(pads), (1, 1))
    y2, vjp2 = jax.vjp(f, x, wt)
    dx_fake, dw_fake = vjp2(dy)

    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dw_fake), np.asarray(dw_true),
                               rtol=1e-5, atol=1e-5)
    # per-channel sums of dX are preserved exactly (the only live use)
    np.testing.assert_allclose(
        np.asarray(jnp.sum(dx_fake, axis=(0, 1, 2))),
        np.asarray(jnp.sum(dx_true, axis=(0, 1, 2))),
        rtol=1e-4, atol=1e-4)


def _stem_net(num_classes=10):
    """Reference-ResNet-shaped entry: data -> BN(fix_gamma) -> conv."""
    data = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(data, fix_gamma=True, name="bn_data")
    net = mx.sym.Convolution(net, kernel=(7, 7), stride=(2, 2),
                             pad=(3, 3), num_filter=8, no_bias=True,
                             name="conv0")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_elide_plan_detects_stem():
    sym = _stem_net()
    plan = fused.plan_input_bn_elide(sym._topo(), sym._entries, {"data"})
    assert len(plan) == 1


def test_elide_plan_respects_fix_gamma_and_names():
    data = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(data, fix_gamma=False, name="bn_data")
    net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1), num_filter=4,
                             no_bias=True, name="conv0")
    topo, entries = net._topo(), net._entries
    # trainable gamma needs sum(dy * xhat): elision would be wrong
    assert not fused.plan_input_bn_elide(topo, entries, {"data"})
    sym = _stem_net()
    # a BN over a non-declared variable (e.g. a weight) is not elided
    assert not fused.plan_input_bn_elide(sym._topo(), sym._entries,
                                         {"other"})


def _trainer(elide, stem_s2d=False, **kw):
    mesh = build_mesh(tp=1)
    return ShardedTrainer(
        _stem_net(), mesh,
        data_shapes={"data": (8, 3, 16, 16)},
        label_shapes={"softmax_label": (8,)},
        layout="NHWC", seed=5, learning_rate=0.1, momentum=0.9,
        elide_input_bn_grad=elide, stem_space_to_depth=stem_s2d, **kw)


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"data": rng.uniform(-1, 1, (8, 3, 16, 16)).astype(np.float32),
            "softmax_label": rng.randint(0, 10, 8).astype(np.float32)}


@pytest.mark.parametrize("stem_s2d", [False, True])
def test_elide_trainer_parity(stem_s2d):
    """Training with the elision matches the plain path (all params,
    including the input BN's beta, which is the one grad the elided
    backward-data pass was feeding)."""
    a = _trainer(elide=False, stem_s2d=stem_s2d)
    b = _trainer(elide=True, stem_s2d=stem_s2d)
    for i in range(3):
        la = float(a.step(_batch(i)))
        lb = float(b.step(_batch(i)))
        assert np.isclose(la, lb, rtol=1e-4)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=2e-4, atol=2e-5, err_msg=name)
    # the elided grad actually flowed: beta moved from its zero init
    assert np.abs(np.asarray(b.params["bn_data_beta"])).max() > 0


def test_plans_fire_on_real_resnet_v2_stem():
    """The zoo resnet v2 stem is data -> identity -> bn_data -> conv0;
    both the s2d rewrite and the dX elision must see through the
    pass-through chain (round-2's stem plan silently matched nothing)."""
    from mxnet_tpu import models
    net = models.get_model("resnet18", num_classes=10,
                           image_shape="3,32,32")
    topo, entries = net._topo(), net._entries
    elide = fused.plan_input_bn_elide(topo, entries, {"data"})
    assert len(elide) == 1  # conv0 only
    net224 = models.get_model("resnet18", num_classes=10,
                              image_shape="3,224,224")
    assert len(fused.plan_stem_s2d(net224._topo())) == 1


# ----------------------------------------------------- run_steps (scan)
def test_run_steps_matches_step_loop():
    a = _trainer(elide=False)
    b = _trainer(elide=False)
    batch = _batch(0)
    losses_a = [float(a.step(batch)) for _ in range(4)]
    losses_b = np.asarray(b.run_steps(batch, 4))
    np.testing.assert_allclose(losses_b, losses_a, rtol=1e-5)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=1e-5, atol=1e-6, err_msg=name)
    # bookkeeping advanced identically
    assert a.optimizer.num_update == b.optimizer.num_update


def test_run_steps_lr_schedule_advances_per_inner_step():
    from mxnet_tpu.lr_scheduler import FactorScheduler
    a = _trainer(elide=False,
                 optimizer_params={"lr_scheduler":
                                   FactorScheduler(step=2, factor=0.5)})
    b = _trainer(elide=False,
                 optimizer_params={"lr_scheduler":
                                   FactorScheduler(step=2, factor=0.5)})
    batch = _batch(0)
    for _ in range(4):
        a.step(batch)
    b.run_steps(batch, 4)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=1e-5, atol=1e-6, err_msg=name)


# ------------------------------------------- one lowering of a conv
@pytest.mark.parametrize("kernel,stride,pad", [
    ((1, 1), (1, 1), (0, 0)),    # pointwise: no dot in its place
    ((3, 3), (2, 2), (1, 1)),    # stride 2: no phase-split backward
], ids=["1x1", "3x3s2"])
def test_nhwc_convolution_is_one_conv_call(kernel, stride, pad):
    """Under NHWC the Convolution op is one ``conv_general_dilated``
    forward and XLA's own two transposes of it backward, whatever the
    kernel and stride: no rewrite stands between the op and the call."""
    from mxnet_tpu.ops.nn import image_layout
    from mxnet_tpu.ops.registry import apply_op, get_op, OpContext
    op = get_op("Convolution")
    attrs = op.parse_attrs({"kernel": kernel, "stride": stride, "pad": pad,
                            "num_filter": 16, "no_bias": True})

    def loss(x, w):
        with image_layout("NHWC"):
            (y,) = apply_op(op, attrs, OpContext(is_train=True), x, w)
        return jnp.sum(y * y)

    x = jax.ShapeDtypeStruct((2, 8, 8, 4), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 4) + kernel, jnp.float32)
    fwd = str(jax.make_jaxpr(loss)(x, w))
    both = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w))
    assert fwd.count("conv_general_dilated") == 1
    assert both.count("conv_general_dilated") == 3
    assert "dot_general" not in both and "custom_vjp" not in both


# ------------------------------------------- space-to-depth stem conv
def test_stem_space_to_depth_matches():
    """The 4x4/s1 space-to-depth rewrite of the 7x7/s2 stem trains
    identically to the direct conv (f32); the stem reads the data
    variable directly, with no input BatchNorm."""
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(7, 7), stride=(2, 2),
                             pad=(3, 3), num_filter=8, no_bias=True,
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")

    def make(stem):
        mesh = build_mesh(tp=1)
        return ShardedTrainer(
            sym, mesh,
            data_shapes={"data": (8, 3, 16, 16)},
            label_shapes={"softmax_label": (8,)},
            layout="NHWC", dtype="float32", seed=5, learning_rate=0.1,
            momentum=0.9, stem_space_to_depth=stem)

    t_ref, t_s2d = make(False), make(True)
    rng = np.random.RandomState(3)
    batch = {"data": rng.randn(8, 3, 16, 16).astype("f"),
             "softmax_label": rng.randint(0, 10, 8).astype("f")}
    for t in (t_ref, t_s2d):
        b = t.put_batch(batch)
        t.step(b)
        t.step(b)
    for k in t_ref.params:
        np.testing.assert_allclose(
            np.asarray(t_s2d.params[k]), np.asarray(t_ref.params[k]),
            rtol=1e-4, atol=1e-5, err_msg=k)


# ------------------------------------------- raw-uint8 device ingest
def test_uint8_device_normalize_matches_host_floats():
    """put_batch of raw uint8 NHWC batches (the native reader's
    raw_uint8 output) with device-side (x-mean)/std equals staging
    host-normalized floats — same training trajectory."""
    from mxnet_tpu import models
    mesh = build_mesh(tp=1)
    mean = (123.68, 116.779, 103.939)
    std = (58.393, 57.12, 57.375)

    def make(**kw):
        net = models.get_model("resnet18", num_classes=10,
                               image_shape="3,32,32")
        return ShardedTrainer(
            net, mesh, data_shapes={"data": (8, 3, 32, 32)},
            label_shapes={"softmax_label": (8,)},
            layout="NHWC", seed=6, learning_rate=0.1, momentum=0.9,
            **kw)

    a = make()
    b = make(input_mean=mean, input_std=std)
    rng = np.random.RandomState(0)
    u8_nhwc = rng.randint(0, 256, (8, 32, 32, 3)).astype(np.uint8)
    y = rng.randint(0, 10, 8).astype("f")
    host_norm = ((u8_nhwc.astype("f") - np.asarray(mean, "f"))
                 / np.asarray(std, "f")).transpose(0, 3, 1, 2)

    for _ in range(2):
        la = float(a.step({"data": host_norm, "softmax_label": y}))
        lb = float(b.step(b.put_batch(
            {"data": u8_nhwc, "softmax_label": y})))
        assert np.isclose(la, lb, rtol=1e-3), (la, lb)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=1e-3, atol=1e-4, err_msg=name)


# ------------------------------------------------- fused fit CLI path
def test_fused_fit_cli(tmp_path):
    """examples/image_classification fit --fused 1: the CLI surface
    (lr schedule, Speedometer logging, checkpoints, epoch eval) running
    on ShardedTrainer instead of Module; trains the MLP to threshold
    and writes Module-compatible checkpoints."""
    import argparse
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "examples",
        "image_classification"))
    from common import fit as fit_mod

    rng = np.random.RandomState(42)
    protos = rng.rand(10, 64).astype("f")

    def digits(n, seed):
        r = np.random.RandomState(seed)
        y = r.randint(0, 10, n)
        x = (protos[y] + r.randn(n, 64).astype("f") * 0.2).astype("f")
        return x, y.astype("f")

    def loader(args, kv):
        xtr, ytr = digits(640, 0)
        xva, yva = digits(192, 1)
        train = mx.io.NDArrayIter(xtr, ytr, args.batch_size, shuffle=True,
                                  label_name="softmax_label")
        val = mx.io.NDArrayIter(xva, yva, args.batch_size,
                                label_name="softmax_label")
        return train, val

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    prefix = str(tmp_path / "fused_mlp")
    args = argparse.Namespace(
        network="mlp", num_layers=None, gpus=None, tpus=None,
        kv_store="local", num_epochs=3, lr=0.5, lr_factor=0.1,
        lr_step_epochs="", optimizer="sgd", mom=0.9, wd=1e-4,
        batch_size=64, disp_batches=4, model_prefix=prefix,
        load_epoch=None, top_k=0, data_nthreads=1, test_io=0,
        monitor=0, fused=1, dtype="float32", num_examples=640)
    trainer = fit_mod.fit(args, net, loader)

    xva, yva = digits(192, 1)
    prob = np.asarray(trainer.forward({"data": xva})[0])
    assert (prob.argmax(1) == yva).mean() > 0.9

    # checkpoints are Module-format: load one back through Module
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0003.params")
    symc, arg_p, aux_p = mx.model.load_checkpoint(prefix, 3)
    mod = mx.module.Module(symc, context=mx.cpu())
    mod.bind(data_shapes=[("data", (192, 64))], for_training=False,
             label_shapes=[("softmax_label", (192,))])
    mod.set_params(arg_p, aux_p)
    mod.forward(mx.io.DataBatch([mx.nd.array(xva)], []))
    prob2 = mod.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(prob2, prob, rtol=2e-4, atol=2e-5)

    # resume path: --load-epoch restores through trainer.load_checkpoint
    args.load_epoch = 3
    args.num_epochs = 3  # no further epochs, just restore
    trainer2 = fit_mod.fit(args, net, loader)
    np.testing.assert_allclose(
        np.asarray(trainer2.params["fc1_weight"]),
        np.asarray(trainer.params["fc1_weight"]), rtol=1e-6)
