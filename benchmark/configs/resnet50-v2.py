"""How the system is asked for ResNet-50 v2, and what one step needs.

The trainer's keyword arguments are ``bench.py``'s (copied into the ``.json``
beside this file).  The operation count is the benchmark's own."""
from __future__ import annotations


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    from mxnet_tpu.models.resnet import resnet
    c, h, w = cfg["image_shape"]
    batch = mix["batch_per_chip"] * n_chips
    # what models.get_model("resnet50") passes, taken from the file of sizes
    net = resnet(units=cfg["units"], num_stages=len(cfg["units"]), filter_list=cfg["filter_list"],
                 num_classes=cfg["num_classes"], image_shape=[c, h, w],
                 bottle_neck=cfg["bottle_neck"], version=2)
    return net, {"data": (batch, c, h, w)}, {"softmax_label": (batch,)}


def units_per_step(cfg, mix, n_chips):
    """Images one step trains."""
    return mix["batch_per_chip"] * n_chips


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    Forward once and backward twice (by data, by weights) for every
    convolution and the classifier; the stem needs no gradient by data.  Batch
    norm, ReLU, pooling and the optimizer are left out (under 1%)."""
    _c, h, _w = cfg["image_shape"]
    f = cfg["filter_list"]
    hw = (h // 2) ** 2
    macs = hw * f[0] * 3 * 49
    stem = macs
    hw //= 4
    c_in = f[0]
    for i, (n_units, c_out) in enumerate(zip(cfg["units"], f[1:])):
        mid = c_out // 4
        for j in range(n_units):
            out_hw = hw // 4 if (j == 0 and i > 0) else hw
            macs += hw * mid * c_in + out_hw * mid * mid * 9 + out_hw * c_out * mid
            if j == 0:
                macs += out_hw * c_out * c_in
            hw, c_in = out_hw, c_out
    macs += c_in * cfg["num_classes"]
    return 2.0 * (3 * macs - stem) * units_per_step(cfg, mix, n_chips)
