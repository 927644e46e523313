"""Train on CIFAR-10.

Reference: ``example/image-classification/train_cifar10.py``.  Reads the
reference's ``cifar10_train.rec`` if present, else synthesizes data.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import models
from common import fit


def get_cifar_iter(args, kv):
    train_rec = os.path.join(args.data_dir, "cifar10_train.rec")
    if os.path.exists(train_rec):
        train = mx.image.ImageIter(
            batch_size=args.batch_size, data_shape=(3, 28, 28),
            path_imgrec=train_rec, shuffle=True, rand_crop=True,
            rand_mirror=True, num_parts=kv.num_workers, part_index=kv.rank)
        val = mx.image.ImageIter(
            batch_size=args.batch_size, data_shape=(3, 28, 28),
            path_imgrec=os.path.join(args.data_dir, "cifar10_val.rec"))
        return train, val
    rng = np.random.RandomState(0)
    n = args.num_examples
    y = rng.randint(0, 10, n).astype(np.float32)
    x = rng.rand(n, 3, 28, 28).astype(np.float32) * 0.2
    for i in range(10):
        x[y == i, :, i:i + 3, i:i + 3] += 0.7
    split = int(n * 0.9)
    train = mx.io.NDArrayIter(x[:split], y[:split], args.batch_size,
                              shuffle=True)
    val = mx.io.NDArrayIter(x[split:], y[split:], args.batch_size)
    return train, val


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="train cifar10",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--data-dir", type=str, default="data/cifar10/")
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument("--num-examples", type=int, default=50000)
    fit.add_fit_args(parser)
    parser.set_defaults(network="resnet", num_layers=20, num_epochs=300,
                        lr=0.05, lr_step_epochs="200,250", batch_size=128,
                        kv_store="local")
    args = parser.parse_args()

    net = models.get_model(args.network, num_classes=args.num_classes,
                           num_layers=args.num_layers,
                           image_shape="3,28,28")
    mx.base.use_compile_cache()
    fit.fit(args, net, get_cifar_iter)
