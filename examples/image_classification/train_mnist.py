"""Train mlp/lenet on MNIST.

Reference: ``example/image-classification/train_mnist.py``.  Reads the
standard idx-ubyte files if present (--data-dir), else generates a
synthetic stand-in so the end-to-end path runs anywhere.
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


def get_mnist_iter(args, kv):
    """MNIST iterators (reference train_mnist.py get_mnist_iter)."""
    image = os.path.join(args.data_dir, "train-images-idx3-ubyte")
    label = os.path.join(args.data_dir, "train-labels-idx1-ubyte")
    flat = args.network == "mlp"
    if os.path.exists(image):
        train = mx.io.MNISTIter(image=image, label=label,
                                batch_size=args.batch_size, shuffle=True,
                                flat=flat, num_parts=kv.num_workers,
                                part_index=kv.rank)
        vimage = os.path.join(args.data_dir, "t10k-images-idx3-ubyte")
        vlabel = os.path.join(args.data_dir, "t10k-labels-idx1-ubyte")
        val = mx.io.MNISTIter(image=vimage, label=vlabel,
                              batch_size=args.batch_size, shuffle=False,
                              flat=flat)
        return train, val
    # synthetic fallback: class-separated gaussians shaped like MNIST
    rng = np.random.RandomState(0)
    n = args.num_examples
    y = rng.randint(0, 10, n).astype(np.float32)
    x = rng.rand(n, 784).astype(np.float32) * 0.1
    for i in range(10):
        x[y == i, i * 78:(i + 1) * 78] += 0.8
    if not flat:
        x = x.reshape(n, 1, 28, 28)
    split = int(n * 0.9)
    train = mx.io.NDArrayIter(x[:split], y[:split], args.batch_size,
                              shuffle=True)
    val = mx.io.NDArrayIter(x[split:], y[split:], args.batch_size)
    return train, val


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="train an image classifier on mnist",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--data-dir", type=str, default="data/mnist/",
                        help="the input data directory")
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument("--num-examples", type=int, default=60000)
    fit.add_fit_args(parser)
    parser.set_defaults(network="mlp", num_epochs=10,
                        lr=0.05, lr_step_epochs="10", batch_size=64,
                        kv_store="local")
    args = parser.parse_args()

    net = models.get_model(args.network, num_classes=args.num_classes,
                           image_shape="1,28,28")
    mx.base.use_compile_cache()
    fit.fit(args, net, get_mnist_iter)
