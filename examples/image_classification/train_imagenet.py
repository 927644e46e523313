"""Train ImageNet-scale networks.

Reference: ``example/image-classification/train_imagenet.py`` — the
headline ResNet-50 config (BASELINE.md).  Data from .rec files
(--data-train/--data-val, reference format via mxnet_tpu.image.ImageIter)
or --benchmark 1 for synthetic throughput runs, same as the reference flag.
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


def get_rec_iter(args, kv):
    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    if args.benchmark:
        rng = np.random.RandomState(0)
        n = args.batch_size * 32
        x = rng.rand(n, *image_shape).astype(np.float32)
        y = rng.randint(0, args.num_classes, n).astype(np.float32)
        train = mx.io.NDArrayIter(x, y, args.batch_size)
        return train, None
    from mxnet_tpu import config, io_native
    # native pipeline (reference ImageRecordIter / ImageRecordIOParser2):
    # C++ reader + N JPEG decode threads, no per-image Python cost.
    # Needs cores to beat the in-process PIL path (docs/perf.md) — let
    # MXNET_USE_NATIVE_REC=0/1 override the auto choice.
    forced = os.environ.get("MXNET_USE_NATIVE_REC")
    use_native = config.get_bool(
        "MXNET_USE_NATIVE_REC",
        io_native.jpeg_available() and (os.cpu_count() or 1) >= 2)
    if forced == "1" and not io_native.jpeg_available():
        # an explicit force must fail loudly, not quietly run 4x slower
        raise RuntimeError("MXNET_USE_NATIVE_REC=1 but the native JPEG "
                           "pipeline is unavailable on this host")
    if use_native and io_native.jpeg_available():
        train = mx.io.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=image_shape,
            batch_size=args.batch_size, shuffle=True,
            rand_crop=True, rand_mirror=True,
            num_parts=kv.num_workers, part_index=kv.rank,
            preprocess_threads=args.data_nthreads)
        val = None
        if args.data_val:
            val = mx.io.ImageRecordIter(
                path_imgrec=args.data_val, data_shape=image_shape,
                batch_size=args.batch_size,
                preprocess_threads=args.data_nthreads)
        return train, val
    train = mx.image.ImageIter(
        batch_size=args.batch_size, data_shape=image_shape,
        path_imgrec=args.data_train, path_imgidx=args.data_train_idx or None,
        shuffle=True, rand_crop=True, rand_mirror=True,
        num_parts=kv.num_workers, part_index=kv.rank)
    val = None
    if args.data_val:
        val = mx.image.ImageIter(
            batch_size=args.batch_size, data_shape=image_shape,
            path_imgrec=args.data_val, shuffle=False)
    return train, val


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--data-train", type=str)
    parser.add_argument("--data-train-idx", type=str, default="")
    parser.add_argument("--data-val", type=str)
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--num-examples", type=int, default=1281167)
    parser.add_argument("--benchmark", type=int, default=0,
                        help="if 1, run throughput benchmark on synthetic "
                             "data")
    fit.add_fit_args(parser)
    parser.set_defaults(network="resnet", num_layers=50, num_epochs=80,
                        lr_step_epochs="30,60", batch_size=128)
    args = parser.parse_args()

    net = models.get_model(args.network, num_classes=args.num_classes,
                           num_layers=args.num_layers,
                           image_shape=args.image_shape)
    mx.base.use_compile_cache()
    fit.fit(args, net, get_rec_iter)
