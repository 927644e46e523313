"""IO-in-the-loop training benchmark + decoder-thread scaling.

Measures what docs/perf.md's input-pipeline section claims, with data:

1. decoder scaling — native reader throughput (raw_uint8, no training)
   at 1/2/4 preprocess threads;
2. IO-in-the-loop training — ResNet-50 fused steps fed from the native
   reader (raw uint8 bytes over the host link, (x-mean)/std on device),
   reporting end-to-end img/s plus where the wall time went — the
   per-stage breakdown and bottleneck verdict come from the ioview
   accounting (``mxnet_tpu.telemetry.ioview``), the same numbers every
   production run exports, instead of ad-hoc loop timers.

Usage: python tools/io_train_bench.py [--rec /tmp/synth_imagenet.rec]
       [--batch 128] [--image 224] [--layers 50] [--train-batches 30]
The rec file is synthesized (2000 random 256px JPEGs) if absent.
"""
from __future__ import annotations

import argparse
import io as _io
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def make_rec(path, n=2000, size=256):
    from PIL import Image
    import mxnet_tpu as mx
    rng = np.random.RandomState(0)
    w = mx.recordio.MXRecordIO(path, "w")
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
        buf = _io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=90)
        w.write(mx.recordio.pack(
            mx.recordio.IRHeader(0, float(i % 1000), i, 0),
            buf.getvalue()))
    w.close()


def decoder_scaling(rec, image, batch):
    import mxnet_tpu as mx
    # warm the page cache first: the first configuration measured would
    # otherwise pay the cold file read and look artificially slow
    # (this was the round-3 "208 img/s at 1 thread" artifact)
    with open(rec, "rb") as f:
        while f.read(1 << 22):
            pass
    print("-- decoder-thread scaling (raw_uint8, no training; "
          "%d host cores)" % (os.cpu_count() or 1))
    results = {}
    for threads in (1, 2, 4, 2, 1):   # repeat configs: order effects
        it = mx.io.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, image, image),
            batch_size=batch, preprocess_threads=threads, raw_uint8=True)
        n = 0
        t0 = time.perf_counter()
        c0 = time.process_time()
        for b in it:
            n += b.data[0].shape[0]
        dt = time.perf_counter() - t0
        cpu = time.process_time() - c0
        results.setdefault(threads, []).append(n / dt)
        print("   threads=%d  %7.1f img/s   cpu/wall=%.2f cores"
              % (threads, n / dt, cpu / dt))
    return results


def _io_delta(before, after):
    """Per-stage (seconds, items) deltas between two ioview snapshots."""
    out = {}
    for st, v in after["stages"].items():
        prev = before["stages"].get(st, {"s": 0.0, "items": 0})
        ds = v["s"] - prev["s"]
        di = v["items"] - prev["items"]
        if ds > 0 or di > 0:
            out[st] = (ds, di)
    return out


def _print_io_breakdown(before, after, train_batches):
    """The ioview stage table for the timed loop window."""
    from mxnet_tpu.telemetry import ioview
    print("   io stage breakdown (telemetry.ioview, per timed batch):")
    for st, (ds, di) in sorted(_io_delta(before, after).items()):
        print("     %-13s %7.1f ms/batch  (%d items)"
              % (st, 1e3 * ds / max(1, train_batches), di))
    for kind, label in (("stall_s", "consumer stalled"),
                        ("starved_s", "producer starved")):
        d = {k: after[kind].get(k, 0.0) - before[kind].get(k, 0.0)
             for k in after[kind]}
        d = {k: v for k, v in d.items() if v > 1e-4}
        if d:
            print("     %-16s %s" % (label, "  ".join(
                "%s=%.1fms/batch" % (k, 1e3 * v / max(1, train_batches))
                for k, v in sorted(d.items()))))
    verdict = ioview.classify(force=True)
    if verdict:
        print("     bottleneck: %s (stage %r)"
              % (verdict["verdict"], verdict["stage"]))


def train_loop(rec, image, batch, layers, train_batches,
               prefetch_depth=0):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    from mxnet_tpu.telemetry import ioview

    net = models.get_model("resnet%d" % layers, num_classes=1000,
                           image_shape="3,%d,%d" % (image, image))
    trainer = ShardedTrainer(
        net, build_mesh(tp=1),
        data_shapes={"data": (batch, 3, image, image)},
        label_shapes={"softmax_label": (batch,)},
        optimizer="sgd", learning_rate=0.1, momentum=0.9,
        weight_decay=1e-4, dtype="bfloat16", layout="NHWC",
        input_mean=(123.68, 116.779, 103.939),
        input_std=(58.393, 57.12, 57.375))

    it = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, image, image), batch_size=batch,
        preprocess_threads=max(2, (os.cpu_count() or 1)),
        raw_uint8=True, shuffle=True)

    if prefetch_depth > 0:
        # compile the staging programs and the step on the MAIN thread
        # first: concurrent first-compiles from two threads serialize
        # badly
        b0 = next(it)
        float(trainer.step(trainer.put_batch(
            {"data": b0.data[0].asnumpy(),
             "softmax_label": b0.label[0].asnumpy()})))
        it.reset()
        # decode + host->device staging run on the prefetcher thread,
        # overlapping the step (reference iter_prefetcher.h role)
        pre = mx.io.DevicePrefetchIter(it, trainer.put_batch,
                                       depth=prefetch_depth)
        ioview.track(pre)
        n, loss, warm, t_wall, io0 = 0, None, 2, None, None
        while n < train_batches + warm:
            try:
                dev = next(pre)
            except StopIteration:
                pre.reset()
                dev = next(pre)
            loss = trainer.step(dev)
            n += 1
            if n == warm:
                float(loss)
                t_wall = time.perf_counter()
                io0 = ioview.snapshot()
        lval = float(loss)
        wall = time.perf_counter() - t_wall
        imgs = train_batches * batch
        print("-- IO-in-the-loop training (DevicePrefetchIter depth=%d)"
              % prefetch_depth, flush=True)
        print("   resnet%d batch %d image %d: %7.1f img/s end-to-end "
              "(loss %.3f)" % (layers, batch, image, imgs / wall, lval))
        _print_io_breakdown(io0, ioview.snapshot(), train_batches)
        return imgs / wall

    # ioview accounts the pipeline stages (native decode, batch
    # assembly, H2D staging through trainer.put_batch via step); the
    # only remaining hand timer is the step dispatch itself, which is
    # not a pipeline stage
    ioview.track(it)
    t_step = 0.0
    n = 0
    loss = None
    warm = 2
    t_wall = None
    io0 = None
    while n < train_batches + warm:
        try:
            b = next(it)
        except StopIteration:
            it.reset()
            b = next(it)
        host = {"data": b.data[0].asnumpy(),
                "softmax_label": b.label[0].asnumpy()}
        t1 = time.perf_counter()
        dev = trainer.put_batch(host)
        ioview.account("device_stage", time.perf_counter() - t1, items=1,
                       nbytes=sum(v.nbytes for v in host.values()))
        t2 = time.perf_counter()
        loss = trainer.step(dev)
        t3 = time.perf_counter()
        n += 1
        if n == warm:
            float(loss)          # close the async chain before timing
            t_wall = time.perf_counter()
            io0 = ioview.snapshot()
            t_step = 0.0
            continue
        t_step += t3 - t2
    lval = float(loss)           # drain the pipeline
    wall = time.perf_counter() - t_wall
    imgs = train_batches * batch
    print("-- IO-in-the-loop training (raw_uint8 -> device normalize)")
    print("   resnet%d batch %d image %d: %7.1f img/s end-to-end "
          "(loss %.3f)" % (layers, batch, image, imgs / wall, lval))
    print("   step dispatch %.1f ms/batch (device compute overlaps "
          "asynchronously)" % (1e3 * t_step / train_batches))
    _print_io_breakdown(io0, ioview.snapshot(), train_batches)
    return imgs / wall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rec", default="/tmp/synth_imagenet.rec")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--layers", type=int, default=50)
    ap.add_argument("--train-batches", type=int, default=30)
    ap.add_argument("--skip-scaling", action="store_true")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="also run the DevicePrefetchIter mode at this "
                         "depth (0 = sequential only)")
    args = ap.parse_args()
    if not os.path.exists(args.rec):
        print("synthesizing %s ..." % args.rec)
        make_rec(args.rec)
    if not args.skip_scaling:
        decoder_scaling(args.rec, args.image, args.batch)
    seq = train_loop(args.rec, args.image, args.batch, args.layers,
                     args.train_batches)
    if args.prefetch_depth > 0:
        pre = train_loop(args.rec, args.image, args.batch, args.layers,
                         args.train_batches,
                         prefetch_depth=args.prefetch_depth)
        print("   prefetch speedup: %.2fx" % (pre / seq))


if __name__ == "__main__":
    main()
