#!/usr/bin/env python
"""Measure device-sync (allreduce) bandwidth.

Reference: ``tools/bandwidth/measure.py`` — kvstore push/pull bandwidth over
a resnet-sized parameter set (README shows 11.1 GB/s/GPU on 2 GPUs).  TPU
equivalent: psum over the device mesh (ICI), measured end to end.  Prints
per-device algorithmic bandwidth, directly comparable to the reference's
number.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser(description="measure allreduce "
                                     "bandwidth over the device mesh")
    parser.add_argument("--size-mb", type=float, default=258.0,
                        help="total bytes reduced (default: resnet-200 "
                             "param set, matching the reference README)")
    parser.add_argument("--num-arrays", type=int, default=100,
                        help="number of gradient arrays")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--devices", type=int, default=0,
                        help="0 = all local devices")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding

    devices = jax.devices()
    if args.devices:
        devices = devices[:args.devices]
    n = len(devices)
    mesh = Mesh(np.array(devices), axis_names=("data",))

    total_elems = int(args.size_mb * 1e6 / 4)
    per_array = total_elems // args.num_arrays
    arrays = [np.random.rand(n, per_array).astype(np.float32)
              for _ in range(args.num_arrays)]
    sharding = NamedSharding(mesh, P("data", None))
    dev_arrays = [jax.device_put(a, sharding) for a in arrays]

    @jax.jit
    def allreduce(xs):
        def psum_all(*local):
            return tuple(jax.lax.psum(l, "data") for l in local)
        f = jax.shard_map(psum_all, mesh=mesh,
                      in_specs=tuple(P("data", None) for _ in xs),
                      out_specs=tuple(P(None, None) for _ in xs))
        return f(*xs)

    # warmup/compile
    out = allreduce(dev_arrays)
    jax.block_until_ready(out)

    tic = time.perf_counter()
    for _ in range(args.iters):
        out = allreduce(dev_arrays)
    jax.block_until_ready(out)
    dt = time.perf_counter() - tic

    total_bytes = sum(a.nbytes // n for a in arrays)  # per-device shard
    # ring allreduce moves 2(n-1)/n of the data per device
    algo_bw = total_bytes * args.iters / dt / 1e9
    print("devices: %d, payload %.1f MB, time per allreduce %.2f ms" %
          (n, args.size_mb, dt / args.iters * 1e3))
    print("allreduce bandwidth: %.2f GB/s per device" % algo_bw)


if __name__ == "__main__":
    main()
