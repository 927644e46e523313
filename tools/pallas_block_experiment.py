#!/usr/bin/env python
"""Mosaic block-kernel experiment: one ResNet stage-1 bottleneck as a
single Pallas kernel (VERDICT r3 #3b).

The round-3 per-conv experiment (conv1x1+BN epilogue) lost 41% to
pallas_call layout boundaries.  The hypothesis to test here: amortize
that boundary over a WHOLE bottleneck block — BN-ReLU-conv1x1(64) ->
BN-ReLU-conv3x3(64) -> BN-ReLU-conv1x1(256) at stage-1 shapes
(N, 56, 56, C), where channel padding hurts XLA's convs most — keeping
every intermediate in VMEM, the 3x3 computed as 9 shifted matmuls on
the MXU.  BN is folded to per-channel scale/shift (inference form; the
boundary-amortization question is the same).

The artifact times the Pallas block against XLA jitting the identical
math (same scale/shift convs) and prints a measured win or failure.

Usage: python tools/pallas_block_experiment.py [--batch 128]
Prints one JSON line; see docs/perf.md (conv ceiling section).
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

H = W = 56


def _block_kernel(x_ref, w1_ref, w2_ref, w3_ref, s_ref, b_ref, y_ref, *,
                  rows, cin, cmid, cout):
    """x block (1, rows+2, W+2, cin) -> y block (1, rows, W, cout).

    The halo (one row/col each side, zero-filled by the index map edge
    padding) feeds the 3x3; all three matmul chains run f32 on the MXU.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x = x_ref[0].astype(jnp.float32)               # (rows+2, W+2, cin)
    s0 = s_ref[0, 0]; b0 = b_ref[0, 0]             # (cin,)
    s1 = s_ref[0, 1, :cmid]; b1 = b_ref[0, 1, :cmid]
    s2 = s_ref[0, 2, :cmid]; b2 = b_ref[0, 2, :cmid]

    # BN-ReLU -> 1x1 (on the full haloed block: the 3x3 needs it)
    a = jnp.maximum(x * s0 + b0, 0.0)
    t1 = jax.lax.dot_general(
        a.reshape(-1, cin), w1_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(rows + 2, W + 2, cmid)

    # BN-ReLU -> 3x3 as 9 shifted matmuls accumulating in VMEM
    t1 = jnp.maximum(t1 * s1 + b1, 0.0)
    # zero the IMAGE-edge padding ring: conv padding contributes zero,
    # but the pointwise chain above turned those x=0 cells into
    # relu(b)@w1 (block-interior halo rows are real neighbors — keep)
    qi = pl.program_id(1)
    # 3-D iotas: Mosaic cannot minor-dim-reshape an i1 mask
    grow = qi * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows + 2, W + 2, 1), 0)       # padded-array row ids
    gcol = jax.lax.broadcasted_iota(jnp.int32, (rows + 2, W + 2, 1), 1)
    interior = ((grow >= 1) & (grow <= H) & (gcol >= 1) & (gcol <= W))
    t1 = jnp.where(interior, t1, 0.0)
    acc = jnp.zeros((rows * W, cmid), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = t1[dy:dy + rows, dx:dx + W, :].reshape(-1, cmid)
            wmat = w2_ref[0, dy * 3 + dx].astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                patch, wmat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    # BN-ReLU -> 1x1 expand
    t2 = jnp.maximum(acc * s2 + b2, 0.0)
    y = jax.lax.dot_general(
        t2, w3_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.reshape(rows, W, -1).astype(y_ref.dtype)


def pallas_block(x, w1, w2, w3, scales, shifts, rows=8, interpret=False):
    """x (N, 56, 56, cin) -> (N, 56, 56, cout); weights pre-reshaped:
    w1 (cin, cmid), w2 (9, cmid, cmid), w3 (cmid, cout)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n, h, w_, cin = x.shape
    cmid, cout = w1.shape[1], w3.shape[1]
    assert h == H and w_ == W and h % rows == 0
    # zero halo once in HBM (XLA pads); blocks then read with overlap
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))

    kernel = functools.partial(_block_kernel, rows=rows, cin=cin,
                               cmid=cmid, cout=cout)
    grid = (n, h // rows)
    # overlapping row blocks via element-indexed dims: the (rows+2)-row
    # halo window starts at ELEMENT offset qi*rows of the padded array
    yshape = jax.ShapeDtypeStruct((n, h, w_, cout), x.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pl.Element(1), pl.Element(rows + 2),
                          pl.Element(w_ + 2), pl.Element(cin)),
                         lambda ni, qi: (ni, qi * rows, 0, 0)),
            pl.BlockSpec((1,) + w1.shape, lambda ni, qi: (0, 0, 0)),
            pl.BlockSpec((1,) + w2.shape, lambda ni, qi: (0, 0, 0, 0)),
            pl.BlockSpec((1,) + w3.shape, lambda ni, qi: (0, 0, 0)),
            pl.BlockSpec((1,) + scales.shape, lambda ni, qi: (0, 0, 0)),
            pl.BlockSpec((1,) + shifts.shape, lambda ni, qi: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, w_, cout),
                               lambda ni, qi: (ni, qi, 0, 0)),
        out_shape=yshape,
        interpret=interpret,
    )(xp, w1[None], w2[None], w3[None], scales[None], shifts[None])


def xla_block(x, w1, w2, w3, scales, shifts):
    """Identical math through XLA's convs (the thing to beat)."""
    import jax.numpy as jnp
    from jax import lax

    cin, cmid = w1.shape
    cout = w3.shape[1]

    def bnrelu(t, i, c):
        return jnp.maximum(t * scales[i, :c] + shifts[i, :c], 0.0)

    a = bnrelu(x.astype(jnp.float32), 0, cin)
    dn1 = lax.conv_dimension_numbers(a.shape, (cmid, cin, 1, 1),
                                     ("NHWC", "OIHW", "NHWC"))
    t1 = lax.conv_general_dilated(
        a.astype(x.dtype), jnp.transpose(w1, (1, 0))[:, :, None, None],
        (1, 1), [(0, 0), (0, 0)], dimension_numbers=dn1)
    t1 = bnrelu(t1.astype(jnp.float32), 1, cmid)
    w2k = jnp.transpose(w2.reshape(3, 3, cmid, cmid), (3, 2, 0, 1))
    dn2 = lax.conv_dimension_numbers(t1.shape, w2k.shape,
                                     ("NHWC", "OIHW", "NHWC"))
    t2 = lax.conv_general_dilated(
        t1.astype(x.dtype), w2k, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=dn2)
    t2 = bnrelu(t2.astype(jnp.float32), 2, cmid)
    dn3 = lax.conv_dimension_numbers(t2.shape, (cout, cmid, 1, 1),
                                     ("NHWC", "OIHW", "NHWC"))
    y = lax.conv_general_dilated(
        t2.astype(x.dtype), jnp.transpose(w3, (1, 0))[:, :, None, None],
        (1, 1), [(0, 0), (0, 0)], dimension_numbers=dn3)
    return y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--cin", type=int, default=64)
    ap.add_argument("--cmid", type=int, default=64)
    ap.add_argument("--cout", type=int, default=256)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--check-only", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16
    n = 2 if (args.check_only or args.interpret) else args.batch
    x = jnp.asarray(rng.uniform(-1, 1, (n, H, W, args.cin)), dt)
    w1 = jnp.asarray(rng.normal(0, 0.1, (args.cin, args.cmid)), dt)
    w2 = jnp.asarray(rng.normal(0, 0.05, (9, args.cmid, args.cmid)), dt)
    w3 = jnp.asarray(rng.normal(0, 0.1, (args.cmid, args.cout)), dt)
    cmax = max(args.cin, args.cmid)
    scales = jnp.asarray(rng.uniform(0.5, 1.5, (3, cmax)), jnp.float32)
    shifts = jnp.asarray(rng.uniform(-0.2, 0.2, (3, cmax)), jnp.float32)

    jp = jax.jit(lambda x: pallas_block(x, w1, w2, w3, scales, shifts,
                                        rows=args.rows,
                                        interpret=args.interpret))
    jx = jax.jit(lambda x: xla_block(x, w1, w2, w3, scales, shifts))

    yp = np.asarray(jp(x), np.float32)
    yx = np.asarray(jx(x), np.float32)
    err = np.abs(yp - yx).max() / max(1e-6, np.abs(yx).max())
    if args.check_only or args.interpret:
        print("rel err %.3e" % err)
        assert err < 5e-2, err
        print("OK")
        return

    # timing via the autotuner's measurement runner (mxnet_tpu.
    # autotune.measure): K=10 data-dependent applications chained in
    # ONE program (bench.py uses the same in-program chaining),
    # compile excluded, min-of-N
    # wall — the costdb timing semantics, one code path for every
    # experiment.
    from mxnet_tpu.autotune import measure
    K = 10
    tp = measure(lambda x: pallas_block(x, w1, w2, w3, scales, shifts,
                                        rows=args.rows,
                                        interpret=args.interpret),
                 (x,), repeats=args.repeats, chain=K)
    tx = measure(lambda x: xla_block(x, w1, w2, w3, scales, shifts),
                 (x,), repeats=args.repeats, chain=K)
    gflop = (2 * n * H * W *
             (args.cin * args.cmid + 9 * args.cmid * args.cmid
              + args.cmid * args.cout)) / 1e9
    print(json.dumps({
        "metric": "stage1_block_pallas_vs_xla",
        "pallas_ms": round(tp * 1e3, 3), "xla_ms": round(tx * 1e3, 3),
        "speedup": round(tx / tp, 3), "rel_err": float("%.3e" % err),
        "gflop": round(gflop, 2),
        "pallas_tflops": round(gflop / tp / 1e3, 2),
        "xla_tflops": round(gflop / tx / 1e3, 2),
        "batch": n, "rows": args.rows,
    }))


if __name__ == "__main__":
    main()
