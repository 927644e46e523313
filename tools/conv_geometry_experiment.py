#!/usr/bin/env python
"""Conv-geometry experiment (VERDICT r4 #3): can a changed GEOMETRY —
not changed fusion boundaries — beat XLA's conv custom call on the
C<=64 ResNet stages that starve the MXU's K dimension?

Round 4 established (tools/pallas_block_experiment.py) that fusing
MORE around the conv does not help because a 3x3 conv at C=64 feeds
the 128-wide MXU K dim at half occupancy no matter who schedules it.
This artifact tests the two geometry rewrites the verdict names:

* ``im2col``: materialize the 9 shifted taps as channels
  (B,H,W,9C) and run ONE GEMM with K=9C=576 — full MXU K occupancy,
  paid for with 9x activation traffic.
* ``s2d-phase``: 2x2 space-to-depth packs C 64->256, the 3x3 becomes
  four phase-specific 2x2 convs (K=1024 per shifted tap) whose outputs
  interleave back — full K occupancy, paid for with 16/9 = 1.78x FLOPs
  (zero-padded taps) + the pack/unpack relayouts.

Each formulation runs fwd + full vjp (what the training step pays),
K instances per dispatch, and is scored by PROFILER DEVICE TIME
(docs/perf.md).  Equivalence vs
the XLA conv is asserted numerically before timing.

Usage: python tools/conv_geometry_experiment.py [--batch 128]
Prints one JSON line per (shape, formulation).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def xla_conv(x, w):
    import jax.numpy as jnp
    from jax import lax
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(x, w, (1, 1), [(1, 1), (1, 1)],
                                    dimension_numbers=dn)


def im2col_conv(x, w):
    """9 shifted taps concatenated channelwise, one K=9C GEMM."""
    import jax.numpy as jnp
    b, h, ww, c = x.shape
    kh, kw, ci, co = w.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = [xp[:, dy:dy + h, dx:dx + ww, :]
            for dy in range(kh) for dx in range(kw)]
    patches = jnp.concatenate(taps, axis=-1)           # (B,H,W,9C)
    y = jnp.dot(patches.reshape(-1, kh * kw * ci),
                w.reshape(kh * kw * ci, co),
                preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(b, h, ww, co)


def s2d_phase_conv(x, w):
    """2x2 space-to-depth (C->4C), four phase-specific 2x2 convs,
    outputs interleaved back to the full grid.

    out[b, 2y+a, 2x+c] = sum_{dy,dx} in[b, 2y+a+dy-1, 2x+c+dx-1] w[dy,dx]
    With z[b,y,x,(p,q,:)] = in[b,2y+p,2x+q,:], each (a,c) output phase
    is a 2x2 conv over z whose kernel scatters w's taps into the
    (e,p,f,q) slots they land in (one quarter stays zero — the 1.78x
    FLOP tax).
    """
    import jax.numpy as jnp
    from jax import lax
    b, h, ww, c = x.shape
    kh, kw, ci, co = w.shape
    assert (kh, kw) == (3, 3) and h % 2 == 0 and ww % 2 == 0
    z = x.reshape(b, h // 2, 2, ww // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    z = z.reshape(b, h // 2, ww // 2, 4 * c)           # (p,q,c) packed
    dn = lax.conv_dimension_numbers(z.shape, (2, 2, 4 * c, co),
                                    ("NHWC", "HWIO", "NHWC"))
    phases = [(a, cph) for a in range(2) for cph in range(2)]
    # phase kernels assembled from w's taps at trace time (static
    # scatter: concat/stack of slices, no device gather)
    kernels = []
    for a, cph in phases:
        # tap (dy,dx) lands on packed-grid offset e=(a+dy-1)//2 with
        # in-cell phase p=(a+dy-1)%2; each output phase spans exactly
        # two consecutive e values starting at e_min=(a-1)//2
        e_min, f_min = (a - 1) // 2, (cph - 1) // 2
        slots = {}
        for dy in range(3):
            e, p = divmod(a + dy - 1, 2)
            for dx in range(3):
                f, q = divmod(cph + dx - 1, 2)
                slots[(e - e_min, p, f - f_min, q)] = (dy, dx)
        rows = []
        for e in range(2):
            cols = []
            for f in range(2):
                pq = []
                for p in range(2):
                    for q in range(2):
                        tap = slots.get((e, p, f, q))
                        if tap is None:
                            pq.append(jnp.zeros((ci, co), x.dtype))
                        else:
                            pq.append(w[tap[0], tap[1]])
                cols.append(jnp.concatenate(pq, axis=0))  # (4C, O)
            rows.append(jnp.stack(cols, axis=0))          # (2, 4C, O)
        kernels.append((jnp.stack(rows, axis=0),          # (2,2,4C,O)
                        e_min + 1, f_min + 1))
    zp = jnp.pad(z, ((0, 0), (1, 1), (1, 1), (0, 0)))
    outs = []
    for (k, sy, sx) in kernels:
        y_ph = lax.conv_general_dilated(zp, k, (1, 1), "VALID",
                                        dimension_numbers=dn)
        outs.append(y_ph[:, sy:sy + h // 2, sx:sx + ww // 2, :])
    o = jnp.stack(outs, axis=3)                  # (B,H/2,W/2,4,O)
    o = o.reshape(b, h // 2, ww // 2, 2, 2, co)
    o = o.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, ww, co)
    return o


def device_ms(fn_name, prog, args_dev, outdir, total_instances):
    """Profiler device time per instance for one compiled program."""
    import jax
    out = prog(*args_dev)
    jax.block_until_ready(out)          # warm compile
    float(np.asarray(out[0]))
    d = os.path.join(outdir, fn_name)
    os.makedirs(d, exist_ok=True)
    jax.profiler.start_trace(d)
    float(np.asarray(prog(*args_dev)[0]))
    jax.profiler.stop_trace()
    planes = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)
    if not planes:
        return float("nan")
    data = jax.profiler.ProfileData.from_file(planes[-1])
    total = 0
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                total += ev.duration_ns
    return total / 1e6 / total_instances


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--k", type=int, default=10,
                    help="instances per dispatch (amortizes dispatch)")
    ap.add_argument("--outdir", default=".profiles/geometry")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    shapes = [  # (H, C, O): the K-starved 3x3 stages
        (56, 64, 64),     # stage-1 bottleneck 3x3
        (28, 128, 128),   # stage-2
    ]
    forms = [("xla", xla_conv), ("im2col", im2col_conv),
             ("s2d_phase", s2d_phase_conv)]

    rng = np.random.RandomState(0)
    for (h, c, o) in shapes:
        x_np = rng.uniform(-1, 1, (args.batch, h, h, c)).astype(np.float32)
        w_np = (rng.uniform(-1, 1, (3, 3, c, o)) / np.sqrt(9 * c)) \
            .astype(np.float32)
        x = jnp.asarray(x_np, jnp.bfloat16)
        w = jnp.asarray(w_np, jnp.bfloat16)

        # numerical equivalence first (f32, small slice)
        xf = jnp.asarray(x_np[:2], jnp.float32)
        wf = jnp.asarray(w_np, jnp.float32)
        ref = np.asarray(jax.jit(xla_conv)(xf, wf), np.float32)
        for name, f in forms[1:]:
            got = np.asarray(jax.jit(f)(xf, wf), np.float32)
            err = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
            assert err < 2e-5, (name, h, c, err)

        results = {}
        for name, f in forms:
            def make_prog(fun):
                @jax.jit
                def prog(x, w):
                    outs = []
                    xi = x
                    for i in range(args.k):
                        # instance-chained, cotangent = y: nonlinear in
                        # x so the scalar-mul-through-conv hoist cannot
                        # collapse instances, and dx depends on the
                        # instance (a ones cotangent made every dx
                        # identical -> legitimately CSE'd -> 10x
                        # undercount, caught by a >peak TFLOP/s reading)
                        y, vjp = jax.vjp(fun, xi, w)
                        dx, dw = vjp(y)
                        outs.append(jnp.sum(y.astype(jnp.float32))
                                    + jnp.sum(dw.astype(jnp.float32))
                                    + jnp.sum(dx.astype(jnp.float32)))
                        xi = x + 1e-3 * jnp.mean(dx).astype(x.dtype)
                    return jnp.stack(outs)
                return prog
            ms = device_ms("%s_h%d" % (name, h), make_prog(f), (x, w),
                           args.outdir, args.k)
            results[name] = ms
            flops = 3 * 2 * args.batch * h * h * (9 * c) * o  # fwd+2 bwd
            print(json.dumps({
                "shape": "%dx%dx%d->%d" % (h, h, c, o), "form": name,
                "device_ms_per_instance": round(ms, 3),
                "tflops": round(flops / (ms * 1e-3) / 1e12, 2),
                "vs_xla": round(results["xla"] / ms, 3)}), flush=True)


if __name__ == "__main__":
    main()
