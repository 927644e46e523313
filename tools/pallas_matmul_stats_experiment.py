"""Experiment: Pallas GEMM with fused BN-statistics epilogue vs XLA
dot + separate stats pass, on the ResNet-50 1x1-conv shapes.

Motivation (docs/perf.md): BN statistics reduces are 8.4 ms/step of
separate HBM passes because XLA cannot fuse a reduction into a
conv/dot's epilogue.  A Pallas kernel that computes
    y = x @ w;  s = sum(y, 0);  ss = sum(y*y, 0)
in one pass removes the extra read of y.  This script measures whether
the Pallas GEMM holds XLA's throughput while doing so.

Optionally also fuses the *previous* BN's normalize+relu into the
prologue (x is read raw, scale/shift applied in VMEM).

    python tools/pallas_matmul_stats_experiment.py
"""
from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _kernel(x_ref, w_ref, y_ref, s_ref, ss_ref):
    i = pl.program_id(0)
    y = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        ss_ref[:] = jnp.zeros_like(ss_ref)

    s_ref[:] += jnp.sum(y, axis=0, keepdims=True)
    ss_ref[:] += jnp.sum(y * y, axis=0, keepdims=True)


def _kernel_prologue(x_ref, w_ref, scale_ref, shift_ref, y_ref, s_ref,
                     ss_ref):
    """Prologue: x_hat = relu(x * scale + shift) before the dot (the
    previous BatchNorm's inference transform folded into this GEMM)."""
    i = pl.program_id(0)
    xh = jnp.maximum(
        x_ref[:].astype(jnp.float32) * scale_ref[:] + shift_ref[:], 0.0)
    y = jnp.dot(xh.astype(x_ref.dtype), w_ref[:],
                preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        ss_ref[:] = jnp.zeros_like(ss_ref)

    s_ref[:] += jnp.sum(y, axis=0, keepdims=True)
    ss_ref[:] += jnp.sum(y * y, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bm",))
def matmul_stats(x, w, bm=512):
    m, k = x.shape
    _, n = w.shape
    grid = (m // bm,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * 2 + k * n * 2 + m * n * 2,
            transcendentals=0),
    )(x, w)


@functools.partial(jax.jit, static_argnames=("bm",))
def matmul_stats_prologue(x, w, scale, shift, bm=512):
    m, k = x.shape
    _, n = w.shape
    return pl.pallas_call(
        _kernel_prologue,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
    )(x, w, scale, shift)


@jax.jit
def xla_ref(x, w):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    yf = y.astype(jnp.float32)
    return y, jnp.sum(yf, 0), jnp.sum(yf * yf, 0)


@jax.jit
def xla_dot_only(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def bench(f, *args, iters=24):
    """ms per application via the autotuner's measurement runner
    (:func:`mxnet_tpu.autotune.measure`): `iters` data-dependent
    applications chained inside ONE jitted program (per-call
    dispatch otherwise buries the kernel time),
    compile excluded, min-of-N wall, value-fetch synchronized — the
    exact costdb timing semantics, one code path for every
    experiment."""
    from mxnet_tpu.autotune import measure
    return measure(f, args, repeats=2, chain=iters) * 1e3


def main():
    rng = np.random.RandomState(0)
    batch = 128
    # (H*W, K, N) of the ResNet-50 1x1 convs at batch 128
    shapes = [
        (batch * 56 * 56, 64, 256),
        (batch * 56 * 56, 256, 64),
        (batch * 28 * 28, 512, 128),
        (batch * 28 * 28, 128, 512),
        (batch * 14 * 14, 1024, 256),
        (batch * 14 * 14, 256, 1024),
        (batch * 7 * 7, 2048, 512),
        (batch * 7 * 7, 512, 2048),
    ]
    print(f"{'M':>9} {'K':>5} {'N':>5} | {'xla dot':>8} {'xla+st':>8} "
          f"{'pallas':>8} {'pal+pro':>8}  (ms)")
    for m, k, n in shapes:
        x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        w = jnp.asarray(rng.randn(k, n) * 0.05, jnp.bfloat16)
        scale = jnp.asarray(rng.rand(1, k), jnp.float32)
        shift = jnp.asarray(rng.randn(1, k), jnp.float32)

        # correctness: y matches; stats match to bf16-accumulation slack
        # (pallas sums the pre-rounding f32 products — slightly MORE
        # precise than the XLA ref, which sums the rounded bf16 y)
        y0, s0, ss0 = xla_ref(x, w)
        y1, s1, ss1 = matmul_stats(x, w)
        np.testing.assert_allclose(
            np.asarray(y1, np.float32), np.asarray(y0, np.float32),
            rtol=2e-2, atol=2e-1)
        stat_scale = float(np.sqrt(np.mean(np.asarray(ss0))))
        err = np.abs(np.asarray(s1[0]) - np.asarray(s0)) / stat_scale
        assert err.max() < 0.05, ("stats diverge", err.max())

        t_dot = bench(xla_dot_only, x, w)
        t_xla = bench(xla_ref, x, w)
        t_pal = bench(matmul_stats, x, w)
        t_pro = bench(matmul_stats_prologue, x, w, scale, shift)
        print(f"{m:>9} {k:>5} {n:>5} | {t_dot:8.3f} {t_xla:8.3f} "
              f"{t_pal:8.3f} {t_pro:8.3f}")


if __name__ == "__main__":
    main()
