"""Conv(1x1) + BatchNorm fusion: GEMM with a statistics epilogue.

Why: BN statistics are separate HBM passes over each conv's output —
XLA cannot fuse a reduction into a conv/dot epilogue, and the stats
bucket is ~18% of the ResNet-50 step (docs/perf.md).  ResNet-50's 40
pointwise convs are GEMMs, so a Pallas kernel can produce
``y = x @ w`` and the (shifted) per-channel ``sum`` / ``sum_sq`` of y in
one pass, eliminating the forward stats read entirely for those layers.

Scope: training-mode BatchNorm directly consuming an eligible
Convolution (kernel 1x1, stride 1, pad 0, no bias, single consumer)
under NHWC activations.  The graph pass (`plan_conv_bn_fusion`) runs at
trace time inside :func:`mxnet_tpu.symbol.eval_graph` when enabled via
``conv_bn_fusion(True)`` (ShardedTrainer(fuse_conv_bn=True)) or
``MXNET_FUSE_CONV_BN=1``.

Numerics match ``ops/nn.py _bn_core``: stats are shifted by the moving
mean to avoid E[x²]-E[x]² cancellation; backward is the same two-pass
formulation, with dX/dW as plain GEMMs.

Reference roles: src/operator/batch_norm-inl.h (the BN kernel) and the
reference's fused-op philosophy (optimizer_op.cc); the fusion itself is
TPU-native — the reference relies on cuDNN, which fuses neither.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from ..base import MXNetError


def _trace_flag(env_var, doc):
    """(context_manager_class, enabled_fn) for a tri-state trace flag:
    None -> the env var decides, True/False -> forced by the context."""
    state = {"v": None}

    class Ctx:
        def __init__(self, enable):
            self.enable = enable

        def __enter__(self):
            self._prev = state["v"]
            state["v"] = self.enable
            return self

        def __exit__(self, *exc):
            state["v"] = self._prev

    Ctx.__doc__ = doc

    def enabled():
        if state["v"] is not None:
            return bool(state["v"])
        return os.environ.get(env_var, "0") == "1"

    return Ctx, enabled


conv_bn_fusion, fusion_enabled = _trace_flag(
    "MXNET_FUSE_CONV_BN",
    "Context manager enabling/disabling the conv1x1+BN fusion during a "
    "trace.")

# Block-granularity fusion (ISSUE 6): the graph-level pass lives in
# :mod:`mxnet_tpu.analysis.fusion`; the fused-region math it lowers to
# lives below (`fused_block_*`).  When enabled it supersedes the
# conv1x1-only pass above for every chain the old pass does not claim.
block_fusion, block_fusion_enabled = _trace_flag(
    "MXNET_FUSE_BLOCKS",
    "Context manager enabling the block-granularity fusion pass "
    "(conv+BN+ReLU / FC+activation regions, analysis.fusion) during a "
    "trace.")


# ------------------------------------------------------------ the kernel
def _pick_bm(m):
    for bm in (512, 448, 256, 128, 64, 32, 16, 8):
        if m % bm == 0:
            return bm
    return None


#: the kernel's device name: the ``name=`` of its ``pallas_call``, which
#: XLA makes the custom call's instruction name in a profiler trace
MATMUL_STATS = "mxtpu_matmul_stats"


def _stats_kernel(x_ref, w_ref, c_ref, y_ref, s1_ref, s2_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    y = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    ys = y - c_ref[:]

    @pl.when(i == 0)
    def _init():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    s1_ref[:] += jnp.sum(ys, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(ys * ys, axis=0, keepdims=True)


def _tuned_bm(m, k, n, x_dtype, w_dtype):
    """Tuning-cache row block for this GEMM shape (None on miss/off/
    invalid; emits the cache hit/miss metrics) — the ``bm`` the
    autotuner measured fastest wins over the `_pick_bm` heuristic."""
    try:
        from .. import autotune
        cfg = autotune.kernel_config(
            "matmul_stats", [(m, k), (k, n)],
            [str(x_dtype), str(w_dtype)])
        if cfg:
            bm = int(cfg.get("bm", 0))
            if bm > 0 and m % bm == 0:
                return bm
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(the tuning-cache lookup is advisory; any failure degrades to the heuristic block pick)
        pass
    return None


def matmul_stats(x2d, w2d, c, bm=None, interpret=False):
    """(M,K)@(K,N) -> y (M,N) in x's dtype, plus f32 (N,) sums of
    (y - c) and (y - c)^2.  Pallas on TPU, jnp elsewhere.  ``bm``:
    explicit row-block override (the autotuner measures candidates
    through it); default consults the tuning cache, then the
    `_pick_bm` heuristic.  ``interpret`` runs the Pallas path in
    interpreter mode regardless of backend (CPU tuning/CI)."""
    from ..parallel import mesh as _mesh
    full_m, k = x2d.shape
    full_n = w2d.shape[1]
    # under a multi-device mesh each device runs the kernel on its own
    # (rows/data, columns/model) tile: m, n are the per-device sizes
    mesh = _mesh.active_kernel_mesh()
    row_axis = col_axis = None
    m, n = full_m, full_n
    if mesh is not None:
        # columns split in whole 128-lane groups or not at all
        row_axis, col_axis = _mesh.kernel_axes(mesh, full_m,
                                               full_n // 128)
        m = full_m // (mesh.shape[row_axis] if row_axis else 1)
        n = full_n // (mesh.shape[col_axis] if col_axis else 1)
    # the cache is consulted (and hit/miss counted) ONLY when the
    # Pallas path is actually reachable — a jnp-fallback dispatch must
    # not report a tuned config it never used
    eligible = (_context.on_tpu() or interpret) \
        and n % 128 == 0 and k % 8 == 0
    if eligible:
        if bm is None or m % bm:
            bm = _tuned_bm(m, k, n, x2d.dtype, w2d.dtype) \
                or _pick_bm(m)
    else:
        bm = None
    if eligible and bm is not None:
        # label the chosen M block in the cost database so the block
        # choice is queryable by problem shape (telemetry.costdb;
        # note_kernel never raises into the trace)
        from ..telemetry import costdb
        costdb.note_kernel(
            "matmul_stats", [(m, k), (k, n)],
            [str(x2d.dtype), str(w2d.dtype)],
            flops=2.0 * m * n * k,
            bytes_accessed=float(
                m * k * x2d.dtype.itemsize
                + k * n * w2d.dtype.itemsize
                + m * n * x2d.dtype.itemsize),
            block_config={"bm": int(bm), "grid_m": int(m // bm)})
        c2d = c.reshape(1, full_n).astype(jnp.float32)
        if mesh is None:
            y, s1, s2 = _matmul_stats_call(x2d, w2d, c2d, bm, interpret)
        else:
            from jax.sharding import PartitionSpec as P

            def tile(x, w, cc):
                y, s1, s2 = _matmul_stats_call(x, w, cc, bm, interpret)
                if row_axis is not None:
                    s1, s2 = lax.psum((s1, s2), row_axis)
                return y, s1, s2

            cols = P(None, col_axis)
            y, s1, s2 = _mesh.shard_map_nocheck(
                tile, mesh,
                in_specs=(P(row_axis, None), cols, cols),
                out_specs=(P(row_axis, col_axis), cols, cols),
            )(x2d, w2d, c2d)
        return y, s1[0], s2[0]
    # fallback: plain dot + fused reduces (still correct, not fused)
    y = jnp.dot(x2d, w2d,
                preferred_element_type=jnp.float32)
    ys = y - c.reshape(1, full_n)
    s1 = jnp.sum(ys, axis=0)
    s2 = jnp.sum(ys * ys, axis=0)
    return y.astype(x2d.dtype), s1, s2


def _matmul_stats_call(x2d, w2d, c2d, bm, interpret):
    """The ``pallas_call`` of :func:`matmul_stats` on one device's
    (M,K) @ (K,N) tile; ``c2d`` is (1,N) f32, sums come back (1,N)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x2d.shape
    n = w2d.shape[1]
    return pl.pallas_call(
        _stats_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0),
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
            jax.ShapeDtypeStruct((m, n), x2d.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * x2d.dtype.itemsize
            + k * n * w2d.dtype.itemsize + m * n * x2d.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
        name=MATMUL_STATS,
    )(x2d, w2d, c2d)


# --------------------------------------------- fused conv1x1+BN (train)
@functools.lru_cache(maxsize=None)
def _fused_conv_bn(eps, momentum, relu=False, interpret=False):
    """custom_vjp: NHWC x (N,H,W,K) + OIHW w (N_out,K,1,1) + BN params
    -> (out, mean, var, new_mm, new_mv), _bn_core numerics.  With
    ``relu`` the activation folds into the same region (forward epilogue
    + mask in the hand-written backward) — the conv+BN+ReLU block stays
    one fused dispatch each way (analysis.fusion).  ``interpret`` runs
    the Pallas GEMM in interpreter mode (autotuner A/B on CPU)."""

    # mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
    def fwd_math(x, w, gamma, beta, mm, mv):
        nb, h, wd, k = x.shape
        nout = w.shape[0]
        m = nb * h * wd
        x2d = x.reshape(m, k)
        w2d = jnp.transpose(w.reshape(nout, k)).astype(x.dtype)
        c = lax.stop_gradient(mm.astype(jnp.float32))
        y2d, s1, s2 = matmul_stats(x2d, w2d, c, interpret=interpret)
        meanc = s1 / m
        var = jnp.maximum(s2 / m - jnp.square(meanc), 0.0)
        mean = meanc + c
        new_mm = mm * momentum + mean * (1 - momentum)
        new_mv = mv * momentum + var * (1 - momentum)
        inv = lax.rsqrt(var + eps)
        scale = gamma.astype(jnp.float32) * inv
        shift = beta.astype(jnp.float32) - mean * scale
        out2d = y2d.astype(jnp.float32) * scale + shift
        if relu:
            out2d = jnp.maximum(out2d, 0.0)
        out = out2d.astype(x.dtype).reshape(nb, h, wd, nout)
        return ((out, mean, var, new_mm, new_mv),
                (x, w, y2d, gamma, beta, mean, inv, c))

    @jax.custom_vjp
    def f(x, w, gamma, beta, mm, mv):
        return fwd_math(x, w, gamma, beta, mm, mv)[0]

    def f_fwd(x, w, gamma, beta, mm, mv):
        return fwd_math(x, w, gamma, beta, mm, mv)

    def f_bwd(res, cots):
        x, w, y2d, gamma, beta, mean, inv, c = res
        dout, dmean_o, dvar_o, dmm_o, dmv_o = cots
        nb, h, wd, k = x.shape
        nout = w.shape[0]
        m = nb * h * wd
        x2d = x.reshape(m, k)
        w2d = jnp.transpose(w.reshape(nout, k)).astype(x.dtype)
        dyf = dout.reshape(m, nout).astype(jnp.float32)
        if relu:
            # mask from the recomputed pre-activation (saving it would
            # cost an extra (M, Nout) residual; scale/shift are vectors)
            scale = gamma.astype(jnp.float32) * inv
            shift = beta.astype(jnp.float32) - mean * scale
            pre = y2d.astype(jnp.float32) * scale + shift
            dyf = jnp.where(pre > 0, dyf, 0.0)
        ys = y2d.astype(jnp.float32) - c
        meanc = mean - c
        dbeta = jnp.sum(dyf, axis=0)
        sdyxs = jnp.sum(dyf * ys, axis=0)
        dgamma = (sdyxs - meanc * dbeta) * inv
        a = gamma.astype(jnp.float32) * inv
        dmean = dmean_o + (1 - momentum) * dmm_o
        dvar = dvar_o + (1 - momentum) * dmv_o
        kk = (-a * inv * dgamma + 2.0 * dvar) * (1.0 / m)
        d = -kk * meanc - a * dbeta * (1.0 / m) + dmean * (1.0 / m)
        dY = dyf * a + ys * kk + d                  # (M, Nout) f32
        dYc = dY.astype(x.dtype)
        dx2d = jnp.dot(dYc, jnp.transpose(w2d),
                       preferred_element_type=jnp.float32)
        dw2d = jnp.dot(jnp.transpose(x2d), dYc,
                       preferred_element_type=jnp.float32)
        dx = dx2d.astype(x.dtype).reshape(x.shape)
        # w2d is (K, Nout) = w.reshape(Nout, K).T
        dw = jnp.transpose(dw2d).reshape(w.shape).astype(w.dtype)
        dmm = momentum * dmm_o
        dmv = momentum * dmv_o
        return (dx, dw, dgamma.astype(gamma.dtype),
                dbeta.astype(gamma.dtype), dmm, dmv)

    f.defvjp(f_fwd, f_bwd)
    return f


# mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
def fused_conv_bn_apply(conv_attrs, bn_attrs, is_train, x, w, gamma,
                        beta, mm, mv):
    """Evaluate the fused pair; returns BatchNorm-op-shaped outputs
    (out[, mean, var], new_mm, new_mv)."""
    eps = float(bn_attrs["eps"])
    momentum = float(bn_attrs["momentum"])
    if bn_attrs["fix_gamma"]:
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    f = _fused_conv_bn(eps, momentum)
    out, mean, var, new_mm, new_mv = f(
        x, w, gamma, beta, mm.astype(jnp.float32),
        mv.astype(jnp.float32))
    new_mm = new_mm.astype(mm.dtype)
    new_mv = new_mv.astype(mv.dtype)
    if bn_attrs.get("output_mean_var"):
        return out, mean, var, new_mm, new_mv
    return out, new_mm, new_mv


# ------------------------------------------- block-granularity regions
# The fused-region math the analysis.fusion pass lowers each matched
# chain to.  Every region is a jax.custom_vjp whose backward is
# hand-written, so training keeps ONE fused dispatch per block in each
# direction: XLA sees a single region boundary instead of a
# conv->materialize->stats->materialize->relu chain, and the layout at
# that boundary is pinned by the plan (no relayout between fused
# blocks).  All statics (layout, attrs) are baked into the lru-cache
# key: the custom-vjp backward is traced OUTSIDE the image_layout
# context (jax pulls it when the caller's vjp runs), so nothing in a
# backward may read trace-time globals.


def _conv_key(conv_attrs):
    """Hashable statics of a 2-d Convolution node (region cache key)."""
    kernel = tuple(conv_attrs["kernel"])
    nd = len(kernel)
    return (kernel,
            tuple(conv_attrs["stride"]) or (1,) * nd,
            tuple(conv_attrs["dilate"]) or (1,) * nd,
            tuple(conv_attrs["pad"]) or (0,) * nd,
            int(conv_attrs.get("num_group", 1)))


def _conv2d_fn(conv_key, layout):
    """(x, w_oihw) -> y for one conv static config, layout baked in
    (mirrors ops/nn.py `convolution` for the respective layout)."""
    kernel, stride, dilate, pad, groups = conv_key

    def conv(x, w):
        if layout == "NHWC":
            dn = lax.conv_dimension_numbers(
                x.shape, w.shape[2:] + w.shape[1:2] + w.shape[:1],
                ("NHWC", "HWIO", "NHWC"))
            w_ = jnp.transpose(w, (2, 3, 1, 0))
        else:
            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NCHW", "OIHW", "NCHW"))
            w_ = w
        return lax.conv_general_dilated(
            x, w_, window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=dn, feature_group_count=groups)

    return conv


# mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
def _bn_epilogue_fwd(yf, gamma, beta, mm, mv, red, bshape, eps,
                     momentum, train_stats, act):
    """Shared BN(+act) forward epilogue over a pre-computed f32 tensor.
    Returns (out_f32, new_mm, new_mv, mean, inv)."""
    if train_stats:
        n = 1
        for i in red:
            n *= yf.shape[i]
        # shifted single-pass stats, same formulation as ops/nn._bn_core
        c = lax.stop_gradient(mm.astype(jnp.float32))
        ys = yf - c.reshape(bshape)
        s1 = jnp.sum(ys, axis=red)
        s2 = jnp.sum(jnp.square(ys), axis=red)
        meanc = s1 / n
        var = jnp.maximum(s2 / n - jnp.square(meanc), 0.0)
        mean = meanc + c
        new_mm = mm * momentum + mean * (1 - momentum)
        new_mv = mv * momentum + var * (1 - momentum)
    else:
        mean = mm.astype(jnp.float32)
        var = mv.astype(jnp.float32)
        new_mm, new_mv = mm, mv
    inv = lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    out = yf * scale.reshape(bshape) + shift.reshape(bshape)
    if act == "relu":
        out = jnp.maximum(out, 0.0)
    return out, new_mm, new_mv, mean, inv


# mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
def _bn_epilogue_bwd(dout, yf, gamma, beta, mean, inv, mm, red, bshape,
                     momentum, train_stats, act, dmm_o, dmv_o):
    """Shared BN(+act) backward: cotangent of the epilogue's input
    tensor plus the BN parameter/aux gradients.  Returns
    (dY_f32, dgamma, dbeta, dmm, dmv)."""
    dyf = dout.astype(jnp.float32)
    a = gamma.astype(jnp.float32) * inv
    if act == "relu":
        # mask from the recomputed pre-activation (vector scale/shift;
        # saving the mask would cost a full-tensor residual)
        scale = a
        shift = beta.astype(jnp.float32) - mean * scale
        pre = yf * scale.reshape(bshape) + shift.reshape(bshape)
        dyf = jnp.where(pre > 0, dyf, 0.0)
    # shifted by c = the moving mean snapshot (== mean in eval mode)
    c = lax.stop_gradient(mm.astype(jnp.float32))
    ys = yf - c.reshape(bshape)
    meanc = mean - c
    dbeta = jnp.sum(dyf, axis=red)
    sdyxs = jnp.sum(dyf * ys, axis=red)
    dgamma = (sdyxs - meanc * dbeta) * inv
    if train_stats:
        n = 1
        for i in red:
            n *= yf.shape[i]
        dmean = (1 - momentum) * dmm_o
        dvar = (1 - momentum) * dmv_o
        k = (-a * inv * dgamma + 2.0 * dvar) * (1.0 / n)
        d = -k * meanc - a * dbeta * (1.0 / n) + dmean * (1.0 / n)
        dY = (dyf * a.reshape(bshape) + ys * k.reshape(bshape)
              + d.reshape(bshape))
        dmm = momentum * dmm_o
        dmv = momentum * dmv_o
    else:
        dY = dyf * a.reshape(bshape)
        dmm, dmv = dmm_o, dmv_o
    return dY, dgamma, dbeta, dmm, dmv


@functools.lru_cache(maxsize=None)
def _fused_conv_bn_act_xla(conv_key, layout, eps, momentum, train_stats,
                           act, has_bias):
    """General conv->BN(->act) region (any 2-d conv, NCHW or NHWC):
    f(x, w[, b], gamma, beta, mm, mv) -> (out, new_mm, new_mv).
    Backward: BN/act math hand-written (one reduce pass + one dY pass),
    conv dX/dW via jax.vjp of the conv closure — still one region."""
    conv = _conv2d_fn(conv_key, layout)
    ch = 3 if layout == "NHWC" else 1
    red = tuple(i for i in range(4) if i != ch)

    def bias_shape(nout):
        return (1, nout, 1, 1) if ch == 1 else (nout,)

    def fwd_math(x, w, b, gamma, beta, mm, mv):
        from .nn import _mxu_out
        y = _mxu_out(conv(x, w).astype(x.dtype))
        if b is not None:
            y = y + b.reshape(bias_shape(b.shape[0])).astype(x.dtype)
        bshape = tuple(1 if i != ch else y.shape[ch] for i in range(4))
        yf = y.astype(jnp.float32)
        out, new_mm, new_mv, mean, inv = _bn_epilogue_fwd(
            yf, gamma, beta, mm, mv, red, bshape, eps, momentum,
            train_stats, act)
        res = (x, w, y, gamma, beta, mean, inv, mm)
        return (out.astype(x.dtype), new_mm, new_mv), res

    def bwd_math(res, cots):
        x, w, y, gamma, beta, mean, inv, mm = res
        dout, dmm_o, dmv_o = cots
        bshape = tuple(1 if i != ch else y.shape[ch] for i in range(4))
        dY, dgamma, dbeta, dmm, dmv = _bn_epilogue_bwd(
            dout, y.astype(jnp.float32), gamma, beta, mean, inv, mm,
            red, bshape, momentum, train_stats, act, dmm_o, dmv_o)
        dYc = dY.astype(x.dtype)
        _, cvjp = jax.vjp(lambda xx, ww: conv(xx, ww).astype(x.dtype),
                          x, w)
        dx, dw = cvjp(dYc)
        db = jnp.sum(dY, axis=red)
        return (dx, dw, db, dgamma.astype(gamma.dtype),
                dbeta.astype(beta.dtype), dmm, dmv)

    if has_bias:
        @jax.custom_vjp
        def f(x, w, b, gamma, beta, mm, mv):
            return fwd_math(x, w, b, gamma, beta, mm, mv)[0]

        def f_fwd(x, w, b, gamma, beta, mm, mv):
            out, res = fwd_math(x, w, b, gamma, beta, mm, mv)
            return out, res + (b,)

        def f_bwd(res, cots):
            b = res[-1]
            dx, dw, db, dgamma, dbeta, dmm, dmv = bwd_math(res[:-1],
                                                           cots)
            # db accumulates in f32; the cotangent aval must match the
            # primal bias (bf16 under the trainer's compute view)
            return dx, dw, db.astype(b.dtype), dgamma, dbeta, dmm, dmv

        f.defvjp(f_fwd, f_bwd)
        return f

    @jax.custom_vjp
    def f(x, w, gamma, beta, mm, mv):
        return fwd_math(x, w, None, gamma, beta, mm, mv)[0]

    def f_fwd(x, w, gamma, beta, mm, mv):
        return fwd_math(x, w, None, gamma, beta, mm, mv)

    def f_bwd(res, cots):
        dx, dw, _db, dgamma, dbeta, dmm, dmv = bwd_math(res, cots)
        return dx, dw, dgamma, dbeta, dmm, dmv

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _fused_bn_act_xla(eps, momentum, train_stats, ch, ndim, act):
    """BN(->act) region for chains whose producer is not a fusable
    conv (pre-activation nets are full of BN->ReLU pairs):
    f(x, gamma, beta, mm, mv) -> (out, new_mm, new_mv)."""
    red = tuple(i for i in range(ndim) if i != ch)

    # mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
    def fwd_math(x, gamma, beta, mm, mv):
        bshape = tuple(1 if i != ch else x.shape[ch] for i in range(ndim))
        xf = x.astype(jnp.float32)
        out, new_mm, new_mv, mean, inv = _bn_epilogue_fwd(
            xf, gamma, beta, mm, mv, red, bshape, eps, momentum,
            train_stats, act)
        return ((out.astype(x.dtype), new_mm, new_mv),
                (x, gamma, beta, mean, inv, mm))

    @jax.custom_vjp
    def f(x, gamma, beta, mm, mv):
        return fwd_math(x, gamma, beta, mm, mv)[0]

    def f_fwd(x, gamma, beta, mm, mv):
        return fwd_math(x, gamma, beta, mm, mv)

    def f_bwd(res, cots):
        x, gamma, beta, mean, inv, mm = res
        dout, dmm_o, dmv_o = cots
        bshape = tuple(1 if i != ch else x.shape[ch] for i in range(ndim))
        dY, dgamma, dbeta, dmm, dmv = _bn_epilogue_bwd(
            dout, x.astype(jnp.float32), gamma, beta, mean, inv, mm,
            red, bshape, momentum, train_stats, act, dmm_o, dmv_o)
        return (dY.astype(x.dtype), dgamma.astype(gamma.dtype),
                dbeta.astype(beta.dtype), dmm, dmv)

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _fused_fc_act_xla(act, flatten, has_bias):
    """FullyConnected(->act) region: f(x, w[, b]) -> out with the
    activation derivative folded into the hand-written backward, so the
    matmul->bias->act block is one fused dispatch each way."""

    def act_fwd(y):
        if act == "relu":
            return jnp.maximum(y, 0)
        if act == "sigmoid":
            return jax.nn.sigmoid(y)
        if act == "tanh":
            return jnp.tanh(y)
        raise MXNetError("unfusable activation %r" % (act,))

    def act_grad(out, g):
        if act == "relu":
            return jnp.where(out > 0, g, jnp.zeros_like(g))
        if act == "sigmoid":
            return g * out * (1 - out)
        if act == "tanh":
            return g * (1 - jnp.square(out))
        raise MXNetError("unfusable activation %r" % (act,))

    def fwd_math(x, w, b):
        from .nn import _mxu_out
        x2 = x.reshape((x.shape[0], -1)) if flatten and x.ndim > 2 else x
        y = jnp.dot(x2, w.T)
        if b is not None:
            y = y + b
        out = act_fwd(_mxu_out(y.astype(x.dtype)))
        return out, (x, w, out)

    def bwd_math(res, g):
        x, w, out = res
        x2 = x.reshape((x.shape[0], -1)) if flatten and x.ndim > 2 else x
        gy = act_grad(out, g).astype(x.dtype)
        # flatten=False keeps leading batch dims (y = x @ w.T on rank-n
        # x, ops/nn.py): contract ALL of them, not just axis 0
        red = tuple(range(gy.ndim - 1))
        dx2 = jnp.dot(gy, w)
        dw = jnp.tensordot(gy, x2, axes=(red, red))
        db = jnp.sum(gy.astype(jnp.float32), axis=red)
        return dx2.reshape(x.shape).astype(x.dtype), \
            dw.astype(w.dtype), db

    if has_bias:
        @jax.custom_vjp
        def f(x, w, b):
            return fwd_math(x, w, b)[0]

        def f_fwd(x, w, b):
            out, res = fwd_math(x, w, b)
            return out, res + (b,)

        def f_bwd(res, g):
            dx, dw, db = bwd_math(res[:-1], g)
            # the cotangent aval must match the primal bias, which may
            # not share the weight's dtype (caller-bound executor args)
            return dx, dw, db.astype(res[-1].dtype)

        f.defvjp(f_fwd, f_bwd)
        return f

    @jax.custom_vjp
    def f(x, w):
        return fwd_math(x, w, None)[0]

    def f_fwd(x, w):
        return fwd_math(x, w, None)

    def f_bwd(res, g):
        dx, dw, _db = bwd_math(res, g)
        return dx, dw

    f.defvjp(f_fwd, f_bwd)
    return f


def _block_scope(kind):
    """``jax.named_scope`` round one fused block's region (its kinds are
    analysis.fusion's): every op of the block, forward and backward,
    and the relayouts XLA puts beside it carry ``mxtpu.block.<kind>``
    in their ``op_name``."""
    return jax.named_scope("mxtpu.block." + kind)


# mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
def fused_block_conv_bn_act(conv_attrs, bn_attrs, layout, is_train, act,
                            pallas, x, w, b, gamma, beta, mm, mv,
                            interpret=False):
    """Evaluate a planned conv->BN(->act) block; returns
    (out, new_mm, new_mv).  ``pallas`` routes the eligible 1x1 case
    through the matmul-with-stats-epilogue kernel (`matmul_stats`);
    everything else runs the general single-region custom_vjp.
    ``interpret`` runs the Pallas leg in interpreter mode (the
    autotuner's CPU A/B; never set on the training path)."""
    eps = float(bn_attrs["eps"])
    momentum = float(bn_attrs["momentum"])
    train_stats = bool(is_train and not bn_attrs.get("use_global_stats"))
    if bn_attrs.get("fix_gamma"):
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    mm32 = mm.astype(jnp.float32)
    mv32 = mv.astype(jnp.float32)
    if pallas and train_stats and b is None and layout == "NHWC":
        f = _fused_conv_bn(eps, momentum, relu=(act == "relu"),
                           interpret=interpret)
        args = (x, w, gamma, beta, mm32, mv32)
    else:
        f = _fused_conv_bn_act_xla(_conv_key(conv_attrs), layout, eps,
                                   momentum, train_stats, act,
                                   b is not None)
        args = (x, w) + ((b,) if b is not None else ()) + \
            (gamma, beta, mm32, mv32)
    with _block_scope("conv_bn_act" if act else "conv_bn"):
        # the Pallas leg also returns the batch mean and variance
        out, *_, new_mm, new_mv = f(*args)
    return out, new_mm.astype(mm.dtype), new_mv.astype(mv.dtype)


# mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
def fused_block_bn_act(bn_attrs, ch, is_train, act, x, gamma, beta, mm,
                       mv):
    """Evaluate a planned BN(->act) block; returns
    (out, new_mm, new_mv)."""
    eps = float(bn_attrs["eps"])
    momentum = float(bn_attrs["momentum"])
    train_stats = bool(is_train and not bn_attrs.get("use_global_stats"))
    if bn_attrs.get("fix_gamma"):
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    f = _fused_bn_act_xla(eps, momentum, train_stats, ch, x.ndim, act)
    with _block_scope("bn_act"):
        out, new_mm, new_mv = f(x, gamma, beta, mm.astype(jnp.float32),
                                mv.astype(jnp.float32))
    return out, new_mm.astype(mm.dtype), new_mv.astype(mv.dtype)


def fused_block_fc_act(fc_attrs, act, x, w, b):
    """Evaluate a planned FullyConnected(->act) block."""
    f = _fused_fc_act_xla(act, bool(fc_attrs.get("flatten", True)),
                          b is not None)
    with _block_scope("fc_act"):
        return f(x, w, b) if b is not None else f(x, w)


# ---------------------------------------------------------- graph pass
def _conv_eligible(node):
    a = node.attrs
    kernel = tuple(a.get("kernel") or ())
    stride = tuple(a.get("stride") or ()) or (1,) * len(kernel)
    pad = tuple(a.get("pad") or ()) or (0,) * len(kernel)
    dilate = tuple(a.get("dilate") or ()) or (1,) * len(kernel)
    return (kernel == (1, 1) and stride == (1, 1) and pad == (0, 0)
            and dilate == (1, 1) and int(a.get("num_group", 1)) == 1
            and bool(a.get("no_bias")))


def plan_conv_bn_fusion(topo, entries=()):
    """id(BatchNorm node) -> Convolution node for fusable pairs; plus the
    set of conv-node ids to skip.  A conv is fusable when it feeds
    EXACTLY its BatchNorm and nothing else (graph heads count as uses)."""
    uses = {}
    for node in topo:
        for (src, _i) in node.inputs:
            uses[id(src)] = uses.get(id(src), 0) + 1
    for (node, _i) in entries:
        uses[id(node)] = uses.get(id(node), 0) + 1
    plan, skip = {}, set()
    for node in topo:
        if node.is_variable or node.op is None:
            continue
        if node.op.name != "BatchNorm":
            continue
        if node.attrs.get("use_global_stats"):
            continue
        if int(node.attrs.get("axis", 1)) != 1:
            continue
        src, idx = node.inputs[0]
        if (src.is_variable or src.op is None
                or src.op.name != "Convolution" or idx != 0):
            continue
        if uses.get(id(src), 0) != 1 or not _conv_eligible(src):
            continue
        plan[id(node)] = src
        skip.add(id(src))
    return plan, skip


# ------------------------------------------- pointwise conv as a dot
# A 1x1/s1/p0 conv IS a GEMM over flattened spatial positions.  XLA:TPU
# lowers convolutions through the conv library (opaque to fusion) but
# dots through the standard MXU emitter, which CAN fuse elementwise
# producers/consumers — the BN normalize/ReLU passes around ResNet's 40
# pointwise convs could fold into the GEMM's operand reads.
conv1x1_dot, conv1x1_dot_enabled = _trace_flag(
    "MXNET_CONV1X1_DOT",
    "Context manager lowering eligible pointwise convs as dots.")


def conv1x1_as_dot(x, w_hwio):
    """x NHWC, w (1, 1, I, O) -> conv output via a flattened dot."""
    nb, h, wd, cin = x.shape
    nout = w_hwio.shape[3]
    y = jnp.dot(x.reshape(nb * h * wd, cin),
                w_hwio.reshape(cin, nout))
    return y.reshape(nb, h, wd, nout).astype(x.dtype)


# --------------------------------- phase-decomposed stride-2 backward
# XLA computes backward-data of a strided conv as a conv over the
# lhs-dilated cotangent: for stride 2, ~3/4 of the MACs multiply
# inserted zeros.  The exact phase decomposition removes every wasted
# MAC: output positions of parity (r_h, r_w) only receive kernel taps of
# matching parity, so dX splits into 4 dense stride-1 convs of dY with
# the parity sub-kernels, interleaved back (depth-to-space).  Derivation
# (per dim, stride 2, pad P, kernel k):
#
#   dX[i] = sum_{a ≡ (i+P) mod 2} dY[(i+P-a)/2] * W[a]
#         = sum_u dY[q-u] * W[r+2u],  q = floor((i+P)/2), r = (i+P) mod 2
#
# — a correlation of dY with the reversed parity-r sub-kernel, offset so
# q' = q - ku + 1 (left pad ku-1-q_lo, right pad q_max-Ho+1; negative
# pads crop).  Mathematically exact; bitwise it differs from the dilated
# form only in f32 accumulation order.  Enabled per-trace by the
# ``phase_bwd`` context (ShardedTrainer strided_bwd_phase=True).
phase_bwd, phase_bwd_enabled = _trace_flag(
    "MXNET_PHASE_BWD",
    "Context manager enabling the stride-2 backward decomposition.")


def _phase_ranges(k, pad, h_in, h_out):
    """Per-parity (ku, q_lo, pad_l, pad_r, i0) for one spatial dim."""
    out = []
    for r in (0, 1):
        ku = max(0, (k - r + 1) // 2)          # taps a = r, r+2, ... < k
        # i = 2q + r - pad ranges over [0, h_in): q in [q_lo, q_lo + h/2)
        q_lo = max(0, (pad - r + 1) // 2)
        i0 = 2 * q_lo + r - pad
        n = h_in // 2
        q_max = q_lo + n - 1
        pad_l = ku - 1 - q_lo
        pad_r = q_max - h_out + 1
        out.append((ku, q_lo, pad_l, pad_r, i0))
    return out


def _phase_bwd_dx(dy, w_hwio, pads, x_shape):
    """Exact dX of a stride-2 NHWC/HWIO conv via phase decomposition."""
    kh, kw = w_hwio.shape[0], w_hwio.shape[1]
    nb, h, wd, cin = x_shape
    ho, wo = dy.shape[1], dy.shape[2]
    wt = jnp.transpose(w_hwio, (0, 1, 3, 2))     # contraction over cout
    rows = _phase_ranges(kh, pads[0][0], h, ho)
    cols = _phase_ranges(kw, pads[1][0], wd, wo)
    # phases keyed by output-row parity i0 (each is 0 or 1 exactly once)
    zs = {}
    for (kuh, _qh, plh, prh, i0h) in rows:
        for (kuw, _qw, plw, prw, i0w) in cols:
            rh = (i0h + pads[0][0]) % 2
            rw = (i0w + pads[1][0]) % 2
            if kuh == 0 or kuw == 0:
                zs[(i0h, i0w)] = jnp.zeros(
                    (nb, h // 2, wd // 2, cin), dy.dtype)
                continue
            sub = wt[rh::2, rw::2]               # (kuh, kuw, cout, cin)
            sub = sub[::-1, ::-1]                # reversed correlation
            dn = lax.conv_dimension_numbers(dy.shape, sub.shape,
                                            ("NHWC", "HWIO", "NHWC"))
            zs[(i0h, i0w)] = lax.conv_general_dilated(
                dy, sub, window_strides=(1, 1),
                padding=((plh, prh), (plw, prw)),
                dimension_numbers=dn)
    # interleave: dX[:, 2q+i0h, 2p+i0w, :] = zs[(i0h, i0w)][:, q, p, :]
    w_even = jnp.stack([zs[(0, 0)], zs[(0, 1)]], axis=3)
    w_odd = jnp.stack([zs[(1, 0)], zs[(1, 1)]], axis=3)
    row_even = w_even.reshape(nb, h // 2, wd, cin)
    row_odd = w_odd.reshape(nb, h // 2, wd, cin)
    full = jnp.stack([row_even, row_odd], axis=2)
    return full.reshape(nb, h, wd, cin)


@functools.lru_cache(maxsize=None)
def _phase_bwd_conv(pads):
    """Stride-2 NHWC x HWIO conv whose backward-data uses the phase
    decomposition (backward-filter unchanged)."""

    def conv(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        return lax.conv_general_dilated(
            x, w, window_strides=(2, 2), padding=pads,
            dimension_numbers=dn)

    @jax.custom_vjp
    def f(x, w):
        return conv(x, w)

    def f_fwd(x, w):
        return conv(x, w), (x, w)

    def f_bwd(res, dy):
        x, w = res
        _, wvjp = jax.vjp(lambda ww: conv(x, ww), w)
        (dw,) = wvjp(dy)
        dx = _phase_bwd_dx(dy, w, pads, x.shape)
        return dx.astype(x.dtype), dw

    f.defvjp(f_fwd, f_bwd)
    return f


def phase_bwd_eligible(x_shape, kernel, stride, pad, dilate, num_group):
    return (len(kernel) == 2 and tuple(stride) == (2, 2)
            and tuple(dilate) == (1, 1) and int(num_group) == 1
            and x_shape[1] % 2 == 0 and x_shape[2] % 2 == 0)


def phase_bwd_conv_nhwc(x, w_hwio, pads):
    """Entry point for ops/nn.py: stride-2 conv with decomposed bwd."""
    return _phase_bwd_conv(tuple(pads))(x, w_hwio)


# ------------------------------------------- space-to-depth stem conv
# MLPerf-style stem optimization: the 7x7/s2 conv on C=3 input wastes
# the 128-wide MXU (3 input channels).  Factor-2 space-to-depth turns it
# into an EXACTLY equivalent 4x4/s1 conv on 12 channels at half spatial
# resolution.  Derivation: with a' = kh-3 = 2u+ph (ph in {0,1}),
#   out(x,y) = sum W[a,b] X[2x+a-3, 2y+b-3]
#            = sum_{u,v,ph,pw} W[2u+ph+3, 2v+pw+3] X2[x+u, y+v, (ph,pw,:)]
# i.e. a 4x4 conv (u,v in -2..1) with asymmetric padding (2,1).
stem_s2d, stem_s2d_enabled = _trace_flag(
    "MXNET_STEM_S2D",
    "Context manager enabling the stem rewrite during a trace.")


# ------------------------------------------- input-BN conv dX elision
# In nets whose first layers are data -> BatchNorm(fix_gamma=True) ->
# Convolution (the reference ResNet family), the stem conv's backward-
# data pass exists ONLY to feed the input BN's beta gradient
# (dbeta = sum_nhw conv_dX; the data itself is never differentiated and
# fix_gamma kills dgamma).  That transposed conv is ~4% of the ResNet-50
# step (docs/perf.md "conv1 dX") and is MXU-hostile (3/12 input
# channels).  The channel-sums of dX are computable EXACTLY without it:
#
#   sum_{n,i,j} dX[n,i,j,c]
#     = sum_{a,b,o} W[a,b,c,o] * sum_{n, (p,q) in valid(a) x valid(b)} dY
#
# where valid(a) is the CONTIGUOUS range of output rows whose tap ``a``
# lands in-bounds — so each tap's term is a rectangle sum on the
# integral image of the batch-reduced dY.  The elided conv returns a
# constant-per-channel fake dX carrying those exact sums (sum-preserving
# broadcast), which the BN backward reduces back to dbeta; XLA DCEs
# everything else dX fed (the dead data gradient).
#
# SAFETY: only valid when the conv input's cotangent is consumed by
# channel-sums alone — i.e. the BN input is a non-differentiated batch
# variable and fix_gamma is set.  eval_graph plans it only for convs fed
# by such a BN, and only when the caller declares its batch-variable
# names via ``elide_input_grads`` (ShardedTrainer does: its vjp is over
# params only).  Executor/autograd paths, which may request data
# gradients (adversarial examples), never enable it.
_ELIDE_NAMES = None


class elide_input_grads:
    """Context manager declaring batch-input variable names whose
    gradients the caller will never request."""

    def __init__(self, names):
        self.names = frozenset(names) if names else frozenset()

    def __enter__(self):
        global _ELIDE_NAMES
        self._prev = _ELIDE_NAMES
        _ELIDE_NAMES = self.names
        return self

    def __exit__(self, *exc):
        global _ELIDE_NAMES
        _ELIDE_NAMES = self._prev


def elide_names():
    return _ELIDE_NAMES or frozenset()


def plan_input_bn_elide(topo, entries, names):
    """{id(conv node)} whose backward-data pass can be elided: 2-d
    no-bias group-1 convs consuming (only they) a BatchNorm with
    fix_gamma whose data input is one of ``names``."""
    if not names:
        return set()
    uses = {}
    for node in topo:
        for (src, _i) in node.inputs:
            uses[id(src)] = uses.get(id(src), 0) + 1
    for (node, _i) in entries:
        uses[id(node)] = uses.get(id(node), 0) + 1
    out = set()
    for node in topo:
        if node.is_variable or node.op is None:
            continue
        if node.op.name != "Convolution":
            continue
        a = node.attrs
        if (len(tuple(a.get("kernel") or ())) != 2
                or int(a.get("num_group", 1)) != 1
                or not a.get("no_bias")):
            continue
        src, idx = node.inputs[0]
        if (src.is_variable or src.op is None or idx != 0
                or src.op.name != "BatchNorm"
                or not src.attrs.get("fix_gamma", True)
                or uses.get(id(src), 0) != 1):
            continue
        data_src = _follow_passthrough(src.inputs[0][0])
        if data_src is not None and data_src.is_variable \
                and data_src.name in names:
            out.add(id(node))
    return out


def _follow_passthrough(node):
    """Walk back through shape/value-preserving single-use pass-through
    nodes (identity/_copy — the reference resnet's ``sym.identity`` stem
    wrapper).  Gradient flow through them is the identity, so plans that
    reason about a producer chain may look through them.  Returns the
    first non-pass-through node, or None on a malformed chain."""
    seen = 0
    while (node is not None and not node.is_variable
           and node.op is not None
           and node.op.name in ("identity", "_copy")):
        if not node.inputs:
            return None
        node = node.inputs[0][0]
        seen += 1
        if seen > 32:  # defensive: no such chain is legitimate
            return None
    return node


def _tap_range(a, stride, pad_lo, dilate, size_in, size_out):
    """Inclusive (lo, hi) range of output positions whose tap ``a`` reads
    an in-bounds input element; empty when lo > hi."""
    off = a * dilate - pad_lo
    # p >= ceil(-off / stride), p <= floor((size_in - 1 - off) / stride)
    lo = max(0, (-off + stride - 1) // stride) if off < 0 else 0
    hi = min(size_out - 1, (size_in - 1 - off) // stride)
    return lo, hi


# mxlint: allow-dtype-widening(bn epilogue folds statistics in f32 by contract)
def _dx_channel_sums(dy, w_hwio, strides, padding, dilate, in_h, in_w):
    """Exact (C,) sums over n,h,w of the conv's backward-data cotangent,
    via rectangle sums on the integral image of the batch-reduced dY."""
    kh, kw = w_hwio.shape[0], w_hwio.shape[1]
    ho, wo = dy.shape[1], dy.shape[2]
    d = jnp.sum(dy.astype(jnp.float32), axis=0)          # (Ho, Wo, O)
    integ = jnp.pad(jnp.cumsum(jnp.cumsum(d, axis=0), axis=1),
                    ((1, 0), (1, 0), (0, 0)))
    rows = [_tap_range(a, strides[0], padding[0][0], dilate[0], in_h, ho)
            for a in range(kh)]
    cols = [_tap_range(b, strides[1], padding[1][0], dilate[1], in_w, wo)
            for b in range(kw)]
    taps = []
    for rlo, rhi in rows:
        row_taps = []
        for clo, chi in cols:
            if rlo > rhi or clo > chi:
                row_taps.append(jnp.zeros((d.shape[-1],), jnp.float32))
                continue
            row_taps.append(integ[rhi + 1, chi + 1] - integ[rlo, chi + 1]
                            - integ[rhi + 1, clo] + integ[rlo, clo])
        taps.append(jnp.stack(row_taps))
    rect = jnp.stack(taps)                               # (kh, kw, O)
    return jnp.einsum("abio,abo->i", w_hwio.astype(jnp.float32), rect)


@functools.lru_cache(maxsize=None)
def _elided_conv(strides, padding, dilate):
    """NHWC x HWIO conv whose backward-data is replaced by the exact
    sum-preserving constant broadcast (see module comment above)."""

    def conv(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        return lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            rhs_dilation=dilate, dimension_numbers=dn)

    @jax.custom_vjp
    def f(x, w):
        return conv(x, w)

    def f_fwd(x, w):
        return conv(x, w), (x, w)

    def f_bwd(res, dy):
        x, w = res
        _, wvjp = jax.vjp(lambda ww: conv(x, ww), w)
        (dw,) = wvjp(dy)
        s = _dx_channel_sums(dy, w, strides, padding, dilate,
                             x.shape[1], x.shape[2])
        m = x.shape[0] * x.shape[1] * x.shape[2]
        dx = jnp.broadcast_to((s / m).astype(x.dtype), x.shape)
        return dx, dw

    f.defvjp(f_fwd, f_bwd)
    return f


def elided_conv_apply(attrs, x, w):
    """Evaluate an elide-planned Convolution node (NHWC activations,
    reference-OIHW weight), mirroring ops/nn.py `convolution`."""
    from .nn import _mxu_out
    kernel = tuple(attrs["kernel"])
    nd = len(kernel)
    stride = tuple(attrs["stride"]) or (1,) * nd
    dilate = tuple(attrs["dilate"]) or (1,) * nd
    pad = tuple(attrs["pad"]) or (0,) * nd
    w_hwio = jnp.transpose(w, (2, 3, 1, 0))
    f = _elided_conv(tuple(stride), tuple((p, p) for p in pad),
                     tuple(dilate))
    return _mxu_out(f(x, w_hwio).astype(x.dtype))


def _stem_eligible(node):
    a = node.attrs
    return (tuple(a.get("kernel") or ()) == (7, 7)
            and (tuple(a.get("stride") or ()) or (1, 1)) == (2, 2)
            and (tuple(a.get("pad") or ()) or (0, 0)) == (3, 3)
            and (tuple(a.get("dilate") or ()) or (1, 1)) == (1, 1)
            and int(a.get("num_group", 1)) == 1 and bool(a.get("no_bias")))


def plan_stem_s2d(topo):
    """{id(conv node)} for stem convs fed by the input pipeline: a data
    variable, possibly through identity/_copy wrappers and/or an input
    BatchNorm (the reference resnet v2's ``id`` + ``bn_data`` chain —
    shape-preserving, so the s2d rewrite of the conv stays exact)."""
    out = set()
    for node in topo:
        if node.is_variable or node.op is None:
            continue
        if node.op.name != "Convolution" or not _stem_eligible(node):
            continue
        src = _follow_passthrough(node.inputs[0][0])
        if (src is not None and not src.is_variable and src.op is not None
                and src.op.name == "BatchNorm"):
            src = _follow_passthrough(src.inputs[0][0])
        if src is not None and src.is_variable:
            out.add(id(node))
    return out


def stem_s2d_conv(x, w, elide=False):
    """x: NHWC (N, H, W, 3) with H, W even; w: OIHW (O, C, 7, 7).
    Returns the identical conv1 output at (N, H/2, W/2, O).

    ``elide=True`` swaps the inner conv's backward-data pass for the
    exact channel-sum elision (`_elided_conv`); valid only under an
    active `elide_input_grads` plan.  The sum-preserving fake dX
    backpropagates through the (bijective) space-to-depth rearrangement,
    so the upstream BN still receives exact channel sums."""
    nb, h, wd, cin = x.shape
    nout = w.shape[0]
    # space-to-depth 2x2, phase-major channels (ph, pw, i)
    x2 = x.reshape(nb, h // 2, 2, wd // 2, 2, cin)
    x2 = jnp.transpose(x2, (0, 1, 3, 2, 4, 5))      # N, H2, W2, ph, pw, C
    x2 = x2.reshape(nb, h // 2, wd // 2, 4 * cin)
    # weight: W2[(u+2),(v+2),(ph,pw,i),o] = W[o,i,2u+ph+3,2v+pw+3]
    wp = jnp.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))  # offsets -4..3
    # wp index a = a'+4 = 2u+ph+4 = 2(u+2)+ph ; split into (u+2, ph)
    w6 = wp.reshape(nout, cin, 4, 2, 4, 2)          # O, C, u, ph, v, pw
    w2 = jnp.transpose(w6, (2, 4, 3, 5, 1, 0))      # u, v, ph, pw, C, O
    w2 = w2.reshape(4, 4, 4 * cin, nout).astype(x.dtype)
    if elide:
        f = _elided_conv((1, 1), ((2, 1), (2, 1)), (1, 1))
        return f(x2, w2)
    import jax.lax as _lax
    dn = _lax.conv_dimension_numbers(x2.shape, w2.shape,
                                     ("NHWC", "HWIO", "NHWC"))
    return _lax.conv_general_dilated(
        x2, w2, window_strides=(1, 1), padding=((2, 1), (2, 1)),
        dimension_numbers=dn)
