"""Fused-region math for the block pass, and the conv-only stem rewrites.

`analysis.fusion` plans conv->BN(->act), BN(->act) and FC(->act) blocks;
each lowers to ONE region here (`fused_block_*`): a ``jax.custom_vjp``
whose BN/activation backward is hand-written, so XLA sees a single
region boundary per block in each direction.  Beside them live the two
exact rewrites of the input stem (space-to-depth conv, input-BN dX
elision) that `symbol.eval_graph` plans under NHWC.

Numerics match ``ops/nn.py _bn_core``: stats are shifted by the moving
mean to avoid E[x²]-E[x]² cancellation; backward is the same two-pass
formulation.

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


# Block-granularity fusion (ISSUE 6): the graph-level pass lives in
# :mod:`mxnet_tpu.analysis.fusion`; the fused-region math it lowers to
# lives below (`fused_block_*`).
block_fusion, block_fusion_enabled = _trace_flag(
    "MXNET_FUSE_BLOCKS",
    "Context manager enabling the block-granularity fusion pass "
    "(conv+BN+ReLU / FC+activation regions, analysis.fusion) during a "
    "trace.")


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
                            x, w, b, gamma, beta, mm, mv):
    """Evaluate a planned conv->BN(->act) block; returns
    (out, new_mm, new_mv)."""
    eps = float(bn_attrs["eps"])
    momentum = float(bn_attrs["momentum"])
    train_stats = bool(is_train and not bn_attrs.get("use_global_stats"))
    if bn_attrs.get("fix_gamma"):
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    mm32 = mm.astype(jnp.float32)
    mv32 = mv.astype(jnp.float32)
    f = _fused_conv_bn_act_xla(_conv_key(conv_attrs), layout, eps,
                               momentum, train_stats, act, b is not None)
    args = (x, w) + ((b,) if b is not None else ()) + \
        (gamma, beta, mm32, mv32)
    with _block_scope("conv_bn_act" if act else "conv_bn"):
        out, new_mm, new_mv = f(*args)
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
