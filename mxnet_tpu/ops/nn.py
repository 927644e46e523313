"""Neural-network layer ops (the reference's `src/operator/*-inl.h` corpus).

Each op is a pure JAX function over jnp/lax; layout is NCHW to match the
reference default.  Convs and matmuls are expressed with
``lax.conv_general_dilated`` / ``jnp.dot`` so XLA tiles them onto the MXU;
elementwise pieces are left for XLA to fuse.

Reference citations per op are in each docstring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import register


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        t = tuple(int(x) for x in v)
        return t if len(t) == n else t * n if len(t) == 1 else t
    return (int(v),) * n


# ----------------------------------------------------------------- layout
# Global image-layout mode for the conv/pool/batchnorm family.  The symbol
# graphs are written against the reference's NCHW convention; on TPU the
# MXU/vector units want the channel dim minor (NHWC), so the performant
# path (ShardedTrainer(layout="NHWC")) activates this flag *at trace time*
# and feeds NHWC activations end-to-end instead of paying per-op
# transposes.  Weights keep the reference OIHW layout (cheap per-step
# transpose, preserves checkpoint compatibility).
_IMAGE_LAYOUT = "NCHW"


class image_layout:
    """Context manager selecting the activation layout ('NCHW'/'NHWC')
    seen by Convolution/Pooling/BatchNorm during tracing."""

    def __init__(self, layout):
        if layout not in ("NCHW", "NHWC"):
            raise MXNetError("unsupported image layout %r" % (layout,))
        self.layout = layout

    def __enter__(self):
        global _IMAGE_LAYOUT
        self._prev = _IMAGE_LAYOUT
        _IMAGE_LAYOUT = self.layout
        return self

    def __exit__(self, *exc):
        global _IMAGE_LAYOUT
        _IMAGE_LAYOUT = self._prev
        return False


def current_image_layout():
    return _IMAGE_LAYOUT


def _is_nhwc(data):
    """True when a 4-d activation flows channel-minor (trainer NHWC mode)."""
    return data.ndim == 4 and _IMAGE_LAYOUT == "NHWC"


def _ch_axis(data):
    return 3 if _is_nhwc(data) else 1


# Ops that index the channel axis but have no NHWC adaptation; a trainer in
# NHWC mode refuses graphs containing them rather than silently computing on
# the wrong axis.  Extend this list when adding channel-sensitive ops.
NHWC_UNAWARE_OPS = frozenset({
    "SwapAxis", "SpatialTransformer", "BilinearSampler", "GridGenerator",
    "ROIPooling", "Correlation", "Proposal", "MultiBoxPrior",
    "MultiBoxTarget", "MultiBoxDetection",
})


def _mxu_out(y):
    """Name MXU-op outputs for the remat policy: under
    MXNET_BACKWARD_DO_MIRROR the backward pass saves exactly these and
    recomputes everything else (BN/activation), the reference's mirroring
    split (graph_executor.cc:218-231).  Identity outside jax.checkpoint."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(y, "mxu_out")


def maybe_mirror(f):
    """MXNET_BACKWARD_DO_MIRROR=1 -> rematerialized backward (reference
    graph_executor.cc:218-231 mirroring): wrap a traced forward in
    jax.checkpoint saving only the MXU-op outputs tagged by
    :func:`_mxu_out`, so BN statistics, activations and other elementwise
    intermediates are recomputed in the backward pass instead of living
    in HBM across it — the 30-50% activation-memory trade the reference
    documents (docs/how_to/env_var.md:64-66; measurements: docs/perf.md).
    Used by the executor backward/fused paths and ShardedTrainer."""
    from .. import config
    if not config.get_bool("MXNET_BACKWARD_DO_MIRROR"):
        return f
    import jax
    policy = jax.checkpoint_policies.save_only_these_names("mxu_out")
    return jax.checkpoint(f, policy=policy)


# --------------------------------------------------------------------- dense
@register("FullyConnected", arg_names=lambda a: ("data", "weight") if a["no_bias"]
          else ("data", "weight", "bias"),
          params={"num_hidden": 0, "no_bias": False, "flatten": True},
          aliases=("fully_connected",))
def fully_connected(attrs, ctx, data, weight, bias=None):
    """Y = X W^T + b.  Reference: src/operator/fully_connected-inl.h:48-145."""
    if attrs["flatten"]:
        x = data.reshape((data.shape[0], -1))
    else:
        x = data
    # the TPU MXU accumulates bf16 dots in f32 natively; no upcast
    # annotation (preferred_element_type breaks the conv/dot transpose rule)
    y = jnp.dot(x, weight.T)
    if bias is not None:
        y = y + bias
    return _mxu_out(y.astype(data.dtype))


# ---------------------------------------------------------------------- conv
@register("Convolution", arg_names=lambda a: ("data", "weight") if a["no_bias"]
          else ("data", "weight", "bias"),
          params={"kernel": (1, 1), "stride": (), "dilate": (), "pad": (),
                  "num_filter": 0, "num_group": 1, "no_bias": False,
                  "workspace": 1024, "cudnn_tune": None, "cudnn_off": False,
                  "layout": None},
          aliases=("convolution", "Convolution_v1"))
def convolution(attrs, ctx, data, weight, bias=None):
    """N-d convolution, NCHW/NCW/NCDHW.  Reference: src/operator/convolution-inl.h:103-325.

    Weight layout (num_filter, C/group, *kernel) as in the reference; lowered
    to one lax.conv_general_dilated so XLA maps it onto the MXU.
    """
    kernel = tuple(attrs["kernel"])
    nd = len(kernel)
    stride = tuple(attrs["stride"]) or (1,) * nd
    dilate = tuple(attrs["dilate"]) or (1,) * nd
    pad = tuple(attrs["pad"]) or (0,) * nd
    layout = attrs.get("layout") or _IMAGE_LAYOUT
    if attrs.get("layout") and _IMAGE_LAYOUT == "NHWC" \
            and attrs["layout"] != "NHWC":
        raise MXNetError(
            "Convolution node pins layout=%r but the trainer runs "
            "image_layout('NHWC'); drop the explicit layout attr or train "
            "in NCHW" % (attrs["layout"],))
    if nd == 2 and layout == "NHWC":
        # activations NHWC, weight kept reference-OIHW -> HWIO view
        dn = lax.conv_dimension_numbers(
            data.shape, weight.shape[2:] + weight.shape[1:2] + weight.shape[:1],
            ("NHWC", "HWIO", "NHWC"))
        w = jnp.transpose(weight, (2, 3, 1, 0))
        y = lax.conv_general_dilated(
            data, w, window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=int(attrs["num_group"]))
        if bias is not None:
            y = y + bias
        return _mxu_out(y.astype(data.dtype))
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ("NCHW", "OIHW", "NCHW") if nd == 2 else
        ("NCW", "OIW", "NCW") if nd == 1 else ("NCDHW", "OIDHW", "NCDHW"))
    y = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=int(attrs["num_group"]))
    if bias is not None:
        y = y + bias.reshape((1, -1) + (1,) * nd)
    return _mxu_out(y.astype(data.dtype))


@register("Deconvolution", arg_names=lambda a: ("data", "weight") if a["no_bias"]
          else ("data", "weight", "bias"),
          params={"kernel": (1, 1), "stride": (), "dilate": (), "pad": (),
                  "adj": (), "target_shape": (), "num_filter": 0,
                  "num_group": 1, "no_bias": True, "workspace": 512,
                  "cudnn_tune": None, "cudnn_off": False, "layout": None})
def deconvolution(attrs, ctx, data, weight, bias=None):
    """Transposed convolution.  Reference: src/operator/deconvolution-inl.h.

    Implemented as conv_general_dilated with lhs_dilation (the XLA-native
    formulation of conv-transpose).  Weight layout (C_in, C_out/group, *k).
    """
    kernel = tuple(attrs["kernel"])
    nd = len(kernel)
    stride = tuple(attrs["stride"]) or (1,) * nd
    pad = tuple(attrs["pad"]) or (0,) * nd
    adj = tuple(attrs["adj"]) or (0,) * nd
    groups = int(attrs["num_group"])
    # flip spatial dims and swap in/out channels -> direct conv on dilated input
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if groups == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        ci, co = weight.shape[0], weight.shape[1]
        w = w.reshape((groups, ci // groups, co) + kernel)
        w = jnp.swapaxes(w, 1, 2).reshape((groups * co, ci // groups) + kernel)
    padding = [(kernel[i] - 1 - pad[i], kernel[i] - 1 - pad[i] + adj[i])
               for i in range(nd)]
    if nd == 2 and _is_nhwc(data):
        dn = lax.conv_dimension_numbers(
            data.shape, w.shape[2:] + w.shape[1:2] + w.shape[:1],
            ("NHWC", "HWIO", "NHWC"))
        y = lax.conv_general_dilated(
            data, jnp.transpose(w, (2, 3, 1, 0)),
            window_strides=(1, 1), padding=padding,
            lhs_dilation=stride, dimension_numbers=dn,
            feature_group_count=groups)
        if bias is not None:
            y = y + bias
        return y.astype(data.dtype)
    dn = lax.conv_dimension_numbers(
        data.shape, w.shape,
        ("NCHW", "OIHW", "NCHW") if nd == 2 else
        ("NCW", "OIW", "NCW") if nd == 1 else ("NCDHW", "OIDHW", "NCDHW"))
    y = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        y = y + bias.reshape((1, -1) + (1,) * nd)
    return y.astype(data.dtype)


# ------------------------------------------------------------------- pooling
@register("Pooling",
          params={"kernel": (1, 1), "pool_type": "max", "global_pool": False,
                  "stride": (), "pad": (), "pooling_convention": "valid",
                  "cudnn_off": False},
          aliases=("pooling", "Pooling_v1"))
def pooling(attrs, ctx, data):
    """Max/avg/sum pooling via lax.reduce_window.

    Reference: src/operator/pooling-inl.h (+pooling.cc registration).
    """
    nd = data.ndim - 2
    nhwc = nd == 2 and _IMAGE_LAYOUT == "NHWC"
    sp0 = 1 if nhwc else 2  # first spatial axis
    if attrs["global_pool"]:
        kernel = data.shape[sp0:sp0 + nd]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = _pair(attrs["kernel"], nd)
        # reference defaults stride to 1 (pooling-inl.h), NOT to the kernel
        stride = tuple(attrs["stride"]) or (1,) * nd
        pad = tuple(attrs["pad"]) or (0,) * nd
    conv = attrs.get("pooling_convention", "valid")
    spatial_pad = []
    for i in range(nd):
        lo = hi = pad[i]
        if conv == "full":
            # ceil division convention: pad extra on the high side as needed
            in_sz = data.shape[sp0 + i] + 2 * pad[i]
            rem = (in_sz - kernel[i]) % stride[i]
            if rem:
                hi += stride[i] - rem
        spatial_pad.append((lo, hi))
    if nhwc:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        padding = [(0, 0)] + spatial_pad + [(0, 0)]
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        padding = [(0, 0), (0, 0)] + spatial_pad
    ptype = attrs["pool_type"]
    # init values must be python literals (the identity element) so JAX's
    # reduce_window autodiff monoid pattern-match fires
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max,
                                 window, strides, padding)
    zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
    summed = lax.reduce_window(data, zero, lax.add,
                               window, strides, padding)
    if ptype == "sum":
        return summed
    if ptype == "avg":
        # reference divides by full window size (count_include_pad)
        wsize = 1
        for k in kernel:
            wsize *= k
        return (summed / wsize).astype(data.dtype)
    raise MXNetError(f"unknown pool_type {ptype}")


# ---------------------------------------------------------------- batch norm
@functools.lru_cache(maxsize=None)
def _bn_core(eps, momentum, train_stats, bshape_key):
    """Hand-scheduled BatchNorm fwd/bwd (custom_vjp).

    BN statistics are the #1 non-MXU cost in conv nets (they tie the convs
    in the ResNet-50 step profile), so the pass structure is explicit:
      fwd: ONE fused stats pass (sum, sum of squares -> mean, biased var),
           then one normalize pass as a single multiply-add per element.
      bwd: ONE fused reduce pass (sum dy, sum dy*x), then one dx pass
           (dx = a*dy + c*x + d with per-channel scalars).
    The jax-autodiff formulation of mean/var costs roughly twice these
    memory passes.  Reference kernel: src/operator/batch_norm-inl.h.
    """
    import jax as _jax

    bshape = tuple(bshape_key)
    red = tuple(i for i, s in enumerate(bshape) if s == 1)

    # mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
    def fwd_math(x, gamma, beta, mm, mv):
        xf = x.astype(jnp.float32)
        if train_stats:
            n = 1
            for i in red:
                n *= x.shape[i]
            # single-pass sum/sum² stats, SHIFTED by the moving mean: for
            # any constant c, var = E[(x-c)²] - E[x-c]².  With c ≈ the true
            # mean (which the moving mean approaches) this avoids the
            # catastrophic f32 cancellation of the raw E[x²]-E[x]² form on
            # large-mean channels, while keeping one fused read of x.
            c = lax.stop_gradient(mm.astype(jnp.float32))
            xs = xf - c.reshape(bshape)
            s1 = jnp.sum(xs, axis=red)
            s2 = jnp.sum(jnp.square(xs), axis=red)
            meanc = s1 / n
            var = jnp.maximum(s2 / n - jnp.square(meanc), 0.0)
            mean = meanc + c
            new_mm = mm * momentum + mean * (1 - momentum)
            new_mv = mv * momentum + var * (1 - momentum)
        else:
            mean, var = mm.astype(jnp.float32), mv.astype(jnp.float32)
            new_mm, new_mv = mm, mv
        inv = lax.rsqrt(var + eps)
        scale = gamma.astype(jnp.float32) * inv
        shift = beta.astype(jnp.float32) - mean * scale
        out = (xf * scale.reshape(bshape) + shift.reshape(bshape))
        return (out.astype(x.dtype), mean, var, new_mm, new_mv), \
            (mean, inv, mm)

    @_jax.custom_vjp
    def bn(x, gamma, beta, mm, mv):
        return fwd_math(x, gamma, beta, mm, mv)[0]

    def bn_fwd(x, gamma, beta, mm, mv):
        outs, (mean, inv, mm_res) = fwd_math(x, gamma, beta, mm, mv)
        return outs, (x, gamma, mean, inv, mm_res)

    def bn_bwd(res, cots):
        x, gamma, mean, inv, mm = res
        dy, dmean_o, dvar_o, dmm_o, dmv_o = cots
        n = 1
        for i in red:
            n *= x.shape[i]
        dyf = dy.astype(jnp.float32)
        # same shifted formulation as forward (avoids cancellation in the
        # sum(dy*x) - mean*sum(dy) difference on large-mean channels)
        c = lax.stop_gradient(mm.astype(jnp.float32))
        xs = x.astype(jnp.float32) - c.reshape(bshape)
        meanc = mean - c
        dbeta = jnp.sum(dyf, axis=red)
        sdyxs = jnp.sum(dyf * xs, axis=red)
        dgamma = (sdyxs - meanc * dbeta) * inv  # = sum(dy * xhat)
        a = gamma.astype(jnp.float32) * inv
        if train_stats:
            # dx = (a/n)(n*dy - sum(dy) - xhat*sum(dy*xhat)), written as
            # a*dy + K*(x - mean) + const, plus the cotangent paths of the
            # explicit mean/var/moving outputs
            dmean = dmean_o + (1 - momentum) * dmm_o
            dvar = dvar_o + (1 - momentum) * dmv_o
            k = (-a * inv * dgamma + 2.0 * dvar) * (1.0 / n)
            d = -k * meanc - a * dbeta * (1.0 / n) + dmean * (1.0 / n)
            dx = (dyf * a.reshape(bshape) + xs * k.reshape(bshape)
                  + d.reshape(bshape))
            dmm = momentum * dmm_o
            dmv = momentum * dmv_o
        else:
            # eval/global-stats: moving stats are aux constants; the
            # normalize path into them is not differentiated (the
            # reference never backprops into moving stats)
            dx = dyf * a.reshape(bshape)
            dmm = dmm_o + dmean_o
            dmv = dmv_o + dvar_o
        return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
                dbeta.astype(gamma.dtype), dmm, dmv)

    bn.defvjp(bn_fwd, bn_bwd)
    return bn


@register("BatchNorm",
          arg_names=("data", "gamma", "beta"),
          aux_names=("moving_mean", "moving_var"),
          num_outputs=lambda a: 3 if a.get("output_mean_var") else 1,
          params={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                  "use_global_stats": False, "output_mean_var": False,
                  "axis": 1, "cudnn_off": False},
          aliases=("batch_norm", "BatchNorm_v1"))
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def batch_norm(attrs, ctx, data, gamma, beta, moving_mean, moving_var):
    """Batch normalization with functional aux-state threading.

    Reference: src/operator/batch_norm-inl.h / batch_norm.cc.  The reference
    mutates moving_{mean,var} aux states in forward during training; here the
    updated stats are returned as trailing outputs and threaded by the
    executor (SURVEY §7 'hard parts': aux state).
    Returns (out[, mean, var], new_moving_mean, new_moving_var).
    """
    axis = int(attrs["axis"])
    if axis == 1 and data.ndim == 4 and _IMAGE_LAYOUT == "NHWC":
        axis = 3  # NHWC mode: symbols declare the reference NCHW channel axis
    eps = float(attrs["eps"])
    momentum = float(attrs["momentum"])
    bshape = tuple(1 if i != axis else data.shape[axis]
                   for i in range(data.ndim))
    if attrs["fix_gamma"]:
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    train_stats = bool(ctx.is_train and not attrs["use_global_stats"])
    bn = _bn_core(eps, momentum, train_stats, bshape)
    out, mean, var, new_mm, new_mv = bn(data, gamma, beta,
                                        moving_mean.astype(jnp.float32),
                                        moving_var.astype(jnp.float32))
    new_mm = new_mm.astype(moving_mean.dtype)
    new_mv = new_mv.astype(moving_var.dtype)
    if attrs.get("output_mean_var"):
        return out, mean, var, new_mm, new_mv
    return out, new_mm, new_mv


@register("LayerNorm", arg_names=("data", "gamma", "beta"),
          num_outputs=lambda a: 3 if a.get("output_mean_var") else 1,
          params={"axis": -1, "eps": 1e-5, "output_mean_var": False})
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def layer_norm(attrs, ctx, data, gamma, beta):
    """Layer normalization over ``axis`` (the transformer workhorse;
    post-reference-era op — the 0.10.1 reference predates attention —
    kept API-compatible with mxnet's later LayerNorm)."""
    axis = int(attrs["axis"])
    eps = float(attrs["eps"])
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    bshape = tuple(data.shape[axis] if i == (axis % data.ndim) else 1
                   for i in range(data.ndim))
    out = ((xf - mean) * inv * gamma.astype(jnp.float32).reshape(bshape)
           + beta.astype(jnp.float32).reshape(bshape)).astype(data.dtype)
    if attrs.get("output_mean_var"):
        # mxnet's LayerNorm(output_mean_var=True) returns (out, mean, std)
        return (out, jnp.squeeze(mean, axis),
                jnp.squeeze(jnp.sqrt(var + eps), axis))
    return out


@register("RMSNorm", arg_names=("data", "gamma"),
          params={"axis": -1, "eps": 1e-5}, aliases=("rms_norm",))
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def rms_norm(attrs, ctx, data, gamma):
    """Root-mean-square normalization over ``axis``:
    ``x * rsqrt(mean(x^2) + eps) * gamma`` with ``gamma`` of that axis'
    length, no mean subtraction and no shift (Zhang & Sennrich,
    arXiv:1910.07467).  Statistics in float32, output in the input's
    dtype.  With a 4-d ``(batch, seq, heads, head_dim)`` input and the
    default axis it is the per-head query/key norm."""
    axis = int(attrs["axis"])
    xf = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
                    + float(attrs["eps"]))
    bshape = tuple(data.shape[axis] if i == (axis % data.ndim) else 1
                   for i in range(data.ndim))
    return (xf * inv * gamma.astype(jnp.float32).reshape(bshape)
            ).astype(data.dtype)


@register("_contrib_GatedRMSNorm", arg_names=("data", "gate", "gamma"),
          params={"eps": 1e-5, "gate_act": "sigmoid", "gate_first": False,
                  "gamma_axes": 1},
          aliases=("GatedRMSNorm",))
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def gated_rms_norm(attrs, ctx, data, gate, gamma):
    """Gated RMS normalization over the last axis:
    ``x * rsqrt(mean(x^2) + eps) * gamma * sigmoid(gate)`` with ``gate``
    of ``data``'s shape and ``gamma`` of the last axis' length: the
    output norm of a linear-attention layer, over each head of
    ``(batch, seq, heads, head_dim)`` with one gain a channel of the
    head.  ``gate_act`` (``sigmoid`` | ``silu``) is the gate's function;
    with ``gate_first`` the gate comes before the statistics,
    ``norm(x * act(gate)) * gamma`` (Mamba-2's ``MambaRMSNormGated`` with
    ``norm_before_gate`` false); ``gamma_axes=2``: ``gamma`` spans the
    last two axes, a gain of its own for every channel of every group of
    ``(batch, seq, groups, group_size)``.  Statistics and the gate in
    float32, rounded once to the input's dtype."""
    axes = int(attrs["gamma_axes"])
    if gate.shape != data.shape or axes not in (1, 2) \
            or gamma.shape != data.shape[-axes:]:
        raise MXNetError(
            "_contrib_GatedRMSNorm wants a gate of the data's shape and a "
            "gamma of its last %s; got data %s, gate %s, gamma %s"
            % ("axis" if axes == 1 else "two axes", tuple(data.shape),
               tuple(gate.shape), tuple(gamma.shape)))
    eps = float(attrs["eps"])
    if attrs["gate_act"] not in ("sigmoid", "silu"):
        raise MXNetError("_contrib_GatedRMSNorm: gate_act %r is neither "
                         "sigmoid nor silu" % (attrs["gate_act"],))
    act = _ACTIVATIONS[attrs["gate_act"]]
    first = bool(attrs["gate_first"])

    # rematerialised: the backward keeps the op's inputs, not their
    # float32 copies
    @jax.checkpoint
    # mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
    def norm(data, gate, gamma):
        xf = data.astype(jnp.float32)
        if first:
            xf = xf * act(gate.astype(jnp.float32))
        inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + eps)
        y = xf * inv * gamma.astype(jnp.float32)
        return (y if first else y * act(gate.astype(jnp.float32))
                ).astype(data.dtype)

    return norm(data, gate, gamma)


@register("_contrib_RotaryEmbedding",
          params={"base": 10000.0, "offset": 0},
          aliases=("RotaryEmbedding",))
# mxlint: allow-dtype-widening(rotation angles need float32: position 8191 times a frequency has no bf16)
def rotary_embedding(attrs, ctx, data):
    """Rotary position embedding (Su et al., arXiv:2104.09864) over the
    whole last axis of ``(batch, seq, heads, head_dim)``, rotate-half
    convention: with ``x = [x1, x2]`` split in the middle of the head,
    ``out = x * cos + [-x2, x1] * sin`` where the angle of position
    ``p`` and pair ``i`` is ``(p + offset) * base^(-2i/head_dim)``, the
    same for ``i`` and ``i + head_dim/2``.  Angles in float32 from an
    iota (no table argument), output in the input's dtype."""
    if data.ndim != 4 or data.shape[-1] % 2:
        raise MXNetError(
            "_contrib_RotaryEmbedding wants (batch, seq, heads, head_dim) "
            "with an even head_dim; got %s" % (tuple(data.shape),))
    t, d = data.shape[1], data.shape[3]
    inv_freq = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                       * (-jnp.log(jnp.float32(attrs["base"])) / d))
    pos = jnp.arange(t, dtype=jnp.float32) + float(attrs["offset"])
    ang = pos[:, None] * inv_freq[None, :]              # (t, d/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = data.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(data.dtype)


#: ``jax.named_scope`` of the short convolution's ops on the device
SCOPE_SHORTCONV = "mxtpu.block.shortconv"


@register("_contrib_CausalConv1D",
          arg_names=lambda a: ("data", "weight") if a["no_bias"]
          else ("data", "weight", "bias"),
          params={"kernel": 3, "act_type": "", "no_bias": True},
          aliases=("CausalConv1D",))
# mxlint: allow-dtype-widening(the taps' sum accumulates in f32 and is rounded once)
def causal_conv1d(attrs, ctx, data, weight, bias=None):
    """Depthwise causal convolution along the sequence of
    ``(batch, seq, channels)``: ``out[t] = sum_j w[:, j] * x[t - (K-1) + j]``
    with zeros before position 0, one ``K``-tap filter a channel
    (``weight`` is ``(channels, K)``); with ``no_bias=False`` a third
    input ``bias`` ``(channels,)`` is added to the sum.  The sequence
    stays the second axis and the channels the last: no relayout to the
    ``(batch, channels, width)`` that ``Convolution`` wants, and no
    symmetric padding to cut off again.  It is ``K`` shifted
    multiply-adds that XLA fuses into one pass over the activation.
    With ``act_type`` (an ``Activation`` type, ``silu`` say) the
    activation of the sum is returned, rounded once, and the pair is
    rematerialised: the backward keeps ``data`` and not the sum."""
    k = int(attrs["kernel"])
    if data.ndim != 3 or weight.shape != (data.shape[2], k) \
            or (bias is not None and bias.shape != data.shape[2:]):
        raise MXNetError(
            "_contrib_CausalConv1D wants (batch, seq, channels) data, a "
            "(channels, %d) weight and a (channels,) bias if any; got data "
            "%s, weight %s%s"
            % (k, tuple(data.shape), tuple(weight.shape),
               "" if bias is None else ", bias %s" % (tuple(bias.shape),)))
    t = data.shape[1]

    # mxlint: allow-dtype-widening(the taps' sum accumulates in f32 and is rounded once)
    def taps(data, weight, *bias):
        xp = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0)))
        w = weight.astype(jnp.float32)
        out = sum(xp[:, j:j + t, :].astype(jnp.float32) * w[:, j]
                  for j in range(k))
        return out + bias[0].astype(jnp.float32) if bias else out

    params = (weight,) if bias is None else (weight, bias)
    with jax.named_scope(SCOPE_SHORTCONV):
        if not attrs["act_type"]:
            return taps(data, *params).astype(data.dtype)
        act = _activation_fn(attrs["act_type"], data)
        return jax.checkpoint(
            lambda d, *p: act(taps(d, *p)).astype(d.dtype))(data, *params)


@register("InstanceNorm", arg_names=("data", "gamma", "beta"),
          params={"eps": 1e-3})
def instance_norm(attrs, ctx, data, gamma, beta):
    """Reference: src/operator/instance_norm-inl.h."""
    ch = _ch_axis(data)
    red = tuple(i for i in range(1, data.ndim) if i != ch)
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = tuple(-1 if i == ch else 1 for i in range(data.ndim))
    out = (data - mean) * lax.rsqrt(var + attrs["eps"])
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization", params={"eps": 1e-10, "mode": "instance"})
def l2_normalization(attrs, ctx, data):
    """Reference: src/operator/l2_normalization-inl.h."""
    mode = attrs["mode"]
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        keep = True
    elif mode == "channel":
        red, keep = (1,), True
    elif mode == "spatial":
        red, keep = tuple(range(2, data.ndim)), True
    else:
        raise MXNetError(f"unknown mode {mode}")
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=keep)
                    + attrs["eps"])
    return data / norm


@register("LRN", params={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": 5})
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def lrn(attrs, ctx, data):
    """Local response norm across channels.  Reference: src/operator/lrn-inl.h."""
    nsize = int(attrs["nsize"])
    ch = _ch_axis(data)
    sq = jnp.square(data.astype(jnp.float32))
    pre = nsize // 2
    post = nsize - pre - 1
    pads = [(0, 0)] * data.ndim
    pads[ch] = (pre, post)
    padded = jnp.pad(sq, pads)
    acc = sum(lax.slice_in_dim(padded, i, i + data.shape[ch], axis=ch)
              for i in range(nsize))
    scale = attrs["knorm"] + (attrs["alpha"] / nsize) * acc
    return (data * scale ** (-attrs["beta"])).astype(data.dtype)


# ------------------------------------------------------------- activations
#: ``act_type`` -> function: every activation that ``Activation`` computes
_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": lambda x: x / (1 + jnp.abs(x)),
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


@register("Activation", params={"act_type": "relu"}, aliases=("activation",))
def activation(attrs, ctx, data):
    """Reference: src/operator/activation-inl.h; functors mshadow_op.h."""
    return _activation_fn(attrs["act_type"], data)(data)


def _activation_fn(t, data):
    """The elementwise function of ``act_type`` ``t`` (``data``: what it
    is meant for, for the error)."""
    if t not in _ACTIVATIONS:
        raise MXNetError("unknown act_type %r on data of shape %s; known: %s"
                         % (t, tuple(data.shape), ", ".join(_ACTIVATIONS)))
    return _ACTIVATIONS[t]


@register("LeakyReLU", arg_names=lambda a: ("data", "gamma")
          if a["act_type"] == "prelu" else ("data",),
          params={"act_type": "leaky", "slope": 0.25,
                  "lower_bound": 0.125, "upper_bound": 0.334},
          stochastic=lambda a: a["act_type"] == "rrelu")
def leaky_relu(attrs, ctx, data, gamma=None):
    """Reference: src/operator/leaky_relu-inl.h."""
    t = attrs["act_type"]
    if t == "leaky":
        return jnp.where(data > 0, data, data * attrs["slope"])
    if t == "prelu":
        ch = _ch_axis(data)
        g = gamma.reshape(tuple(-1 if i == ch else 1
                                for i in range(data.ndim)))
        return jnp.where(data > 0, data, data * g)
    if t == "elu":
        return jnp.where(data > 0, data, attrs["slope"] * (jnp.exp(data) - 1))
    if t == "rrelu":
        if ctx.is_train:
            lo, hi = attrs["lower_bound"], attrs["upper_bound"]
            slope = jax.random.uniform(ctx.require_key(),
                                       data.shape, data.dtype, lo, hi)
        else:
            slope = (attrs["lower_bound"] + attrs["upper_bound"]) / 2.0
        return jnp.where(data > 0, data, data * slope)
    raise MXNetError(f"unknown act_type {t}")


@register("Dropout", params={"p": 0.5, "mode": "training"}, stochastic=True,
          aliases=("dropout",))
def dropout(attrs, ctx, data):
    """Inverted dropout.  Reference: src/operator/dropout-inl.h."""
    p = float(attrs["p"])
    if not ctx.is_train or p <= 0.0:
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(ctx.require_key(), keep, data.shape)
    return jnp.where(mask, data / keep, 0).astype(data.dtype)


# ------------------------------------------------------------------ softmax
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def _softmax(x, axis):
    return jax.nn.softmax(x.astype(jnp.float32), axis=axis).astype(x.dtype)


@register("softmax", params={"axis": -1, "temperature": None})
def softmax_op(attrs, ctx, data):
    """Reference: softmax in src/operator/nn-era tensor ops (softmax_output.cc kin)."""
    x = data
    if attrs.get("temperature"):
        x = x / attrs["temperature"]
    return _softmax(x, int(attrs["axis"]))


@register("log_softmax", params={"axis": -1})
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def log_softmax_op(attrs, ctx, data):
    return jax.nn.log_softmax(data.astype(jnp.float32),
                              axis=int(attrs["axis"])).astype(data.dtype)


@register("SoftmaxActivation", params={"mode": "instance"})
def softmax_activation(attrs, ctx, data):
    """Reference: src/operator/softmax_activation-inl.h."""
    if attrs["mode"] == "channel":
        return _softmax(data, _ch_axis(data))
    return _softmax(data.reshape((data.shape[0], -1)), -1).reshape(data.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _softmax_output(data, label, grad_scale, multi_output, use_ignore,
                    ignore_label, normalization):
    axis = 1 if multi_output else -1
    return _softmax(data, axis)


def _softmax_output_fwd(data, label, grad_scale, multi_output, use_ignore,
                        ignore_label, normalization):
    out = _softmax_output(data, label, grad_scale, multi_output, use_ignore,
                          ignore_label, normalization)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, multi_output, use_ignore, ignore_label,
                        normalization, res, g):
    # Reference backward (src/operator/softmax_output-inl.h): grad = p - onehot,
    # ignoring the incoming head gradient (it is a terminal loss op).
    out, label = res
    axis = 1 if multi_output else -1
    nclass = out.shape[axis]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, nclass, dtype=jnp.float32, axis=axis)
    grad = out.astype(jnp.float32) - onehot
    valid = None
    if use_ignore:
        keep = (lab != int(ignore_label))
        keepb = jnp.expand_dims(keep, axis=axis)
        grad = grad * keepb
        valid = jnp.maximum(jnp.sum(keep), 1)
    scale = grad_scale
    if normalization == "batch":
        scale = scale / out.shape[0]
    elif normalization == "valid" and valid is not None:
        scale = scale / valid
    elif normalization == "valid":
        scale = scale / lab.size
    return (grad * scale).astype(out.dtype), jnp.zeros_like(label)


_softmax_output.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", arg_names=("data", "label"),
          params={"grad_scale": 1.0, "ignore_label": -1.0, "multi_output": False,
                  "use_ignore": False, "preserve_shape": False,
                  "normalization": "null", "out_grad": False,
                  "smooth_alpha": 0.0},
          is_loss=True, aliases=("Softmax",))
def softmax_output(attrs, ctx, data, label):
    """Softmax forward + cross-entropy-style custom backward.

    Reference: src/operator/softmax_output.cc:32,114 (+`Softmax` deprecated
    alias) — forward is softmax; backward is (p - onehot(label)) * grad_scale
    regardless of head grad.
    """
    return _softmax_output(data, label, float(attrs["grad_scale"]),
                           bool(attrs["multi_output"]), bool(attrs["use_ignore"]),
                           float(attrs["ignore_label"]), attrs["normalization"])


def _head_grad_op(fwd_fn, bwd_fn):
    """Build a custom_vjp op whose backward ignores the head gradient."""
    f = jax.custom_vjp(fwd_fn)
    f.defvjp(lambda *args: (fwd_fn(*args), args), bwd_fn)
    return f


_linreg = _head_grad_op(
    lambda data, label: data,
    lambda res, g: ((res[0] - res[1].reshape(res[0].shape)).astype(res[0].dtype),
                    jnp.zeros_like(res[1])))
_maereg = _head_grad_op(
    lambda data, label: data,
    lambda res, g: (jnp.sign(res[0] - res[1].reshape(res[0].shape)).astype(res[0].dtype),
                    jnp.zeros_like(res[1])))
_logreg = _head_grad_op(
    lambda data, label: jax.nn.sigmoid(data),
    lambda res, g: ((jax.nn.sigmoid(res[0]) - res[1].reshape(res[0].shape)).astype(res[0].dtype),
                    jnp.zeros_like(res[1])))


@register("LinearRegressionOutput", arg_names=("data", "label"),
          params={"grad_scale": 1.0}, is_loss=True)
def linear_regression_output(attrs, ctx, data, label):
    """Reference: src/operator/regression_output-inl.h (grad = pred - label)."""
    return _linreg(data, label)


@register("MAERegressionOutput", arg_names=("data", "label"),
          params={"grad_scale": 1.0}, is_loss=True)
def mae_regression_output(attrs, ctx, data, label):
    return _maereg(data, label)


@register("LogisticRegressionOutput", arg_names=("data", "label"),
          params={"grad_scale": 1.0}, is_loss=True)
def logistic_regression_output(attrs, ctx, data, label):
    return _logreg(data, label)


@register("SVMOutput", arg_names=("data", "label"),
          params={"margin": 1.0, "regularization_coefficient": 1.0,
                  "use_linear": False}, is_loss=True)
def svm_output(attrs, ctx, data, label):
    """Reference: src/operator/svm_output-inl.h."""
    margin = float(attrs["margin"])
    reg = float(attrs["regularization_coefficient"])
    use_linear = bool(attrs["use_linear"])

    def bwd(res, g):
        x, lab = res
        n = x.shape[-1]
        onehot = jax.nn.one_hot(lab.astype(jnp.int32), n, dtype=x.dtype)
        score_correct = jnp.sum(x * onehot, axis=-1, keepdims=True)
        if use_linear:
            viol = ((margin - (2 * onehot - 1) * x) > 0).astype(x.dtype)
            grad = -(2 * onehot - 1) * viol * reg
        else:
            viol = ((x - score_correct + margin) > 0).astype(x.dtype) * (1 - onehot)
            grad = viol - onehot * jnp.sum(viol, axis=-1, keepdims=True)
            grad = grad * reg
        return grad, jnp.zeros_like(lab)

    f = _head_grad_op(lambda d, l: d, bwd)
    return f(data, label)


@register("MakeLoss", params={"grad_scale": 1.0, "valid_thresh": 0.0,
                              "normalization": "null"}, is_loss=True)
def make_loss(attrs, ctx, data):
    """Forward identity; backward = grad_scale (loss source).

    Reference: src/operator/make_loss-inl.h.
    """
    scale = float(attrs["grad_scale"])
    norm = attrs["normalization"]
    thresh = float(attrs["valid_thresh"])

    def bwd(res, g):
        (x,) = res
        if norm == "batch":
            s = jnp.asarray(scale / x.shape[0], x.dtype)
        elif norm == "valid":
            # divide by the count of entries above valid_thresh
            # (make_loss-inl.h:98-113) — SSD's per-positive-anchor scaling
            valid = jnp.maximum(jnp.sum(x > thresh), 1).astype(x.dtype)
            s = jnp.asarray(scale, x.dtype) / valid
        else:
            s = jnp.asarray(scale, x.dtype)
        return (jnp.broadcast_to(s, x.shape).astype(x.dtype),)

    f = _head_grad_op(lambda d: d, bwd)
    return f(data)


@register("BlockGrad", aliases=("stop_gradient",))
def block_grad(attrs, ctx, data):
    """Reference: src/operator/slice_channel / blockgrad op — stops gradients."""
    return lax.stop_gradient(data)


# ----------------------------------------------------------------- shape ops
@register("Flatten", aliases=("flatten",))
def flatten_op(attrs, ctx, data):
    """Reference: reshape family in src/operator/tensor/matrix_op.cc."""
    return data.reshape((data.shape[0], -1))


@register("Concat", arg_names=lambda a: tuple(f"arg{i}" for i in range(int(a["num_args"]))),
          params={"num_args": 1, "dim": 1}, key_var_num_args="num_args",
          aliases=("concat",))
def concat(attrs, ctx, *args):
    """Reference: src/operator/concat-inl.h."""
    dim = int(attrs["dim"])
    if dim == 1 and all(_is_nhwc(a) for a in args):
        dim = 3  # channel concat under the trainer's NHWC activation mode
    return jnp.concatenate(args, axis=dim)


@register("SliceChannel",
          num_outputs=lambda a: int(a["num_outputs"]),
          params={"num_outputs": 1, "axis": 1, "squeeze_axis": False},
          aliases=("split",))
def slice_channel(attrs, ctx, data):
    """Reference: src/operator/slice_channel-inl.h."""
    axis = int(attrs["axis"])
    if axis == 1 and _is_nhwc(data):
        axis = 3
    parts = jnp.split(data, int(attrs["num_outputs"]), axis=axis)
    if attrs["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


@register("Embedding", arg_names=("data", "weight"),
          params={"input_dim": 0, "output_dim": 0, "dtype": "float32"})
def embedding(attrs, ctx, data, weight):
    """Reference: src/operator/tensor/indexing_op.cc Embedding."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register("Pad", params={"mode": "constant", "pad_width": (), "constant_value": 0.0})
def pad_op(attrs, ctx, data):
    """Reference: src/operator/pad-inl.h (pad_width is declared in the
    reference NCHW axis order; permuted here when activations are NHWC)."""
    pw = tuple(attrs["pad_width"])
    pairs = [(int(pw[2 * i]), int(pw[2 * i + 1])) for i in range(len(pw) // 2)]
    if len(pairs) == 4 and _is_nhwc(data):
        pairs = [pairs[0], pairs[2], pairs[3], pairs[1]]
    mode = attrs["mode"]
    if mode == "constant":
        return jnp.pad(data, pairs, constant_values=attrs["constant_value"])
    if mode == "edge":
        return jnp.pad(data, pairs, mode="edge")
    if mode == "reflect":
        return jnp.pad(data, pairs, mode="reflect")
    raise MXNetError(f"unknown pad mode {mode}")


@register("UpSampling",
          arg_names=lambda a: tuple(f"arg{i}" for i in range(int(a["num_args"]))),
          params={"scale": 1, "num_filter": 0, "sample_type": "nearest",
                  "multi_input_mode": "concat", "num_args": 1, "workspace": 512},
          key_var_num_args="num_args")
def upsampling(attrs, ctx, *args):
    """Nearest-neighbour upsampling.  Reference: src/operator/upsampling-inl.h."""
    scale = int(attrs["scale"])
    h_ax = 1 if _is_nhwc(args[0]) else 2
    outs = []
    target = args[0].shape[h_ax] * scale
    for a in args:
        s = target // a.shape[h_ax]
        out = jnp.repeat(jnp.repeat(a, s, axis=h_ax), s, axis=h_ax + 1)
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    if attrs["multi_input_mode"] == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=3 if _is_nhwc(args[0]) else 1)


@register("Crop", arg_names=lambda a: tuple(f"arg{i}" for i in range(int(a["num_args"]))),
          params={"num_args": 1, "offset": (0, 0), "h_w": (0, 0),
                  "center_crop": False},
          key_var_num_args="num_args")
def crop(attrs, ctx, *args):
    """Reference: src/operator/crop-inl.h."""
    data = args[0]
    nhwc = _is_nhwc(data)
    h_ax = 1 if nhwc else 2
    if len(args) == 2:
        h, w = args[1].shape[h_ax], args[1].shape[h_ax + 1]
    else:
        h, w = attrs["h_w"]
    if attrs["center_crop"]:
        oh = (data.shape[h_ax] - h) // 2
        ow = (data.shape[h_ax + 1] - w) // 2
    else:
        oh, ow = attrs["offset"]
    if nhwc:
        return lax.dynamic_slice(data, (0, oh, ow, 0),
                                 (data.shape[0], h, w, data.shape[3]))
    return lax.dynamic_slice(data, (0, 0, oh, ow),
                             (data.shape[0], data.shape[1], h, w))


@register("SwapAxis", params={"dim1": 0, "dim2": 0}, aliases=("swapaxes",))
def swapaxis(attrs, ctx, data):
    """Reference: src/operator/swapaxis-inl.h."""
    return jnp.swapaxes(data, int(attrs["dim1"]), int(attrs["dim2"]))


# -------------------------------------------------------------- sequence ops
def _seq_mask(data, length, batch_axis, time_axis):
    steps = jnp.arange(data.shape[time_axis])
    mshape = [1] * data.ndim
    mshape[time_axis] = data.shape[time_axis]
    mask = steps.reshape(mshape) < jnp.reshape(
        length, [data.shape[batch_axis] if i == batch_axis else 1
                 for i in range(data.ndim)])
    return mask


@register("SequenceMask", arg_names=lambda a: ("data", "sequence_length")
          if a["use_sequence_length"] else ("data",),
          params={"use_sequence_length": False, "value": 0.0, "axis": 0})
def sequence_mask(attrs, ctx, data, sequence_length=None):
    """Reference: src/operator/sequence_mask-inl.h (time-major [T,B,...])."""
    if sequence_length is None:
        return data
    mask = _seq_mask(data, sequence_length, batch_axis=1, time_axis=0)
    return jnp.where(mask, data, jnp.asarray(attrs["value"], data.dtype))


@register("SequenceLast", arg_names=lambda a: ("data", "sequence_length")
          if a["use_sequence_length"] else ("data",),
          params={"use_sequence_length": False, "axis": 0})
def sequence_last(attrs, ctx, data, sequence_length=None):
    """Reference: src/operator/sequence_last-inl.h."""
    if sequence_length is None:
        return data[-1]
    idx = (sequence_length.astype(jnp.int32) - 1)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0)[0]


@register("SequenceReverse", arg_names=lambda a: ("data", "sequence_length")
          if a["use_sequence_length"] else ("data",),
          params={"use_sequence_length": False, "axis": 0})
def sequence_reverse(attrs, ctx, data, sequence_length=None):
    """Reference: src/operator/sequence_reverse-inl.h."""
    if sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T).reshape((T, 1))
    lens = sequence_length.astype(jnp.int32).reshape((1, -1))
    src = jnp.where(steps < lens, lens - 1 - steps, steps)  # [T, B]
    src = src.reshape((T, -1) + (1,) * (data.ndim - 2))
    return jnp.take_along_axis(data, src, axis=0)


@register("softmax_cross_entropy", arg_names=("data", "label"))
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def softmax_cross_entropy(attrs, ctx, data, label):
    """Scalar cross entropy of softmax(data) against integer labels
    (reference loss_binary_op.cc:11-60)."""
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    idx = jnp.clip(label.astype(jnp.int32), 0, data.shape[-1] - 1)
    picked = jnp.take_along_axis(logp, idx[:, None], axis=-1)
    return -jnp.sum(picked).reshape((1,)).astype(data.dtype)


@register("_contrib_TokenCrossEntropy", arg_names=("data", "label"),
          aliases=("TokenCrossEntropy",))
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def token_cross_entropy(attrs, ctx, data, label):
    """``-log softmax(data)[label]`` of every row, in float32: ``data``
    ``(rows, classes)`` in any float dtype, ``label`` ``(rows,)`` class ids
    (clipped to the classes, as ``pick``).  The term of a loss that is made
    in the graph (``MakeLoss`` over a weighted sum of several of these).
    Rematerialised: the backward keeps ``data`` as it came, not the float32
    log-softmax of it, so that two heads over one vocabulary hold one
    float32 copy of their logits at a time."""
    idx = jnp.clip(label.astype(jnp.int32), 0, data.shape[-1] - 1)

    @jax.checkpoint
    # mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
    def nll(data):
        logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, idx[:, None], axis=-1)[:, 0]

    return nll(data)


@register("IdentityAttachKLSparseReg", arg_names=("data",),
          aux_names=("moving_avg",),
          params={"sparseness_target": 0.1, "penalty": 0.001,
                  "momentum": 0.9})
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def identity_attach_kl_sparse_reg(attrs, ctx, data, moving_avg):
    """Identity forward; backward adds a KL sparseness penalty against a
    running mean activation (identity_attach_KL_sparse_reg-inl.h:60-110;
    pair with sigmoid activations).  The reference updates the running
    mean during backward; here it updates on the training forward — the
    same once-per-step cadence in functional form."""
    s = float(attrs["sparseness_target"])
    penalty = float(attrs["penalty"])
    momentum = float(attrs["momentum"])
    x2 = data.reshape((data.shape[0], -1)).astype(jnp.float32)
    if ctx.is_train:
        avg = jnp.mean(x2, axis=0)
        new_ma = momentum * moving_avg.astype(jnp.float32) \
            + (1 - momentum) * avg
    else:
        new_ma = moving_avg.astype(jnp.float32)
    ma = lax.stop_gradient(new_ma)

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, ()

    def bwd(res, g):
        reg = penalty * (-s / ma + (1 - s) / (1 - ma))
        return ((g.reshape(x2.shape) + reg).reshape(g.shape).astype(g.dtype),)

    f.defvjp(fwd, bwd)
    return f(data), new_ma.astype(moving_avg.dtype)


@register("LSoftmax", arg_names=("data", "weight", "label"),
          params={"num_hidden": 0, "margin": 2, "beta": 1.0,
                  "beta_min": 0.0, "scale": 1.0, "verbose": False})
# mxlint: allow-dtype-widening(normalization/softmax statistics accumulate in f32 by contract)
def lsoftmax(attrs, ctx, data, weight, label):
    """Large-margin softmax inner product (reference lsoftmax.cc /
    lsoftmax.cu — GPU-only there; this jnp formulation runs on every
    backend).  For the label class: f = |x||w| psi(theta) with
    psi(theta) = (-1)^k cos(m*theta) - 2k on the monotone extension of
    cos, blended with the plain product by beta/(1+beta).
    """
    m = int(attrs["margin"])
    beta = float(attrs["beta"])
    x = data.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    out = x @ w.T                                     # (N, C)
    if m == 1 or not ctx.is_train:
        return out.astype(data.dtype)
    n = x.shape[0]
    y = jnp.clip(label.astype(jnp.int32), 0, w.shape[0] - 1)
    wy = w[y]                                          # (N, D)
    xn = jnp.linalg.norm(x, axis=1)
    wn = jnp.linalg.norm(wy, axis=1)
    fy = jnp.take_along_axis(out, y[:, None], axis=1)[:, 0]
    cos = jnp.clip(fy / jnp.maximum(xn * wn, 1e-12), -1.0, 1.0)
    # k such that theta in [k*pi/m, (k+1)*pi/m): count thresholds above cos
    j = jnp.arange(1, m + 1, dtype=jnp.float32)
    thresholds = jnp.cos(j * jnp.pi / m)               # (m,)
    k = jnp.sum(cos[:, None] < thresholds[None, :], axis=1).astype(
        jnp.float32)
    k = lax.stop_gradient(k)
    # cos(m*theta) via the Chebyshev polynomial T_m(cos theta)
    theta = jnp.arccos(cos)
    cos_m = jnp.cos(m * theta)
    psi = ((-1.0) ** k) * cos_m - 2.0 * k
    fy_new = (beta * fy + xn * wn * psi) / (1.0 + beta)
    out = out.at[jnp.arange(n), y].set(fy_new)
    return out.astype(data.dtype)
