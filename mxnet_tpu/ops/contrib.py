"""Contrib ops: SSD MultiBox family, CTC, quantization, FFT.

Reference: ``src/operator/contrib/`` — MultiBoxPrior/Target/Detection
(`contrib/multibox_prior.cc:78` etc., the SSD ops), CTCLoss, quantize ops.
The MultiBox ops are the reference's most data-dependent kernels (box
matching, NMS); here they are expressed with masked dense jnp ops so they
compile under jit with static shapes — Pallas variants can replace the hot
paths later without API change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, dtype_np
from .registry import register


@register("_contrib_MultiBoxPrior",
          params={"sizes": (1.0,), "ratios": (1.0,), "clip": False,
                  "steps": (-1.0, -1.0), "offsets": (0.5, 0.5)},
          aliases=("MultiBoxPrior",))
def multibox_prior(attrs, ctx, data):
    """Anchor box generation.  Reference: src/operator/contrib/multibox_prior.cc.

    data: [N, C, H, W] feature map; returns [1, H*W*num_anchors, 4] corners.
    """
    h, w = data.shape[2], data.shape[3]
    sizes = tuple(attrs["sizes"]) if isinstance(attrs["sizes"], (tuple, list)) \
        else (attrs["sizes"],)
    ratios = tuple(attrs["ratios"]) if isinstance(attrs["ratios"], (tuple, list)) \
        else (attrs["ratios"],)
    steps = attrs["steps"]
    offs = attrs["offsets"]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w
    cy = (jnp.arange(h, dtype=jnp.float32) + offs[0]) * step_y
    cx = (jnp.arange(w, dtype=jnp.float32) + offs[1]) * step_x
    # anchor set: (s_i, r_0) for all sizes + (s_0, r_j) for ratios[1:]
    whs = [(s * (h / float(w)) ** 0 * jnp.sqrt(ratios[0]),
            s / jnp.sqrt(ratios[0])) for s in sizes]
    whs += [(sizes[0] * jnp.sqrt(r), sizes[0] / jnp.sqrt(r))
            for r in ratios[1:]]
    ws = jnp.asarray([p[0] for p in whs], jnp.float32)
    hs = jnp.asarray([p[1] for p in whs], jnp.float32)
    CY, CX = jnp.meshgrid(cy, cx, indexing="ij")
    centers = jnp.stack([CX.ravel(), CY.ravel()], axis=-1)  # [HW, 2]
    half = jnp.stack([ws, hs], axis=-1) / 2.0               # [A, 2]
    mins = centers[:, None, :] - half[None, :, :]
    maxs = centers[:, None, :] + half[None, :, :]
    boxes = jnp.concatenate([mins, maxs], axis=-1).reshape((-1, 4))
    if attrs["clip"]:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes[None]


def _iou(boxes_a, boxes_b):
    """Pairwise IoU of corner boxes [A,4] x [B,4] -> [A,B]."""
    tl = jnp.maximum(boxes_a[:, None, :2], boxes_b[None, :, :2])
    br = jnp.minimum(boxes_a[:, None, 2:], boxes_b[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((boxes_a[:, 2] - boxes_a[:, 0])
                         * (boxes_a[:, 3] - boxes_a[:, 1]), 0.0)
    area_b = jnp.maximum((boxes_b[:, 2] - boxes_b[:, 0])
                         * (boxes_b[:, 3] - boxes_b[:, 1]), 0.0)
    return inter / jnp.maximum(area_a[:, None] + area_b[None, :] - inter, 1e-12)


@register("_contrib_MultiBoxTarget",
          arg_names=("anchor", "label", "cls_pred"),
          num_outputs=3,
          params={"overlap_threshold": 0.5, "ignore_label": -1.0,
                  "negative_mining_ratio": -1.0, "negative_mining_thresh": 0.5,
                  "minimum_negative_samples": 0, "variances": (0.1, 0.1, 0.2, 0.2)},
          aliases=("MultiBoxTarget",))
# mxlint: allow-dtype-widening(detection/loss reference math runs in f32 by contract)
def multibox_target(attrs, ctx, anchor, label, cls_pred):
    """Anchor matching + target encoding.

    Reference: src/operator/contrib/multibox_target.cc.  Dense-masked
    formulation: per-batch [A] anchors matched against [M] padded GT boxes
    (label rows with id < 0 are padding), vmapped over the batch.
    Returns (loc_target [N, A*4], loc_mask [N, A*4], cls_target [N, A]).
    """
    variances = jnp.asarray(attrs["variances"], jnp.float32)
    thresh = float(attrs["overlap_threshold"])
    anchors = anchor.reshape((-1, 4))

    def one(lab, pred):
        ids = lab[:, 0]
        valid = ids >= 0
        gt = lab[:, 1:5]
        iou = _iou(anchors, gt)                        # [A, M]
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_gt = jnp.argmax(iou, axis=1)              # per anchor
        best_iou = jnp.max(iou, axis=1)
        # force-match: each valid gt claims its best anchor
        best_anchor = jnp.argmax(iou, axis=0)          # [M]
        forced = jnp.zeros(anchors.shape[0], bool)
        forced = forced.at[best_anchor].set(valid)
        claimed_gt = jnp.zeros(anchors.shape[0], jnp.int32)
        claimed_gt = claimed_gt.at[best_anchor].set(
            jnp.where(valid, jnp.arange(lab.shape[0]), 0).astype(jnp.int32))
        pos = forced | (best_iou >= thresh)
        match = jnp.where(forced, claimed_gt, best_gt)
        g = gt[match]
        # encode offsets (corner->center form), as the reference does
        aw = anchors[:, 2] - anchors[:, 0]
        ah = anchors[:, 3] - anchors[:, 1]
        acx = (anchors[:, 0] + anchors[:, 2]) / 2
        acy = (anchors[:, 1] + anchors[:, 3]) / 2
        gw = jnp.maximum(g[:, 2] - g[:, 0], 1e-8)
        gh = jnp.maximum(g[:, 3] - g[:, 1], 1e-8)
        gcx = (g[:, 0] + g[:, 2]) / 2
        gcy = (g[:, 1] + g[:, 3]) / 2
        loc = jnp.stack([(gcx - acx) / (aw * variances[0]),
                         (gcy - acy) / (ah * variances[1]),
                         jnp.log(gw / aw) / variances[2],
                         jnp.log(gh / ah) / variances[3]], axis=-1)
        loc = jnp.where(pos[:, None], loc, 0.0)
        mask = jnp.where(pos[:, None], 1.0, 0.0)
        mask = jnp.broadcast_to(mask, loc.shape)
        ratio = float(attrs["negative_mining_ratio"])
        if ratio > 0:
            # hard-negative mining (multibox_target.cc): keep the
            # ratio*npos highest-foreground-confidence negatives among
            # anchors overlapping gt below negative_mining_thresh; all
            # other negatives become ignore_label and drop out of the
            # classification loss — without this SSD collapses to
            # all-background (positives are <1% of anchors)
            ignore = float(attrs["ignore_label"])
            neg_thr = float(attrs["negative_mining_thresh"])
            min_neg = float(attrs["minimum_negative_samples"])
            fg = jax.nn.softmax(pred, axis=0)[1:].max(axis=0)
            eligible = (~pos) & (best_iou < neg_thr)
            score = jnp.where(eligible, fg, -jnp.inf)
            order = jnp.argsort(-score)
            rank = jnp.zeros(anchors.shape[0], jnp.int32).at[order].set(
                jnp.arange(anchors.shape[0], dtype=jnp.int32))
            num_neg = jnp.minimum(
                jnp.maximum(ratio * pos.sum(), min_neg), eligible.sum())
            neg = eligible & (rank < num_neg)
            cls_t = jnp.where(pos, ids[match] + 1.0,
                              jnp.where(neg, 0.0, ignore))
        else:
            cls_t = jnp.where(pos, ids[match] + 1.0, 0.0)
        return loc.reshape(-1), mask.reshape(-1), cls_t

    loc_t, loc_m, cls_t = jax.vmap(one)(label.astype(jnp.float32),
                                        cls_pred.astype(jnp.float32))
    return loc_t, loc_m, cls_t


@register("_contrib_MultiBoxDetection",
          arg_names=("cls_prob", "loc_pred", "anchor"),
          params={"clip": True, "threshold": 0.01, "background_id": 0,
                  "nms_threshold": 0.5, "force_suppress": False,
                  "variances": (0.1, 0.1, 0.2, 0.2), "nms_topk": -1},
          aliases=("MultiBoxDetection",))
# mxlint: allow-dtype-widening(detection/loss reference math runs in f32 by contract)
def multibox_detection(attrs, ctx, cls_prob, loc_pred, anchor):
    """Decode + class-wise NMS, static-shape (masked) formulation.

    Reference: src/operator/contrib/multibox_detection.cc.  Returns
    [N, A, 6] rows (class_id, score, xmin, ymin, xmax, ymax); suppressed
    rows have class_id -1 (reference convention).
    """
    variances = jnp.asarray(attrs["variances"], jnp.float32)
    anchors = anchor.reshape((-1, 4))
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2
    bg = int(attrs["background_id"])
    thr = float(attrs["threshold"])
    nms_thr = float(attrs["nms_threshold"])
    force = bool(attrs["force_suppress"])

    def one(probs, loc):
        loc = loc.reshape((-1, 4))
        cx = loc[:, 0] * variances[0] * aw + acx
        cy = loc[:, 1] * variances[1] * ah + acy
        w = jnp.exp(loc[:, 2] * variances[2]) * aw
        h = jnp.exp(loc[:, 3] * variances[3]) * ah
        boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2],
                          axis=-1)
        if attrs["clip"]:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        cls = jnp.argmax(probs, axis=0)
        # mask out background / low scores
        score_nobg = jnp.where(cls == bg, 0.0, jnp.max(probs, axis=0))
        keep = score_nobg > thr
        order = jnp.argsort(-score_nobg)
        # nms_topk (reference multibox_detection.cc nms_topk param) bounds
        # the pairwise-IoU working set to K^2 — mandatory at SSD anchor
        # counts (A^2 would be tens of GB); beyond-K rows are suppressed
        # like the reference's post-topk tail
        n_anchors = boxes.shape[0]
        topk = int(attrs["nms_topk"])
        k = min(topk, n_anchors) if topk > 0 else n_anchors
        order_k = order[:k]
        boxes_o = boxes[order_k]
        cls_o = cls[order_k]
        score_o = score_nobg[order_k]
        keep_o = keep[order_k]
        iou = _iou(boxes_o, boxes_o)
        same_class = (cls_o[:, None] == cls_o[None, :]) | force
        # greedy NMS as a scan over score-sorted boxes
        def body(alive, i):
            sup = (iou[i] > nms_thr) & same_class[i] & (jnp.arange(iou.shape[0]) > i)
            alive = jnp.where(alive[i], alive & ~sup, alive)
            return alive, None
        alive, _ = lax.scan(body, keep_o, jnp.arange(boxes_o.shape[0]))
        # reference convention: class ids exclude background (shift down when
        # background_id == 0); suppressed rows get -1
        shift = 1.0 if bg == 0 else 0.0
        out_cls = jnp.where(alive, cls_o.astype(jnp.float32) - shift, -1.0)
        out = jnp.concatenate([out_cls[:, None], score_o[:, None], boxes_o],
                              axis=-1)
        if k < n_anchors:
            pad = jnp.concatenate(
                [jnp.full((n_anchors - k, 1), -1.0),
                 score_nobg[order[k:], None], boxes[order[k:]]], axis=-1)
            out = jnp.concatenate([out, pad], axis=0)
        return out

    return jax.vmap(one)(cls_prob.astype(jnp.float32),
                         loc_pred.astype(jnp.float32))


@register("_contrib_CTCLoss", arg_names=("data", "label"),
          num_outputs=1, params={"use_data_lengths": False,
                                 "use_label_lengths": False, "blank_label": "first"},
          aliases=("CTCLoss", "ctc_loss"), is_loss=True)
# mxlint: allow-dtype-widening(detection/loss reference math runs in f32 by contract)
def ctc_loss(attrs, ctx, data, label):
    """CTC loss (reference: src/operator/contrib/ctc_loss.cc via warpctc).

    data: [T, B, V] unnormalized activations; label: [B, L] padded with 0
    (blank is class 0, 'first').  Dense log-alpha forward recursion under scan.
    """
    T, B, V = data.shape
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    labels = label.astype(jnp.int32)
    L = labels.shape[1]
    blank = 0 if attrs["blank_label"] == "first" else V - 1
    if blank != 0:
        raise MXNetError("only blank_label='first' supported")
    # label lengths: count of entries > 0 (reference padding convention)
    lab_len = jnp.sum((labels > 0).astype(jnp.int32), axis=1)
    # extended label sequence with interleaved blanks: length 2L+1
    S = 2 * L + 1
    ext = jnp.zeros((B, S), jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    neg_inf = -1e30

    def forward_b(logp_b, ext_b, lab_len_b):
        s_len = 2 * lab_len_b + 1
        alpha0 = jnp.full((S,), neg_inf)
        alpha0 = alpha0.at[0].set(logp_b[0, blank])
        alpha0 = alpha0.at[1].set(jnp.where(lab_len_b > 0,
                                            logp_b[0, ext_b[1]], neg_inf))

        def step(alpha, logp_t):
            prev1 = jnp.concatenate([jnp.array([neg_inf]), alpha[:-1]])
            prev2 = jnp.concatenate([jnp.array([neg_inf, neg_inf]), alpha[:-2]])
            idx = jnp.arange(S)
            can_skip = (idx % 2 == 1) & (idx >= 2)
            same = jnp.where(idx >= 2, ext_b == jnp.roll(ext_b, 2), True)
            allow2 = can_skip & ~same
            a = jnp.logaddexp(alpha, prev1)
            a = jnp.where(allow2, jnp.logaddexp(a, prev2), a)
            a = a + logp_t[ext_b]
            a = jnp.where(idx < s_len, a, neg_inf)
            return a, None

        alphaT, _ = lax.scan(step, alpha0, logp_b[1:])
        last = alphaT[jnp.maximum(s_len - 1, 0)]
        last2 = jnp.where(s_len >= 2, alphaT[jnp.maximum(s_len - 2, 0)], neg_inf)
        return -jnp.logaddexp(last, last2)

    return jax.vmap(forward_b)(jnp.swapaxes(logp, 0, 1), ext, lab_len)


@register("_contrib_quantize", arg_names=("data", "min_range", "max_range"),
          num_outputs=3, params={"out_type": "uint8"})
def quantize(attrs, ctx, data, min_range, max_range):
    """Reference: src/operator/contrib/quantize.cc."""
    out_dt = dtype_np(attrs["out_type"])
    qmin = float(jnp.iinfo(out_dt).min)
    qmax = float(jnp.iinfo(out_dt).max)
    scale = (qmax - qmin) / (max_range - min_range)
    q = jnp.clip(jnp.round((data - min_range) * scale + qmin), qmin, qmax)
    return q.astype(out_dt), min_range, max_range


@register("_contrib_dequantize", arg_names=("data", "min_range", "max_range"),
          params={"out_type": "float32"})
# mxlint: allow-dtype-widening(detection/loss reference math runs in f32 by contract)
def dequantize(attrs, ctx, data, min_range, max_range):
    info = jnp.iinfo(data.dtype)
    scale = (max_range - min_range) / (float(info.max) - float(info.min))
    return ((data.astype(jnp.float32) - float(info.min)) * scale
            + min_range).astype(dtype_np(attrs["out_type"]))


@register("_contrib_fft", params={"compute_size": 128})
# mxlint: allow-dtype-widening(detection/loss reference math runs in f32 by contract)
def fft(attrs, ctx, data):
    """Reference: src/operator/contrib/fft.cc — rfft packed as interleaved re/im."""
    out = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    return jnp.stack([out.real, out.imag], axis=-1).reshape(
        data.shape[:-1] + (2 * data.shape[-1],)).astype(jnp.float32)


@register("_contrib_ifft", params={"compute_size": 128})
def ifft(attrs, ctx, data):
    re = data[..., 0::2]
    im = data[..., 1::2]
    out = jnp.fft.ifft(re + 1j * im, axis=-1)
    return out.real.astype(jnp.float32)


@register("_contrib_count_sketch", arg_names=("data", "h", "s"),
          params={"out_dim": 0, "processing_batch_size": 32})
def count_sketch(attrs, ctx, data, h, s):
    """Reference: src/operator/contrib/count_sketch.cc."""
    out_dim = int(attrs["out_dim"])
    idx = h.astype(jnp.int32).reshape(-1)
    sign = s.astype(data.dtype).reshape(-1)
    out = jnp.zeros(data.shape[:-1] + (out_dim,), data.dtype)
    return out.at[..., idx].add(data * sign)


@register("_contrib_SwitchMoE",
          arg_names=("data", "router_weight", "expert1_weight",
                     "expert1_bias", "expert2_weight", "expert2_bias"),
          num_outputs=2,
          params={"num_experts": 0, "hidden_size": 0,
                  "capacity_factor": 1.25},
          aliases=("SwitchMoE",))
def switch_moe_op(attrs, ctx, data, router_weight, expert1_weight,
                  expert1_bias, expert2_weight, expert2_bias):
    """Switch-routed mixture-of-experts FFN over (batch, seq, d) or
    (tokens, d) inputs; returns (output, load_balance_loss).

    Symbol-level surface of :func:`mxnet_tpu.parallel.moe.switch_moe`
    (expert sharding comes from the surrounding mesh via GSPMD when the
    step runs under one — the op itself is placement-agnostic).
    """
    from ..parallel.moe import switch_moe as _moe
    if int(attrs["num_experts"]) <= 0 or int(attrs["hidden_size"]) <= 0:
        raise MXNetError("_contrib_SwitchMoE requires num_experts > 0 "
                         "and hidden_size > 0")
    if (router_weight.shape[1] != int(attrs["num_experts"])
            or expert1_weight.shape[2] != int(attrs["hidden_size"])):
        raise MXNetError(
            "_contrib_SwitchMoE: weights shaped for E=%d, ff=%d do not "
            "match num_experts=%s hidden_size=%s"
            % (router_weight.shape[1], expert1_weight.shape[2],
               attrs["num_experts"], attrs["hidden_size"]))
    shape = data.shape
    x = data.reshape(-1, shape[-1]) if data.ndim > 2 else data
    y, aux = _moe(x, router_weight, expert1_weight, expert1_bias,
                  expert2_weight, expert2_bias,
                  capacity_factor=float(attrs["capacity_factor"]))
    return y.reshape(shape), aux


def _topk_moe_args(attrs):
    names = ("data", "router_weight")
    if attrs.get("use_expert_bias", True):
        names += ("expert_bias",)
    if attrs.get("expert_act", "silu_gated") == "relu2":
        return names + ("w1_weight", "w2_weight")
    return names + ("w1_weight", "w3_weight", "w2_weight")


@register("_contrib_TopKMoE", arg_names=_topk_moe_args,
          aux_names=("load",),
          params={"num_experts": 0, "experts_held": 0, "expert_offset": 0,
                  "num_experts_per_tok": 1, "hidden_size": 0,
                  "norm_topk_prob": True, "routed_scaling_factor": 1.0,
                  "use_expert_bias": True, "router_trained": True,
                  "expert_act": "silu_gated", "score_func": "sigmoid"},
          aliases=("TopKMoE",))
def topk_moe_op(attrs, ctx, data, router_weight, *rest):
    """Token-choice top-k mixture-of-experts feed-forward over
    (batch, seq, d) or (tokens, d), computing the share of the result
    that the experts held here give.

    ``num_experts`` is the router's width (all experts of the layer),
    ``experts_held`` (0: all) and ``expert_offset`` say which of them
    this layer holds; ``router_weight`` is ``(num_experts, d)``,
    ``expert_bias`` ``(num_experts,)`` (selection only; absent with
    ``use_expert_bias=False``; the scores are ``sigmoid`` of the router's
    outputs or, with ``score_func="softmax"``, their softmax over all
    ``num_experts`` in float32), ``w1_weight``/``w3_weight``
    ``(experts_held, d, hidden_size)`` and ``w2_weight``
    ``(experts_held, hidden_size, d)``: with ``expert_act``
    ``"silu_gated"`` an expert is ``w2(silu(x w1) * (x w3))``, with
    ``"relu2"`` the ungated ``w2(relu(x w1^T)^2)``: there is no
    ``w3_weight`` input and ``w1_weight`` is ``(experts_held,
    hidden_size, d)``, the model's width last in both of an expert's
    matrices.  The partial results of shares
    that together hold all the experts add up to the whole layer's.
    So do their gradients for ``data`` and ``router_weight``: a share
    returns its true part of both.  ``router_trained=False`` makes the
    scores constants to the gradient instead (no gradient for
    ``router_weight``, none through the gates), for a graph that is one
    share and runs without the others; see ``topk_moe``.
    The auxiliary state ``load`` (``experts_held + 1``) carries the held
    experts' assignment counts of the last step and the tokens with no
    held expert.

    Symbol-level surface of :func:`mxnet_tpu.parallel.moe.topk_moe`.
    """
    from ..parallel import moe as _moe
    load = rest[-1]
    bias = rest[0] if attrs.get("use_expert_bias", True) else None
    act = attrs.get("expert_act", "silu_gated")
    if act not in ("silu_gated", "relu2"):
        raise MXNetError("_contrib_TopKMoE: expert_act %r is neither "
                         "silu_gated nor relu2" % (act,))
    score = attrs.get("score_func", "sigmoid")
    if score not in ("sigmoid", "softmax"):
        raise MXNetError("_contrib_TopKMoE: score_func %r is neither "
                         "sigmoid nor softmax" % (score,))
    if act == "relu2":
        (w1, w2), w3 = rest[-3:-1], None
    else:
        w1, w3, w2 = rest[-4:-1]
    e, k = int(attrs["num_experts"]), int(attrs["num_experts_per_tok"])
    held = int(attrs["experts_held"]) or e
    off, ff = int(attrs["expert_offset"]), int(attrs["hidden_size"])
    if not (0 < k <= e and 0 < held and 0 <= off and off + held <= e
            and ff > 0):
        raise MXNetError(
            "_contrib_TopKMoE: num_experts=%d, experts_held=%d, "
            "expert_offset=%d, num_experts_per_tok=%d, hidden_size=%d do "
            "not describe a share of a layer" % (e, held, off, k, ff))
    d = data.shape[-1]
    for name, arr, shape in (("router_weight", router_weight, (e, d)),
                             ("w1_weight", w1, (held, ff, d) if w3 is None
                              else (held, d, ff)),
                             ("w3_weight", w3, (held, d, ff)),
                             ("w2_weight", w2, (held, ff, d))):
        if arr is not None and tuple(arr.shape) != shape:
            raise MXNetError(
                "_contrib_TopKMoE: %s is %s where the attributes ask for "
                "%s" % (name, tuple(arr.shape), shape))
    x = data.reshape(-1, d)
    y, new_load = _moe.topk_moe(
        x, router_weight, bias, w1, w3, w2, k, expert_offset=off,
        norm_topk_prob=bool(attrs["norm_topk_prob"]),
        routed_scaling_factor=float(attrs["routed_scaling_factor"]),
        router_trained=bool(attrs["router_trained"]), score_func=score)
    return y.reshape(data.shape), new_load.astype(load.dtype)


@register("_contrib_KDAGate", arg_names=("data", "a_log", "dt_bias"),
          params={"num_heads": 1}, aliases=("KDAGate",))
# mxlint: allow-dtype-widening(the log-decay is float32 by the op's definition: its running sums reach the hundreds inside a chunk)
def kda_gate(attrs, ctx, data, a_log, dt_bias):
    """The per-channel log-decay of a gated delta-rule layer, in
    float32: ``g = -exp(a_log) * softplus(data + dt_bias)`` over
    ``(batch, seq, num_heads * head_dim)``, with ``a_log``
    ``(num_heads,)`` one scalar a head and ``dt_bias`` ``(num_heads *
    head_dim,)``; returns ``(batch, seq, num_heads, head_dim)``,
    ``<= 0``, so that ``exp(g)`` lies in ``(0, 1]``."""
    h = int(attrs["num_heads"])
    if data.ndim != 3 or h <= 0 or data.shape[2] % h \
            or a_log.shape != (h,) or dt_bias.shape != data.shape[2:]:
        raise MXNetError(
            "_contrib_KDAGate wants (batch, seq, num_heads * head_dim) data, "
            "a_log (num_heads,) and dt_bias (num_heads * head_dim,); got "
            "data %s, a_log %s, dt_bias %s with num_heads=%d"
            % (tuple(data.shape), tuple(a_log.shape), tuple(dt_bias.shape), h))
    f32 = jnp.float32

    # rematerialised: the backward keeps the op's inputs, not its float32
    # softplus
    @jax.checkpoint
    def gate(data, a_log, dt_bias):
        soft = jax.nn.softplus(data.astype(f32) + dt_bias.astype(f32))
        return soft.reshape(data.shape[:2] + (h, -1)) \
            * -jnp.exp(a_log.astype(f32))[:, None]

    return gate(data, a_log, dt_bias)


@register("_contrib_GatedDeltaRule",
          arg_names=("q", "k", "v", "g", "beta"),
          params={"chunk_size": 64, "qk_l2norm": False, "scale": 1.0},
          aliases=("GatedDeltaRule",))
def gated_delta_rule_op(attrs, ctx, q, k, v, g, beta):
    """Linear attention by the gated delta rule with a per-channel decay
    (Kimi Delta Attention): ``q, k`` ``(batch, seq, heads, dk)``, ``v``
    ``(batch, seq, heads, dv)``, the log-decay ``g`` ``(batch, seq,
    heads, dk)`` (float32, ``<= 0``: ``_contrib_KDAGate``'s) and the
    write strength ``beta`` ``(batch, seq, heads)``; returns ``(batch,
    seq, heads, dv)``.  A head carries a ``dk x dv`` state ``S``
    (``S_0 = 0``) along the sequence::

        S'  = Diag(exp(g_t)) S_{t-1}
        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t

    computed as a scan over chunks of ``chunk_size`` positions (matrix
    products inside a chunk, one state a head carried between chunks and
    kept for the backward): :mod:`mxnet_tpu.ops.delta_rule`.  With
    ``qk_l2norm`` each head of ``q`` and of ``k`` is first normalised (``x
    * rsqrt(sum x^2 + 1e-6)``); ``q`` is then multiplied by ``scale``."""
    from . import delta_rule
    shapes = (tuple(q.shape), tuple(k.shape), tuple(v.shape),
              tuple(g.shape), tuple(beta.shape))
    if q.ndim != 4 or k.shape != q.shape or g.shape != q.shape \
            or v.shape[:3] != q.shape[:3] or v.ndim != 4 \
            or beta.shape != q.shape[:3] or int(attrs["chunk_size"]) <= 0:
        raise MXNetError(
            "_contrib_GatedDeltaRule wants q, k, g (batch, seq, heads, dk), "
            "v (batch, seq, heads, dv) and beta (batch, seq, heads); got "
            "q %s, k %s, v %s, g %s, beta %s" % shapes)
    return delta_rule.gated_delta_rule(
        q, k, v, g, beta, chunk=int(attrs["chunk_size"]),
        qk_l2norm=bool(attrs["qk_l2norm"]), scale=float(attrs["scale"]))


@register("_contrib_SSDScan",
          arg_names=("x", "dt", "B", "C", "A_log", "D", "dt_bias"),
          params={"chunk_size": 128}, aliases=("SSDScan",))
# mxlint: allow-dtype-widening(the step is float32 by the op's definition: its products with the decay are summed over a chunk)
def ssd_scan_op(attrs, ctx, x, dt, b, c, a_log, d, dt_bias):
    """Mamba-2's selective state-space recurrence (arXiv:2405.21060):
    ``x`` ``(batch, seq, heads, head_dim)``, the raw steps ``dt`` ``(batch,
    seq, heads)``, ``B`` and ``C`` ``(batch, seq, groups, state)`` (head
    ``h`` reads group ``h // (heads / groups)``), ``A_log``, ``D`` and
    ``dt_bias`` ``(heads,)``; returns ``(batch, seq, heads, head_dim)``.
    The step is ``delta_t = softplus(dt_t + dt_bias)``, taken in float32
    (``mamba_ssm``'s ``dt_bias`` under ``dt_softplus``).  A head carries a
    ``head_dim x state`` float32 state ``S`` (``S_0 = 0``) along the
    sequence, with ``A = -exp(A_log)``::

        S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T
        y_t = S_t C_t + D x_t

    computed as a scan over chunks of ``chunk_size`` positions (four
    batched products a chunk, one state a head carried between chunks and
    one kept a group of chunks for the backward):
    :mod:`mxnet_tpu.ops.ssd`, whose ``ssd_scan`` takes the step itself.
    ``seq`` is a whole number of chunks."""
    from . import ssd
    shapes = tuple(tuple(a.shape) for a in (x, dt, b, c, a_log, d, dt_bias))
    if x.ndim != 4 or dt.shape != x.shape[:3] or b.ndim != 4 \
            or c.shape != b.shape or b.shape[:2] != x.shape[:2] \
            or a_log.shape != x.shape[2:3] or d.shape != x.shape[2:3] \
            or dt_bias.shape != x.shape[2:3] or x.shape[2] % b.shape[2]:
        raise MXNetError(
            "_contrib_SSDScan wants x (batch, seq, heads, head_dim), dt "
            "(batch, seq, heads), B, C (batch, seq, groups, state) with "
            "heads a multiple of groups, A_log, D and dt_bias (heads,); got "
            "x %s, dt %s, B %s, C %s, A_log %s, D %s, dt_bias %s" % shapes)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    try:
        return ssd.ssd_scan(x, dt, b, c, a_log, d,
                            chunk=int(attrs["chunk_size"]))
    except ValueError as e:
        raise MXNetError("_contrib_SSDScan: %s" % e) from e
