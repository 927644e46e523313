"""Parameter-shape inference hooks.

The reference infers weight/bias/aux shapes from data shapes via each op's
``FInferShape``/``OperatorProperty::InferShape`` (e.g. FullyConnected weight =
(num_hidden, flattened-in-dim), `src/operator/fully_connected-inl.h:148-187`).
The TPU build gets *output* shapes for free from ``jax.eval_shape`` over
fcompute; only the shapes of parameter/aux inputs need op-specific rules —
registered here, consumed by ``Symbol.infer_shape``/``simple_bind``.

Hook signature: ``hook(attrs, known) -> {arg_or_aux_name: shape}`` where
``known`` maps already-inferred input names (normally just ``data``) to
shapes.  A hook may return only what it can infer.
"""
from __future__ import annotations

from .rnn import rnn_param_size
from .nn import current_image_layout

_PARAM_SHAPE_HOOKS = {}


def _channels(data, attrs=None):
    """Channel count of an activation under the active image layout.
    Weights always keep the reference (channel-major) layout; only 4-d
    activations move to NHWC under ``image_layout('NHWC')``."""
    if len(data) == 4 and current_image_layout() == "NHWC":
        return int(data[3])
    return int(data[1])


def register_param_shapes(op_name):
    def deco(fn):
        _PARAM_SHAPE_HOOKS[op_name] = fn
        return fn
    return deco


def get_param_shapes(op_name):
    return _PARAM_SHAPE_HOOKS.get(op_name)


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


@register_param_shapes("FullyConnected")
def _fc(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    num_hidden = int(attrs["num_hidden"])
    in_dim = _prod(data[1:]) if attrs["flatten"] else int(data[-1])
    out = {"weight": (num_hidden, in_dim)}
    if not attrs["no_bias"]:
        out["bias"] = (num_hidden,)
    return out


@register_param_shapes("Convolution")
def _conv(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    kernel = tuple(int(k) for k in attrs["kernel"])
    num_filter = int(attrs["num_filter"])
    group = int(attrs["num_group"])
    out = {"weight": (num_filter, _channels(data) // group) + kernel}
    if not attrs["no_bias"]:
        out["bias"] = (num_filter,)
    return out


@register_param_shapes("Deconvolution")
def _deconv(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    kernel = tuple(int(k) for k in attrs["kernel"])
    num_filter = int(attrs["num_filter"])
    group = int(attrs["num_group"])
    # reference: weight shape (C, num_filter/group, *kernel)
    # (src/operator/deconvolution-inl.h InferShape)
    out = {"weight": (_channels(data), num_filter // group) + kernel}
    if not attrs["no_bias"]:
        out["bias"] = (num_filter,)
    return out


@register_param_shapes("BatchNorm")
def _bn(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    axis = int(attrs.get("axis", 1))
    c = (_channels(data) if axis == 1 else int(data[axis]),)
    return {"gamma": c, "beta": c, "moving_mean": c, "moving_var": c}


@register_param_shapes("InstanceNorm")
def _in(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    c = (_channels(data),)
    return {"gamma": c, "beta": c}


@register_param_shapes("LayerNorm")
def _ln(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    axis = int(attrs.get("axis", -1))
    c = (int(data[axis]),)
    return {"gamma": c, "beta": c}


@register_param_shapes("RMSNorm")
def _rms(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    return {"gamma": (int(data[int(attrs.get("axis", -1))]),)}


@register_param_shapes("_contrib_GatedRMSNorm")
def _gated_rms(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    axes = int(attrs.get("gamma_axes", 1))
    return {"gamma": tuple(int(n) for n in data[-axes:])}


@register_param_shapes("_contrib_KDAGate")
def _kda_gate(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    return {"a_log": (int(attrs["num_heads"]),), "dt_bias": (int(data[-1]),)}


@register_param_shapes("_contrib_SSDScan")
def _ssd_scan(attrs, known):
    x = known.get("x")
    if x is None:
        return {}
    heads = (int(x[2]),)
    return {"A_log": heads, "D": heads, "dt_bias": heads}


@register_param_shapes("_contrib_CausalConv1D")
def _causal_conv1d(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    return {"weight": (int(data[-1]), int(attrs["kernel"])),
            "bias": (int(data[-1]),)}


@register_param_shapes("LeakyReLU")
def _prelu(attrs, known):
    data = known.get("data")
    if data is None or attrs["act_type"] != "prelu":
        return {}
    return {"gamma": (_channels(data),)}


@register_param_shapes("Embedding")
def _embedding(attrs, known):
    return {"weight": (int(attrs["input_dim"]), int(attrs["output_dim"]))}


@register_param_shapes("SoftmaxOutput")
def _softmax_out(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    # reference: label is class indices (batch,) unless multi_output
    # (softmax_output.cc InferShape)
    if attrs.get("multi_output"):
        return {"label": (int(data[0]),) + tuple(int(d) for d in data[2:])}
    return {"label": (int(data[0]),)}


@register_param_shapes("SVMOutput")
def _svm_out(attrs, known):
    data = known.get("data")
    return {} if data is None else {"label": (int(data[0]),)}


def _same_as_data(attrs, known):
    data = known.get("data")
    return {} if data is None else {"label": tuple(data)}


for _nm in ("LinearRegressionOutput", "MAERegressionOutput",
            "LogisticRegressionOutput", "MakeLoss"):
    _PARAM_SHAPE_HOOKS.setdefault(_nm, _same_as_data)


@register_param_shapes("RNN")
def _rnn(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    # data layout TNC (reference rnn-inl.h: seq_len, batch, input_size)
    seq_len, batch, input_size = int(data[0]), int(data[1]), int(data[2])
    mode = attrs["mode"]
    state_size = int(attrs["state_size"])
    num_layers = int(attrs["num_layers"])
    bid = bool(attrs["bidirectional"])
    dirs = 2 if bid else 1
    out = {
        "parameters": (rnn_param_size(mode, input_size, state_size,
                                      num_layers, bid),),
        "state": (num_layers * dirs, batch, state_size),
    }
    if mode == "lstm":
        out["state_cell"] = (num_layers * dirs, batch, state_size)
    return out


@register_param_shapes("Custom")
def _custom(attrs, known):
    """Let a CustomOpProp's infer_shape fill its parameter-arg shapes
    (reference custom-inl.h InferShape callback: props conventionally
    derive label/weight shapes from the data shape)."""
    from .. import operator as _op
    try:
        prop = _op._make_prop(attrs)
    except Exception:  # mxlint: allow-broad-except(user CustomOpProp constructors raise arbitrary types; hooks are best-effort)
        return {}
    args = prop.list_arguments()
    in_shapes = [list(known[nm]) if nm in known else None for nm in args]
    if not in_shapes or in_shapes[0] is None:
        return {}
    if any(s is None for s in in_shapes):
        # partial info: props conventionally only need in_shape[0], but a
        # prop that indexes a missing input is allowed to give up here
        try:
            arg_shapes, _, _ = prop.infer_shape(in_shapes)
        except Exception:  # mxlint: allow-broad-except(user infer_shape on partial info may legitimately fail; full-info failures propagate below)
            return {}
    else:
        # all inputs known: a failure is a real bug in the user's
        # infer_shape — propagate it (reference custom-inl.h behavior)
        arg_shapes, _, _ = prop.infer_shape(in_shapes)
    return {nm: tuple(s) for nm, s in zip(args, arg_shapes)
            if s is not None}


@register_param_shapes("_contrib_SwitchMoE")
def _switch_moe(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    e = int(attrs["num_experts"])
    ff = int(attrs["hidden_size"])
    return {"router_weight": (d, e), "expert1_weight": (e, d, ff),
            "expert1_bias": (e, ff), "expert2_weight": (e, ff, d),
            "expert2_bias": (e, d)}


@register_param_shapes("_contrib_TopKMoE")
def _topk_moe(attrs, known):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    e = int(attrs["num_experts"])
    held = int(attrs["experts_held"]) or e
    ff = int(attrs["hidden_size"])
    up = (held, ff, d) if attrs.get("expert_act") == "relu2" else (held, d, ff)
    return {"router_weight": (e, d), "expert_bias": (e,),
            "w1_weight": up, "w3_weight": (held, d, ff),
            "w2_weight": (held, ff, d), "load": (held + 1,)}
