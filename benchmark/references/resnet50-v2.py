"""Plain reference of ResNet-50 v2 (He et al., "Identity Mappings in Deep
Residual Networks"; MXNet ``example/image-classification/symbols/resnet.py``).

Straightforward ``jax.numpy``/``lax`` in float32, NHWC activations, weights in
the published OIHW shapes under the published names.  Batch norm uses the
batch's own statistics (training mode); the moving averages are not followed
because nothing in a training step reads them.  Each residual unit is
rematerialised in the backward pass so that the float32 activations of a batch
of 128 need less of the chip than the program under test does (4.3 GB against
5.8 GB); that changes no value.  (Running a stage's like-shaped units as one
``lax.scan`` was tried for a shorter compile: it needed 6.9 GB, so it went.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from common import q, softmax_xent

EPS = 2e-5
UNITS = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


def _stages(cfg):
    return list(zip(cfg.get("units") or UNITS[cfg["num_layers"]], cfg["filter_list"][1:]))


def param_shapes(cfg):
    f0 = cfg["filter_list"][0]
    s = {"bn_data_gamma": (3,), "bn_data_beta": (3,),
         "conv0_weight": (f0, 3, 7, 7), "bn0_gamma": (f0,), "bn0_beta": (f0,)}
    c_in = f0
    for i, (n_units, c_out) in enumerate(_stages(cfg)):
        mid = c_out // 4
        for j in range(n_units):
            p = "stage%d_unit%d_" % (i + 1, j + 1)
            s[p + "bn1_gamma"] = s[p + "bn1_beta"] = (c_in,)
            s[p + "conv1_weight"] = (mid, c_in, 1, 1)
            s[p + "bn2_gamma"] = s[p + "bn2_beta"] = (mid,)
            s[p + "conv2_weight"] = (mid, mid, 3, 3)
            s[p + "bn3_gamma"] = s[p + "bn3_beta"] = (mid,)
            s[p + "conv3_weight"] = (c_out, mid, 1, 1)
            if j == 0:
                s[p + "sc_weight"] = (c_out, c_in, 1, 1)
            c_in = c_out
    s["bn1_gamma"] = s["bn1_beta"] = (c_in,)
    s["fc1_weight"] = (cfg["num_classes"], c_in)
    s["fc1_bias"] = (cfg["num_classes"],)
    return s


def init_params(cfg, key):
    """He-normal weights, unit gammas, zero betas and biases, from one key."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_weight"):
            fan_in = 1
            for d in shp[1:]:
                fan_in *= d
            std = (2.0 / fan_in) ** 0.5
            if name.endswith("conv3_weight"):   # the last convolution of a residual branch
                std *= cfg.get("residual_branch_scale", 1.0)
            out[name] = jax.random.normal(k, shp, jnp.float32) * std
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shp, jnp.float32)
        else:
            out[name] = jnp.zeros(shp, jnp.float32)
    return out


def _conv(x, w, stride, pad, quant):
    return lax.conv_general_dilated(
        q(x, quant), q(jnp.transpose(w, (2, 3, 1, 0)), quant), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * gamma + beta


def _unit(x, p, stride, match, quant):
    a1 = jax.nn.relu(_bn(x, p["bn1_gamma"], p["bn1_beta"]))
    c1 = _conv(a1, p["conv1_weight"], 1, 0, quant)
    a2 = jax.nn.relu(_bn(c1, p["bn2_gamma"], p["bn2_beta"]))
    c2 = _conv(a2, p["conv2_weight"], stride, 1, quant)
    a3 = jax.nn.relu(_bn(c2, p["bn3_gamma"], p["bn3_beta"]))
    c3 = _conv(a3, p["conv3_weight"], 1, 0, quant)
    return c3 + (x if match else _conv(a1, p["sc_weight"], stride, 0, quant))


def loss(params, batch, cfg, quant=None):
    """(sum, mean) of the cross-entropy of one NCHW batch."""
    x = jnp.transpose(batch["data"].astype(jnp.float32), (0, 2, 3, 1))
    labels = batch["softmax_label"].astype(jnp.int32)
    x = _bn(x, jnp.ones_like(params["bn_data_gamma"]), params["bn_data_beta"])  # fix_gamma
    x = _conv(x, params["conv0_weight"], 2, 3, quant)
    x = jax.nn.relu(_bn(x, params["bn0_gamma"], params["bn0_beta"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    unit = jax.checkpoint(_unit, static_argnums=(2, 3, 4))
    for i, (n_units, _c) in enumerate(_stages(cfg)):
        for j in range(n_units):
            pre = "stage%d_unit%d_" % (i + 1, j + 1)
            p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
            x = unit(x, p, 2 if (j == 0 and i > 0) else 1, j > 0, quant)
    x = jax.nn.relu(_bn(x, params["bn1_gamma"], params["bn1_beta"]))
    x = jnp.mean(x, axis=(1, 2))
    logits = q(x, quant) @ q(params["fc1_weight"], quant).T + params["fc1_bias"]
    return softmax_xent(logits, labels)
