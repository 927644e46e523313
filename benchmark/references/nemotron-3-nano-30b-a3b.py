"""Plain reference of the Nemotron-H decoder
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` ``config.json``, ``model_type``
``nemotron_h``; layer equations as ``transformers``' ``modeling_nemotron_h.py``
and ``mamba_ssm``'s ``Mamba2`` (arXiv:2405.21060) compute them): layers that are
ONE mixer under one norm each, read from ``hybrid_override_pattern``: Mamba-2
state-space mixers (``M``), top-6-of-128 layers of ungated relu^2 experts with
sigmoid scores, a selection-only bias and one shared expert (``E``), and causal
attention of 32 query heads over 2 key/value heads without any positional
encoding (``*``); an untied head.

It is given the same share of the deployment as the system
(``configs/nemotron-3-nano-30b-a3b.json``): the experts ``expert_offset ..
expert_offset + n_routed_experts - 1`` of a router ``router_num_experts`` wide,
and the sliced vocabulary.  Every token is routed over the router's whole width
and the gates normalised over all its chosen experts; what the absent experts
would have added is left out.  There is no buffer here: every held assignment is
computed.  Departures from the published model, the same as the system's graph:
a selection bias that training does not move, positions from 0 and no cache,
and, where the configuration says ``router_trained: false``, scores that are
constants to the gradient.

Straightforward ``jax.numpy`` in float32.  The Mamba-2 recurrence runs TOKEN BY
TOKEN, as it is written: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
S_t C_t + D x_t`` (``lax.scan`` over positions, one ``P x N`` state a head; no
chunk, no ``L``; ``jax.checkpoint`` over blocks of positions so that its gradient
fits: blocking, no algebra).  Its step is elementwise products and one sum, no
``dot`` (one function, :func:`recurrence`, for all the mixers; what it costs to
compile and run is in PERF.md).  The convolution is four shifted sums;
attention's scores are made 1024 query rows at a time with the key/value heads
repeated; experts by a plain loop over the held experts with a mask, no sort, no
kernel.  Sequences do not interact, so the loss is summed one sequence at a time
and each layer is rematerialised in the backward pass.  ``q(...)`` marks every
matmul operand but the router's, the recurrence's ``x``, ``B``, ``C`` among them
(the fp8 control rounds them).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from common import q, softmax_xent

EXPERT_BIAS_STD = 0.1
#: the selection biases are ``normal(PRNGKey(EXPERT_BIAS_DRAW) folded with the
#: layer's index)``, the same in every run (a model's bias is the model's: PERF.md
#: 6, PR 26).  ``configs/nemotron-3-nano-30b-a3b.json`` (``assumed.expert_bias``)
#: says how the draw was chosen and the four shares it gives.
EXPERT_BIAS_DRAW = 124
#: query rows whose float32 scores against every key are held at a time
ATTENTION_ROWS = 1024
#: positions of the recurrence between two kept states
RECURRENCE_BLOCK = 64


def _layers(cfg):
    """``[(index, kind)]`` of the layers built: ``M``, ``E`` or ``*``."""
    return list(enumerate(cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]))


def _mamba_sizes(cfg):
    """(heads, head width, groups, state)"""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p, g, n = _mamba_sizes(cfg)
    inner, conv = h * p, h * p + 2 * g * n
    ha, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    fe, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    held = cfg["n_routed_experts"]
    e = cfg.get("router_num_experts", held)
    s = {"embed_weight": (v, d)}
    for i, kind in _layers(cfg):
        pre = "layer%d_" % i
        s[pre + "norm_gamma"] = (d,)
        if kind == "M":
            s[pre + "in_proj_weight"] = (inner + conv + h, d)
            s[pre + "conv_weight"] = (conv, cfg["conv_kernel"])
            s[pre + "conv_bias"] = (conv,)
            s[pre + "a_log_bias"] = s[pre + "d_gamma"] = s[pre + "dt_bias"] = (h,)
            s[pre + "mixer_norm_gamma"] = (g, inner // g)
            s[pre + "out_proj_weight"] = (d, inner)
        elif kind == "*":
            s[pre + "q_weight"] = (ha * hd, d)
            s[pre + "k_weight"] = s[pre + "v_weight"] = (hk * hd, d)
            s[pre + "o_weight"] = (d, ha * hd)
        else:
            s[pre + "moe_router_weight"], s[pre + "moe_expert_bias"] = (e, d), (e,)
            s[pre + "moe_w1_weight"] = s[pre + "moe_w2_weight"] = (held, fe, d)
            if cfg.get("n_shared_experts", 0):
                s[pre + "shared_w1_weight"], s[pre + "shared_w2_weight"] = (fs, d), (d, fs)
    s["final_norm_gamma"], s["lm_head_weight"] = (d,), (v, d)
    return s


def init_params(cfg, key):
    """Normal(0, initializer_range) for every ``*_weight`` and the convolutions'
    biases from ``key``, but for two kinds (the configuration's ``assumed`` has
    why): a mixer's ``out_proj`` is drawn as ``rescale_prenorm_residual`` draws
    it, uniform(+-1 / sqrt(inner width)) over the square root of the PUBLISHED
    number of layers, and an expert's or the shared expert's down-projection
    ``w2`` is its normal draw less its mean over the hidden units (every
    ``relu^2`` unit is non-negative: a row that sums to zero adds no vector
    common to all tokens).  Unit gains, ``D`` (``*_d_gamma``) among them; ``A_log
    = log(uniform(1, 16))`` a head and ``dt_bias`` the inverse softplus of
    ``exp(uniform(log time_step_min, log time_step_max))`` from ``key``
    (``Mamba2``'s own initialisation; ``time_step_floor`` lies under
    ``time_step_min`` and never binds); selection biases normal(0, 0.1) from
    ``EXPERT_BIAS_DRAW`` and the layer's index, the same in every run."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    bias_key = jax.random.PRNGKey(EXPERT_BIAS_DRAW)
    std = cfg.get("initializer_range", 0.02)
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_out_proj_weight") and cfg.get("rescale_prenorm_residual"):
            bound = shp[1] ** -0.5 / math.sqrt(depth)
            out[name] = jax.random.uniform(k, shp, jnp.float32, -bound, bound)
        elif name.endswith("_w2_weight"):
            w = jax.random.normal(k, shp, jnp.float32) * std
            out[name] = w - jnp.mean(w, axis=1, keepdims=True)   # hidden: axis 1 of both
        elif name.endswith("_weight") or name.endswith("_conv_bias"):
            out[name] = jax.random.normal(k, shp, jnp.float32) * std
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shp, jnp.float32)
        elif name.endswith("_a_log_bias"):
            out[name] = jnp.log(jax.random.uniform(k, shp, jnp.float32, 1.0, 16.0))
        elif name.endswith("_dt_bias"):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shp, jnp.float32, lo, hi)),
                             cfg["time_step_floor"])
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:  # layer<i>_moe_expert_bias
            layer = int(name[len("layer"):name.index("_")])
            out[name] = jax.random.normal(jax.random.fold_in(bias_key, layer), shp,
                                          jnp.float32) * EXPERT_BIAS_STD
    return out


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _linear(x, w, quant):
    return q(x, quant) @ q(w, quant).T


def relu2_mlp(x, w1, w2, quant):
    """``w2(relu(w1 x)^2)``, no gate matrix; ``w1`` (out, in), ``w2`` (in, out):
    the model's width is the last axis of both."""
    h = jnp.square(jax.nn.relu(_linear(x, w1, quant)))
    return q(h, quant) @ q(w2, quant)


def conv_silu(x, w, b):
    """``silu`` of the depthwise causal convolution of ``x`` (positions, channels)
    with ``w`` (channels, taps) plus the bias ``b``: ``sum_j w[:, j] x[t - (taps -
    1) + j] + b``."""
    taps = w.shape[1]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * xp[j:j + x.shape[0]] for j in range(taps)) + b)


def recurrence(x, dt, bm, cm, a, d):
    """Mamba-2's recurrence, one position after another.  ``x`` (positions, heads,
    P), ``dt`` (positions, heads), ``bm, cm`` (positions, heads, N): a head's own
    copy of its group's ``B_t``, ``C_t``; ``a`` (heads,) negative, ``d`` (heads,).
    The state of a head is (P, N) and starts at zero."""
    t, h, p = x.shape

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d[:, None] * x_t

    block = math.gcd(t, RECURRENCE_BLOCK)
    xs = [v.reshape((t // block, block) + v.shape[1:]) for v in (x, dt, bm, cm)]
    run = jax.checkpoint(lambda state, inp: lax.scan(step, state, inp))
    _, y = lax.scan(run, jnp.zeros((h, p, bm.shape[-1]), jnp.float32), xs)
    return y.reshape(t, h, p)


def gated_group_norm(y, z, gamma, eps):
    """``RMSNorm(y * silu(z)) * gamma`` over the last axis of (positions, groups,
    channels of a group): the gate before the statistics, a gain a channel."""
    return _rms(y * jax.nn.silu(z), gamma, eps)


def _mamba(u, p, cfg, quant):
    h, hp, g, n = _mamba_sizes(cfg)
    inner, s = h * hp, u.shape[0]
    zxbcdt = _linear(u, p["in_proj_weight"], quant)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=1)
    xbc = conv_silu(xbc, p["conv_weight"], p["conv_bias"])
    x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=1)
    x = x.reshape(s, h, hp)
    bm, cm = (jnp.repeat(m.reshape(s, g, n), h // g, axis=1) for m in (bm, cm))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(q(x, quant), dt, q(bm, quant), q(cm, quant),
                   -jnp.exp(p["a_log_bias"]), p["d_gamma"])
    y = gated_group_norm(y.reshape(s, g, inner // g), z.reshape(s, g, inner // g),
                         p["mixer_norm_gamma"], cfg["layer_norm_epsilon"])
    return _linear(y.reshape(s, inner), p["out_proj_weight"], quant)


def attention(qh, kh, vh, quant=None):
    """Causal softmax attention of (positions, heads, d) queries over (positions,
    key/value heads, d) keys and values, query head ``h`` reading key/value head
    ``h // (heads / key/value heads)``, scaled by ``d ** -0.5``."""
    s, h, dk = qh.shape
    kh, vh = (jnp.repeat(m, h // kh.shape[1], axis=1) for m in (kh, vh))
    blk = min(s, ATTENTION_ROWS)

    def rows(start):
        """Queries ``start .. start + blk`` against every key."""
        qb = lax.dynamic_slice_in_dim(qh, start, blk)
        sc = jnp.einsum("qhd,khd->hqk", q(qb, quant), q(kh, quant)) * dk ** -0.5
        seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(sc, axis=-1), quant), q(vh, quant))

    return lax.map(jax.checkpoint(rows), jnp.arange(0, s, blk)).reshape(s, h, dk)


def _attention(x, p, cfg, quant):
    s, hd = x.shape[0], cfg["head_dim"]
    heads = {n: _linear(x, p[n + "_weight"], quant).reshape(s, -1, hd) for n in "qkv"}
    att = attention(heads["q"], heads["k"], heads["v"], quant)
    return _linear(att.reshape(s, -1), p["o_weight"], quant)


def expert_layer(x, p, cfg, quant=None):
    """The held experts' part of the top-k layer's result for ``x`` (tokens, d),
    without the shared expert."""
    k, off = cfg["num_experts_per_tok"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(x @ p["moe_router_weight"].T)
    if not cfg.get("router_trained", True):
        s = lax.stop_gradient(s)
    _, idx = lax.top_k(s + p["moe_expert_bias"], k)
    gates = jnp.take_along_axis(s, idx, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    gates = gates * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(x)
    for j in range(cfg["n_routed_experts"]):
        gate = jnp.sum(jnp.where(idx == off + j, gates, 0.0), axis=1, keepdims=True)
        y = y + gate * relu2_mlp(x, p["moe_w1_weight"][j], p["moe_w2_weight"][j], quant)
    return y


def shared_expert(x, p, quant=None):
    return relu2_mlp(x, p["shared_w1_weight"], p["shared_w2_weight"].T, quant)


def _experts(x, p, cfg, quant):
    y = expert_layer(x, p, cfg, quant)
    return y + shared_expert(x, p, quant) if cfg.get("n_shared_experts", 0) else y


_MIXERS = {"M": _mamba, "E": _experts, "*": _attention}


def _layer(x, p, kind, cfg, quant):
    """One norm, one mixer, one residual."""
    return x + _MIXERS[kind](_rms(x, p["norm_gamma"], cfg["layer_norm_epsilon"]),
                             p, cfg, quant)


def _sequence_loss(params, tokens, labels, cfg, quant):
    x = params["embed_weight"][tokens]
    for i, kind in _layers(cfg):
        pre = "layer%d_" % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(lambda x, p, kd=kind: _layer(x, p, kd, cfg, quant))(x, p)
    x = _rms(x, params["final_norm_gamma"], cfg["layer_norm_epsilon"])
    return softmax_xent(_linear(x, params["lm_head_weight"], quant), labels)[0]


def loss(params, batch, cfg, quant=None):
    """(sum, mean) of the next-token cross-entropy of one (batch, seq) batch."""
    tokens = batch["data"].astype(jnp.int32)
    labels = batch["softmax_label"].astype(jnp.int32)
    one = jax.checkpoint(lambda t, l: _sequence_loss(params, t, l, cfg, quant))
    total = lax.scan(lambda acc, tl: (acc + one(*tl), None), jnp.float32(0), (tokens, labels))[0]
    return total, total / tokens.size
