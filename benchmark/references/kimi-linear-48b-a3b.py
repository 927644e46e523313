"""Plain reference of the Kimi Linear decoder
(``moonshotai/Kimi-Linear-48B-A3B-Instruct`` ``config.json``, ``model_type``
``kimi_linear``; arXiv:2510.26692; layer equations as its ``modeling_kimi.py``
and ``fla.layers.kda`` compute them): Kimi Delta Attention layers (a gated
delta rule with a per-channel decay), latent-attention layers without
positional encoding, one leading dense gated MLP, then top-8-of-256 expert
layers with sigmoid scores, a selection-only bias and one shared expert; an
untied head.

It is given the same share of the deployment as the system
(``configs/kimi-linear-48b-a3b.json``): the experts ``expert_offset ..
expert_offset + num_experts - 1`` of a router ``router_num_experts`` wide, and
the sliced vocabulary.  Every token is routed over the router's whole width and
the gates normalised over all its chosen experts; what the absent experts would
have added is left out.  There is no buffer here: every held assignment is
computed.  Departures from the published model, the same as the system's graph:
a selection bias that training does not move, positions from 0 and no cache,
and, where the configuration says ``router_trained: false``, scores that are
constants to the gradient.

Straightforward ``jax.numpy`` in float32.  The delta rule runs TOKEN BY TOKEN,
the recurrence as it is written (``lax.scan`` over positions; ``jax.checkpoint``
over blocks of positions so that its gradient fits: blocking, no chunk
algebra); attention's scores are made 1024 query rows at a time; experts by a
plain loop over the held experts with a mask, no sort, no kernel.  Sequences do
not interact, so the loss is summed one sequence at a time and each layer is
rematerialised in the backward pass.  ``q(...)`` marks every matmul operand but
the router's, the recurrence's ``q, k, v`` among them (the fp8 control rounds
them).
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
#: 6, PR 26).  Chosen by the held share of the routing under isotropic scores
#: (logits normal(0, 0.96), what init_std 0.02 gives at hidden 2304) with experts
#: 0-7 of 256 held and 8 a token, over the draws 0..511: 456 keeps the four expert
#: layers of the five-layer cut closest to the even 3.125% (2.97 / 3.45 / 3.11 /
#: 2.92% held; largest held expert 4.1 times the even load of one expert).
EXPERT_BIAS_DRAW = 456
#: query rows whose float32 scores against every key are held at a time
ATTENTION_ROWS = 1024
#: positions of the recurrence between two kept states
RECURRENCE_BLOCK = 64
L2_EPS = 1e-6


def _layers(cfg):
    """``[(index from 0, is KDA, is dense)]`` of the layers built."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return [(i, i + 1 in kda, i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    ha = cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, e = cfg["num_experts"], cfg.get("router_num_experts", cfg["num_experts"])
    s = {"embed_weight": (v, d)}
    for i, kda, dense in _layers(cfg):
        p = "layer%d_" % i
        s[p + "op_norm_gamma"] = (d,)
        if kda:
            for n in "qkv":
                s[p + n + "_weight"] = (h * hd, d)
                s[p + n + "_conv_weight"] = (h * hd, lin["short_conv_kernel_size"])
            s[p + "f_down_weight"], s[p + "f_up_weight"] = (hd, d), (h * hd, hd)
            s[p + "a_log_bias"], s[p + "dt_bias"] = (h,), (h * hd,)
            s[p + "b_weight"] = (h, d)
            s[p + "g_down_weight"], s[p + "g_up_weight"] = (hd, d), (h * hd, hd)
            s[p + "o_norm_gamma"], s[p + "o_weight"] = (hd,), (d, h * hd)
        else:
            s[p + "q_weight"] = (ha * (nope + rope), d)
            s[p + "kv_a_weight"], s[p + "kv_norm_gamma"] = (rank + rope, d), (rank,)
            s[p + "kv_b_weight"] = (ha * (nope + dv), rank)
            s[p + "o_weight"] = (d, ha * dv)
        s[p + "ffn_norm_gamma"] = (d,)
        if dense:
            s[p + "w1_weight"] = s[p + "w3_weight"] = (f, d)
            s[p + "w2_weight"] = (d, f)
        else:
            s[p + "moe_router_weight"], s[p + "moe_expert_bias"] = (e, d), (e,)
            s[p + "moe_w1_weight"] = s[p + "moe_w3_weight"] = (held, d, fe)
            s[p + "moe_w2_weight"] = (held, fe, d)
            fs = fe * cfg.get("num_shared_experts", 0)
            if fs:  # one MLP as wide as that many experts
                s[p + "shared_w1_weight"] = s[p + "shared_w3_weight"] = (fs, d)
                s[p + "shared_w2_weight"] = (d, fs)
    s["final_norm_gamma"], s["lm_head_weight"] = (d,), (v, d)
    return s


def init_params(cfg, key):
    """Normal(0, init_std) weights from ``key``, unit gains; ``A_log =
    log(uniform(1, 16))`` a head and ``dt_bias`` the inverse softplus of
    ``exp(uniform(log 0.001, log 0.1))`` from ``key`` (``fla.layers.kda``'s own
    initialisation); selection biases normal(0, 0.1) from ``EXPERT_BIAS_DRAW``
    and the layer's index, the same in every run."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    bias_key = jax.random.PRNGKey(EXPERT_BIAS_DRAW)
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_weight"):
            out[name] = jax.random.normal(k, shp, jnp.float32) * cfg.get("init_std", 0.02)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shp, jnp.float32)
        elif name.endswith("_a_log_bias"):
            out[name] = jnp.log(jax.random.uniform(k, shp, jnp.float32, 1.0, 16.0))
        elif name.endswith("_dt_bias"):
            dt = jnp.exp(jax.random.uniform(k, shp, jnp.float32, math.log(0.001), math.log(0.1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:  # layer<i>_moe_expert_bias
            layer = int(name[len("layer"):name.index("_")])
            out[name] = jax.random.normal(jax.random.fold_in(bias_key, layer), shp,
                                          jnp.float32) * EXPERT_BIAS_STD
    return out


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _linear(x, w, quant):
    return q(x, quant) @ q(w, quant).T


def _gated(x, w1, w3, w2, quant):
    """``w2(silu(w1 x) * w3 x)`` with (in, out) matrices."""
    h = jax.nn.silu(q(x, quant) @ q(w1, quant)) * (q(x, quant) @ q(w3, quant))
    return q(h, quant) @ q(w2, quant)


def conv_silu(x, w):
    """``silu`` of the depthwise causal convolution of ``x`` (positions, channels)
    with ``w`` (channels, taps): ``sum_j w[:, j] x[t - (taps - 1) + j]``."""
    taps = w.shape[1]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * xp[j:j + x.shape[0]] for j in range(taps)))


def log_decay(f, a_log, dt_bias, heads):
    """``-exp(A_log) * softplus(f + dt_bias)`` as (positions, heads, channels)."""
    soft = jax.nn.softplus(f + dt_bias).reshape(f.shape[0], heads, -1)
    return -jnp.exp(a_log)[:, None] * soft


def delta_rule(qh, kh, vh, g, beta):
    """The gated delta rule, one position after another.  ``qh, kh, g``
    (positions, heads, dk), ``vh`` (positions, heads, dv), ``beta`` (positions,
    heads); the state of a head is (dk, dv) and starts at zero."""
    t, h, dk = qh.shape
    dv = vh.shape[-1]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", state, k_t))
        state = state + k_t[..., None] * u[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t)

    block = math.gcd(t, RECURRENCE_BLOCK)
    xs = [a.reshape((t // block, block) + a.shape[1:]) for a in (qh, kh, vh, g, beta)]
    run = jax.checkpoint(lambda state, x: lax.scan(step, state, x))
    _, o = lax.scan(run, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(t, h, dv)


def gated_norm(o, gate, gamma, eps):
    """``RMSNorm(o) * sigmoid(gate)`` over the last axis."""
    return _rms(o, gamma, eps) * jax.nn.sigmoid(gate)


def _kda(x, p, cfg, quant):
    lin = cfg["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    s = x.shape[0]

    def mixed(n):
        return conv_silu(_linear(x, p[n + "_weight"], quant),
                         p[n + "_conv_weight"]).reshape(s, h, hd)

    qh, kh, vh = _l2(mixed("q")) * hd ** -0.5, _l2(mixed("k")), mixed("v")
    g = log_decay(_linear(_linear(x, p["f_down_weight"], quant), p["f_up_weight"], quant),
                  p["a_log_bias"], p["dt_bias"], h)
    beta = jax.nn.sigmoid(_linear(x, p["b_weight"], quant))
    o = delta_rule(q(qh, quant), q(kh, quant), q(vh, quant), g, beta)
    gate = _linear(_linear(x, p["g_down_weight"], quant), p["g_up_weight"], quant)
    o = gated_norm(o, gate.reshape(s, h, hd), p["o_norm_gamma"], cfg["rms_norm_eps"])
    return _linear(o.reshape(s, h * hd), p["o_weight"], quant)


def attention(qh, kh, vh, quant=None):
    """Causal softmax attention of (positions, heads, dk) queries and keys over
    (positions, heads, dv) values, scaled by ``dk ** -0.5``."""
    s, _h, dk = qh.shape
    blk = min(s, ATTENTION_ROWS)

    def rows(start):
        """Queries ``start .. start + blk`` against every key."""
        qb = lax.dynamic_slice_in_dim(qh, start, blk)
        sc = jnp.einsum("qhd,khd->hqk", q(qb, quant), q(kh, quant)) * dk ** -0.5
        seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(sc, axis=-1), quant), q(vh, quant))

    return lax.map(jax.checkpoint(rows), jnp.arange(0, s, blk)).reshape(s, -1, vh.shape[-1])


def _mla(x, p, cfg, quant):
    s = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    qh = _linear(x, p["q_weight"], quant).reshape(s, h, nope + rope)
    kva = _linear(x, p["kv_a_weight"], quant)
    latent = _rms(kva[:, :rank], p["kv_norm_gamma"], cfg["rms_norm_eps"])
    kvb = _linear(latent, p["kv_b_weight"], quant).reshape(s, h, nope + dv)
    k_rope = jnp.broadcast_to(kva[:, None, rank:], (s, h, rope))
    kh = jnp.concatenate([kvb[..., :nope], k_rope], axis=-1)
    att = attention(qh, kh, kvb[..., nope:], quant)
    return _linear(att.reshape(s, h * dv), p["o_weight"], quant)


def expert_layer(x, p, cfg, quant=None):
    """The held experts' part of the top-k layer's result for ``x`` (tokens, d),
    without the shared expert."""
    k, off = cfg["num_experts_per_token"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(x @ p["moe_router_weight"].T)
    if not cfg.get("router_trained", True):
        s = lax.stop_gradient(s)
    _, idx = lax.top_k(s + p["moe_expert_bias"], k)
    gates = jnp.take_along_axis(s, idx, axis=1)
    if cfg["moe_renormalize"]:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-6)
    gates = gates * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(x)
    for j in range(cfg["num_experts"]):
        gate = jnp.sum(jnp.where(idx == off + j, gates, 0.0), axis=1, keepdims=True)
        y = y + gate * _gated(x, p["moe_w1_weight"][j], p["moe_w3_weight"][j],
                              p["moe_w2_weight"][j], quant)
    return y


def shared_expert(x, p, quant=None):
    return _gated(x, p["shared_w1_weight"].T, p["shared_w3_weight"].T,
                  p["shared_w2_weight"].T, quant)


def _layer(x, p, kda, dense, cfg, quant):
    h = _rms(x, p["op_norm_gamma"], cfg["rms_norm_eps"])
    x = x + (_kda(h, p, cfg, quant) if kda else _mla(h, p, cfg, quant))
    h = _rms(x, p["ffn_norm_gamma"], cfg["rms_norm_eps"])
    if dense:
        return x + _gated(h, p["w1_weight"].T, p["w3_weight"].T, p["w2_weight"].T, quant)
    y = expert_layer(h, p, cfg, quant)
    if cfg.get("num_shared_experts", 0):
        y = y + shared_expert(h, p, quant)
    return x + y


def _sequence_loss(params, tokens, labels, cfg, quant):
    x = params["embed_weight"][tokens]
    for i, kda, dense in _layers(cfg):
        pre = "layer%d_" % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(
            lambda x, p, a=kda, dn=dense: _layer(x, p, a, dn, cfg, quant))(x, p)
    x = _rms(x, params["final_norm_gamma"], cfg["rms_norm_eps"])
    return softmax_xent(_linear(x, params["lm_head_weight"], quant), labels)[0]


def loss(params, batch, cfg, quant=None):
    """(sum, mean) of the next-token cross-entropy of one (batch, seq) batch."""
    tokens = batch["data"].astype(jnp.int32)
    labels = batch["softmax_label"].astype(jnp.int32)
    one = jax.checkpoint(lambda t, l: _sequence_loss(params, t, l, cfg, quant))
    total = lax.scan(lambda acc, tl: (acc + one(*tl), None), jnp.float32(0), (tokens, labels))[0]
    return total, total / tokens.size
