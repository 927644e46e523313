"""Plain reference of the Arcee Trinity decoder (``arcee-ai/Trinity-Mini``
``config.json``, ``model_type`` ``afmoe``; layer equations as ``transformers``'
``modeling_afmoe.py`` computes them): grouped-query attention under a sliding
window on three layers of four and over the whole prefix on the fourth, rotary
embedding on the sliding layers only, a sigmoid gate on the attention output,
four norms a layer, an embedding scaled by ``sqrt(hidden_size)``, leading dense
gated MLPs, then top-8-of-128 expert layers with sigmoid scores, a
selection-only bias and one shared expert; an untied head.

It is given the same share of the deployment as the system
(``configs/trinity-mini.json``): the experts ``expert_offset .. expert_offset +
num_experts - 1`` of a router ``router_num_experts`` wide, and the sliced
vocabulary.  Every token is routed over the router's whole width and the gates
normalised over all its chosen experts; what the absent experts would have
added is left out.  There is no buffer here: every held assignment is computed.
Departures from the published model, the same as the system's graph: a
selection bias that training does not move, positions from 0 and no cache, and,
where the configuration says ``router_trained: false``, scores that are
constants to the gradient.

Straightforward ``jax.numpy`` in float32: attention by ``softmax(mask(q k^T))``
1024 query rows at a time against every key, the mask the inequality ``0 <= t -
j < window`` as it is written (no kernel, no tile, key/value heads repeated);
experts by a plain loop over the held experts with a mask, no sort, no kernel.
Sequences do not interact, so the loss is summed one sequence at a time and each
layer is rematerialised in the backward pass.  ``q(...)`` marks every matmul
operand but the router's (the fp8 control rounds them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from common import q, softmax_xent

EXPERT_BIAS_STD = 0.1
#: the selection biases are ``normal(PRNGKey(EXPERT_BIAS_DRAW) folded with the
#: layer's index)``, the same in every run (a model's bias is the model's: PERF.md
#: 6, PR 26).  Chosen by the held share of the routing under isotropic scores
#: (logits normal(0, 0.905), what init_std 0.02 gives at hidden 2048) with experts
#: 0-7 of 128 held and 8 a token, over the draws 0..511: see ``assumed`` of
#: ``configs/trinity-mini.json`` for what each layer holds.
EXPERT_BIAS_DRAW = 251
#: query rows whose float32 scores against every key are held at a time
#: (32 heads x 1024 x 8192 x 4 B = 1 GB at the cell's size)
ATTENTION_ROWS = 1024


def _layers(cfg):
    """``[(index, is sliding, is dense)]`` of the layers built."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return [(i, kind == "sliding_attention", i < cfg["num_dense_layers"])
            for i, kind in enumerate(kinds)]


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, e = cfg["num_experts"], cfg.get("router_num_experts", cfg["num_experts"])
    s = {"embed_weight": (v, d)}
    for i, _sliding, dense in _layers(cfg):
        p = "layer%d_" % i
        s[p + "op_norm_gamma"] = (d,)
        s[p + "q_weight"], s[p + "q_norm_gamma"] = (hq * hd, d), (hd,)
        s[p + "k_weight"], s[p + "k_norm_gamma"] = (hk * hd, d), (hd,)
        s[p + "v_weight"], s[p + "g_weight"] = (hk * hd, d), (hq * hd, d)
        s[p + "o_weight"] = (d, hq * hd)
        s[p + "post_op_norm_gamma"] = s[p + "ffn_norm_gamma"] = (d,)
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
        s[p + "post_ffn_norm_gamma"] = (d,)
    s["final_norm_gamma"], s["lm_head_weight"] = (d,), (v, d)
    return s


def init_params(cfg, key):
    """Normal(0, init_std) weights from ``key``; unit gains, but
    ``qk_norm_gain_init`` (default 1) for the norms a head of ``q`` and ``k``
    (the configuration's ``assumed`` has why the cell says 2); selection biases
    normal(0, 0.1) from ``EXPERT_BIAS_DRAW`` and the layer's index, the same in
    every run."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    bias_key = jax.random.PRNGKey(EXPERT_BIAS_DRAW)
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_weight"):
            out[name] = jax.random.normal(k, shp, jnp.float32) * cfg.get("init_std", 0.02)
        elif name.endswith(("_q_norm_gamma", "_k_norm_gamma")):
            out[name] = jnp.full(shp, cfg.get("qk_norm_gain_init", 1.0), jnp.float32)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shp, jnp.float32)
        else:  # layer<i>_moe_expert_bias
            layer = int(name[len("layer"):name.index("_")])
            out[name] = jax.random.normal(jax.random.fold_in(bias_key, layer), shp,
                                          jnp.float32) * EXPERT_BIAS_STD
    return out


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _linear(x, w, quant):
    return q(x, quant) @ q(w, quant).T


def _gated(x, w1, w3, w2, quant):
    """``w2(silu(w1 x) * w3 x)`` with (in, out) matrices."""
    h = jax.nn.silu(q(x, quant) @ q(w1, quant)) * (q(x, quant) @ q(w3, quant))
    return q(h, quant) @ q(w2, quant)


def rope(x, theta):
    """Rotate-half rotary embedding over the whole head of ``x`` (positions,
    heads, head_dim), positions from 0."""
    s, _h, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def softmax_attention(qh, kh, vh, window, quant=None):
    """Softmax attention of (positions, heads, head_dim) queries over as many
    keys and values (the key/value heads repeated already), scaled by
    ``head_dim ** -0.5``: position ``t`` sees the keys ``j`` with ``0 <= t - j <
    window`` (``window`` None: ``0 <= t - j``)."""
    s, _h, hd = qh.shape
    blk = min(s, ATTENTION_ROWS)

    def rows(start):
        """Queries ``start .. start + blk`` against every key."""
        qb = lax.dynamic_slice_in_dim(qh, start, blk)
        sc = jnp.einsum("qhd,khd->hqk", q(qb, quant), q(kh, quant)) * hd ** -0.5
        back = (start + jnp.arange(blk))[:, None] - jnp.arange(s)[None, :]   # t - j
        seen = back >= 0 if window is None else (back >= 0) & (back < window)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(sc, axis=-1), quant), q(vh, quant))

    return lax.map(jax.checkpoint(rows), jnp.arange(0, s, blk)).reshape(s, -1, hd)


def attention_layer(x, p, sliding, cfg, quant=None):
    """One attention sub-layer on ``x`` (positions, hidden), its norms apart."""
    s = x.shape[0]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qh = _rms(_linear(x, p["q_weight"], quant).reshape(s, hq, hd), p["q_norm_gamma"], eps)
    kh = _rms(_linear(x, p["k_weight"], quant).reshape(s, hk, hd), p["k_norm_gamma"], eps)
    vh = _linear(x, p["v_weight"], quant).reshape(s, hk, hd)
    if sliding:     # a full layer has no positional encoding
        qh, kh = rope(qh, cfg["rope_theta"]), rope(kh, cfg["rope_theta"])
    kh, vh = jnp.repeat(kh, hq // hk, axis=1), jnp.repeat(vh, hq // hk, axis=1)
    att = softmax_attention(qh, kh, vh, cfg["sliding_window"] if sliding else None, quant)
    gate = jax.nn.sigmoid(_linear(x, p["g_weight"], quant))
    return _linear(att.reshape(s, hq * hd) * gate, p["o_weight"], quant)


def expert_layer(x, p, cfg, quant=None):
    """The held experts' part of the top-k layer's result for ``x`` (tokens, d),
    without the shared expert."""
    k, off = cfg["num_experts_per_tok"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(x @ p["moe_router_weight"].T)
    if not cfg.get("router_trained", True):
        s = lax.stop_gradient(s)
    _, idx = lax.top_k(s + p["moe_expert_bias"], k)
    gates = jnp.take_along_axis(s, idx, axis=1)
    if cfg["route_norm"]:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    gates = gates * cfg["route_scale"]
    y = jnp.zeros_like(x)
    for j in range(cfg["num_experts"]):
        gate = jnp.sum(jnp.where(idx == off + j, gates, 0.0), axis=1, keepdims=True)
        y = y + gate * _gated(x, p["moe_w1_weight"][j], p["moe_w3_weight"][j],
                              p["moe_w2_weight"][j], quant)
    return y


def shared_expert(x, p, quant=None):
    return _gated(x, p["shared_w1_weight"].T, p["shared_w3_weight"].T,
                  p["shared_w2_weight"].T, quant)


def _layer(x, p, sliding, dense, cfg, quant):
    eps = cfg["rms_norm_eps"]
    h = attention_layer(_rms(x, p["op_norm_gamma"], eps), p, sliding, cfg, quant)
    x = x + _rms(h, p["post_op_norm_gamma"], eps)
    h = _rms(x, p["ffn_norm_gamma"], eps)
    if dense:
        y = _gated(h, p["w1_weight"].T, p["w3_weight"].T, p["w2_weight"].T, quant)
    else:
        y = expert_layer(h, p, cfg, quant)
        if cfg.get("num_shared_experts", 0):
            y = y + shared_expert(h, p, quant)
    return x + _rms(y, p["post_ffn_norm_gamma"], eps)


def _sequence_loss(params, tokens, labels, cfg, quant):
    x = params["embed_weight"][tokens] * cfg["hidden_size"] ** 0.5
    for i, sliding, dense in _layers(cfg):
        pre = "layer%d_" % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(
            lambda x, p, a=sliding, dn=dense: _layer(x, p, a, dn, cfg, quant))(x, p)
    x = _rms(x, params["final_norm_gamma"], cfg["rms_norm_eps"])
    return softmax_xent(_linear(x, params["lm_head_weight"], quant), labels)[0]


def loss(params, batch, cfg, quant=None):
    """(sum, mean) of the next-token cross-entropy of one (batch, seq) batch."""
    tokens = batch["data"].astype(jnp.int32)
    labels = batch["softmax_label"].astype(jnp.int32)
    one = jax.checkpoint(lambda t, l: _sequence_loss(params, t, l, cfg, quant))
    total = lax.scan(lambda acc, tl: (acc + one(*tl), None), jnp.float32(0), (tokens, labels))[0]
    return total, total / tokens.size
