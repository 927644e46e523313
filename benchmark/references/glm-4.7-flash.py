"""Plain reference of GLM-4.7-Flash (``zai-org/GLM-4.7-Flash`` ``config.json``,
``model_type`` ``glm4_moe_lite``; GLM-4.5, arXiv:2508.06471, whose layer and
multi-token-prediction module are DeepSeek-V3's, arXiv:2412.19437 sections 2.1
and 2.2): latent attention in every layer with a query latent and a rotary
part, one leading dense gated MLP, then top-4-of-64 expert layers with sigmoid
scores, a selection-only bias and one shared expert; an untied head; one
multi-token-prediction module that shares the embedding and the head, and the
loss ``L_main + mtp_loss_weight * L_mtp``.

It is given the same share of the deployment as the system
(``configs/glm-4.7-flash.json``): the experts ``expert_offset .. expert_offset +
n_routed_experts - 1`` of a router ``router_num_experts`` wide, and the sliced
vocabulary.  Every token is routed over the router's whole width and the gates
normalised over all its chosen experts; what the absent experts would have added
is left out.  There is no buffer here: every held assignment is computed.
Departures from the published model, the same as the system's graph: a selection
bias that training does not move, rotate-half rotary pairs (the checkpoint's
interleaved order is a fixed permutation of rows), the module fed the last
layer's output before the final norm with the embedding's half first, positions
from 0 and no cache, and, where the configuration says ``router_trained:
false``, scores that are constants to the gradient.

Straightforward ``jax.numpy`` in float32.  Attention's scores are made 1024 query
rows at a time; experts by a plain loop over the held experts with a mask, no
sort, no kernel.  Sequences do not interact, so the loss is summed one sequence
at a time and each layer is rematerialised in the backward pass.  ``q(...)``
marks every matmul operand but the router's (the fp8 control rounds them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from common import q, softmax_xent

EXPERT_BIAS_STD = 0.1
#: the selection biases are ``normal(PRNGKey(EXPERT_BIAS_DRAW) folded with the
#: layer's index)`` (the module's layer has index ``num_hidden_layers``, as in the
#: checkpoint), the same in every run (a model's bias is the model's: PERF.md 6,
#: PR 26).  Chosen by the held share of the routing under isotropic scores (logits
#: normal(0, 0.905), what init_std 0.02 gives at hidden 2048) with experts 0-7 of
#: 64 held and 4 a token, over the draws 0..511: ``configs/glm-4.7-flash.json``
#: (``assumed.expert_bias``) has the shares it gives.
EXPERT_BIAS_DRAW = 385
#: query rows whose float32 scores against every key are held at a time
ATTENTION_ROWS = 1024
MTP_LOSS_WEIGHT = 0.3


def _blocks(cfg):
    """``[(parameter prefix, is dense)]`` of the decoder layers built, the
    multi-token-prediction module's (``mtp_``) last where there is one."""
    out = [("layer%d_" % i, i < cfg["first_k_dense_replace"])
           for i in range(cfg["num_hidden_layers"])]
    if cfg.get("num_nextn_predict_layers", 0):
        out.append(("mtp_", False))
    return out


def param_shapes(cfg):
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, q_rank = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    e = cfg.get("router_num_experts", held)
    s = {"embed_weight": (v, d), "lm_head_weight": (v, d)}
    for p, dense in _blocks(cfg):
        s[p + "op_norm_gamma"] = (d,)
        s[p + "q_a_weight"], s[p + "q_norm_gamma"] = (q_rank, d), (q_rank,)
        s[p + "q_b_weight"] = (h * (nope + rope), q_rank)
        s[p + "kv_a_weight"], s[p + "kv_norm_gamma"] = (rank + rope, d), (rank,)
        s[p + "kv_b_weight"] = (h * (nope + dv), rank)
        s[p + "o_weight"] = (d, h * dv)
        s[p + "ffn_norm_gamma"] = (d,)
        if dense:
            s[p + "w1_weight"] = s[p + "w3_weight"] = (f, d)
            s[p + "w2_weight"] = (d, f)
        else:
            s[p + "moe_router_weight"], s[p + "moe_expert_bias"] = (e, d), (e,)
            s[p + "moe_w1_weight"] = s[p + "moe_w3_weight"] = (held, d, fe)
            s[p + "moe_w2_weight"] = (held, fe, d)
            fs = fe * cfg.get("n_shared_experts", 0)
            if fs:  # one MLP as wide as that many experts
                s[p + "shared_w1_weight"] = s[p + "shared_w3_weight"] = (fs, d)
                s[p + "shared_w2_weight"] = (d, fs)
    s["final_norm_gamma"] = (d,)
    if cfg.get("num_nextn_predict_layers", 0):
        s["mtp_enorm_gamma"] = s["mtp_hnorm_gamma"] = s["mtp_final_norm_gamma"] = (d,)
        s["mtp_eh_proj_weight"] = (d, 2 * d)
    return s


def init_params(cfg, key):
    """Normal(0, init_std) weights from ``key``, unit gains; selection biases
    normal(0, 0.1) from ``EXPERT_BIAS_DRAW`` and the layer's index, the same in
    every run."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    bias_key = jax.random.PRNGKey(EXPERT_BIAS_DRAW)
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_weight"):
            out[name] = jax.random.normal(k, shp, jnp.float32) * cfg.get("init_std", 0.02)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shp, jnp.float32)
        else:  # layer<i>_moe_expert_bias, mtp_moe_expert_bias
            layer = cfg["num_hidden_layers"] if name.startswith("mtp_") \
                else int(name[len("layer"):name.index("_")])
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


def rotary(x, base):
    """Rotary embedding over the whole last axis of ``x`` (positions, ..., dims),
    positions from 0: the pair ``(i, i + dims / 2)`` of position ``p`` is turned
    by the angle ``p * base ** (-2 i / dims)``."""
    dims = x.shape[-1]
    freq = base ** (-jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (dims // 2,))
    x1, x2 = x[..., :dims // 2], x[..., dims // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


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


def queries_keys_values(x, p, cfg, quant=None):
    """``(q, k, v)`` of one latent-attention layer for ``x`` (positions, d):
    (positions, heads, nope + rope) twice and (positions, heads, dv), the rotary
    part turned."""
    s, h = x.shape[0], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, eps, base = cfg["kv_lora_rank"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = _rms(_linear(x, p["q_a_weight"], quant), p["q_norm_gamma"], eps)
    qh = _linear(c_q, p["q_b_weight"], quant).reshape(s, h, nope + rope)
    qh = jnp.concatenate([qh[..., :nope], rotary(qh[..., nope:], base)], axis=-1)
    kva = _linear(x, p["kv_a_weight"], quant)
    kvb = _linear(_rms(kva[:, :rank], p["kv_norm_gamma"], eps), p["kv_b_weight"],
                  quant).reshape(s, h, nope + dv)
    k_rope = jnp.broadcast_to(rotary(kva[:, None, rank:], base), (s, h, rope))
    return qh, jnp.concatenate([kvb[..., :nope], k_rope], axis=-1), kvb[..., nope:]


def _mla(x, p, cfg, quant):
    qh, kh, vh = queries_keys_values(x, p, cfg, quant)
    att = attention(qh, kh, vh, quant)
    return _linear(att.reshape(x.shape[0], -1), p["o_weight"], quant)


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
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-6)
    gates = gates * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(x)
    for j in range(cfg["n_routed_experts"]):
        gate = jnp.sum(jnp.where(idx == off + j, gates, 0.0), axis=1, keepdims=True)
        y = y + gate * _gated(x, p["moe_w1_weight"][j], p["moe_w3_weight"][j],
                              p["moe_w2_weight"][j], quant)
    return y


def shared_expert(x, p, quant=None):
    return _gated(x, p["shared_w1_weight"].T, p["shared_w3_weight"].T,
                  p["shared_w2_weight"].T, quant)


def _layer(x, p, dense, cfg, quant):
    x = x + _mla(_rms(x, p["op_norm_gamma"], cfg["rms_norm_eps"]), p, cfg, quant)
    h = _rms(x, p["ffn_norm_gamma"], cfg["rms_norm_eps"])
    if dense:
        return x + _gated(h, p["w1_weight"].T, p["w3_weight"].T, p["w2_weight"].T, quant)
    y = expert_layer(h, p, cfg, quant)
    if cfg.get("n_shared_experts", 0):
        y = y + shared_expert(h, p, quant)
    return x + y


def _block(x, params, prefix, dense, cfg, quant):
    p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
    return jax.checkpoint(lambda x, p: _layer(x, p, dense, cfg, quant))(x, p)


def sequence_logits(params, tokens, labels, cfg, quant=None):
    """``(logits, logits')`` of one sequence, (T, vocab) each: the main head's,
    whose row ``i`` predicts ``labels[i]``, and the multi-token-prediction
    module's, whose row ``i`` predicts ``labels[i + 1]`` (None without one)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_weight"][tokens]
    for prefix, dense in _blocks(cfg)[:cfg["num_hidden_layers"]]:
        x = _block(x, params, prefix, dense, cfg, quant)
    main = _linear(_rms(x, params["final_norm_gamma"], eps), params["lm_head_weight"], quant)
    if not cfg.get("num_nextn_predict_layers", 0):
        return main, None
    both = jnp.concatenate(
        [_rms(params["embed_weight"][labels], params["mtp_enorm_gamma"], eps),
         _rms(x, params["mtp_hnorm_gamma"], eps)], axis=-1)
    z = _block(_linear(both, params["mtp_eh_proj_weight"], quant), params, "mtp_", False,
               cfg, quant)
    return main, _linear(_rms(z, params["mtp_final_norm_gamma"], eps),
                         params["lm_head_weight"], quant)


def sequence_losses(params, tokens, labels, cfg, quant=None):
    """``(L_main, L_mtp)`` of one sequence: the mean next-token cross-entropy over
    its T rows, and the mean cross-entropy of the module's rows ``0 .. T-2``
    against the token after the next (0 without a module)."""
    main, ahead = sequence_logits(params, tokens, labels, cfg, quant)
    l_main = softmax_xent(main, labels)[1]
    if ahead is None:
        return l_main, jnp.float32(0)
    return l_main, softmax_xent(ahead[:-1], labels[1:])[1]


def loss(params, batch, cfg, quant=None):
    """(sum, mean) over the batch's sequences of ``L_main + mtp_loss_weight *
    L_mtp``."""
    tokens = batch["data"].astype(jnp.int32)
    labels = batch["softmax_label"].astype(jnp.int32)
    weight = cfg.get("mtp_loss_weight", MTP_LOSS_WEIGHT)

    @jax.checkpoint
    def one(t, l):
        l_main, l_mtp = sequence_losses(params, t, l, cfg, quant)
        return l_main + weight * l_mtp

    total = lax.scan(lambda acc, tl: (acc + one(*tl), None), jnp.float32(0), (tokens, labels))[0]
    return total, total / tokens.shape[0]
