"""Plain reference of the OPT decoder (Zhang et al., "OPT: Open Pre-trained
Transformer Language Models", arXiv:2205.01068; ``facebook/opt-1.3b``
``config.json``): pre-LayerNorm blocks, learned positions, ReLU feed-forward,
causal softmax attention scaled by 1/sqrt(head size).

Departures from the published model, the same as the system's graph
(``configs/opt-1.3b.json`` ``departures``): an output head of its own (not tied
to the embedding), position offset 0, no dropout.

Straightforward ``jax.numpy`` in float32.  Sequences do not interact, so the
loss is summed one sequence at a time and each block is rematerialised in the
backward pass: the float32 logits and attention scores of one sequence, not of
the batch, are what has to fit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from common import q, softmax_xent

LN_EPS = 1e-5


def param_shapes(cfg):
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["ffn_dim"]
    s = {"tok_embed_weight": (v, d), "pos_embed_weight": (cfg["max_position_embeddings"], d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "block%d_" % i
        s[p + "ln1_gamma"] = s[p + "ln1_beta"] = (d,)
        s[p + "qkv_weight"], s[p + "qkv_bias"] = (3 * d, d), (3 * d,)
        s[p + "proj_weight"], s[p + "proj_bias"] = (d, d), (d,)
        s[p + "ln2_gamma"] = s[p + "ln2_beta"] = (d,)
        s[p + "ffn1_weight"], s[p + "ffn1_bias"] = (f, d), (f,)
        s[p + "ffn2_weight"], s[p + "ffn2_bias"] = (d, f), (d,)
    s["ln_f_gamma"] = s["ln_f_beta"] = (d,)
    s["lm_head_weight"], s["lm_head_bias"] = (v, d), (v,)
    return s


def init_params(cfg, key):
    """Normal(0, 0.02) weights (OPT's ``init_std``), unit gammas, zero rest."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_weight"):
            out[name] = jax.random.normal(k, shp, jnp.float32) * cfg.get("init_std", 0.02)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shp, jnp.float32)
        else:
            out[name] = jnp.zeros(shp, jnp.float32)
    return out


def _ln(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * gamma + beta


def _dense(x, w, b, quant):
    return q(x, quant) @ q(w, quant).T + b


def _block(x, p, heads, quant):
    s, d = x.shape
    h = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = _dense(h, p["qkv_weight"], p["qkv_bias"], quant).reshape(s, 3, heads, d // heads)
    qh, kh, vh = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    sc = jnp.einsum("qhd,khd->hqk", q(qh, quant), q(kh, quant)) / (d // heads) ** 0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(sc, axis=-1), quant), q(vh, quant))
    x = x + _dense(att.reshape(s, d), p["proj_weight"], p["proj_bias"], quant)
    h = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    h = jax.nn.relu(_dense(h, p["ffn1_weight"], p["ffn1_bias"], quant))
    return x + _dense(h, p["ffn2_weight"], p["ffn2_bias"], quant)


def _sequence_loss(params, tokens, labels, cfg, quant):
    s = tokens.shape[0]
    x = params["tok_embed_weight"][tokens] + params["pos_embed_weight"][:s]
    for i in range(cfg["num_hidden_layers"]):
        pre = "block%d_" % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(_block, static_argnums=(2, 3))(x, p, cfg["num_attention_heads"], quant)
    x = _ln(x, params["ln_f_gamma"], params["ln_f_beta"])
    logits = _dense(x, params["lm_head_weight"], params["lm_head_bias"], quant)
    return softmax_xent(logits, labels)[0]


def loss(params, batch, cfg, quant=None):
    """(sum, mean) of the next-token cross-entropy of one (batch, seq) batch."""
    tokens = batch["data"].astype(jnp.int32)
    labels = batch["softmax_label"].astype(jnp.int32)
    one = jax.checkpoint(lambda t, l: _sequence_loss(params, t, l, cfg, quant))
    total = lax.scan(lambda acc, tl: (acc + one(*tl), None), jnp.float32(0), (tokens, labels))[0]
    return total, total / tokens.size
