"""Plain reference of the SDAR expert decoder trained by block diffusion
(``JetLM/SDAR-30B-A3B-Chat`` ``config.json``, ``model_type`` ``sdar_moe``; the
layer is Qwen3-MoE's as ``transformers``' ``modeling_qwen3_moe.py`` computes it;
the training form is BD3-LM's, arXiv:2503.09573, as SDAR, arXiv:2510.06303,
uses it): grouped-query attention with an RMSNorm a head on ``q`` and ``k`` and
rotary embedding, top-8-of-128 expert layers with softmax scores renormalised
over the chosen and no shared expert, an untied head; one pass over a clean and
a noised copy of each document under the three-part mask, a ``1 / t``-weighted
cross-entropy over the masked positions.

The batch's ``data`` is ``(documents, 2 L + L / B)`` ids: the document ``x_0``,
then the mask draws ``u_i = (id + 0.5) / vocab_size``, then the level draws
``s_b = (id + 0.5) / vocab_size`` of the ``L / B`` blocks.  ``t_b = noise_eps +
(1 - noise_eps) s_b``; position ``i`` is masked where ``u_i < t_{i // B}``; the
noised copy holds ``mask_token_id`` there.  Rows ``0 .. L-1`` are the clean copy
``c``, rows ``L .. 2L-1`` the noised copy ``n``, both at positions ``0 .. L-1``.
Which keys a row sees, written as the three sentences say it:

* ``c_i`` sees ``c_j`` iff ``j // B <= i // B``;
* ``n_i`` sees ``n_j`` iff ``j // B == i // B``, and ``c_j`` iff ``j // B < i // B``;
* no clean row sees a noised row.

Only the noised rows go through the final norm and the head; the logits at
``n_i`` predict ``x_0,i`` (no shift); the loss of a document is ``(1 / L) sum_i
(m_i / t_{i // B}) * -log softmax(logits(n_i))[x_0,i]``.

It is given the same share of the deployment as the system
(``configs/sdar-30b-a3b-chat.json``): the experts ``expert_offset ..
expert_offset + num_experts - 1`` of a router ``router_num_experts`` wide, and
the sliced vocabulary.  Every row is routed over the router's whole width and the
gates normalised over all its chosen experts; what the absent experts would have
added is left out.  There is no buffer here: every held assignment is computed.
Where the configuration says ``router_trained: false`` the scores are constants
to the gradient, as in the system's graph.

Straightforward ``jax.numpy`` in float32: attention by ``softmax(where(sees, q
k^T, -inf))`` 1024 query rows at a time against all ``2L`` keys (no kernel, no
tile, key/value heads repeated), rematerialised; experts by a plain loop over the
held experts with a mask, no sort, no kernel.  Documents do not interact, so the
loss is summed one document at a time and each layer is rematerialised in the
backward pass.  ``q(...)`` marks every matmul operand but the router's (the fp8
control rounds them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from common import q

#: query rows whose float32 scores against every key are held at a time
#: (32 heads x 1024 x 8192 x 4 B = 1 GB at the cell's size)
ATTENTION_ROWS = 1024


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    fe = cfg["moe_intermediate_size"]
    held, e = cfg["num_experts"], cfg.get("router_num_experts", cfg["num_experts"])
    s = {"embed_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        s[p + "op_norm_gamma"] = (d,)
        s[p + "q_weight"], s[p + "q_norm_gamma"] = (hq * hd, d), (hd,)
        s[p + "k_weight"], s[p + "k_norm_gamma"] = (hk * hd, d), (hd,)
        s[p + "v_weight"], s[p + "o_weight"] = (hk * hd, d), (d, hq * hd)
        s[p + "ffn_norm_gamma"] = (d,)
        s[p + "moe_router_weight"] = (e, d)
        s[p + "moe_w1_weight"] = s[p + "moe_w3_weight"] = (held, d, fe)
        s[p + "moe_w2_weight"] = (held, fe, d)
    s["final_norm_gamma"], s["lm_head_weight"] = (d,), (v, d)
    return s


def init_params(cfg, key):
    """Normal(0, ``initializer_range``) weights from ``key``; unit gains, but
    ``qk_norm_gain_init`` (default 1) for the norms a head of ``q`` and ``k``."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shp) in zip(keys, shapes.items()):
        if name.endswith("_weight"):
            out[name] = jax.random.normal(k, shp, jnp.float32) \
                * cfg.get("initializer_range", 0.02)
        elif name.endswith(("_q_norm_gamma", "_k_norm_gamma")):
            out[name] = jnp.full(shp, cfg.get("qk_norm_gain_init", 1.0), jnp.float32)
        else:
            out[name] = jnp.ones(shp, jnp.float32)
    return out


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _linear(x, w, quant):
    return q(x, quant) @ q(w, quant).T


def _gated(x, w1, w3, w2, quant):
    """``w2(silu(w1 x) * w3 x)`` with (in, out) matrices."""
    h = jax.nn.silu(q(x, quant) @ q(w1, quant)) * (q(x, quant) @ q(w3, quant))
    return q(h, quant) @ q(w2, quant)


def noise(data, cfg):
    """``(x_0, x_t, m, t)`` of one document's row of the batch: the document,
    its noised copy, which positions are masked and each position's level."""
    block = cfg["block_length"]
    n = data.shape[0] * block // (2 * block + 1)          # L of 2 L + L / B
    vocab, eps = float(cfg["vocab_size"]), float(cfg["noise_eps"])
    x0 = data[:n]
    u = (data[n:2 * n] + 0.5) / vocab
    s = (data[2 * n:] + 0.5) / vocab
    t = jnp.repeat(s * (1.0 - eps) + eps, block)
    m = u < t
    xt = jnp.where(m, float(cfg["mask_token_id"]), x0)
    return x0.astype(jnp.int32), xt.astype(jnp.int32), m, t


def rope(x, pos, theta):
    """Rotate-half rotary embedding over the whole head of ``x`` (rows, heads,
    head_dim) at the positions ``pos`` (rows,)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def sees(kind_q, i, kind_k, j, block):
    """Row ``(kind_q, i)`` sees key ``(kind_k, j)``; a kind is 0 for the clean
    copy and 1 for the noised one, ``i`` and ``j`` positions."""
    bi, bj = i // block, j // block
    clean_q, clean_k = kind_q == 0, kind_k == 0
    return (clean_q & clean_k & (bj <= bi)) \
        | (~clean_q & ~clean_k & (bj == bi)) \
        | (~clean_q & clean_k & (bj < bi))


def masked_attention(qh, kh, vh, block, quant=None):
    """Softmax attention of ``2L`` rows (rows, heads, head_dim), clean copy
    first, over as many keys and values (the key/value heads repeated already),
    scaled by ``head_dim ** -0.5``, each row over the keys it ``sees``."""
    rows, _h, hd = qh.shape
    half = rows // 2
    blk = min(rows, ATTENTION_ROWS)
    key = jnp.arange(rows)

    def part(start):
        """Queries ``start .. start + blk`` against every key."""
        qb = lax.dynamic_slice_in_dim(qh, start, blk)
        sc = jnp.einsum("qhd,khd->hqk", q(qb, quant), q(kh, quant)) * hd ** -0.5
        row = (start + jnp.arange(blk))[:, None]
        seen = sees(row // half, row % half, key[None, :] // half,
                    key[None, :] % half, block)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(sc, axis=-1), quant),
                          q(vh, quant))

    return lax.map(jax.checkpoint(part), jnp.arange(0, rows, blk)).reshape(rows, -1, hd)


def attention_layer(x, p, cfg, quant=None):
    """One attention sub-layer on ``x`` (2L rows, hidden), its norm apart."""
    rows = x.shape[0]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(rows) % (rows // 2)
    qh = _rms(_linear(x, p["q_weight"], quant).reshape(rows, hq, hd), p["q_norm_gamma"], eps)
    kh = _rms(_linear(x, p["k_weight"], quant).reshape(rows, hk, hd), p["k_norm_gamma"], eps)
    vh = _linear(x, p["v_weight"], quant).reshape(rows, hk, hd)
    qh, kh = rope(qh, pos, cfg["rope_theta"]), rope(kh, pos, cfg["rope_theta"])
    kh, vh = jnp.repeat(kh, hq // hk, axis=1), jnp.repeat(vh, hq // hk, axis=1)
    att = masked_attention(qh, kh, vh, cfg["block_length"], quant)
    return _linear(att.reshape(rows, hq * hd), p["o_weight"], quant)


def expert_layer(x, p, cfg, quant=None):
    """The held experts' part of the top-k layer's result for ``x`` (rows, d)."""
    k, off = cfg["num_experts_per_tok"], cfg.get("expert_offset", 0)
    s = jax.nn.softmax(x @ p["moe_router_weight"].T, axis=-1)
    if not cfg.get("router_trained", True):
        s = lax.stop_gradient(s)
    gates, idx = lax.top_k(s, k)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(cfg["num_experts"]):
        gate = jnp.sum(jnp.where(idx == off + j, gates, 0.0), axis=1, keepdims=True)
        y = y + gate * _gated(x, p["moe_w1_weight"][j], p["moe_w3_weight"][j],
                              p["moe_w2_weight"][j], quant)
    return y


def _layer(x, p, cfg, quant):
    eps = cfg["rms_norm_eps"]
    x = x + attention_layer(_rms(x, p["op_norm_gamma"], eps), p, cfg, quant)
    return x + expert_layer(_rms(x, p["ffn_norm_gamma"], eps), p, cfg, quant)


def logits(params, x0, xt, cfg, quant=None):
    """The noised rows' logits ``(L, vocab)`` of one document."""
    x = params["embed_weight"][jnp.concatenate([x0, xt])]
    for i in range(cfg["num_hidden_layers"]):
        pre = "layer%d_" % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(lambda x, p: _layer(x, p, cfg, quant))(x, p)
    x = _rms(x[x0.shape[0]:], params["final_norm_gamma"], cfg["rms_norm_eps"])
    return _linear(x, params["lm_head_weight"], quant)


def _document_loss(params, data, cfg, quant):
    x0, xt, m, t = noise(data, cfg)
    logp = jax.nn.log_softmax(logits(params, x0, xt, cfg, quant), axis=-1)
    nll = -jnp.take_along_axis(logp, x0[:, None], axis=1)[:, 0]
    return jnp.sum(jnp.where(m, nll / t, 0.0)) / x0.shape[0]


def loss(params, batch, cfg, quant=None):
    """(sum over the documents, mean) of the weighted loss of one batch; the
    gradient the comparison follows is of the sum over the batch's rows, that is
    of the mean."""
    data = batch["data"].astype(jnp.float32)
    one = jax.checkpoint(lambda row: _document_loss(params, row, cfg, quant))
    total = lax.scan(lambda acc, row: (acc + one(row), None), jnp.float32(0), data)[0]
    return total, total / data.shape[0]
