"""LFM2-MoE decoder (``model_type`` ``lfm2_moe``) built from its
configuration's own keys.

Source: ``https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json``
and the layer equations of ``transformers``' ``lfm2_moe``.  ``x`` is
``(batch, seq, hidden_size)``; every linear map is without bias; RMSNorm is
``x * rsqrt(mean(x^2) + norm_eps) * gamma``.

* Layer ``i``: ``h = x + Op_i(RMSNorm(x))``; ``y = h + FF_i(RMSNorm(h))``.
  ``Op_i`` is attention where ``layer_types[i] == "full_attention"``, else
  the gated short convolution; ``FF_i`` is the dense MLP for
  ``i < num_dense_layers``, else the expert layer.  One more RMSNorm after
  the last layer, then the head.
* Gated short convolution (``conv_L_cache`` taps, ``conv_bias`` false):
  ``[B, C, u] = split3(Linear(x))``; ``out = Linear(C * conv(B * u))`` with
  ``conv`` depthwise and causal (``_contrib_CausalConv1D``).
* Attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, an RMSNorm of its own over each
  head of ``q`` and of ``k``, rotary embedding over the whole head
  (rotate-half, ``rope_theta``), causal softmax scaled by
  ``head_dim ** -0.5`` (``_contrib_FlashAttention``, grouped queries).
* Dense MLP (``intermediate_size``) and each expert
  (``moe_intermediate_size``): ``w2(silu(w1 x) * w3 x)``.
* Expert layer (``_contrib_TopKMoE``): sigmoid scores in float32, the
  ``num_experts_per_tok`` largest of score + ``expert_bias`` chosen, gates
  the scores at those divided by their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; no capacity, no dropped token, no shared
  expert, no auxiliary loss.

Keys beside the published ones say which share of a deployment this
process holds (``model-configs`` section 4): ``num_experts`` is the experts
HELD here, ``router_num_experts`` the router's published width (default:
all held), ``expert_offset`` the first held expert and ``router_trained``
whether this share moves its routers (below); a sliced ``vocab_size`` is
simply a smaller vocabulary.  ``layer_types`` may be the
published list: the first ``num_hidden_layers`` entries are used.

Departures from the published model, all of them:

* the output head is tied to the token embedding (one variable,
  ``embed_weight``, feeds ``Embedding`` and the head's ``FullyConnected``):
  the family's ``tie_embedding``, which the catalog's ``config`` does not
  state;
* ``expert_bias`` is a parameter that no gradient reaches (it enters the
  selection only), so training leaves it where the initialiser put it; the
  published model moves it by a load-balancing rule outside the loss;
* ``router_trained`` (default true; a key beside the published ones): with
  ``false`` every expert layer is built with ``router_trained=False`` and
  treats its scores as constants to the gradient: the routers stay as
  initialised and nothing reaches the hidden state through the gates.  For
  a graph that is one share of a deployment and runs without the others:
  its part of the router's gradient alone is biased towards the experts
  held here (``parallel.moe.topk_moe``), and the exchange that would bring
  the rest does not exist on one chip.  Freezing the router's weights
  alone is not enough: through the gates the layers below learn the same
  preference (measured on the chip, ``PERF.md`` 6);
* positions start at 0 and there is no cache: this graph trains, it does
  not decode;
* an expert's weights are stored ``(experts, in, out)``, where the
  published checkpoints hold one ``(out, in)`` matrix an expert.
"""
from __future__ import annotations

from .. import symbol as sym
from ..base import MXNetError
from ..telemetry.spans import span
from .decoder_blocks import gated_mlp as _gated_mlp, \
    grouped_query_attention, linear as _linear, topk_experts

CONV, ATTENTION = "conv", "full_attention"


def _short_conv(x, cfg, prefix):
    d, taps = cfg["hidden_size"], int(cfg["conv_L_cache"])
    if cfg.get("conv_bias"):
        raise MXNetError("lfm2_moe: conv_bias=true is not built")
    bcu = _linear(x, 3 * d, prefix + "conv_in")
    b_, c_, u_ = (sym.slice_axis(bcu, axis=2, begin=i * d, end=(i + 1) * d)
                  for i in range(3))
    conv = sym._contrib_CausalConv1D(b_ * u_, kernel=taps,
                                     name=prefix + "conv")
    return _linear(c_ * conv, d, prefix + "conv_out")


def _attention(x, cfg, prefix):
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return grouped_query_attention(
        x, prefix, d, hq, cfg["num_key_value_heads"],
        cfg.get("head_dim") or d // hq, float(cfg["norm_eps"]),
        rope_theta=cfg["rope_theta"])


def _experts(x, cfg, prefix):
    return topk_experts(x, cfg, prefix + "moe", cfg["num_experts_per_tok"],
                        cfg["norm_topk_prob"], cfg["use_expert_bias"])


def get_symbol(cfg, seq_len):
    """A ``SoftmaxOutput``-headed Symbol of the model ``cfg`` describes
    (the keys of the published ``config.json``, see the module's
    docstring), over ``data`` and ``softmax_label`` of ``(batch, seq_len)``
    token ids; ``ShardedTrainer`` and ``Module`` take it as it is."""
    with span("model.build", category="model", model="lfm2_moe"):
        return _build(cfg, int(seq_len))


def _build(cfg, seq_len):
    d, vocab, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])[:n]
    if len(kinds) != n or set(kinds) - {CONV, ATTENTION}:
        raise MXNetError(
            "lfm2_moe: layer_types %r does not give %d layers of %r or %r"
            % (cfg["layer_types"], n, CONV, ATTENTION))
    if seq_len > cfg["max_position_embeddings"]:
        raise MXNetError("lfm2_moe: %d positions, the model declares %d"
                         % (seq_len, cfg["max_position_embeddings"]))
    eps = float(cfg["norm_eps"])
    embed = sym.Variable("embed_weight")
    x = sym.Embedding(sym.Variable("data"), weight=embed, input_dim=vocab,
                      output_dim=d, name="embed")
    for i, kind in enumerate(kinds):
        p = "layer%d_" % i
        h = sym.RMSNorm(x, eps=eps, name=p + "op_norm")
        x = x + (_attention(h, cfg, p) if kind == ATTENTION
                 else _short_conv(h, cfg, p))
        h = sym.RMSNorm(x, eps=eps, name=p + "ffn_norm")
        x = x + (_gated_mlp(h, cfg["intermediate_size"], d, p)
                 if i < cfg["num_dense_layers"] else _experts(h, cfg, p))
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = _linear(sym.Reshape(x, shape=(-1, d)), vocab, "lm_head",
                     weight=embed)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return sym.SoftmaxOutput(logits, label=label, name="softmax")
