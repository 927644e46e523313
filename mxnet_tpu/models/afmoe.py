"""Arcee Trinity decoder (``model_type`` ``afmoe``) built from its
configuration's own keys.

Source: ``https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json``
(Arcee Trinity Mini 26B-A3B, 2025-12) and the layer equations of
``transformers``' ``modeling_afmoe.py``.  The configuration gives the
sizes, the layer pattern, the window and the routing's keys; what it does
not give is the modelling code's as this file's author knows it, each such
point marked **(code)**.  ``x`` is ``(batch, seq, hidden_size)``; every
linear map is without bias; RMSNorm is ``x * rsqrt(mean(x^2) +
rms_norm_eps) * gamma``.

* Input: ``h_0 = Embedding(ids) * sqrt(hidden_size)`` (``mup_enabled``
  true **(code)**: the scale is ``hidden_size ** 0.5``).
* Layer ``i`` (0-based, as ``layer_types`` counts), four norms **(code)**:
  ``a = h + RMSNorm_post_attn(Attn_i(RMSNorm_in(h)))``; ``h' = a +
  RMSNorm_post_mlp(FF_i(RMSNorm_pre_mlp(a)))``.  ``FF_i`` is the dense MLP
  (``intermediate_size``) for ``i < num_dense_layers``, else the expert
  layer.  One more RMSNorm after the last layer, then an untied head
  (``tie_word_embeddings`` false).
* Attention (``decoder_blocks.grouped_query_attention``):
  ``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim``; ``g = W_g x`` as wide as the query
  heads together **(code)**; an RMSNorm of its own over each head of ``q``
  and of ``k`` **(code)**; rotary embedding over the whole head
  (rotate-half, ``rope_theta``, ``rope_scaling`` null) on ``q`` and ``k``
  where ``layer_types[i] == "sliding_attention"`` only: a
  ``full_attention`` layer has no positional encoding **(code)**; softmax
  of ``q k^T * head_dim ** -0.5`` over the keys ``j`` with ``0 <= t - j <
  sliding_window`` on a sliding layer and ``j <= t`` on a full one
  (``_contrib_FlashAttention`` with ``window``); ``out = W_o(softmax(...) v
  * sigmoid(g))``, the gate elementwise on the concatenated head outputs,
  before ``W_o`` **(code)**.
* Dense MLP, each expert and the shared expert
  (``moe_intermediate_size``): ``w2(silu(w1 x) * w3 x)``.
* Expert layer (``_contrib_TopKMoE`` + the shared expert added to it;
  ``score_func`` sigmoid, ``n_group`` = ``topk_group`` = 1: the group step
  is the identity): ``s = sigmoid(x W_r)`` in float32; the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` chosen; gates
  ``s`` at those over their sum (``route_norm``) times ``route_scale``;
  ``y = sum_e gate_e Expert_e(x) + Shared(x)``.  No capacity, no auxiliary
  loss in the graph.

Keys beside the published ones say which share of a deployment this
process holds (``model-configs`` section 4), as in ``lfm2_moe``:
``num_experts`` is the experts HELD here, ``router_num_experts`` the
router's published width (default: all held), ``expert_offset`` the first
held expert, ``router_trained`` whether this share moves its routers; a
sliced ``vocab_size`` is simply a smaller vocabulary.  ``layer_types`` may
be the published list: the first ``num_hidden_layers`` entries are used.

Departures from the published model, all of them:

* ``expert_bias`` is a parameter that no gradient reaches (it enters the
  selection only), so training leaves it where the initialiser put it; the
  published model moves it by a rule outside the loss at rate
  ``load_balance_coeff``;
* ``router_trained`` (default true): with ``false`` every expert layer
  treats its scores as constants to the gradient (``lfm2_moe`` has why a
  lone share says so);
* the gates' sum has 1e-6 added before the division
  (``parallel.moe.topk_moe``), where the modelling code adds 1e-20: 2.5e-7
  of a sum of eight sigmoids;
* an expert layer that holds less than a quarter of its experts computes
  at most four times their even load (``parallel.moe.buffer_rows``); held
  assignments past that are left out.  That is the bound only: where
  a buffer of twice the even load saves more than half a row a token
  (``parallel.moe.small_buffer_rows``), a step that holds no more runs
  over that many rows and leaves out nothing;
* positions start at 0 and there is no cache: this graph trains, it does
  not decode;
* an expert's weights are stored ``(experts, in, out)``.
"""
from __future__ import annotations

import math

from .. import symbol as sym
from ..base import MXNetError
from ..telemetry.spans import span
from .decoder_blocks import add_shared_expert, gated_mlp, \
    grouped_query_attention, linear, topk_experts

SLIDING, FULL = "sliding_attention", "full_attention"


def _attention(x, cfg, kind, prefix):
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    sliding = kind == SLIDING
    return grouped_query_attention(
        x, prefix, d, hq, cfg["num_key_value_heads"],
        cfg.get("head_dim") or d // hq, float(cfg["rms_norm_eps"]),
        rope_theta=cfg["rope_theta"] if sliding else None,
        window=int(cfg["sliding_window"]) if sliding else 0, gated=True)


def _experts(x, cfg, prefix):
    if cfg.get("score_func", "sigmoid") != "sigmoid" \
            or int(cfg.get("n_group", 1)) != 1 \
            or int(cfg.get("topk_group", 1)) != 1:
        raise MXNetError("afmoe: only sigmoid routing over one expert group "
                         "is built")
    y = topk_experts(
        x, dict(cfg, routed_scaling_factor=cfg["route_scale"]),
        prefix + "moe", cfg["num_experts_per_tok"], cfg["route_norm"], True)
    return add_shared_expert(
        y, x, int(cfg.get("num_shared_experts", 0))
        * cfg["moe_intermediate_size"], cfg["hidden_size"], prefix)


def get_symbol(cfg, seq_len):
    """A ``SoftmaxOutput``-headed Symbol of the model ``cfg`` describes
    (the keys of the published ``config.json``, see the module's
    docstring), over ``data`` and ``softmax_label`` of ``(batch, seq_len)``
    token ids; ``ShardedTrainer`` and ``Module`` take it as it is."""
    with span("model.build", category="model", model="afmoe"):
        return _build(cfg, int(seq_len))


def _build(cfg, seq_len):
    d, vocab, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])[:n]
    if len(kinds) != n or set(kinds) - {SLIDING, FULL}:
        raise MXNetError(
            "afmoe: layer_types %r does not give %d layers of %r or %r"
            % (cfg["layer_types"], n, SLIDING, FULL))
    if seq_len > cfg["max_position_embeddings"]:
        raise MXNetError("afmoe: %d positions, the model declares %d"
                         % (seq_len, cfg["max_position_embeddings"]))
    if cfg.get("tie_word_embeddings") or cfg.get("rope_scaling") \
            or not cfg.get("mup_enabled", True):
        raise MXNetError("afmoe: a tied head, scaled rotary embedding and an "
                         "unscaled embedding are not built")
    eps = float(cfg["rms_norm_eps"])
    x = sym.Embedding(sym.Variable("data"), input_dim=vocab, output_dim=d,
                      name="embed") * math.sqrt(d)
    for i, kind in enumerate(kinds):
        p = "layer%d_" % i
        h = _attention(sym.RMSNorm(x, eps=eps, name=p + "op_norm"), cfg,
                       kind, p)
        x = x + sym.RMSNorm(h, eps=eps, name=p + "post_op_norm")
        h = sym.RMSNorm(x, eps=eps, name=p + "ffn_norm")
        h = gated_mlp(h, cfg["intermediate_size"], d, p) \
            if i < cfg["num_dense_layers"] else _experts(h, cfg, p)
        x = x + sym.RMSNorm(h, eps=eps, name=p + "post_ffn_norm")
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = linear(sym.Reshape(x, shape=(-1, d)), vocab, "lm_head")
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return sym.SoftmaxOutput(logits, label=label, name="softmax")
