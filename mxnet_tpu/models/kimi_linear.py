"""Kimi Linear decoder (``model_type`` ``kimi_linear``) built from its
configuration's own keys.

Source: ``https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json``
(Kimi Linear, arXiv:2510.26692) and the layer equations of its
``modeling_kimi.py`` and of ``fla.layers.kda``.  ``x`` is ``(batch, seq,
hidden_size)``; every linear map is without bias; RMSNorm is ``x *
rsqrt(mean(x^2) + rms_norm_eps) * gamma``.  The configuration counts its
layers from 1; parameter names here count from 0 (``layer0_`` is its
layer 1).

* Layer ``i`` (1-based): ``h = x + Mix_i(RMSNorm(x))``; ``y = h +
  FF_i(RMSNorm(h))``.  ``Mix_i`` is Kimi Delta Attention for ``i`` in
  ``linear_attn_config.kda_layers`` and latent attention for ``i`` in
  ``linear_attn_config.full_attn_layers``; ``FF_i`` is the dense MLP
  (``intermediate_size``) for ``i <= first_k_dense_replace``, else the
  expert layer.  One more RMSNorm after the last layer, then an untied
  head.  There is no positional encoding anywhere.
* Kimi Delta Attention (``linear_attn_config``: ``num_heads`` heads of
  ``head_dim`` for keys and values alike, ``short_conv_kernel_size``
  taps): ``q = L2(silu(conv(W_q x)))``, ``k = L2(silu(conv(W_k x)))``,
  ``v = silu(conv(W_v x))`` with ``conv`` depthwise and causal
  (``_contrib_CausalConv1D`` with ``act_type="silu"``) and ``L2`` over each
  head (``x * rsqrt(sum x^2 + 1e-6)``), ``q`` then scaled by ``head_dim **
  -0.5`` (``_contrib_GatedDeltaRule``'s ``qk_l2norm`` and ``scale``).  Log-decay in float32 (``_contrib_KDAGate``):
  ``g = -exp(A_log) * softplus(W_f_up W_f_down x + dt_bias)``, ``W_f_down``
  to ``head_dim``, one ``A_log`` a head.  ``beta = sigmoid(W_b x)``, one a
  head.  The recurrence is ``_contrib_GatedDeltaRule``'s.  Output:
  ``W_o(RMSNorm_head(o) * sigmoid(W_g_up W_g_down x))``
  (``_contrib_GatedRMSNorm``, one gain of ``head_dim``).
* Latent attention (``q_lora_rank`` null, ``kv_lora_rank``,
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
  ``mla_use_nope`` true): ``q = W_q x`` as heads of ``qk_nope_head_dim +
  qk_rope_head_dim``; ``[c, k_r] = W_kva x``; ``[k_n, v] = W_kvb
  RMSNorm(c)`` a head; ``k = [k_n, k_r]`` with the one ``k_r`` shared by
  all heads; no rotary embedding is applied (the "rope" dims are plain
  dims); causal softmax of ``q k^T`` scaled by the query width ``** -0.5``
  over values of ``v_head_dim`` (``_contrib_FlashAttention`` with a value
  width of its own).  The training form: the latent is expanded, nothing
  is absorbed, there is no cache.
* Dense MLP, each expert and the shared expert
  (``moe_intermediate_size``): ``w2(silu(w1 x) * w3 x)``.
* Expert layer (``_contrib_TopKMoE`` + the shared expert added to it):
  sigmoid scores in float32 (``moe_router_activation_func``), the
  ``num_experts_per_token`` largest of score + the gate's selection bias
  chosen (``num_expert_group`` = ``topk_group`` = 1: the group step is the
  identity), gates the scores at those over their sum
  (``moe_renormalize``) times ``routed_scaling_factor``; no capacity, no
  auxiliary loss.

Keys beside the published ones say which share of a deployment this
process holds (``model-configs`` section 4), as in ``lfm2_moe``:
``num_experts`` is the experts HELD here, ``router_num_experts`` the
router's published width (default: all held), ``expert_offset`` the first
held expert, ``router_trained`` whether this share moves its routers; a
sliced ``vocab_size`` is simply a smaller vocabulary.  The two layer lists
may be the published ones: the first ``num_hidden_layers`` layers are
built.

Departures from the published model, all of them:

* the selection bias (``e_score_correction_bias``) is a parameter that no
  gradient reaches, so training leaves it where the initialiser put it;
  the published model moves it by a load-balancing rule outside the loss;
* ``router_trained`` (default true): with ``false`` every expert layer
  treats its scores as constants to the gradient (``lfm2_moe`` has why a
  lone share says so);
* an expert layer that holds less than a quarter of its experts computes
  at most four times their even load (``parallel.moe.buffer_rows``); held
  assignments past that are left out.  That is the bound only: where
  a buffer of twice the even load saves more than half a row a token
  (``parallel.moe.small_buffer_rows``), a step that holds no more runs
  over that many rows and leaves out nothing;
* ``A_log`` and ``dt_bias`` reach the decay through the trainer's compute
  dtype like every parameter (bfloat16 under ``dtype="bfloat16"``); the
  decay itself, its running sums and the state are float32;
* positions start at 0 and there is no cache: this graph trains, it does
  not decode;
* an expert's weights are stored ``(experts, in, out)``.
"""
from __future__ import annotations

from .. import initializer
from .. import symbol as sym
from ..base import MXNetError
from ..telemetry.spans import span
from .decoder_blocks import add_shared_expert, gated_mlp, \
    latent_attention, linear, topk_experts


def _kda(x, cfg, prefix):
    lin = cfg["linear_attn_config"]
    d, h, hd = cfg["hidden_size"], int(lin["num_heads"]), int(lin["head_dim"])
    taps, eps = int(lin["short_conv_kernel_size"]), float(cfg["rms_norm_eps"])

    def mixed(name):
        y = sym._contrib_CausalConv1D(
            linear(x, h * hd, prefix + name), kernel=taps, act_type="silu",
            name=prefix + name + "_conv")
        return sym.Reshape(y, shape=(0, 0, h, hd))

    decay = sym._contrib_KDAGate(
        linear(linear(x, hd, prefix + "f_down"), h * hd, prefix + "f_up"),
        a_log=sym.Variable(prefix + "a_log_bias",
                           init=initializer.LogUniform(1.0, 16.0)),
        dt_bias=sym.Variable(prefix + "dt_bias",
                             init=initializer.InverseSoftplusLogUniform(
                                 0.001, 0.1)),
        num_heads=h, name=prefix + "decay")
    beta = sym.Activation(linear(x, h, prefix + "b"), act_type="sigmoid")
    o = sym._contrib_GatedDeltaRule(
        mixed("q"), mixed("k"), mixed("v"), decay, beta, qk_l2norm=True,
        scale=hd ** -0.5, name=prefix + "kda")
    gate = sym.Reshape(
        linear(linear(x, hd, prefix + "g_down"), h * hd, prefix + "g_up"),
        shape=(0, 0, h, hd))
    o = sym._contrib_GatedRMSNorm(o, gate, eps=eps, name=prefix + "o_norm")
    return linear(sym.Reshape(o, shape=(0, 0, -3)), d, prefix + "o")


def _mla(x, cfg, prefix):
    if cfg.get("q_lora_rank") is not None or not cfg.get("mla_use_nope", True):
        raise MXNetError("kimi_linear: its latent attention has no query "
                         "latent (q_lora_rank) and turns no dims "
                         "(mla_use_nope); models.glm4_moe_lite builds both")
    return latent_attention(x, dict(cfg, mla_use_nope=True), prefix)


def _experts(x, cfg, prefix):
    if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid" \
            or int(cfg.get("num_expert_group", 1)) != 1 \
            or int(cfg.get("topk_group", 1)) != 1:
        raise MXNetError("kimi_linear: only sigmoid routing over one expert "
                         "group is built")
    y = topk_experts(x, cfg, prefix + "moe", cfg["num_experts_per_token"],
                     cfg["moe_renormalize"], True)
    return add_shared_expert(
        y, x, int(cfg.get("num_shared_experts", 0))
        * cfg["moe_intermediate_size"], cfg["hidden_size"], prefix)


def get_symbol(cfg, seq_len):
    """A ``SoftmaxOutput``-headed Symbol of the model ``cfg`` describes
    (the keys of the published ``config.json``, see the module's
    docstring), over ``data`` and ``softmax_label`` of ``(batch, seq_len)``
    token ids; ``ShardedTrainer`` and ``Module`` take it as it is."""
    with span("model.build", category="model", model="kimi_linear"):
        return _build(cfg, int(seq_len))


def _build(cfg, seq_len):
    d, vocab, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    lin = cfg["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or not set(range(1, n + 1)) <= kda | full:
        raise MXNetError(
            "kimi_linear: kda_layers %r and full_attn_layers %r do not give "
            "each of the %d layers one mixer"
            % (lin["kda_layers"], lin["full_attn_layers"], n))
    if seq_len > cfg.get("model_max_length", seq_len):
        raise MXNetError("kimi_linear: %d positions, the model declares %d"
                         % (seq_len, cfg["model_max_length"]))
    if cfg.get("tie_word_embeddings"):
        raise MXNetError("kimi_linear: a tied head is not built")
    eps = float(cfg["rms_norm_eps"])
    x = sym.Embedding(sym.Variable("data"), input_dim=vocab, output_dim=d,
                      name="embed")
    for i in range(n):
        p = "layer%d_" % i
        h = sym.RMSNorm(x, eps=eps, name=p + "op_norm")
        x = x + (_kda(h, cfg, p) if i + 1 in kda else _mla(h, cfg, p))
        h = sym.RMSNorm(x, eps=eps, name=p + "ffn_norm")
        x = x + (gated_mlp(h, cfg["intermediate_size"], d, p)
                 if i < cfg["first_k_dense_replace"] else _experts(h, cfg, p))
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = linear(sym.Reshape(x, shape=(-1, d)), vocab, "lm_head")
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return sym.SoftmaxOutput(logits, label=label, name="softmax")
