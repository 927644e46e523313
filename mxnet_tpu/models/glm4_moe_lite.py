"""GLM-4.7-Flash decoder (``model_type`` ``glm4_moe_lite``) with its
multi-token-prediction module, built from its configuration's own keys.

Source: ``https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json``
(GLM-4.5, arXiv:2508.06471; the layer is DeepSeek-V3's, arXiv:2412.19437:
latent attention of its section 2.1.1, the expert layer of 2.1.2, the
multi-token-prediction module of 2.2).  ``x`` is ``(batch, T,
hidden_size)``; every linear map is without bias; RMSNorm is ``x *
rsqrt(mean(x^2) + rms_norm_eps) * gamma``.  Layers count from 0.

* Layer ``l < num_hidden_layers``: ``h = x + MLA_l(RMSNorm(x))``; ``y = h +
  FF_l(RMSNorm(h))``; ``FF_l`` is the gated MLP of ``intermediate_size`` for
  ``l < first_k_dense_replace``, else the expert layer.  One more RMSNorm
  after the last layer, then an untied head: ``logits = W_head
  RMSNorm_f(x_L)``.
* Latent attention (``decoder_blocks.latent_attention`` with both its
  options): ``c_q = RMSNorm(W_qa x)`` (``q_lora_rank``); ``q = W_qb c_q`` as
  ``num_attention_heads`` heads of ``[q_n (qk_nope_head_dim), q_r
  (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank), k_r] = W_kva x``; ``[k_n,
  v (v_head_dim)] = W_kvb RMSNorm(c_kv)`` a head; ``q_r`` and ``k_r`` get the
  rotary embedding over their own ``qk_rope_head_dim`` dims (positions from
  0, base ``rope_theta``, rotate-half over the slice); ``k = [k_n, k_r]``
  with the one ``k_r`` shared by all heads; causal softmax of ``q k^T *
  (qk_nope_head_dim + qk_rope_head_dim) ** -0.5`` over ``v``; ``W_o``.  The
  training form: the latent is expanded, nothing is absorbed, no cache.
* Dense MLP, each expert and the shared expert (``moe_intermediate_size``
  times ``n_shared_experts``): ``w2(silu(w1 x) * w3 x)``.
* Expert layer (``_contrib_TopKMoE`` + the shared expert): sigmoid scores
  in float32, the ``num_experts_per_tok`` largest of score + the gate's
  selection bias chosen (``topk_method`` ``noaux_tc``; ``n_group`` =
  ``topk_group`` = 1: the group step is the identity, anything else
  raises), gates the scores at those over their sum (``norm_topk_prob``)
  times ``routed_scaling_factor``; no capacity, no auxiliary loss.
* The multi-token-prediction module (``num_nextn_predict_layers`` 1; the
  checkpoint's layer ``num_hidden_layers``: ``enorm``, ``hnorm``,
  ``eh_proj``, one decoder layer of the expert kind, ``shared_head.norm``).
  With ``t_0 .. t_T`` the ids (``data`` = ``t_0 .. t_{T-1}``,
  ``softmax_label`` = ``t_1 .. t_T``) and ``x_L`` the last layer's output
  BEFORE the final norm: ``u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(x_L,i)]`` (``W_eh``: ``2d -> d``; ``Emb`` is the model's one
  embedding, looked up on ``softmax_label``); ``z = Layer_mtp(u)`` (latent
  attention + expert layer, causal, positions from 0); ``logits'_i = W_head
  RMSNorm_m(z_i)`` with the model's one head.
* The loss, made in the graph (``MakeLoss``; float32 log-softmax): ``L_main``
  the mean of ``-log softmax(logits_i)[t_{i+1}]`` over ``i = 0 .. T-1``;
  ``L_mtp`` the mean of ``-log softmax(logits'_i)[t_{i+2}]`` over ``i = 0 ..
  T-2`` (the label is ``softmax_label`` shifted left by one in the graph;
  the last row's term is dropped); ``L = L_main + mtp_loss_weight L_mtp``,
  one value a sequence.  ``ShardedTrainer`` monitors the head's mean.

``embed_weight`` and ``lm_head_weight`` are each ONE parameter with two
consumers (the main path and the module): their gradients are the sums of
both uses, and a trainer holds one master and one optimizer state for each.

Keys beside the published ones say which share of a deployment this
process holds (``model-configs`` section 4), as in ``lfm2_moe``:
``n_routed_experts`` is the experts HELD here, ``router_num_experts`` the
router's published width (default: all held), ``expert_offset`` the first
held expert, ``router_trained`` whether this share moves its routers; a
sliced ``vocab_size`` is simply a smaller vocabulary; ``mtp_loss_weight``
(default 0.3) is the ``lambda`` above, which the configuration does not
give.

Departures from the published model, all of them:

* the selection bias (``e_score_correction_bias``) is a parameter that no
  gradient reaches, so training leaves it where the initialiser put it; the
  published model moves it by a load-balancing rule outside the loss, and
  adds a sequence-wise balance loss that is not built;
* ``router_trained`` (default true): with ``false`` every expert layer
  treats its scores as constants to the gradient (``lfm2_moe`` has why a
  lone share says so);
* the gates' sum has 1e-6 added before the division
  (``parallel.moe.topk_moe``) where the modelling code adds 1e-20;
* an expert layer that holds less than a quarter of its experts computes
  at most four times their even load (``parallel.moe.buffer_rows``); a step
  that holds no more than twice it runs over that many rows
  (``parallel.moe.small_buffer_rows``) and leaves out nothing;
* the rotary pairs are ``(i, i + qk_rope_head_dim / 2)`` (rotate-half); the
  checkpoint stores them interleaved ``(2i, 2i + 1)``, a fixed permutation
  of ``W_qb``'s and ``W_kva``'s rotary rows that leaves every score as it
  is;
* the module's hidden input is ``x_L`` before the final norm (it has a
  norm of its own, ``hnorm``), the embedding's half comes first in
  ``W_eh``'s input, and every row stays ``T`` long: row ``T-1`` of the
  module is computed on ``t_T`` and carries no loss;
* positions start at 0 and there is no cache: this graph trains, it does
  not decode, and the module drafts nothing;
* an expert's weights are stored ``(experts, in, out)``.
"""
from __future__ import annotations

from .. import symbol as sym
from ..base import MXNetError
from ..telemetry import plan as _plan
from ..telemetry.spans import span
from .decoder_blocks import SCOPE_MLA, add_shared_expert, block_scope, \
    gated_mlp, latent_attention, linear, plan_note, topk_experts

#: scope of everything the multi-token-prediction module adds: its norms,
#: ``W_eh``, its layer, its head and its loss
SCOPE_MTP = "mxtpu.block.mtp"
#: ``lambda`` where the configuration gives none: DeepSeek-V3's first-phase
#: value (arXiv:2412.19437 section 4.2), which GLM-4.5 keeps
MTP_LOSS_WEIGHT = 0.3


def last_plan_summary():
    """What the step traced last in this process holds of this model's two
    block kinds (None before any such step): ``mla_layers``, one entry a
    latent-attention layer in the graph's order (``q_lora_rank``,
    ``rope_dims``, ``dk``, ``dv``, ``heads``), and ``mtp``, the module's
    own note (``depth``, ``layer_rows`` a sequence puts through a layer,
    ``head_rows`` it puts through the output head, both heads together,
    ``loss_weight``, ``shared``: the parameters it shares with the main
    path) or None.  A pure function of ``telemetry.plan``, as
    ``moe.last_plan_summary()``."""
    mla, mtp = _plan.last(SCOPE_MLA), _plan.last(SCOPE_MTP)
    if mla is None and mtp is None:
        return None
    return {"mla_layers": [dict(layer) for layer in mla or ()],
            "mtp": dict(mtp[-1]) if mtp else None}


def get_symbol(cfg, seq_len):
    """A ``MakeLoss``-headed Symbol of the model ``cfg`` describes (the keys
    of the published ``config.json``, see the module's docstring), over
    ``data`` and ``softmax_label`` of ``(batch, seq_len)`` token ids;
    ``ShardedTrainer`` and ``Module`` take it as it is."""
    with span("model.build", category="model", model="glm4_moe_lite"):
        return _build(cfg, int(seq_len))


def _experts(x, cfg, prefix):
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1 \
            or cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise MXNetError("glm4_moe_lite: only noaux_tc routing over one "
                         "expert group is built")
    y = topk_experts(x, dict(cfg, num_experts=cfg["n_routed_experts"]),
                     prefix + "moe", cfg["num_experts_per_tok"],
                     cfg["norm_topk_prob"], True)
    return add_shared_expert(
        y, x, int(cfg.get("n_shared_experts", 0))
        * cfg["moe_intermediate_size"], cfg["hidden_size"], prefix)


def _layer(x, cfg, prefix, dense):
    d, eps = cfg["hidden_size"], float(cfg["rms_norm_eps"])
    x = x + latent_attention(sym.RMSNorm(x, eps=eps, name=prefix + "op_norm"),
                             cfg, prefix)
    h = sym.RMSNorm(x, eps=eps, name=prefix + "ffn_norm")
    return x + (gated_mlp(h, cfg["intermediate_size"], d, prefix) if dense
                else _experts(h, cfg, prefix))


def _mean_nll(logits, labels, seq_len, rows):
    """``(batch,)``: the mean over the first ``rows`` positions of each
    sequence of ``-log softmax(logits)[label]``; ``logits`` ``(batch * T,
    vocab)``, ``labels`` ``(batch, seq_len)``."""
    nll = sym._contrib_TokenCrossEntropy(logits,
                                         sym.Reshape(labels, shape=(-1,)))
    nll = sym.Reshape(nll, shape=(-1, seq_len))
    return sym.sum(sym.slice_axis(nll, axis=1, begin=0, end=rows), axis=1) \
        * (1.0 / rows)


def _build(cfg, seq_len):
    d, vocab, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    depth = int(cfg.get("num_nextn_predict_layers", 0))
    if depth > 1:
        raise MXNetError("glm4_moe_lite: %d multi-token-prediction modules; "
                         "one is built" % depth)
    if depth and seq_len < 2:
        raise MXNetError("glm4_moe_lite: the multi-token-prediction loss "
                         "needs two positions; got %d" % seq_len)
    if seq_len > cfg.get("max_position_embeddings", seq_len):
        raise MXNetError("glm4_moe_lite: %d positions, the model declares %d"
                         % (seq_len, cfg["max_position_embeddings"]))
    if cfg.get("tie_word_embeddings") or cfg.get("attention_bias") \
            or float(cfg.get("partial_rotary_factor", 1)) != 1:
        raise MXNetError("glm4_moe_lite: a tied head, biased projections and "
                         "a partial_rotary_factor other than 1 are not built")
    eps = float(cfg["rms_norm_eps"])
    table, head = sym.Variable("embed_weight"), sym.Variable("lm_head_weight")
    label = sym.Variable("softmax_label")

    def embed(ids, name):
        return sym.Embedding(ids, weight=table, input_dim=vocab,
                             output_dim=d, name=name)

    def logits(x, norm):
        x = sym.RMSNorm(x, eps=eps, name=norm)
        return linear(sym.Reshape(x, shape=(-1, d)), vocab, norm + "_head",
                      weight=head)

    x = embed(sym.Variable("data"), "embed")
    for i in range(n):
        x = _layer(x, cfg, "layer%d_" % i, i < cfg["first_k_dense_replace"])
    loss = _mean_nll(logits(x, "final_norm"), label, seq_len, seq_len)
    if depth:
        weight = float(cfg.get("mtp_loss_weight", MTP_LOSS_WEIGHT))
        with block_scope(SCOPE_MTP):
            u = linear(
                sym.Concat(
                    sym.RMSNorm(embed(label, "mtp_embed"), eps=eps,
                                name="mtp_enorm"),
                    sym.RMSNorm(x, eps=eps, name="mtp_hnorm"), dim=2),
                d, "mtp_eh_proj")
            z = _layer(u, cfg, "mtp_", dense=False)
            shifted = sym.Concat(
                sym.slice_axis(label, axis=1, begin=1, end=seq_len),
                sym.slice_axis(label, axis=1, begin=0, end=1), dim=1)
            loss = sym.elemwise_add(
                loss, _mean_nll(logits(z, "mtp_final_norm"), shifted, seq_len,
                                seq_len - 1) * weight,
                attr=plan_note(SCOPE_MTP, depth=depth, layer_rows=seq_len,
                               head_rows=2 * seq_len, loss_weight=weight,
                               shared=["embed_weight", "lm_head_weight"]))
    return sym.MakeLoss(loss, name="loss")
