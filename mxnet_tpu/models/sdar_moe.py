"""SDAR expert decoder (``model_type`` ``sdar_moe``) trained by block
diffusion, built from its configuration's own keys.

Source: ``https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json``
(SDAR 30B-A3B, 2025-10; "SDAR: A Synergistic Diffusion-AutoRegression
Paradigm for Scalable Sequence Generation", arXiv:2510.06303).  The layer
is Qwen3-MoE's as ``transformers``' ``modeling_qwen3_moe.py`` computes it,
which ``sdar_moe`` keeps; the training form is Block Diffusion's (BD3-LM,
arXiv:2503.09573).  The configuration gives the sizes and the routing's
keys; what it does not give is the modelling code's (**(code)**) or the
papers' (**(paper)**) as this file's author knows them, and what neither
gives is a key beside the published ones (**assumed**: ``block_length``,
``noise_eps``, ``mask_token_id``).  Every linear map is without bias
(``attention_bias`` false); RMSNorm is ``x * rsqrt(mean(x^2) +
rms_norm_eps) * gamma``.

* Decoder: ``h_0 = Embedding(ids)`` (no scale).  Layer: ``a = h +
  Attn(RMSNorm_in(h))``; ``h' = a + MoE(RMSNorm_post(a))``.  One RMSNorm
  after the last layer, then an untied head (``tie_word_embeddings``
  false).  Every layer is an expert layer (``decoder_sparse_step`` 1,
  ``mlp_only_layers`` []): ``intermediate_size`` is read by no layer.
* Attention (``decoder_blocks.grouped_query_attention``):
  ``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim``; an RMSNorm over each head of ``q`` and
  of ``k``, one gain of ``head_dim`` for all heads **(code)**; rotary
  embedding over the whole head (rotate-half, ``rope_theta``,
  ``rope_scaling`` null) at the row's position; ``softmax(q k^T *
  head_dim ** -0.5)`` over the keys the row sees (below).
* Expert layer (``_contrib_TopKMoE``, ``score_func`` softmax): ``p =
  softmax(x W_r)`` over all ``num_experts`` in float32; the
  ``num_experts_per_tok`` largest chosen; gates ``p_e`` over the sum of
  the chosen (``norm_topk_prob``); ``y = sum_e gate_e Expert_e(x)``,
  ``Expert(x) = W_down(silu(W_gate x) * W_up x)`` of
  ``moe_intermediate_size``.  No selection bias, no scale, no shared
  expert, no capacity; the auxiliary balancing loss is not in the graph.
* Block-diffusion training **(paper)**.  A document ``x_0`` of ``L``
  tokens is ``L / B`` blocks of ``B = block_length``.  Block ``b`` has a
  level ``t_b = noise_eps + (1 - noise_eps) s_b`` with ``s_b`` uniform on
  (0, 1); position ``i`` is masked, ``m_i = 1``, where its own uniform
  draw ``u_i < t_{i // B}``; ``x_t,i`` is ``mask_token_id`` where masked,
  else ``x_0,i``.  The decoder runs once over ``2L`` rows: the clean
  copy ``c_i`` (token ``x_0,i``, position ``i``) and then the noised copy
  ``n_i`` (token ``x_t,i``, the same position ``i``).  ``c_i`` sees
  ``c_j`` iff ``j // B <= i // B``; ``n_i`` sees ``n_j`` iff ``j // B ==
  i // B`` and ``c_j`` iff ``j // B < i // B``; no clean row sees a noised
  one (``_contrib_FlashAttention``'s ``diffusion_block``).  Only the
  noised rows go through the final norm and the head, and the logits at
  ``n_i`` predict position ``i``'s own token (no shift).  The loss of a
  batch of ``b`` documents is ``(1 / (b L)) sum_i (m_i / t_{i // B}) *
  (-log softmax(logits(n_i))[x_0,i])`` in float32: a ``MakeLoss`` head
  over one value a document, its ``1 / L`` included, which
  ``ShardedTrainer`` monitors by its mean.

The batch (``docs/how_to/block_diffusion.md``): ``data`` is ``(batch, 2 L
+ L / B)`` token ids as floats: the document, then ``L`` ids that are the
mask draws ``u_i = (id + 0.5) / vocab_size``, then ``L / B`` ids that are
the level draws ``s_b = (id + 0.5) / vocab_size``.  The noising is ops of
the graph, so a run's noise is its batch's and a reference that reads the
same batch sees the same masks.  No label input is read.

Keys beside the published ones say which share of a deployment this
process holds (``model-configs`` section 4), as in ``lfm2_moe``:
``num_experts`` is the experts HELD here, ``router_num_experts`` the
router's published width (default: all held), ``expert_offset`` the first
held expert, ``router_trained`` whether this share moves its routers; a
sliced ``vocab_size`` is simply a smaller vocabulary.

Departures from the published model, all of them:

* ``router_trained`` (default true): with ``false`` every expert layer
  treats its scores as constants to the gradient (``lfm2_moe`` has why a
  lone share says so);
* the gates' sum has 1e-6 added before the division
  (``parallel.moe.topk_moe``), where the modelling code adds nothing;
* an expert layer that holds less than a quarter of its experts computes
  at most four times their even load (``parallel.moe.buffer_rows``); a
  step that holds no more than twice it runs over that many rows
  (``parallel.moe.small_buffer_rows``) and leaves out nothing;
* a ``mask_token_id`` inside a sliced vocabulary may also turn up in a
  document; it carries no weight there, because ``m`` comes from the
  draws and not from the ids;
* the last layer's attention output and expert layer are computed on the
  clean rows too, though nothing reads them;
* no cache (this graph trains, it does not generate); an expert's weights
  are stored ``(experts, in, out)``.
"""
from __future__ import annotations

from .. import symbol as sym
from ..base import MXNetError
from ..telemetry.spans import span
from .decoder_blocks import grouped_query_attention, linear, topk_experts

_LAST_PLAN = None


def last_plan_summary():
    """What the graph built last in this process runs a document through
    (None before any): ``doc_len``, ``block_length``, ``layers``,
    ``layer_rows`` (rows a document puts through every layer: both
    copies) and ``head_rows`` (rows through the final norm and the head:
    the noised copy).  As ``ops.ssd.last_plan_summary()``."""
    return _LAST_PLAN


def get_symbol(cfg, doc_len):
    """A ``MakeLoss``-headed Symbol of the model ``cfg`` describes (the
    keys of the published ``config.json`` and the training keys, see the
    module's docstring) over ``data`` of ``(batch, 2 * doc_len + doc_len
    // block_length)``; ``ShardedTrainer`` and ``Module`` take it as it
    is."""
    with span("model.build", category="model", model="sdar_moe"):
        return _build(cfg, int(doc_len))


def noised(data, cfg, doc_len):
    """``(document ids, noised ids, weights m / t)`` of ``(batch,
    doc_len)`` each, from ``data`` as the module's docstring lays it
    out."""
    n_blocks, block = doc_len // cfg["block_length"], cfg["block_length"]
    vocab, eps = float(cfg["vocab_size"]), float(cfg["noise_eps"])

    def part(begin, end):
        return sym.slice_axis(data, axis=1, begin=begin, end=end)

    doc = part(0, doc_len)
    u = (part(doc_len, 2 * doc_len) + 0.5) / vocab
    s = (part(2 * doc_len, 2 * doc_len + n_blocks) + 0.5) / vocab
    t = sym.repeat(s * (1.0 - eps) + eps, repeats=block, axis=1)
    m = sym.broadcast_lesser(u, t)
    # exact in float32: ids are whole numbers under 2 ** 24
    ids = doc + m * (float(cfg["mask_token_id"]) - doc)
    return doc, ids, m / t


def _build(cfg, doc_len):
    global _LAST_PLAN
    d, vocab, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    block = int(cfg["block_length"])
    if block <= 0 or doc_len % block:
        raise MXNetError("sdar_moe: a document of %d tokens is no whole "
                         "number of blocks of %d" % (doc_len, block))
    if doc_len > cfg["max_position_embeddings"]:
        raise MXNetError("sdar_moe: %d positions, the model declares %d"
                         % (doc_len, cfg["max_position_embeddings"]))
    if cfg.get("tie_word_embeddings") or cfg.get("rope_scaling") \
            or cfg.get("attention_bias") or cfg.get("use_sliding_window") \
            or cfg.get("mlp_only_layers") \
            or int(cfg.get("decoder_sparse_step", 1)) != 1:
        raise MXNetError("sdar_moe: a tied head, scaled rotary embedding, "
                         "biased projections, a sliding window and dense "
                         "layers are not built")
    if not 0 <= int(cfg["mask_token_id"]) < vocab:
        raise MXNetError("sdar_moe: mask_token_id %d lies outside the %d ids"
                         % (cfg["mask_token_id"], vocab))
    eps = float(cfg["rms_norm_eps"])
    hq = cfg["num_attention_heads"]
    doc, ids, weight = noised(sym.Variable("data"), cfg, doc_len)
    table = sym.Variable("embed_weight")
    # the clean copy first; the first lookup reads the batch's ids through
    # a slice alone, which keeps ``data`` float32 in a bfloat16 trainer
    x = sym.Concat(*[sym.Embedding(i, weight=table, input_dim=vocab,
                                   output_dim=d, name=name)
                     for i, name in ((doc, "embed"), (ids, "embed_noised"))],
                   dim=1)
    for i in range(n):
        p = "layer%d_" % i
        x = x + grouped_query_attention(
            sym.RMSNorm(x, eps=eps, name=p + "op_norm"), p, d, hq,
            cfg["num_key_value_heads"], cfg.get("head_dim") or d // hq, eps,
            rope_theta=cfg["rope_theta"], diffusion_block=block,
            rope_period=(doc_len, 2))
        x = x + topk_experts(
            sym.RMSNorm(x, eps=eps, name=p + "ffn_norm"),
            dict(cfg, routed_scaling_factor=1.0), p + "moe",
            cfg["num_experts_per_tok"], cfg["norm_topk_prob"], False,
            score_func="softmax")
    x = sym.slice_axis(x, axis=1, begin=doc_len, end=2 * doc_len)
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = linear(sym.Reshape(x, shape=(-1, d)), vocab, "lm_head")
    logp = sym.log_softmax(sym.Cast(logits, dtype="float32"), axis=-1)
    nll = sym.pick(logp, sym.Reshape(doc, shape=(-1,)), axis=-1) \
        * sym.Reshape(weight, shape=(-1,)) * (-1.0 / doc_len)
    _LAST_PLAN = {"doc_len": doc_len, "block_length": block, "layers": n,
                  "layer_rows": 2 * doc_len, "head_rows": doc_len}
    return sym.MakeLoss(sym.sum(sym.Reshape(nll, shape=(-1, doc_len)),
                                axis=1), name="loss")
