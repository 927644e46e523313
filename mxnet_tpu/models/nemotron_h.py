"""Nemotron-H decoder (``model_type`` ``nemotron_h``) built from its
configuration's own keys.

Source: ``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json``
(Nemotron 3 Nano 30B-A3B, 2025-12) and the layer equations of
``transformers``' ``modeling_nemotron_h.py`` and of ``mamba_ssm``'s
``Mamba2`` (arXiv:2405.21060).  ``x`` is ``(batch, seq, hidden_size)``;
every linear map is without bias (``use_bias``, ``mlp_bias``,
``attention_bias``, ``mamba_proj_bias`` false; any of them true is
refused); RMSNorm is ``x * rsqrt(mean(x^2) + layer_norm_epsilon) * gamma``.

* Layer ``i`` is the ``i``-th character of ``hybrid_override_pattern``:
  ``h = x + Mix_i(RMSNorm_i(x))``, ONE norm and ONE residual a layer;
  ``Mix_i`` is the Mamba-2 mixer for ``M``, the expert layer for ``E``,
  attention for ``*``; any other character is refused.  The first
  ``num_hidden_layers`` characters are built.  One more RMSNorm after the
  last layer, then an untied head.  There is no positional encoding
  anywhere.
* Mamba-2 mixer (``mamba_num_heads`` H heads of ``mamba_head_dim`` P, inner
  width H P, NOT ``expand`` x ``hidden_size``; ``ssm_state_size`` N,
  ``n_groups`` G, ``conv_kernel`` taps, ``chunk_size``): ``[z, xBC, dt] =
  W_in u`` of widths H P, H P + 2 G N, H in that order; ``xBC = silu(conv(xBC)
  + b_conv)``, depthwise and causal (``_contrib_CausalConv1D`` with its
  bias and ``act_type="silu"``); ``[x, B, C] = xBC`` of widths H P, G N,
  G N, ``x`` as (H, P), ``B`` and ``C`` as (G, N), head ``h`` reading group
  ``h // (H / G)``; ``dt = softplus(dt + dt_bias)`` a head, no clamp; ``A =
  -exp(A_log)`` a head; the recurrence is ``_contrib_SSDScan``'s (``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``).
  Output: ``W_out(RMSNorm_group(y * silu(z)) * gamma)``, the gate BEFORE
  the norm, statistics over each of the G groups of H P / G channels, a
  gain a channel (``_contrib_GatedRMSNorm`` with ``gate_act="silu"``,
  ``gate_first``, ``gamma_axes=2``).
* Attention (``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``): ``W_o(softmax(q
  k^T * head_dim ** -0.5) v)``, causal, query head ``h`` reading key/value
  head ``h // (heads / key/value heads)``; no rotary embedding and no norm
  a head (``rope_theta`` and ``partial_rotary_factor`` are in the
  configuration and nothing reads them).
* Expert layer (``_contrib_TopKMoE`` with ``expert_act="relu2"`` + the
  shared expert added to it): sigmoid scores in float32, the
  ``num_experts_per_tok`` largest of score + the selection bias chosen
  (``n_group`` = ``topk_group`` = 1: the group step is the identity), gates
  the scores at those over their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; an expert is ``w2(relu(w1 x)^2)`` of width
  ``moe_intermediate_size`` (``mlp_hidden_act`` ``relu2``: no gate
  matrix), the shared one the same of width
  ``moe_shared_expert_intermediate_size``; no capacity, no auxiliary loss.

Keys beside the published ones say which share of a deployment this process
holds (``model-configs`` section 4), as in ``kimi_linear``:
``n_routed_experts`` is the experts HELD here, ``router_num_experts`` the
router's published width (default: all held), ``expert_offset`` the first
held expert, ``router_trained`` whether this share moves its routers; a
sliced ``vocab_size`` is simply a smaller vocabulary.

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
* the gates' sum has 1e-6 added where the published code adds 1e-20 (3e-7
  of a gate: the sum of six sigmoids is about 3);
* ``A_log``, ``dt_bias`` and ``D`` reach the scan through the trainer's
  compute dtype like every parameter (bfloat16 under ``dtype="bfloat16"``);
  the step, the decay, its running sums and the state are float32;
* ``rescale_prenorm_residual`` is an initialisation, not an equation: the
  Symbol's default initialisers do not apply it (the benchmark's own
  initialisation does);
* positions start at 0 and there is no cache: this graph trains, it does
  not decode;
* an expert's two matrices are stored ``(experts, width, hidden)``: the
  up-projection ``(experts, out, in)``, the down-projection ``(experts, in,
  out)`` (``parallel.moe._experts`` has why).
"""
from __future__ import annotations

from .. import initializer
from .. import symbol as sym
from ..base import MXNetError
from ..telemetry.spans import span
from .decoder_blocks import add_shared_expert, grouped_query_attention, \
    linear, topk_experts

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _mamba(u, cfg, prefix):
    d = cfg["hidden_size"]
    h, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    inner, bc = h * p, g * n
    if cfg.get("mamba_hidden_act", "silu") != "silu" or h % g or inner % g:
        raise MXNetError("nemotron_h: a Mamba-2 mixer of %d heads over %d "
                         "groups with act %r is not built"
                         % (h, g, cfg.get("mamba_hidden_act")))
    zxbcdt = linear(u, 2 * inner + 2 * bc + h, prefix + "in_proj")

    def part(begin, end):
        return sym.slice_axis(zxbcdt, axis=2, begin=begin, end=end)

    xbc = sym._contrib_CausalConv1D(
        part(inner, 2 * inner + 2 * bc), kernel=int(cfg["conv_kernel"]),
        act_type="silu", no_bias=not cfg.get("use_conv_bias", True),
        name=prefix + "conv")

    def heads(begin, end, shape):
        return sym.Reshape(sym.slice_axis(xbc, axis=2, begin=begin, end=end),
                           shape=(0, 0) + shape)

    y = sym._contrib_SSDScan(
        heads(0, inner, (h, p)), part(2 * inner + 2 * bc, 2 * inner + 2 * bc + h),
        heads(inner, inner + bc, (g, n)), heads(inner + bc, inner + 2 * bc, (g, n)),
        A_log=sym.Variable(prefix + "a_log_bias",
                           init=initializer.LogUniform(1.0, 16.0)),
        D=sym.Variable(prefix + "d_gamma"),
        dt_bias=sym.Variable(
            prefix + "dt_bias", init=initializer.InverseSoftplusLogUniform(
                float(cfg["time_step_min"]), float(cfg["time_step_max"]))),
        chunk_size=int(cfg["chunk_size"]),
        name=prefix + "ssd")
    y = sym._contrib_GatedRMSNorm(
        sym.Reshape(y, shape=(0, 0, g, inner // g)),
        sym.Reshape(part(0, inner), shape=(0, 0, g, inner // g)),
        eps=float(cfg["layer_norm_epsilon"]), gate_act="silu",
        gate_first=True, gamma_axes=2, name=prefix + "mixer_norm")
    return linear(sym.Reshape(y, shape=(0, 0, -3)), d, prefix + "out_proj")


def _attention(x, cfg, prefix):
    return grouped_query_attention(
        x, prefix, cfg["hidden_size"], int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), None,
        qk_norm=False)


def _experts(x, cfg, prefix):
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1 \
            or cfg.get("mlp_hidden_act") != "relu2":
        raise MXNetError("nemotron_h: only relu2 experts routed over one "
                         "expert group are built")
    y = topk_experts(
        x, dict(cfg, num_experts=cfg["n_routed_experts"]), prefix + "moe",
        cfg["num_experts_per_tok"], cfg["norm_topk_prob"], True,
        expert_act="relu2")
    shared = int(cfg.get("n_shared_experts", 0)) \
        and int(cfg["moe_shared_expert_intermediate_size"])
    return add_shared_expert(y, x, shared, cfg["hidden_size"], prefix,
                             expert_act="relu2")


_MIXERS = {MAMBA: _mamba, EXPERTS: _experts, ATTENTION: _attention}


def get_symbol(cfg, seq_len):
    """A ``SoftmaxOutput``-headed Symbol of the model ``cfg`` describes
    (the keys of the published ``config.json``, see the module's
    docstring), over ``data`` and ``softmax_label`` of ``(batch, seq_len)``
    token ids; ``ShardedTrainer`` and ``Module`` take it as it is."""
    with span("model.build", category="model", model="nemotron_h"):
        return _build(cfg, int(seq_len))


def _build(cfg, seq_len):
    d, vocab, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    pattern = cfg["hybrid_override_pattern"]
    if n > len(pattern):
        raise MXNetError("nemotron_h: %d layers, hybrid_override_pattern %r "
                         "names %d" % (n, pattern, len(pattern)))
    strange = sorted(set(pattern[:n]) - set(_MIXERS))
    if strange:
        raise MXNetError("nemotron_h: hybrid_override_pattern %r holds %r; a "
                         "layer is one of %s"
                         % (pattern, "".join(strange), " ".join(_MIXERS)))
    biased = [k for k in ("use_bias", "mlp_bias", "attention_bias",
                          "mamba_proj_bias") if cfg.get(k)]
    if biased or cfg.get("tie_word_embeddings"):
        raise MXNetError("nemotron_h: %s is not built"
                         % (", ".join(biased) or "a tied head"))
    if seq_len > cfg.get("max_position_embeddings", seq_len):
        raise MXNetError("nemotron_h: %d positions, the model declares %d"
                         % (seq_len, cfg["max_position_embeddings"]))
    eps = float(cfg["layer_norm_epsilon"])
    x = sym.Embedding(sym.Variable("data"), input_dim=vocab, output_dim=d,
                      name="embed")
    for i, kind in enumerate(pattern[:n]):
        p = "layer%d_" % i
        x = x + _MIXERS[kind](sym.RMSNorm(x, eps=eps, name=p + "norm"),
                              cfg, p)
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = linear(sym.Reshape(x, shape=(-1, d)), vocab, "lm_head")
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return sym.SoftmaxOutput(logits, label=label, name="softmax")
