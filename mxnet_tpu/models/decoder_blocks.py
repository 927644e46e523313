"""Symbol-level pieces the decoder language models share
(``lfm2_moe``, ``kimi_linear``, ``afmoe``, ``nemotron_h``, ``sdar_moe``,
``glm4_moe_lite``): a linear map without bias, the gated and the plain MLP,
grouped-query attention, latent attention, and the top-k expert layer over
the experts held here with the shared expert beside it.  An expert's kind
is ``_contrib_TopKMoE``'s ``expert_act``: ``"silu_gated"`` (``gated_mlp``)
or ``"relu2"`` (``plain_mlp`` with ``relu2``)."""
from __future__ import annotations

import json

from .. import symbol as sym
from .. import attribute
from ..base import MXNetError
# the scope of a latent-attention block's ops is the one an attention call
# whose values have a width of their own has always carried
from ..ops.pallas_kernels import SCOPE_MLA


def block_scope(scope):
    """Every op made within carries ``jax.named_scope(scope)`` on the device
    (``symbol.eval_graph`` reads the ``__scope__`` attribute); inside
    another block's scope, that one's first (``mxtpu.block.mtp/
    mxtpu.block.mla``)."""
    outer = attribute.current().get(None).get("__scope__")
    return attribute.AttrScope(
        __scope__=scope if outer is None else outer + "/" + scope)


def plan_note(scope, **info):
    """``attr=`` of the one node of a block that says what the block is:
    traced inside a ``telemetry.plan.recording`` it notes ``info`` under
    ``scope`` (``symbol.eval_graph`` reads ``__plan_note__``)."""
    return {"__plan_note__": json.dumps([scope, info], sort_keys=True)}


def linear(x, n_out, name, weight=None):
    """``x W^T`` over the last axis, no bias; ``weight``: a variable shared
    with another node (a tied head)."""
    kw = {} if weight is None else {"weight": weight}
    return sym.FullyConnected(x, num_hidden=n_out, flatten=False,
                              no_bias=True, name=name, **kw)


def gated_mlp(x, width, d, prefix):
    """``w2(silu(w1 x) * w3 x)``."""
    gate = sym.Activation(linear(x, width, prefix + "w1"), act_type="silu")
    return linear(gate * linear(x, width, prefix + "w3"), d, prefix + "w2")


def plain_mlp(x, width, d, prefix, act):
    """``w2(act(w1 x))``, no gate matrix; ``act`` an ``Activation`` type."""
    return linear(sym.Activation(linear(x, width, prefix + "w1"),
                                 act_type=act), d, prefix + "w2")


def grouped_query_attention(x, prefix, d, hq, hk, hd, eps, rope_theta=None,
                            window=0, gated=False, qk_norm=True,
                            diffusion_block=0, rope_period=None):
    """``W_o(softmax(q k^T * hd ** -0.5) v)`` of ``hq`` query heads over
    ``hk`` key/value heads of ``hd`` (``_contrib_FlashAttention``, causal):
    an RMSNorm of its own over each head of ``q`` and of ``k`` (none
    with ``qk_norm`` false, and then no rotary embedding either); rotary
    embedding over the whole head (rotate-half, base ``rope_theta``) on
    both, none with ``rope_theta`` None; with ``window``, position ``t``
    sees the keys ``t - window < j <= t`` only; ``gated``: the concatenated
    head outputs times ``sigmoid(W_g x)``, elementwise, before ``W_o``.
    ``diffusion_block``: not causal but under the block-diffusion mask
    in blocks of that many positions, the rows a clean and then a noised
    copy of a document (``_contrib_FlashAttention``'s ``diffusion_block``);
    ``rope_period`` ``(period, copies)``: the rows are ``copies`` copies
    of ``period`` positions and row ``r`` has position ``r mod period``
    (the two copies share their positions)."""
    def heads(name, n, normed):
        y = sym.Reshape(linear(x, n * hd, prefix + name), shape=(0, 0, n, hd))
        if normed:
            y = sym.RMSNorm(y, eps=eps, name=prefix + name + "_norm")
            if rope_theta is not None and rope_period:
                # the op counts positions from an iota over its rows: each
                # copy as a sequence of its own (two free reshapes)
                period, copies = rope_period
                y = sym.Reshape(sym._contrib_RotaryEmbedding(
                    sym.Reshape(y, shape=(-1, int(period), n, hd)),
                    base=float(rope_theta)),
                    shape=(-1, int(period) * int(copies), n, hd))
            elif rope_theta is not None:
                y = sym._contrib_RotaryEmbedding(y, base=float(rope_theta))
        return y

    mask = dict(causal=False, diffusion_block=int(diffusion_block)) \
        if diffusion_block else dict(causal=True, window=int(window))
    att = sym._contrib_FlashAttention(
        heads("q", hq, qk_norm), heads("k", hk, qk_norm),
        heads("v", hk, False), name=prefix + "attn", **mask)
    att = sym.Reshape(att, shape=(0, 0, -3))
    if gated:
        att = att * sym.Activation(linear(x, hq * hd, prefix + "g"),
                                   act_type="sigmoid")
    return linear(att, d, prefix + "o")


def latent_attention(x, cfg, prefix):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) in its
    training form, from the keys every such configuration has:
    ``num_attention_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``
    for the scores and ``v_head_dim`` for the values, a key/value latent of
    ``kv_lora_rank``.  ``[c, k_r] = W_kva x``; ``[k_n, v] = W_kvb
    RMSNorm(c)`` a head; ``k = [k_n, k_r]`` with the one ``k_r`` shared by
    all heads; causal softmax of ``q k^T`` scaled by the query width ``**
    -0.5`` over ``v``; ``W_o``.  The latent is expanded, nothing is absorbed,
    there is no cache.  The two options:

    * ``q_lora_rank`` (None: ``q = W_q x``): a query latent, ``q = W_qb
      RMSNorm(W_qa x)``;
    * ``rope_theta`` with ``mla_use_nope`` false (the default here; Kimi
      Linear sets it true: its "rope" dims are plain dims): the last
      ``qk_rope_head_dim`` dims of each query head and ``k_r`` get the rotary
      embedding over their own width (``_contrib_RotaryEmbedding`` on the
      slices: rotate-half, positions from 0, base ``rope_theta``); the other
      dims are not turned.

    Every op carries the scope ``mxtpu.block.mla`` and the attention call
    notes the layer (``q_lora_rank``, ``rope_dims``, ``dk``, ``dv``,
    ``heads``) in the step's plan."""
    d, h = cfg["hidden_size"], int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, rank = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    q_rank, eps = cfg.get("q_lora_rank"), float(cfg["rms_norm_eps"])
    theta = None if cfg.get("mla_use_nope") else cfg.get("rope_theta")
    if cfg.get("rope_scaling"):
        raise MXNetError("latent attention: a scaled rotary embedding "
                         "(rope_scaling %r) is not built"
                         % (cfg["rope_scaling"],))

    def turned(y, name):
        return y if theta is None else sym._contrib_RotaryEmbedding(
            y, base=float(theta), name=prefix + name)

    with block_scope(SCOPE_MLA):
        if q_rank is None:
            q = linear(x, h * (nope + rope), prefix + "q")
        else:
            q = linear(sym.RMSNorm(linear(x, int(q_rank), prefix + "q_a"),
                                   eps=eps, name=prefix + "q_norm"),
                       h * (nope + rope), prefix + "q_b")
        q = sym.Reshape(q, shape=(0, 0, h, nope + rope))
        if theta is not None:
            q = sym.Concat(
                sym.slice_axis(q, axis=3, begin=0, end=nope),
                turned(sym.slice_axis(q, axis=3, begin=nope,
                                      end=nope + rope), "q_rope"), dim=3)
        kva = linear(x, rank + rope, prefix + "kv_a")
        latent = sym.RMSNorm(sym.slice_axis(kva, axis=2, begin=0, end=rank),
                             eps=eps, name=prefix + "kv_norm")
        kvb = sym.Reshape(linear(latent, h * (nope + dv), prefix + "kv_b"),
                          shape=(0, 0, h, nope + dv))
        k_rope = sym.broadcast_axis(
            turned(sym.Reshape(sym.slice_axis(kva, axis=2, begin=rank,
                                              end=rank + rope),
                               shape=(0, 0, 1, rope)), "k_rope"),
            axis=2, size=h)
        k = sym.Concat(sym.slice_axis(kvb, axis=3, begin=0, end=nope), k_rope,
                       dim=3)
        att = sym._contrib_FlashAttention(
            q, k, sym.slice_axis(kvb, axis=3, begin=nope, end=nope + dv),
            causal=True, name=prefix + "attn",
            attr=plan_note(
                SCOPE_MLA, q_lora_rank=None if q_rank is None else int(q_rank),
                rope_dims=0 if theta is None else rope, dk=nope + rope, dv=dv,
                heads=h))
        return linear(sym.Reshape(att, shape=(0, 0, -3)), d, prefix + "o")


def add_shared_expert(y, x, width, d, prefix, expert_act="silu_gated"):
    """``y`` plus the shared expert's part: one MLP of ``width`` and of the
    routed experts' kind on every token; every chip that shares the layer
    computes it alike.  ``width`` 0: no shared expert."""
    if not width:
        return y
    if expert_act == "relu2":
        return y + plain_mlp(x, width, d, prefix + "shared_", "relu2")
    return y + gated_mlp(x, width, d, prefix + "shared_")


def topk_experts(x, cfg, name, top_k, renormalize, use_bias,
                 expert_act="silu_gated", score_func="sigmoid"):
    """``_contrib_TopKMoE`` from a configuration's keys.  The ones every
    such configuration has: ``num_experts`` (the experts HELD here),
    ``router_num_experts`` (the router's published width; default: all
    held), ``expert_offset`` (the first held expert), ``router_trained``
    (whether this share moves its routers), ``moe_intermediate_size``
    and ``routed_scaling_factor``; the families name the rest
    differently, so the caller reads them.  ``score_func``: ``"sigmoid"``
    or ``"softmax"`` over the router's whole width."""
    held = int(cfg["num_experts"])
    return sym._contrib_TopKMoE(
        x, num_experts=int(cfg.get("router_num_experts", held)),
        router_trained=bool(cfg.get("router_trained", True)),
        experts_held=held, expert_offset=int(cfg.get("expert_offset", 0)),
        num_experts_per_tok=int(top_k),
        hidden_size=int(cfg["moe_intermediate_size"]),
        norm_topk_prob=bool(renormalize),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        use_expert_bias=bool(use_bias), expert_act=expert_act,
        score_func=score_func, name=name)
