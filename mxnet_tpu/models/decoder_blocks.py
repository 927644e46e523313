"""Symbol-level pieces the decoder language models share
(``lfm2_moe``, ``kimi_linear``): a linear map without bias, the gated
MLP and the top-k expert layer over the experts held here."""
from __future__ import annotations

from .. import symbol as sym


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


def topk_experts(x, cfg, name, top_k, renormalize, use_bias):
    """``_contrib_TopKMoE`` from a configuration's keys.  The ones every
    such configuration has: ``num_experts`` (the experts HELD here),
    ``router_num_experts`` (the router's published width; default: all
    held), ``expert_offset`` (the first held expert), ``router_trained``
    (whether this share moves its routers), ``moe_intermediate_size``
    and ``routed_scaling_factor``; the families name the rest
    differently, so the caller reads them."""
    held = int(cfg["num_experts"])
    return sym._contrib_TopKMoE(
        x, num_experts=int(cfg.get("router_num_experts", held)),
        router_trained=bool(cfg.get("router_trained", True)),
        experts_held=held, expert_offset=int(cfg.get("expert_offset", 0)),
        num_experts_per_tok=int(top_k),
        hidden_size=int(cfg["moe_intermediate_size"]),
        norm_topk_prob=bool(renormalize),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        use_expert_bias=bool(use_bias), name=name)
