"""How the system is asked for the LFM2-MoE decoder, and what one step needs.

The graph is ``mxnet_tpu.models.lfm2_moe.get_symbol`` from the configuration
file's own keys; the trainer's arguments are the file's ``optimizer`` and
``trainer``.  The operation counts are the benchmark's own."""
from __future__ import annotations


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    from mxnet_tpu.models import lfm2_moe
    seq = int(mix["seq"])
    batch = mix["batch_per_chip"] * n_chips
    return (lfm2_moe.get_symbol(cfg, seq), {"data": (batch, seq)},
            {"softmax_label": (batch, seq)})


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains."""
    return mix["batch_per_chip"] * n_chips * mix["seq"]


def _kinds(cfg):
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    n_att = sum(1 for k in kinds if k == "full_attention")
    n_moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return n_att, len(kinds) - n_att, cfg["num_dense_layers"], n_moe


def router_params(cfg):
    """The routers' parameters one token meets: hidden x the router's width,
    an expert layer."""
    e = cfg.get("router_num_experts", cfg["num_experts"])
    return _kinds(cfg)[3] * cfg["hidden_size"] * e


def matmul_params_per_token(cfg):
    """Matmul parameters one token meets in a forward pass, the routers' apart
    (``router_params``), expecting even routing: ``num_experts_per_tok * held /
    router width`` held experts a token and expert layer (one of 32 x 4, here).
    The realised count is ``moe_assignments_held_pct.tok``: the cell's routing
    holds 23.9-24.3%, so the experts' third of the count reads 3-4% high."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    n_att, n_conv, n_dense, n_moe = _kinds(cfg)
    att = 2 * d * d + 2 * d * cfg["num_key_value_heads"] * hd
    conv = 3 * d * d + d * d
    dense = 3 * d * cfg["intermediate_size"]
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held_per_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    moe = held_per_token * 3 * d * cfg["moe_intermediate_size"]
    return n_att * att + n_conv * conv + n_dense * dense + n_moe * moe + d * v


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter and token (forward, and backward by data and by
    weights), the experts at their expected load (see
    ``matmul_params_per_token``); the routers 6 too where they are trained and 2
    where ``router_trained`` is false (their forward product alone); causal attention
    is half of the full score and value products: 2*S*d forward per token and
    attention layer, three times that with the backward.  The short
    convolution's 3 taps and the recomputation inside the flash backward are
    not counted."""
    n_att = _kinds(cfg)[0]
    per_token = 6.0 * matmul_params_per_token(cfg) \
        + (6.0 if cfg.get("router_trained", True) else 2.0) * router_params(cfg) \
        + 6.0 * mix["seq"] * cfg["hidden_size"] * n_att
    return per_token * units_per_step(cfg, mix, n_chips)


def kernel_costs(cfg, mix, n_chips=1):
    """``{kernel name on the device: {"flops", "bytes", "calls"}}`` of one
    training step, for the kernels this configuration brings: what the
    algorithm needs from its shapes (2 per multiply-add; bf16 operands), not
    what an implementation recomputes or pads.  ``calls`` is how many
    instances a step runs; flops and bytes are of all of them together.

    * ``mxtpu_flash_fwd_stream`` / ``mxtpu_flash_bwd_stream``: causal
      attention of ``num_attention_heads`` query heads over
      ``num_key_value_heads`` key/value heads; 2 products forward and 4
      backward over the causal half (the backward's recomputed scores are not
      needed work); Q, K, V read and O written (bf16) forward; Q, K, V, O, dO
      read (bf16) and dQ, dK, dV written (float32) backward.
    * ``ragged-dot`` (XLA:TPU's grouped matmul, the lowering of
      ``jax.lax.ragged_dot``): the three products of the gated experts over
      the expected held assignments, forward, and by data and by weights
      backward: 9 grouped products an expert layer; each reads its two
      operands and writes its result once."""
    rows = units_per_step(cfg, mix, n_chips)
    seq, d = mix["seq"], cfg["hidden_size"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    n_att, _c, _d, n_moe = _kinds(cfg)
    half = 0.5 * 2.0 * rows * seq * hq * hd          # one causal product
    qo, kv = rows * hq * hd, rows * hk * hd
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held = rows * cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    ff, n_w = cfg["moe_intermediate_size"], cfg["num_experts"] * d * cfg["moe_intermediate_size"]
    product = 2.0 * held * d * ff
    moved = 2.0 * (held * d + held * ff + n_w)       # bf16 operands and result
    return {
        "mxtpu_flash_fwd_stream": {
            "calls": n_att, "flops": n_att * 2 * half,
            "bytes": n_att * 2.0 * (2 * qo + 2 * kv)},
        "mxtpu_flash_bwd_stream": {
            "calls": n_att, "flops": n_att * 4 * half,
            "bytes": n_att * (2.0 * (3 * qo + 2 * kv) + 4.0 * (qo + 2 * kv))},
        "ragged-dot": {
            "calls": n_moe * 9, "flops": n_moe * 9 * product,
            "bytes": n_moe * 9 * moved},
    }
