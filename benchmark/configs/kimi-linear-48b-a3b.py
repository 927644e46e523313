"""How the system is asked for the Kimi Linear decoder, and what one step needs.

The graph is ``mxnet_tpu.models.kimi_linear.get_symbol`` from the configuration
file's own keys; the trainer's arguments are the file's ``optimizer`` and
``trainer``.  The operation counts are the benchmark's own."""
from __future__ import annotations

#: positions of a chunk of the linear-attention scan, as the program's
#: ``_contrib_GatedDeltaRule`` has them by default
KDA_CHUNK = 64


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    from mxnet_tpu.models import kimi_linear
    seq = int(mix["seq"])
    batch = mix["batch_per_chip"] * n_chips
    return (kimi_linear.get_symbol(cfg, seq), {"data": (batch, seq)},
            {"softmax_label": (batch, seq)})


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains."""
    return mix["batch_per_chip"] * n_chips * mix["seq"]


def _kinds(cfg):
    """(KDA layers, latent-attention layers, dense layers, expert layers) built."""
    n = cfg["num_hidden_layers"]
    n_kda = sum(1 for i in cfg["linear_attn_config"]["kda_layers"] if i <= n)
    n_dense = min(n, cfg["first_k_dense_replace"])
    return n_kda, n - n_kda, n_dense, n - n_dense


def router_params(cfg):
    """The routers' parameters one token meets: hidden x the router's width,
    an expert layer."""
    e = cfg.get("router_num_experts", cfg["num_experts"])
    return _kinds(cfg)[3] * cfg["hidden_size"] * e


def matmul_params_per_token(cfg):
    """Matmul parameters one token meets in a forward pass, the routers' apart
    (``router_params``), expecting even routing: ``num_experts_per_token * held
    / router width`` held experts a token and expert layer (a quarter of one,
    here); the shared expert whole.  The realised count is
    ``moe_assignments_held_pct.tok``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    hw = lin["num_heads"] * lin["head_dim"]
    kda = 4 * d * hw + 2 * (d * lin["head_dim"] + lin["head_dim"] * hw) \
        + d * lin["num_heads"]
    h = cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mla = d * h * (nope + rope) + d * (cfg["kv_lora_rank"] + rope) \
        + cfg["kv_lora_rank"] * h * (nope + dv) + h * dv * d
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held_per_token = cfg["num_experts_per_token"] * cfg["num_experts"] / e
    n_kda, n_mla, n_dense, n_moe = _kinds(cfg)
    return n_kda * kda + n_mla * mla + n_dense * dense \
        + n_moe * (cfg.get("num_shared_experts", 0) + held_per_token) * expert + d * v


def _kda_products(cfg, rows):
    """Multiply-adds x 2 of one KDA layer's scan over ``rows`` positions, forward,
    by chunk length ``C``: what the chunk algebra needs a position and head
    (``dk`` = ``dv`` = ``d``): the two score-like matrices over the causal half
    of a chunk (``C d``), the triangular solve applied to keys and values (``2 C
    d`` over the half), the chunk's three products with the state (``3 d d``),
    the intra-chunk output (``C d`` over the half) and the state's update (``d
    d``)."""
    lin = cfg["linear_attn_config"]
    d, c = lin["head_dim"], KDA_CHUNK
    per_position = 2.0 * (0.5 * 2 * c * d + 0.5 * 2 * c * d + 0.5 * c * d + 4 * d * d)
    return per_position * rows * lin["num_heads"]


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter and token (forward, and backward by data and by
    weights), the routed experts at their expected load (see
    ``matmul_params_per_token``); the routers 6 too where they are trained and 2
    where ``router_trained`` is false; causal latent attention is half of the
    full score (192 wide) and value (128 wide) products, three times that with
    the backward; the linear-attention scan at ``_kda_products`` forward and
    twice that backward.  The short convolutions' taps, the norms and the
    recomputation inside the flash and the scan backward are not counted."""
    n_kda, n_mla = _kinds(cfg)[:2]
    rows = units_per_step(cfg, mix, n_chips)
    h = cfg["num_attention_heads"]
    att = 3.0 * 0.5 * 2.0 * mix["seq"] * h * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    per_token = 6.0 * matmul_params_per_token(cfg) \
        + (6.0 if cfg.get("router_trained", True) else 2.0) * router_params(cfg) \
        + att * n_mla
    return per_token * rows + 3.0 * n_kda * _kda_products(cfg, rows)


def kernel_costs(cfg, mix, n_chips=1):
    """``{kernel name on the device: {"flops", "bytes", "calls"}}`` of one
    training step, for the kernels this configuration brings: what the
    algorithm needs from its shapes (2 per multiply-add; bf16 operands), not
    what an implementation recomputes or pads.

    * ``mxtpu_flash_fwd_stream`` / ``mxtpu_flash_bwd_stream``: causal latent
      attention, scores over ``dk`` = 192 and values of ``dv`` = 128, over the
      causal half: forward one product of each width (QK^T, PV); backward two of
      each (dQ, dK over ``dk``; dV, dP over ``dv``; the recomputed scores are not
      needed work).  Q, K (``dk``) and V (``dv``) read, O written forward; Q, K,
      V, O, dO read (bf16) and dQ, dK, dV written (float32) backward.
    * ``mxtpu.block.kda`` (the scope of the chunked scan's XLA ops; no single
      kernel): ``_kda_products`` forward and twice that backward; q, k, v, beta
      (bf16) and g (float32) read and o written forward; those, o's cotangent
      and one float32 state a chunk read, five gradients written backward, plus
      the states written forward.
    * ``ragged-dot``: the three products of the gated experts over the expected
      held assignments, forward, and by data and by weights backward: 9 grouped
      products an expert layer."""
    rows = units_per_step(cfg, mix, n_chips)
    seq, d = mix["seq"], cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n_kda, n_mla, _d, n_moe = _kinds(cfg)
    half = 0.5 * 2.0 * rows * seq * h                 # one causal product a unit of width
    q_, v_ = rows * h * dk, rows * h * dv
    lin = cfg["linear_attn_config"]
    hk, hd = lin["num_heads"], lin["head_dim"]
    wide = rows * hk * hd
    states = 4.0 * (rows // KDA_CHUNK) * hk * hd * hd
    kda_fwd_bytes = 2.0 * 4 * wide + 4.0 * wide + 2.0 * rows * hk + states
    kda_bwd_bytes = 2.0 * 5 * wide + 4.0 * wide + 2.0 * rows * hk + states \
        + 2.0 * 3 * wide + 4.0 * wide + 2.0 * rows * hk
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held = rows * cfg["num_experts_per_token"] * cfg["num_experts"] / e
    ff = cfg["moe_intermediate_size"]
    n_w = cfg["num_experts"] * d * ff
    product = 2.0 * held * d * ff
    moved = 2.0 * (held * d + held * ff + n_w)
    return {
        "mxtpu_flash_fwd_stream": {
            "calls": n_mla, "flops": n_mla * half * (dk + dv),
            "bytes": n_mla * 2.0 * (2 * q_ + 2 * v_)},
        "mxtpu_flash_bwd_stream": {
            "calls": n_mla, "flops": n_mla * half * 2 * (dk + dv),
            "bytes": n_mla * (2.0 * (2 * q_ + 3 * v_) + 4.0 * (2 * q_ + v_))},
        "mxtpu.block.kda": {
            "calls": n_kda, "flops": 3.0 * n_kda * _kda_products(cfg, rows),
            "bytes": n_kda * (kda_fwd_bytes + kda_bwd_bytes)},
        "ragged-dot": {
            "calls": n_moe * 9, "flops": n_moe * 9 * product,
            "bytes": n_moe * 9 * moved},
    }
