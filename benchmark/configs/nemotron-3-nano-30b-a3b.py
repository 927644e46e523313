"""How the system is asked for the Nemotron-H decoder, and what one step needs.

The graph is ``mxnet_tpu.models.nemotron_h.get_symbol`` from the configuration
file's own keys; the trainer's arguments are the file's ``optimizer`` and
``trainer``.  The operation counts are the benchmark's own."""
from __future__ import annotations

# at the module's top, so that a program without the model fails when the cell
# is looked up, at once
from mxnet_tpu.models import nemotron_h


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    seq = int(mix["seq"])
    batch = mix["batch_per_chip"] * n_chips
    return (nemotron_h.get_symbol(cfg, seq), {"data": (batch, seq)},
            {"softmax_label": (batch, seq)})


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains."""
    return mix["batch_per_chip"] * n_chips * mix["seq"]


def _kinds(cfg):
    """(Mamba-2 mixers, expert layers, attention layers) built."""
    built = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return built.count("M"), built.count("E"), built.count("*")


def _held_per_token(cfg):
    """Held experts a token meets in an expert layer under even routing."""
    e = cfg.get("router_num_experts", cfg["n_routed_experts"])
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / e


def router_params(cfg):
    """The routers' parameters one token meets: hidden x the router's width,
    an expert layer."""
    e = cfg.get("router_num_experts", cfg["n_routed_experts"])
    return _kinds(cfg)[1] * cfg["hidden_size"] * e


def matmul_params_per_token(cfg):
    """Matmul parameters one token meets in a forward pass, the routers' apart
    (``router_params``), expecting even routing: ``num_experts_per_tok * held /
    router width`` held experts a token and expert layer (three eighths of one,
    here), each of two matrices; the shared expert whole.  The realised count is
    ``moe_assignments_held_pct.tok``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    mixer = d * (2 * h * p + 2 * bc + h) + h * p * d
    ha, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = d * hd * (2 * ha + 2 * hk)
    experts = 2 * d * (_held_per_token(cfg) * cfg["moe_intermediate_size"]
                       + cfg.get("n_shared_experts", 0)
                       * cfg["moe_shared_expert_intermediate_size"])
    n_m, n_e, n_a = _kinds(cfg)
    return n_m * mixer + n_e * experts + n_a * attention + d * v


def _ssd_products(cfg, rows):
    """Multiply-adds x 2 of one mixer's scan over ``rows`` positions, forward, by
    chunk length ``Q``: what the chunk algebra needs a position and head: ``C
    B^T`` once a group over the causal half of a chunk (``Q N`` shared by the
    heads of a group), the masked product with ``X`` over the half (``Q P``),
    the chunk's own state and the product with the state it was handed (``2 P N``
    each)."""
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    c = cfg["chunk_size"]
    per_position = 2.0 * (0.5 * c * n * cfg["n_groups"] / h + 0.5 * c * p + 2 * p * n)
    return per_position * rows * h


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter and token (forward, and backward by data and by
    weights), the routed experts at their expected load (see
    ``matmul_params_per_token``); the routers 6 too where they are trained and 2
    where ``router_trained`` is false; causal attention is half of the full score
    and value products, three times that with the backward; the state-space scan
    at ``_ssd_products`` forward and twice that backward.  The convolutions' taps,
    the norms and the recomputation inside the flash and the scan backward are
    not counted."""
    n_m, _n_e, n_a = _kinds(cfg)
    rows = units_per_step(cfg, mix, n_chips)
    att = 3.0 * 0.5 * 2.0 * mix["seq"] * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    per_token = 6.0 * matmul_params_per_token(cfg) \
        + (6.0 if cfg.get("router_trained", True) else 2.0) * router_params(cfg) \
        + att * n_a
    return per_token * rows + 3.0 * n_m * _ssd_products(cfg, rows)


def kernel_costs(cfg, mix, n_chips=1):
    """``{kernel name on the device: {"flops", "bytes", "calls"}}`` of one
    training step, for the kernels this configuration brings: what the
    algorithm needs from its shapes (2 per multiply-add; bf16 operands), not
    what an implementation recomputes or pads.

    * ``mxtpu.block.ssd`` (the scope of the chunked scan's XLA ops; no single
      kernel): ``_ssd_products`` forward and twice that backward; ``x``, ``B``,
      ``C`` (bf16) and ``dt`` (float32) read and ``y`` written once forward;
      those and ``y``'s cotangent read and the four gradients written backward.
    * ``mxtpu_flash_fwd_stream`` / ``mxtpu_flash_bwd_stream``: causal attention
      of 32 query heads over 2 key/value heads of 128, over the causal half:
      forward two products (QK^T, PV); backward four (dQ, dK, dV, dP; the
      recomputed scores are not needed work).  Q, K, V read, O written forward;
      Q, K, V, O, dO read (bf16) and dQ, dK, dV written (float32) backward.  One
      call's worth a layer, in however many calls the backward runs.
    * ``ragged-dot``: the two products of the ungated experts over the expected
      held assignments, forward, and by data and by weights backward: 6 grouped
      products an expert layer."""
    rows = units_per_step(cfg, mix, n_chips)
    seq, d = mix["seq"], cfg["hidden_size"]
    n_m, n_e, n_a = _kinds(cfg)
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    x_, bc_, dt_ = rows * h * p, rows * cfg["n_groups"] * cfg["ssm_state_size"], rows * h
    ssd_fwd = 2.0 * (2 * x_ + 2 * bc_) + 4.0 * dt_
    ssd_bwd = 2.0 * (2 * x_ + 2 * bc_) + 4.0 * dt_ + 2.0 * (x_ + 2 * bc_) + 4.0 * dt_
    ha, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    half = 0.5 * 2.0 * rows * seq * ha * hd             # one causal product
    q_, kv_ = rows * ha * hd, rows * hk * hd
    held = rows * _held_per_token(cfg)
    ff = cfg["moe_intermediate_size"]
    product = 2.0 * held * d * ff
    moved = 2.0 * (held * d + held * ff + cfg["n_routed_experts"] * d * ff)
    return {
        "mxtpu.block.ssd": {
            "calls": n_m, "flops": 3.0 * n_m * _ssd_products(cfg, rows),
            "bytes": n_m * (ssd_fwd + ssd_bwd)},
        "mxtpu_flash_fwd_stream": {
            "calls": n_a, "flops": n_a * 2 * half,
            "bytes": n_a * 2.0 * (2 * q_ + 2 * kv_)},
        "mxtpu_flash_bwd_stream": {
            "calls": n_a, "flops": n_a * 4 * half,
            "bytes": n_a * (2.0 * (3 * q_ + 2 * kv_) + 4.0 * (q_ + 2 * kv_))},
        "ragged-dot": {
            "calls": n_e * 6, "flops": n_e * 6 * product,
            "bytes": n_e * 6 * moved},
    }
