"""How the system is asked for GLM-4.7-Flash with its multi-token-prediction
module, and what one step needs.

The graph is ``mxnet_tpu.models.glm4_moe_lite.get_symbol`` from the configuration
file's own keys; the trainer's arguments are the file's ``optimizer`` and
``trainer``.  The operation counts are the benchmark's own."""
from __future__ import annotations

# at import, not inside ``build``: a program without the model (the parent of
# the PR that added it) fails the cell at once, before the reference's minutes
try:
    from mxnet_tpu.models import glm4_moe_lite
except ImportError as e:
    raise SystemExit("benchmark: this program cannot run the configuration "
                     "glm-4.7-flash: %s" % e)


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    seq = int(mix["seq"])
    batch = mix["batch_per_chip"] * n_chips
    return (glm4_moe_lite.get_symbol(cfg, seq), {"data": (batch, seq)},
            {"softmax_label": (batch, seq)})


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains: T a sequence, not the 2 T rows that go through the
    two heads."""
    return mix["batch_per_chip"] * n_chips * mix["seq"]


def _kinds(cfg):
    """(latent-attention layers, dense layers, expert layers, modules) built, the
    multi-token-prediction module's layer counted among the first and the third."""
    n, mtp = cfg["num_hidden_layers"], int(cfg.get("num_nextn_predict_layers", 0))
    n_dense = min(n, cfg["first_k_dense_replace"])
    return n + mtp, n_dense, n - n_dense + mtp, mtp


def router_params(cfg):
    """The routers' parameters one token meets: hidden x the router's width, an
    expert layer."""
    e = cfg.get("router_num_experts", cfg["n_routed_experts"])
    return _kinds(cfg)[2] * cfg["hidden_size"] * e


def mla_params(cfg):
    """Matmul parameters of one latent-attention layer: ``W_qa``, ``W_qb``,
    ``W_kva``, ``W_kvb``, ``W_o``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope) \
        + d * (cfg["kv_lora_rank"] + rope) + cfg["kv_lora_rank"] * h * (nope + dv) + h * dv * d


def matmul_params_per_token(cfg):
    """Matmul parameters one token meets in a forward pass, the routers' apart
    (``router_params``), expecting even routing: ``num_experts_per_tok * held /
    router width`` held experts a token and expert layer (half of one, here); the
    shared expert whole; the head twice and ``W_eh`` once with the module.  The
    realised count is ``moe_assignments_held_pct.tok``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    e = cfg.get("router_num_experts", cfg["n_routed_experts"])
    held_per_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / e
    n_mla, n_dense, n_moe, mtp = _kinds(cfg)
    return n_mla * mla_params(cfg) + n_dense * 3 * d * cfg["intermediate_size"] \
        + n_moe * (cfg.get("n_shared_experts", 0) + held_per_token) * expert \
        + (1 + mtp) * d * v + mtp * 2 * d * d


def attention_flops_per_token(cfg, mix):
    """One causal latent-attention layer, forward and backward, a token: half of
    the full score (``dk`` wide) and value (``dv`` wide) products, three times
    that with the backward."""
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 3.0 * 0.5 * 2.0 * mix["seq"] * cfg["num_attention_heads"] * (dk + cfg["v_head_dim"])


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter and token (forward, and backward by data and by
    weights), the routed experts at their expected load (see
    ``matmul_params_per_token``); the routers 6 too where they are trained and 2
    where ``router_trained`` is false; causal latent attention at
    ``attention_flops_per_token`` a layer, the module's among them.  The rotary
    turns, the norms, the two log-softmaxes and every recomputation (inside the
    flash backward, of the log-softmaxes, of the expert layers' branches) are not
    counted."""
    rows = units_per_step(cfg, mix, n_chips)
    per_token = 6.0 * matmul_params_per_token(cfg) \
        + (6.0 if cfg.get("router_trained", True) else 2.0) * router_params(cfg) \
        + attention_flops_per_token(cfg, mix) * _kinds(cfg)[0]
    return per_token * rows


def kernel_costs(cfg, mix, n_chips=1):
    """``{kernel name on the device: {"flops", "bytes", "calls"}}`` of one
    training step, for the kernels this configuration runs: what the algorithm
    needs from its shapes (2 per multiply-add; bf16 operands), not what an
    implementation recomputes or pads.

    * ``mxtpu_flash_fwd_stream`` / ``mxtpu_flash_bwd_stream``: causal latent
      attention, scores over ``dk`` = 256 and values of ``dv`` = 256, over the
      causal half: forward one product of each width (QK^T, PV); backward two of
      each (dQ, dK over ``dk``; dV, dP over ``dv``; the recomputed scores are not
      needed work).  Q, K (``dk``) and V (``dv``) read, O written forward; Q, K,
      V, O, dO read (bf16) and dQ, dK, dV written (float32) backward.
    * ``ragged-dot``: the three products of the gated experts over the expected
      held assignments, forward, and by data and by weights backward: 9 grouped
      products an expert layer."""
    rows = units_per_step(cfg, mix, n_chips)
    seq, d = mix["seq"], cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n_mla, _dense, n_moe, _mtp = _kinds(cfg)
    half = 0.5 * 2.0 * rows * seq * h                 # one causal product a unit of width
    q_, v_ = rows * h * dk, rows * h * dv
    e = cfg.get("router_num_experts", cfg["n_routed_experts"])
    held = rows * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / e
    ff = cfg["moe_intermediate_size"]
    product = 2.0 * held * d * ff
    moved = 2.0 * (held * d + held * ff + cfg["n_routed_experts"] * d * ff)
    return {
        "mxtpu_flash_fwd_stream": {
            "calls": n_mla, "flops": n_mla * half * (dk + dv),
            "bytes": n_mla * 2.0 * (2 * q_ + 2 * v_)},
        "mxtpu_flash_bwd_stream": {
            "calls": n_mla, "flops": n_mla * half * 2 * (dk + dv),
            "bytes": n_mla * (2.0 * (2 * q_ + 3 * v_) + 4.0 * (2 * q_ + v_))},
        "ragged-dot": {
            "calls": n_moe * 9, "flops": n_moe * 9 * product,
            "bytes": n_moe * 9 * moved},
    }
