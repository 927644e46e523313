"""How the system is asked for the Trinity (afmoe) decoder, and what one step needs.

The graph is ``mxnet_tpu.models.afmoe.get_symbol`` from the configuration file's
own keys; the trainer's arguments are the file's ``optimizer`` and ``trainer``.
The operation counts are the benchmark's own."""
from __future__ import annotations

# here and not in ``build``: a program without the model fails when the cell is
# looked up, before the reference's first steps are computed for nothing
from mxnet_tpu.models import afmoe


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    seq = int(mix["seq"])
    batch = mix["batch_per_chip"] * n_chips
    return (afmoe.get_symbol(cfg, seq), {"data": (batch, seq)},
            {"softmax_label": (batch, seq)})


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains."""
    return mix["batch_per_chip"] * n_chips * mix["seq"]


def _kinds(cfg):
    """(sliding layers, full layers, dense layers, expert layers) built."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    n_sliding = sum(1 for k in kinds if k == "sliding_attention")
    n_dense = min(len(kinds), cfg["num_dense_layers"])
    return n_sliding, len(kinds) - n_sliding, n_dense, len(kinds) - n_dense


def router_params(cfg):
    """The routers' parameters one token meets: hidden x the router's width,
    an expert layer."""
    e = cfg.get("router_num_experts", cfg["num_experts"])
    return _kinds(cfg)[3] * cfg["hidden_size"] * e


def matmul_params_per_token(cfg):
    """Matmul parameters one token meets in a forward pass, the routers' apart
    (``router_params``), expecting even routing: ``num_experts_per_tok * held /
    router width`` held experts a token and expert layer (half of one, here);
    the shared expert whole.  The realised count is
    ``moe_assignments_held_pct.tok``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    att = 3 * d * hq * hd + 2 * d * hk * hd          # q, gate, o; k, v
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held_per_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    n_sliding, n_full, n_dense, n_moe = _kinds(cfg)
    return (n_sliding + n_full) * att + n_dense * dense \
        + n_moe * (cfg.get("num_shared_experts", 0) + held_per_token) * expert + d * v


def score_pairs(seq, window=None):
    """(query, key) pairs one head's softmax runs over in a sequence of ``seq``:
    the causal half, the diagonal included; under ``window`` the band ``0 <= t -
    j < window``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _attention_flops(cfg, mix, n_chips, window):
    """One product over the needed pairs of every head, 2 per multiply-add."""
    batch = mix["batch_per_chip"] * n_chips
    return 2.0 * batch * cfg["num_attention_heads"] * cfg["head_dim"] \
        * score_pairs(mix["seq"], window)


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter and token (forward, and backward by data and by
    weights), the routed experts at their expected load (see
    ``matmul_params_per_token``); the routers 6 too where they are trained and 2
    where ``router_trained`` is false; attention 2 products forward and 4
    backward over the pairs a layer needs: the causal half on a full layer, the
    window's band, not the causal half, on a sliding one.  The norms, the gate's
    sigmoid, rotary embedding and the recomputation inside the flash backward
    are not counted."""
    n_sliding, n_full = _kinds(cfg)[:2]
    rows = units_per_step(cfg, mix, n_chips)
    per_token = 6.0 * matmul_params_per_token(cfg) \
        + (6.0 if cfg.get("router_trained", True) else 2.0) * router_params(cfg)
    return per_token * rows \
        + 6.0 * n_full * _attention_flops(cfg, mix, n_chips, None) \
        + 6.0 * n_sliding * _attention_flops(cfg, mix, n_chips, cfg["sliding_window"])


def kernel_costs(cfg, mix, n_chips=1):
    """``{kernel name on the device: {"flops", "bytes", "calls"}}`` of one
    training step, for the kernels this configuration brings: what the
    algorithm needs from its shapes (2 per multiply-add; bf16 operands), not
    what an implementation recomputes or pads.  ``calls`` is how many instances
    a step runs; flops and bytes are of all of them together.

    * ``mxtpu_flash_fwd_window`` / ``mxtpu_flash_bwd_window``: the sliding
      layers' attention over the band's pairs (``score_pairs`` under
      ``sliding_window``): 2 products forward and 4 backward (the backward's
      recomputed scores are not needed work); Q, K, V read and O written (bf16)
      forward; Q, K, V, O, dO read (bf16) and dQ, dK, dV written (float32)
      backward.
    * ``mxtpu_flash_fwd_stream`` / ``mxtpu_flash_bwd_stream``: the full layers'
      over the causal half, ``num_attention_heads`` query heads over
      ``num_key_value_heads`` key/value heads of ``head_dim``; the same traffic.
    * ``ragged-dot``: the three products of the gated experts over the expected
      held assignments, forward, and by data and by weights backward: 9 grouped
      products an expert layer; each reads its two operands and writes its
      result once."""
    rows = units_per_step(cfg, mix, n_chips)
    d = cfg["hidden_size"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n_sliding, n_full, _d, n_moe = _kinds(cfg)
    band = _attention_flops(cfg, mix, n_chips, cfg["sliding_window"])
    half = _attention_flops(cfg, mix, n_chips, None)
    qo, kv = rows * hq * hd, rows * hk * hd
    fwd_bytes = 2.0 * (2 * qo + 2 * kv)
    bwd_bytes = 2.0 * (3 * qo + 2 * kv) + 4.0 * (qo + 2 * kv)
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held = rows * cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    ff = cfg["moe_intermediate_size"]
    n_w = cfg["num_experts"] * d * ff
    product = 2.0 * held * d * ff
    moved = 2.0 * (held * d + held * ff + n_w)       # bf16 operands and result
    return {
        "mxtpu_flash_fwd_window": {
            "calls": n_sliding, "flops": n_sliding * 2 * band,
            "bytes": n_sliding * fwd_bytes},
        "mxtpu_flash_bwd_window": {
            "calls": n_sliding, "flops": n_sliding * 4 * band,
            "bytes": n_sliding * bwd_bytes},
        "mxtpu_flash_fwd_stream": {
            "calls": n_full, "flops": n_full * 2 * half, "bytes": n_full * fwd_bytes},
        "mxtpu_flash_bwd_stream": {
            "calls": n_full, "flops": n_full * 4 * half, "bytes": n_full * bwd_bytes},
        "ragged-dot": {
            "calls": n_moe * 9, "flops": n_moe * 9 * product,
            "bytes": n_moe * 9 * moved},
    }
