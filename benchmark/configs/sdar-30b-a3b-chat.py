"""How the system is asked for the SDAR expert decoder under block-diffusion
training, and what one step needs.

The graph is ``mxnet_tpu.models.sdar_moe.get_symbol`` from the configuration
file's own keys; the trainer's arguments are the file's ``optimizer`` and
``trainer``.  The operation counts are the benchmark's own.

A step trains ``document`` tokens a document.  The method puts ``2 * document``
rows through every layer (a clean and a noised copy) and ``document`` rows
through the head; the doubled rows are the method's cost and are not counted as
tokens."""
from __future__ import annotations

# here and not in ``build``: a program without the model fails when the cell is
# looked up, before the reference's first steps are computed for nothing
from mxnet_tpu.models import sdar_moe


def _document(cfg, mix):
    """Tokens of one document; the mix's ``seq`` is the batch row that carries
    it: the document, as many mask draws, and a level draw a block."""
    doc = int(mix["document"])
    want = 2 * doc + doc // int(cfg["block_length"])
    if int(mix["seq"]) != want or doc % int(cfg["block_length"]):
        raise ValueError(
            "traffic mix: seq %d, but a document of %d tokens in blocks of %d needs "
            "seq == 2 * document + document / block_length = %d (the document's ids, "
            "its mask draws and its blocks' level draws)"
            % (mix["seq"], doc, cfg["block_length"], want))
    return doc


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``.  The graph
    reads ``data`` alone; ``softmax_label`` is declared because the harness
    stages it with every token batch."""
    doc, seq = _document(cfg, mix), int(mix["seq"])
    batch = mix["batch_per_chip"] * n_chips
    return (sdar_moe.get_symbol(cfg, doc), {"data": (batch, seq)},
            {"softmax_label": (batch, seq)})


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains: the documents' own, not the rows of both copies."""
    return mix["batch_per_chip"] * n_chips * _document(cfg, mix)


def layer_rows(cfg, mix, n_chips):
    """Rows one step puts through every layer: both copies of every document."""
    return 2 * units_per_step(cfg, mix, n_chips)


def router_params(cfg):
    """The routers' parameters one row meets: hidden x the router's width, a layer."""
    return cfg["num_hidden_layers"] * cfg["hidden_size"] \
        * cfg.get("router_num_experts", cfg["num_experts"])


def layer_params_per_row(cfg):
    """Matmul parameters one row meets in the layers in a forward pass, the
    routers' apart, expecting even routing: ``num_experts_per_tok * held /
    router width`` held experts a row and layer (one, here).  The realised
    count is ``moe_assignments_held_pct.tok``."""
    d = cfg["hidden_size"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    att = 2 * d * hq * hd + 2 * d * hk * hd              # q, o; k, v
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held_per_row = cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    return cfg["num_hidden_layers"] * (att + held_per_row * 3 * d
                                       * cfg["moe_intermediate_size"])


def score_pairs(doc, block):
    """(query, key) pairs one head's softmax runs over for a document of ``doc``
    tokens in blocks of ``block``: the clean copy over its blocks' prefix
    (``sum_b B * (b + 1) B``), the noised copy over its own block and the clean
    blocks before it (``sum_b B * (B + b B)``): ``doc^2 + doc * block``."""
    return doc * doc + doc * block


def _attention_flops(cfg, mix, n_chips):
    """One product over the needed pairs of every head, 2 per multiply-add."""
    return 2.0 * mix["batch_per_chip"] * n_chips * cfg["num_attention_heads"] \
        * cfg["head_dim"] * score_pairs(_document(cfg, mix), cfg["block_length"])


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter of the layers and row (forward, and backward by data
    and by weights) over the ``2 * document`` rows of both copies, the experts at
    their expected load; the routers 6 too where they are trained and 2 where
    ``router_trained`` is false; the head 6 over the ``document`` noised rows
    alone; attention 2 products forward and 4 backward over the mask's own pairs
    (``score_pairs``), every layer.  The norms, rotary embedding, the noising and
    the recomputation inside the flash backward are not counted, nor is the last
    layer's work on the clean rows taken off, though nothing reads it."""
    rows = layer_rows(cfg, mix, n_chips)
    router = (6.0 if cfg.get("router_trained", True) else 2.0) * router_params(cfg)
    head = 6.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return (6.0 * layer_params_per_row(cfg) + router) * rows \
        + head * units_per_step(cfg, mix, n_chips) \
        + 6.0 * cfg["num_hidden_layers"] * _attention_flops(cfg, mix, n_chips)


def kernel_costs(cfg, mix, n_chips=1):
    """``{kernel name on the device: {"flops", "bytes", "calls"}}`` of one
    training step, for the kernels this configuration brings: what the
    algorithm needs from its shapes (2 per multiply-add; bf16 operands), not
    what an implementation recomputes or pads.  ``calls`` is how many instances
    a step runs; flops and bytes are of all of them together.

    * ``mxtpu_flash_fwd_blockdiff`` / ``mxtpu_flash_bwd_blockdiff``: every
      layer's attention over the mask's own pairs (``score_pairs``),
      ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
      heads of ``head_dim``: 2 products forward and 4 backward (the backward's
      recomputed scores are not needed work); Q, K, V read and O written (bf16)
      forward; Q, K, V, O, dO read (bf16) and dQ, dK, dV written (float32)
      backward, over the ``2 * document`` rows.
    * ``ragged-dot``: the three products of the gated experts over the expected
      held assignments, forward, and by data and by weights backward: 9 grouped
      products a layer; each reads its two operands and writes its result once."""
    rows = layer_rows(cfg, mix, n_chips)
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pairs = _attention_flops(cfg, mix, n_chips)
    qo, kv = rows * hq * hd, rows * hk * hd
    fwd_bytes = 2.0 * (2 * qo + 2 * kv)
    bwd_bytes = 2.0 * (3 * qo + 2 * kv) + 4.0 * (qo + 2 * kv)
    e = cfg.get("router_num_experts", cfg["num_experts"])
    held = rows * cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    ff = cfg["moe_intermediate_size"]
    product = 2.0 * held * d * ff
    moved = 2.0 * (held * d + held * ff + cfg["num_experts"] * d * ff)
    return {
        "mxtpu_flash_fwd_blockdiff": {
            "calls": n, "flops": n * 2 * pairs, "bytes": n * fwd_bytes},
        "mxtpu_flash_bwd_blockdiff": {
            "calls": n, "flops": n * 4 * pairs, "bytes": n * bwd_bytes},
        "ragged-dot": {
            "calls": n * 9, "flops": n * 9 * product, "bytes": n * 9 * moved},
    }
