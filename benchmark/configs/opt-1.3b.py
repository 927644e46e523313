"""How the system is asked for the OPT decoder, and what one step needs.

The graph is ``examples/transformer/train_lm.py`` ``gpt_symbol`` (the repo's
transformer) at OPT-1.3B's published widths; the trainer's arguments are
``build_bench_trainer``'s.  The operation count is the benchmark's own."""
from __future__ import annotations

import os
import sys


def build(cfg, mix, n_chips):
    """(symbol, data_shapes, label_shapes) for ``ShardedTrainer``."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(root, "examples", "transformer"))
    from train_lm import gpt_symbol
    seq = mix["seq"]
    if seq != cfg["max_position_embeddings"]:
        raise ValueError("the graph's position table is the sequence length: %d != %d"
                         % (seq, cfg["max_position_embeddings"]))
    batch = mix["batch_per_chip"] * n_chips
    net = gpt_symbol(cfg["vocab_size"], seq, cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_hidden_layers"], dropout=cfg["dropout"], attention="flash")
    return net, {"data": (batch, seq)}, {"softmax_label": (batch, seq)}


def units_per_step(cfg, mix, n_chips):
    """Tokens one step trains."""
    return mix["batch_per_chip"] * n_chips * mix["seq"]


def step_flops(cfg, mix, n_chips):
    """Floating-point operations one training step needs, 2 per multiply-add.

    6 per matmul parameter and token (forward, and backward by data and by
    weights); causal attention is half of the full score and value products:
    2*S*d forward per token and layer, three times that with the backward.
    Recomputation inside the flash backward is not counted."""
    d, f, v, n = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"], cfg["num_hidden_layers"]
    matmul_params = n * (4 * d * d + 2 * d * f) + d * v
    per_token = 6.0 * matmul_params + 6.0 * mix["seq"] * d * n
    return per_token * units_per_step(cfg, mix, n_chips)
