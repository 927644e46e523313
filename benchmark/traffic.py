"""The one general generator of a cell's input: reads a mix file's parameters
and the configuration's ``input`` kind, and makes the host batch from the seed.

A training mix is a fixed batch staged once and trained in chains; its
parameters are ``batch_per_chip``, ``seq`` (token input), ``chain`` and ``mesh``.
Every row of a batch differs."""
from __future__ import annotations

import numpy as np


def host_batch(cfg, mix, n_chips, seed):
    rng = np.random.default_rng(int(seed))
    rows = int(mix["batch_per_chip"]) * n_chips
    if cfg["input"] == "images":
        c, h, w = cfg["image_shape"]
        x = rng.uniform(-1.0, 1.0, (rows, c, h, w)).astype(np.float32)
        y = rng.integers(0, cfg["num_classes"], rows).astype(np.float32)
        return {"data": x, "softmax_label": y}
    if cfg["input"] == "tokens":
        ids = rng.integers(0, cfg["vocab_size"], (rows, int(mix["seq"]) + 1))
        return {"data": ids[:, :-1].astype(np.float32),
                "softmax_label": ids[:, 1:].astype(np.float32)}
    raise ValueError("unknown input kind %r" % (cfg["input"],))
