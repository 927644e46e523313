#!/usr/bin/env python
"""The expert layer's sum of a sorted buffer's rows into token order, alone:
``y[t] = sum over the buffer rows r of token t of weight[r] * rows[r]`` in
three forms, at the shapes the benchmark's six expert cells run it at.

- ``scatter_add``: ``jnp.zeros((T, d), float32).at[token].add(rows * weight)``,
  what ``mxnet_tpu/parallel/moe.py`` ran before PR 46;
- ``gathers``: slot ``(t, j)`` sits at sorted position ``inv[t k + j]``, so
  ``T k`` rows are gathered, masked and summed over ``j``;
- ``kernel``: ``mxtpu_moe_token_sum`` (``mxnet_tpu/ops/moe_token_sum.py``), at
  each of ``--blocks`` tokens a block.

Each is one jitted call from what ``topk_moe`` has at that point (bf16 rows,
float32 gates, the sort's ``order`` and ``inv``) to ``y`` in bf16, with
``--what unit`` the transpose of the dispatch gather (unit weights) instead.
The routing is drawn as the cells' is held (uniform scores, the first
``held`` experts here), so the buffer is about half filled.  Timed by device
events under one profiler session, the union of a call's op intervals, median
of ``--reps`` calls; ``gb_s`` divides the bytes the sum needs (the buffer's
rows read once, ``y`` written once) by that.

Usage (on the TPU host; prints one JSON line a form and shape):
    python tools/moe_sum_bench.py [--cells lfm2,sdar] [--blocks 128,256]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: cell: (T, k, held, E, d, rows summed): the buffer a step's window runs over
#: (``small_buffer_rows``; Kimi Linear's one size, ``buffer_rows``)
SHAPES = {
    "lfm2": (8192, 4, 8, 32, 2048, 16384),
    "sdar": (8192, 8, 16, 128, 2048, 16384),
    "trinity": (8192, 8, 8, 128, 2048, 8192),
    "nemotron": (8192, 6, 8, 128, 2688, 6144),
    "kimi": (8192, 8, 8, 256, 2304, 8192),
    "glm": (4096, 4, 8, 64, 2048, 4096),
}


def routing(rng, t, k, held, num_experts):
    """``(gates, local, order, inv)`` as ``topk_moe`` makes them."""
    import numpy as np
    idx = np.argsort(-rng.rand(t, num_experts), axis=1)[:, :k]
    local = np.where(idx < held, idx, held).astype(np.int32)
    order = np.argsort(local.reshape(-1), kind="stable").astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    return rng.rand(t, k).astype(np.float32), local, order, inv


def forms(n_rows, held, blocks, unit):
    """``{name: f(rows, gates, local, order, inv) -> y}``."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe_token_sum

    def filled_rows(local):
        return jnp.minimum(jnp.sum(local < held), n_rows)

    def scatter_add(rows, gates, local, order, inv):
        t, k = gates.shape
        head = order[:n_rows]
        filled = jnp.arange(n_rows) < filled_rows(local)
        weight = jnp.where(filled, 1.0 if unit else gates.reshape(-1)[head],
                           0.0)
        rows = jnp.where(filled[:, None], rows.astype(jnp.float32), 0.0) \
            * weight[:, None]
        return jnp.zeros((t, rows.shape[1]), jnp.float32).at[head // k].add(
            rows).astype(jnp.bfloat16)

    def gathers(rows, gates, local, order, inv):
        t, k = gates.shape
        pos = inv.reshape(t, k)
        there = (local < held) & (pos < filled_rows(local))
        back = rows[jnp.minimum(pos, n_rows - 1).reshape(-1)].reshape(
            t, k, -1).astype(jnp.float32)
        weight = jnp.where(there, 1.0 if unit else gates, 0.0)
        return jnp.sum(jnp.where(there[:, :, None], back, 0.0)
                       * weight[:, :, None], axis=1).astype(jnp.bfloat16)

    def kernel(block):
        def run(rows, gates, local, order, inv):
            t, k = gates.shape
            hit = (local[:, :, None] == jnp.arange(held)[None, None, :]) \
                & (inv.reshape(t, k) < filled_rows(local))[:, :, None]
            pos = jnp.sum(jnp.where(hit, inv.reshape(t, k, 1), 0), axis=1) \
                - (~jnp.any(hit, axis=1))
            weight = None if unit else jnp.sum(
                jnp.where(hit, gates[:, :, None], 0.0), axis=1)
            return moe_token_sum.token_sum(rows, pos, weight, block=block)
        return run

    out = {"scatter_add": scatter_add, "gathers": gathers}
    out.update({"kernel_b%d" % b: kernel(b) for b in blocks})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--what", default="weighted,unit")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None, help="also append the lines here")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from kda_bench import busy_ns, device_events, split_calls
    if jax.default_backend() != "tpu":
        sys.exit("moe_sum_bench: no TPU attached (backend %s); a time from "
                 "another device is not these forms' time"
                 % jax.default_backend())
    blocks = [int(b) for b in args.blocks.split(",")]
    for cell in args.cells.split(","):
        t, k, held, num_experts, d, n_rows = SHAPES[cell]
        rng = np.random.RandomState(len(cell) + t + k)
        gates, local, order, inv = routing(rng, t, k, held, num_experts)
        rows = jnp.asarray(rng.normal(0, 1, (n_rows, d)), jnp.bfloat16)
        operands = (rows,) + tuple(jnp.asarray(a)
                                   for a in (gates, local, order, inv))
        filled = min(int((local < held).sum()), n_rows)
        moved = 2.0 * d * (n_rows + t)
        for what in args.what.split(","):
            first = None
            for name, form in forms(n_rows, held, blocks,
                                    what == "unit").items():
                line = {"cell": cell, "what": what, "form": name,
                        "tokens": t, "k": k, "held": held, "d": d,
                        "rows": n_rows, "filled": filled}
                try:
                    fn = jax.jit(form)
                    y = jax.block_until_ready(fn(*operands))    # compiles
                    with tempfile.TemporaryDirectory() as trace_dir:
                        jax.profiler.start_trace(trace_dir)
                        for _ in range(args.reps):
                            jax.block_until_ready(fn(*operands))
                        jax.profiler.stop_trace()
                        events = device_events(trace_dir)
                    calls = split_calls(events, args.reps)
                    ms = statistics.median(busy_ns(c) for c in calls) / 1e6
                    own = statistics.median(
                        sum(e - s for s, e, n in c if "moe_token_sum" in n)
                        for c in calls) / 1e6
                    y = np.asarray(y, np.float32)
                    first = y if first is None else first
                    line.update(ms=round(ms, 4), kernel_ms=round(own, 4),
                                device_ops=statistics.median(
                                    len(c) for c in calls),
                                gb_s=round(moved / ms / 1e6, 1),
                                max_diff_vs_scatter_add=float(
                                    np.abs(y - first).max()))
                except Exception as e:  # mxlint: allow-broad-except(a block whose accumulator and windows pass the default of VMEM is refused by the chip's compiler with its own error type: the table says so on that line and goes on)
                    line["error"] = str(e).splitlines()[0][:200]
                print(json.dumps(line), flush=True)
                if args.out:
                    os.makedirs(os.path.dirname(args.out), exist_ok=True)
                    with open(args.out, "a") as f:
                        f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
