#!/usr/bin/env python
"""Transformer-LM MFU benchmark at MXU-saturating scale.

VERDICT r3 #2: ResNet-50's MFU ceiling hides behind XLA's opaque conv
custom calls; a transformer is matmul-bound, so its MFU is the
framework's true matmul story.  This artifact trains a GPT-style LM
(default d_model=1024, 12 layers, seq 1024, bf16, flash attention)
through the fused ShardedTrainer path with `run_steps` scan chaining,
and reports tokens/s AND model FLOPs utilization with the FLOP
accounting printed term by term.

FLOP accounting (per token, forward; train = 3x forward for the
standard fwd + 2x bwd matmul count — the methodology of the PaLM MFU
appendix / the scaling book, reference docs/how_to/perf.md:161-193 for
the measurement discipline):

  per layer : qkv 6*d^2        (2*d*3d)
              proj 2*d^2
              ffn  16*d^2      (two 2*d*4d matmuls)
              attn 4*S*d       (QK^T and AV, FULL panel — the causal
                                kernel computes the whole panel, and
                                non-causal accounting is the standard
                                MFU convention)
  head      : 2*d*V
  (embedding lookups, layernorms, softmax: not counted — convention)

Usage (real chip):
    python tools/transformer_mfu.py            # prints one JSON line
    python tools/transformer_mfu.py --json-only
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))


def flops_per_token(d, n_layers, seq, vocab):
    per_layer = 24 * d * d + 4 * seq * d
    head = 2 * d * vocab
    fwd = n_layers * per_layer + head
    return {"per_layer": per_layer, "head": head, "fwd": fwd,
            "train": 3 * fwd}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--vocab", type=int, default=16384)
    p.add_argument("--steps", type=int, default=8,
                   help="scan-chained steps per timed program")
    p.add_argument("--repeats", type=int, default=3,
                   help="timed run_steps launches (best is reported)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--peak-tflops", type=float, default=197.0,
                   help="chip bf16 peak (v5e: 197)")
    p.add_argument("--json-only", action="store_true")
    a = p.parse_args()

    from mxnet_tpu.base import use_compile_cache
    from train_lm import build_bench_trainer
    use_compile_cache()

    def note(msg):
        if not a.json_only:
            print("[mfu] " + msg, flush=True)

    note("building trainer (param upload rides the host link)...")
    trainer, batch = build_bench_trainer(
        vocab=a.vocab, seq=a.seq, d_model=a.d_model, heads=a.heads,
        layers=a.layers, batch=a.batch, dtype=a.dtype)

    # compile + warm
    note("compiling the %d-step scan + first run..." % a.steps)
    losses = trainer.run_steps(batch, a.steps)
    assert np.isfinite(float(np.asarray(losses)[-1]))
    note("measuring...")

    times = []
    for _ in range(a.repeats):
        t0 = time.perf_counter()
        losses = trainer.run_steps(batch, a.steps)
        last = float(np.asarray(losses)[-1])   # VALUE fetch ends the timed region
        times.append(time.perf_counter() - t0)
    assert np.isfinite(last), last
    dt = min(times) / a.steps

    tokens = a.batch * a.seq
    acct = flops_per_token(a.d_model, a.layers, a.seq, a.vocab)
    step_tflop = acct["train"] * tokens / 1e12
    tflops = step_tflop / dt
    mfu = tflops / a.peak_tflops
    tok_s = tokens / dt

    n_params = sum(int(np.prod(v.shape)) for v in trainer.params.values())
    if not a.json_only:
        print("config: d=%d L=%d H=%d S=%d B=%d V=%d dtype=%s  "
              "params=%.1fM" % (a.d_model, a.layers, a.heads, a.seq,
                                a.batch, a.vocab, a.dtype, n_params / 1e6))
        print("flops/token: layer=%s x%d  head=%s  fwd=%s  train=%s"
              % ("{:,}".format(acct["per_layer"]), a.layers,
                 "{:,}".format(acct["head"]),
                 "{:,}".format(acct["fwd"]),
                 "{:,}".format(acct["train"])))
        print("step: %.2f ms  (%d-step scan, best of %d; loss %.4f)"
              % (dt * 1e3, a.steps, a.repeats, last))
    print(json.dumps({
        "metric": "transformer_lm_mfu",
        "value": round(mfu * 100, 2), "unit": "%",
        "tokens_per_sec": round(tok_s, 1),
        "tflops_per_sec": round(tflops, 2),
        "peak_tflops": a.peak_tflops,
        "step_ms": round(dt * 1e3, 3),
        "config": {"d_model": a.d_model, "layers": a.layers,
                   "heads": a.heads, "seq": a.seq, "batch": a.batch,
                   "vocab": a.vocab, "dtype": a.dtype,
                   "params_m": round(n_params / 1e6, 1)},
    }))


if __name__ == "__main__":
    main()
