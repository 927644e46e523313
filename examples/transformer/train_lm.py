"""Decoder-only transformer language model (GPT-mini).

Beyond-reference capability demo: the 0.10.1 reference predates
attention, but this framework treats long-context as first-class —
``_contrib_FlashAttention`` (Pallas block-streaming kernel on TPU, jnp
fallback elsewhere), ``LayerNorm``, and (for multi-chip) the ring
attention in ``mxnet_tpu.parallel.sequence``.  This example trains a
causal LM through the standard Module API on a synthetic Markov corpus,
where the learnable structure gives a crisp perplexity target.

    python train_lm.py --epochs 5
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def transformer_block(x, d_model, n_heads, prefix,
                      ffn_mult=4, dropout=0.1, attention="flash"):
    """Pre-norm block: x + Attn(LN(x)); x + FFN(LN(x)).

    attention="ring" swaps in ``_contrib_RingAttention`` — identical
    math single-chip, and under ShardedTrainer(sequence_parallel=True)
    the sequence dim shards over the mesh and K/V ride the ICI ring.
    """
    h = mx.sym.LayerNorm(x, name=prefix + "_ln1")
    qkv = mx.sym.FullyConnected(h, num_hidden=3 * d_model, flatten=False,
                                name=prefix + "_qkv")
    qkv = mx.sym.Reshape(qkv, shape=(0, 0, 3, n_heads, -1))
    # each slice: (B, S, 1, H, hd) -> (B, S, H, hd), the attention layout
    q = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=2, begin=0, end=1),
                       shape=(0, 0, -3, -2))
    k = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=2, begin=1, end=2),
                       shape=(0, 0, -3, -2))
    v = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=2, begin=2, end=3),
                       shape=(0, 0, -3, -2))
    attn_op = (mx.sym._contrib_RingAttention if attention == "ring"
               else mx.sym._contrib_FlashAttention)
    att = attn_op(q, k, v, causal=True, name=prefix + "_attn")
    att = mx.sym.Reshape(att, shape=(0, 0, -3))
    att = mx.sym.FullyConnected(att, num_hidden=d_model, flatten=False,
                                name=prefix + "_proj")
    if dropout > 0:
        att = mx.sym.Dropout(att, p=dropout)
    x = x + att

    h = mx.sym.LayerNorm(x, name=prefix + "_ln2")
    h = mx.sym.FullyConnected(h, num_hidden=ffn_mult * d_model,
                              flatten=False, name=prefix + "_ffn1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=d_model, flatten=False,
                              name=prefix + "_ffn2")
    if dropout > 0:
        h = mx.sym.Dropout(h, p=dropout)
    return x + h


def gpt_symbol(vocab_size, seq_len, d_model=128, n_heads=4, n_layers=2,
               dropout=0.1, attention="flash"):
    with mx.telemetry.span("model.build", category="model", model="gpt"):
        data = mx.sym.Variable("data")              # (batch, seq)
        label = mx.sym.Variable("softmax_label")
        tok = mx.sym.Embedding(data, input_dim=vocab_size,
                               output_dim=d_model, name="tok_embed")
        # learned positional embedding, looked up with a constant iota
        pos_ids = mx.sym.arange(start=0, stop=seq_len, name="pos_ids")
        pos = mx.sym.Embedding(pos_ids, input_dim=seq_len,
                               output_dim=d_model, name="pos_embed")
        x = mx.sym.broadcast_add(tok, mx.sym.expand_dims(pos, axis=0))
        for i in range(n_layers):
            x = transformer_block(x, d_model, n_heads, "block%d" % i,
                                  dropout=dropout, attention=attention)
        x = mx.sym.LayerNorm(x, name="ln_f")
        x = mx.sym.Reshape(x, shape=(-1, d_model))
        logits = mx.sym.FullyConnected(x, num_hidden=vocab_size,
                                       name="lm_head")
        label = mx.sym.Reshape(label, shape=(-1,))
        return mx.sym.SoftmaxOutput(logits, label=label, name="softmax")


def build_bench_trainer(vocab=16384, seq=1024, d_model=1024, heads=16,
                        layers=12, batch=16, dtype="bfloat16"):
    """(fused trainer, staged synthetic batch) at benchmark scale — ONE
    definition shared by tools/transformer_mfu.py and tools/xprof_top.py
    so the profiled program and the benchmarked program are identical
    by construction."""
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh

    net = gpt_symbol(vocab, seq, d_model, heads, layers, dropout=0.0,
                     attention="flash")
    trainer = ShardedTrainer(
        net, build_mesh(n_devices=1),
        data_shapes={"data": (batch, seq)},
        label_shapes={"softmax_label": (batch, seq)},
        optimizer="adam", learning_rate=1e-4, dtype=dtype)
    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, (batch, seq)).astype("f")
    staged = trainer.put_batch({"data": x,
                                "softmax_label": np.roll(x, -1, 1).copy()})
    return trainer, staged


def markov_batches(n_tokens, vocab_size, seq_len, batch_size, seed=0):
    rng = np.random.RandomState(seed)
    trans = np.random.RandomState(42).dirichlet(
        np.ones(vocab_size) * 0.05, size=vocab_size)
    toks = [rng.randint(vocab_size)]
    for _ in range(n_tokens):
        toks.append(rng.choice(vocab_size, p=trans[toks[-1]]))
    toks = np.array(toks)
    n_seq = (len(toks) - 1) // seq_len
    x = toks[: n_seq * seq_len].reshape(n_seq, seq_len)
    y = toks[1: n_seq * seq_len + 1].reshape(n_seq, seq_len)
    return (mx.io.NDArrayIter(x.astype("f"), y.astype("f"), batch_size,
                              shuffle=True),
            trans)


def train(epochs=5, batch_size=16, seq_len=64, vocab_size=64,
          d_model=64, n_heads=4, n_layers=2, ctx=None):
    ctx = ctx or mx.context.current_context()
    it, trans = markov_batches(40000, vocab_size, seq_len, batch_size)
    net = gpt_symbol(vocab_size, seq_len, d_model, n_heads, n_layers)
    mod = mx.module.Module(net, context=ctx)
    mod.fit(it, num_epoch=epochs,
            initializer=mx.init.Xavier(),
            optimizer="adam", optimizer_params={"learning_rate": 3e-3},
            eval_metric=mx.metric.Perplexity(None),
            batch_end_callback=mx.callback.Speedometer(batch_size, 20))
    ppl = mod.score(it, mx.metric.Perplexity(None))[0][1]
    # entropy floor of the generating chain (best achievable ppl)
    stat = np.linalg.matrix_power(trans.T, 50)[:, 0]
    h = -np.sum(stat[:, None] * trans * np.log(np.maximum(trans, 1e-12)))
    logging.info("train perplexity %.2f (chain floor %.2f, vocab %d)",
                 ppl, float(np.exp(h)), vocab_size)
    return ppl, float(np.exp(h))


def train_sequence_parallel(sp=2, steps=120, batch_size=8, seq_len=64,
                            vocab_size=64, d_model=64, n_heads=4,
                            n_layers=2):
    """Sequence-parallel training: the sequence dim sharded ``sp`` ways
    over the mesh 'model' axis, attention via ``_contrib_RingAttention``
    (K/V blocks rotate over the ICI ring; per-device attention memory is
    O(seq/sp)).  Data parallelism rides the 'data' axis at the same
    time when the mesh has more devices than ``sp``.

    Returns (first_loss, last_loss) of the fused training run.
    """
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh

    net = gpt_symbol(vocab_size, seq_len, d_model, n_heads, n_layers,
                     dropout=0.0, attention="ring")
    mesh = build_mesh(tp=sp)  # 'model' axis carries the sequence shards
    trainer = ShardedTrainer(
        net, mesh,
        data_shapes={"data": (batch_size, seq_len)},
        label_shapes={"softmax_label": (batch_size, seq_len)},
        optimizer="adam", learning_rate=3e-3,
        sequence_parallel=True)

    it, _trans = markov_batches(steps * batch_size * seq_len + seq_len,
                                vocab_size, seq_len, batch_size)
    losses = []
    for epoch in range(2):
        it.reset()
        for b in it:
            losses.append(float(trainer.step(
                {"data": b.data[0].asnumpy(),
                 "softmax_label": b.label[0].asnumpy()})))
            if len(losses) >= steps:
                break
        if len(losses) >= steps:
            break
    logging.info("sequence-parallel (sp=%d): loss %.3f -> %.3f over %d "
                 "steps", sp, losses[0], losses[-1], len(losses))
    return losses[0], losses[-1]


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--ring", type=int, default=0,
                   help="train sequence-parallel with this many "
                        "sequence shards (needs >= that many devices)")
    a = p.parse_args()
    if a.ring > 1:
        train_sequence_parallel(sp=a.ring)
    else:
        train(epochs=a.epochs)
