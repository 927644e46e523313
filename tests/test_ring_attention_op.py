"""_contrib_RingAttention as a framework operator: single-device
fallback parity, sequence-parallel trainer parity over the virtual
mesh, and the sequence-parallel transformer example.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import ShardedTrainer, build_mesh


def test_ring_op_single_device_matches_flash():
    """Without an active sequence_parallel context the op IS plain
    attention — identical to _contrib_FlashAttention."""
    rng = np.random.RandomState(0)
    q = mx.nd.array(rng.randn(2, 16, 2, 8).astype("f"))
    k = mx.nd.array(rng.randn(2, 16, 2, 8).astype("f"))
    v = mx.nd.array(rng.randn(2, 16, 2, 8).astype("f"))
    for causal in (False, True):
        a = mx.nd._contrib_RingAttention(q, k, v, causal=causal)
        b = mx.nd._contrib_FlashAttention(q, k, v, causal=causal)
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                   rtol=1e-5, atol=1e-5)


def _ring_lm(seq, vocab, d=16, heads=2):
    """Tiny causal LM around _contrib_RingAttention."""
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    x = mx.sym.Embedding(data, input_dim=vocab, output_dim=d,
                         name="embed")
    h = mx.sym.LayerNorm(x, name="ln1")
    qkv = mx.sym.FullyConnected(h, num_hidden=3 * d, flatten=False,
                                name="qkv")
    qkv = mx.sym.Reshape(qkv, shape=(0, 0, 3, heads, -1))
    q = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=2, begin=0, end=1),
                       shape=(0, 0, -3, -2))
    k = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=2, begin=1, end=2),
                       shape=(0, 0, -3, -2))
    v = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=2, begin=2, end=3),
                       shape=(0, 0, -3, -2))
    att = mx.sym._contrib_RingAttention(q, k, v, causal=True,
                                        name="attn")
    att = mx.sym.Reshape(att, shape=(0, 0, -3))
    x = x + mx.sym.FullyConnected(att, num_hidden=d, flatten=False,
                                  name="proj")
    x = mx.sym.LayerNorm(x, name="ln_f")
    x = mx.sym.Reshape(x, shape=(-1, d))
    logits = mx.sym.FullyConnected(x, num_hidden=vocab, name="head")
    label = mx.sym.Reshape(label, shape=(-1,))
    return mx.sym.SoftmaxOutput(logits, label=label, name="softmax")


def _batch(bsz, seq, vocab, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randint(0, vocab, (bsz, seq)).astype("f")
    return {"data": x, "softmax_label": x.copy()}


def test_sequence_parallel_trainer_matches_single_device():
    """Training with the sequence sharded 4 ways == single-device,
    step for step (the ring schedule is numerically the same attention)."""
    bsz, seq, vocab = 4, 16, 16

    def make(sp):
        return ShardedTrainer(
            _ring_lm(seq, vocab), build_mesh(n_devices=sp, tp=sp),
            data_shapes={"data": (bsz, seq)},
            label_shapes={"softmax_label": (bsz, seq)},
            learning_rate=0.05, momentum=0.9, seed=13,
            sequence_parallel=sp > 1)

    a, b = make(1), make(4)
    for i in range(2):
        batch = _batch(bsz, seq, vocab, seed=i)
        la, lb = float(a.step(batch)), float(b.step(batch))
        assert np.isclose(la, lb, rtol=2e-4), (la, lb)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=5e-4, atol=5e-5, err_msg=name)


def test_sequence_parallel_requires_model_axis():
    with pytest.raises(mx.base.MXNetError, match="model"):
        ShardedTrainer(
            _ring_lm(16, 16), build_mesh(n_devices=2, tp=1),
            data_shapes={"data": (4, 16)},
            label_shapes={"softmax_label": (4, 16)},
            sequence_parallel=True)


def test_sequence_parallel_example_converges():
    """The dp x sp transformer example (examples/transformer) descends
    on the Markov corpus with the sequence sharded over the mesh."""
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "examples", "transformer"))
    import train_lm

    first, last = train_lm.train_sequence_parallel(
        sp=4, steps=40, batch_size=8, seq_len=32, vocab_size=16,
        d_model=32, n_heads=2, n_layers=1)
    assert np.isfinite(last)
    assert last < first * 0.8, (first, last)
