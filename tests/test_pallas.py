"""Pallas flash-attention kernel vs the jnp oracle (interpret mode on the
CPU test mesh; the same kernel compiles for the MXU on TPU)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _qkv(B=2, T=128, H=2, D=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.normal(0, 1, (B, T, H, D)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_interpret(causal):
    from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                              _attention_jnp)
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, True)  # interpret=True
    ref = _attention_jnp(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward_kernel(causal):
    """The Pallas flash backward (Q-block streaming, dK/dV accumulation
    over the grid, P reconstituted from the saved log-sum-exp) must
    match the dense jnp attention vjp."""
    import jax
    from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                              _attention_jnp)
    q, k, v = _qkv(T=256)
    rng = np.random.RandomState(7)
    g = rng.normal(0, 1, q.shape).astype(np.float32)

    _o, vjp = jax.vjp(lambda q, k, v:
                      flash_attention(q, k, v, causal, True), q, k, v)
    _r, ref_vjp = jax.vjp(lambda q, k, v:
                          _attention_jnp(q, k, v, causal), q, k, v)
    for got, want in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_op_fallback():
    q, k, v = _qkv(T=32)
    out = mx.nd._contrib_FlashAttention(mx.nd.array(q), mx.nd.array(k),
                                        mx.nd.array(v))
    from mxnet_tpu.ops.pallas_kernels import _attention_jnp
    ref = _attention_jnp(q, k, v, False)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_streaming_path(causal):
    """T > _BLOCK_K takes the K/V-streaming kernels (online-softmax
    forward scratch, full-sequence dQ accumulator backward, causal
    tile skip) — the path that lifts the old panel kernels' VMEM wall
    at S>=4096 (VERDICT r4 #2).  Exercised here at a shrunk _BLOCK_K
    so interpret mode stays fast while covering the real code path."""
    import jax
    from mxnet_tpu.ops import pallas_kernels as pk
    old_bk = pk._BLOCK_K
    pk._BLOCK_K = 256          # T=512 -> 2 K blocks: streaming engaged
    try:
        q, k, v = _qkv(B=1, T=512, H=2, D=32)
        rng = np.random.RandomState(7)
        g = rng.normal(0, 1, q.shape).astype(np.float32)
        out, vjp = jax.vjp(lambda q, k, v:
                           pk.flash_attention(q, k, v, causal, True),
                           q, k, v)
        ref, ref_vjp = jax.vjp(lambda q, k, v:
                               pk._attention_jnp(q, k, v, causal),
                               q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        for got, want in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=2e-4, atol=3e-5)
    finally:
        pk._BLOCK_K = old_bk


def test_block_choice_cliff_shapes():
    """ADVICE r5 perf cliff: a seq length that is not a _BLOCK_K
    multiple used to collapse straight to 128-wide K blocks (3200 ->
    25 tiny streams).  _blocks must now pick the largest block_q-
    multiple divisor of t that still fits the VMEM budget."""
    from mxnet_tpu.ops.pallas_kernels import _BLOCK_K, _BLOCK_Q, _blocks

    # multiples of _BLOCK_K stream the full panel
    assert _blocks(2048) == (128, 2048)
    assert _blocks(4096) == (128, 2048)
    # short sequences keep the single-panel fast path
    assert _blocks(512) == (128, 512)
    # the cliff shapes: largest 128-multiple divisor <= _BLOCK_K
    assert _blocks(3200) == (128, 640)    # 5 K blocks (was 25)
    assert _blocks(2304) == (128, 1152)   # 2 K blocks (was 18)
    assert _blocks(6144) == (128, 2048)   # 3 K blocks (was 48)
    # 2176 = 128 * 17: no larger divisor exists, 128 is genuinely best
    assert _blocks(2176) == (128, 128)

    # invariants across every Q-tileable length: the K block always
    # divides t (the grid is exact), is a block_q multiple (MXU
    # tileable), and never exceeds the VMEM budget
    for t in range(128, 8193, 128):
        bq, bk = _blocks(t)
        assert bq == min(_BLOCK_Q, t)
        assert t % bk == 0, t
        assert bk % bq == 0, t
        assert bk <= max(_BLOCK_K, bq), t


def test_attention_step_names_its_kernels(monkeypatch):
    """A tiny attention train step, lowered for the TPU from here: the
    flash kernels carry their own names into the program (``name=`` of
    the ``pallas_call``), forward under ``mxtpu.fwd`` and backward
    under ``mxtpu.bwd`` — what a device trace's "XLA Ops" line shows
    where it used to show ``transpose_jvp___``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import context
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    B, T, H, D = 2, 128, 2, 64
    x = mx.sym.Variable("data")
    qkv = mx.sym.FullyConnected(x, num_hidden=3 * H * D, flatten=False,
                                name="qkv")
    qkv = mx.sym.Reshape(qkv, shape=(0, 0, 3, H, -1))
    q, k, v = (mx.sym.Reshape(
        mx.sym.slice_axis(qkv, axis=2, begin=i, end=i + 1),
        shape=(0, 0, -3, -2)) for i in range(3))
    att = mx.sym._contrib_FlashAttention(q, k, v, causal=True, name="attn")
    att = mx.sym.Reshape(att, shape=(-1, H * D))
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(att, num_hidden=16, name="head"),
        label=mx.sym.Reshape(mx.sym.Variable("softmax_label"),
                             shape=(-1,)), name="softmax")
    t = ShardedTrainer(net, build_mesh(n_devices=1),
                       data_shapes={"data": (B, T, 32)},
                       label_shapes={"softmax_label": (B, T)},
                       optimizer="adam", learning_rate=1e-4)
    spec = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    args = (spec(t.params), spec(t.opt_state), spec(t.aux),
            {"data": jax.ShapeDtypeStruct((B, T, 32), jnp.float32),
             "softmax_label": jax.ShapeDtypeStruct((B, T), jnp.float32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    text = jax.jit(t._py_step).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") >= 2
    assert (pk.FLASH_FWD_PANEL, pk.FLASH_BWD_PANEL) == \
        ("mxtpu_flash_fwd_panel", "mxtpu_flash_bwd_panel")
    assert "mxtpu.fwd/jvp(mxtpu_flash_fwd_panel)/pallas_call" in text
    assert "jvp(mxtpu_flash_bwd_panel)/pallas_call" in text
    assert "mxtpu.bwd/" in text and "mxtpu.opt/" in text
    # the streaming kernels' names are constants of their call sites too
    assert (pk.FLASH_FWD_STREAM, pk.FLASH_BWD_STREAM) == \
        ("mxtpu_flash_fwd_stream", "mxtpu_flash_bwd_stream")
