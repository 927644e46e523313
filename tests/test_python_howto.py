"""The python-howto walkthrough scripts run end to end.

Reference: example/python-howto/ (monitor_weights, multiple_outputs,
debug_conv, data_iter) — API walkthroughs, the one example-tail family
that is not dataset/Kaldi-bound (VERDICT r4 missing #5).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples", "python_howto"))


def test_monitor_weights_runs_and_learns():
    import mxnet_tpu as mx
    import monitor_weights
    # the initializer and the iterator's shuffle draw from numpy's global
    # generator, whose state is whatever the worker's last file left
    np.random.seed(0)
    mx.random.seed(0)
    model = monitor_weights.main(num_epoch=10)
    x, y = monitor_weights.synthetic_digits(200, seed=2)
    it = mx.io.NDArrayIter(x, y, batch_size=100,
                           label_name="softmax_label")
    prob = model.predict(it)
    assert (np.asarray(prob).argmax(1) == y).mean() > 0.9


def test_multiple_outputs_group():
    import multiple_outputs
    group, executor = multiple_outputs.main()
    assert group.list_outputs() == ["fc1_output", "softmax_output"]
    fc1, sm = executor.outputs
    assert fc1.shape == (4, 128) and sm.shape == (4, 64)
    np.testing.assert_allclose(np.asarray(sm.asnumpy()).sum(1),
                               np.ones(4), rtol=1e-5)  # 64-way softmax


def test_debug_conv_monitor():
    import debug_conv
    res = debug_conv.main()
    assert res.shape == (1, 1, 5, 5)
    assert np.isfinite(res).all()


def test_data_iter_walkthrough():
    pytest.importorskip("PIL")
    import data_iter
    assert data_iter.main() >= 2
