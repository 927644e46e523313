"""``Symbol.infer_shape`` / ``infer_shape_partial`` against values recorded
with the parent of PR 42 (commit 97e7f6b, whose inference traced every
node's ancestors again): the seven benchmark builders at their toy sizes
(``benchmark/configs/smoke-*.json``; ResNet also under the trainer's NHWC
layout), three zoo models, an unrolled RNN stack (deferred batch dims in
``begin_state``, grouped heads) and a weight whose first consumer has no
param-shape rule.

The values are data, ``tests/infer_shape_parent.json``.  A PR that means to
move them records them again with the code it compares against::

    JAX_PLATFORMS=cpu python tests/test_infer_shape_parity.py <checkout> <out.json>
"""
import contextlib
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "infer_shape_parent.json")

#: toy configuration -> its traffic mix
TOYS = {"smoke-resnet": "smoke-b8-chain2", "smoke-opt": "smoke-s32-b2-chain2",
        "smoke-lfm2": "smoke-s64-b1-chain2", "smoke-kimi": "smoke-s64-b1-chain2",
        "smoke-trinity": "smoke-s64-b1-chain2",
        "smoke-nemotron": "smoke-s64-b1-chain2",
        "smoke-sdar": "smoke-bd-s64-b1-chain2"}
ZOO = {"zoo-lenet": ("lenet", (2, 1, 28, 28)),
       "zoo-inception-bn": ("inception-bn", (2, 3, 224, 224)),
       "zoo-vgg11": ("vgg11", (2, 3, 224, 224))}
NAMES = sorted(TOYS) + ["smoke-resnet.nhwc"] + sorted(ZOO) + [
    "rnn-lstm-gru", "tied-weight-late"]


def _load(path):
    with open(path) as f:
        return json.load(f)


def _toy(root, name):
    bench = os.path.join(root, "benchmark")
    cfg = _load(os.path.join(bench, "configs", name + ".json"))
    spec = importlib.util.spec_from_file_location(
        "toy_builder", os.path.join(bench, "configs", cfg["code"] + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    net, data, label = mod.build(
        cfg, _load(os.path.join(bench, "traffic", TOYS[name] + ".json")), 1)
    return net, {**data, **label}


def graph(name, root=os.path.dirname(HERE)):
    """``(symbol, {input: shape}, image layout or None)``."""
    import mxnet_tpu as mx
    if name in TOYS:
        return _toy(root, name) + (None,)
    if name == "smoke-resnet.nhwc":     # as ShardedTrainer._analyse_graph asks
        net, shapes = _toy(root, "smoke-resnet")
        n, c, h, w = shapes["data"]
        return net, dict(shapes, data=(n, h, w, c)), "NHWC"
    if name in ZOO:
        model, data = ZOO[name]
        return (mx.models.get_model(model, num_classes=10),
                {"data": data, "softmax_label": data[:1]}, None)
    if name == "rnn-lstm-gru":
        stack = mx.rnn.SequentialRNNCell()
        stack.add(mx.rnn.LSTMCell(num_hidden=16, prefix="l0_"))
        stack.add(mx.rnn.GRUCell(num_hidden=8, prefix="l1_"))
        outs, states = stack.unroll(3, inputs=mx.sym.Variable("data"),
                                    merge_outputs=True)
        return mx.sym.Group([outs] + list(states)), {"data": (4, 3, 10)}, None
    assert name == "tied-weight-late"
    # transpose has no param-shape rule and comes first in the walk; the
    # weight's shape is learnt from FullyConnected, its second consumer
    w = mx.sym.Variable("w")
    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), weight=w,
                               num_hidden=4, no_bias=True, name="fc")
    return mx.sym.Group([mx.sym.transpose(w), fc]), {"data": (2, 8)}, None


def infer(name, withheld=None, root=os.path.dirname(HERE)):
    """What the parity is about, as JSON holds it: the three lists of
    ``infer_shape`` (``infer_shape_partial`` with one input withheld)."""
    from mxnet_tpu.ops.nn import image_layout
    net, shapes, layout = graph(name, root)
    with image_layout(layout) if layout else contextlib.nullcontext():
        if withheld is None:
            out = net.infer_shape(**shapes)
        else:
            out = net.infer_shape_partial(
                **{k: v for k, v in shapes.items() if k != withheld})
    return [None if part is None else
            [None if s is None else list(s) for s in part] for part in out]


#: (graph, the input withheld): every graph takes ``data``, the trained ones a label
PARTIAL = [(n, k) for n in NAMES for k in ("data", "softmax_label")
           if k == "data" or n not in ("rnn-lstm-gru", "tied-weight-late")]


@pytest.mark.parametrize("name", NAMES)
def test_infer_shape_returns_the_parents_shapes(name):
    want = _load(RECORDED)[name]["full"]
    got = infer(name)
    assert [len(p) for p in got] == [len(p) for p in want]
    assert got == want


@pytest.mark.parametrize("name,withheld", PARTIAL)
def test_infer_shape_partial_returns_what_the_parent_returned(name, withheld):
    # without ``data`` little is known and the outputs are None; a label's
    # shape the loss op's own rule gives back (SoftmaxOutput), and all is known
    assert infer(name, withheld) == _load(RECORDED)[name]["partial"][withheld]


def test_recorded_values_cover_every_graph_and_input():
    recorded = _load(RECORDED)
    assert sorted(recorded) == sorted(NAMES)
    assert sorted((n, k) for n in NAMES for k in graph(n)[1]) == sorted(PARTIAL)
    assert sorted((n, k) for n in NAMES for k in recorded[n]["partial"]) \
        == sorted(PARTIAL)


if __name__ == "__main__":
    checkout, out_path = sys.argv[1:3]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, checkout)
    rec = {}
    for graph_name in NAMES:
        rec[graph_name] = {
            "full": infer(graph_name, root=checkout),
            "partial": {k: infer(graph_name, k, checkout)
                        for n, k in PARTIAL if n == graph_name}}
    with open(out_path, "w") as f:
        json.dump(rec, f, sort_keys=True, separators=(",", ":"))
        f.write("\n")
