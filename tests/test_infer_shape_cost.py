"""Shape inference visits each node once (PR 42): ``Symbol.infer_shape``
evaluates an op's compute function once per op node, and what it leaves in
the span ring is one short ``jax.trace`` record an op node.  Before, every op
with a param-shape rule traced its inputs' whole ancestor sub-graphs again:
N (N + 1) / 2 evaluations on a chain of N, and 48-107 long traces in a
``ShardedTrainer`` build's ``trainer.build.graph``."""
import os
import threading
import time

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as _symbol
from mxnet_tpu.analysis import verify_symbol
from mxnet_tpu.parallel import ShardedTrainer, build_mesh
from mxnet_tpu.symbol import _topo_order
from mxnet_tpu.telemetry import spans

from test_infer_shape_parity import HERE, _load, graph

N = 64


def _chain(n):
    net = mx.sym.Variable("data")
    for i in range(n):
        net = mx.sym.FullyConnected(net, num_hidden=8 + i % 3, name="fc%d" % i)
    return net


@pytest.fixture
def applied(monkeypatch):
    """``(instant, op name)`` of every evaluation of an op's compute function
    that ``symbol.py`` asks for (the walk's and ``eval_graph``'s)."""
    calls, real = [], _symbol.apply_op

    def counting(op, attrs, octx, *ins):
        calls.append((time.perf_counter(), op.name))
        return real(op, attrs, octx, *ins)

    monkeypatch.setattr(_symbol, "apply_op", counting)
    return calls


def _op_nodes(net):
    return [n for n in _topo_order(net._entries) if not n.is_variable]


@pytest.mark.parametrize("n", [8, N])
def test_a_chain_of_n_rules_costs_n_evaluations(applied, n):
    net = _chain(n)
    t0 = time.perf_counter()
    args, outs, aux = net.infer_shape(data=(4, 16))
    # the parent of PR 42: n (n + 1) / 2, the rules' inputs and the heads (2,080 at 64)
    assert len(applied) == n
    assert (len(args), outs, aux) == (2 * n + 1, [(4, 8 + (n - 1) % 3)], [])
    # one outermost trace an op node, none of a sub-graph
    assert len([r for r in spans.records("jax.trace", since=t0)
                if r.thread == threading.get_ident()]) == n


def test_partial_inference_and_the_verifier_cost_the_same_walk(applied):
    net = _chain(N)
    args, outs, _aux = net.infer_shape_partial()
    assert len(applied) == 0 and outs is None and args == [None] * (2 * N + 1)
    assert verify_symbol(net, shapes={"data": (4, 16)}).ok
    assert len(applied) == N


def test_a_late_learnt_weight_is_evaluated_in_a_second_pass(applied):
    """``transpose(w)`` is walked before the rule that sizes ``w``: the second
    pass evaluates it alone."""
    net, shapes, _layout = graph("tied-weight-late")
    assert net.infer_shape(**shapes)[1] == [(8, 4), (2, 4)]
    assert sorted(name for _t, name in applied) == ["FullyConnected", "transpose"]


def test_a_trainer_build_traces_each_op_node_once(applied):
    """``trainer.build.graph`` of the benchmark's toy ResNet: as many
    evaluations, and as many ``jax.trace`` records under the span, as the
    graph has op nodes."""
    cfg = _load(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                             "smoke-resnet.json"))
    net, shapes, _layout = graph("smoke-resnet")
    t0 = time.perf_counter()
    opt = dict(cfg["optimizer"])
    ShardedTrainer(net, build_mesh(devices=jax.devices()[:1], tp=1),
                   data_shapes={"data": shapes["data"]},
                   label_shapes={"softmax_label": shapes["softmax_label"]},
                   optimizer=opt.pop("optimizer"), seed=1, **opt, **cfg["trainer"])
    (built,) = spans.records("trainer.build.graph", since=t0)
    n_ops = len(_op_nodes(net))
    inside = [name for t, name in applied if built.start <= t <= built.end]
    assert len(inside) == n_ops > 50
    traces = [r for r in spans.records("jax.trace", since=t0)
              if r.parent == built.id]
    assert len(traces) == n_ops
