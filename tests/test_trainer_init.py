"""Where and from what ``ShardedTrainer`` draws its initial state.

A parameter whose rule is ``traceable`` (``initializer.rule_for``) comes
out of one jitted program keyed by the constructor's ``seed``; a rule
that only fills a host array (a user's ``_init_weight`` over
``np.random``) is drawn on the host as it always was.  The distribution
is the host rule's: same scale from the same fans of the reference OIHW
shape, as convolution masters are held.
"""
import functools
import os
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import initializer as init_mod
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import ShardedTrainer, build_mesh
from mxnet_tpu.telemetry import spans

CONV = (48, 32, 3, 3)    # OIHW, as the rules see it and the master is held
FC = (10, 48)


def _net():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=32,
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1), num_filter=48,
                             no_bias=True, name="conv1")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _trainer(initializer=None, mesh=None, seed=3, net=None, **kw):
    kw.setdefault("layout", "NHWC")
    return ShardedTrainer(
        net or _net(), mesh or build_mesh(n_devices=1, tp=1),
        data_shapes={"data": (8, 3, 8, 8)},
        label_shapes={"softmax_label": (8,)},
        initializer=initializer, seed=seed, **kw)


def _init_attrs():
    """The attributes of the newest ``trainer.build.init_params`` span."""
    return spans.records("trainer.build.init_params")[-1].attrs


def _values(t):
    return {n: np.asarray(v) for n, v in {**t.params, **t.aux}.items()}


# ---------------------------------------------------------------- the rules
def _xavier_scale(magnitude, factor_type, shape):
    hw = int(np.prod(shape[2:]))
    fan_in, fan_out = shape[1] * hw, shape[0] * hw
    factor = {"in": fan_in, "out": fan_out,
              "avg": (fan_in + fan_out) / 2.0}[factor_type]
    return np.sqrt(magnitude / factor)


#: id -> (initializer, kind, its parameter as a function of the OIHW shape)
RULES = {"zero": (mx.init.Zero(), "constant", lambda s: 0.0),
         "one": (mx.init.One(), "constant", lambda s: 1.0),
         "constant": (mx.init.Constant(0.3), "constant", lambda s: 0.3),
         "uniform": (mx.init.Uniform(0.2), "uniform", lambda s: 0.2),
         "normal": (mx.init.Normal(0.05), "gaussian", lambda s: 0.05),
         "default": (None, "gaussian", lambda s: _xavier_scale(2, "in", s))}
for _rnd in ("uniform", "gaussian"):
    for _factor in ("avg", "in", "out"):
        RULES["xavier-%s-%s" % (_rnd, _factor)] = (
            mx.init.Xavier(_rnd, _factor, 2.5), _rnd,
            lambda s, f=_factor: _xavier_scale(2.5, f, s))
for _factor in ("avg", "in", "out"):
    RULES["msraprelu-" + _factor] = (
        mx.init.MSRAPrelu(_factor, 0.1), "gaussian",
        lambda s, f=_factor: _xavier_scale(2.0 / (1 + 0.1 ** 2), f, s))


@functools.lru_cache(maxsize=None)
def _rule_trainer(rule):
    return _values(_trainer(RULES[rule][0])), _init_attrs()


@pytest.mark.parametrize("name,shape", [("fc_weight", FC),
                                        ("conv1_weight", CONV)])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_device_draw_has_the_host_rules_distribution(rule, name, shape):
    values, attrs = _rule_trainer(rule)
    assert attrs["host_params"] == 0 and attrs["host_bytes"] == 0
    w = values[name]
    assert w.dtype == np.float32
    assert w.shape == shape
    _, kind, param = RULES[rule]
    p, n = param(shape), w.size
    if kind == "constant":
        assert (w == np.float32(p)).all()
    elif kind == "uniform":     # U(-p, p): sd p / sqrt(3)
        assert -p <= w.min() < -0.98 * p and 0.98 * p < w.max() <= p
        assert abs(w.mean()) < 4 * p / np.sqrt(3 * n)
        assert w.std() == pytest.approx(p / np.sqrt(3), rel=0.03)
    else:                       # N(0, p)
        assert abs(w.mean()) < 4 * p / np.sqrt(n)
        assert w.std() == pytest.approx(p, rel=0.03)
        assert np.abs(w).max() > 3 * p


@pytest.mark.parametrize("name,value", [
    ("conv0_bias", 0.0), ("fc_bias", 0.0), ("bn0_gamma", 1.0),
    ("bn0_beta", 0.0), ("bn0_moving_mean", 0.0), ("bn0_moving_var", 1.0)])
def test_constants_by_suffix(name, value):
    values, _ = _rule_trainer("default")
    assert (values[name] == value).all() and values[name].dtype == np.float32


@pytest.mark.parametrize("name,value", [
    ("moe_load", 0.0), ("bn_running_var", 1.0), ("x_moving_avg", 0.0)])
def test_aux_suffix_rules_have_the_device_form(name, value):
    rule = init_mod.rule_for(mx.init.Xavier(), init_mod.InitDesc(name))
    assert rule.traceable
    out = jax.jit(lambda k: init_mod.draw(rule, name, (3,), k))(
        jax.random.PRNGKey(0))
    assert np.asarray(out).tolist() == [value] * 3


@pytest.mark.parametrize("init", [
    mx.init.Orthogonal(), mx.init.Bilinear(), mx.init.LSTMBias(),
    mx.init.Load({}, default_init=mx.init.Xavier()),
    mx.init.FusedRNN(mx.init.Xavier(), 4, 1, "lstm")],
    ids=lambda i: type(i).__name__)
def test_rules_that_stay_on_the_host(init):
    rule = init_mod.rule_for(init, init_mod.InitDesc("a_weight"))
    assert not getattr(rule, "traceable", False)


def test_variable_init_and_mixed_reach_the_device_form():
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("fc_weight", init=mx.init.Constant(0.25))
    net = mx.sym.FullyConnected(mx.sym.Flatten(data), weight=w,
                                num_hidden=6, name="fc")
    net = mx.sym.FullyConnected(net, num_hidden=50, name="wide")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mixed = mx.init.Mixed(["wide_.*", ".*"],
                          [mx.init.Uniform(0.5), mx.init.Normal(0.01)])
    values = _values(_trainer(mixed, net=net))
    attrs = _init_attrs()
    assert attrs["host_params"] == 0 and attrs["device_params"] == 6
    assert (values["fc_weight"] == 0.25).all()      # the Variable's own
    assert 0.45 < values["wide_weight"].max() <= 0.5     # Mixed: Uniform
    assert (values["wide_bias"] == 0).all()         # ... and its bias rule
    assert 0.005 < values["out_weight"].std() < 0.02     # Mixed: Normal
    with pytest.raises(MXNetError, match="did not match any pattern"):
        _trainer(mx.init.Mixed(["wide_.*"], [mx.init.Zero()]), net=net)


# ---------------------------------------------------------------- the seed
def test_seed_decides_the_draw_and_numpys_does_not():
    np.random.seed(1)
    a = _values(_trainer(seed=11))
    np.random.seed(2)
    state = np.random.get_state()[1].copy()
    b = _values(_trainer(seed=11))
    assert (np.random.get_state()[1] == state).all()   # nothing drawn there
    c = _values(_trainer(seed=12))
    for n in a:
        np.testing.assert_array_equal(a[n], b[n], err_msg=n)
    for n in ("conv0_weight", "conv1_weight", "fc_weight"):
        assert np.abs(a[n] - c[n]).max() > 1e-3
        # ... and no two parameters share a stream
    assert not np.array_equal(a["conv0_weight"].ravel()[:64],
                              a["conv1_weight"].ravel()[:64])


def _key_of(t, seed, name):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), 0x696e6974),
        t._param_names.index(name))


@pytest.mark.parametrize("which", ["equal-shapes", "convolution"])
def test_every_parameter_is_its_own_keys_draw(which):
    """Parameters of one rule and one shape share a traced function,
    not a stream: each is ``jax.random``'s draw of its shape under
    ``fold_in(fold_in(PRNGKey(seed), "init"), index)``, scaled."""
    if which == "equal-shapes":
        net = mx.sym.Flatten(mx.sym.Variable("data"))
        for name in "abc":
            net = mx.sym.FullyConnected(net, num_hidden=16, name=name)
        t = _trainer(net=mx.sym.SoftmaxOutput(net, name="softmax"), seed=21)
        names = ["a_weight", "b_weight", "c_weight"]
        assert t.params["b_weight"].shape == t.params["c_weight"].shape
    else:
        t = _trainer(seed=21)
        names = ["conv0_weight", "conv1_weight", "fc_weight"]
    for name in names:
        got = np.asarray(t.params[name])
        shape = got.shape
        want = np.asarray(jax.random.normal(_key_of(t, 21, name), shape)) \
            * _xavier_scale(2, "in", shape)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8,
                                   err_msg=name)
    assert not np.array_equal(np.asarray(t.params[names[1]]),
                              np.asarray(t.params[names[2]]))


def test_init_stream_is_not_the_steps():
    t = _trainer(seed=11)
    step_key = np.asarray(t._key)
    assert (step_key == np.asarray(jax.random.PRNGKey(11))).all()
    first = np.asarray(jax.random.normal(
        jax.random.fold_in(t._key, 0), (64,)))
    assert not np.allclose(np.sort(first), np.sort(
        np.asarray(t.params[t._param_names[0]]).ravel()[:64]))


@pytest.mark.parametrize("axes", [dict(tp=1), dict(tp=2), dict(tp=4)],
                         ids=["dp4", "dp2tp2", "tp4"])
def test_four_devices_start_where_one_does(axes):
    one = _trainer(seed=5)
    four = _trainer(seed=5, mesh=build_mesh(n_devices=4, **axes))
    sharded = 0
    for name, whole in {**one.params, **one.aux}.items():
        whole = np.asarray(whole)
        arr = {**four.params, **four.aux}[name]
        assert len(arr.addressable_shards) == 4
        for shard in arr.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(shard.data), whole[shard.index], err_msg=name)
            sharded += shard.data.shape != whole.shape
    assert (sharded > 0) == (axes["tp"] > 1)


# ---------------------------------------------------------------- the host
class NumpyUniform(mx.init.Initializer):
    """A user's initializer, written against numpy's global generator."""

    def _init_weight(self, name, arr):
        arr[:] = np.random.uniform(-1, 1, arr.shape) * 0.1


def test_users_numpy_rule_keeps_the_host_and_its_values():
    np.random.seed(21)
    t = _trainer(NumpyUniform())
    attrs = _init_attrs()
    # what the constructor drew before this PR: every weight in
    # _param_names order from the pinned global generator, as OIHW
    np.random.seed(21)
    for name in t._param_names:
        if not name.endswith("weight"):
            continue
        want = (np.random.uniform(-1, 1, t._arg_shapes[name]) * 0.1
                ).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(t.params[name]), want)
    # the same trainer's other parameters took the device's rules
    assert attrs["host_names"] == ["conv0_weight", "conv1_weight",
                                   "fc_weight"]
    assert attrs["host_params"] == 3 and attrs["device_params"] == 4
    assert attrs["host_bytes"] == 4 * sum(
        int(np.prod(t._arg_shapes[n])) for n in attrs["host_names"])
    assert attrs["device_bytes"] == 4 * (32 + 32 + 32 + 10)
    assert list(t.params) == t._param_names
    assert (np.asarray(t.params["bn0_gamma"]) == 1).all()
    assert np.isfinite(float(t.step({
        "data": np.ones((8, 3, 8, 8), np.float32),
        "softmax_label": np.zeros(8, np.float32)})))


def test_choice_is_per_parameter():
    mixed = mx.init.Mixed(["fc_.*", ".*"],
                          [NumpyUniform(), mx.init.Normal(0.02)])
    np.random.seed(4)
    a = _values(_trainer(mixed, seed=8))
    attrs = _init_attrs()
    np.random.seed(4)
    want = (np.random.uniform(-1, 1, FC) * 0.1).astype(np.float32)
    np.testing.assert_array_equal(a["fc_weight"], want)
    assert attrs["host_names"] == ["fc_weight"]
    assert attrs["device_params"] == 6
    np.random.seed(99)      # moves the host's draw and nothing else
    b = _values(_trainer(mixed, seed=8))
    assert not np.array_equal(a["fc_weight"], b["fc_weight"])
    np.testing.assert_array_equal(a["conv1_weight"], b["conv1_weight"])
    assert a["conv1_weight"].std() == pytest.approx(0.02, rel=0.03)


def test_a_subclass_that_overrides_call_is_its_own_rule():
    class Own(mx.init.Xavier):
        def __call__(self, desc, arr):
            arr[:] = 7.0

    values = _values(_trainer(Own()))
    assert all((values[n] == 7).all() for n in ("fc_weight", "bn0_gamma"))
    assert _init_attrs()["device_params"] == 0


@pytest.mark.parametrize("where", ["host", "trace", "resolve"])
def test_an_initializer_that_raises_still_raises(where):
    class Boom(mx.init.Initializer):
        def _init_weight(self, name, arr):
            raise ValueError("boom on the host")

    if where == "host":
        with pytest.raises(ValueError, match="boom on the host"):
            _trainer(Boom())
    elif where == "trace":      # Xavier refuses a vector, under the trace too
        data = mx.sym.Variable("data")
        scale = mx.sym.Variable("scale_weight", shape=(1, 3, 1, 1))
        net = mx.sym.FullyConnected(
            mx.sym.Flatten(mx.sym.broadcast_mul(data, scale)),
            num_hidden=4, name="fc")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        _trainer(net=net, layout=None)     # 4-d: has fans, is drawn
        vec = mx.sym.Variable("gain_weight", shape=(4,))
        net = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=4,
                                    name="fc")
        net = mx.sym.SoftmaxOutput(mx.sym.broadcast_mul(
            net, mx.sym.reshape(vec, shape=(1, 4))), name="softmax")
        with pytest.raises(MXNetError, match="cannot be applied to vector"):
            _trainer(net=net, layout=None)
    else:                       # a name no suffix rule knows
        data = mx.sym.Variable("data")
        odd = mx.sym.Variable("odd_name", shape=(1, 3, 1, 1))
        net = mx.sym.FullyConnected(
            mx.sym.Flatten(mx.sym.broadcast_mul(data, odd)),
            num_hidden=4, name="fc")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        with pytest.raises(MXNetError, match="Unknown initialization"):
            _trainer(net=net, layout=None)


def test_host_and_module_path_is_numpys_as_before():
    """``Module`` and the executor hand the rules host arrays: numpy's
    global generator, float64 drawn then cast, bit for bit."""
    for init, ref in [
            (mx.init.Uniform(0.3), lambda s: np.random.uniform(-0.3, 0.3, s)),
            (mx.init.Normal(0.2), lambda s: np.random.normal(0, 0.2, s)),
            (mx.init.Xavier("gaussian", "in", 2), lambda s: np.random.normal(
                0, _xavier_scale(2, "in", s), s))]:
        arr = mx.nd.zeros(CONV)
        np.random.seed(6)
        init(init_mod.InitDesc("c_weight"), arr)
        np.random.seed(6)
        np.testing.assert_array_equal(arr.asnumpy(),
                                      ref(CONV).astype(np.float32))


# ------------------------------------------------------------ the reader
def _reader():
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        import run
        return run.load_module("layer_metrics", "init_on_host_pct")
    finally:
        sys.path.remove(bench)


@pytest.mark.parametrize("attrs,want", [
    (None, None),
    (dict(device_params=5, device_bytes=4000, host_params=0, host_bytes=0,
          host_names=[]), 0.0),
    (dict(device_params=4, device_bytes=3000, host_params=1,
          host_bytes=1000, host_names=["fc_weight"]), 25.0)],
    ids=["parent", "all-device", "a-quarter-on-host"])
def test_init_on_host_pct_reader(attrs, want):
    saved = list(spans._ring)
    spans.clear()
    try:
        spans._ring.append(spans.Record(
            "trainer.build.init_params", 1.0, 2.0, 1, None, 1, attrs))
        spans._ring.append(spans.Record(   # a later trainer: not set-up's
            "trainer.build.init_params", 11.0, 12.0, 2, None, 1,
            dict(device_bytes=0, host_bytes=8)))
        got = _reader().read({"samples": [(10.0, 10.1, 10.2, [1.0])]})
        assert got == want
    finally:
        spans.clear()
        spans._ring.extend(saved)
