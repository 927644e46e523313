"""Static analysis subsystem: graph verifier + mxlint + the CI gate.

Each verifier defect class gets a seeded-defect test asserting the
diagnostic carries the offending node's name (ISSUE 2 acceptance)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu import analysis
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(report):
    return [d.rule for d in report]


def _find(report, rule):
    return [d for d in report if d.rule == rule]


# ------------------------------------------------------- seeded defects

def test_verify_clean_model():
    from mxnet_tpu import models
    net = models.get_model("lenet", num_classes=10)
    report = net.verify(data=(2, 1, 28, 28), softmax_label=(2,))
    assert report.ok and not report.warnings, str(report)


def test_verify_shape_mismatch_names_node():
    d = sym.var("data")
    w = sym.var("w", shape=(5, 999))          # wrong contracting dim
    fc = sym.FullyConnected(d, weight=w, num_hidden=5, name="fc_bad")
    report = fc.verify(data=(4, 10))
    bad = _find(report, "MXG005")
    assert bad and bad[0].node == "fc_bad", str(report)
    assert bad[0].severity == "error"
    assert "fc_bad" in str(report)


def test_verify_missing_shape_rule_names_node():
    # an op with a parameter-style argument but no ops.shapes hook
    if not registry.has_op("_test_noshaperule"):
        @registry.register("_test_noshaperule", arg_names=("data", "gain"))
        def _gain(attrs, ctx, data, gain):
            return data * gain
    g = sym._create("_test_noshaperule", "g0", None, [sym.var("data")], {})
    report = g.verify(data=(2, 3))
    bad = _find(report, "MXG004")
    assert bad and bad[0].node == "g0", str(report)
    assert "param-shape rule" in bad[0].message
    # giving the shape explicitly clears the defect
    g2 = sym._create("_test_noshaperule", "g1", None,
                     [sym.var("data"), sym.var("gain", shape=(3,))], {})
    assert g2.verify(data=(2, 3)).ok


def test_verify_dtype_conflict_names_node():
    a = sym.var("a", dtype="float32")
    b = sym.var("b", dtype="float64")
    s = sym.elemwise_add(a, b, name="mixed_add")
    report = s.verify(a=(2, 2), b=(2, 2))
    w = _find(report, "MXG006")
    assert w and w[0].node == "mixed_add", str(report)
    assert "float64" in w[0].message


def test_verify_dtype_conflict_bfloat16():
    """bfloat16 is an ml_dtypes extension type (numpy kind 'V'); the
    promotion audit must still see it — it IS the TPU compute dtype."""
    a = sym.var("a", dtype="bfloat16")
    b = sym.var("b", dtype="float32")
    s = sym.elemwise_add(a, b, name="bf16_add")
    report = s.verify(a=(2, 2), b=(2, 2))
    w = _find(report, "MXG006")
    assert w and w[0].node == "bf16_add", str(report)
    assert "bfloat16" in w[0].message


def test_verify_dead_input_names_node():
    net = sym.FullyConnected(sym.var("data"), num_hidden=4, name="fc1")
    grp = sym.Group([net, sym.var("dead_in")])
    report = grp.verify(data=(2, 8), dead_in=(1,))
    w = _find(report, "MXG003")
    assert w and w[0].node == "dead_in", str(report)


def test_verify_json_malformed_input_is_diagnosed():
    """Malformed JSON becomes an MXG005 diagnostic, not a traceback
    (the CLI contract)."""
    r = analysis.verify_json("{not json")
    assert _rules(r) == ["MXG005"] and not r.ok
    r = analysis.verify_json('{"nodes": "oops", "heads": []}')
    assert _rules(r) == ["MXG005"] and not r.ok


def test_verify_json_unreachable_node():
    net = sym.FullyConnected(sym.var("data"), num_hidden=4, name="fc1")
    js = json.loads(net.tojson())
    js["nodes"].append({"op": "null", "name": "ghost", "inputs": []})
    report = analysis.verify_json(json.dumps(js), shapes={"data": (2, 8)})
    w = _find(report, "MXG003")
    assert w and w[0].node == "ghost", str(report)


def test_verify_missing_tp_rule_names_node():
    d = sym.var("data")
    fc = sym.FullyConnected(d, num_hidden=6, name="tiny_fc")  # 6 % 4 != 0
    report = fc.verify(data=(2, 64), tp_size=4)
    bad = _find(report, "MXG007")
    assert bad and bad[0].node == "tiny_fc", str(report)
    assert "tiny_fc_weight" in bad[0].message
    # explicit replicate annotation is an accepted answer
    fc2 = sym.FullyConnected(d, num_hidden=6, name="tiny_fc2")
    fc2._set_attr(__tp__="replicate")
    assert fc2.verify(data=(2, 64), tp_size=4).ok
    # a shardable graph is covered without annotations
    big = sym.FullyConnected(d, num_hidden=64, name="big_fc")
    assert big.verify(data=(2, 64), tp_size=4).ok


def test_verify_cycle_names_nodes():
    x = sym.var("data")
    f1 = sym.FullyConnected(x, num_hidden=4, name="c1")
    f2 = sym.FullyConnected(f1, num_hidden=4, name="c2")
    f1._entries[0][0].inputs[0] = (f2._entries[0][0], 0)  # c1 <- c2
    report = f2.verify()
    bad = _find(report, "MXG001")
    assert bad, str(report)
    assert "c1" in bad[0].message and "c2" in bad[0].message


def test_verify_duplicate_names():
    d = sym.var("data")
    p = sym.FullyConnected(d, num_hidden=4, name="samename")
    q = sym.FullyConnected(p, num_hidden=4, name="samename")
    report = q.verify(data=(2, 4))
    bad = _find(report, "MXG002")
    assert bad and any(x.node == "samename" for x in bad), str(report)


# ------------------------------------------- infer_shape_partial parity

def test_infer_shape_partial_underdetermined():
    """partial inference yields None out_shapes when underdetermined,
    and verify() attributes the gap to the consuming op node."""
    if not registry.has_op("_test_noshaperule"):
        @registry.register("_test_noshaperule", arg_names=("data", "gain"))
        def _gain(attrs, ctx, data, gain):
            return data * gain
    g = sym._create("_test_noshaperule", "gp", None, [sym.var("data")], {})
    arg_shapes, out_shapes, _aux = g.infer_shape_partial(data=(2, 3))
    assert out_shapes is None
    assert None in arg_shapes
    report = g.verify(data=(2, 3))
    assert [d for d in report if d.node == "gp"], str(report)


# ---------------------------------------------------- strict bind paths

def test_bind_strict_raises_before_compile():
    d = sym.var("data")
    w = sym.var("w", shape=(5, 999))
    fc = sym.FullyConnected(d, weight=w, num_hidden=5, name="fcx")
    args = {"data": mx.nd.zeros((4, 10)), "w": mx.nd.zeros((5, 999)),
            "fcx_bias": mx.nd.zeros((5,))}
    with pytest.raises(MXNetError, match="fcx"):
        fc.bind(mx.cpu(), args, strict=True)
    # same bind without strict defers the failure to execution time
    ex = fc.bind(mx.cpu(), args)
    assert ex is not None


def test_simple_bind_strict_ok():
    net = sym.FullyConnected(sym.var("data"), num_hidden=4, name="fc1")
    ex = net.simple_bind(mx.cpu(), data=(2, 8), strict=True)
    assert ex.forward()[0].shape == (2, 4)


def test_module_bind_strict():
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.var("data"), num_hidden=4, name="fc1"),
        name="softmax")
    mod = mx.mod.Module(symbol=net, label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2,))], strict=True)
    assert mod.binded


def test_strict_bind_env_var(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_STRICT_BIND", "1")
    d = sym.var("data")
    w = sym.var("w", shape=(5, 999))
    fc = sym.FullyConnected(d, weight=w, num_hidden=5, name="fce")
    args = {"data": mx.nd.zeros((4, 10)), "w": mx.nd.zeros((5, 999)),
            "fce_bias": mx.nd.zeros((5,))}
    with pytest.raises(MXNetError, match="fce"):
        fc.bind(mx.cpu(), args)


# ------------------------------------------------------------- registry

def test_registry_rejects_duplicate_op():
    @registry.register("_test_dup_probe")
    def _p(attrs, ctx, data):
        return data
    with pytest.raises(MXNetError, match="duplicate op registration"):
        @registry.register("_test_dup_probe")
        def _q(attrs, ctx, data):
            return data


def test_registry_rejects_alias_collisions():
    # alias colliding with an existing op name
    with pytest.raises(MXNetError, match="duplicate op registration"):
        @registry.register("_test_alias_probe",
                           aliases=("FullyConnected",))
        def _r(attrs, ctx, data):
            return data
    assert not registry.has_op("_test_alias_probe")
    # op name colliding with an existing alias
    alias = sorted(registry._ALIASES)[0]
    with pytest.raises(MXNetError, match="already an alias"):
        @registry.register(alias)
        def _s(attrs, ctx, data):
            return data


def test_registry_selfcheck_clean():
    assert registry.selfcheck() == []


def test_registry_selfcheck_catches_drift():
    from mxnet_tpu.ops import shapes as shapes_mod
    shapes_mod._PARAM_SHAPE_HOOKS["_test_ghost_op"] = lambda a, k: {}
    try:
        problems = registry.selfcheck()
        assert any("_test_ghost_op" in p for p in problems)
    finally:
        del shapes_mod._PARAM_SHAPE_HOOKS["_test_ghost_op"]
    assert registry.selfcheck() == []


def test_squeeze_op_round_trip():
    """squeeze was in tp_rules._PASS_OPS but missing from the registry —
    the drift the selfcheck exists to catch; it is a real op now."""
    x = mx.nd.ones((2, 1, 3))
    assert mx.nd.squeeze(x, axis=1).shape == (2, 3)
    assert mx.nd.squeeze(x).shape == (2, 3)
    s = sym.squeeze(sym.var("d"), axis=1)
    _a, out, _x = s.infer_shape(d=(2, 1, 3))
    assert out == [(2, 3)]


# --------------------------------------------------------------- mxlint

def _mxlint():
    return analysis.load_mxlint()


def _lint(src):
    return _mxlint().lint_source(src)


def test_mxlint_broad_except():
    rules = [f.rule for f in _lint(
        "try:\n    x = 1\nexcept Exception:\n    pass\n")]
    assert rules == ["MXL001"]
    rules = [f.rule for f in _lint(
        "try:\n    x = 1\nexcept:\n    pass\n")]
    assert rules == ["MXL001"]
    rules = [f.rule for f in _lint(
        "try:\n    x = 1\nexcept (ValueError, BaseException):\n    pass\n")]
    assert rules == ["MXL001"]
    assert _lint("try:\n    x = 1\nexcept ValueError:\n    pass\n") == []


def test_mxlint_pragma():
    clean = ("try:\n    x = 1\n"
             "except Exception:  "
             "# mxlint: allow-broad-except(teardown guard)\n    pass\n")
    assert _lint(clean) == []
    # pragma on the preceding line also works
    clean2 = ("try:\n    x = 1\n"
              "# mxlint: disable=MXL001(teardown guard)\n"
              "except Exception:\n    pass\n")
    assert _lint(clean2) == []
    # empty reason is rejected AND the finding stays
    bad = ("try:\n    x = 1\n"
           "except Exception:  # mxlint: allow-broad-except()\n    pass\n")
    rules = sorted(f.rule for f in _lint(bad))
    assert rules == ["MXL000", "MXL001"]
    # prose mentioning mxlint is not a pragma attempt
    assert _lint("x = 1  # run mxlint before committing\n") == []
    assert _lint("# mxlint cannot see dynamic jit wrappers\nx = 1\n") == []


def test_mxlint_host_sync_in_jit():
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return float(x) + 1\n")
    assert [f.rule for f in _lint(src)] == ["MXL002"]
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    y = x.sum()\n"
           "    return y.item()\n")
    assert [f.rule for f in _lint(src)] == ["MXL002"]
    src = ("import jax, numpy as np\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return np.asarray(x)\n")
    assert [f.rule for f in _lint(src)] == ["MXL002"]
    # shape access is concrete, not a sync; outside jit is fine too
    assert _lint("import jax\n@jax.jit\ndef f(x):\n"
                 "    return x.reshape(int(x.shape[0]), -1)\n") == []
    assert _lint("def g(x):\n    return float(x)\n") == []


def test_mxlint_recompile_hazard():
    src = ("import jax\nimport jax.numpy as jnp\n"
           "@jax.jit\n"
           "def f(x, n):\n"
           "    return x + jnp.zeros(n)\n")
    assert [f.rule for f in _lint(src)] == ["MXL003"]
    # static_argnames clears it
    src_static = ("import jax\nimport jax.numpy as jnp\n"
                  "import functools\n"
                  "@functools.partial(jax.jit, static_argnames=('n',))\n"
                  "def f(x, n):\n"
                  "    return x + jnp.zeros(n)\n")
    assert _lint(src_static) == []
    # deriving from .shape is the blessed pattern
    src_shape = ("import jax\nimport jax.numpy as jnp\n"
                 "@jax.jit\n"
                 "def f(x):\n"
                 "    return x + jnp.zeros(x.shape[1])\n")
    assert _lint(src_shape) == []
    # python loop bound over a traced arg
    src_range = ("import jax\n"
                 "@jax.jit\n"
                 "def f(x, k):\n"
                 "    for _ in range(k):\n"
                 "        x = x + 1\n"
                 "    return x\n")
    assert [f.rule for f in _lint(src_range)] == ["MXL003"]


def test_mxlint_captured_mutation():
    src = ("import jax\n"
           "cache = {}\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    cache['last'] = x\n"
           "    return x\n")
    assert [f.rule for f in _lint(src)] == ["MXL004"]
    src_append = ("import jax\n"
                  "log = []\n"
                  "@jax.jit\n"
                  "def f(x):\n"
                  "    log.append(x)\n"
                  "    return x\n")
    assert [f.rule for f in _lint(src_append)] == ["MXL004"]
    # locals (even of nested fns) are trace-local — fine
    src_local = ("import jax\n"
                 "@jax.jit\n"
                 "def f(x):\n"
                 "    def body(y):\n"
                 "        rows = []\n"
                 "        rows.append(y)\n"
                 "        return rows[0]\n"
                 "    acc = {}\n"
                 "    acc['y'] = body(x)\n"
                 "    return acc['y']\n")
    assert _lint(src_local) == []
    # nonlocal at the jit ROOT reaches outside the trace — a hazard
    src_nonlocal = ("import jax\n"
                    "def make_step():\n"
                    "    count = 0\n"
                    "    @jax.jit\n"
                    "    def f(x):\n"
                    "        nonlocal count\n"
                    "        count += 1\n"
                    "        return x * count\n"
                    "    return f\n")
    assert [f.rule for f in _lint(src_nonlocal)] == ["MXL004"]
    # nonlocal to a binding INSIDE the jit body is trace-local — fine
    src_inner = ("import jax\n"
                 "@jax.jit\n"
                 "def f(x):\n"
                 "    acc = 0\n"
                 "    def body(y):\n"
                 "        nonlocal acc\n"
                 "        acc = acc + y\n"
                 "        return acc\n"
                 "    return body(x)\n")
    assert _lint(src_inner) == []
    # global mutation is module state wherever it is declared
    src_global = ("import jax\n"
                  "count = 0\n"
                  "@jax.jit\n"
                  "def f(x):\n"
                  "    global count\n"
                  "    count += 1\n"
                  "    return x\n")
    assert [f.rule for f in _lint(src_global)] == ["MXL004"]


def test_mxlint_missing_donate():
    src = ("import jax\n"
           "def train_step(params, batch):\n"
           "    return params\n"
           "f = jax.jit(train_step)\n")
    assert [f.rule for f in _lint(src)] == ["MXL005"]
    src_ok = ("import jax\n"
              "def train_step(params, batch):\n"
              "    return params\n"
              "f = jax.jit(train_step, donate_argnums=(0,))\n")
    assert _lint(src_ok) == []
    src_deco = ("import jax\n"
                "@jax.jit\n"
                "def fused_step(params, batch):\n"
                "    return params\n")
    assert [f.rule for f in _lint(src_deco)] == ["MXL005"]
    # non-step names are not second-guessed
    src_fwd = ("import jax\n"
               "def fwd(params, batch):\n"
               "    return params\n"
               "f = jax.jit(fwd)\n")
    assert _lint(src_fwd) == []


# ------------------------------------------------------------- CI gate

def _ci_check():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import ci_check
    finally:
        sys.path.pop(0)
    return ci_check


@pytest.mark.parametrize("stage", [sid for sid, _fn in _ci_check().STAGES])
def test_ci_stage(stage):
    """The tier-1 gate, one case a stage of ``tools/ci_check.py``'s
    table (mxlint over the repo, registry selfcheck, the verifier over
    the model zoo, ...), in the table's order."""
    lines = []
    failures = _ci_check().run_stage(stage, REPO, out=lines.append)
    assert failures == [], "\n".join(failures)
    assert any(stage in line for line in lines)


def test_cli_main_inprocess():
    from mxnet_tpu.analysis.__main__ import main
    assert main(["--model", "mlp", "--registry"]) == 0
    # lenet's conv/classifier params are not divisible by 8 and carry no
    # replicate annotation — sharded verification must fail loudly
    assert main(["--model", "lenet", "--tp", "8"]) == 1


@pytest.mark.slow
def test_cli_subprocess():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", "--model", "mlp"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
