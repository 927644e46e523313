"""ShardedTrainer on the virtual 8-device CPU mesh.

Covers the fused pjit path bench.py uses (VERDICT r1 weak #7: a
regression there was invisible to CI): layout modes, pluggable
optimizers, reference wd_mult exemptions, and honest initializer errors.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import ShardedTrainer, build_mesh


def _small_convnet(num_classes=10):
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                             no_bias=True, name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    # global pool before Flatten keeps the FC input layout-invariant, so
    # NHWC/NCHW runs share parameter semantics (ResNet/Inception style)
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _batch(batch=8, image=8, classes=10, seed=0):
    rng = np.random.RandomState(seed)
    # non-unit variance + offset: scale-sensitive gradient bugs (e.g. a
    # stray inv factor in BN backward) are invisible on ~N(0,1) data
    data = (rng.uniform(-1, 1, (batch, 3, image, image)) * 3.0 + 0.5)
    return {
        "data": data.astype(np.float32),
        "softmax_label": rng.randint(0, classes, batch).astype(np.float32),
    }


def _make(layout=None, **kw):
    mesh = build_mesh(tp=1)
    kw.setdefault("learning_rate", 0.1)
    return ShardedTrainer(
        _small_convnet(), mesh,
        data_shapes={"data": (8, 3, 8, 8)},
        label_shapes={"softmax_label": (8,)},
        layout=layout, seed=3, **kw)


def test_nhwc_matches_nchw():
    """NHWC is a pure layout change: identical math, identical losses."""
    b = _batch()
    t_nchw = _make(layout=None)
    t_nhwc = _make(layout="NHWC")
    for step in range(3):
        l0 = float(t_nchw.step(b))
        l1 = float(t_nhwc.step(b))
        assert np.isfinite(l0)
        np.testing.assert_allclose(l0, l1, rtol=2e-4), step
    # params stay f32 masters in both
    assert all(v.dtype == np.float32 for v in t_nchw.params.values())
    w0 = np.asarray(t_nchw.params["conv1_weight"])
    w1 = np.asarray(t_nhwc.params["conv1_weight"])
    np.testing.assert_allclose(w0, w1, rtol=1e-3, atol=1e-5)


def test_loss_decreases_sgd():
    t = _make()
    b = _batch()
    first = float(t.step(b))
    for _ in range(15):
        last = float(t.step(b))
    assert last < first


def test_adam_optimizer():
    t = _make(optimizer="adam", optimizer_params={"learning_rate": 1e-2})
    b = _batch()
    first = float(t.step(b))
    for _ in range(15):
        last = float(t.step(b))
    assert last < first
    # adam carries two state slots per param
    assert all(len(s) == 2 for s in t.opt_state.values())


def test_wd_exempts_bias_and_gamma():
    """Reference wd_mult defaults: no decay for params not ending in
    _weight/_gamma (python/mxnet/optimizer.py set_wd_mult)."""
    t = _make(weight_decay=0.5)
    _, wd_bias = t._per_param_hyper("fc1_bias")
    _, wd_beta = t._per_param_hyper("bn1_beta")
    _, wd_w = t._per_param_hyper("conv1_weight")
    assert wd_bias == 0.0 and wd_beta == 0.0
    assert wd_w == pytest.approx(0.5)


def test_initializer_error_propagates():
    class Bad(mx.init.Initializer):
        def _init_weight(self, name, arr):
            arr[:] = np.zeros((1, 2, 3))  # wrong shape: must raise

    with pytest.raises(Exception):
        _make(initializer=Bad())


def test_bfloat16_compute_f32_masters():
    t = _make(dtype="bfloat16")
    b = _batch()
    for _ in range(3):
        loss = float(t.step(b))
    assert np.isfinite(loss)
    assert all(v.dtype == np.float32 for v in t.params.values())
    assert all(v.dtype == np.float32 for v in t.aux.values())


def test_forward_inference():
    t = _make(layout="NHWC")
    heads = t.forward(_batch())
    probs = np.asarray(heads[0], np.float32)
    assert probs.shape == (8, 10)
    np.testing.assert_allclose(probs.sum(-1), np.ones(8), rtol=1e-3)


def test_lr_scheduler_applies():
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    t = _make(optimizer_params={"lr_scheduler": sched,
                                "learning_rate": 0.2})
    b = _batch()
    t.step(b)
    assert t.optimizer.lr_scheduler(t.optimizer.num_update) == \
        pytest.approx(0.2)
    for _ in range(4):
        t.step(b)
    assert sched(t.optimizer.num_update) < 0.2


def test_forward_accepts_staged_batch():
    """put_batch output must not be re-transposed by forward (NHWC)."""
    t = _make(layout="NHWC")
    staged = t.put_batch(_batch())
    heads = t.forward(staged)
    assert np.asarray(heads[0]).shape == (8, 10)


def test_post_build_lr_mult_honored():
    """Reference workflow: set_lr_mult after construction must apply."""
    b = _batch()
    t = _make()
    t.step(b)
    before = {k: np.asarray(v) for k, v in t.params.items()}
    t.optimizer.set_lr_mult({n: 0.0 for n in t.params})
    t.optimizer.momentum = 0.0  # kill momentum carry-over too
    t.step(b)
    after = t.params
    for k in before:
        # lr_mult 0 (and no wd) => params unchanged up to momentum decay
        np.testing.assert_allclose(before[k], np.asarray(after[k]),
                                   rtol=0, atol=1e-4)


def test_nhwc_guard_rejects_axis_ops():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                             name="c1")
    net = mx.sym.softmax(net, axis=-3)  # channel softmax in NCHW convention
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = build_mesh(tp=1)
    with pytest.raises(Exception, match="NHWC"):
        ShardedTrainer(net, mesh, data_shapes={"data": (8, 3, 8, 8)},
                       label_shapes={"softmax_label": (8,)}, layout="NHWC")


def test_nhwc_deconv_builds():
    """Deconvolution shape hook must resolve channels under NHWC."""
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                             name="c1")
    net = mx.sym.Deconvolution(net, kernel=(2, 2), stride=(2, 2),
                               num_filter=4, name="d1")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = build_mesh(tp=1)
    t = ShardedTrainer(net, mesh, data_shapes={"data": (8, 3, 8, 8)},
                       label_shapes={"softmax_label": (8,)}, layout="NHWC")
    assert t.params["d1_weight"].shape == (4, 4, 2, 2)
    loss = float(t.step(_batch()))
    assert np.isfinite(loss)


def test_out_of_range_label_finite_loss():
    """Monitoring loss stays finite when a label exceeds the class count
    (take_along_axis must clip, not NaN-fill, under jit)."""
    np.random.seed(0)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = build_mesh(tp=1)
    t = ShardedTrainer(net, mesh, data_shapes={"data": (8, 4)},
                       label_shapes={"softmax_label": (8,)})
    labels = np.arange(8, dtype=np.float32) % 5  # values up to 4 >= 2 classes
    loss = float(t.step({"data": np.random.randn(8, 4).astype(np.float32),
                         "softmax_label": labels}))
    assert np.isfinite(loss)


def test_bench_script_cpu_smoke(monkeypatch, capsys):
    """bench.py end-to-end on the CPU mesh (tiny config).

    Dry-run is the smoke contract: without it bench.py runs the full
    ResNet-50 config, which on the 8-device virtual CPU mesh never
    finishes inside the tier-1 window (and starves every test after
    this file of its budget)."""
    import importlib.util
    import json as _json
    import os
    monkeypatch.setenv("BENCH_DRYRUN", "1")
    # by path: another test file of the same worker may have put tools/
    # (which holds a bench.py of its own) ahead of the root on sys.path
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(root, "bench.py"))
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)
    bench_mod.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = _json.loads(line)
    assert rec["unit"] in ("img/s/chip", "samples/s/chip")
    assert rec["value"] > 0


def test_trainer_checkpoint_roundtrip_and_module_interop(tmp_path):
    """save_checkpoint/load_checkpoint on the fused path: params,
    optimizer slots, and step counter resume identically; the files are
    Module-format (arg:/aux: prefixes + symbol JSON)."""
    import os
    prefix = os.path.join(str(tmp_path), "ck")

    t1 = _make(optimizer="adam")
    b = t1.put_batch(_batch())
    for _ in range(3):
        loss_before = float(t1.step(b))
    t1.save_checkpoint(prefix, 3, save_optimizer_states=True)

    t2 = _make(optimizer="adam")
    t2.load_checkpoint(prefix, 3, load_optimizer_states=True)
    for k in t1.params:
        np.testing.assert_allclose(np.asarray(t2.params[k]),
                                   np.asarray(t1.params[k]),
                                   rtol=1e-6, err_msg=k)
    b2 = t2.put_batch(_batch())
    l1 = float(t1.step(b))
    l2 = float(t2.step(b2))
    np.testing.assert_allclose(l1, l2, rtol=1e-5)

    # Module can read the same files (reference checkpoint interop)
    sym, args, auxs = mx.model.load_checkpoint(prefix, 3)
    assert set(args) == set(t1.params)


def test_trainer_checkpoint_optimizer_mismatch_raises(tmp_path):
    import os
    prefix = os.path.join(str(tmp_path), "mm")
    t1 = _make(optimizer="adam")
    b = t1.put_batch(_batch())
    float(t1.step(b))
    t1.save_checkpoint(prefix, 1, save_optimizer_states=True)
    t2 = _make(optimizer="sgd")
    with pytest.raises(mx.base.MXNetError, match="optimizer state"):
        t2.load_checkpoint(prefix, 1, load_optimizer_states=True)


# ------------------------------------------- span records (telemetry.spans)

def _tiny_mlp_trainer():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    t = ShardedTrainer(net, build_mesh(tp=1), data_shapes={"data": (8, 12)},
                       label_shapes={"softmax_label": (8,)},
                       learning_rate=0.1, seed=5)
    rng = np.random.RandomState(0)
    batch = t.put_batch({
        "data": rng.randn(8, 12).astype(np.float32),
        "softmax_label": rng.randint(0, 4, 8).astype(np.float32)})
    return t, batch


def _children(rec, recs):
    return sorted((r for r in recs if r.parent == rec.id),
                  key=lambda r: r.start)


def test_build_span_has_its_phases_as_children():
    from mxnet_tpu.telemetry import spans
    t0 = time.perf_counter()
    _tiny_mlp_trainer()
    recs = spans.records(since=t0)
    (build,) = [r for r in recs if r.name == "trainer.build"]
    kids = _children(build, recs)
    assert [k.name for k in kids] == [
        "trainer.build.graph", "trainer.build.init_params",
        "trainer.build.place", "trainer.build.plan"]
    for a, b in zip(kids, kids[1:]):
        assert a.end <= b.start
    assert build.start <= kids[0].start and kids[-1].end <= build.end
    assert spans.self_time(build, recs) >= 0.0


def test_run_steps_span_children_say_where_a_dispatch_goes():
    from mxnet_tpu.telemetry import spans
    t, batch = _tiny_mlp_trainer()

    def dispatch():
        t0 = time.perf_counter()
        t.run_steps(batch, 2)
        recs = spans.records(since=t0)
        (whole,) = [r for r in recs if r.name == "trainer.run_steps"]
        return whole, _children(whole, recs), recs

    # the first dispatch compiles: lowering and compiling lie between
    # .prepare and .launch, named for the program, through the one seam
    whole, kids, recs = dispatch()
    assert whole.attrs == {"steps": 2}
    assert [k.name for k in kids] == [
        "trainer.run_steps.prepare", "program.lower", "program.compile",
        "program.plan", "trainer.run_steps.launch",
        "trainer.run_steps.account"]
    assert kids[1].attrs == kids[2].attrs == kids[3].attrs == \
        {"program": "trainer.run_steps"}
    assert not [r for r in recs if r.name.endswith(".sync")]

    # the first dispatch after the compile: exactly the three phases,
    # in order, without overlap, covering the span; the cost database
    # blocks on its output (MXNET_TPU_COSTDB_SAMPLE: first, then 16th)
    whole, kids, recs = dispatch()
    assert [k.name for k in kids] == [
        "trainer.run_steps.prepare", "trainer.run_steps.launch",
        "trainer.run_steps.account"]
    for a, b in zip(kids, kids[1:]):
        assert a.end <= b.start
    assert whole.start <= kids[0].start and kids[-1].end <= whole.end
    covered = sum(k.end - k.start for k in kids)
    assert covered >= 0.9 * (whole.end - whole.start)
    account = kids[-1]
    inside = [r.name for r in _children(account, recs)]
    assert inside == ["trainer.run_steps.sync", "telemetry.step_end"]

    # the second after it: no sync
    whole, kids, recs = dispatch()
    assert [k.name for k in kids] == [
        "trainer.run_steps.prepare", "trainer.run_steps.launch",
        "trainer.run_steps.account"]
    assert [r.name for r in _children(kids[-1], recs)] == \
        ["telemetry.step_end"]


def test_step_span_covers_the_whole_method():
    from mxnet_tpu.telemetry import spans
    t, batch = _tiny_mlp_trainer()
    t.step(batch)
    t0 = time.perf_counter()
    t.step(batch)
    recs = spans.records(since=t0)
    (whole,) = [r for r in recs if r.name == "trainer.step"]
    assert whole.attrs == {"step": 2}
    assert [k.name for k in _children(whole, recs)] == [
        "trainer.step.prepare", "trainer.step.launch",
        "trainer.step.account"]
