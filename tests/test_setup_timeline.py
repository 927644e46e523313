"""Set-up as one timeline of span records: JAX's traces, lowerings and
compiles as ``jax.*`` records under the span open on the compiling thread,
``spans.uncovered``, the records of the program's set-up work
(``mesh.build``, ``trainer.put_batch``, ``model.build``, ``program.plan``,
``process.before_import``), and the benchmark's readers that split
``setup_s`` by them."""
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.parallel import ShardedTrainer, build_mesh
from mxnet_tpu.telemetry import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
#: the benchmark's harness (``benchmark/run.py``: its loader finds the readers),
#: set for this file's tests by ``_benchmark_modules``
run = None
ME = threading.get_ident()


@pytest.fixture(scope="module", autouse=True)
def _benchmark_modules():
    """The benchmark's modules, importable while this file's tests run and
    gone after them (``tests/test_lfm2_moe.py`` has why)."""
    global run
    path, before = list(sys.path), dict(sys.modules)
    shadowed = {name: sys.modules.pop(name) for name in ("common", "run")
                if name in sys.modules}
    sys.path[:0] = [BENCH, os.path.join(ROOT, "examples", "transformer")]
    import run as harness
    run = harness
    yield
    sys.path[:] = path
    for name, mod in list(sys.modules.items()):
        if name not in before and \
                (getattr(mod, "__file__", None) or "").startswith(BENCH):
            del sys.modules[name]
    sys.modules.update(shadowed)


def _jax_records(recs, fun):
    return {r.name: r for r in recs
            if r.name.startswith("jax.") and fun in r.attrs["fun_name"]}


def test_a_jit_inside_a_span_leaves_trace_lower_compile_records():
    import jax
    import jax.numpy as jnp

    def setup_timeline_probe(x):
        return jnp.sin(x) * 2.0 + 1.0

    fn = jax.jit(setup_timeline_probe)
    compiles = telemetry.counter("mxtpu_compile_total")
    seconds = telemetry.counter("mxtpu_compile_seconds_total")
    x = jnp.ones(7)
    n0, s0 = compiles.get(), seconds.get()
    with telemetry.span("probe.outer"):
        fn(x)
    t1 = time.perf_counter()
    (outer,) = spans.records("probe.outer")
    found = _jax_records(spans.records("jax."), "setup_timeline_probe")
    assert sorted(found) == ["jax.compile", "jax.lower", "jax.trace"]
    for r in found.values():
        # on the perf_counter clock, inside the span, the span its parent
        assert outer.start <= r.start <= r.end <= outer.end
        assert r.parent == outer.id and r.thread == ME
    assert found["jax.trace"].end <= found["jax.lower"].end \
        <= found["jax.compile"].start + 1e-3
    assert found["jax.compile"].attrs["cache_hit"] is False
    # the counters the one listener also feeds still count
    assert compiles.get() == n0 + 1
    assert seconds.get() - s0 == pytest.approx(
        found["jax.compile"].end - found["jax.compile"].start, abs=1e-3)
    # a second call traces, lowers and compiles nothing
    with telemetry.span("probe.outer"):
        fn(x)
    assert not _jax_records(spans.records("jax.", since=t1),
                            "setup_timeline_probe")
    assert compiles.get() == n0 + 1


def test_a_trace_inside_a_trace_leaves_no_record_of_its_own():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def timeline_inner(x):
        return x * 3.0

    def timeline_outer(x):
        return timeline_inner(x) + jnp.cos(x)

    x = jnp.ones(5)
    t0 = time.perf_counter()
    jax.jit(timeline_outer).lower(x)
    traces = [r.attrs["fun_name"] for r in spans.records("jax.trace", since=t0)]
    assert traces == ["timeline_outer"]
    # outside any span and any trace: a root record
    (rec,) = spans.records("jax.trace", since=t0)
    assert rec.parent is None


def _put(name, start, end, rid, parent=None, thread=ME, **attrs):
    spans._ring.append(spans.Record(name, start, end, rid, parent, thread,
                                    attrs or None))


@pytest.fixture
def empty_ring():
    kept = list(spans._ring)
    spans.clear()
    yield
    spans.clear()
    spans._ring.extend(kept)


def test_uncovered_on_hand_made_records(empty_ring):
    # an empty ring: the whole interval, with no neighbour
    assert spans.uncovered(10.0, 20.0) == [spans.Gap(10.0, 20.0, None, None)]
    _put("a", 11.0, 13.0, 1)
    _put("a.child", 11.5, 12.0, 2, parent=1)        # nested: covers nothing new
    _put("b", 12.5, 14.0, 3)                        # overlaps a
    _put("other", 14.0, 19.5, 4, thread=ME + 1)     # another thread's
    _put("c", 17.0, 18.0, 5)
    _put("early", 5.0, 10.5, 6)                     # straddles the start
    gaps = spans.uncovered(10.0, 20.0)
    assert gaps == [spans.Gap(14.0, 17.0, "b", "c"),
                    spans.Gap(18.0, 20.0, "c", None),
                    spans.Gap(10.5, 11.0, "early", "a")]
    assert sum(g.end - g.start for g in gaps) == pytest.approx(5.5)
    # the other thread's timeline is its own
    assert spans.uncovered(14.0, 20.0, thread=ME + 1) == \
        [spans.Gap(19.5, 20.0, "other", None)]
    # a record over the whole interval leaves nothing
    _put("all", 9.0, 21.0, 7)
    assert spans.uncovered(10.0, 20.0) == []


def test_record_takes_its_parent_from_the_threads_open_span(empty_ring):
    with telemetry.span("timeline.open"):
        spans.record("timeline.made", 1.0, 2.0, k=1)
    spans.record("timeline.root", 3.0, 4.0)
    made, opened, root = spans.records("timeline.")
    assert made.parent == opened.id and made.attrs == {"k": 1}
    assert root.parent is None


def _toy_trainer():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    t = ShardedTrainer(net, build_mesh(tp=1), data_shapes={"data": (8, 12)},
                       label_shapes={"softmax_label": (8,)},
                       learning_rate=0.1, seed=5)
    rng = np.random.RandomState(0)
    host = {"data": rng.randn(8, 12).astype(np.float32),
            "softmax_label": rng.randint(0, 4, 8).astype(np.float32)}
    return t, host


def _dispatch(t, batch, steps):
    t0 = time.perf_counter()
    t.run_steps(batch, steps)
    recs = spans.records(since=t0)
    (whole,) = [r for r in recs if r.name == "trainer.run_steps"]
    return whole, recs


def _below(whole, recs):
    """The records with ``whole`` among their ancestors."""
    by_id = {r.id: r for r in recs}
    out = []
    for r in recs:
        up = by_id.get(r.parent)
        while up is not None and up.id != whole.id:
            up = by_id.get(up.parent)
        if up is not None:
            out.append(r)
    return out


def test_a_first_dispatch_is_covered_by_its_children_and_a_second_compiles_nothing():
    t, host = _toy_trainer()
    batch = t.put_batch(host)
    whole, recs = _dispatch(t, batch, 2)
    kids = sorted((r for r in recs if r.parent == whole.id), key=lambda r: r.start)
    assert [k.name for k in kids] == [
        "trainer.run_steps.prepare", "program.lower", "program.compile",
        "program.plan", "trainer.run_steps.launch", "trainer.run_steps.account"]
    covered = sum(k.end - k.start for k in kids)
    assert covered >= 0.95 * (whole.end - whole.start)
    # the chain program's own trace, lowering and compile lie under the seam's spans
    below = _below(whole, recs)
    lower = kids[1]
    assert [r.name for r in below if r.parent == lower.id and r.name == "jax.lower"]
    (compiled,) = [r for r in below if r.name == "jax.compile" and r.parent == kids[2].id]
    program = compiled.attrs["fun_name"]
    # the second call of the same chain: nothing planned, nothing jitted
    whole, recs = _dispatch(t, batch, 2)
    assert not [r.name for r in _below(whole, recs)
                if r.name.startswith(("program.", "jax."))]
    # a new chain length compiles inside the call: the ring names the function
    # that recompiled and the dispatch it happened in
    whole, recs = _dispatch(t, batch, 3)
    again = [r for r in _below(whole, recs) if r.name == "jax.compile"
             and r.attrs["fun_name"] == program]
    assert len(again) == 1
    assert whole.attrs == {"steps": 3}


def test_set_up_work_leaves_its_records():
    t0 = time.perf_counter()
    t, host = _toy_trainer()
    t.put_batch(host)
    recs = spans.records(since=t0)
    (mesh,) = [r for r in recs if r.name == "mesh.build"]
    assert mesh.attrs == {"devices": 8, "axes": {"data": 8, "model": 1}}
    (put,) = [r for r in recs if r.name == "trainer.put_batch"]
    assert put.attrs == {"host_bytes": 8 * 12 * 4 + 8 * 4, "inputs": 2}
    (build,) = [r for r in recs if r.name == "trainer.build"]
    assert mesh.end <= build.start <= build.end <= put.start


@pytest.mark.parametrize("model", ["resnet", "gpt"])
def test_model_builders_leave_a_model_build_record(model):
    t0 = time.perf_counter()
    if model == "resnet":
        from mxnet_tpu.models.resnet import resnet
        net = resnet(units=[1, 1], num_stages=2, filter_list=[8, 8, 16],
                     num_classes=4, image_shape=[3, 16, 16], bottle_neck=False)
    else:
        from train_lm import gpt_symbol
        net = gpt_symbol(32, 8, d_model=16, n_heads=2, n_layers=1)
    assert "softmax_label" in net.list_arguments()
    (rec,) = spans.records("model.build", since=t0)
    assert rec.attrs == {"model": model}


def test_the_process_before_the_import_is_a_root_record():
    # the ring is process-wide and tests empty it: read the module's own marks
    start = mx._process_start()
    assert start is not None and start <= mx._IMPORT_T0
    # CLOCK_BOOTTIME against /proc: steady to a clock tick between two readings
    assert mx._process_start() == pytest.approx(start, abs=0.05)
    assert mx._PROCESS_T0 == pytest.approx(start, abs=0.05)
    assert mx._JAX_IMPORTED in (True, False) and mx._BACKEND_UP in (True, False)
    assert not (mx._BACKEND_UP and not mx._JAX_IMPORTED)


# -- the benchmark's readers, through the harness's own loader ----------------

#: set-up ends at the window's first dispatch, 100.0; a traced run's profiler
#: start lies between the session's last record (61.5) and it
CTX = {"samples": [(100.0, 100.01, 100.8, [1.0])]}


@pytest.fixture
def hand_made_session(empty_ring):
    _put("process.before_import", 0.0, 10.0, 1)
    _put("mxnet_tpu.import", 10.0, 14.0, 2)
    _put("jax.compile", 15.0, 19.0, 3, fun_name="jit(reference)")   # before the session
    _put("model.build", 20.0, 20.5, 4, model="toy")
    _put("mesh.build", 20.5, 20.6, 5)
    _put("trainer.build.graph", 21.0, 23.0, 7, parent=6)
    _put("jax.trace", 23.1, 23.4, 8, parent=9, fun_name="init")
    _put("jax.compile", 23.4, 24.4, 10, parent=9, fun_name="jit(init)")
    _put("trainer.build.init_params", 23.0, 25.0, 9, parent=6)
    _put("trainer.build.plan", 25.0, 25.25, 11, parent=6)
    _put("trainer.build", 21.0, 25.5, 6)
    _put("jax.compile", 26.0, 26.5, 12, fun_name="jit(reseed)")     # the harness's own
    _put("trainer.put_batch", 27.0, 27.75, 13, host_bytes=64, inputs=2)
    # two first dispatches; the first one's planned trace is nested in its lowering
    for base, rid in ((30.0, 20), (50.0, 40)):
        _put("trainer.run_steps.prepare", base, base + 0.5, rid + 1, parent=rid)
        _put("jax.trace", base + 0.6, base + 1.5, rid + 3, parent=rid + 2, fun_name="chain")
        _put("jax.lower", base + 1.5, base + 2.0, rid + 4, parent=rid + 2, fun_name="jit(chain)")
        _put("program.lower", base + 0.5, base + 2.0, rid + 2, parent=rid)
        _put("jax.compile", base + 2.0, base + 5.0, rid + 6, parent=rid + 5, fun_name="jit(chain)")
        _put("program.compile", base + 2.0, base + 5.0, rid + 5, parent=rid)
        _put("program.plan", base + 5.0, base + 5.25, rid + 7, parent=rid)
        _put("trainer.run_steps.launch", base + 5.25, base + 6.0, rid + 8, parent=rid)
        _put("trainer.run_steps.account", base + 6.0, base + 6.5, rid + 9, parent=rid)
        _put("trainer.run_steps", base, base + 6.5, rid, steps=1)
    # the harness's first_grad: a trace with a jitted helper's compile inside it
    _put("jax.compile", 58.25, 58.5, 60, fun_name="jit(helper)")
    _put("jax.trace", 58.0, 59.0, 61, fun_name="first_grad")
    _put("jax.compile", 59.0, 61.5, 62, fun_name="jit(first_grad)")
    _put("noise", 40.0, 45.0, 63, thread=ME + 1)   # another thread: covers nothing
    # the window, and a record after it
    _put("trainer.run_steps", 100.0, 100.01, 70, steps=10)
    _put("model.build", 103.0, 104.0, 71)
    _put("trainer.put_batch", 104.0, 105.0, 72)


#: (reader, what the hand-made session gives)
EXPECTED = [
    ("build_graph_s", 2.0),
    ("build_plan_s", 0.25),
    ("model_build_s", 0.5),
    ("put_batch_s", 0.75),
    # 2 x 6.5 less the planned 2 x (1.5 + 3.0)
    ("first_dispatch_s", 4.0),
    # init 0.3 + 1.0, reseed 0.5, first_grad 1.0 (the helper's inside it once) + 2.5
    ("jit_unplanned_s", 5.3),
    # session 20.0 .. 61.5 on this thread: 41.5 less model 0.5, mesh 0.1, build 4.5,
    # reseed 0.5, put_batch 0.75, dispatches 13.0, first_grad 3.5
    ("setup_unspanned_s", 18.65),
]


@pytest.mark.parametrize("name,value", EXPECTED)
def test_reader_value(hand_made_session, name, value):
    assert run.load_module("layer_metrics", name).read(CTX) == pytest.approx(value)


@pytest.mark.parametrize("name,value", EXPECTED)
def test_reader_cuts_at_the_windows_first_dispatch(hand_made_session, name, value):
    # a later window takes the late records in; the profiler's gap stays out
    later = {"samples": [(110.0, 110.1, 110.2, [1.0])]}
    got = run.load_module("layer_metrics", name).read(later)
    if name in ("model_build_s", "put_batch_s"):
        assert got == pytest.approx(value + 1.0)
    elif name == "first_dispatch_s":
        assert got == pytest.approx(value + 0.01)
    elif name == "setup_unspanned_s":
        # the session now ends at 105.0: 61.5 .. 100.0 and 100.01 .. 103.0 join
        assert got == pytest.approx(value + 38.5 + 2.99)
    else:
        assert got == pytest.approx(value)
    # a window that starts before everything: nothing to read
    early = {"samples": [(5.0, 5.1, 5.2, [1.0])]}
    assert run.load_module("layer_metrics", name).read(early) is None


@pytest.mark.parametrize("name,_value", EXPECTED)
def test_reader_none_on_a_program_without_the_record(empty_ring, monkeypatch, name, _value):
    read = run.load_module("layer_metrics", name).read
    # the parent's records: a session (its three newer builders leave
    # model.build) but no jax.*, trainer.put_batch or spans.uncovered
    _put("mxnet_tpu.import", 10.0, 14.0, 1)
    _put("executor.forward", 20.0, 21.0, 2)
    assert read(CTX) is None
    if name in ("jit_unplanned_s", "setup_unspanned_s"):
        _put("model.build", 22.0, 23.0, 3)
        _put("trainer.run_steps", 30.0, 31.0, 4)
        monkeypatch.delattr(spans, "uncovered")
        assert read(CTX) is None
    # an older program still, whose span tracer keeps no records at all
    monkeypatch.delattr(spans, "records")
    assert read(CTX) is None
