"""The readers that split ``setup_s`` by the program's span records
(``layer_metrics/setup_spans.py`` and the seven that use it), on hand-made records
in the two orders the cells have: the package imported before the reference runs
(Trinity-Mini) and inside ``runner.open`` after it (the other five).  The session
starts at the first ``model.build`` after the last ``mxnet_tpu.import`` in both, so
the reference's own compiles stay out; a traced run's profiler start, after the
session's last record, stays out too.

Run: ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402
from mxnet_tpu.telemetry import spans  # noqa: E402

READERS = ("build_graph_s", "build_plan_s", "model_build_s", "put_batch_s",
           "first_dispatch_s", "jit_unplanned_s", "setup_unspanned_s")
CTX = {"samples": [(100.0, 100.01, 100.8, [1.0])]}


def read(name, ctx=CTX):
    return run.load_module("layer_metrics", name).read(ctx)


def put(name, start, end, rid, parent=None, thread=1, **attrs):
    spans._ring.append(spans.Record(name, start, end, rid, parent, thread, attrs or None))


def session_records():
    """One session from 40.0: model, mesh, build, the harness's reseed, the batch,
    one first dispatch, the harness's ``change``; then the window."""
    put("model.build", 40.0, 41.0, 10, model="toy")
    put("mesh.build", 41.0, 41.5, 11)
    put("trainer.build.graph", 42.0, 46.0, 13, parent=12)
    put("trainer.build.plan", 46.0, 46.5, 14, parent=12)
    put("trainer.build", 42.0, 47.0, 12)
    put("jax.compile", 47.0, 48.0, 15, fun_name="jit(init_params)", cache_hit=True)
    put("trainer.put_batch", 48.0, 50.0, 16, host_bytes=1 << 20, inputs=2)
    put("program.lower", 52.5, 55.0, 18, parent=17)
    put("jax.compile", 55.0, 58.0, 20, parent=19, fun_name="jit(chain)", cache_hit=True)
    put("program.compile", 55.0, 58.0, 19, parent=17)
    put("program.plan", 58.0, 58.5, 21, parent=17)
    put("trainer.run_steps", 52.0, 60.0, 17, steps=2)
    put("jax.compile", 61.0, 61.5, 22, fun_name="jit(change)", cache_hit=True)
    put("compile.worker", 40.0, 62.0, 23, thread=2)      # another thread's
    put("trainer.run_steps", 100.0, 100.01, 30, steps=2)


@pytest.fixture(params=["import_before_reference", "import_inside_open"])
def order(request):
    spans.clear()
    if request.param == "import_before_reference":
        put("mxnet_tpu.import", 5.0, 9.0, 1)
        put("jax.compile", 10.0, 30.0, 2, fun_name="jit(reference)")
    else:
        put("jax.compile", 5.0, 25.0, 2, fun_name="jit(reference)")
        put("mxnet_tpu.import", 30.0, 39.0, 1)
    session_records()
    yield request.param
    spans.clear()


def test_the_session_starts_at_the_model_and_keeps_the_reference_out(order):
    assert read("model_build_s") == pytest.approx(1.0)
    assert read("build_graph_s") == pytest.approx(4.0)
    assert read("build_plan_s") == pytest.approx(0.5)
    assert read("put_batch_s") == pytest.approx(2.0)
    # 8.0 of the call less the planned 2.5 + 3.0
    assert read("first_dispatch_s") == pytest.approx(2.5)
    # init_params, change: the reference's 20 s are before the session
    assert read("jit_unplanned_s") == pytest.approx(1.5)
    # 40.0 .. 61.5 on the session's thread, less 1 + 0.5 + 5 + 1 + 2 + 8 + 0.5
    assert read("setup_unspanned_s") == pytest.approx(3.5)


def test_the_profilers_start_and_the_window_stay_out(order):
    # the same records under a window that opens later: nothing changes, since
    # the session ends at its last record and not at the window
    later = {"samples": [(100.0 - 1e-9, 100.01, 100.8, [1.0])]}
    assert read("setup_unspanned_s", later) == pytest.approx(3.5)
    # a model built again before the last import starts no session
    put("mxnet_tpu.import", 62.0, 63.0, 40)
    assert read("setup_unspanned_s") is None and read("jit_unplanned_s") is None
    assert read("model_build_s") == pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_none_where_the_program_lacks_the_record(name, monkeypatch):
    spans.clear()
    # the parent of the PR that brought these readers: no such record anywhere
    put("mxnet_tpu.import", 10.0, 14.0, 1)
    put("trainer.build", 20.0, 26.0, 2)
    assert read(name) is None
    # its three newer builders leave model.build: a session, but no jax.* record
    # and no spans.uncovered
    put("model.build", 15.0, 16.0, 3)
    monkeypatch.delattr(spans, "uncovered")
    if name != "model_build_s":
        assert read(name) is None
    # an older program still, whose span tracer keeps no records at all
    monkeypatch.delattr(spans, "records")
    assert read(name) is None
    spans.clear()
