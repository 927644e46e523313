"""The per-layer readers of the program's span records, on a hand-made ``ctx``
and hand-made records: the set-up / window cut at ``samples[0][0]``, the median
over dispatches, ``None`` when the program keeps no such record.

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

READERS = ("import_s", "build_s", "init_params_s", "lower_s", "prepare_ms",
           "launch_ms", "account_ms", "host_syncs_in_window")

#: three dispatches of the window, as ``run.measure`` samples them: (enqueue
#: start, enqueue end, fetch end, losses) on ``time.perf_counter()``; the last
#: one's enqueue waits for the device inside the program (its ``.sync``)
CTX = {"samples": [(100.0, 100.010, 100.8, [1.0]), (100.8, 100.806, 101.6, [1.0]),
                   (101.6, 102.409, 102.41, [1.0])]}


def read(name, ctx=CTX):
    return run.load_module("layer_metrics", name).read(ctx)


def put(name, start, end, rid, parent=None, **attrs):
    spans._ring.append(spans.Record(name, start, end, rid, parent, 1, attrs or None))


@pytest.fixture(autouse=True)
def hand_made_records():
    spans.clear()
    # -- set-up: everything that ended before samples[0][0] = 100.0
    put("mxnet_tpu.import", 10.0, 14.5, 1)
    put("trainer.build.graph", 20.0, 21.0, 3, parent=2)
    put("trainer.build.init_params", 21.0, 24.0, 4, parent=2)
    put("trainer.build.place", 24.0, 26.0, 5, parent=2)
    put("trainer.build.plan", 26.0, 26.5, 6, parent=2)
    put("trainer.build", 20.0, 26.5, 2)
    put("program.lower", 30.0, 32.0, 7, program="trainer.run_steps")
    put("program.compile", 32.0, 40.0, 8, program="trainer.run_steps")
    put("program.lower", 50.0, 53.0, 9, program="trainer.run_steps")
    # the first steps' dispatches: before the window, so no window reader's
    put("trainer.run_steps.prepare", 60.0, 60.5, 11, parent=10)
    put("trainer.run_steps.launch", 60.5, 60.6, 12, parent=10)
    put("trainer.run_steps.sync", 60.6, 61.4, 14, parent=13)
    put("trainer.run_steps.account", 60.6, 61.5, 13, parent=10)
    # -- the window's three dispatches: prepare 2, 4, 3 ms; launch 1, 1, 5 ms;
    # account 0.5 ms, 0.7 ms and 800.6 ms of which 800 ms waiting in .sync
    rid = 100
    for a, prep, launch, account, sync in ((100.0, 0.002, 0.001, 0.0005, 0.0),
                                          (100.8, 0.004, 0.001, 0.0007, 0.0),
                                          (101.6, 0.003, 0.005, 0.8006, 0.8)):
        whole, rid = rid, rid + 5
        put("trainer.run_steps.prepare", a, a + prep, whole + 1, parent=whole)
        t = a + prep
        put("trainer.run_steps.launch", t, t + launch, whole + 2, parent=whole)
        t += launch
        if sync:
            put("trainer.run_steps.sync", t, t + sync, whole + 4, parent=whole + 3)
        put("trainer.run_steps.account", t, t + account, whole + 3, parent=whole)
        put("trainer.run_steps", a, t + account, whole, steps=10)
    # a record after the window's last fetch: outside it
    put("trainer.run_steps.prepare", 102.5, 102.9, 200)
    put("program.lower", 103.0, 109.0, 201, program="late")
    yield
    spans.clear()


def test_setup_readers_take_what_ended_before_the_window():
    assert read("import_s") == pytest.approx(4.5)
    assert read("build_s") == pytest.approx(6.5)            # the parent alone
    assert read("init_params_s") == pytest.approx(3.0 + 2.0)
    assert read("lower_s") == pytest.approx(2.0 + 3.0)      # not the late one


def test_window_readers_take_the_median_over_the_windows_dispatches():
    assert read("prepare_ms") == pytest.approx(3.0)
    assert read("launch_ms") == pytest.approx(1.0)
    # the wait inside .account is .sync's, not the accounting's
    assert read("account_ms") == pytest.approx(0.6)
    assert read("host_syncs_in_window") == 1                # set-up's is not counted


def test_the_cut_moves_with_the_samples():
    early = {"samples": [(59.0, 59.5, 62.0, [1.0])]}
    assert read("prepare_ms", early) == pytest.approx(500.0)
    assert read("host_syncs_in_window", early) == 1
    assert read("account_ms", early) == pytest.approx(100.0)
    assert read("lower_s", early) == pytest.approx(5.0)
    assert read("build_s", early) == pytest.approx(6.5)
    before_all = {"samples": [(5.0, 5.5, 6.0, [1.0])]}
    for name in READERS[:4]:
        assert read(name, before_all) is None


@pytest.mark.parametrize("name", READERS)
def test_none_when_the_span_is_absent(name, monkeypatch):
    spans.clear()
    # a program that records none of these spans: nothing to read
    put("executor.forward", 100.1, 100.2, 1)
    expected = 0 if name == "host_syncs_in_window" else None
    assert read(name) == expected
    # an older program, whose span tracer keeps no records at all
    monkeypatch.delattr(spans, "records")
    assert read(name) is None
