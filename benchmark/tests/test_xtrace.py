"""The trace reduction on small traces: a hand-made one whose numbers are worked
out by hand, and the recorded one under ``testdata/`` against a brute-force
rasterisation.  Run: ``python3 -m pytest benchmark/tests -q``."""
import glob
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import xtrace  # noqa: E402


def hand_made():
    ops = [
        # a while loop 100..900 holding a fusion, a Pallas call and a collective
        ["%while.1", 100.0, 800.0, "while"],
        ["%fusion.1", 100.0, 200.0, "fusion:Output"],
        ["%custom-call.2", 300.0, 100.0, "custom-call:tpu_custom_call"],
        ["%all-reduce.3", 400.0, 200.0, "all-reduce"],
        ["%fusion.4", 500.0, 300.0, "fusion:Loop"],   # overlaps the collective's second half
        ["%copy.5", 1000.0, 100.0, "copy"],
    ]
    host = [["bench:enqueue", 0.0, 50.0, ""], ["bench:fetch", 50.0, 1150.0, ""]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_union_subtract_and_self_times():
    assert xtrace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xtrace.total(xtrace.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert xtrace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    own = xtrace.self_times([("w", 0, 10, ""), ("a", 1, 4, ""), ("b", 5, 9, "")])
    assert own == {"a": 3e-9, "b": 4e-9, "w": 3e-9}


def test_hand_made_trace():
    red = xtrace.reduce(hand_made())
    # window: first enqueue's start (0) to last fetch's end (1200)
    assert red["window_s"] == pytest.approx(1200e-9)
    # leaves: 100-300, 300-400, 400-600, 500-800, 1000-1100 -> union 700 + 100
    assert red["busy_s"] == pytest.approx(800e-9)
    assert red["idle_pct"] == pytest.approx(100 * (1 - 800 / 1200))
    assert red["pallas_pct"] == pytest.approx(100 * 100 / 800)
    # the collective runs alone 400-500 only
    assert red["collective_exposed_pct"] == pytest.approx(100 * 100 / 1200)
    gaps = dict(red["idle_gaps"])
    assert gaps["enqueue"] == pytest.approx(50e-9)      # 0-100 has its middle in enqueue
    assert gaps["fetch"] == pytest.approx(350e-9)       # 800-1000, 1100-1200 and half... see below
    assert red["device_ops"][0][0] == "%fusion.4 fusion:Loop"


def test_names_and_classifiers():
    text = ('%fusion.2303 = (f32[256]{0:T(256)S(1)}, bf16[128,56,56,256]{3,0,2,1:T(8,128)(2,1)}) '
            'fusion(bf16[128,28,28,512]{3,0,2,1:T(8,128)(2,1)S(1)} %custom-call.8), kind=kOutput, '
            'calls=%fused_computation.53')
    assert xtrace.short(text) == ("%fusion.2303", "fusion:Output")
    call = ('%custom-call.8 = bf16[128,28,28,512]{3,0,2,1:T(8,128)(2,1)S(1)} custom-call(bf16[1,2] %x), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert xtrace.short(call) == ("%custom-call.8", "custom-call:tpu_custom_call")
    assert xtrace.short("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b")[1] == "while"
    assert xtrace.is_container("%while.12", "while") and not xtrace.is_container("%fusion.3", "fusion:Loop")
    assert xtrace.is_pallas(*xtrace.short(call)) and not xtrace.is_pallas(*xtrace.short(text))
    assert not xtrace.is_pallas("%custom-call.2", "custom-call:AllocateBuffer")
    assert xtrace.is_collective("%all-reduce.1", "all-reduce") and not xtrace.is_collective("%copy.1", "copy")
    assert xtrace.is_collective("%all-reduce-start.1", "all-reduce-start")


def _raster_busy(ops, lo, hi, step):
    n = int((hi - lo) / step)
    hit = [False] * n
    for name, s, e, _c in ops:
        if xtrace.is_container(name, _c):
            continue
        for i in range(max(0, int((s - lo) / step)), min(n, int((e - lo) / step) + 1)):
            mid = lo + (i + 0.5) * step
            if s <= mid < e:
                hit[i] = True
    return sum(hit) * step


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(os.path.dirname(HERE), "testdata",
                                                               "*.json.gz"))) or [None])
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace under testdata/")
    trace = xtrace.load(path)
    red = xtrace.reduce(trace)
    lo, hi = xtrace.window_of(trace)
    ops = next(iter(xtrace.device_ops(trace).values()))
    step = (hi - lo) / 20000
    assert red["busy_s"] * 1e9 == pytest.approx(_raster_busy(ops, lo, hi, step), rel=2e-2)
    assert 0 <= red["idle_pct"] <= 100 and 0 <= red["pallas_pct"] <= 100
    assert red["device_ops"] and red["window_s"] > 0
