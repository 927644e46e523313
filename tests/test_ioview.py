"""Data-plane observability (telemetry.ioview + tools/io_top.py).

Covers the contracts in docs/api/telemetry.md "Input-pipeline
observability": per-stage accounting oracles, time-weighted queue
occupancy (and the depth-gauge consistency fix), producer-starved vs
consumer-stalled attribution, the bottleneck classifier's edges, the
``position()`` API threaded through the DataIter chain and its
roundtrip through checkpoint-manifest meta, the per-step JSONL ``io``
block, io_top's renderings + ``--json`` schema, the run-timeline
io_bottleneck roll-up, and the 2-process end-to-end test where a
seeded slow decode on one rank is named (stage + rank) by
``run_top --summarize``.
"""
import importlib.util
import io as _pyio
import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import distview, flight, ioview

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_TELEMETRY_JSONL", raising=False)
    monkeypatch.delenv("MXNET_TPU_IOVIEW_EVERY", raising=False)
    monkeypatch.delenv("MXNET_TPU_IOVIEW_WINDOW", raising=False)
    telemetry.reset()
    yield
    from mxnet_tpu import resilience
    resilience.clear_faults()
    telemetry.reset()


# ------------------------------------------------- stage accounting

def test_account_oracle():
    ioview.account("decode", 0.25, items=3, nbytes=1000)
    ioview.account("decode", 0.75, items=1, nbytes=24)
    ioview.account("read", 0.1, items=2)
    snap = ioview.snapshot()
    assert snap["stages"]["decode"] == {"s": 1.0, "items": 4,
                                        "bytes": 1024}
    assert snap["stages"]["read"]["items"] == 2
    # the same numbers land in the catalog metrics
    h = telemetry.histogram("mxtpu_io_stage_seconds").labels(
        stage="decode").get()
    assert h["count"] == 2 and h["sum"] == pytest.approx(1.0)
    assert telemetry.counter("mxtpu_io_stage_items_total").labels(
        stage="decode").get() == 4
    assert telemetry.counter("mxtpu_io_bytes_total").labels(
        stage="decode").get() == 1024


def test_stall_starved_counters():
    ioview.note_stall("host", 0.2)
    ioview.note_starved("host", 0.3)
    ioview.note_starved("device", -1.0)        # clamped, never negative
    snap = ioview.snapshot()
    assert snap["stall_s"]["host"] == pytest.approx(0.2)
    assert snap["starved_s"]["host"] == pytest.approx(0.3)
    assert snap["starved_s"]["device"] == 0.0
    assert telemetry.counter(
        "mxtpu_io_prefetch_starved_seconds_total").labels(
        iter="host").get() == pytest.approx(0.3)


# ------------------------------------------- time-weighted occupancy

def test_occupancy_weighting(monkeypatch):
    clock = [100.0]
    monkeypatch.setattr(ioview, "_now", lambda: clock[0])
    tr = ioview.OccupancyTracker("host")
    tr.set_depth(0)            # t=100, depth 0
    clock[0] = 101.0
    tr.adjust(+1)              # 1s at depth 0
    clock[0] = 104.0
    tr.adjust(+1)              # 3s at depth 1
    clock[0] = 104.5
    tr.adjust(-1)              # 0.5s at depth 2
    snap = tr.snapshot()
    assert snap["depth"] == 1
    assert snap["levels"] == {"0": 1.0, "1": 3.0, "2": 0.5}
    # time-weighted mean: (0*1 + 1*3 + 2*0.5) / 4.5
    assert snap["mean"] == pytest.approx(4.0 / 4.5, abs=1e-3)
    # the weighted histogram: bucket counts are seconds-at-depth
    h = telemetry.histogram("mxtpu_io_queue_occupancy").labels(
        iter="host").get()
    assert h["count"] == pytest.approx(4.5)
    assert h["sum"] == pytest.approx(4.0)
    # the legacy gauge is the consistent last-observed depth
    assert telemetry.gauge("mxtpu_io_prefetch_depth").labels(
        iter="host").get() == 1.0


def test_device_prefetch_depth_consistent():
    """The satellite fix: the tracker owns the depth counter, so the
    exported depth cannot flap negative or stick above the queue; a
    drained iterator ends at depth 0."""
    x = np.arange(24 * 3, dtype=np.float32).reshape(24, 3)
    it = mx.io.NDArrayIter(x, np.zeros(24, np.float32), batch_size=4)
    pre = mx.io.DevicePrefetchIter(it, lambda d: d, depth=2)
    n = sum(1 for _ in pre)
    assert n == 6
    tr = ioview.queue_tracker("device")
    assert tr.depth() == 0
    assert telemetry.gauge("mxtpu_io_prefetch_depth").labels(
        iter="device").get() == 0.0
    levels = tr.snapshot()["levels"]
    assert all(float(d) >= 0 for d in levels)
    # device_stage accounted one unit per staged batch
    assert ioview.snapshot()["stages"]["device_stage"]["items"] == 6


# --------------------------------------------- bottleneck classifier

def test_classifier_edges():
    # no activity at all: no verdict
    assert ioview.classify(force=True) is None
    # producer-bound: the consumer stalls, decode is the slow stage
    ioview.account("decode", 1.0, items=10)
    ioview.account("read", 0.1, items=10)
    ioview.note_stall("host", 0.5)
    v = ioview.classify(force=True)
    assert v["verdict"] == "producer-bound" and v["stage"] == "decode"
    assert telemetry.counter("mxtpu_io_bottleneck_total").labels(
        stage="decode").get() == 1
    assert any(e.get("kind") == "io_bottleneck"
               for e in flight.events())
    # consumer-bound: producers starve waiting on a slow training loop
    ioview.note_starved("device", 0.8)
    v = ioview.classify(force=True)
    assert v["verdict"] == "consumer-bound" and v["stage"] == "consumer"
    # balanced: both sides comparable
    ioview.note_stall("host", 0.1)
    ioview.note_starved("host", 0.1)
    v = ioview.classify(force=True)
    assert v["verdict"] == "balanced"


def test_classifier_respects_window(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_IOVIEW_WINDOW", "3600")
    ioview.account("read", 0.5, items=1)
    ioview.note_stall("host", 0.5)
    assert ioview.classify() is None       # first call arms the window
    ioview.note_stall("host", 0.5)
    assert ioview.classify() is None       # window not elapsed: no verdict
    v = ioview.classify(force=True)
    assert v["verdict"] == "producer-bound"


def test_seeded_slow_prefetch_stage_named():
    """The ci_check stage-14 shape: a kind=delay io.prefetch fault is a
    seeded slow host_prefetch stage the classifier must name."""
    from mxnet_tpu import resilience
    resilience.configure_faults("io.prefetch:kind=delay,delay=0.02")
    x = np.zeros((16, 3), np.float32)
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(x, np.zeros(16, np.float32), batch_size=4))
    n = sum(1 for _ in it)
    assert n == 4
    v = ioview.classify(force=True)
    assert v["verdict"] == "producer-bound"
    assert v["stage"] == "host_prefetch"


def test_host_prefetch_excludes_inner_stage_time(tmp_path):
    """Review fix: a PrefetchingIter over a decode-bound pipeline must
    let the classifier name DECODE — host_prefetch accounts its wall
    exclusive of the inner stages running on the producer thread."""
    from mxnet_tpu import resilience
    rec = _tiny_rec(tmp_path / "t.rec", n=8)
    resilience.configure_faults("io.decode:kind=delay,delay=0.03")
    it = mx.io.PrefetchingIter(
        mx.image.ImageIter(batch_size=4, data_shape=(3, 8, 8),
                           path_imgrec=rec))
    n = sum(1 for _ in it)
    assert n == 2
    snap = ioview.snapshot()["stages"]
    assert snap["decode"]["s"] > snap["host_prefetch"]["s"]
    v = ioview.classify(force=True)
    assert v["verdict"] == "producer-bound" and v["stage"] == "decode"


def test_starved_ignores_idle_gaps():
    """Review fix: a producer parked across a validation pass (an
    interval far beyond the classifier window) is not backpressure and
    must not flip the verdict to consumer-bound."""
    ioview.note_starved("host", 60.0)       # idle gap: dropped
    assert ioview.snapshot()["starved_s"] == {}
    ioview.note_starved("host", 0.5)        # genuine backpressure
    assert ioview.snapshot()["starved_s"]["host"] == pytest.approx(0.5)


def test_summary_is_read_only():
    """Review fix: summary() must not rotate the live classifier
    window, bump the verdict counter, or touch the flight ring."""
    ioview.account("decode", 1.0, items=4)
    ioview.note_stall("host", 0.5)
    assert ioview.classify() is None        # arms the live window
    t0 = ioview._win_state["t0"]
    before_events = len([e for e in flight.events()
                         if e.get("kind") == "io_bottleneck"])
    for _ in range(3):
        s = ioview.summary()
    assert s["bottleneck"]["verdict"] == "producer-bound"
    assert s["bottleneck"]["stage"] == "decode"
    assert ioview._win_state["t0"] == t0    # window not rotated
    assert telemetry.counter("mxtpu_io_bottleneck_total").labels(
        stage="decode").get() == 0
    assert len([e for e in flight.events()
                if e.get("kind") == "io_bottleneck"]) == before_events


def test_device_prefetch_depth_survives_thread_races():
    """Review fix: +1 before the put, -1 after the take — the tracker
    can transiently over-read but never underflows into the 0-clamp
    (which would leave a permanent phantom batch).  Stressed with an
    aggressive switch interval."""
    import sys as _sys
    old = _sys.getswitchinterval()
    _sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            x = np.zeros((12, 3), np.float32)
            it = mx.io.NDArrayIter(x, np.zeros(12, np.float32),
                                   batch_size=4)
            pre = mx.io.DevicePrefetchIter(it, lambda d: d, depth=2)
            assert sum(1 for _ in pre) == 3
            assert ioview.queue_tracker("device").depth() == 0
    finally:
        _sys.setswitchinterval(old)


def test_shard_skew_ignores_unmeasured_ranks():
    """Review fix: a rank whose io blocks carry no window data must
    not be named 'slowest at 0 items/s'."""
    recs = []
    for r, window in ((0, 1.0), (1, 1.0), (2, None)):
        io = {"stages": {"read": {"s": 0.1, "items": 100 if r == 0
                                  else 50, "bytes": 1}}}
        if window:
            io["window_s"] = window
        recs.append({"step": 1, "rank": r, "io": io})
    doc = ioview.summarize_io(recs)
    assert doc["shard_skew"]["slowest_rank"] == 1
    assert doc["ranks"]["2"]["ingest_items_per_s"] is None


def test_prefetch_starved_measures_slow_consumer():
    """Satellite: a slow CONSUMER must show up as producer-starved
    time, not read as a healthy pipeline."""
    x = np.zeros((20, 3), np.float32)
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(x, np.zeros(20, np.float32), batch_size=4))
    for _b in it:
        time.sleep(0.02)               # the training loop is the slow side
    snap = ioview.snapshot()
    assert snap["starved_s"].get("host", 0.0) > 0.05
    v = ioview.classify(force=True)
    assert v["verdict"] == "consumer-bound"


# --------------------------------------------------------- position

def test_position_threading_ndarray_and_wrappers():
    x = np.arange(24 * 3, dtype=np.float32).reshape(24, 3)
    it = mx.io.NDArrayIter(x, np.zeros(24, np.float32), batch_size=4)
    assert it.position() == {"epoch": 0, "offset": 0}
    it.next()
    it.next()
    assert it.position() == {"epoch": 0, "offset": 8}
    it.reset()
    assert it.position() == {"epoch": 1, "offset": 0}
    rs = mx.io.ResizeIter(it, 2)
    assert rs.position()["epoch"] == 1
    pre = mx.io.PrefetchingIter(it)
    assert pre.position()["epoch"] == 1
    dev = mx.io.DevicePrefetchIter(it, lambda d: d, depth=1)
    assert dev.position()["epoch"] == 1
    # base iterators default to None
    assert mx.io.DataIter().position() is None


def _tiny_rec(path, n=6, size=8):
    from PIL import Image
    w = mx.recordio.MXRecordIO(str(path), "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
        buf = _pyio.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=85)
        w.write(mx.recordio.pack(
            mx.recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    w.close()
    return str(path)


def test_image_iter_position_and_stage_accounting(tmp_path):
    rec = _tiny_rec(tmp_path / "t.rec")
    it = mx.image.ImageIter(batch_size=3, data_shape=(3, 8, 8),
                            path_imgrec=rec)
    it.next()
    pos = it.position()
    assert pos["epoch"] == 0 and pos["shard"] == 0
    assert pos["offset"] == 3 and pos["resyncs"] == 0
    it.reset()
    assert it.position()["epoch"] == 1
    assert it.position()["offset"] == 0
    snap = ioview.snapshot()["stages"]
    # the real pipeline accounted every stage it touched
    assert snap["read"]["items"] == 3
    assert snap["decode"]["items"] == 3
    assert snap["augment"]["items"] == 3
    assert snap["batch"]["items"] == 3
    assert snap["decode"]["bytes"] > 0


def test_seeded_slow_decode_io_decode_seam(tmp_path):
    from mxnet_tpu import resilience
    rec = _tiny_rec(tmp_path / "t.rec", n=3)
    it = mx.image.ImageIter(batch_size=3, data_shape=(3, 8, 8),
                            path_imgrec=rec)
    base = ioview.snapshot()["stages"].get(
        "decode", {"s": 0.0})["s"]
    resilience.configure_faults("io.decode:kind=delay,delay=0.05")
    it.next()
    slow = ioview.snapshot()["stages"]["decode"]["s"] - base
    assert slow > 0.12          # 3 images x 50ms seeded delay


def test_position_roundtrip_manifest(tmp_path):
    """Acceptance: the tracked iterator's position lands in the
    checkpoint manifest meta as advisory data_position."""
    from mxnet_tpu import resilience
    x = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    it = mx.io.NDArrayIter(x, np.zeros(16, np.float32), batch_size=4)
    it.next()
    it.next()
    ioview.track(it)
    prefix = str(tmp_path / "ckpt")
    mx.model.save_checkpoint(
        prefix, 3, None, {"w": mx.nd.array(np.ones((2, 2)))}, {})
    doc = resilience.load_manifest(prefix, 3)
    assert doc["meta"]["data_position"] == {"epoch": 0, "offset": 8}
    # the checkpoint still loads (symbol=None -> params only)
    _sym, args, _aux = None, None, None
    _epoch = mx.model.find_checkpoints(prefix)
    assert _epoch == [3]
    # untracked runs write no position key
    telemetry.reset()
    mx.model.save_checkpoint(
        prefix, 4, None, {"w": mx.nd.array(np.ones((2, 2)))}, {})
    doc = resilience.load_manifest(prefix, 4)
    assert "data_position" not in doc["meta"]


def test_trainer_checkpoint_carries_position(tmp_path):
    from mxnet_tpu import models, resilience
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    trainer = ShardedTrainer(
        models.get_model("mlp", num_classes=10), build_mesh(tp=1),
        data_shapes={"data": (8, 64)},
        label_shapes={"softmax_label": (8,)}, dtype="float32")
    x = np.arange(32 * 64, dtype=np.float32).reshape(32, 64)
    it = mx.io.NDArrayIter(x, np.zeros(32, np.float32), batch_size=8)
    it.next()
    ioview.track(it)
    prefix = str(tmp_path / "tr")
    trainer.save_checkpoint(prefix, 1)
    doc = resilience.load_manifest(prefix, 1)
    assert doc["meta"]["mesh"]           # schema v2 intact
    assert doc["meta"]["data_position"]["offset"] == 4 + 4


def test_current_position_never_raises():
    class Bad:
        def position(self):
            raise RuntimeError("boom")
    b = Bad()
    ioview.track(b)
    assert ioview.current_position() is None
    del b
    assert ioview.current_position() is None    # weakref died


# --------------------------------------------------- step record / JSONL

def test_step_record_cadence_and_deltas(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_IOVIEW_EVERY", "2")
    ioview.account("read", 0.1, items=2, nbytes=10)
    rec = ioview.step_record()               # call 1: emits
    assert rec["stages"]["read"]["items"] == 2
    ioview.account("read", 0.2, items=3, nbytes=20)
    assert ioview.step_record() is None      # call 2: off-cadence
    ioview.account("read", 0.3, items=5, nbytes=30)
    rec = ioview.step_record()               # call 3: emits the DELTA
    assert rec["stages"]["read"]["items"] == 8
    assert rec["stages"]["read"]["s"] == pytest.approx(0.5)
    assert rec["window_s"] > 0
    monkeypatch.setenv("MXNET_TPU_IOVIEW_EVERY", "0")
    ioview.account("read", 0.1, items=1)
    assert ioview.step_record() is None      # disabled


def test_io_block_rides_jsonl_step_records(tmp_path, monkeypatch):
    path = str(tmp_path / "run.jsonl")
    monkeypatch.setenv("MXNET_TPU_TELEMETRY_JSONL", path)
    x = np.zeros((16, 3), np.float32)
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(x, np.zeros(16, np.float32), batch_size=4))
    ioview.track(it)
    for _b in it:
        telemetry.step_end(samples=4, step_time=0.001)
    recs = [json.loads(line) for line in open(path)]
    with_io = [r for r in recs if "io" in r]
    assert with_io, "no io blocks in the step-log"
    last = with_io[-1]["io"]
    assert "host_prefetch" in last.get("stages", {}) or \
        any("host_prefetch" in r["io"].get("stages", {})
            for r in with_io)
    assert with_io[-1]["io"]["position"] == {"epoch": 0, "offset": 16}
    assert "queues" in last


# ------------------------------------------------------------ io_top

def _synthetic_step_log(path, ranks=(0,), slow_stage="decode",
                        slow_rank=0, steps=3):
    with open(path, "w") as f:
        for step in range(1, steps + 1):
            for r in ranks:
                slow = r == slow_rank
                io = {
                    "stages": {
                        "read": {"s": 0.01, "items": 8, "bytes": 800},
                        slow_stage: {"s": 0.2 if slow else 0.02,
                                     "items": 8, "bytes": 8000},
                        "batch": {"s": 0.005, "items": 8,
                                  "bytes": 6144},
                    },
                    "stall_s": {"host": 0.18 if slow else 0.001},
                    "starved_s": {"host": 0.001},
                    "queues": {"host": {"depth": 0, "mean": 0.2,
                                        "levels": {"0": 0.5,
                                                   "1": 0.1}}},
                    "window_s": 0.25,
                    "position": {"epoch": 0, "shard": r,
                                 "offset": 8 * step, "resyncs": 0},
                }
                f.write(json.dumps({"ts": 1000.0 + step, "step": step,
                                    "rank": r, "step_time_s": 0.25,
                                    "io": io}) + "\n")


def test_io_top_renders_and_names_stage(tmp_path, capsys):
    log = str(tmp_path / "io.jsonl")
    _synthetic_step_log(log)
    io_top = _load_tool("io_top")
    assert io_top.main([log]) == 0
    out = capsys.readouterr().out
    assert "bottleneck: producer-bound — stage 'decode'" in out
    assert "read" in out and "batch" in out
    assert "queue host" in out and "position:" in out


def test_io_top_json_schema(tmp_path, capsys):
    log = str(tmp_path / "io.jsonl")
    _synthetic_step_log(log)
    io_top = _load_tool("io_top")
    assert io_top.main([log, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "mxtpu-iotop/1"
    assert doc["bottleneck"]["verdict"] == "producer-bound"
    assert doc["bottleneck"]["stage"] == "decode"
    assert doc["bottleneck"]["rank"] == 0
    assert doc["ranks"]["0"]["position"]["offset"] == 24
    assert doc["stages"]["decode"]["items"] == 24


def test_io_top_rejects_io_free_log(tmp_path, capsys):
    log = str(tmp_path / "none.jsonl")
    with open(log, "w") as f:
        f.write(json.dumps({"step": 1, "step_time_s": 0.1}) + "\n")
    io_top = _load_tool("io_top")
    assert io_top.main([log, "--json"]) == 1
    assert "no io blocks" in capsys.readouterr().err


def test_io_top_timeline_mode_names_rank(tmp_path, monkeypatch,
                                         capsys):
    """A 2-rank mxtpu-run/1 timeline: io_top aggregates per rank and
    names the slow stage on the slow rank; shard skew is reported."""
    monkeypatch.delenv("MXNET_TPU_FLIGHT_DIR", raising=False)
    base = str(tmp_path / "run.jsonl")
    steplog = str(tmp_path / "steps.jsonl")
    _synthetic_step_log(steplog, ranks=(0, 1), slow_rank=1, steps=4)
    agg = distview.RunAggregator(base, 2)
    for line in open(steplog):
        rec = json.loads(line)
        agg.feed(rec["rank"], rec)
    agg.close()
    io_top = _load_tool("io_top")
    assert io_top.main([base + ".run", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["num_ranks"] == 2
    assert doc["bottleneck"] == {
        "verdict": "producer-bound", "stage": "decode", "rank": 1}
    assert doc["shard_skew"] is None or "slowest_rank" in doc["shard_skew"]
    assert io_top.main([base + ".run"]) == 0
    out = capsys.readouterr().out
    assert "stage 'decode' on rank 1" in out


# ----------------------------------------- cross-rank summarize/run_top

def _timeline_with_io(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_TPU_FLIGHT_DIR", raising=False)
    base = str(tmp_path / "run.jsonl")
    agg = distview.RunAggregator(base, 2)
    for step in range(1, 4):
        for r in (0, 1):
            slow = r == 1
            io = {"stages": {
                      "decode": {"s": 0.2 if slow else 0.01,
                                 "items": 8, "bytes": 100},
                      "read": {"s": 0.005, "items": 8, "bytes": 800}},
                  "stall_s": {"host": 0.18 if slow else 0.001},
                  "window_s": 0.25,
                  "position": {"epoch": 0, "shard": r,
                               "offset": 8 * step}}
            seg = {"compute": 0.02,
                   "input_wait": 0.21 if slow else 0.01,
                   "collective_wait": 0.0}
            agg.feed(r, {"step": step, "ts": 1000.0 + step,
                         "step_time_s": 0.23 if slow else 0.03,
                         "segments": seg, "io": io})
    agg.close()
    return base + ".run"


def test_summarize_run_names_io_bottleneck(tmp_path, monkeypatch):
    run_path = _timeline_with_io(tmp_path, monkeypatch)
    summary = distview.summarize_run(
        distview.read_run_timeline(run_path))
    assert summary["straggler"] == 1
    iob = summary["io_bottleneck"]
    assert iob["rank"] == 1 and iob["stage"] == "decode"
    assert iob["stage_s"] == pytest.approx(0.6)
    pr = summary["per_rank"]["1"]
    assert pr["io_stages_s"]["decode"] == pytest.approx(0.6)
    assert pr["data_position"]["offset"] == 24
    # the FAST rank is compute-dominated: no io bottleneck claimed on it
    assert summary["per_rank"]["0"]["io_stages_s"]["decode"] == \
        pytest.approx(0.03)


def test_run_top_prints_io_bottleneck(tmp_path, monkeypatch, capsys):
    run_path = _timeline_with_io(tmp_path, monkeypatch)
    run_top = _load_tool("run_top")
    assert run_top.main([run_path, "--summarize"]) == 0
    out = capsys.readouterr().out
    assert "input bottleneck: stage 'decode' on rank 1" in out
    assert run_top.main([run_path]) == 0
    out = capsys.readouterr().out
    assert "input bottleneck: stage 'decode' on rank 1" in out


def test_summarize_run_no_io_bottleneck_when_compute_bound(tmp_path,
                                                           monkeypatch):
    """A compute-dominated straggler must NOT be blamed on the data
    plane even when io stages were reported."""
    monkeypatch.delenv("MXNET_TPU_FLIGHT_DIR", raising=False)
    base = str(tmp_path / "run.jsonl")
    agg = distview.RunAggregator(base, 1)
    agg.feed(0, {"step": 1, "ts": 1.0, "step_time_s": 0.5,
                 "segments": {"compute": 0.45, "input_wait": 0.05,
                              "collective_wait": 0.0},
                 "io": {"stages": {"decode": {"s": 0.04, "items": 8,
                                              "bytes": 1}},
                        "window_s": 0.5}})
    agg.close()
    summary = distview.summarize_run(
        distview.read_run_timeline(base + ".run"))
    assert summary["io_bottleneck"] is None


# --------------------------------------------------- 2-process end-to-end

def test_dist_seeded_slow_decode_named_stage_and_rank(tmp_path):
    """Acceptance: a REAL 2-process run (tools/launch.py) where rank 1's
    decode is seeded slow through the io.decode delay seam — the merged
    timeline must let run_top name the stage AND the rank."""
    import subprocess

    base = str(tmp_path / "run.jsonl")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_NUM_PROCESSES", None)
    env.pop("MXNET_TPU_PROCESS_ID", None)
    env.pop("MXNET_TPU_FAULTS", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "MXNET_TPU_TELEMETRY_JSONL": base,
                "DISTVIEW_IO": "1",
                "DISTVIEW_STEPS": "3",
                "DISTVIEW_SLOW_RANK": "1",
                "DISTVIEW_SLOW_S": "0.05",
                "DISTVIEW_BASE_S": "0.02"})
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--launcher", "local",
         "--heartbeat-interval", "0.1",
         sys.executable,
         os.path.join(ROOT, "tests", "dist_distview_worker.py")],
        capture_output=True, text=True, timeout=240, cwd=ROOT, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    run_path = base + ".run"
    assert os.path.exists(run_path)

    # run_top --summarize --json names stage AND rank
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_top.py"),
         run_path, "--summarize", "--json"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert res.returncode == 0, res.stderr
    summary = json.loads(res.stdout)
    assert summary["straggler"] == 1
    iob = summary["io_bottleneck"]
    assert iob and iob["rank"] == 1 and iob["stage"] == "decode", iob
    assert summary["per_rank"]["1"]["data_position"]["shard"] == 1

    # the text rendering says it in one line
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_top.py"),
         run_path, "--summarize"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert "input bottleneck: stage 'decode' on rank 1" in res.stdout

    # io_top over the same timeline agrees on stage + rank
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "io_top.py"),
         run_path, "--json"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert res.returncode == 0, res.stderr
    doc = json.loads(res.stdout)
    assert doc["schema"] == "mxtpu-iotop/1"
    assert doc["bottleneck"]["stage"] == "decode"
    assert doc["bottleneck"]["rank"] == 1
