"""Serving tier: batch ladder, continuous batcher, front door, chaos.

Exercises mxnet_tpu/serving/ (docs/api/serving.md).  The scheduler
oracles run against a FAKE ladder (pure python — coalescing, EDF,
shedding and fail-fast are queue properties, not model properties);
the AOT/pad-slice/zero-compile contracts run against a real
BatchLadder over a tiny FC net on the CPU backend.  The acceptance
scenario (ISSUE 18): requests coalesce into ladder rungs with zero
compiles after warm-up, hopeless requests are shed early, and an
injected ``serve.dispatch`` fault fails its batch fast without
wedging the queue.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.predictor import Predictor, pad_batch
from mxnet_tpu.resilience import FaultInjected
from mxnet_tpu.serving import (BatchLadder, Batcher, RequestShed,
                               Server, ladder_rungs)


# --------------------------------------------------------------------------
# fake ladder: the batcher's documented duck-type contract
# --------------------------------------------------------------------------
class FakeLadder:
    """Records dispatches; outputs are the input rows times two."""

    def __init__(self, rungs=(1, 4), wall=0.0005, tail=(3,)):
        self.rungs = tuple(rungs)
        self.max_rung = self.rungs[-1]
        self.input_names = ["data"]
        self._tail = tuple(tail)
        self._wall = wall
        self.dispatches = []     # (rung, rows_padded)
        self.observed = []

    def input_tail(self, name):
        return self._tail

    def input_dtype(self, name):
        return np.float32

    def pick_rung(self, rows):
        for r in self.rungs:
            if r >= rows:
                return r
        return None

    def estimate_wall(self, rung):
        return self._wall

    def observe_wall(self, rung, wall):
        self.observed.append((rung, wall))

    def dispatch(self, rung, feed):
        self.dispatches.append((rung, feed["data"].shape[0]))
        return [feed["data"] * 2.0]


def _rows(n, fill=1.0, tail=(3,)):
    return {"data": np.full((n,) + tuple(tail), fill, np.float32)}


def test_batcher_coalesces_concurrent_requests_into_one_rung():
    lad = FakeLadder(rungs=(1, 4))
    bat = Batcher(lad, window_ms=50, queue_depth=16,
                  default_deadline_ms=5000)
    try:
        results = [None] * 3
        errors = []

        def go(i):
            try:
                results[i] = bat.submit(_rows(1, fill=float(i)))
            except Exception as e:  # mxlint: allow-broad-except(collected and re-asserted below)
                errors.append(e)

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        # one coalesced rung-4 dispatch carrying all 3 requests (the
        # 50 ms window is ample for three same-instant submits)
        assert lad.dispatches == [(4, 4)]
        for i, out in enumerate(results):
            assert out[0].shape == (1, 3)
            np.testing.assert_allclose(out[0], float(i) * 2.0)
    finally:
        bat.close()


def test_batcher_single_request_takes_smallest_rung():
    lad = FakeLadder(rungs=(1, 4))
    bat = Batcher(lad, window_ms=5, queue_depth=16,
                  default_deadline_ms=5000)
    try:
        out = bat.submit(_rows(1))
        assert lad.dispatches == [(1, 1)]
        assert out[0].shape == (1, 3)
        # an unbatched single row is accepted and batched to 1 row
        out = bat.submit({"data": np.ones((3,), np.float32)})
        assert out[0].shape == (1, 3)
    finally:
        bat.close()


class SlowLadder(FakeLadder):
    """A dispatch really takes its ``wall``: the queue backs up behind it
    however slowly the machine starts the submitting threads."""

    def dispatch(self, rung, feed):
        time.sleep(self._wall)
        return super().dispatch(rung, feed)


def test_batcher_sheds_on_queue_full():
    lad = SlowLadder(rungs=(1,), wall=0.2)   # slow: the queue backs up
    bat = Batcher(lad, window_ms=1, queue_depth=2,
                  default_deadline_ms=10000)
    try:
        sheds, oks = [], []

        def go():
            try:
                oks.append(bat.submit(_rows(1), timeout=30))
            except RequestShed as e:
                sheds.append(e)

        threads = [threading.Thread(target=go) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sheds, "8 submits against a depth-2 queue never shed"
        assert all(e.reason == "queue_full" for e in sheds)
        assert oks, "the queue served nothing"
    finally:
        bat.close()


def test_batcher_sheds_hopeless_deadline_at_submit():
    lad = FakeLadder(rungs=(1, 4), wall=10.0)   # 10 s estimated wall
    bat = Batcher(lad, window_ms=1, queue_depth=8,
                  default_deadline_ms=50)
    try:
        with pytest.raises(RequestShed) as ei:
            bat.submit(_rows(1))
        assert ei.value.reason == "deadline"
        assert lad.dispatches == []        # shed BEFORE any TPU time
    finally:
        bat.close()


def test_batcher_edf_orders_most_urgent_first():
    lad = FakeLadder(rungs=(2,), wall=0.0005)
    bat = Batcher(lad, window_ms=60, queue_depth=16,
                  default_deadline_ms=5000, start=False)
    order = []
    real_dispatch = lad.dispatch

    def spy(rung, feed):
        order.append(feed["data"][0, 0])
        return real_dispatch(rung, feed)

    lad.dispatch = spy
    done = []

    def go(fill, deadline_ms):
        done.append(bat.submit(_rows(1, fill=fill),
                               deadline_ms=deadline_ms))

    # three 1-row requests into rung-2 batches: the two most urgent
    # (smallest deadline) must ride the FIRST dispatch
    threads = [
        threading.Thread(target=go, args=(1.0, 4000)),
        threading.Thread(target=go, args=(2.0, 900)),
        threading.Thread(target=go, args=(3.0, 2000)),
    ]
    for t in threads:
        t.start()
    time.sleep(0.02)           # let all three enqueue inside the window
    bat._thread.start()
    for t in threads:
        t.join()
    bat.close()
    assert len(done) == 3
    # first dispatched batch leads with the 900 ms request
    assert order[0] == 2.0


def test_batcher_rejects_rows_over_max_rung():
    lad = FakeLadder(rungs=(1, 4))
    bat = Batcher(lad, window_ms=1, queue_depth=8,
                  default_deadline_ms=5000)
    try:
        with pytest.raises(MXNetError, match="largest ladder rung"):
            bat.submit(_rows(5))
    finally:
        bat.close()


def test_chaos_fault_fails_batch_fast_without_wedging_queue():
    lad = FakeLadder(rungs=(1, 4))
    bat = Batcher(lad, window_ms=1, queue_depth=8,
                  default_deadline_ms=5000)
    try:
        resilience.configure_faults("serve.dispatch:n=1")
        t0 = time.monotonic()
        with pytest.raises(FaultInjected):
            bat.submit(_rows(1))
        assert time.monotonic() - t0 < 2.0, "fault did not fail fast"
        # the scheduler kept draining: the NEXT submit succeeds
        out = bat.submit(_rows(1))
        assert out[0].shape == (1, 3)
        assert bat.alive
    finally:
        resilience.configure_faults("")
        bat.close()


def test_ladder_rungs_parsing():
    assert ladder_rungs("1,4,16") == (1, 4, 16)
    assert ladder_rungs((8, 2)) == (2, 8)
    with pytest.raises(MXNetError):
        ladder_rungs("0,4")
    with pytest.raises(MXNetError):
        ladder_rungs("nope")


# --------------------------------------------------------------------------
# real ladder over a tiny net: pad-slice parity + the AOT contract
# --------------------------------------------------------------------------
def _tiny_predictor(batch=4, features=6, hidden=5):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    rng = np.random.RandomState(7)
    params = {
        "fc_weight": mx.nd.array(
            rng.uniform(-0.5, 0.5, (hidden, features)).astype(np.float32)),
        "fc_bias": mx.nd.array(np.zeros(hidden, np.float32)),
    }
    return Predictor(net.tojson(), params, {"data": (batch, features)})


def test_pad_batch_helper():
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    padded = pad_batch(x, 4)
    assert padded.shape == (4, 3)
    np.testing.assert_allclose(padded[:2], x)
    np.testing.assert_allclose(padded[2:], 0.0)
    assert pad_batch(x, 2) is x
    with pytest.raises(MXNetError):
        pad_batch(x, 1)
    with pytest.raises(MXNetError):
        pad_batch(np.float32(1.0), 2)


def test_predictor_pads_and_slices_partial_batch():
    pred = _tiny_predictor(batch=4)
    x = np.random.RandomState(0).uniform(
        -1, 1, (2, 6)).astype(np.float32)
    pred.forward(data=x)
    out = pred.get_output(0)
    assert out.shape == (2, 5)          # sliced back to the fed rows
    # parity against a natively batch-2 handle (row-independent net)
    ref = pred.reshaped({"data": (2, 6)})
    ref.forward(data=x)
    np.testing.assert_allclose(out, ref.get_output(0),
                               rtol=1e-5, atol=1e-6)


def test_predictor_set_input_then_argless_forward_slices():
    # the documented staging flow: set_input -> forward() -> get_output
    # (regression: forward() used to wipe the partial-rows marker staged
    # by set_input, returning the padded rows unsliced)
    pred = _tiny_predictor(batch=4)
    x = np.random.RandomState(3).uniform(
        -1, 1, (4, 6)).astype(np.float32)
    pred.set_input("data", x)
    pred.forward()
    full = pred.get_output(0)
    assert full.shape == (4, 5)
    pred.set_input("data", x[:2])
    pred.forward()
    part = pred.get_output(0)
    assert part.shape == (2, 5)
    np.testing.assert_allclose(part, full[:2], rtol=1e-5, atol=1e-6)
    # a full-shape restage clears the marker — no stale slicing
    pred.set_input("data", x)
    pred.forward()
    assert pred.get_output(0).shape == (4, 5)


def test_predictor_larger_batch_is_loud_not_a_retrace():
    pred = _tiny_predictor(batch=2)
    with pytest.raises(MXNetError, match="serving batch ladder"):
        pred.forward(data=np.zeros((3, 6), np.float32))


def test_ladder_zero_compiles_after_warmup():
    if not telemetry.compile.installed():
        telemetry.compile.install()
    if not telemetry.compile.installed():
        pytest.skip("jax.monitoring compile listener unavailable")
    pred = _tiny_predictor(batch=1)
    ladder = BatchLadder(pred, rungs=(1, 2, 4))
    counter = telemetry.counter("mxtpu_compile_total")
    before = counter.get()
    bat = Batcher(ladder, window_ms=1, queue_depth=8,
                  default_deadline_ms=5000)
    try:
        for rows in (1, 2, 3, 4, 1, 3):
            out = bat.submit(_rows(rows, tail=(6,)))
            assert out[0].shape == (rows, 5)
    finally:
        bat.close()
    assert counter.get() == before, \
        "the request path compiled after warm-up (AOT contract broken)"


def test_ladder_dispatch_matches_oneshot_predictor():
    pred = _tiny_predictor(batch=1)
    ladder = BatchLadder(pred, rungs=(1, 4))
    x = np.random.RandomState(3).uniform(
        -1, 1, (3, 6)).astype(np.float32)
    outs = ladder.dispatch(4, {"data": pad_batch(x, 4)})
    ref = pred.reshaped({"data": (3, 6)})
    ref.forward(data=x)
    np.testing.assert_allclose(outs[0][:3], ref.get_output(0),
                               rtol=1e-5, atol=1e-6)


def test_ladder_describe_and_walls():
    pred = _tiny_predictor(batch=1)
    ladder = BatchLadder(pred, rungs=(1, 2))
    doc = ladder.describe()
    assert doc["rungs"] == [1, 2]
    assert doc["warmed"] is True
    assert set(doc["wall_ms"]) == {"1", "2"}   # measured at warm-up
    assert ladder.estimate_wall(2) > 0
    assert ladder.pick_rung(2) == 2
    assert ladder.pick_rung(3) is None


# --------------------------------------------------------------------------
# front door end to end (in-process HTTP)
# --------------------------------------------------------------------------
def test_server_end_to_end():
    pred = _tiny_predictor(batch=1)
    ladder = BatchLadder(pred, rungs=(1, 4))
    bat = Batcher(ladder, window_ms=2, queue_depth=8,
                  default_deadline_ms=5000)
    server = Server(ladder, batcher=bat, port=0).start()
    base = "http://127.0.0.1:%d" % server.port
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            doc = json.loads(r.read())
        assert r.status == 200 and doc["status"] == "ok"
        assert doc["ladder"]["rungs"] == [1, 4]

        body = json.dumps(
            {"data": [[0.1] * 6, [0.2] * 6]}).encode()
        req = urllib.request.Request(
            base + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            doc = json.loads(r.read())
        assert doc["rows"] == 2
        assert np.asarray(doc["outputs"][0]).shape == (2, 5)

        # a hopeless deadline is a 503 with the shed reason
        body = json.dumps(
            {"data": [[0.1] * 6], "deadline_ms": 1e-6}).encode()
        req = urllib.request.Request(
            base + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["shed"] == "deadline"

        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for name in ("mxtpu_serve_requests_total",
                     "mxtpu_serve_rung_dispatch_total",
                     "mxtpu_serve_request_seconds_bucket",
                     "mxtpu_serve_rung_occupancy"):
            assert name in text, "missing %s in /metrics" % name
    finally:
        server.close()
    # closed batcher: healthz contract flips to 503 (watchdog liveness)
    assert not bat.alive
