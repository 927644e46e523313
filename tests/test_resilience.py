"""Resilience subsystem: fault injection, atomic checkpoints, recovery.

Exercises mxnet_tpu/resilience.py and its wiring through checkpointing
(model.py + parallel/trainer.py), the data pipeline (recordio.py), and
multihost rendezvous (parallel/multihost.py).  The acceptance scenario
(ISSUE 1): a training run with MXNET_TPU_FAULTS injecting a
checkpoint-save crash and 5% corrupt records completes to the loss
threshold, restores from the last verified checkpoint, and reports
skipped-record counts — all under JAX_PLATFORMS=cpu.
"""
import logging
import os
import signal
import struct
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio as rec
from mxnet_tpu import resilience as R
from mxnet_tpu.base import MXNetError
from mxnet_tpu.model import (save_checkpoint, load_checkpoint,
                             find_checkpoints, load_latest_checkpoint)
from mxnet_tpu.parallel import ShardedTrainer, build_mesh, multihost


@pytest.fixture(autouse=True)
def _clean_faults():
    R.clear_faults()
    yield
    R.clear_faults()


# ------------------------------------------------------------ fault registry

def _fire_sequence(site, n):
    out = []
    for _ in range(n):
        try:
            R.fault_point(site)
            out.append(0)
        except R.FaultInjected:
            out.append(1)
    return out


def test_fault_spec_grammar_and_determinism():
    R.configure_faults("recordio.read:p=0.3,seed=11;checkpoint.save:n=2")
    seq1 = _fire_sequence("recordio.read", 40)
    # re-configuring resets counters AND the RNG: identical sequence
    R.configure_faults("recordio.read:p=0.3,seed=11")
    seq2 = _fire_sequence("recordio.read", 40)
    assert seq1 == seq2
    assert 0 < sum(seq1) < 40
    # a different seed gives a different sequence
    R.configure_faults("recordio.read:p=0.3,seed=12")
    assert _fire_sequence("recordio.read", 40) != seq1


def test_fault_times_and_after():
    R.configure_faults("checkpoint.load:n=2,after=3")
    seq = _fire_sequence("checkpoint.load", 10)
    assert seq == [0, 0, 0, 1, 1, 0, 0, 0, 0, 0]
    stats = R.fault_stats()["checkpoint.load"]
    assert stats == {"calls": 10, "hits": 2}


def test_fault_env_arming(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FAULTS", "multihost.barrier:n=1")
    with pytest.raises(R.FaultInjected):
        R.fault_point("multihost.barrier")
    R.fault_point("multihost.barrier")  # n=1 exhausted
    monkeypatch.setenv("MXNET_TPU_FAULTS", "")
    R.fault_point("multihost.barrier")


def test_fault_spec_rejects_garbage():
    with pytest.raises(MXNetError):
        R.configure_faults("recordio.read:frobnicate=1")
    with pytest.raises(MXNetError):
        R.configure_faults("recordio.read:p")


def test_unarmed_sites_are_free():
    R.configure_faults("")
    R.fault_point("recordio.read")
    R.fault_point("never.declared")


# -------------------------------------------------------- retry / timeout

def test_retry_call_recovers_then_exhausts():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    assert R.retry_call(flaky, retries=3, exceptions=(IOError,),
                        base_delay=0.001) == "ok"
    assert len(calls) == 3

    def always():
        raise IOError("permanent")

    with pytest.raises(MXNetError, match="permanent"):
        R.retry_call(always, retries=2, exceptions=(IOError,),
                     base_delay=0.001)


def test_retry_deadline_bounds_total_time():
    t0 = time.monotonic()
    with pytest.raises(MXNetError):
        R.retry_call(lambda: (_ for _ in ()).throw(IOError("x")),
                     retries=100, exceptions=(IOError,),
                     base_delay=0.05, max_delay=0.05, deadline=0.2)
    assert time.monotonic() - t0 < 2.0


def test_backoff_delays_deterministic_with_seed():
    a = [next(d) for d in [R.backoff_delays(seed=5)] for _ in range(6)]
    b = []
    g = R.backoff_delays(seed=5)
    for _ in range(6):
        b.append(next(g))
    assert a == b
    g = R.backoff_delays(base=0.1, factor=2, max_delay=0.4, jitter=0)
    assert [next(g) for _ in range(4)] == [0.1, 0.2, 0.4, 0.4]


def test_with_timeout():
    assert R.with_timeout(lambda: 7, 1.0) == 7
    assert R.with_timeout(lambda: 7, None) == 7
    with pytest.raises(R.TimeoutError, match="did not complete"):
        R.with_timeout(lambda: time.sleep(5), 0.1, name="hang")
    with pytest.raises(KeyError):
        R.with_timeout(lambda: {}["missing"], 1.0)


def test_retryable_decorator():
    state = {"n": 0}

    @R.retryable(retries=2, exceptions=(ValueError,), base_delay=0.001)
    def f(x):
        state["n"] += 1
        if state["n"] < 2:
            raise ValueError("nope")
        return x * 2

    assert f(21) == 42


# -------------------------------------------------- atomic checkpoint layer

def _mlp_sym():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params():
    rng = np.random.RandomState(3)
    return ({"fc_weight": mx.nd.array(rng.rand(4, 3).astype("f")),
             "fc_bias": mx.nd.array(np.zeros(4, "f"))}, {})


def test_atomic_save_crash_leaves_last_good(tmp_path):
    """Kill between tmp write and rename: loader picks last-good."""
    prefix = str(tmp_path / "ck")
    args, aux = _params()
    net = _mlp_sym()
    save_checkpoint(prefix, 1, net, args, aux)
    save_checkpoint(prefix, 2, net, args, aux)
    R.configure_faults("checkpoint.save:n=1")
    with pytest.raises(R.FaultInjected):
        save_checkpoint(prefix, 3, net, args, aux)
    R.clear_faults()
    # the crashed epoch left a stray tmp, no .params, no manifest
    assert not os.path.exists("%s-0003.params" % prefix)
    assert not os.path.exists(R.manifest_path(prefix, 3))
    assert any(".tmp." in f for f in os.listdir(str(tmp_path)))
    assert find_checkpoints(prefix) == [1, 2]
    ep, sym, a, x = load_latest_checkpoint(prefix)
    assert ep == 2
    np.testing.assert_array_equal(a["fc_weight"].asnumpy(),
                                  args["fc_weight"].asnumpy())


def test_manifest_detects_corruption_and_falls_back(tmp_path, caplog):
    prefix = str(tmp_path / "ck")
    args, aux = _params()
    net = _mlp_sym()
    save_checkpoint(prefix, 1, net, args, aux)
    save_checkpoint(prefix, 2, net, args, aux)
    with open("%s-0002.params" % prefix, "r+b") as f:
        f.seek(40)
        f.write(b"\xff\xff")
    with pytest.raises(MXNetError, match="CRC32"):
        load_checkpoint(prefix, 2)
    with caplog.at_level(logging.WARNING):
        ep, _, _, _ = load_latest_checkpoint(prefix)
    assert ep == 1
    # per-array CRCs are recorded in the manifest
    doc = R.load_manifest(prefix, 1)
    assert "arg:fc_weight" in doc["arrays"]
    assert doc["arrays"]["arg:fc_weight"]["crc32"] == \
        R.array_crc32(args["fc_weight"].asnumpy())


def test_find_checkpoints_five_digit_epochs(tmp_path):
    """%04d renders epochs >= 10000 with 5 digits; the scanner must see
    them (preemption epochs are step counts, so they get there)."""
    prefix = str(tmp_path / "ck")
    args, aux = _params()
    net = _mlp_sym()
    save_checkpoint(prefix, 9999, net, args, aux)
    save_checkpoint(prefix, 10002, net, args, aux)
    assert find_checkpoints(prefix) == [9999, 10002]
    ep, _, _, _ = load_latest_checkpoint(prefix)
    assert ep == 10002


def test_load_checkpoint_missing_raises_descriptive(tmp_path):
    prefix = str(tmp_path / "nothing")
    with pytest.raises(MXNetError, match="symbol file .* is missing"):
        load_checkpoint(prefix, 0)
    # symbol present, params missing: error names the params path
    _mlp_sym().save("%s-symbol.json" % prefix)
    with pytest.raises(MXNetError, match="params file .* is missing"):
        load_checkpoint(prefix, 7)
    with pytest.raises(MXNetError, match="no complete checkpoint"):
        load_latest_checkpoint(prefix)


def test_truncated_params_named_not_unpickle_error(tmp_path):
    prefix = str(tmp_path / "ck")
    args, aux = _params()
    save_checkpoint(prefix, 1, _mlp_sym(), args, aux)
    os.remove(R.manifest_path(prefix, 1))   # legacy checkpoint: no manifest
    with open("%s-0001.params" % prefix, "r+b") as f:
        f.truncate(20)
    with pytest.raises(MXNetError, match="corrupt"):
        load_checkpoint(prefix, 1)


# ------------------------------------------------ trainer checkpoint wiring

def _trainer(seed=5):
    mesh = build_mesh(tp=1)
    return ShardedTrainer(
        _mlp_sym(), mesh,
        data_shapes={"data": (32, 64)},
        label_shapes={"softmax_label": (32,)},
        learning_rate=0.15, momentum=0.9, seed=seed)


_PROTOS = np.random.RandomState(42).rand(10, 64).astype("f")


def _cluster_batch(step, batch=32):
    rng = np.random.RandomState(500 + step)
    y = rng.randint(0, 10, batch)
    x = (_PROTOS[y] + rng.randn(batch, 64) * 0.2).astype("f")
    return x, y.astype("f")


def test_trainer_save_is_atomic_and_verified(tmp_path):
    prefix = str(tmp_path / "tr")
    t = _trainer()
    x, y = _cluster_batch(0)
    t.step({"data": x, "softmax_label": y})
    t.save_checkpoint(prefix, 1, save_optimizer_states=True)
    doc = R.verify_manifest(prefix, 1)
    assert "%s-0001.params" % os.path.basename(prefix) \
        in {os.path.basename(k) for k in doc["files"]}
    # states covered too
    assert any(f.endswith("0001.states") for f in doc["files"])
    # crashed save: invisible to find_checkpoints
    R.configure_faults("checkpoint.save:n=1")
    with pytest.raises(R.FaultInjected):
        t.save_checkpoint(prefix, 2, save_optimizer_states=True)
    R.clear_faults()
    assert find_checkpoints(prefix, require_states=True) == [1]
    t2 = _trainer()
    assert t2.load_latest_checkpoint(
        prefix, load_optimizer_states=True) == 1
    np.testing.assert_allclose(np.asarray(t2.params["fc1_weight"]),
                               np.asarray(t.params["fc1_weight"]))
    # empty dir: returns None (start fresh), not an exception
    assert _trainer().load_latest_checkpoint(str(tmp_path / "no")) is None


def test_trainer_load_corrupt_raises_descriptive(tmp_path):
    prefix = str(tmp_path / "tr")
    t = _trainer()
    t.save_checkpoint(prefix, 3)
    with open("%s-0003.params" % prefix, "r+b") as f:
        f.seek(64)
        f.write(b"\x00\x01\x02\x03")
    with pytest.raises(MXNetError, match="CRC32"):
        t.load_checkpoint(prefix, 3)


def test_preemption_handler_checkpoints_on_sigterm(tmp_path):
    """SIGTERM -> atomic checkpoint + clean SystemExit(0)."""
    prefix = str(tmp_path / "pre")
    t = _trainer()
    x, y = _cluster_batch(0)
    for step in range(3):
        t.step({"data": x, "softmax_label": y})
    handler = t.install_preemption_handler(prefix)
    try:
        with pytest.raises(SystemExit) as ei:
            os.kill(os.getpid(), signal.SIGTERM)
            # the handler runs between bytecodes; give it a beat
            for _ in range(100):
                time.sleep(0.01)
        assert ei.value.code == 0
        assert handler.triggered
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
    assert find_checkpoints(prefix, require_states=True) == [3]
    t2 = _trainer()
    assert t2.load_latest_checkpoint(
        prefix, load_optimizer_states=True) == 3


# --------------------------------------- flight recorder / OOM forensics

def _load_flight_read():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "flight_read", os.path.join(root, "tools", "flight_read.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _flight_dumps(d):
    return sorted(f for f in os.listdir(str(d))
                  if f.startswith("flight-") and f.endswith(".json"))


class _OomRaiser:
    """Stands in for the compiled step: a backend RESOURCE_EXHAUSTED."""

    def __call__(self, *args, **kwargs):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "9437184 bytes.")


def test_injected_oom_is_annotated_and_black_boxed(tmp_path, monkeypatch):
    """Acceptance (ISSUE 4): RESOURCE_EXHAUSTED during a ShardedTrainer
    step produces (a) an MXNetError whose message carries the static
    memory plan breakdown and live-bytes snapshot, and (b) a flight
    dump with the recent step/compile/plan events."""
    import json
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import memory as tmem
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    telemetry.reset()
    t = _trainer()
    x, y = _cluster_batch(0)
    # a clean step compiles the program and registers its memory plan
    t.step({"data": x, "softmax_label": y})
    assert tmem.get_plan("trainer.step") is not None
    t._step_fn = _OomRaiser()
    with pytest.raises(MXNetError) as ei:
        t.step({"data": x, "softmax_label": y})
    assert isinstance(ei.value, tmem.HbmOomError)
    msg = str(ei.value)
    assert "RESOURCE_EXHAUSTED" in msg
    assert "static memory plan" in msg
    assert "argument=" in msg and "temp=" in msg and "total=" in msg
    assert "live device memory" in msg      # snapshot (or its absence)
    assert isinstance(ei.value.__cause__, RuntimeError)
    dumps = _flight_dumps(tmp_path)
    assert len(dumps) == 1
    doc = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert doc["reason"] == "oom"
    kinds = [e["kind"] for e in doc["events"]]
    for want in ("step_begin", "step_end", "memory_plan", "oom"):
        assert want in kinds, (want, kinds)
    assert "trainer.step" in doc["memory_plans"]
    assert doc["memory_plans"]["trainer.step"]["total_bytes"] > 0
    # the reader parses and formats it
    fr = _load_flight_read()
    assert "reason=oom" in fr.format_dump(fr.load(
        os.path.join(str(tmp_path), dumps[0])))
    # and the recovery path still works: restore the real step fn
    t2 = _trainer()
    loss = float(t2.step({"data": x, "softmax_label": y}))
    assert np.isfinite(loss)


def test_trainer_fault_seam_dumps_black_box(tmp_path, monkeypatch):
    """The trainer.step fault seam (MXNET_TPU_FAULTS) exercises the
    dump-on-MXNetError path end to end."""
    import json
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    telemetry.reset()
    t = _trainer()
    x, y = _cluster_batch(0)
    t.step({"data": x, "softmax_label": y})
    R.configure_faults("trainer.step:n=1")
    with pytest.raises(R.FaultInjected):
        t.step({"data": x, "softmax_label": y})
    # n=1 exhausted: training continues after the injected failure
    R.clear_faults()
    float(t.step({"data": x, "softmax_label": y}))
    dumps = _flight_dumps(tmp_path)
    assert len(dumps) == 1
    doc = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert doc["reason"] == "error"
    faults = [e for e in doc["events"] if e["kind"] == "fault"]
    assert faults and faults[-1]["site"] == "trainer.step"


def test_preemption_dump_written_with_checkpoint(tmp_path, monkeypatch):
    """SIGTERM preemption leaves BOTH a checkpoint and a black box."""
    import json
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    prefix = str(tmp_path / "pre")
    t = _trainer()
    x, y = _cluster_batch(0)
    t.step({"data": x, "softmax_label": y})
    handler = t.install_preemption_handler(prefix, exit_process=False)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if handler.triggered:
                break
            time.sleep(0.01)
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
    assert handler.triggered
    assert find_checkpoints(prefix, require_states=True) == [1]
    dumps = _flight_dumps(tmp_path)
    assert len(dumps) == 1
    doc = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert doc["reason"] == "sigterm"
    pre = [e for e in doc["events"] if e["kind"] == "preemption"]
    assert pre and pre[0]["epoch"] == 1


# ----------------------------------------------------- data pipeline layer

def _write_rec(path, n=60, seed=0):
    rng = np.random.RandomState(seed)
    w = rec.MXRecordIO(str(path), "w")
    offsets, payloads = [], []
    for i in range(n):
        buf = rng.bytes(120 + 4 * (i % 5))
        offsets.append(w.tell())
        w.write(buf)
        payloads.append(buf)
    w.close()
    return offsets, payloads


def test_bad_record_quota_resync(tmp_path):
    path = tmp_path / "a.rec"
    offsets, payloads = _write_rec(path)
    with open(str(path), "r+b") as f:
        f.seek(offsets[7])
        f.write(b"\x01\x02\x03\x04")            # clobbered magic
        f.seek(offsets[31] + 4)
        f.write(struct.pack("<I", (1 << 29) - 8))  # absurd length
    r = rec.MXRecordIO(str(path), "r", skip_bad_records=8)
    got = []
    while True:
        b = r.read()
        if b is None:
            break
        got.append(b)
    r.close()
    assert len(got) == 58
    assert payloads[7] not in got and payloads[31] not in got
    assert payloads[8] in got and payloads[32] in got
    assert r.bad_records == 2 and r.resyncs == 2
    assert r.skipped_bytes > 0

    # strict mode (default): first corruption raises IOError naming file
    r2 = rec.MXRecordIO(str(path), "r")
    with pytest.raises(IOError, match="a.rec"):
        while r2.read() is not None:
            pass
    r2.close()

    # quota exhaustion names the file and counts
    r3 = rec.MXRecordIO(str(path), "r", skip_bad_records=1)
    with pytest.raises(IOError, match="quota exhausted"):
        while r3.read() is not None:
            pass
    r3.close()


def test_bad_record_quota_env(tmp_path, monkeypatch):
    path = tmp_path / "b.rec"
    offsets, payloads = _write_rec(path, n=20)
    with open(str(path), "r+b") as f:
        f.seek(offsets[3])
        f.write(b"\xde\xad\xbe\xef")
    monkeypatch.setenv("MXNET_TPU_BAD_RECORD_QUOTA", "5")
    r = rec.MXRecordIO(str(path), "r")
    n = 0
    while r.read() is not None:
        n += 1
    assert n == 19 and r.bad_records == 1


def test_recordio_fault_seam_skips_and_counts(tmp_path):
    """Injected per-record corruption on a CLEAN file: deterministic
    skip pattern, counts surfaced, remaining records intact."""
    path = tmp_path / "c.rec"
    _, payloads = _write_rec(path, n=50)
    R.configure_faults("recordio.read:p=0.1,seed=3")
    r = rec.MXRecordIO(str(path), "r", skip_bad_records=20)
    got = []
    while True:
        b = r.read()
        if b is None:
            break
        got.append(b)
    assert len(got) + r.bad_records == 50
    assert r.bad_records > 0
    skipped_first = r.bad_records
    for g in got:
        assert g in payloads
    # deterministic: the same spec skips the same records
    R.configure_faults("recordio.read:p=0.1,seed=3")
    r2 = rec.MXRecordIO(str(path), "r", skip_bad_records=20)
    got2 = []
    while True:
        b = r2.read()
        if b is None:
            break
        got2.append(b)
    assert got2 == got and r2.bad_records == skipped_first


def test_unpack_header_errors_are_named():
    with pytest.raises(ValueError, match="invalid IRHeader"):
        rec.unpack(b"\x01\x02")


def test_prefetch_seam_retries_then_surfaces(tmp_path):
    from mxnet_tpu import io as mio
    data = np.arange(64, dtype=np.float32).reshape(16, 4)
    labels = np.zeros(16, np.float32)
    # a bounded fault (n=1) is absorbed by the prefetch retry
    R.configure_faults("io.prefetch:n=1")
    it = mio.PrefetchingIter(mio.NDArrayIter(data, labels, batch_size=4))
    n = 0
    for _ in it:
        n += 1
    assert n == 4
    # an unbounded p=1 fault exhausts the retry and surfaces as an error
    R.configure_faults("io.prefetch")
    it2 = mio.PrefetchingIter(mio.NDArrayIter(data, labels, batch_size=4))
    with pytest.raises(MXNetError, match="io.prefetch"):
        for _ in it2:
            pass


# ------------------------------------------------------------ multihost layer

def test_barrier_fault_bounded_retry_then_error():
    """An armed multihost.barrier seam is retried with backoff, then
    surfaces as MXNetError (the dead-rank detector contract)."""
    R.configure_faults("multihost.barrier:n=1")
    multihost.process_barrier("resilience_test")   # one fault absorbed
    stats = R.fault_stats()["multihost.barrier"]
    assert stats["hits"] == 1 and stats["calls"] >= 2
    R.configure_faults("multihost.barrier")        # always fires
    with pytest.raises(MXNetError, match="process_barrier"):
        multihost.process_barrier("resilience_test")


def test_init_fault_bounded_retry():
    R.configure_faults("multihost.init:n=2")
    multihost.ensure_initialized()   # 2 faults absorbed by 2 retries
    assert R.fault_stats()["multihost.init"]["hits"] == 2
    R.configure_faults("multihost.init")
    with pytest.raises(MXNetError, match="ensure_initialized"):
        multihost.ensure_initialized()


def test_barrier_timeout_on_simulated_hang(monkeypatch):
    """kind=delay simulates a hang; the timeout wrapper + retry bound
    turn it into a clear error instead of an unbounded wait.  (With one
    process sync_global_devices is a no-op, so the hang is the seam's
    own delay — the timeout machinery around it is what's under test.)"""
    monkeypatch.setenv("MXNET_TPU_BARRIER_TIMEOUT", "1")
    t0 = time.monotonic()
    R.configure_faults("multihost.barrier:kind=delay,delay=0.02")
    multihost.process_barrier("delayed")      # stall < timeout: fine
    assert time.monotonic() - t0 < 5.0


# --------------------------------------------------- acceptance: end to end

def _train_from_rec(reader, trainer, prefix, steps, start_step=0,
                    ckpt_every=4, batch=32, feat=64):
    """Train `steps` steps reading (label, data) records from `reader`,
    checkpointing every `ckpt_every`; a failed save is logged and
    skipped (training must survive it).  Returns per-step losses."""
    losses = []
    for step in range(start_step, steps):
        xs, ys = [], []
        while len(xs) < batch:
            raw = reader.read()
            if raw is None:
                reader.reset()
                continue
            header, payload = rec.unpack(raw)
            ys.append(float(header.label))
            xs.append(np.frombuffer(payload, np.float32, count=feat))
        x = np.stack(xs).astype("f")
        y = np.asarray(ys, "f")
        losses.append(float(trainer.step({"data": x,
                                          "softmax_label": y})))
        done = step + 1
        if done % ckpt_every == 0:
            try:
                trainer.save_checkpoint(prefix, done,
                                        save_optimizer_states=True)
            except (R.FaultInjected, MXNetError) as e:
                logging.warning("checkpoint at step %d failed (%s); "
                                "training continues", done, e)
    return losses


def test_faulted_training_recovers_end_to_end(tmp_path):
    """ISSUE 1 acceptance: MXNET_TPU_FAULTS injects a checkpoint-save
    crash and ~5% corrupt records; the run checkpoints, is 'preempted',
    restores from the last VERIFIED checkpoint, completes to the loss
    threshold, and surfaces the skipped-record count."""
    # dataset: 10 gaussian clusters, one record per sample
    rng = np.random.RandomState(9)
    path = str(tmp_path / "train.rec")
    w = rec.MXRecordIO(path, "w")
    for i in range(512):
        y = rng.randint(0, 10)
        x = (_PROTOS[y] + rng.randn(64) * 0.2).astype(np.float32)
        w.write(rec.pack(rec.IRHeader(0, float(y), i, 0), x.tobytes()))
    w.close()

    prefix = str(tmp_path / "job")
    R.configure_faults("recordio.read:p=0.05,seed=7;checkpoint.save:n=1")

    # ---- leg 1: train 10 steps; the step-4 checkpoint save crashes
    # (FaultInjected between tmp write and rename), step-8 save lands
    reader = rec.MXRecordIO(path, "r", skip_bad_records=200)
    trainer = _trainer(seed=5)
    _train_from_rec(reader, trainer, prefix, steps=10)
    skipped_leg1 = reader.bad_records
    assert skipped_leg1 > 0, "5% corruption must have skipped records"
    # the crashed save is invisible; the later one is complete
    eps = find_checkpoints(prefix, require_states=True)
    assert 4 not in eps and 8 in eps

    # ---- leg 2: 'preemption' — a fresh process restores the newest
    # verified checkpoint and trains on to the threshold
    reader2 = rec.MXRecordIO(path, "r", skip_bad_records=200)
    trainer2 = _trainer(seed=5)
    resumed = trainer2.load_latest_checkpoint(prefix,
                                              load_optimizer_states=True)
    assert resumed == 8
    losses = _train_from_rec(reader2, trainer2, prefix, steps=30,
                             start_step=resumed)
    total_skipped = skipped_leg1 + reader2.bad_records
    stats = R.fault_stats()
    assert stats["recordio.read"]["hits"] == total_skipped
    assert stats["checkpoint.save"]["hits"] == 1
    assert losses[-1] < 0.35, losses
    R.clear_faults()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_run_harness(tmp_path):
    """tools/chaos_run.py: a short training job under a sampled fault
    spec recovers cleanly (kept out of tier-1 by the `not slow` filter)."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "chaos_run.py"),
         "--seed", "3", "--steps", "24", "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=540,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "chaos run OK" in res.stdout, res.stdout + res.stderr
