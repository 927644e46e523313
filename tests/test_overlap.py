"""Bucketed async gradient allreduce + double-buffered staging
(ISSUE 15, ROADMAP item 4 — parallel/overlap.py, docs/api/overlap.md).

Unit coverage for the overlap layer: the deterministic bucket plan,
the fleet-agreed scheduler ordering, BucketQueue's launch-on-fill /
ordered-drain / all-or-nothing contract (including the chaos-seamed
mid-drain collective fault), the batched local-replica merge in
DistKVStore.push, the Module update path's bucketed branch
(bit-parity overlap-on vs overlap-off), MXG011's bucketed-schedule
modeling, and the double-buffered H2D staging seams
(DevicePrefetchIter + ShardedTrainer.staged_batches).  The 2-process
acceptance A/B lives in test_dist_multiprocess.py /
tools/overlap_ab.py.
"""
import importlib.util
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import overlap
from mxnet_tpu.telemetry import flight

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_overlap_ab():
    spec = importlib.util.spec_from_file_location(
        "overlap_ab", os.path.join(ROOT, "tools", "overlap_ab.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- planning

def test_plan_buckets_fill_and_determinism():
    sizes = [("a", 100), ("b", 100), ("c", 300), ("d", 10), ("e", 10)]
    plan = overlap.plan_buckets(sizes, target_bytes=200)
    assert plan == [["a", "b"], ["c"], ["d", "e"]]
    # pure function of the input: every rank computes the same plan
    assert plan == overlap.plan_buckets(sizes, target_bytes=200)
    # an oversized key closes its own bucket
    assert overlap.plan_buckets([("big", 999)], 10) == [["big"]]
    # default target comes from MXNET_TPU_BUCKET_BYTES
    old = os.environ.get("MXNET_TPU_BUCKET_BYTES")
    os.environ["MXNET_TPU_BUCKET_BYTES"] = "150"
    try:
        assert overlap.bucket_bytes() == 150
        assert overlap.plan_buckets(sizes) == \
            overlap.plan_buckets(sizes, 150)
    finally:
        if old is None:
            os.environ.pop("MXNET_TPU_BUCKET_BYTES")
        else:
            os.environ["MXNET_TPU_BUCKET_BYTES"] = old


def test_scheduler_slowest_first_and_fleet_deterministic():
    s1, s2 = overlap.OverlapScheduler(), overlap.OverlapScheduler()
    # two "ranks" feeding the SAME fleet-agreed skews stay identical
    for s in (s1, s2):
        s.observe_skew(0, 0.01)
        s.observe_skew(1, 0.05)
        s.observe_skew(2, 0.03)
        s.observe_skew(1, 0.04)
    assert s1.order([0, 1, 2]) == s2.order([0, 1, 2]) == [1, 2, 0]
    # unmeasured buckets keep id order (cost 0, id tiebreak)
    assert s1.order([5, 3, 4]) == [3, 4, 5]


# ---------------------------------------------------------- BucketQueue

def _mk_queue(target=64, launches=None):
    launches = launches if launches is not None else []

    def reduce_fn(bucket):
        launches.append(sorted(bucket))
        return lambda: {k: v * 2 for k, v in bucket.items()}

    q = overlap.BucketQueue(reduce_fn, target_bytes=target,
                            site="test.push", skew_probe=lambda: None)
    return q, launches


def test_bucket_queue_launch_on_fill_and_drain():
    q, launches = _mk_queue(target=64)
    for i, k in enumerate("abcd"):
        q.push(k, float(i), 32)          # 2 keys fill one 64-byte bucket
    assert launches == [["a", "b"], ["c", "d"]]   # launched during push
    q.push("e", 9.0, 8)                  # tail bucket, below target
    assert q.pending == 3
    n0 = len([e for e in flight.events()
              if e.get("kind") == "overlap"])
    out = q.drain()
    assert launches[-1] == ["e"]
    assert out == {"a": 0.0, "b": 2.0, "c": 4.0, "d": 6.0, "e": 18.0}
    assert q.pending == 0
    evs = [e for e in flight.events() if e.get("kind") == "overlap"]
    assert len(evs) > n0
    drains = [e for e in evs if e.get("op") == "drain"]
    assert drains and drains[-1]["buckets"] == 3
    launches_ev = [e for e in evs if e.get("op") == "bucket_launch"]
    assert {e["phase"] for e in launches_ev} == {"backward", "drain"}
    # a second round reuses the queue cleanly
    q.push("f", 1.0, 8)
    assert q.drain() == {"f": 2.0}


def test_bucket_queue_drain_uses_scheduler_order():
    q, launches = _mk_queue(target=1 << 30)   # nothing fills early
    # seed the scheduler: bucket ids are assigned in creation order,
    # but with one open bucket at drain the ordering is trivial — so
    # drive the scheduler API directly for the ordering property
    sched = q.scheduler
    sched.observe_skew(7, 0.2)
    sched.observe_skew(3, 0.9)
    assert sched.order([3, 7]) == [3, 7]
    q.push("x", 1.0, 4)
    assert q.drain() == {"x": 2.0}


def test_bucket_queue_duplicate_key_refused():
    q, _ = _mk_queue(target=1 << 30)
    q.push("a", 1.0, 4)
    with pytest.raises(MXNetError, match="already holds key"):
        q.push("a", 2.0, 4)


def test_bucket_queue_transport_error_names_bucket():
    def bad_reduce(bucket):
        def handle():
            raise RuntimeError("peer died")
        return handle

    q = overlap.BucketQueue(bad_reduce, target_bytes=1 << 30,
                            site="test.push", skew_probe=lambda: None)
    q.push("a", 1.0, 4)
    with pytest.raises(MXNetError) as ei:
        q.drain()
    msg = str(ei.value)
    assert "bucket 0" in msg and "optimizer state is untouched" in msg
    assert q.pending == 0                 # reusable after the failure


@pytest.mark.chaos
def test_collective_fault_mid_drain_leaves_optimizer_state_untouched(
        tmp_path):
    """ISSUE 15 satellite: an injected ``kvstore.collective`` fault
    mid-bucket-drain must surface as a descriptive MXNetError with NO
    partially-applied buckets — the store's weights (the optimizer
    state of the update_on_kvstore contract) stay bit-identical, and
    the next clean drain applies normally."""
    ab = _load_overlap_ab()
    transport = ab.FileAllreduce(str(tmp_path), rank=0, world=1)
    kv = ab._OverlapABStore(transport, "on", bucket_bytes=16)
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         rescale_grad=1.0))
    keys = list(range(4))
    for k in keys:
        kv.init(k, mx.nd.ones((4,)) * (k + 1))
    before = {k: kv._store[k].asnumpy().copy() for k in keys}

    # two 16-byte buckets launch during the pushes; the third (tail)
    # launches mid-drain — arm the seam now so the DRAIN-phase launch
    # is the one that faults, with real in-flight buckets pending
    for k in keys[:3]:
        kv.push_bucketed(k, mx.nd.ones((4,)))
    kv.push_bucketed(3, mx.nd.ones((1,)))       # tail, below target
    resilience.configure_faults("kvstore.collective:n=1")
    try:
        with pytest.raises(MXNetError) as ei:
            kv.drain()
    finally:
        resilience.clear_faults()
    assert "optimizer state is untouched" in str(ei.value)
    after = {k: kv._store[k].asnumpy() for k in keys}
    for k in keys:
        np.testing.assert_array_equal(before[k], after[k])

    # clean retry: re-push everything, drain applies exactly once
    for k in keys[:3]:
        kv.push_bucketed(k, mx.nd.ones((4,)))
    kv.push_bucketed(3, mx.nd.ones((1,)))
    kv.drain()
    for k in keys[:3]:
        np.testing.assert_allclose(kv._store[k].asnumpy(),
                                   before[k] - 0.1)


# ------------------------------------------- DistKVStore local merge

def test_dist_kvstore_batched_merge_matches_serial():
    kv = mx.kv.create("dist_sync")       # single process: world of 1
    a = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    b = mx.nd.array(np.ones((2, 3), np.float32) * 0.25)
    c = mx.nd.array(np.full((2, 3), -1.5, np.float32))
    merged, nbytes = kv._merge_local([7, 7, 7], [a, b, c])
    assert list(merged) == [7]
    np.testing.assert_array_equal(
        merged[7].asnumpy(),
        a.asnumpy() + b.asnumpy() + c.asnumpy())
    assert nbytes == 24
    # single-member groups pass through without the defensive copy...
    merged2, _ = kv._merge_local(3, a)
    assert merged2[3] is a
    # ...but a store assignment still must not alias the caller's
    # gradient (push copies on store for the single-process path)
    kv._store.clear()
    kv.push(3, a)
    a[:] = 0
    np.testing.assert_array_equal(
        kv._store[3].asnumpy(),
        np.arange(6, dtype=np.float32).reshape(2, 3))


def test_dist_kvstore_user_updater_gets_private_recv_buffer():
    """The single-member merge skips the defensive copy, so the apply
    path must re-protect: a user updater mutating its recv gradient in
    place (the reference contract allows it) must not corrupt the
    caller's live gradient array."""
    kv = mx.kv.create("dist_sync")
    kv.init(5, mx.nd.zeros((4,)))

    def scaling_updater(key, recv, stored):
        recv *= 2                        # in place, on the recv buffer
        stored += recv

    kv.set_updater(scaling_updater)
    g = mx.nd.ones((4,))
    kv.push(5, g)
    np.testing.assert_array_equal(g.asnumpy(), np.ones(4))
    np.testing.assert_array_equal(kv._store[5].asnumpy(),
                                  np.ones(4) * 2)


def test_dist_kvstore_pull_drains_inflight_buckets():
    """push_bucketed → pull without an explicit drain() must join the
    in-flight buckets first (same guard as AsyncKVStore.pull) instead
    of silently returning the stale pre-drain values."""
    kv = mx.kv.create("dist_sync")
    kv.init(1, mx.nd.zeros((3,)))
    # pretend fleet: the bucketed path only engages multi-worker, and
    # the fake reduce stands in for the cross-host allreduce
    kv._num_workers = 2
    kv._bucket_queue = overlap.BucketQueue(
        lambda bucket: (lambda: {k: v * 2 for k, v in bucket.items()}),
        target_bytes=1 << 30, site="kvstore.push",
        skew_probe=lambda: None)
    kv.push_bucketed(1, mx.nd.ones((3,)))
    assert kv._bucket_queue.pending == 1
    out = mx.nd.zeros((3,))
    kv.pull(1, out=out)
    assert kv._bucket_queue.pending == 0   # pull joined the buckets
    np.testing.assert_array_equal(out.asnumpy(), np.ones(3) * 2)


def test_dist_kvstore_overlap_inactive_single_process():
    kv = mx.kv.create("dist_sync")
    assert kv.num_workers == 1
    assert kv.overlap_active is False    # no collective to hide
    # push_bucketed degrades to the synchronous push semantics
    kv.init(1, mx.nd.zeros((3,)))
    kv.push_bucketed(1, mx.nd.ones((3,)))
    kv.drain()                           # no-op, nothing pending
    np.testing.assert_array_equal(kv._store[1].asnumpy(), np.ones(3))


# ------------------------------------- Module path: on/off bit parity

def _train_module(tmp_path, mode, steps=4):
    ab = _load_overlap_ab()
    root = str(tmp_path / mode)
    os.makedirs(root, exist_ok=True)
    transport = ab.FileAllreduce(root, rank=0, world=1)
    kv = ab._OverlapABStore(transport, mode, bucket_bytes=2048)

    protos = np.random.RandomState(42).rand(10, 64).astype("f")
    rng = np.random.RandomState(5)
    y = rng.randint(0, 10, 256)
    x = (protos[y] + rng.randn(256, 64) * 0.25).astype("f")
    it = mx.io.NDArrayIter(x, y.astype("f"), batch_size=64,
                           label_name="softmax_label")
    np.random.seed(7)
    mx.random.seed(7)
    mod = mx.module.Module(ab._mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore=kv, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    count = 0
    while count < steps:
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()                 # routes per kv.overlap_active
            count += 1
            if count >= steps:
                break
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def test_module_update_bit_parity_overlap_on_vs_off(tmp_path):
    """The bucketed drain branch of _update_params_on_kvstore must be
    bit-identical to the legacy per-key push/pull interleave — overlap
    is a scheduling change, never a numeric one."""
    p_off = _train_module(tmp_path, "off")
    p_on = _train_module(tmp_path, "on")
    assert sorted(p_off) == sorted(p_on)
    for k in p_off:
        assert p_off[k].tobytes() == p_on[k].tobytes(), k


# -------------------------------------------------- MXG011 modeling

def test_mxg011_models_bucketed_schedule():
    from mxnet_tpu import analysis
    from mxnet_tpu.analysis import spmd

    # the plan-order schedule (the overlap invariant) verifies clean
    cfg = analysis.build_config(kv_push=True,
                                kv_buckets=[4096, 2048, 1024])
    rep = spmd.verify_spmd(None, {"data": 2}, cfg)
    assert rep.ok, str(rep)
    # schedule shape: one sampled barrier + one allreduce per bucket
    sched = spmd.collective_schedule(None, {"data": 2}, cfg)
    ops = [(e.op, e.shape) for e in sched[0]["bwd"]
           if e.node and e.node.startswith("kv.")]
    assert ops == [("barrier", ()), ("allreduce", (4096,)),
                   ("allreduce", (2048,)), ("allreduce", (1024,))]

    # a seeded rank-divergent launch order is the reordering defect:
    # MXG011 fires naming the first mismatched bucket
    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        kv_push=True, kv_buckets=[4096, 2048, 1024],
        kv_bucket_order={1: [2, 1, 0]}))
    bad = [d for d in rep if d.rule == "MXG011"]
    assert bad, str(rep)
    assert "kv.bucket" in str(bad[0])
    assert "diverges" in bad[0].message


def test_mxg011_equal_size_buckets_divergent_order_detected():
    """EQUAL-sized buckets in rank-divergent launch order must still be
    flagged: the (op, axis, shape, dtype) surface matches, but the
    operand is a keyed pytree — reducing rank A's bucket 0 against
    rank B's bucket 1 corrupts both silently (no deadlock), so the
    matching key carries the payload identity too.  A transformer's N
    identical layers make equal-size buckets the COMMON case."""
    from mxnet_tpu import analysis
    from mxnet_tpu.analysis import spmd

    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        kv_push=True, kv_buckets=[1024, 1024],
        kv_bucket_order={1: [1, 0]}))
    bad = [d for d in rep if d.rule == "MXG011"]
    assert bad, str(rep)
    assert "kv.bucket" in str(bad[0])
    assert "payload" in bad[0].message
    # the agreed plan order over equal sizes stays clean
    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        kv_push=True, kv_buckets=[1024, 1024]))
    assert rep.ok, str(rep)


# ------------------------------------- double-buffered H2D staging

def test_device_prefetch_double_buffer_order_and_exhaustion():
    x = np.arange(48, dtype=np.float32).reshape(12, 4)
    it = mx.io.NDArrayIter(x, np.zeros(12, np.float32), batch_size=4,
                           label_name="softmax_label")
    seen = []

    def stage(host):
        seen.append(host["data"][0, 0])
        return dict(host)

    import time
    pre = mx.io.DevicePrefetchIter(it, stage, depth=1)
    got = []
    for batch in pre:
        time.sleep(0.01)                 # slow consumer: queue backs up
        got.append(batch["data"][0, 0])
    assert got == [0.0, 16.0, 32.0]      # order preserved, none lost
    assert seen == got
    with pytest.raises(StopIteration):
        next(pre)                        # stays exhausted
    pre.reset()
    assert next(pre)["data"][0, 0] == 0.0


def test_device_prefetch_serial_when_overlap_off():
    old = os.environ.get("MXNET_TPU_OVERLAP")
    os.environ["MXNET_TPU_OVERLAP"] = "0"
    try:
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        it = mx.io.NDArrayIter(x, np.zeros(8, np.float32), batch_size=4,
                               label_name="softmax_label")
        pre = mx.io.DevicePrefetchIter(it, dict, depth=1)
        got = [b["data"][0, 0] for b in pre]
        assert got == [0.0, 16.0]
    finally:
        if old is None:
            os.environ.pop("MXNET_TPU_OVERLAP")
        else:
            os.environ["MXNET_TPU_OVERLAP"] = old


def _tiny_trainer():
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    # one seed: two constructions get bit-identical initial params
    return ShardedTrainer(
        models.get_model("mlp", num_classes=10), build_mesh(tp=1),
        data_shapes={"data": (8, 64)},
        label_shapes={"softmax_label": (8,)}, dtype="float32", seed=3)


def test_trainer_staged_batches_matches_inline_steps():
    rng = np.random.RandomState(0)
    batches = [{"data": rng.uniform(-1, 1, (8, 64)).astype("f"),
                "softmax_label": rng.randint(0, 10, 8).astype("f")}
               for _ in range(3)]
    t_inline = _tiny_trainer()
    inline = [float(t_inline.step(b)) for b in batches]
    t_staged = _tiny_trainer()
    staged = [float(t_staged.step(dev))
              for dev in t_staged.staged_batches(batches)]
    assert staged == inline              # staging never changes math
    # staged batches are device arrays: the step charges no input_wait
    import jax
    dev = next(iter(t_staged.staged_batches([batches[0]])))
    assert isinstance(next(iter(dev.values())), jax.Array)
