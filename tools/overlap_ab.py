#!/usr/bin/env python
"""overlap_ab — the 2-process overlap-on/overlap-off A/B dry run.

ISSUE 15 acceptance evidence (ROADMAP item 4): with a seeded slow rank,
the bucketed overlap path on and off reach bit-identical final
parameters, and the on leg launches the same whole number of buckets a
step on both ranks.  The FAST rank's measured collective wait
(``mxtpu_collective_wait_seconds``) and its step-segment
``collective_wait`` share are reported for both modes; they are two wall
times on a shared machine, so they are printed and not gated.

Design: this jax/CPU backend cannot run real cross-process collectives
(the long-standing dist_multiprocess constraint, see
``tests/dist_distview_worker.py``), so the worker trains a REAL
``Module.fit``-style loop through a kvstore whose allreduce transport
is the filesystem — each rank atomically publishes its per-bucket
arrays and sums all ranks' files in rank order.  Everything else is
the production machinery: the overlap-on leg routes through
``model._update_params_on_kvstore``'s bucketed branch,
``parallel.overlap.BucketQueue`` (async bucket launches, ordered
drain, flight events, ``mxtpu_overlap_*`` metrics), while the
overlap-off leg mirrors ``DistKVStore.push``'s per-key
barrier-then-allreduce.  The transport's measured blocking waits land
in ``mxtpu_collective_wait_seconds`` and the step's ``collective_wait``
segment exactly where the real pre-collective barrier puts them.

What overlap hides here is what it hides on a pod: the per-collective
transport latency serializes on the critical path in off mode (one
barrier + synchronous reduce per key), while in on mode the bucket
publishes ride behind gradient production and the drain only pays the
residual skew — the (N-1) hidden transfers are the measured win.

Usage::

    python tools/overlap_ab.py [--steps 6] [--slow-s 0.008] [--json]
    python tools/overlap_ab.py --worker     # run by launch.py, not you

The driver launches ``tools/launch.py -n 2`` twice (off, then on),
reports the fast rank's wait totals and segment shares, verifies the
final params of BOTH ranks are bit-identical across modes, and checks
the on-leg's ``overlap`` bucket flight events parse via
``tools/flight_read.py`` and count the same on both ranks, a whole
number a step.  Prints one ``mxtpu-overlap-ab/1`` JSON document; exit 0
when every gate holds, 1 otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)

SCHEMA = "mxtpu-overlap-ab/1"


# --------------------------------------------------------------- worker

def _file_barrier(root, tag, rank, world, poll, timeout=120.0):
    """Filesystem rendezvous: publish arrival, wait for every peer.
    Returns this rank's measured wait seconds (≈0 on the straggler,
    ≈the straggler's lead on the fast ranks — the pre-collective
    barrier's semantics)."""
    open(os.path.join(root, "%s.arrive%d" % (tag, rank)), "w").close()
    t0 = time.perf_counter()
    deadline = t0 + timeout
    for r in range(world):
        p = os.path.join(root, "%s.arrive%d" % (tag, r))
        while not os.path.exists(p):
            if time.perf_counter() > deadline:
                raise RuntimeError("barrier %s: rank %d never arrived"
                                   % (tag, r))
            time.sleep(poll)
    return time.perf_counter() - t0


class FileAllreduce:
    """Sum-across-ranks over a shared directory: atomic per-rank npz
    publish + poll-read of every peer, summed in rank order (the fixed
    reduction order that keeps on/off bit parity)."""

    def __init__(self, root, rank, world, poll=0.002):
        self.root = root
        self.rank = rank
        self.world = world
        self.poll = poll
        self.seq = 0
        self.wait_s = 0.0       # accumulated blocking wait (taken per step)

    def _note_wait(self, dt):
        self.wait_s += dt
        from mxnet_tpu.telemetry.registry import histogram
        histogram("mxtpu_collective_wait_seconds").observe(dt)

    def launch(self, arrays):
        """Publish this rank's contribution; returns the handle that
        materializes the summed result (polls the peers — the lazy
        half, exactly BucketQueue's reduce_fn contract)."""
        import numpy as np
        tag = "b%06d" % self.seq
        self.seq += 1
        mine = {str(k): np.asarray(
            v.asnumpy() if hasattr(v, "asnumpy") else v, np.float32)
            for k, v in arrays.items()}
        tmp = os.path.join(self.root, "%s.r%d.tmp" % (tag, self.rank))
        dst = os.path.join(self.root, "%s.r%d.npz" % (tag, self.rank))
        with open(tmp, "wb") as f:
            np.savez(f, **mine)
        os.replace(tmp, dst)

        def handle():
            t0 = time.perf_counter()
            deadline = t0 + 120.0
            total = None
            for r in range(self.world):
                p = os.path.join(self.root, "%s.r%d.npz" % (tag, r))
                while not os.path.exists(p):
                    if time.perf_counter() > deadline:
                        raise RuntimeError(
                            "allreduce %s: rank %d never published"
                            % (tag, r))
                    time.sleep(self.poll)
                with np.load(p) as z:
                    part = {k: z[k] for k in z.files}
                total = part if total is None else \
                    {k: total[k] + part[k] for k in total}
            self._note_wait(time.perf_counter() - t0)
            from mxnet_tpu import ndarray as nd
            return {_unkey(k): nd.array(v) for k, v in total.items()}
        return handle

    def take_wait(self):
        w, self.wait_s = self.wait_s, 0.0
        return w


def _unkey(k):
    try:
        return int(k)
    except ValueError:
        return k


def _OverlapABStore(transport, mode, slow_rank=-1, slow_s=0.0,
                    bucket_bytes=None):
    """Build a ``dist_sync``-shaped kvstore over the file transport
    (factory so this module's top-level imports stay stdlib-only —
    Module's kvstore resolution requires a real KVStore subclass).

    Off mode mirrors ``DistKVStore.push`` — per-key fleet barrier
    (measured wait) then a synchronous allreduce then the updater; on
    mode exposes the overlap surface (``overlap_active`` /
    ``push_bucketed`` / ``drain``) through the REAL
    ``parallel.overlap.BucketQueue``, so ``model.
    _update_params_on_kvstore`` takes its production bucketed branch.
    The seeded slow rank sleeps ``slow_s`` per pushed key — gradient
    production skew, identical in both modes."""
    from mxnet_tpu.kvstore import (KVStore, _ctype_key_value,
                                   _group_kv_pairs)
    from mxnet_tpu.parallel import overlap as _overlap

    class Store(KVStore):
        def __init__(self):
            super().__init__("dist_sync")
            self._transport = transport
            self._mode = mode
            self._slow = slow_s if transport.rank == slow_rank else 0.0
            self._queue = _overlap.BucketQueue(
                lambda bucket: transport.launch(bucket),
                target_bytes=bucket_bytes, site="overlap_ab.push",
                skew_probe=lambda: None)

        @property
        def rank(self):
            return self._transport.rank

        @property
        def num_workers(self):
            return self._transport.world

        @property
        def overlap_active(self):
            return self._mode == "on"

        def _merge(self, key, value):
            keys, vals = _ctype_key_value(key, value)
            uniq, grouped = _group_kv_pairs(keys, vals)
            out = {}
            for k, group in zip(uniq, grouped):
                m = group[0]
                if len(group) > 1:
                    m = m.copy()
                    for other in group[1:]:
                        m += other
                out[k] = m
            return out

        def push(self, key, value, priority=0):
            merged = self._merge(key, value)
            for k, m in merged.items():
                if self._slow:
                    time.sleep(self._slow)   # seeded slow production
                t = self._transport
                wait = _file_barrier(t.root, "k%06d" % t.seq, t.rank,
                                     t.world, t.poll)
                t._note_wait(wait)
                reduced = t.launch({k: m})()  # synchronous, per key
                self._apply(reduced)

        def push_bucketed(self, key, value, priority=0):
            import numpy as np
            merged = self._merge(key, value)
            for k, m in merged.items():
                if self._slow:
                    time.sleep(self._slow)   # seeded slow production
                nbytes = int(np.prod(m.shape)) * 4
                self._queue.push(k, m, nbytes)

        def drain(self):
            reduced = self._queue.drain(
                mesh={"hosts": self._transport.world})
            self._apply(reduced)

        def _apply(self, reduced):
            for k, m in reduced.items():
                self._updater(k, m, self._store[k])

        def pull(self, key, out=None, priority=0):
            keys, outs = _ctype_key_value(key, out)
            for k, o in zip(keys, outs):
                o[:] = self._store[k]

        def barrier(self):
            pass

    return Store()


def _mlp():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=48)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=24)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc3", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def worker_main():
    import numpy as np

    sys.path.insert(0, _ROOT)
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import distview, flight

    rank = int(os.environ.get("MXNET_TPU_PROCESS_ID", "0"))
    world = int(os.environ.get("MXNET_TPU_NUM_PROCESSES", "1"))
    mode = os.environ.get("OVERLAP_AB_MODE", "on")
    root = os.environ["OVERLAP_AB_DIR"]
    steps = int(os.environ.get("OVERLAP_AB_STEPS", "6"))
    slow_rank = int(os.environ.get("OVERLAP_AB_SLOW_RANK", "1"))
    slow_s = float(os.environ.get("OVERLAP_AB_SLOW_S", "0.008"))
    bucket_bytes = int(os.environ.get("MXNET_TPU_BUCKET_BYTES", "4096"))

    transport = FileAllreduce(root, rank, world)
    kv = _OverlapABStore(transport, mode, slow_rank=slow_rank,
                         slow_s=slow_s, bucket_bytes=bucket_bytes)

    # identical init on every rank; per-rank data shards
    protos = np.random.RandomState(42).rand(10, 64).astype("f")
    rng = np.random.RandomState(100 + rank)
    y = rng.randint(0, 10, 512)
    x = (protos[y] + rng.randn(512, 64) * 0.25).astype("f")
    it = mx.io.NDArrayIter(x, y.astype("f"), batch_size=64,
                           label_name="softmax_label")

    np.random.seed(7)
    mx.random.seed(7)
    mod = mx.module.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore=kv, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})

    wait_total = 0.0
    step_total = 0.0
    batches = iter(it)
    for _ in range(steps):
        try:
            batch = next(batches)
        except StopIteration:
            it.reset()
            batches = iter(it)
            batch = next(batches)
        t0 = time.perf_counter()
        mod.forward_backward(batch)
        mod.update()                      # the sync under test
        total = time.perf_counter() - t0
        collective_s = transport.take_wait()
        wait_total += collective_s
        step_total += total
        segments = distview.record_step_segments(
            total, input_s=0.0, collective_s=collective_s)
        telemetry.step_end(samples=batch.data[0].shape[0],
                           step_time=total,
                           extra={"segments": segments})

    # final params, for the cross-mode bit-parity gate
    args, _aux = mod.get_params()
    out = {k: v.asnumpy() for k, v in args.items()}
    np.savez(os.path.join(root, "params.%s.r%d.npz" % (mode, rank)),
             **out)
    if flight.dump_dir():
        flight.dump("overlap_ab")

    share = wait_total / step_total if step_total > 0 else 0.0
    print("overlap-ab worker %d/%d OK mode=%s wait_s=%.6f share=%.6f"
          % (rank, world, mode, wait_total, share))


# --------------------------------------------------------------- driver

def _scrubbed_env(extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_NUM_PROCESSES", None)
    env.pop("MXNET_TPU_PROCESS_ID", None)
    env.update(extra)
    return env


def _run_leg(mode, workdir, steps, slow_s, timeout=300):
    import shutil
    import subprocess
    root = os.path.join(workdir, mode)
    # fresh transport dir: stale barrier/bucket files from a previous
    # attempt would satisfy the polls instantly and zero the waits
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root, exist_ok=True)
    flight_dir = os.path.join(root, "flight")
    os.makedirs(flight_dir, exist_ok=True)
    env = _scrubbed_env({
        "OVERLAP_AB_MODE": mode,
        "OVERLAP_AB_DIR": root,
        "OVERLAP_AB_STEPS": str(steps),
        "OVERLAP_AB_SLOW_RANK": "1",
        "OVERLAP_AB_SLOW_S": "%g" % slow_s,
        "MXNET_TPU_BUCKET_BYTES": "4096",
        "MXNET_TPU_FLIGHT_DIR": flight_dir,
        "MXNET_TPU_TELEMETRY_JSONL": os.path.join(root, "run.jsonl"),
    })
    res = subprocess.run(
        [sys.executable, os.path.join(_HERE, "launch.py"),
         "-n", "2", "--launcher", "local",
         "--heartbeat-interval", "0.1",
         "--", sys.executable, os.path.abspath(__file__), "--worker"],
        capture_output=True, text=True, timeout=timeout, cwd=_ROOT,
        env=env)
    out = res.stdout + res.stderr
    if res.returncode != 0:
        raise RuntimeError("overlap_ab %s leg failed (%d):\n%s"
                           % (mode, res.returncode, out[-2000:]))
    # regex over the whole capture, not splitlines: the local
    # launcher's merged stream can butt two ranks' OK records together
    # with no newline between them
    import re
    pat = re.compile(r"overlap-ab worker (\d+)/\d+ OK mode=%s "
                     r"wait_s=([0-9.eE+-]+?) share=([0-9.eE+-]+?)"
                     r"(?=overlap-ab|\s|$)" % re.escape(mode))
    ranks = {}
    for m in pat.finditer(out):
        ranks[int(m.group(1))] = {"wait_s": float(m.group(2)),
                                  "share": float(m.group(3))}
    if sorted(ranks) != [0, 1]:
        raise RuntimeError("overlap_ab %s leg: missing worker OK lines"
                           ":\n%s" % (mode, out[-2000:]))
    return {"root": root, "flight_dir": flight_dir, "ranks": ranks}


def _count_overlap_flight_events(flight_dir):
    """Parse every dump in the leg's flight dir through
    tools/flight_read.py and count well-formed ``overlap`` bucket
    events by the rank that dumped them (the gate: they must exist AND
    parse, as many on one rank as on the other)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "flight_read", os.path.join(_HERE, "flight_read.py"))
    fr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fr)
    by_rank = {}
    for name in sorted(os.listdir(flight_dir)):
        if not (name.startswith("flight-") and name.endswith(".json")):
            continue
        doc = fr.load(os.path.join(flight_dir, name))
        for ev in doc["events"]:
            if ev.get("kind") == "overlap" and \
                    ev.get("op") == "bucket_launch" and \
                    isinstance(ev.get("bucket"), int) and \
                    isinstance(ev.get("bytes"), int):
                rank = "rank%s" % doc.get("rank")
                by_rank[rank] = by_rank.get(rank, 0) + 1
    return by_rank


def _params_bit_identical(workdir):
    import numpy as np
    ok = True
    detail = {}
    for r in (0, 1):
        a = np.load(os.path.join(workdir, "off",
                                 "params.off.r%d.npz" % r))
        b = np.load(os.path.join(workdir, "on",
                                 "params.on.r%d.npz" % r))
        same = sorted(a.files) == sorted(b.files) and all(
            a[k].tobytes() == b[k].tobytes() for k in a.files)
        detail["rank%d" % r] = bool(same)
        ok = ok and same
    return ok, detail


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--worker", action="store_true",
                    help="run as a per-rank worker (launch.py mode)")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--slow-s", type=float, default=0.008,
                    help="seeded per-key production lag of rank 1")
    ap.add_argument("--workdir", default=None,
                    help="keep artifacts here instead of a tmpdir")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.worker:
        worker_main()
        return 0

    import shutil
    import tempfile

    workdir = args.workdir or tempfile.mkdtemp(prefix="mxtpu_overlap_ab_")
    try:
        off = _run_leg("off", workdir, args.steps, args.slow_s)
        on = _run_leg("on", workdir, args.steps, args.slow_s)
        bit_ok, bit_detail = _params_bit_identical(workdir)
        buckets = _count_overlap_flight_events(on["flight_dir"])
    finally:
        if args.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)
    fast = 0      # rank 1 is the seeded straggler
    wait_off, wait_on = (leg["ranks"][fast]["wait_s"] for leg in (off, on))
    share_off, share_on = (leg["ranks"][fast]["share"] for leg in (off, on))
    # both ranks train one model: the same buckets every step on each
    buckets_ok = sorted(buckets) == ["rank0", "rank1"] and \
        len(set(buckets.values())) == 1 and \
        all(n > 0 and n % args.steps == 0 for n in buckets.values())
    doc = {
        "schema": SCHEMA,
        "steps": args.steps,
        "slow_s": args.slow_s,
        "fast_rank": fast,
        # reported, not gated: two wall times on a shared machine
        "off": {"wait_s": round(wait_off, 6), "share": round(share_off, 6)},
        "on": {"wait_s": round(wait_on, 6), "share": round(share_on, 6)},
        "wait_reduction": round(1 - wait_on / wait_off, 4)
        if wait_off > 0 else None,
        "overlap_flight_events": sum(buckets.values()),
        "overlap_buckets_by_rank": buckets,
        "params_bit_identical": bit_ok,
        "params_by_rank": bit_detail,
        "pass": bool(bit_ok and buckets_ok),
    }
    print(json.dumps(doc) if args.json else json.dumps(doc, indent=2))
    return 0 if doc["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
