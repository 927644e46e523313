#!/usr/bin/env python
"""Chaos harness: a short training job under a sampled fault spec.

Samples a fault-injection spec from a seeded RNG (so every run is
reproducible from its seed alone), arms it via
``mxnet_tpu.resilience.configure_faults``, trains a small cluster-MLP
job reading records through the tolerant RecordIO path with periodic
atomic checkpoints, simulates a mid-run preemption (fresh trainer +
``load_latest_checkpoint``), and asserts clean recovery: the loss
threshold is reached, skipped-record counts line up with the injection
stats, and no crashed save is ever visible to the loader.

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_run.py --seed 3 --steps 24

Exit code 0 = recovered cleanly.  Pytest wrapper:
``tests/test_resilience.py::test_chaos_run_harness`` (markers
``chaos`` + ``slow`` keep it out of tier-1).
"""
from __future__ import annotations

import argparse
import logging
import os
import random
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def sample_spec(rng):
    """A random-but-reproducible fault spec: corrupt records at a
    sampled rate, plus (usually) one checkpoint-save crash, a few
    prefetch/barrier hiccups, and — since the resume leg reshapes the
    mesh when more than one device exists — elastic-path faults in the
    reshard gather/scatter/rejoin seams (docs/api/reshard.md)."""
    parts = ["recordio.read:p=%.3f,seed=%d"
             % (rng.uniform(0.01, 0.08), rng.randrange(1 << 16))]
    if rng.random() < 0.8:
        parts.append("checkpoint.save:n=1,after=%d" % rng.randrange(3))
    if rng.random() < 0.5:
        parts.append("io.prefetch:p=0.2,seed=%d,n=4"
                     % rng.randrange(1 << 16))
    if rng.random() < 0.5:
        parts.append("multihost.barrier:n=1")
    if rng.random() < 0.4:
        parts.append("reshard.scatter:n=1")
    if rng.random() < 0.3:
        parts.append("reshard.gather:n=1,after=%d" % rng.randrange(4))
    if rng.random() < 0.3:
        parts.append("elastic.rejoin:n=1")
    # the exactly-once data plane (docs/api/io_resume.md): the resume
    # leg restores the reader's durable state and remaps a ledger
    # cursor, so mid-restore faults must leave both retryable from the
    # very same state (n=1: one shot, the in-harness retry must land)
    if rng.random() < 0.4:
        parts.append("io.resume:n=1")
    if rng.random() < 0.3:
        parts.append("io.remap:n=1")
    return ";".join(parts)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="chaos seed: fixes the sampled spec AND the "
                         "data/model RNGs")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--loss-threshold", type=float, default=0.35)
    ap.add_argument("--workdir", type=str, default=None)
    opts = ap.parse_args()
    if opts.steps < opts.ckpt_every + 2:
        # leg 1 must land >= 1 checkpoint and leg 2 must train >= 1 step
        ap.error("--steps must be at least --ckpt-every + 2 (got "
                 "steps=%d, ckpt-every=%d)" % (opts.steps, opts.ckpt_every))

    import mxnet_tpu as mx
    from mxnet_tpu import recordio as rec
    from mxnet_tpu import resilience as R
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.model import find_checkpoints
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh

    logging.basicConfig(level=logging.WARNING)
    workdir = opts.workdir or tempfile.mkdtemp(prefix="mxtpu_chaos_")
    rng = random.Random(opts.seed)
    spec = sample_spec(rng)
    print("chaos spec (seed %d): %s" % (opts.seed, spec))

    # ---- dataset: 10 gaussian clusters in .rec records
    protos = np.random.RandomState(42).rand(10, 64).astype("f")
    drng = np.random.RandomState(opts.seed + 1)
    path = os.path.join(workdir, "chaos.rec")
    w = rec.MXRecordIO(path, "w")
    for i in range(16 * opts.batch):
        y = drng.randint(0, 10)
        x = (protos[y] + drng.randn(64) * 0.2).astype(np.float32)
        w.write(rec.pack(rec.IRHeader(0, float(y), i, 0), x.tobytes()))
    w.close()

    def make_trainer(mesh=None):
        net = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        return ShardedTrainer(
            net, mesh if mesh is not None else build_mesh(tp=1),
            data_shapes={"data": (opts.batch, 64)},
            label_shapes={"softmax_label": (opts.batch,)},
            learning_rate=0.15, momentum=0.9, seed=5)

    def run_leg(trainer, reader, prefix, start, steps):
        losses = []
        for step in range(start, steps):
            xs, ys = [], []
            while len(xs) < opts.batch:
                raw = reader.read()
                if raw is None:
                    reader.reset()
                    continue
                header, payload = rec.unpack(raw)
                ys.append(float(header.label))
                xs.append(np.frombuffer(payload, np.float32, count=64))
            losses.append(float(trainer.step(
                {"data": np.stack(xs).astype("f"),
                 "softmax_label": np.asarray(ys, "f")})))
            if (step + 1) % opts.ckpt_every == 0:
                try:
                    trainer.save_checkpoint(prefix, step + 1,
                                            save_optimizer_states=True)
                except MXNetError as e:
                    print("checkpoint at step %d failed under chaos "
                          "(%s); continuing" % (step + 1, e))
        return losses

    prefix = os.path.join(workdir, "job")
    R.configure_faults(spec)
    quota = 4 * opts.steps * opts.batch          # generous: chaos != quota test

    half = max(opts.ckpt_every + 1, opts.steps // 2)
    reader = rec.MXRecordIO(path, "r", skip_bad_records=quota)
    # leg 1 trains on a single-device mesh so that leg 2's resume on
    # the full device set is a genuine mesh reshape (the elastic
    # reshard.gather/scatter seams get exercised under chaos whenever
    # >1 device exists)
    run_leg(make_trainer(build_mesh(n_devices=1)), reader, prefix,
            0, half)
    skipped = reader.bad_records

    # ---- simulated preemption: fresh trainer (on the FULL mesh —
    # a rank-join-style reshape when devices allow) resumes the newest
    # verified checkpoint; an injected reshard fault makes the loader
    # fall back to an older verified epoch instead of dying
    eps = find_checkpoints(prefix, require_states=True)
    assert eps, "no complete checkpoint to resume from (spec %r)" % spec
    trainer2 = make_trainer(build_mesh(tp=1))
    resumed = trainer2.load_latest_checkpoint(prefix,
                                              load_optimizer_states=True)
    read_hits_carry = 0
    scatter_hits = R.fault_stats().get("reshard.scatter",
                                       {}).get("hits", 0)
    if resumed is None and scatter_hits:
        # every retained epoch burned one injected reshard fault; a
        # real operator would clear the (transient) fault and retry —
        # the checkpoints themselves must still be loadable.
        # configure_faults resets per-site counters, so carry the
        # recordio hit count for the end-of-run accounting below
        print("all epochs consumed by injected reshard faults; "
              "retrying with the seam disarmed")
        read_hits_carry = R.fault_stats().get("recordio.read",
                                              {}).get("hits", 0)
        R.configure_faults(";".join(
            p for p in spec.split(";") if not p.startswith("reshard.")))
        resumed = trainer2.load_latest_checkpoint(
            prefix, load_optimizer_states=True)
    if scatter_hits:
        # an injected scatter fault legitimately burns the newest epoch
        assert resumed in eps, (resumed, eps)
    else:
        assert resumed == eps[-1], (resumed, eps)
    # ---- exactly-once data plane under chaos: leg 2's reader resumes
    # the byte offset leg 1 stopped at via the io.resume seam, and a
    # ledger cursor is remapped across a world-size change via the
    # io.remap seam.  The chaos contract for both: an injected fault
    # surfaces as MXNetError BEFORE any mutation, so ONE retry from the
    # very same state must succeed.
    from mxnet_tpu import io_resume as ior
    data_state = reader.state()
    reader2 = rec.MXRecordIO(path, "r", skip_bad_records=quota)
    for attempt in (1, 2):
        try:
            ior.restore_iterator(reader2, data_state)
            break
        except MXNetError as e:
            assert attempt == 1, "io.resume retry did not land: %s" % e
            print("io.resume fault (%s); retrying from the same state"
                  % e)
    assert reader2.state()["byte"] == data_state["byte"], \
        "reader resumed at the wrong byte offset"
    ledger_state = {"v": 1, "kind": "ledger", "epoch": 0, "cursor": 3,
                    "seed": opts.seed, "rank": 0, "world": 2,
                    "num_samples": 16 * opts.batch}
    for attempt in (1, 2):
        try:
            remapped = ior.remap_state(ledger_state, 0, 1)
            break
        except MXNetError as e:
            assert attempt == 1, "io.remap retry did not land: %s" % e
            print("io.remap fault (%s); retrying the same remap" % e)
    assert remapped["cursor"] == 6 and remapped["world"] == 1, remapped
    losses = run_leg(trainer2, reader2, prefix, resumed, opts.steps)
    skipped += reader2.bad_records

    stats = R.fault_stats()
    print("fault stats: %s; skipped records: %d" % (stats, skipped))
    read_stats = stats.get("recordio.read")
    if read_stats is not None:
        assert read_stats["hits"] + read_hits_carry == skipped, \
            (read_stats, read_hits_carry, skipped)
        assert skipped > 0, "corruption rate sampled but nothing skipped"
    assert losses[-1] < opts.loss_threshold, \
        "no recovery to loss threshold: %s" % losses
    R.clear_faults()
    print("chaos run OK: resumed from epoch %d, final loss %.3f, "
          "%d records skipped" % (resumed, losses[-1], skipped))
    return 0


if __name__ == "__main__":
    sys.exit(main())
