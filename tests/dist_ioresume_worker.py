"""Worker for the exactly-once data-plane CI gate (ISSUE 16).

Each rank consumes its :class:`~mxnet_tpu.io_resume.ShardedLedgerIter`
shard of ONE epoch through a tiny local trainer (no collectives — the
exactly-once property under test is a data-plane property, and this
repo's CPU jax cannot run cross-process collectives), logging every
consumed sample id per step to ``IORESUME_IDLOG.rank<r>``.  Rank 0
checkpoints every ``IORESUME_CKPT_EVERY`` steps; the manifest carries
the ledger's durable ``data_state``.

Phases (``IORESUME_PHASE``):

* ``train``  — EVERY rank SIGKILLs itself at ``IORESUME_KILL_STEP``
  (a fleet death mid-epoch, after at least one checkpoint landed).
* ``resume`` — runs at world size 1: ``load_latest_checkpoint``
  stashes the manifest ``data_state``, ``restore_data_iter`` remaps
  the rank-0-of-W cursor to rank-0-of-1 (the ``io.remap`` path), and
  the survivor consumes the REST of the epoch, logging ids the same
  way.  The CI stage (``tools/ci_check.py io_resume_check``) feeds
  both legs' logs to :class:`~mxnet_tpu.io_resume.SampleAccountant`:
  the union must be exactly one epoch, no drop, no double.
"""
import json
import os
import signal
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import io_resume as ior  # noqa: E402
from mxnet_tpu.parallel import ShardedTrainer, build_mesh  # noqa: E402
from mxnet_tpu.telemetry import ioview  # noqa: E402

N_SAMPLES = 96
BATCH = 8
SEED = 5
_PROTOS = np.random.RandomState(42).rand(10, 16).astype("f")


def _dataset():
    """Deterministic per-sample data: sample id i belongs to cluster
    i % 10 — every process derives the identical arrays."""
    labels = (np.arange(N_SAMPLES) % 10).astype("f")
    noise = np.random.RandomState(7).randn(N_SAMPLES, 16) * 0.2
    data = (_PROTOS[labels.astype(int)] + noise).astype("f")
    return data, labels


def _mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _await_fleet(idlog, world, steps, timeout=120.0):
    """A fleet death is every rank dying at the same step, and
    ``launch.py`` kills the survivors of the first death.  The ranks
    share no collective here (each trains on a mesh of its own), so
    nothing orders them: hold this rank's kill until every rank's id
    log holds ``steps`` lines (rank 0's last checkpoint is then on
    disk: it saves before it logs the next step).  Otherwise a rank
    that saves nothing reaches its kill before rank 0 has saved once
    ("leg A left no complete checkpoint"), or a late rank loses steps
    that the checkpoint's cursor counts as consumed.  A fleet that
    never assembles is an error of its own, not a death."""
    deadline = time.monotonic() + timeout
    for r in range(world):
        while True:
            try:
                with open("%s.rank%d" % (idlog, r)) as f:
                    if sum(1 for _ in f) >= steps:
                        break
            except FileNotFoundError:
                pass
            if time.monotonic() > deadline:
                sys.stderr.write("ioresume worker: FLEET NEVER ASSEMBLED: "
                                 "rank %d logged fewer than %d steps in "
                                 "%.0f s\n" % (r, steps, timeout))
                sys.stderr.flush()
                sys.exit(3)
            time.sleep(0.02)


def main():
    phase = os.environ.get("IORESUME_PHASE", "train")
    prefix = os.environ["IORESUME_CKPT"]
    idlog = os.environ["IORESUME_IDLOG"]
    kill_step = int(os.environ.get("IORESUME_KILL_STEP", "5"))
    ckpt_every = int(os.environ.get("IORESUME_CKPT_EVERY", "2"))
    rank = int(os.environ.get("MXNET_TPU_PROCESS_ID", "0"))
    world = int(os.environ.get("MXNET_TPU_NUM_PROCESSES", "1"))

    data, labels = _dataset()
    it = ior.ShardedLedgerIter(data, labels, batch_size=BATCH,
                               seed=SEED, rank=rank, world=world)
    # the tracked iterator's state() rides every checkpoint manifest
    ioview.track(it)

    trainer = ShardedTrainer(
        _mlp(), build_mesh(n_devices=1),
        data_shapes={"data": (BATCH, 16)},
        label_shapes={"softmax_label": (BATCH,)},
        learning_rate=0.1, momentum=0.9, seed=3)

    start = 0
    if phase == "resume":
        resumed = trainer.load_latest_checkpoint(
            prefix, load_optimizer_states=True)
        assert resumed is not None, "no checkpoint to resume from"
        entry = trainer.restore_data_iter(it)
        assert entry is not None, \
            "checkpoint manifest carried no data_state entry"
        start = int(resumed)
        sys.stderr.write("worker %d/%d resumed epoch %d at cursor %d\n"
                         % (rank, world, resumed, it.state()["cursor"]))

    log = open("%s.rank%d" % (idlog, rank), "a")
    step = start
    while True:
        try:
            batch = next(it)
        except StopIteration:
            break
        # log BEFORE the train step: a kill between consume and train
        # must count the batch as consumed (the checkpoint cursor the
        # accounting trusts was captured before these samples)
        log.write(json.dumps({"step": step, "phase": phase,
                              "ids": batch.index.tolist()}) + "\n")
        log.flush()
        trainer.step({"data": batch.data[0].asnumpy(),
                      "softmax_label": batch.label[0].asnumpy()})
        step += 1
        if phase == "train" and rank == 0 and step % ckpt_every == 0:
            trainer.save_checkpoint(prefix, step,
                                    save_optimizer_states=True)
        if phase == "train" and step == kill_step:
            sys.stderr.write("worker %d/%d: simulating fleet death "
                             "(SIGKILL self) at step %d\n"
                             % (rank, world, step))
            sys.stderr.flush()
            log.close()
            _await_fleet(idlog, world, kill_step)
            os.kill(os.getpid(), signal.SIGKILL)
    log.close()
    print("ioresume worker %d/%d OK phase=%s start=%d end=%d"
          % (rank, world, phase, start, step))


if __name__ == "__main__":
    main()
