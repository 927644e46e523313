"""Worker for the elastic rank leave/join acceptance test (ISSUE 10).

The ROADMAP item 5 scenario: a fleet loses a rank mid-run, the
launch.py ``--elastic`` watchdog resumes the job at the SURVIVING size
(the resumed worker reshards the checkpoint onto the smaller mesh and
records ``rank_leave``), and a later relaunch at the full size re-adds
the rank (``rank_join``) — the loss trajectory continuing from the
checkpoint through every leg.

Phases (ELASTIC_PHASE):

* ``kill``   — rank KILL_RANK SIGKILLs itself at step KILL_STEP of the
  FIRST attempt (MXNET_TPU_RESTART_COUNT=0); restarted attempts resume
  from the latest CRC-verified checkpoint at whatever world size the
  elastic supervisor chose.
* ``rejoin`` — no kill; every rank resumes from the checkpoint the
  smaller fleet left and trains to the loss threshold.
"""
import json
import os
import signal
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.parallel import ShardedTrainer, build_mesh, multihost  # noqa: E402

GBATCH = 64
STEPS = 14
CKPT_EVERY = 3
_PROTOS = np.random.RandomState(42).rand(10, 64).astype("f")


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _batch(step):
    rng = np.random.RandomState(500 + step)
    y = rng.randint(0, 10, GBATCH)
    x = (_PROTOS[y] + rng.randn(GBATCH, 64) * 0.2).astype("f")
    return x, y.astype("f")


def main():
    phase = os.environ.get("ELASTIC_PHASE", "kill")
    prefix = os.environ["ELASTIC_CKPT"]
    kill_rank = int(os.environ.get("KILL_RANK", "1"))
    kill_step = int(os.environ.get("KILL_STEP", "7"))
    restart_count = int(os.environ.get("MXNET_TPU_RESTART_COUNT", "0"))

    multihost.ensure_initialized()
    import jax

    rank, nproc = jax.process_index(), jax.process_count()
    mesh = build_mesh(devices=jax.devices(),
                      axis_names=("data", "model"), tp=1)
    trainer = ShardedTrainer(
        _mlp(), mesh,
        data_shapes={"data": (GBATCH, 64)},
        label_shapes={"softmax_label": (GBATCH,)},
        learning_rate=0.15, momentum=0.9, seed=5)

    # resume from the newest FULLY-verified checkpoint whatever world
    # size saved it: the manifest mesh descriptor makes the load a
    # reshard when the fleet size changed (rank_join/rank_leave land in
    # this rank's JSONL stream and the run timeline)
    start = trainer.load_latest_checkpoint(
        prefix, load_optimizer_states=True) or 0

    may_kill = phase == "kill" and restart_count == 0

    def shard(a):
        per = GBATCH // nproc
        return a[rank * per:(rank + 1) * per]

    losses = []
    for step in range(start, STEPS):
        x, y = _batch(step)
        losses.append(float(trainer.step({"data": shard(x),
                                          "softmax_label": shard(y)})))
        done = step + 1
        if done % CKPT_EVERY == 0 and done < STEPS:
            trainer.save_checkpoint(prefix, done,
                                    save_optimizer_states=True)
        if may_kill and rank == kill_rank and done == kill_step:
            sys.stderr.write("worker %d: simulating rank leave "
                             "(SIGKILL self) at step %d\n" % (rank, done))
            sys.stderr.flush()
            os.kill(os.getpid(), signal.SIGKILL)

    assert losses[-1] < 0.35, losses
    multihost.process_barrier("elastic_done")
    print("elastic worker %d/%d OK phase=%s start=%d losses=%s"
          % (rank, nproc, phase, start, json.dumps(losses)))


if __name__ == "__main__":
    main()
